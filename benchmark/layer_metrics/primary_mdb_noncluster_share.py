"""Share of the pairs the streaming primary retains (at or under the retention
bound: the on-device compact, the shard store, `mdb_build` and `Mdb.csv` carry
them) whose two genomes end in different primary clusters: species of one
genus that do not cluster. `edges_between_clusters` / `edges_retained` of the
record's `primary_linkage`, summed over the window's jobs; None where the
record has no such counts."""


def read(run: dict):
    between = retained = 0
    for job in run.get("jobs", []):
        did = job["record"].get("primary_linkage") or {}
        if "edges_between_clusters" in did:
            between += did["edges_between_clusters"]
            retained += did["edges_retained"]
    return 100.0 * between / retained if retained else None
