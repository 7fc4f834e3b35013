"""Share of the genomes that lie in a loose component of the primary's cutoff
graph: a component of two or more that is no clique, which average linkage has
to cut (a genus with no gap at the primary cut) where a clique is one cluster
whatever the merge order. `rows_loose` / `genomes` of the record's
`primary_linkage`, summed over the window's jobs. A program whose streaming
route books no `primary_linkage`, or a dense route's (which counts
`rows_linked`), gives None."""


def read(run: dict):
    loose = genomes = 0
    for job in run.get("jobs", []):
        did = job["record"].get("primary_linkage") or {}
        if "rows_loose" in did:
            loose += did["rows_loose"]
            genomes += did["genomes"]
    return 100.0 * loose / genomes if genomes else None
