"""Share of the index's genomes, after the update, that sit in a primary
cluster the job recomputed: the record's `index.members_recomputed` over
`n_old + admitted`, in percent. The batch decides the least of it (every
cluster it touches); what lies over that is recomputation an update could
spare. Median over the window's jobs; None where the record has no `index`."""
import statistics


def read(run: dict):
    shares = []
    for job in run.get("jobs", []):
        did = job["record"].get("index") or {}
        n = did.get("n_old", 0) + did.get("admitted", 0)
        if n and "members_recomputed" in did:
            shares.append(100.0 * did["members_recomputed"] / n)
    return statistics.median(shares) if shares else None
