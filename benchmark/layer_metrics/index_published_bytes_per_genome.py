"""Bytes a job published over the genomes it admitted: the record's
`index.bytes_published` (every file the store wrote: the batch's shards, their
parts, the whole state) over `index.admitted`. A genome's own sketches are
about 170 KB; what lies over that is state rewritten whole. Median over the
window's jobs; None where the record has no `index`."""
import statistics


def read(run: dict):
    per = []
    for job in run.get("jobs", []):
        did = job["record"].get("index") or {}
        if did.get("admitted") and "bytes_published" in did:
            per.append(did["bytes_published"] / did["admitted"])
    return statistics.median(per) if per else None
