"""The host kernel's own rate: megabases sketched per second of a worker's
time. From the record's `ingest` counter: the bases of the genomes the job
sketched over the seconds their `sketch_one` calls took, summed over the pool's
workers (parse, k-mers, hash, sort; not the pool's start, not the pickling
back). Over the window's jobs. No device kernel, so no roofline share: this
rate is the kernel's measure."""


def read(run: dict):
    bases = busy = 0.0
    for job in run.get("jobs", []):
        ingest = job["record"].get("ingest") or {}
        bases += ingest.get("bases", 0)
        busy += ingest.get("busy_seconds", 0.0)
    return bases / 1e6 / busy if busy > 0 else None
