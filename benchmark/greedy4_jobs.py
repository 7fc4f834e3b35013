"""Traffic of kind `greedy4_jobs`: whole jobs of the program's CLI through the
streaming primary and the greedy secondary, back to back, ONE process over
every chip of the host.

The window, the routes and counters a job's record has to hold, and the
comparison that decides `correct` are ``greedy_jobs.run`` itself, called, not
copied (which calls ``batch_jobs.run``): a job is one call of
``drep_tpu.controller.main(argv)`` on a fresh hard-linked copy of the planted
workdir, after one untimed warm-up job, and its answers are compared at the
cell's full size, no sample, with ``reference_greedy`` (both partitions, every
Mdb row, the Ndb's pair set and every pair's values, the Cdb of every job).
The reference decides one pair at a time, whatever the program's block size,
so the same file judges a block of 128 on one chip and of 512 on four.

What differs is made here, after it returns: a job's own record has to show
that the host's chips served it. A job whose streaming walk reached fewer
devices than the cell's `chips` (``stream4_jobs.slot_faults``), or whose
`secondary_greedy_calls` hold an engine cluster of ``MESH_MIN_ROWS`` genomes
or more with `mesh_devices` under `chips`, counts as failed: a four-chip run
whose secondary quietly fell to one chip must not read like one that used the
host's four. A program whose record has no `mesh_devices` at all cannot run
the cell, and a run says so before it spends a set-up on it.

Off a TPU the engine takes its gather route, which has no mesh. A REHEARSAL,
and only a rehearsal, therefore sets the program's existing knob
`DREP_TPU_GREEDY_MATMUL=1` in the job's environment, so that four virtual
devices rehearse the sharded calls; at full size on the chip nothing is set
and the route is the program's own choice.
"""

from __future__ import annotations

import os
import statistics

from benchmark import greedy_jobs, stream4_jobs

MESH_MIN_ROWS = 64  # the program's MESH_MIN_GENOMES: a smaller cluster is never put on a mesh


def mesh_faults(rec: dict, chips: int) -> list[str]:
    """Why a job's own record says its greedy engine was not served by the
    chips the cell asks for. Empty for a sound job."""
    faults = []
    for call in rec.get("secondary_greedy_calls") or []:
        if call["rows"] < MESH_MIN_ROWS:
            continue
        served = call.get("mesh_devices")
        if served is None:
            faults.append(f"the entry of a cluster of {call['rows']} holds no mesh_devices")
        elif served < chips:
            faults.append(f"a cluster of {call['rows']} was served by {served} device(s), "
                          f"the cell asks for {chips}")
    return faults


def mesh_digest(rec: dict) -> dict:
    """Who served the engine's clusters and what crossed the link, in one
    line: what every seed has to give alike."""
    calls = rec.get("secondary_greedy_calls") or []
    keys = ("mesh_devices", "block_rows", "blocks", "rep_tiles_replicated", "partial_tile_ships",
            "block_bytes", "rep_bytes")
    return {"devices_used": (rec.get("gauges") or {}).get("streaming_devices_used"),
            **{k: [c.get(k) for c in calls] for k in keys}}


def run(ctx: dict) -> dict:
    """``greedy_jobs.run`` (whose `ctx` this takes) for set-up, window, routes
    and comparison, then the record's reach on both stages."""
    chips = int(ctx["cell"]["chips"])
    unknown = greedy_jobs.counters_unknown({"counters": ["mesh_devices"]})
    if unknown:
        raise SystemExit(f"this program's record has no {unknown}: every job of the cell would "
                         f"count as failed, nothing to measure")
    if ctx["rehearse"]:
        os.environ["DREP_TPU_GREEDY_MATMUL"] = "1"
    try:
        out = greedy_jobs.run(ctx)
    finally:
        os.environ.pop("DREP_TPU_GREEDY_MATMUL", None)
    jobs, failed = out["run"]["jobs"], out["failed"]
    for job in jobs:
        faults = stream4_jobs.slot_faults(job["record"], chips) + mesh_faults(job["record"], chips)
        job["error"] = "; ".join(faults) or None
        if job["error"]:
            failed += 1
            print(f"job failed: {job['error']}", flush=True)
    sound = [j for j in jobs if not j["error"]]
    if not sound:
        raise SystemExit("no job of the window ran soundly: nothing to report")
    print(f"mesh: {mesh_digest(sound[-1]['record'])}", flush=True)
    return {**out, "failed": failed,
            "end_to_end": {**out["end_to_end"],
                           "job_wall_s": statistics.median(j["wall_s"] for j in sound)},
            "run": {**out["run"], "jobs": sound}}
