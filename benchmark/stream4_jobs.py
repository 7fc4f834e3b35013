"""Traffic of kind `stream4_jobs`: whole jobs of the program's CLI through the
streaming primary, back to back, ONE process over every chip of the host.

The window is ``batch_jobs.run`` itself, called, not copied (as
``greedy_jobs.py`` calls it): a job is one call of
``drep_tpu.controller.main(argv)`` on a fresh hard-linked copy of the planted
workdir, after one untimed warm-up job; the records' first reading (device,
hiding counters, the primary estimator), the medians and the count of jobs
whose Cdb differs from the last job's are its own. Two things differ and are
made here, after it returns.

A job's own record has to show that the walk reached every chip the cell asks
for: ``batch_jobs.record_faults`` cannot ask for a gauge, so a job whose
`streaming_devices_used` is under the cell's `chips` counts as failed here (a
four-chip run whose tiles all landed on one chip must not read like one that
used the host's four).

The comparison that decides `correct` is made at the cell's full size, no
sample: ``reference.py``'s `candidate_pairs` walks one offset at a time (767
passes over 2.5e7 entries at 24,576 genomes), so the reference is
``reference_greedy.primary``: the same values, bit for bit
(``reference_species.mash_matrix``), the genomes grouped by one sort and the
linkage run inside each connected group. Both partitions (the job's against
the reference's, the reference's against the planting), the Mdb's pair set
and every retained distance are compared by ``greedy_jobs.check_greedy``,
handed the reference's answers and the two names of `compare`.
"""

from __future__ import annotations

import os
import statistics
import time

import numpy as np

from benchmark import batch_jobs, check, greedy_jobs
from benchmark import reference_greedy as rg


def read_answers(wd: str, names: list[str]) -> dict:
    """What a job wrote, in ``greedy_jobs.check_greedy``'s form: "primary" [n]
    labels and "mash" {"i", "j", "dist"}, one entry a Mdb row of two
    different genomes. A `--SkipSecondary` job writes no Ndb."""
    import pandas as pd

    tables = os.path.join(wd, "data_tables")
    index = pd.Series(np.arange(len(names)), index=names)
    cdb = pd.read_csv(os.path.join(tables, "Cdb.csv")).set_index("genome").loc[names]
    mdb = pd.read_csv(os.path.join(tables, "Mdb.csv"), usecols=["genome1", "genome2", "dist"])
    mdb = mdb[mdb["genome1"] != mdb["genome2"]]
    return {"primary": pd.factorize(cdb["primary_cluster"])[0],
            "mash": {"i": index[mdb["genome1"]].to_numpy(), "j": index[mdb["genome2"]].to_numpy(),
                     "dist": mdb["dist"].to_numpy(np.float64)}}


def expected_answers(data, params: dict, lower_precision: bool = False) -> dict:
    """The reference's answers in the same form. `lower_precision` is the
    control: every distance under 1 rounded to bfloat16 before the linkage."""
    labels, mash = rg.primary(data.bottom, int(params["sketch_size"]), int(params["kmer_size"]),
                              1.0 - params["P_ani"], lower_precision)
    return {"primary": labels, "mash": mash}


def slot_faults(rec: dict, chips: int) -> list[str]:
    """Why a job's own record says the walk did not reach the chips the cell
    asks for. Empty for a sound job."""
    used = (rec.get("gauges") or {}).get("streaming_devices_used")
    if used is None:
        return ["the record holds no gauge streaming_devices_used"]
    if used < chips:
        return [f"the tiles reached {used:g} device(s), the cell asks for {chips}"]
    return []


def slots_digest(rec: dict) -> dict:
    """The record's dealing in one line: what every seed has to give alike."""
    slots = rec.get("primary_stream_slots") or {}
    return {"devices_used": (rec.get("gauges") or {}).get("streaming_devices_used"),
            **{k: slots.get(k) for k in ("slots", "stripes", "tiles", "turns")},
            "tiles_by_slot": [s["tiles"] for s in slots.get("by_slot", [])]}


def run(ctx: dict) -> dict:
    """``batch_jobs.run`` for set-up, window and medians (whose `ctx` this
    takes), then the record's reach and the comparison above."""
    cfg, mix = ctx["config"], ctx["traffic"]
    chips = int(ctx["cell"]["chips"])
    planted: dict = {}

    class Planting:  # batch_jobs.run asks the generator once: keep what it planted
        @staticmethod
        def prepare(config, seed, out_dir):
            planted.update(ctx["generator"].prepare(config, seed, out_dir))
            return {"workdir": planted["workdir"],
                    "data": greedy_jobs._nothing_to_compare(planted["data"])}

    window = batch_jobs.run({**ctx, "generator": Planting, "traffic": {**mix, "compare": []}})
    data = planted["data"]
    jobs, failed = window["run"]["jobs"], window["failed"]
    for job in jobs:
        job["error"] = "; ".join(slot_faults(job["record"], chips)) or None
        if job["error"]:
            failed += 1
            print(f"job failed: {job['error']}", flush=True)
    sound = [j for j in jobs if not j["error"]]
    if not sound:
        raise SystemExit("no job of the window ran soundly: nothing to report")
    print(f"stream: {slots_digest(sound[-1]['record'])}", flush=True)
    t_ref = time.monotonic()
    comparisons = greedy_jobs.check_greedy(
        read_answers(sound[-1]["workdir"], data.names), data, cfg["params"], mix["compare"],
        mix["limits"], expected=expected_answers(data, cfg["params"]))
    print(f"reference: {time.monotonic() - t_ref:.1f}s after the window "
          f"({len(sound)} sound job(s) of {window['attempted']} in "
          f"{window['run']['window_s']:.1f}s)", flush=True)
    correct = check.report(comparisons) and window["correct"]
    return {
        "correct": correct, "attempted": window["attempted"], "failed": failed,
        "end_to_end": {"setup_s": window["end_to_end"]["setup_s"],
                       "job_wall_s": statistics.median(j["wall_s"] for j in sound)},
        "run": {**window["run"], "jobs": sound, "traffic": mix, "planted": data},
    }
