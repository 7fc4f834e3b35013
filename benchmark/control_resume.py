#!/usr/bin/env python3
"""The control of the guarantees a cell of kind `resume_jobs` compares: the
program is put in the place of one that KEEPS NOTHING. The cell's own job
(the mix's argv, stopped where the mix stops it, rerun on the same work
directory) runs with both stores emptied between the attempts
(``resume_jobs.empty_stores``, after the kind has listed what they held), so
every attempt starts from nothing, as a program without a shard store and
without secondary checkpoints would. The kind's four comparisons are printed
beside their limits: the two limit-0 counts (tiles dispatched inside published
stripes, clusters computed among the published ones) have to FAIL, and the
tables have to stay byte for byte the undisturbed job's, which shows that the
comparison can see a program that keeps nothing and that its answer does not
depend on the stores.

    python3 benchmark/control_resume.py --workload gtdb_release_preempt_6k.compare_greedy_resume --seeds 1,2,3 [--rehearse]

Not part of a benchmark run. It runs the program, on whatever backend JAX
finds (the counts are the same on any; at full size a CPU needs about an hour
a seed for the jobs' device work, a chip a minute).
"""

from __future__ import annotations

import argparse
import os
import shutil
import sys
import tempfile

BENCH_DIR = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(BENCH_DIR))

from benchmark import batch_jobs, cells, check, resume_jobs  # noqa: E402


def control(loaded: dict, seed: int, rehearse: bool, work_dir: str) -> tuple[bool, list[dict]]:
    """(did the control come out as it has to, its comparisons) for one seed."""
    cfg, mix = loaded["config"], loaded["traffic"]
    if rehearse:
        cfg = {**cfg, "data": {**cfg["data"], **cfg.get("rehearse", {})}}
    stops = (mix.get("rehearse", {}) if rehearse else {}).get("stops", mix["stops"])
    prepared = loaded["generator"].prepare(cfg, seed, work_dir)
    plain = batch_jobs.run_job(mix["argv"], prepared["workdir"], os.path.join(work_dir, "undisturbed"))
    if plain["error"]:
        raise SystemExit(f"the undisturbed job failed: {plain['error']}")
    batch_jobs._read_record(plain)
    undisturbed = {"digests": resume_jobs.table_digests(plain["workdir"]), "record": plain["record"]}
    job = resume_jobs.run_job(mix["argv"], stops, prepared["workdir"], os.path.join(work_dir, "emptied"),
                              between=resume_jobs.empty_stores)
    if job["error"]:
        raise SystemExit(f"the emptied job failed: {job['error']}")
    resume_jobs.read_records(job)
    out = resume_jobs.guarantee_comparisons([job], undisturbed, mix["limits"])
    tables, tiles, clusters, _twice = out
    return tables["ok"] and not tiles["ok"] and not clusters["ok"], out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--rehearse", action="store_true")
    args = ap.parse_args(argv)
    loaded = cells.load_cell(args.workload)
    as_it_has_to = True
    for seed in (int(s) for s in args.seeds.split(",")):
        os.makedirs(os.path.join(BENCH_DIR, ".work"), exist_ok=True)  # run.py's own, git-ignored
        work_dir = tempfile.mkdtemp(prefix="control_resume-", dir=os.path.join(BENCH_DIR, ".work"))
        try:
            held, out = control(loaded, seed, args.rehearse, work_dir)
        finally:
            shutil.rmtree(work_dir, ignore_errors=True)
        print(f"control, {args.workload}, seed {seed}:", flush=True)
        check.report(out)
        print(f"control, {args.workload}, seed {seed}: both limit-0 counts failed and no table "
              f"moved = {held}", flush=True)
        as_it_has_to = as_it_has_to and held
    return 0 if as_it_has_to else 1


if __name__ == "__main__":
    sys.exit(main())
