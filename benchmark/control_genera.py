#!/usr/bin/env python3
"""The control of "How `correct` is decided" for a cell of kind
`genera_jobs`: ``control_greedy.py``'s idea with that kind's comparisons. The
reference is put in the program's place and computed in the precision below
(distances, ANIs and coverages rounded to bfloat16 before the linkage, the
counts and the greedy rule see them), at the cell's own size; every number the
cell compares is printed beside its limit, and the control has to come out as
not correct, by every value limit.

    python3 benchmark/control_genera.py --workload gtdb_genera_6k.compare_greedy_loose --seeds 1,2,3 [--rehearse]

Not part of a benchmark run; NumPy and SciPy only, so it runs without a chip.
"""

from __future__ import annotations

import argparse
import os
import sys

BENCH_DIR = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(BENCH_DIR))

from benchmark import cells, check, genera_jobs, greedy_jobs  # noqa: E402
from benchmark import reference_genera as rgen  # noqa: E402


def control(cfg: dict, mix: dict, data) -> list[dict]:
    """Every comparison of the cell, the bfloat16 reference against the
    float64 one (a record's counts are the lower precision's own)."""
    p = cfg["params"]
    want = rgen.compare_genera(data.bottom, data.scaled, data.n_kmers, p)
    low = rgen.compare_genera(data.bottom, data.scaled, data.n_kmers, p, lower_precision=True)
    out = greedy_jobs.check_greedy(low, data, p, mix["compare"], mix["limits"], expected=want)
    return out + genera_jobs.check_linkage({"primary_linkage": low["linkage"]}, low, want, p["retention_dist"])


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--rehearse", action="store_true")
    args = ap.parse_args(argv)
    loaded = cells.load_cell(args.workload)
    cfg, mix = loaded["config"], loaded["traffic"]
    if args.rehearse:
        cfg = {**cfg, "data": {**cfg["data"], **cfg.get("rehearse", {})}}
    failed_all = True
    for seed in (int(s) for s in args.seeds.split(",")):
        out = control(cfg, mix, loaded["generator"].generate(cfg["data"], seed))
        print(f"control, {args.workload}, seed {seed}:", flush=True)
        ok = check.report(out)
        values_failed = all(not c["ok"] for c in out if c["limit"] > 0)
        print(f"control, {args.workload}, seed {seed}: correct = {ok}, every value limit failed = "
              f"{values_failed}", flush=True)
        failed_all = failed_all and not ok and values_failed
    return 0 if failed_all else 1  # the control has to come out as not correct


if __name__ == "__main__":
    sys.exit(main())
