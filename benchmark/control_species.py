#!/usr/bin/env python3
"""The control of "How `correct` is decided" for a cell of kind
`species_jobs`: ``control.py``'s idea with that kind's own comparison. The
reference is put in the program's place and computed in the precision below
(distances and ANIs rounded to bfloat16), at the cell's own size; every
number the cell compares is printed beside its limit, and the control has to
fail at least one.

    python3 benchmark/control_species.py --workload ecoli_1k.secondary_deep --seeds 1,2,3 [--rehearse]

Not part of a benchmark run; NumPy and SciPy only, so it runs without a chip.
"""

from __future__ import annotations

import argparse
import os
import sys

BENCH_DIR = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(BENCH_DIR))

from benchmark import cells, check, species_jobs  # noqa: E402


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
        data = loaded["generator"].generate(cfg["data"], seed)
        out = species_jobs.check_species({}, data, cfg["params"], mix["compare"], mix["limits"],
                                         lower_precision=True)
        print(f"control, {args.workload}, seed {seed}:", flush=True)
        ok = check.report(out)
        print(f"control, {args.workload}, seed {seed}: correct = {ok}", flush=True)
        failed_all = failed_all and not ok
    return 0 if failed_all else 1  # the control has to come out as not correct


if __name__ == "__main__":
    sys.exit(main())
