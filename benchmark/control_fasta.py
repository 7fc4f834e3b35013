#!/usr/bin/env python3
"""The control of "How `correct` is decided" for a cell of kind `fasta_jobs`:
``control.py``'s idea with that kind's own comparison. The reference is put in
the program's place and computed in the precision below (Mash distances and
ANIs rounded to bfloat16 before the clusters, the centralities and the scores
are derived from them), at the cell's own size; every number the cell compares
is printed beside its limit, and the control has to fail at least one. What
the control does not move (the files' numbers, the sketches: integers) is not
printed.

    python3 benchmark/control_fasta.py --workload mag_fasta_384.dereplicate --seeds 1,2,3 [--rehearse]

Not part of a benchmark run; NumPy and SciPy only, so it runs without a chip.
"""

from __future__ import annotations

import argparse
import os
import shutil
import sys
import tempfile

BENCH_DIR = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(BENCH_DIR))

from benchmark import cells, check, fasta_jobs  # noqa: E402
from benchmark import reference_fasta as rf  # noqa: E402


def control(cfg: dict, mix: dict, generator, seed: int) -> list[dict]:
    """The cell's comparisons with the bfloat16 reference as `got`."""
    out_dir = tempfile.mkdtemp(prefix="control_fasta_")  # a gigabyte at full size
    try:
        data = generator.prepare(cfg, seed, out_dir)["data"]
        sketches, quality, want = fasta_jobs.reference_answers(data, cfg["params"])
        low = rf.dereplicate(data.names, sketches, quality, cfg["params"], lower_precision=True)
        low["winners"] = set(low["winners"].values())
        return fasta_jobs.compare_answers(low, want, cfg["params"], mix["limits"])
    finally:
        shutil.rmtree(out_dir, ignore_errors=True)


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
        out = control(cfg, mix, loaded["generator"], seed)
        print(f"control, {args.workload}, seed {seed}:", flush=True)
        ok = check.report(out)
        print(f"control, {args.workload}, seed {seed}: correct = {ok}", flush=True)
        failed_all = failed_all and not ok
    return 0 if failed_all else 1  # the control has to come out as not correct


if __name__ == "__main__":
    sys.exit(main())
