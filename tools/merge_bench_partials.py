"""Union the per-attempt bench partials into one artifact.

A bench run that dies mid-way leaves a per-attempt partial, so a round's
hardware evidence can accumulate as BENCH_r<N>_attempt<A>_partial.json
files whose stage coverage differs. This tool merges them into
BENCH_r<N>_merged.json:
for every stage key, the best successful record across attempts, stamped
with which attempt produced it and that attempt's measured link health
(the `link` stage: dispatch latency + h2d/d2h bandwidth) so a reader can
tell a healthy-link number from a degraded-link one without consulting
the logs.

Merge rules, deterministic:
- ``*_error`` entries never shadow a successful record; they are kept
  only when NO attempt succeeded at that stage (honest failure evidence).
- for stages reporting ``pairs_per_sec_per_chip`` (or nested variants of
  it), the attempt with the highest rate wins — best-of across sessions
  is the same variance control bench.py's _best_of applies within one.
- otherwise the latest attempt wins (later attempts carry link records
  and the newest code state).

The one-line driver contract (bench.py printing a single JSON line) is
untouched — this writes a separate, richer artifact.
"""

from __future__ import annotations

import argparse
import glob
import json
import os
import re
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from drep_tpu.utils.durableio import atomic_write_bytes  # noqa: E402


def _rate(rec) -> float | None:
    """Comparable throughput for a stage record, if it has one."""
    if not isinstance(rec, dict):
        return None
    if "pairs_per_sec_per_chip" in rec:
        return float(rec["pairs_per_sec_per_chip"])
    nested = [
        float(v["pairs_per_sec_per_chip"])
        for v in rec.values()
        if isinstance(v, dict) and "pairs_per_sec_per_chip" in v
    ]
    return max(nested) if nested else None


def load_attempts(pattern: str, with_paths: bool = False):
    """(attempt_number, record) pairs for every readable partial matching
    `pattern` — or (attempt_number, record, path) triples with
    `with_paths=True`, so the CLI can REPORT exactly which files it
    consumed (the r04 strays sat in the repo root for two rounds because
    nothing ever said what had already been folded in)."""
    out = []
    for path in glob.glob(pattern):
        m = re.search(r"attempt(\d+)", os.path.basename(path))
        if not m:
            continue
        try:
            with open(path) as f:
                rec = json.loads(f.read().strip() or "{}")
        except Exception:
            continue  # unreadable partial: nothing to merge from it
        if rec.get("stages"):
            out.append((int(m.group(1)), rec, path))
    # key on the attempt number ONLY: an attempt can leave two files (its
    # emitted partial plus a preserved killed-partial), and bare tuple
    # sorting would fall through to comparing the dicts — a TypeError
    out.sort(key=lambda t: t[0])
    if with_paths:
        return out  # ascending attempt order; later overwrites earlier
    return [(n, rec) for n, rec, _ in out]


def prefer_new(old, new) -> bool:
    """Should `new` replace `old` for the same stage key? The ONE record-
    preference rule (complete beats pending, cold beats warm-started,
    then best-of on rate) — shared by merge() below and bench.py's
    durable per-stage records, so the two merge paths cannot drift."""
    old_warm = isinstance(old, dict) and old.get("warm_start_shards", 0) > 0
    new_warm = isinstance(new, dict) and new.get("warm_start_shards", 0) > 0
    old_pend = isinstance(old, dict) and bool(
        old.get("resume_pending") or old.get("measurement_pending")
    )
    new_pend = isinstance(new, dict) and bool(
        new.get("resume_pending") or new.get("measurement_pending")
    )
    if old_pend != new_pend:
        # completeness beats rate (ADVICE r4): an attempt that wedged
        # mid-stage (pending marker still set) must not displace a
        # complete record on a marginally higher fresh-leg rate — that
        # drops the resume evidence and re-queues the stage, wasting a
        # recovery window
        return not new_pend
    if old_warm != new_warm:
        # a warm-started scale run's wall-clock rode a previous attempt's
        # shards — its (inflated) rate never beats a cold measurement,
        # and a cold one always replaces it
        return not new_warm
    old_rate, new_rate = _rate(old), _rate(new)
    if old_rate is not None and new_rate is not None and new_rate < old_rate:
        return False  # keep the faster measurement (best-of)
    return True


def merge(attempts: list[tuple[int, dict]]) -> dict:
    stages: dict[str, dict] = {}
    provenance: dict[str, dict] = {}
    errors: dict[str, dict] = {}
    for n, rec in attempts:
        link = rec.get("stages", {}).get("link")
        for key, val in rec.get("stages", {}).items():
            if key.endswith("_error") or (isinstance(val, dict) and "error" in val):
                errors.setdefault(key, {"attempt": n, "record": val})
                errors[key] = {"attempt": n, "record": val}  # keep latest failure
                continue
            if key in stages and not prefer_new(stages[key], val):
                continue
            stages[key] = val
            provenance[key] = {"attempt": n, "link": link}
    # a failure entry survives only while no attempt succeeded there
    for key, info in errors.items():
        base = key[: -len("_error")] if key.endswith("_error") else key
        if not any(s == base or s.startswith(base) for s in stages):
            stages[key] = info["record"]
            provenance[key] = {"attempt": info["attempt"], "link": None}

    versions = {rec.get("drep_tpu_version") for _, rec in attempts}
    primary = stages.get("primary", {})
    value = primary.get("pairs_per_sec_per_chip")
    return {
        "metric": "genome-pairs/sec/chip",
        "value": value,
        "unit": "pairs/s",
        "vs_baseline": primary.get("vs_baseline"),
        "drep_tpu_version": sorted(v for v in versions if v),
        "merged_from": [f"attempt{n}" for n, _ in attempts],
        "stages": stages,
        "stage_provenance": provenance,
    }


def newest_round(cwd: str = ".") -> int | None:
    """The highest round number among BENCH_r<N>*_partial.json files
    present — the default round, so the tool follows the rounds instead
    of pinning one (the old hardcoded r05 default silently merged a
    STALE round's partials once r06 started)."""
    rounds = [
        int(m.group(1))
        for f in glob.glob(os.path.join(cwd, "BENCH_r*_partial.json"))
        if (m := re.search(r"BENCH_r(\d+)", os.path.basename(f)))
    ]
    return max(rounds) if rounds else None


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument(
        "--pattern", default=None,
        help="glob of per-attempt partials (attempt number parsed from "
             "name). Default: the NEWEST round's partials present "
             "(BENCH_r<max>_attempt*_partial.json)",
    )
    ap.add_argument(
        "--out", default=None,
        help="merged artifact path (default BENCH_r<max>_merged.json for "
             "the derived round)",
    )
    args = ap.parse_args()
    if args.pattern is None:
        n = newest_round()
        if n is None:
            raise SystemExit(
                "no BENCH_r*_partial.json files present — pass --pattern "
                "explicitly to merge from elsewhere"
            )
        args.pattern = f"BENCH_r{n:02d}_attempt*_partial.json"
        if args.out is None:
            args.out = f"BENCH_r{n:02d}_merged.json"
    if args.out is None:
        m = re.search(r"BENCH_r(\d+)", args.pattern)
        args.out = f"BENCH_r{int(m.group(1)):02d}_merged.json" if m else "BENCH_merged.json"
    triples = load_attempts(args.pattern, with_paths=True)
    if not triples:
        raise SystemExit(f"no partials match {args.pattern}")
    merged = merge([(n, rec) for n, rec, _ in triples])
    # provenance: WHICH files fed this artifact — once folded in, the
    # source partials are safe to delete (this note replaces them)
    merged["merged_from_files"] = [os.path.basename(p) for _, _, p in triples]
    # atomic publish (PR 5 funnel): a crash mid-merge must not replace the
    # durable artifact the source partials were deleted in favor of with
    # a torn half-document
    atomic_write_bytes(args.out, (json.dumps(merged, indent=1) + "\n").encode())
    covered = [k for k in merged["stages"] if not k.endswith("_error")]
    failed = [k for k in merged["stages"] if k.endswith("_error")]
    print(
        f"merged {len(triples)} attempts -> {args.out}: "
        f"{len(covered)} stage records ({', '.join(sorted(covered))})"
        + (f"; unresolved failures: {', '.join(sorted(failed))}" if failed else "")
    )
    print(
        "consumed: "
        + ", ".join(os.path.basename(p) for _, _, p in triples)
        + " (recorded in merged_from_files; the source partials may now be deleted)"
    )


if __name__ == "__main__":
    main()
