"""Traffic of kind `genera_jobs`: ``greedy_jobs``' whole `compare` jobs on a
collection whose genera are loose components of the cutoff graph
(generators/planted_genera.py), held to what the streaming primary's linkage
has to book and to what it has to find.

The window, the routes, the counters, the greedy rule's comparison and the
medians are ``greedy_jobs.run``'s, called, not copied: a program whose
streaming route books no `primary_linkage` (the parent of the PR that brought
the cell) fails every job there by `expect.counters`, and the run ends with
no sound job. What this kind adds:

- two faults that fail a job, from its own record's `primary_linkage`
  (`expect.primary_linkage`): more `uncertified_merges` than the cell allows
  (0: the partition is then certified equal to full-matrix average linkage),
  and fewer `loose_components` than the cell means to run;
- comparisons, exact, of the last sound job against ``reference_genera``: the
  record's `loose_components`, `rows_loose`, `edges_retained`,
  `edges_under_cutoff`, `edges_between_clusters` and `merges` against the
  reference's counts from the exact pairs, and the Mdb's pairs that join two
  different primary clusters against the reference's (printed: most of this
  Mdb is such pairs).
"""

from __future__ import annotations

import statistics
import time

import numpy as np

from benchmark import check, greedy_jobs
from benchmark import reference_genera as rgen
from benchmark import reference_greedy as rg

LINKAGE_COUNTS = ("loose_components", "rows_loose", "edges_retained", "edges_under_cutoff",
                  "edges_between_clusters", "merges")


def linkage_faults(rec: dict, expect: dict) -> list[str]:
    """Why the record's `primary_linkage` says this was not the cell's job."""
    did, want = rec.get("primary_linkage") or {}, expect.get("primary_linkage") or {}
    faults = []
    most = want.get("uncertified_merges_at_most")
    if most is not None and not did.get("uncertified_merges", most + 1) <= most:
        faults.append(f"primary_linkage.uncertified_merges = {did.get('uncertified_merges')!r}, the cell "
                      f"allows {most}: the partition is not certified equal to full-matrix average linkage")
    least = want.get("loose_components_at_least")
    if least is not None and not did.get("loose_components", -1) >= least:
        faults.append(f"primary_linkage.loose_components = {did.get('loose_components')!r}, the cell "
                      f"means at least {least}")
    return faults


def between_clusters(answers: dict, n: int, keep: float) -> int:
    """Distinct Mdb pairs at or under the retention bound `keep` whose genomes
    the job's Cdb puts in different primary clusters (a table may hold pairs
    beyond the bound; the reference counts none of them)."""
    m = answers["mash"]
    there = m["dist"] <= keep
    lo, hi = np.minimum(m["i"], m["j"])[there], np.maximum(m["i"], m["j"])[there]
    pairs = np.unique(lo * n + hi)
    return int((answers["primary"][pairs // n] != answers["primary"][pairs % n]).sum())


def check_linkage(record: dict, answers: dict | None, expected: dict, keep: float) -> list[dict]:
    """The record's counts, and the Mdb's pairs between clusters (where the
    caller read the tables), against ``reference_genera.linkage_counts``."""
    did, want = record.get("primary_linkage") or {}, expected["linkage"]
    out = [check.comparison(
        f"primary_linkage.{name} off the reference's {want[name]}",
        abs(int(did.get(name, -1)) - want[name]), 0) for name in LINKAGE_COUNTS]
    if answers is not None:
        got = between_clusters(answers, len(expected["primary"]), keep)
        out.append(check.comparison(
            f"Mdb pairs that join two primary clusters ({got} of {want['edges_retained']} retained) "
            f"off the reference's {want['edges_between_clusters']}",
            abs(got - want["edges_between_clusters"]), 0))
    return out


def run(ctx: dict) -> dict:
    """``greedy_jobs.run`` (whose `ctx` this takes), then the linkage's faults
    and comparisons."""
    cfg, expect = ctx["config"], ctx["traffic"].get("expect", {})
    result = greedy_jobs.run(ctx)
    jobs, failed, sound = result["run"]["jobs"], result["failed"], []
    for job in jobs:
        faults = linkage_faults(job["record"], expect)
        if ctx["rehearse"]:  # a toy table has fewer chains than the cell means: said, not failed
            for f in [f for f in faults if "loose_components" in f]:
                print(f"rehearsal: expected of the full table, not held here (not failed): {f}", flush=True)
            faults = [f for f in faults if "loose_components" not in f]
        if faults:
            failed += 1
            print(f"job failed: {'; '.join(faults)}", flush=True)
        else:
            sound.append(job)
    if not sound:
        raise SystemExit("no job of the window ran soundly: nothing to report")
    data = result["run"]["planted"]
    t_ref = time.monotonic()
    # the greedy scan is `greedy_jobs.run`'s own; the linkage's counts need the exact pairs alone
    p = cfg["params"]
    primary, mash = rg.primary(data.bottom, int(p["sketch_size"]), int(p["kmer_size"]), 1.0 - p["P_ani"])
    expected = {"primary": primary, "linkage": rgen.linkage_counts(len(data.names), mash, primary, p)}
    answers = greedy_jobs.read_answers(sound[-1]["workdir"], data.names)
    comparisons = check_linkage(sound[-1]["record"], answers, expected, p["retention_dist"])
    print(f"linkage: {sound[-1]['record'].get('primary_linkage')}", flush=True)
    print(f"reference of the linkage: {time.monotonic() - t_ref:.1f}s", flush=True)
    correct = check.report(comparisons) and result["correct"]
    return {**result, "correct": correct, "failed": failed,
            "end_to_end": {**result["end_to_end"],
                           "job_wall_s": statistics.median(j["wall_s"] for j in sound)},
            "run": {**result["run"], "jobs": sound}}
