"""Traffic of kind `greedy_jobs`: whole jobs of the program's CLI, back to
back, under `--greedy_secondary_clustering`, on a collection whose primary
clusters hold several planted secondary groups
(generators/planted_release.py).

The window is ``batch_jobs.run`` itself, called, not copied: a job is one
call of ``drep_tpu.controller.main(argv)`` on a fresh hard-linked copy of the
planted workdir, after one untimed warm-up job; the records' first reading,
the medians and the count of jobs whose Cdb differs from the last job's are
its own. What differs is the comparison that decides `correct`, made here
after it returns. Under the greedy rule the Ndb holds
genome-against-representative rows only, and WHICH rows is part of the
answer: the reference is ``reference_greedy`` (the rule as it reads, a pair
at a time), the Ndb's pair set is compared exactly (a pair the rule does not
consume, or a missing one, is wrong), and every pair's ANI and both
coverages, every Mdb distance and both partitions are compared: no sample.
``batch_jobs.run``'s own comparison (average linkage over all pairs of a
sample of clusters) is handed nothing to compare.

A job's own record has to name the route the cell means (`expect.
secondary_path`), no route outside `expect.secondary_paths_only`, and hold
every counter of `expect.counters`; else the job counts as failed. Off a TPU
the engine takes its gather route, so a rehearsal prints what it did not
hold of the routes and does not fail it.
"""

from __future__ import annotations

import os
import statistics
import time
from types import SimpleNamespace

import numpy as np

from benchmark import batch_jobs, check
from benchmark import reference as ref
from benchmark import reference_greedy as rg
from benchmark.species_jobs import _worst

# ---- a job's tables, over genome numbers ---------------------------------------------


def read_answers(wd: str, names: list[str]) -> dict:
    """What a job wrote, in ``reference_greedy.compare_greedy``'s form:
    "primary" and "secondary" [n] labels, "mash" {"i", "j", "dist"} with one
    entry a Mdb row of two different genomes, "rows" {"q", "r", "ani",
    "cov_q", "cov_r"} with one entry an Ndb row."""
    import pandas as pd

    tables = os.path.join(wd, "data_tables")
    index = pd.Series(np.arange(len(names)), index=names)
    cdb = pd.read_csv(os.path.join(tables, "Cdb.csv")).set_index("genome").loc[names]
    mdb = pd.read_csv(os.path.join(tables, "Mdb.csv"), usecols=["genome1", "genome2", "dist"])
    mdb = mdb[mdb["genome1"] != mdb["genome2"]]
    ndb = pd.read_csv(os.path.join(tables, "Ndb.csv"),
                      usecols=["querry", "reference", "ani", "alignment_coverage", "ref_coverage"])
    return {
        "primary": pd.factorize(cdb["primary_cluster"])[0],
        "secondary": pd.factorize(cdb["secondary_cluster"])[0],
        "mash": {"i": index[mdb["genome1"]].to_numpy(), "j": index[mdb["genome2"]].to_numpy(),
                 "dist": mdb["dist"].to_numpy(np.float64)},
        "rows": {"q": index[ndb["querry"]].to_numpy(), "r": index[ndb["reference"]].to_numpy(),
                 "ani": ndb["ani"].to_numpy(np.float64),
                 "cov_q": ndb["alignment_coverage"].to_numpy(np.float64),
                 "cov_r": ndb["ref_coverage"].to_numpy(np.float64)},
    }


# ---- the comparison ----------------------------------------------------------------------


def _matched(got_keys: np.ndarray, want_keys: np.ndarray):
    """(positions in got, positions in want) of the keys both hold, and how
    many keys one side holds and the other does not (a key twice counts as
    one more)."""
    _, at_got, at_want = np.intersect1d(got_keys, want_keys, return_indices=True)
    return at_got, at_want, len(got_keys) + len(want_keys) - 2 * len(at_got)


def check_greedy(got: dict | None, data, params: dict, want: list[str], limits: dict,
                 lower_precision: bool = False, expected: dict | None = None) -> list[dict]:
    """Compare one job's answers (``read_answers``) with the reference computed
    from the planted sketches (`expected`, where the caller holds it already).
    `lower_precision` puts the control in the program's place: `got` is then
    ignored and the reference's own bfloat16 answers are compared with its
    float64 ones."""
    n = len(data.names)
    if expected is None:
        expected = rg.compare_greedy(data.bottom, data.scaled, data.n_kmers, params)
    if lower_precision:
        got = rg.compare_greedy(data.bottom, data.scaled, data.n_kmers, params, lower_precision=True)
    out = []
    if "primary" in want:
        out.append(check.comparison(
            "genomes in a primary cluster the reference does not have",
            ref.partition_mismatch(ref.partition_of(got["primary"]),
                                   ref.partition_of(expected["primary"])), 0))
        out.append(check.comparison(
            "genomes whose reference primary cluster is not the planted one",
            ref.partition_mismatch(ref.partition_of(expected["primary"]),
                                   ref.partition_of(data.primary_labels)), 0))
    if "mdb" in want:
        # the table may hold a pair in either direction or both, and unrelated
        # pairs at distance 1 (a small collection's table is dense)
        m, e = got["mash"], expected["mash"]
        there = m["dist"] < 1.0
        lo, hi = np.minimum(m["i"], m["j"])[there], np.maximum(m["i"], m["j"])[there]
        keys = lo * n + hi
        by_key = np.argsort(e["i"] * n + e["j"])
        known, known_dist = (e["i"] * n + e["j"])[by_key], e["dist"][by_key]
        must = known[known_dist <= params["retention_dist"]]
        out.append(check.comparison(
            "Mdb pairs missing, or present and not in the reference",
            len(np.setdiff1d(must, keys)) + len(np.setdiff1d(keys, known)), 0))
        both = np.isin(keys, known)
        out.append(check.comparison(
            f"largest Mash distance error over {int(both.sum())} Mdb rows",
            _worst(m["dist"][there][both], known_dist[np.searchsorted(known, keys[both])]),
            limits["mash_dist"]))
    if "secondary" in want:
        out.append(check.comparison(
            "genomes in a secondary cluster the reference does not have",
            ref.partition_mismatch(ref.partition_of(got["secondary"]),
                                   ref.partition_of(expected["secondary"])), 0))
        out.append(check.comparison(
            "genomes whose reference secondary cluster is not the planted group",
            ref.partition_mismatch(ref.partition_of(expected["secondary"]),
                                   ref.partition_of(data.labels)), 0))
    if "ndb" in want:
        g, e = got["rows"], expected["rows"]
        at_got, at_want, unmatched = _matched(g["q"] * n + g["r"], e["q"] * n + e["r"])
        out.append(check.comparison(
            f"Ndb pairs the greedy scan does not consume, or missing (it consumes {len(e['q'])})",
            unmatched, 0))
        out.append(check.comparison(
            f"largest ANI error over {len(at_got)} Ndb rows",
            _worst(g["ani"][at_got], e["ani"][at_want]), limits["ani"]))
        out.append(check.comparison(
            f"largest coverage error over {len(at_got)} Ndb rows, both directions",
            max(_worst(g["cov_q"][at_got], e["cov_q"][at_want]),
                _worst(g["cov_r"][at_got], e["cov_r"][at_want])), limits["coverage"]))
    return out


# ---- a job's own record -------------------------------------------------------------------


def route_faults(rec: dict, expect: dict) -> list[str]:
    """What the record's `secondary_paths` does not hold of the cell's
    routes: the one it means (`secondary_path`), and none but those of
    `secondary_paths_only`."""
    booked = rec.get("secondary_paths") or {}
    faults = []
    want = expect.get("secondary_path")
    if want and want not in booked:
        faults.append(f"secondary path {want!r} did not serve (paths: {booked})")
    only = expect.get("secondary_paths_only")
    other = sorted(p for p in booked if only is not None and p not in only)
    if other:
        faults.append(f"secondary served by {other}, outside the cell's routes {only}")
    return faults


def counter_faults(rec: dict, expect: dict) -> list[str]:
    return [f"the record holds no {name}" for name in expect.get("counters", [])
            if not rec.get(name)]


def counters_unknown(expect: dict) -> list[str]:
    """Counters of `expect.counters` that the program's record can never
    hold: its writer (utils/profiling.py) does not know their names. Every
    job of such a program would count as failed, and a run says so before
    it spends a window on it."""
    import inspect

    from drep_tpu.utils import profiling

    source = inspect.getsource(profiling)
    return [name for name in expect.get("counters", []) if f'"{name}"' not in source]


def greedy_digest(rec: dict) -> dict:
    """The record's greedy counters in one line: what every seed has to give
    alike (PERF.md section 4 says which entries the seed may move)."""
    calls = rec.get("secondary_greedy_calls") or []
    keys = ("rows", "blocks", "reps", "rep_rows_shipped", "rep_rows_real", "chunks", "widths",
            "device_calls", "compared_pairs", "id_slots")
    return {"clusters": len(calls), **{k: [c[k] for c in calls] for k in keys},
            "extent": [c["extent"] for c in calls], "hashes": sum(c["hashes"] for c in calls),
            "batched": rec.get("secondary_greedy_batched")}


# ---- the runner ---------------------------------------------------------------------------


def _nothing_to_compare(data) -> SimpleNamespace:
    """The planted names with empty sketches: ``batch_jobs.run``'s own
    comparison then finds no pair and, with nothing listed under `compare`,
    compares nothing. The greedy rule's comparison is made here."""
    return SimpleNamespace(names=data.names, labels=data.labels,
                           bottom=[np.zeros(0, np.uint64)] * len(data.names), scaled=[])


def run(ctx: dict) -> dict:
    """``batch_jobs.run`` for set-up, window and medians (whose `ctx` this
    takes), then the record's routes and counters and the comparison above."""
    cfg, mix = ctx["config"], ctx["traffic"]
    expect = mix.get("expect", {})
    unknown = counters_unknown(expect)
    if unknown:
        raise SystemExit(f"this program's record has no {unknown}: every job of the cell would count "
                         f"as failed, nothing to measure")
    planted: dict = {}

    class Planting:  # batch_jobs.run asks the generator once: keep what it planted
        @staticmethod
        def prepare(config, seed, out_dir):
            planted.update(ctx["generator"].prepare(config, seed, out_dir))
            return {"workdir": planted["workdir"], "data": _nothing_to_compare(planted["data"])}

    # in a rehearsal batch_jobs.run would fail the route hard: it is judged below, softly
    its_expect = {k: v for k, v in expect.items() if not (ctx["rehearse"] and k == "secondary_path")}
    window = batch_jobs.run({**ctx, "generator": Planting,
                             "traffic": {**mix, "compare": [], "expect": its_expect}})
    data = planted["data"]
    jobs, failed = window["run"]["jobs"], window["failed"]
    not_held: list[str] = []
    for job in jobs:
        faults = counter_faults(job["record"], expect)
        routes = route_faults(job["record"], expect)
        if ctx["rehearse"]:
            not_held += [f for f in routes if f not in not_held]
        else:
            faults += routes
        job["error"] = "; ".join(faults) or None
        if job["error"]:
            failed += 1
            print(f"job failed: {job['error']}", flush=True)
    for f in not_held:
        print(f"rehearsal: expected of the device path, not held here (not failed): {f}", flush=True)
    sound = [j for j in jobs if not j["error"]]
    if not sound:
        raise SystemExit("no job of the window ran soundly: nothing to report")
    print(f"greedy: {greedy_digest(sound[-1]['record'])}", flush=True)
    t_ref = time.monotonic()
    answers = read_answers(sound[-1]["workdir"], data.names)
    comparisons = check_greedy(answers, data, cfg["params"], mix["compare"], mix["limits"])
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
