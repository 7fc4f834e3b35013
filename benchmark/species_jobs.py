"""Traffic of kind `species_jobs`: whole jobs of the program's CLI, back to
back, on a collection whose primary clusters hold several planted secondary
groups (generators/planted_species.py).

The window is ``batch_jobs``'s: a job is one call of
``drep_tpu.controller.main(argv)`` on a fresh hard-linked copy of the planted
workdir, after one untimed warm-up job. What differs is the comparison that
decides `correct`. ``check.check_batch`` holds the primary and the secondary
partition to one array of planted labels and samples clusters for the pair
values; here the planted data carries two label arrays (``primary_labels``,
``labels``), the reference is ``reference_species`` (a whole cluster at
once), and every pair of every cluster is compared: no sample.
"""

from __future__ import annotations

import os
import shutil
import statistics
import time

import numpy as np

from benchmark import check, tracered
from benchmark import reference as ref
from benchmark import reference_species as refs
from benchmark.batch_jobs import _read_record, record_faults, run_job

# ---- the tables of a job, as matrices over the planted names ------------------


def read_pair_tables(wd: str, names: list[str], want: list[str]) -> dict:
    """Cdb as ``check.read_tables`` gives it, and the pair tables as [n, n]
    matrices in the order of `names`, NaN where the table has no row:
    "mdb" [genome1, genome2] = dist; "ani" and "cov" [querry, reference]."""
    import pandas as pd

    out = check.read_tables(wd, [])
    n = len(names)
    index = pd.Series(np.arange(n), index=names)
    tables = os.path.join(wd, "data_tables")
    if "mdb" in want:
        mdb = pd.read_csv(os.path.join(tables, "Mdb.csv"), usecols=["genome1", "genome2", "dist"])
        out["mdb"] = np.full((n, n), np.nan)
        out["mdb"][index[mdb["genome1"]].to_numpy(), index[mdb["genome2"]].to_numpy()] = \
            mdb["dist"].to_numpy(np.float64)
    if "ndb" in want:
        ndb = pd.read_csv(os.path.join(tables, "Ndb.csv"),
                          usecols=["querry", "reference", "ani", "alignment_coverage"])
        q, r = index[ndb["querry"]].to_numpy(), index[ndb["reference"]].to_numpy()
        out["ani"] = np.full((n, n), np.nan)
        out["cov"] = np.full((n, n), np.nan)
        out["ani"][q, r] = ndb["ani"].to_numpy(np.float64)
        out["cov"][q, r] = ndb["alignment_coverage"].to_numpy(np.float64)
    return out


# ---- the comparison ------------------------------------------------------------


def _worst(got: np.ndarray, want: np.ndarray) -> float:
    """Largest |got - want|; a value the table lacks (NaN) is infinitely wrong."""
    if got.size == 0:
        return 0.0
    return float(np.max(np.where(np.isnan(got), np.inf, np.abs(got - want))))


def check_species(tables: dict, data, params: dict, want: list[str], limits: dict,
                  lower_precision: bool = False) -> list[dict]:
    """Compare one job's tables (``read_pair_tables``) with the reference
    computed from the planted sketches. `lower_precision` puts the control in
    the program's place: `tables` is then ignored and the reference's own
    bfloat16 values are compared with its float64 ones."""
    names = data.names
    n = len(names)
    k, s = int(params["kmer_size"]), int(params["sketch_size"])
    cut = 1.0 - params["P_ani"]
    dist = refs.mash_matrix(data.bottom, s, k)
    ref_primary = refs.primary_labels(dist, cut)
    if lower_precision:
        got_dist = refs.mash_matrix(data.bottom, s, k, lower_precision=True)
        got_primary = ref.partition_of(refs.primary_labels(got_dist, cut))
    else:
        got_dist = tables.get("mdb")
        got_primary = ref.partition_of([tables["primary"][g] for g in names])
    out = []
    if "primary" in want:
        out.append(check.comparison("genomes in a primary cluster the reference does not have",
                                    ref.partition_mismatch(got_primary, ref.partition_of(ref_primary)), 0))
        out.append(check.comparison(
            "genomes whose reference primary cluster is not the planted one",
            ref.partition_mismatch(ref.partition_of(ref_primary),
                                   ref.partition_of(data.primary_labels)), 0))
    if "mdb" in want:
        upper = np.triu(np.ones((n, n), bool), 1)
        # a pair is in the table if either direction is, at a distance under 1
        there = np.nan_to_num(got_dist, nan=1.0) < 1.0
        either = (there | there.T) & upper
        must = (dist <= params["retention_dist"]) & upper
        known = (dist < 1.0) & upper
        out.append(check.comparison("Mdb pairs missing, or present and not in the reference",
                                    int(np.sum(must & ~either) + np.sum(either & ~known)), 0))
        both = there & (known | known.T)
        out.append(check.comparison(
            f"largest Mash distance error over {int(np.sum(either & known))} pairs",
            _worst(got_dist[both], dist[both]), limits["mash_dist"]))
    if "secondary" not in want and "ndb" not in want:
        return out
    wrong = unplanted = pairs = 0
    ani_err = cov_err = 0.0
    for label in np.unique(ref_primary):
        group = np.flatnonzero(ref_primary == label)
        if len(group) < 2:
            continue
        scaled = [data.scaled[g] for g in group]
        ani, cov, labels = refs.secondary_of_cluster(scaled, k, params["S_ani"], params["cov_thresh"])
        if lower_precision:
            got_ani, got_cov, got_labels = refs.secondary_of_cluster(
                scaled, k, params["S_ani"], params["cov_thresh"], lower_precision=True)
        else:
            got_labels = [tables["secondary"][names[g]] for g in group]
        wrong += ref.partition_mismatch(ref.partition_of(got_labels), ref.partition_of(labels))
        unplanted += ref.partition_mismatch(ref.partition_of(labels),
                                            ref.partition_of(data.labels[group]))
        if "ndb" in want:
            off = ~np.eye(len(group), dtype=bool)
            if not lower_precision:
                got_ani = tables["ani"][np.ix_(group, group)]
                got_cov = tables["cov"][np.ix_(group, group)]
            ani_err = max(ani_err, _worst(got_ani[off], ani[off]))
            cov_err = max(cov_err, _worst(got_cov[off], cov[off]))
            pairs += int(off.sum())
    if "secondary" in want:
        out.append(check.comparison(
            "genomes in a secondary cluster the reference does not have", wrong, 0))
        out.append(check.comparison(
            "genomes whose reference secondary cluster is not the planted group", unplanted, 0))
    if "ndb" in want:
        out.append(check.comparison(f"largest ANI error over {pairs} ordered pairs", ani_err,
                                    limits["ani"]))
        out.append(check.comparison(f"largest coverage error over {pairs} ordered pairs", cov_err,
                                    limits["coverage"]))
    return out


# ---- a job's own record ---------------------------------------------------------


def job_faults(rec: dict, device: dict, expect: dict, resolved: str | None) -> list[str]:
    """``batch_jobs.record_faults`` and the paths the cell means NOT to serve
    (`expect.secondary_paths_absent`)."""
    faults = record_faults(rec, device, expect, resolved)
    served = [p for p in expect.get("secondary_paths_absent", [])
              if p in (rec.get("secondary_paths") or {})]
    if served:
        faults.append(f"secondary served by {served}, which the cell means to leave")
    return faults


def device_path_only(rec: dict, device: dict, resolved: str | None, faults: list[str]) -> list[str]:
    """Those of `faults` that only say which path the dispatch took. Off a
    TPU it takes the CPU's paths, so a rehearsal prints them and does not
    fail them; a wrong device, a retry or a fall-back still fails."""
    hard = [f for f in record_faults(rec, device, {}, resolved)
            if not f.startswith("secondary served by")]
    return [f for f in faults if f not in hard]


# ---- the runner -----------------------------------------------------------------


def run(ctx: dict) -> dict:
    """Set-up, window and check, as ``batch_jobs.run`` (whose `ctx` this
    takes), with the comparison above."""
    cfg, mix = ctx["config"], ctx["traffic"]
    prepared = ctx["generator"].prepare(cfg, ctx["seed"], ctx["work_dir"])
    pristine, data = prepared["workdir"], prepared["data"]
    print(f"setup: planted {len(data.names)} sketch sets in {len(set(data.labels.tolist()))} "
          f"groups at {ctx['setup_clock']():.1f}s", flush=True)
    warm = run_job(mix["argv"], pristine, os.path.join(ctx["work_dir"], "warm"))
    if warm["error"]:
        raise SystemExit(f"the warm-up job failed: {warm['error']}")
    shutil.rmtree(warm["workdir"], ignore_errors=True)
    print(f"setup: warm-up job took {warm['wall_s']:.1f}s", flush=True)
    setup_s = ctx["setup_clock"]()

    # ---- the window ----
    ctx["compiles"].clear()
    jobs: list[dict] = []
    trace = None
    t0 = time.monotonic()
    while True:
        tracing = ctx["trace"] and not jobs
        if tracing:
            trace_dir = os.path.join(ctx["work_dir"], "trace")
            ctx["start_trace"](trace_dir)
        job = run_job(mix["argv"], pristine, os.path.join(ctx["work_dir"], f"job{len(jobs)}"))
        if tracing:
            ctx["stop_trace"]()
            xplane = tracered.find_xplane(trace_dir)
            if xplane is None:
                raise SystemExit("the profiler wrote no trace")
            events = tracered.load_xplane(xplane, ctx["rehearse"])
            trace = {**tracered.reduce_trace(events, job["wall_s"]), "events": events}
        jobs.append(job)
        # another job only if it fits; the first always runs to its end
        if time.monotonic() - t0 + job["wall_s"] > ctx["seconds"]:
            break
    window_s = time.monotonic() - t0
    compiles_in_window = len(ctx["compiles"])

    # ---- after the window: records, then the reference ----
    failed = 0
    not_held: list[str] = []
    for job in jobs:
        if job["error"] is None:
            _read_record(job)
            faults = job_faults(job["record"], ctx["device"], mix.get("expect", {}), job["resolved"])
            if ctx["rehearse"]:
                soft = device_path_only(job["record"], ctx["device"], job["resolved"], faults)
                not_held += [f for f in soft if f not in not_held]
                faults = [f for f in faults if f not in soft]
            job["error"] = "; ".join(faults) or None
        if job["error"]:
            failed += 1
            print(f"job failed: {job['error']}", flush=True)
    for f in not_held:
        print(f"rehearsal: expected of the device path, not held here (not failed): {f}", flush=True)
    sound = [j for j in jobs if not j["error"]]
    if not sound:
        raise SystemExit("no job of the window ran soundly: nothing to report")
    t_ref = time.monotonic()
    tables = read_pair_tables(sound[-1]["workdir"], data.names, mix["compare"])
    comparisons = check_species(tables, data, cfg["params"], mix["compare"], mix["limits"])
    print(f"reference: {time.monotonic() - t_ref:.1f}s after the window "
          f"({len(sound)} sound job(s) of {len(jobs)} in {window_s:.1f}s)", flush=True)
    last = check.cdb_digest(tables)
    comparisons.append(check.comparison(
        f"jobs of {len(sound)} whose Cdb differs from the last job's",
        sum(check.cdb_digest(check.read_tables(j["workdir"], [])) != last for j in sound[:-1]), 0))
    correct = check.report(comparisons)
    return {
        "correct": correct, "attempted": len(jobs), "failed": failed,
        "end_to_end": {"setup_s": setup_s,
                       "job_wall_s": statistics.median(j["wall_s"] for j in sound)},
        "run": {"jobs": sound, "trace": trace, "compiles_in_window": compiles_in_window,
                "window_s": window_s, "config": cfg, "traffic": mix, "device": ctx["device"],
                "peaks": ctx["peaks"], "planted": data},
    }
