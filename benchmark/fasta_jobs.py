"""Traffic of kind `fasta_jobs`: whole `dereplicate` jobs of the program's
CLI that start at the FASTA files, back to back.

A job is one call of ``drep_tpu.controller.main(argv)`` in this process on a
fresh, empty work directory: no sketch cache, no shard store, nothing of an
earlier job. The planted files (generators/planted_fasta.py) are written once
in set-up and shared read-only, so every job reads them from the page cache:
the cell measures parse, hash and pool, not a disk. The job runs from the
call to its return with ``Cdb.csv`` and ``Wdb.csv`` on disk; one untimed job
at the same shapes warms every program up first. ``{genomes}`` in the
traffic file's argv stands for the planted paths, ``{genome_info}`` for the
planted quality table.

After the window the reference (``reference_fasta``: it parses and sketches
every file itself) is computed, outside `setup_s`, and the last job's
sketch cache and tables are held to it, each number beside its limit; every
other job's Cdb and Wdb must equal the last's.
"""

from __future__ import annotations

import hashlib
import json
import os
import shutil
import statistics
import time

import numpy as np

from benchmark import check, tracered
from benchmark import reference_fasta as rf
from benchmark.batch_jobs import record_faults
from benchmark.species_jobs import _worst, read_pair_tables

# ---- one job ---------------------------------------------------------------------


def job_argv(template: list[str], job_dir: str, data) -> list[str]:
    argv: list[str] = []
    for a in template:
        if a == "{genomes}":
            argv += data.paths
        else:
            argv.append(a.replace("{workdir}", job_dir).replace("{genome_info}", data.genome_info))
    return argv


def run_job(template: list[str], data, job_dir: str) -> dict:
    """One job on a work directory that does not exist yet. Returns {"wall_s",
    "workdir", "error"}; the record and the tables are read by the caller,
    outside the timed span."""
    from drep_tpu import controller

    shutil.rmtree(job_dir, ignore_errors=True)
    argv = job_argv(template, job_dir, data)
    error = None
    t0 = time.monotonic()
    try:
        controller.main(argv)
    except SystemExit as e:  # the CLI's way of refusing
        if e.code not in (0, None):
            error = f"exit code {e.code}"
    except Exception as e:  # noqa: BLE001 — a failed job is counted, not fatal
        error = f"{type(e).__name__}: {e}"
    wall = time.monotonic() - t0
    for table in ("Cdb", "Wdb"):
        if error is None and not os.path.exists(os.path.join(job_dir, "data_tables", table + ".csv")):
            error = f"no {table}.csv"
    # the winners' copies (half a gigabyte a job) are not compared: drop them now
    shutil.rmtree(os.path.join(job_dir, "dereplicated_genomes"), ignore_errors=True)
    return {"wall_s": wall, "workdir": job_dir, "error": error}


def job_faults(rec: dict, device: dict, expect: dict, resolved: str | None, kept: int) -> list[str]:
    """``batch_jobs.record_faults``, and what only a job from FASTA can get
    wrong: another ingest kernel than the cell means, or genomes that were
    not sketched in this job (`kept`: the genomes of its filtered Bdb)."""
    faults = record_faults(rec, device, expect, resolved)
    path = (rec.get("notes") or {}).get("ingest_path")
    want = expect.get("ingest_path")
    if want and path != want:
        faults.append(f"record says ingest_path={path!r}, the cell means {want!r}")
    sketched = (rec.get("ingest") or {}).get("genomes")
    if sketched is not None and sketched != kept:
        faults.append(f"the job sketched {sketched} of the {kept} genomes it kept: something was cached")
    return faults


def _read_record(job: dict) -> None:
    import pandas as pd

    from drep_tpu.workdir import WorkDirectory

    with open(os.path.join(job["workdir"], "log", "perf_counters.json")) as f:
        job["record"] = json.load(f)
    job["resolved"] = (WorkDirectory(job["workdir"]).get_arguments("cluster") or {}).get(
        "primary_estimator_resolved")
    job["kept"] = len(pd.read_csv(os.path.join(job["workdir"], "data_tables", "Bdb.csv")))


# ---- the program's answers, read back --------------------------------------------


def read_sketches(wd: str) -> dict[str, tuple[np.ndarray, np.ndarray]]:
    """{genome: (bottom, scaled)} of the job's sketch cache, as integers."""
    from drep_tpu.workdir import WorkDirectory

    arrs = WorkDirectory(wd).get_arrays("sketches")
    b, bo, s, so = arrs["bottom"], arrs["bottom_offsets"], arrs["scaled"], arrs["scaled_offsets"]
    return {str(g): (b[bo[i]:bo[i + 1]], s[so[i]:so[i + 1]]) for i, g in enumerate(arrs["names"])}


def read_answers(wd: str, kept: list[str]) -> dict:
    """What one job left, in ``reference_fasta.dereplicate``'s terms, with
    the pair matrices over `kept` (the reference's filtered names)."""
    import pandas as pd

    tables = os.path.join(wd, "data_tables")
    info = pd.read_csv(os.path.join(tables, "genomeInformation.csv"))
    out = {"stats": {g: (int(a), int(b), int(c)) for g, a, b, c in zip(
               info["genome"], info["length"], info["N50"], info["contigs"])},
           "kept": list(pd.read_csv(os.path.join(tables, "Bdb.csv"))["genome"])}
    if set(out["kept"]) != set(kept):
        return out  # another collection: its pair tables have no place in these matrices
    pair = read_pair_tables(wd, kept, ["mdb", "ndb"])
    sdb = pd.read_csv(os.path.join(tables, "Sdb.csv"))
    return {**out, "primary": pair["primary"], "secondary": pair["secondary"],
            "dist": pair["mdb"], "ani": pair["ani"], "cov": pair["cov"],
            "score": dict(zip(sdb["genome"], sdb["score"].astype(np.float64))),
            "winners": set(pd.read_csv(os.path.join(tables, "Wdb.csv"))["genome"])}


def tables_digest(wd: str) -> str:
    """The clusters and the winners of a job, whatever the clusters are called."""
    import pandas as pd

    tables = check.read_tables(wd, [])
    winners = sorted(pd.read_csv(os.path.join(wd, "data_tables", "Wdb.csv"))["genome"])
    return hashlib.sha1((check.cdb_digest(tables) + "\n".join(winners)).encode()).hexdigest()[:16]


# ---- the comparison ----------------------------------------------------------------


def compare_sketches(cache: dict, sketches: list[dict], names: list[str], kept: list[str],
                     stats: dict) -> list[dict]:
    """The exact comparisons of what ingest and the filter's read produced."""
    by_name = dict(zip(names, sketches))
    off = sum(stats.get(g) != (s["length"], s["N50"], s["contigs"]) for g, s in by_name.items())
    out = [check.comparison(f"genomes of {len(names)} whose length, N50 or contigs differ", off, 0)]
    for what, slot in (("bottom", 0), ("scaled", 1)):
        wrong = sum(g not in cache or not np.array_equal(cache[g][slot], by_name[g][what]) for g in kept)
        out.append(check.comparison(
            f"genomes of {len(kept)} whose {what} sketch is not the reference's, hash for hash "
            f"({sum(len(by_name[g][what]) for g in kept)} hashes), or cached and not kept",
            wrong + len(set(cache) - set(kept)), 0))
    return out


def compare_answers(got: dict, want: dict, params: dict, limits: dict) -> list[dict]:
    """`got` (a job's tables, or the control) against the reference's `want`."""
    kept = want["kept"]
    out = [check.comparison("genomes the filter kept and the reference dropped, or the other way round",
                            len(set(got["kept"]) ^ set(kept)), 0)]
    if not out[0]["ok"]:
        return out  # nothing below is defined over two different collections
    for level in ("primary", "secondary"):
        out.append(check.comparison(
            f"genomes in a {level} cluster the reference does not have",
            rf.partition_mismatch({g: got[level][g] for g in kept}, want[level]), 0))
    m = len(kept)
    upper = np.triu(np.ones((m, m), bool), 1)
    there = np.nan_to_num(got["dist"], nan=1.0) < 1.0  # a pair is in the table if either direction is
    either = (there | there.T) & upper
    known = (want["dist"] < 1.0) & upper
    must = (want["dist"] <= params["retention_dist"]) & upper
    out.append(check.comparison("Mdb pairs missing, or present and not in the reference",
                                int(np.sum(must & ~either) + np.sum(either & ~known)), 0))
    both = there & (known | known.T)
    out.append(check.comparison(f"largest Mash distance error over {int(np.sum(either & known))} pairs",
                                _worst(got["dist"][both], want["dist"][both]), limits["mash_dist"]))
    inside = ~np.isnan(want["ani"]) & ~np.eye(m, dtype=bool)
    for what, key, limit in (("ANI", "ani", "ani"), ("coverage", "cov", "coverage")):
        out.append(check.comparison(f"largest {what} error over {int(inside.sum())} ordered pairs",
                                    _worst(got[key][inside], want[key][inside]), limits[limit]))
    score_err = max(abs(got["score"].get(g, np.inf) - want["score"][g]) for g in kept)
    gap, ties = rf.score_gaps(want)
    out.append(check.comparison(
        f"largest score error over {m} genomes (the two best scores of a cluster are at least "
        f"{gap:.6g} apart, or tie exactly: {ties} clusters, the first name wins)",
        score_err, limits["score"]))
    out.append(check.comparison(
        f"winners of {len(want['winners'])} that are not the reference's, or missing",
        len(got["winners"] ^ set(want["winners"].values())), 0))
    return out


def reference_answers(data, params: dict) -> tuple[list[dict], dict, dict]:
    """(sketches of every planted file, the quality table, the answers)."""
    sketches = rf.sketch_files(data.paths, int(params["kmer_size"]), int(params["sketch_size"]),
                               int(params["scale"]))
    quality = read_quality(data.genome_info)
    return sketches, quality, rf.dereplicate(data.names, sketches, quality, params)


def read_quality(path: str) -> dict[str, dict]:
    with open(path) as f:
        header = f.readline().strip().split(",")
        rows = [dict(zip(header, line.strip().split(","))) for line in f if line.strip()]
    return {r["genome"]: {"completeness": float(r["completeness"]),
                          "contamination": float(r["contamination"])} for r in rows}


def planted_notes(data, want: dict, params: dict) -> list[str]:
    """What the planting meant, beside what the reference found: printed,
    never part of `correct` (the margins are margin_sweep_fasta.py's)."""
    where = {g: i for i, g in enumerate(data.names)}
    idx = [where[g] for g in want["kept"]]
    meant = ((data.completeness >= params["completeness"])
             & (data.contamination <= params["contamination"]) & ~data.short)
    notes = [f"planted to pass the filter {int(meant.sum())} of {len(data.names)}, the reference kept "
             f"{len(idx)}"]
    for level, labels in (("primary", data.primary_labels), ("secondary", data.labels)):
        off = rf.partition_mismatch(want[level], {g: int(labels[i]) for g, i in zip(want["kept"], idx)})
        notes.append(f"genomes whose reference {level} cluster is not the planted one: {off}")
    return notes


# ---- the runner --------------------------------------------------------------------


def run(ctx: dict) -> dict:
    """Set-up, window and check, as ``batch_jobs.run`` (whose `ctx` this takes)."""
    cfg, mix = ctx["config"], ctx["traffic"]
    data = ctx["generator"].prepare(cfg, ctx["seed"], ctx["work_dir"])["data"]
    print(f"setup: planted {len(data.names)} FASTA files, {int(data.bases.sum())} bases, at "
          f"{ctx['setup_clock']():.1f}s", flush=True)
    warm = run_job(mix["argv"], data, os.path.join(ctx["work_dir"], "warm"))
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
        job = run_job(mix["argv"], data, os.path.join(ctx["work_dir"], f"job{len(jobs)}"))
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
    for job in jobs:
        if job["error"] is None:
            _read_record(job)
            job["error"] = "; ".join(job_faults(job["record"], ctx["device"], mix.get("expect", {}),
                                                job["resolved"], job["kept"])) or None
        if job["error"]:
            failed += 1
            print(f"job failed: {job['error']}", flush=True)
    sound = [j for j in jobs if not j["error"]]
    if not sound:
        raise SystemExit("no job of the window ran soundly: nothing to report")
    t_ref = time.monotonic()
    sketches, _, want = reference_answers(data, cfg["params"])
    print(f"reference: sketched {len(sketches)} files at {time.monotonic() - t_ref:.1f}s", flush=True)
    last = sound[-1]["workdir"]
    got = read_answers(last, want["kept"])
    comparisons = compare_sketches(read_sketches(last), sketches, data.names, want["kept"], got["stats"])
    comparisons += compare_answers(got, want, cfg["params"], mix["limits"])
    print(f"reference: {time.monotonic() - t_ref:.1f}s after the window "
          f"({len(sound)} sound job(s) of {len(jobs)} in {window_s:.1f}s)", flush=True)
    for note in planted_notes(data, want, cfg["params"]):
        print(f"note: {note}", flush=True)
    digest = tables_digest(last)
    comparisons.append(check.comparison(
        f"jobs of {len(sound)} whose Cdb or Wdb differs from the last job's",
        sum(tables_digest(j["workdir"]) != digest for j in sound[:-1]), 0))
    correct = check.report(comparisons)
    return {
        "correct": correct, "attempted": len(jobs), "failed": failed,
        "end_to_end": {"setup_s": setup_s,
                       "job_wall_s": statistics.median(j["wall_s"] for j in sound)},
        "run": {"jobs": sound, "trace": trace, "compiles_in_window": compiles_in_window,
                "window_s": window_s, "config": cfg, "traffic": mix, "device": ctx["device"],
                "peaks": ctx["peaks"], "planted": data},
    }
