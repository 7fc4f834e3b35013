"""Traffic of kind `batch_jobs`: whole jobs of the program's CLI, back to back.

A job is one call of ``drep_tpu.controller.main(argv)`` — the CLI's own
function — in this process, on a fresh hard-linked copy of the planted
workdir, from the call to its return with ``Cdb.csv`` on disk. Interpreter and
backend start-up are paid once, in set-up, as they would be a vanishing share
of a deployment-sized job. One untimed job at the cell's own shapes warms
every program up first.
"""

from __future__ import annotations

import json
import os
import shutil
import statistics
import time

from benchmark import check, tracered

# fault counters that mean "a dispatch did not run where it was meant to", and
# secondary paths that are the CPU or a fall-back (chip_smoke.py's lists)
HIDING_COUNTERS = ("retries", "watchdog_trips", "quarantined_devices", "cpu_fallback_tiles",
                   "ring_step_failures", "ring_blocks_recovered")
HIDING_PATHS = ("cpu_tiles", "pallas_range_fallback")


def record_faults(rec: dict, device: dict, expect: dict, resolved: str | None) -> list[str]:
    """Why a job's own record says it did not run where, or how, the cell
    means it to. Empty for a sound job."""
    bad = []
    for key, mine in (("platform", "platform"), ("device_kind", "kind"), ("n_devices", "count")):
        if rec.get(key) != device[mine]:
            bad.append(f"record says {key}={rec.get(key)!r}, the run has {device[mine]!r}")
    hidden = {k: v for k, v in (rec.get("fault_tolerance") or {}).items()
              if k in HIDING_COUNTERS and v}
    if hidden:
        bad.append(f"work did not run where it was meant to: {hidden}")
    paths = [p for p in (rec.get("secondary_paths") or {}) if p in HIDING_PATHS]
    if paths:
        bad.append(f"secondary served by {paths}")
    want = expect.get("primary_estimator_resolved")
    if want and resolved != want:
        bad.append(f"primary estimator resolved to {resolved!r}, the cell means {want!r}")
    want = expect.get("secondary_path")
    if want and want not in (rec.get("secondary_paths") or {}):
        bad.append(f"secondary path {want!r} did not serve (paths: {rec.get('secondary_paths')})")
    return bad


def run_job(argv_template: list[str], pristine: str, job_dir: str) -> dict:
    """One job. Returns {"wall_s", "workdir", "error"}; the record and the
    tables are read by the caller, outside the timed span."""
    from drep_tpu import controller

    shutil.rmtree(job_dir, ignore_errors=True)
    shutil.copytree(pristine, job_dir, copy_function=os.link)
    argv = [a.replace("{workdir}", job_dir) for a in argv_template]
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
    if error is None and not os.path.exists(os.path.join(job_dir, "data_tables", "Cdb.csv")):
        error = "no Cdb.csv"
    return {"wall_s": wall, "workdir": job_dir, "error": error}


def _read_record(job: dict) -> None:
    from drep_tpu.workdir import WorkDirectory

    with open(os.path.join(job["workdir"], "log", "perf_counters.json")) as f:
        job["record"] = json.load(f)
    job["resolved"] = (WorkDirectory(job["workdir"]).get_arguments("cluster") or {}).get(
        "primary_estimator_resolved")


def run(ctx: dict) -> dict:
    """Set-up, window and check of a batch cell. `ctx` comes from run.py:
    config, traffic, generator, seed, seconds, trace, work_dir, device,
    setup_clock (seconds since process start), start_trace / stop_trace and
    compiles (the list of programs built so far, cleared at the window's start)."""
    cfg, mix = ctx["config"], ctx["traffic"]
    prepared = ctx["generator"].prepare(cfg, ctx["seed"], ctx["work_dir"])
    pristine = prepared["workdir"]
    print(f"setup: planted {len(prepared['data'].names)} sketch sets at "
          f"{ctx['setup_clock']():.1f}s", flush=True)
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
        job_dir = os.path.join(ctx["work_dir"], f"job{len(jobs)}")
        tracing = ctx["trace"] and not jobs
        if tracing:
            trace_dir = os.path.join(ctx["work_dir"], "trace")
            ctx["start_trace"](trace_dir)
        job = run_job(mix["argv"], pristine, job_dir)
        if tracing:
            ctx["stop_trace"]()
            xplane = tracered.find_xplane(trace_dir)
            if xplane is None:
                raise SystemExit("the profiler wrote no trace")
            events = tracered.load_xplane(xplane, ctx["rehearse"])
            trace = {**tracered.reduce_trace(events, job["wall_s"]), "events": events}
        jobs.append(job)
        elapsed = time.monotonic() - t0
        # another job only if it fits; the first always runs to its end
        if elapsed + job["wall_s"] > ctx["seconds"]:
            break
    window_s = time.monotonic() - t0
    compiles_in_window = len(ctx["compiles"])

    # ---- after the window: records, then the reference ----
    failed = 0
    for job in jobs:
        if job["error"] is None:
            _read_record(job)
            job["error"] = "; ".join(record_faults(
                job["record"], ctx["device"], mix.get("expect", {}), job["resolved"])) or None
        if job["error"]:
            failed += 1
            print(f"job failed: {job['error']}", flush=True)
    sound = [j for j in jobs if not j["error"]]
    if not sound:
        raise SystemExit("no job of the window ran soundly: nothing to report")
    tables = check.read_tables(sound[-1]["workdir"], mix["compare"])
    t_ref = time.monotonic()
    comparisons = check.check_batch(tables, prepared["data"], cfg["params"], mix["compare"],
                                    mix["limits"], ctx["seed"])
    print(f"reference: {time.monotonic() - t_ref:.1f}s after the window", flush=True)
    last = check.cdb_digest(tables)
    comparisons.append(check.comparison(
        f"jobs of {len(sound)} whose Cdb differs from the last job's",
        sum(check.cdb_digest(check.read_tables(j["workdir"], [])) != last for j in sound[:-1]), 0))
    correct = check.report(comparisons)
    walls = [j["wall_s"] for j in sound]
    return {
        "correct": correct, "attempted": len(jobs), "failed": failed,
        "end_to_end": {"setup_s": setup_s,
                       "job_wall_s": statistics.median(walls)},
        "run": {"jobs": sound, "trace": trace, "compiles_in_window": compiles_in_window,
                "window_s": window_s, "config": cfg, "traffic": mix, "device": ctx["device"],
                "peaks": ctx["peaks"], "planted": prepared["data"]},
    }
