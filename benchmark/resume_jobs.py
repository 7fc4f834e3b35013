"""Traffic of kind `resume_jobs`: whole jobs of the program's CLI, back to
back, each STOPPED WITH NOTICE where the mix's `stops` say and run again with
the same command on the same work directory until it ends.

A job is `len(stops) + 1` attempts, each one call of
``drep_tpu.controller.main(argv)``, the CLI's own function, in this process,
on ONE fresh hard-linked copy of the planted workdir. An attempt that is to
stop gets the stop through the program's own deterministic route: its fault
spec (`stops[k].fault`, ``utils/faults.py``'s `drain` mode: "the SIGTERM path
minus the signal") is set in `DREP_TPU_FAULTS` for that attempt alone, nothing
else is. The program leaves by ``SystemExit(0)`` with no `Cdb.csv` and its
record on disk; the kind reads that record, lists what the two stores hold
(`data/streaming_primary/row_*.npz`, `data/secondary_checkpoints/pc_*.npz`),
clears the drain flag and calls the same argv again. `job_wall_s` is from the
first attempt's call to the last's return, everything between included.

Set-up runs one undisturbed job (its tables' sha256 are kept, and its record:
what a job computes when nothing stops it) and one stopped job, both untimed:
a resumed attempt's batched calls have other row counts than a fresh job's, so
other programs, and nothing may compile inside the window.

A job counts as failed if an attempt that was to stop ended any other way or
at another boundary than the mix names, if the last attempt left no `Cdb.csv`,
if any attempt's record books a hiding counter or path (``batch_jobs``), or if
the routes and counters the mix expects did not serve over the job
(``greedy_jobs``). The readers of the older per-layer metrics are handed ONE
record a job, the attempts' span seconds and counters summed
(``merge_records``); the attempts' own records stay under `attempts`.

`correct` is ``greedy_jobs.check_greedy`` on the last job's tables at the
cell's full size, called, not copied, and four comparisons of the guarantees
(``guarantee_comparisons``): every job's tables byte-equal to the undisturbed
job's, no tile dispatched inside a stripe that was published before its
attempt began, no cluster computed among those published before its attempt
began (both limit 0), and the clusters computed twice over a job at most
those of one batched device call (`limits.clusters_twice`).
"""

from __future__ import annotations

import hashlib
import json
import os
import shutil
import statistics
import time

from benchmark import batch_jobs, check, greedy_jobs, tracered

TABLES = ("Cdb", "Ndb", "Mdb")
STORES = {"stripes": (os.path.join("data", "streaming_primary"), "row_"),
          "clusters": (os.path.join("data", "secondary_checkpoints"), "pc_")}
SUMMED = ("stages", "fault_tolerance", "secondary_paths", "secondary_greedy_batched", "resume",
          "phases")  # sections whose numbers add up over a job's attempts


# ---- what the stores hold, what a job wrote ----------------------------------------------


def published(workdir: str) -> dict:
    """{"stripes": [stripe numbers], "clusters": [primary cluster numbers]}
    of the payloads the two stores hold now."""
    out = {}
    for what, (sub, prefix) in STORES.items():
        path = os.path.join(workdir, sub)
        names = os.listdir(path) if os.path.isdir(path) else []
        out[what] = sorted(int(n[len(prefix):].split(".")[0]) for n in names
                           if n.startswith(prefix) and n.endswith(".npz"))
    return out


def empty_stores(workdir: str) -> None:
    """The control's hook: a program that keeps nothing."""
    for sub, _ in STORES.values():
        shutil.rmtree(os.path.join(workdir, sub), ignore_errors=True)


def table_digests(workdir: str) -> dict:
    out = {}
    for table in TABLES:
        with open(os.path.join(workdir, "data_tables", table + ".csv"), "rb") as f:
            out[table] = hashlib.sha256(f.read()).hexdigest()
    return out


# ---- one record a job ----------------------------------------------------------------------


def _add(into, other):
    """`other` added into `into`, number by number, through nested dicts;
    what is no number (a thread's name) keeps the first attempt's."""
    for key, value in other.items():
        if isinstance(value, dict):
            _add(into.setdefault(key, {}), value)
        elif isinstance(value, (int, float)) and not isinstance(value, bool):
            into[key] = into.get(key, 0) + value
        else:
            into.setdefault(key, value)


def merge_records(records: list[dict]) -> dict:
    """One record for a job of several attempts, for the readers that take a
    job's record: the last attempt's (its device, its `process` ledger, its
    `evaluate`), with the sections of ``SUMMED`` added up over the attempts,
    the one-shot calls added up shape by shape and the greedy engine's
    clusters listed in the order they were served. A rate inside `stages`
    (`pairs_per_sec`) is no sum and is dropped."""
    out = dict(records[-1])
    for section in SUMMED:
        total: dict = {}
        for rec in records:
            _add(total, rec.get(section) or {})
        if total:
            out[section] = total
    for stage in out.get("stages", {}).values():
        for rate in ("pairs_per_sec", "pairs_per_sec_per_chip", "tile_fraction", "skip_fraction"):
            stage.pop(rate, None)
    shapes: dict = {}
    for rec in records:
        for call in rec.get("secondary_calls") or []:
            shape = {k: call[k] for k in ("rows_pad", "width", "v_pad") if k in call}
            _add(shapes.setdefault(tuple(sorted(shape.items())), {}),
                 {k: v for k, v in call.items() if k not in shape})
    if shapes:
        out["secondary_calls"] = [{**dict(shape), **summed} for shape, summed in sorted(shapes.items())]
    engine = [call for rec in records for call in rec.get("secondary_greedy_calls") or []]
    if engine:
        out["secondary_greedy_calls"] = engine
    out.pop("drain", None)  # the attempts' own; the job ended
    return out


# ---- one job ---------------------------------------------------------------------------------


def run_attempt(argv: list[str], fault: str | None) -> dict:
    """One call of the CLI's function. {"wall_s", "began_s", "returned_s"
    (time.monotonic(), the clock the program stamps a drain request on),
    "exit" (None: it returned; else the SystemExit's code), "error"}."""
    from drep_tpu import controller
    from drep_tpu.parallel import faulttol
    from drep_tpu.utils import faults

    if fault:
        os.environ[faults.ENV] = fault
    faults.reset()  # the program reads the variable itself, as in a user's run
    code, error = None, None
    began = time.monotonic()
    try:
        controller.main(argv)
    except SystemExit as e:  # a drain's exit 0, or the CLI's way of refusing
        code = 0 if e.code is None else e.code
    except Exception as e:  # noqa: BLE001 — a failed job is counted, not fatal
        error = f"{type(e).__name__}: {e}"
    returned = time.monotonic()
    # a flag left standing would stop the next attempt at its first boundary
    faulttol.clear_drain()
    os.environ.pop(faults.ENV, None)
    faults.reset()
    return {"wall_s": returned - began, "began_s": began, "returned_s": returned, "exit": code,
            "error": error}


def run_job(argv_template: list[str], stops: list[dict], pristine: str, job_dir: str,
            between=None) -> dict:
    """One job: an attempt a stop, then one to the end. Returns {"wall_s",
    "workdir", "error", "attempts"}; each attempt holds its record as the
    program wrote it (bytes, parsed after the window) and what the stores held
    when it began. `between(workdir)` is the control's hook, called after the
    stores are listed."""
    shutil.rmtree(job_dir, ignore_errors=True)
    shutil.copytree(pristine, job_dir, copy_function=os.link)
    argv = [a.replace("{workdir}", job_dir) for a in argv_template]
    record_path = os.path.join(job_dir, "log", "perf_counters.json")
    attempts: list[dict] = []
    error = None
    t0 = time.monotonic()
    for fault in [s["fault"] for s in stops] + [None]:
        held = published(job_dir)
        if between is not None and attempts:
            between(job_dir)
        attempt = {**run_attempt(argv, fault), "fault": fault, "published_before": held}
        try:
            with open(record_path, "rb") as f:
                attempt["record_bytes"] = f.read()
            os.unlink(record_path)  # the next attempt's record is its own or none
        except OSError:
            attempt["record_bytes"] = None
        attempts.append(attempt)
        if attempt["error"] or attempt["exit"] not in (None, 0):
            error = attempt["error"] or f"exit code {attempt['exit']}"
            break
    wall = time.monotonic() - t0
    if error is None and not os.path.exists(os.path.join(job_dir, "data_tables", "Cdb.csv")):
        error = "no Cdb.csv"
    return {"wall_s": wall, "workdir": job_dir, "error": error, "attempts": attempts}


def read_records(job: dict) -> None:
    """Parse the attempts' records, outside the timed span, and make the
    job's one record of them."""
    from drep_tpu.workdir import WorkDirectory

    for attempt in job["attempts"]:
        raw = attempt.pop("record_bytes", None)
        attempt["record"] = json.loads(raw) if raw else None
    records = [a["record"] for a in job["attempts"]]
    job["record"] = merge_records(records) if all(records) else None
    job["resolved"] = (WorkDirectory(job["workdir"]).get_arguments("cluster") or {}).get(
        "primary_estimator_resolved")


def stop_faults(job: dict, stops: list[dict]) -> list[str]:
    """Why a job was not stopped where the mix stops it. Empty for a sound job."""
    bad = []
    if len(job["attempts"]) != len(stops) + 1:
        return [f"{len(job['attempts'])} attempt(s), the mix means {len(stops) + 1}"]
    for k, (attempt, stop) in enumerate(zip(job["attempts"], stops), start=1):
        rec = attempt["record"]
        if rec is None:
            bad.append(f"attempt {k} left no record")
            continue
        drain = rec.get("drain") or {}
        if attempt["exit"] != 0 or drain.get("stage") != stop["stage"]:
            bad.append(f"attempt {k} was to drain in the {stop['stage']} and ended with exit "
                       f"{attempt['exit']!r}, drain {drain or None}")
        elif drain.get(stop["counts"]) != stop["after"]:
            bad.append(f"attempt {k} drained with {stop['counts']}={drain.get(stop['counts'])}, "
                       f"the mix means {stop['after']}")
    last = job["attempts"][-1]
    if last["record"] is None:
        bad.append("the last attempt left no record")
    elif last["exit"] is not None or last["record"].get("drain"):
        bad.append(f"the last attempt was to run to its end and drained: {last['record'].get('drain')}")
    return bad


# ---- the guarantees, counted ---------------------------------------------------------------


def work_again(job: dict, n_stripes: int) -> dict:
    """What a job's attempts did that the stores held already, from each
    attempt's own `resume` counters and the listing made before it began:
    `tiles_again` (tiles dispatched beyond those of the stripes that were not
    published), `clusters_again` (published clusters that were not looked up
    but computed), over the attempts after the first; `clusters_computed`,
    summed over all attempts."""
    tiles_again = clusters_again = computed = 0
    for k, attempt in enumerate(job["attempts"]):
        did = (attempt["record"] or {}).get("resume") or {}
        computed += did.get("clusters_computed", 0)
        if k == 0:
            continue
        held = attempt["published_before"]
        open_tiles = sum(n_stripes - bi for bi in range(n_stripes) if bi not in held["stripes"])
        tiles_again += max(0, did.get("tiles_computed", 0) - open_tiles)
        clusters_again += max(0, len(held["clusters"]) - did.get("clusters_resumed", 0))
    return {"tiles_again": tiles_again, "clusters_again": clusters_again,
            "clusters_computed": computed}


def guarantee_comparisons(jobs: list[dict], undisturbed: dict, limits: dict) -> list[dict]:
    """The four comparisons of the deployment's own guarantees over the
    window's sound jobs, the worst job's value each. `undisturbed` holds the
    warm-up job's `digests` and `record`."""
    rec = undisturbed["record"]
    n_stripes = int(rec["primary_stream_slots"]["stripes"])
    n_clusters = int(rec["resume"]["clusters_computed"])
    again = [work_again(job, n_stripes) for job in jobs]
    return [
        check.comparison(
            f"jobs of {len(jobs)} whose Cdb, Ndb or Mdb is not byte for byte the undisturbed job's",
            sum(table_digests(job["workdir"]) != undisturbed["digests"] for job in jobs), 0),
        check.comparison(
            "tiles dispatched inside stripes published before their attempt began, worst job",
            max(a["tiles_again"] for a in again), 0),
        check.comparison(
            "clusters computed among those published before their attempt began, worst job",
            max(a["clusters_again"] for a in again), 0),
        check.comparison(
            f"clusters computed twice over a job (an undisturbed job computes {n_clusters}), worst job",
            max(a["clusters_computed"] - n_clusters for a in again), limits["clusters_twice"]),
    ]


# ---- the runner ------------------------------------------------------------------------------


def refuse_a_program_that_cannot_stop(stops: list[dict], expect: dict) -> None:
    """Before any set-up is spent: the program's fault registry has to know
    every stop's site, and its record the sections the kind reads."""
    from drep_tpu.utils import faults

    try:
        faults.configure(",".join(s["fault"] for s in stops))
    except faults.FaultSpecError as e:
        raise SystemExit(f"this program cannot be stopped where the cell stops it: {e}") from e
    finally:
        faults.reset()
    unknown = greedy_jobs.counters_unknown(
        {"counters": list(expect.get("counters", [])) + ["resume", "drain"]})
    if unknown:
        raise SystemExit(f"this program's record has no {unknown}: every job of the cell would "
                         f"count as failed, nothing to measure")


def judge(job: dict, stops: list[dict], device: dict, expect: dict, rehearse: bool,
          not_held: list[str]) -> None:
    """Set `job["error"]` from the attempts' and the job's records."""
    if job["error"]:
        return
    read_records(job)
    faults = stop_faults(job, stops)
    for k, attempt in enumerate(job["attempts"], start=1):
        if attempt["record"] is not None:  # device, hiding counters and paths: every attempt's
            faults += [f"attempt {k}: {f}" for f in
                       batch_jobs.record_faults(attempt["record"], device, {}, None)]
    if job["record"] is not None:
        want = expect.get("primary_estimator_resolved")
        if want and job["resolved"] != want:
            faults.append(f"primary estimator resolved to {job['resolved']!r}, the cell means {want!r}")
        faults += greedy_jobs.counter_faults(job["record"], expect)
        routes = greedy_jobs.route_faults(job["record"], expect)
        if rehearse:  # off a TPU the engine takes its gather route: said, not failed
            not_held += [f for f in routes if f not in not_held]
        else:
            faults += routes
    job["error"] = "; ".join(faults) or None


def run(ctx: dict) -> dict:
    """Set-up, window and check of the cell. `ctx` is ``batch_jobs.run``'s."""
    cfg, mix = ctx["config"], ctx["traffic"]
    expect = mix.get("expect", {})
    stops = (mix.get("rehearse", {}) if ctx["rehearse"] else {}).get("stops", mix["stops"])
    refuse_a_program_that_cannot_stop(stops, expect)
    prepared = ctx["generator"].prepare(cfg, ctx["seed"], ctx["work_dir"])
    pristine, data = prepared["workdir"], prepared["data"]
    print(f"setup: planted {len(data.names)} sketch sets at {ctx['setup_clock']():.1f}s", flush=True)

    # ---- set-up: an undisturbed job, kept as the yardstick, then a stopped one ----
    plain = batch_jobs.run_job(mix["argv"], pristine, os.path.join(ctx["work_dir"], "undisturbed"))
    if plain["error"]:
        raise SystemExit(f"the undisturbed warm-up job failed: {plain['error']}")
    batch_jobs._read_record(plain)
    undisturbed = {"digests": table_digests(plain["workdir"]), "record": plain["record"]}
    shutil.rmtree(plain["workdir"], ignore_errors=True)
    print(f"setup: warm-up job took {plain['wall_s']:.1f}s", flush=True)
    whole = {"primary": plain["record"]["primary_stream_slots"]["stripes"],
             "secondary": plain["record"]["resume"]["clusters_computed"]}
    for stop in stops:  # the mix says of how many boundaries it stops after which
        if whole[stop["stage"]] != stop["of"]:
            raise SystemExit(f"an undisturbed job has {whole[stop['stage']]} boundaries in the "
                             f"{stop['stage']}, the mix's stop assumes {stop['of']}")
    not_held: list[str] = []
    warm = run_job(mix["argv"], stops, pristine, os.path.join(ctx["work_dir"], "warm"))
    judge(warm, stops, ctx["device"], expect, ctx["rehearse"], not_held)
    if warm["error"]:
        raise SystemExit(f"the stopped warm-up job failed: {warm['error']}")
    shutil.rmtree(warm["workdir"], ignore_errors=True)
    print(f"setup: stopped warm-up job took {warm['wall_s']:.1f}s "
          f"(attempts {[round(a['wall_s'], 2) for a in warm['attempts']]})", flush=True)
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
        job = run_job(mix["argv"], stops, pristine, job_dir)
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

    # ---- after the window: records, then the reference and the guarantees ----
    failed = 0
    for job in jobs:
        judge(job, stops, ctx["device"], expect, ctx["rehearse"], not_held)
        if job["error"]:
            failed += 1
            print(f"job failed: {job['error']}", flush=True)
    for f in not_held:
        print(f"rehearsal: expected of the device path, not held here (not failed): {f}", flush=True)
    sound = [j for j in jobs if not j["error"]]
    if not sound:
        raise SystemExit("no job of the window ran soundly: nothing to report")
    last = sound[-1]
    print(f"greedy: {greedy_jobs.greedy_digest(last['record'])}", flush=True)
    print("attempts: " + json.dumps([{
        "wall_s": round(a["wall_s"], 3), "drain": a["record"].get("drain"),
        "resume": a["record"].get("resume"),
        "held_before": {k: len(v) for k, v in a["published_before"].items()}}
        for a in last["attempts"]]), flush=True)
    t_ref = time.monotonic()
    answers = greedy_jobs.read_answers(last["workdir"], data.names)
    comparisons = greedy_jobs.check_greedy(answers, data, cfg["params"], mix["compare"], mix["limits"])
    comparisons += guarantee_comparisons(sound, undisturbed, mix["limits"])
    print(f"reference: {time.monotonic() - t_ref:.1f}s after the window ({len(sound)} sound job(s) "
          f"of {len(jobs)} in {window_s:.1f}s)", flush=True)
    return {
        "correct": check.report(comparisons), "attempted": len(jobs), "failed": failed,
        "end_to_end": {"setup_s": setup_s,
                       "job_wall_s": statistics.median(j["wall_s"] for j in sound)},
        "run": {"jobs": sound, "trace": trace, "compiles_in_window": compiles_in_window,
                "window_s": window_s, "config": cfg, "traffic": mix, "device": ctx["device"],
                "peaks": ctx["peaks"], "planted": data, "undisturbed": undisturbed["record"]},
    }
