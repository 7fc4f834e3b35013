"""What a process pays before its first warm job (ISSUE 36,
utils/profiling.py): the record's ``compile`` section (the programs a job
traced, lowered, compiled or loaded, by function and by span, booked by
``jax.monitoring`` listeners) and its ``process`` section (the ledger that
``Counters.reset`` leaves: when each job began on the process's own clock,
the first job kept with its programs).

Unit half: the booking on hand-made events, the ledger's bounds, no JAX at
import. End-to-end half: ONE fresh process runs three toy ``compare`` jobs
and hands back their records, once on an empty persistent cache and once on
the cache the first left: a test worker's own process has run other jobs
before, so its ledger says nothing about a first job."""

import json
import os
import subprocess
import sys
import time

import pytest

from drep_tpu.utils import profiling, telemetry
from drep_tpu.utils.profiling import Counters

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

TRACE, LOWER, BACKEND = profiling._TRACE_EVENT, profiling._LOWER_EVENT, profiling._BACKEND_EVENT


def _build(c: Counters, name: str, trace=0.25, lower=0.125, backend=1.0, cache="off", load=0.0):
    """One program's events in jax's order, as its listeners would hear them."""
    c.on_compile_scalar(TRACE)
    c.on_compile_duration(TRACE, trace, name)
    c.on_compile_duration(LOWER, lower, f"jit({name})")
    if cache != "off":
        c.on_compile_event(profiling._REQUEST_EVENT)
    if cache == "hit":
        c.on_compile_event(profiling._HIT_EVENT)
        c.on_compile_duration(profiling._RETRIEVAL_EVENT, load, "")
    c.on_compile_duration(BACKEND, backend, f"jit({name})")


# --- the compile section on hand-made events ---------------------------------


def test_a_program_is_one_entry_under_the_span_it_was_built_in():
    c = Counters()
    with c.span("job"):
        with c.span("primary/wait"):
            _build(c, "tile", cache="miss")
            _build(c, "tile", trace=0.5, cache="miss")  # the same function at another shape
        with c.span("secondary/wait"):
            _build(c, "tri", backend=0.5, cache="hit", load=0.375)
    _build(c, "late")  # no span open: named by its thread
    got = c.report(device=False)["compile"]
    assert (got["programs"], got["cache_hits"], got["cache_misses"]) == (4, 1, 2)
    assert got["trace_s"] == 1.25 and got["lower_s"] == 0.5
    # the backend-compile event wraps the cache: a hit's retrieval is booked apart
    assert got["backend_compile_s"] == 3.125 and got["cache_load_s"] == 0.375
    by = {p["fun_name"]: p for p in got["by_program"]}
    assert by["tile"] == {"fun_name": "tile", "span": "primary/wait", "calls": 2, "trace_s": 0.75,
                          "lower_s": 0.25, "backend_compile_s": 2.0, "cache_load_s": 0.0,
                          "hits": 0, "misses": 2}
    assert by["tri"]["span"] == "secondary/wait" and by["tri"]["hits"] == 1
    assert by["tri"]["backend_compile_s"] == 0.125 and by["tri"]["cache_load_s"] == 0.375
    assert by["late"]["span"] == "thread:MainThread" and (by["late"]["hits"], by["late"]["misses"]) == (0, 0)
    # longest first, and the same seconds by span
    assert [p["fun_name"] for p in got["by_program"]] == ["tile", "late", "tri"]
    assert got["by_span"]["primary/wait"]["programs"] == 2
    assert got["by_span"]["secondary/wait"]["cache_load_s"] == 0.375
    assert set(got["by_span"]) == {"primary/wait", "secondary/wait", "thread:MainThread"}


def test_a_trace_inside_a_trace_is_the_outer_program_s_time():
    """`jnp.sin` inside a jitted function fires a trace event of its own,
    inside the outer one: booked once. An eager operation at trace time is a
    program of its own, and the outer trace is booked less its seconds."""
    c = Counters()
    c.on_compile_scalar(TRACE)                       # outer begins
    c.on_compile_scalar(TRACE)                       # sin begins
    c.on_compile_duration(TRACE, 0.125, "sin")       # sin ends: inside, not booked
    c.on_compile_scalar(TRACE)                       # an eager op, traced ...
    c.on_compile_duration(TRACE, 0.0625, "ones")
    c.on_compile_duration(LOWER, 0.25, "jit(ones)")  # ... lowered and compiled whole
    c.on_compile_duration(BACKEND, 0.5, "jit(ones)")
    c.on_compile_duration(TRACE, 2.0, "outer")       # outer ends: 2.0 with all of the above
    c.on_compile_duration(LOWER, 0.5, "jit(outer)")
    c.on_compile_duration(BACKEND, 1.0, "jit(outer)")
    got = c.report(device=False)["compile"]
    by = {p["fun_name"]: p for p in got["by_program"]}
    assert set(by) == {"outer", "ones"}
    assert by["outer"]["trace_s"] == 1.25 and by["ones"]["trace_s"] == 0.0
    # every second of wall is booked once
    assert got["trace_s"] + got["lower_s"] + got["backend_compile_s"] == 2.0 + 0.5 + 1.0
    assert got["programs"] == 2


def test_events_that_are_not_a_program_s_are_ignored():
    c = Counters()
    c.on_compile_duration("/jax/compilation_cache/compile_time_saved_sec", 9.0, "")
    c.on_compile_duration("/jax/core/pjit/some_other_duration", 9.0, "f")
    c.on_compile_event("/jax/compilation_cache/tasks_using_cache")
    c.on_compile_scalar("/jax/something")
    assert c.report(device=False)["compile"]["by_program"] == []


def test_the_program_list_is_bounded_and_the_rest_is_summed():
    c = Counters()
    n = profiling.SECONDARY_SHAPES_MAX + 6
    for i in range(n):
        _build(c, f"f{i}", backend=float(n - i))
    got = c.report(device=False)["compile"]
    assert len(got["by_program"]) == profiling.SECONDARY_SHAPES_MAX + 1
    rest = got["by_program"][-1]
    assert rest["fun_name"] == "" and rest["calls"] == 6
    assert rest["backend_compile_s"] == sum(range(1, 7))
    assert sum(p["calls"] for p in got["by_program"]) == got["programs"] == n


def test_the_compile_instant_is_one_a_program(tmp_path):
    telemetry.configure(log_dir=str(tmp_path), enabled=True, pid=0)
    try:
        c = Counters()
        _build(c, "tile", cache="miss")
        _build(c, "tri", backend=0.5, cache="hit", load=0.25)
    finally:
        telemetry.close()
        telemetry.configure()
    with open(tmp_path / "events.p0.jsonl") as f:
        recs = [json.loads(x) for x in f if x.strip()]
    assert [(r["ev"], r["ph"], r["args"]) for r in recs] == [
        ("compile", "i", {"fun_name": "tile", "dur": 1.0, "cache": "miss"}),
        ("compile", "i", {"fun_name": "tri", "dur": 0.5, "cache": "hit"})]


# --- the process ledger ------------------------------------------------------


def _job(c: Counters, verb: str, programs: int = 0) -> dict:
    c.process.begin(verb)
    c.reset()
    c.process.brought_up()
    with c.span("job"):
        for i in range(programs):
            _build(c, f"{verb}{i}")
        record = c.report(device=False)
        c.finish_job()
    return record


def test_reset_clears_the_compile_section_and_leaves_the_ledger():
    c = Counters()
    first = _job(c, "compare", programs=2)
    assert first["compile"]["programs"] == 2
    # a process's first job is its own first_job, as far as it has come
    assert first["process"]["n_jobs"] == 0 and first["process"]["jobs"] == []
    assert first["process"]["first_job"]["verb"] == "compare"
    assert first["process"]["first_job"]["compile"]["programs"] == 2
    c.reset()
    assert c.report(device=False)["compile"]["programs"] == 0
    second = _job(c, "dereplicate")
    proc = second["process"]
    assert second["compile"]["programs"] == 0
    assert proc["n_jobs"] == 1  # before its own entry
    assert [j["verb"] for j in proc["jobs"]] == ["compare"]
    assert proc["first_job"]["verb"] == "compare"
    assert [p["fun_name"] for p in proc["first_job"]["compile"]["by_program"]] == ["compare0", "compare1"]
    # the list keeps totals only
    assert "by_program" not in proc["jobs"][0]["compile"] and proc["jobs"][0]["compile"]["programs"] == 2
    # the marks: this job began after the first, and after the package's import
    assert proc["began_at_s"] >= proc["first_job"]["began_at_s"] >= proc["imported_at_s"] >= 0.0
    assert proc["clock"] in ("proc_stat", "package_import")


def test_twenty_jobs_leave_sixteen_entries_and_the_first_intact():
    c = Counters()
    for i in range(20):
        _job(c, f"v{i}", programs=1 if i == 0 else 0)
    proc = c.report(device=False)["process"]
    assert proc["n_jobs"] == 20 and len(proc["jobs"]) == profiling.LEDGER_JOBS_MAX == 16
    assert [j["verb"] for j in proc["jobs"]] == [f"v{i}" for i in range(4, 20)]
    assert proc["first_job"]["verb"] == "v0"
    assert proc["first_job"]["compile"]["by_program"][0]["fun_name"] == "v00"
    # outside any job the record names none
    assert proc["began_at_s"] is None and proc["bring_up_s"] is None


def test_the_marks_are_seconds_since_the_process_started():
    c = Counters()
    assert profiling._process_age_s()[1] == c.process.clock
    before = profiling._process_age_s()[0]
    c.process.begin("compare")
    after = profiling._process_age_s()[0]
    time.sleep(0.05)
    c.process.brought_up()
    with c.span("job"):
        time.sleep(0.02)
        entry = c._job_entry({})
    assert before - 0.01 <= entry["began_at_s"] <= after + 0.01
    assert 0.05 <= entry["bring_up_s"] < 0.5 and 0.02 <= entry["job_s"] < 0.5
    if c.process.clock == "proc_stat":  # the interpreter's start lies before the package's import
        assert 0.0 < c.process.imported_at_s < entry["began_at_s"]


_NO_JAX = """
import sys
sys.path.insert(0, {repo!r})
import drep_tpu.sketch_worker
from drep_tpu.utils import profiling
assert "jax" not in sys.modules, "an import loaded jax"
assert profiling._listening is False
profiling.counters.process.begin("index route")
profiling.counters.process.brought_up()
rep = profiling.counters.report(device=False)
assert rep["compile"]["programs"] == 0 and rep["compile"]["by_program"] == []
assert rep["process"]["first_job"]["verb"] == "index route" and rep["process"]["n_jobs"] == 0
assert rep["process"]["imported_at_s"] >= 0.0
assert "jax" not in sys.modules, "the record loaded jax"
print("ok")
"""


def test_the_worker_s_imports_and_a_control_plane_record_never_load_jax():
    """A spawned ingest worker imports `drep_tpu.sketch_worker`, `index
    route` and `index supervise` write their record with `device=False`:
    neither loads JAX, so neither can have registered a listener."""
    out = subprocess.run([sys.executable, "-c", _NO_JAX.format(repo=REPO)],
                         capture_output=True, text=True, timeout=120)
    assert out.returncode == 0 and out.stdout.strip() == "ok", out.stderr


def test_no_listener_is_registered_outside_the_two_bring_ups():
    import ast

    calls = []
    for root, _dirs, files in os.walk(os.path.join(REPO, "drep_tpu")):
        for name in files:
            if not name.endswith(".py"):
                continue
            path = os.path.join(root, name)
            with open(path) as f:
                tree = ast.parse(f.read())
            for fn in ast.walk(tree):
                if not isinstance(fn, ast.FunctionDef):
                    continue
                for node in ast.walk(fn):
                    if (isinstance(node, ast.Call) and isinstance(node.func, ast.Name)
                            and node.func.id == "listen_for_compiles"):
                        calls.append((os.path.relpath(path, REPO), fn.name))
    assert sorted(calls) == [("drep_tpu/workflows.py", "_bring_up"), ("drep_tpu/workflows.py", "_init_index")]


# --- three toy jobs in one fresh process -------------------------------------

_THREE_JOBS = """
import json, os, sys
sys.path.insert(0, {repo!r})
import jax
from jax._src import monitoring
raw = []
jax.monitoring.register_event_duration_secs_listener(
    lambda name, secs, **kw: raw.append((name, secs)))
from drep_tpu.workflows import compare_wrapper

genomes, out = {genomes!r}, {out!r}
records = []
for i, extra in enumerate([{{}}, {{}}, {{"MASH_sketch": 512}}]):
    wd = os.path.join(out, "wd%d" % i)
    compare_wrapper(wd, genomes, skip_plots=True, processes=1, events="on", **extra)
    with open(os.path.join(wd, "log", "perf_counters.json")) as f:
        records.append(json.load(f))
mine = lambda fns: sum(getattr(f, "__module__", "") == "drep_tpu.utils.profiling" for f in fns)
with open(os.path.join(out, "got.json"), "w") as f:
    json.dump({{"records": records, "raw": raw, "listeners": [
        mine(monitoring.get_event_duration_listeners()),
        mine(monitoring.get_event_listeners()),
        mine(monitoring.get_scalar_listeners())]}}, f)
"""


def _three_jobs(out: str, cache: str, genomes: list[str]) -> dict:
    os.makedirs(out)
    env = dict(os.environ, JAX_PLATFORMS="cpu", JAX_COMPILATION_CACHE_DIR=cache,
               # the toy programs compile in milliseconds: store them all the same
               JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS="0",
               JAX_PERSISTENT_CACHE_MIN_ENTRY_SIZE_BYTES="-1")
    env.pop("DREP_TPU_EVENTS", None)
    script = _THREE_JOBS.format(repo=REPO, genomes=list(genomes), out=out)
    done = subprocess.run([sys.executable, "-c", script], capture_output=True, text=True,
                          timeout=900, env=env)
    assert done.returncode == 0, done.stderr[-3000:]
    with open(os.path.join(out, "got.json")) as f:
        got = json.load(f)
    got["out"] = out
    return got


@pytest.fixture(scope="module")
def cold_and_warm(tmp_path_factory, genome_paths):
    root = tmp_path_factory.mktemp("ledger")
    cache = str(root / "cache")
    cold = _three_jobs(str(root / "cold"), cache, genome_paths)
    warm = _three_jobs(str(root / "warm"), cache, genome_paths)
    return cold, warm


def test_the_second_record_holds_the_first_job_and_builds_nothing(cold_and_warm):
    first, second, _third = cold_and_warm[0]["records"]
    assert first["compile"]["programs"] > 0 and first["process"]["n_jobs"] == 0
    proc = second["process"]
    assert proc["n_jobs"] == 1 and len(proc["jobs"]) == 1  # before its own entry
    fj = proc["first_job"]
    assert fj["verb"] == "compare" and fj == {**proc["jobs"][0], "compile": fj["compile"]}
    # the first job's own `job` span, as far as its record had come, and a little more
    assert fj["job_s"] == pytest.approx(first["phases"]["job"]["seconds"], abs=0.25)
    assert fj["job_s"] >= first["phases"]["job"]["seconds"]
    assert fj["bring_up_s"] == first["process"]["bring_up_s"] > 0.0
    assert fj["began_at_s"] == first["process"]["began_at_s"] > first["process"]["imported_at_s"]
    assert fj["compile"]["programs"] == first["compile"]["programs"]
    assert [p["fun_name"] for p in fj["compile"]["by_program"]] == \
        [p["fun_name"] for p in first["compile"]["by_program"]] != []
    # every shape repeats: the second job builds nothing
    assert second["compile"]["programs"] == 0 and second["compile"]["by_program"] == []
    assert proc["began_at_s"] >= fj["began_at_s"] + fj["bring_up_s"] + fj["job_s"]
    assert proc["bring_up_s"] < fj["bring_up_s"] + 0.05


def test_a_shape_forced_new_appears_under_the_span_it_was_built_in(cold_and_warm):
    first, _second, third = cold_and_warm[0]["records"]
    built = third["compile"]
    assert 1 <= built["programs"] < first["compile"]["programs"]
    assert third["process"]["n_jobs"] == 2 and third["process"]["first_job"]["verb"] == "compare"
    for prog in built["by_program"]:
        assert prog["span"] in third["phases"], prog
        assert built["by_span"][prog["span"]]["programs"] >= prog["calls"]
    # the primary's tile at the new sketch width, where the primary waits for it
    tile = [p for p in built["by_program"] if p["calls"] and p["span"].startswith("primary/")]
    assert tile, built["by_program"]
    assert {p["fun_name"] for p in tile} <= {p["fun_name"] for p in first["compile"]["by_program"]}


def test_the_listeners_are_registered_once_over_three_jobs(cold_and_warm):
    for got in cold_and_warm:
        assert got["listeners"] == [1, 1, 1]


def test_the_booked_seconds_are_jax_s_own_and_a_hit_s_retrieval_is_booked_apart(cold_and_warm):
    """On the installed jax the backend-compile event wraps
    `compile_or_get_cached`: its seconds hold the retrieval, which the
    record books under `cache_load_s`."""
    for got in cold_and_warm:
        raw = got["raw"]
        for key, event in (("trace_s", TRACE), ("lower_s", LOWER)):
            booked = sum(r["compile"][key] for r in got["records"])
            heard = sum(s for name, s in raw if name == event)
            assert booked <= heard + 1e-4  # a trace inside a trace is booked once
        backend = sum(s for name, s in raw if name == BACKEND)
        booked = sum(r["compile"]["backend_compile_s"] + r["compile"]["cache_load_s"]
                     for r in got["records"])
        assert booked == pytest.approx(backend, abs=1e-4)
        assert sum(r["compile"]["programs"] for r in got["records"]) == \
            sum(name == BACKEND for name, _s in raw)
    cold, warm = (got["records"][0]["compile"] for got in cold_and_warm)
    assert cold["cache_hits"] == 0 and cold["cache_misses"] == cold["programs"] and cold["cache_load_s"] == 0.0
    assert warm["cache_hits"] == warm["programs"] == cold["programs"] and warm["cache_misses"] == 0
    retrieved = sum(s for name, s in cold_and_warm[1]["raw"] if name == profiling._RETRIEVAL_EVENT)
    assert sum(r["compile"]["cache_load_s"] for r in cold_and_warm[1]["records"]) == \
        pytest.approx(retrieved, abs=1e-4) and retrieved > 0.0
    assert warm["trace_s"] > 0.0 and warm["lower_s"] > 0.0  # paid warm or cold


def test_the_event_log_holds_one_compile_instant_a_program_and_none_a_call(cold_and_warm):
    for got, cache in zip(cold_and_warm, ("miss", "hit")):
        for i, rec in enumerate(got["records"]):
            with open(os.path.join(got["out"], f"wd{i}", "log", "events.p0.jsonl")) as f:
                lines = [json.loads(x) for x in f if x.strip()]
            built = [r for r in lines if r["ev"] == "compile"]
            assert len(built) == rec["compile"]["programs"]
            assert all(r["ph"] == "i" and r["args"]["cache"] == cache for r in built)
            assert sorted({r["args"]["fun_name"] for r in built}) == \
                sorted(p["fun_name"] for p in rec["compile"]["by_program"] if p["calls"])
