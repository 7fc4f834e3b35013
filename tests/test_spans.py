"""The span front door (ISSUE 24, utils/profiling.py ``Counters.span``): one
context manager, three sinks — the record's ``phases`` section, the
profiler's host plane (``drep:<name>``), the JSONL event log.

Unit half: nesting, self time, threads, a raising body, no JAX import, stable
names. End-to-end half: one toy ``compare`` on the CPU, run once with
``--profile`` and ``--events on`` together, whose record, trace and log the
tests below read."""

import ast
import glob
import importlib.util
import json
import os
import resource
import subprocess
import sys
import threading
import time

import numpy as np
import pytest

from drep_tpu.utils import profiling, telemetry
from drep_tpu.utils.profiling import Counters

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

@pytest.fixture(autouse=True)
def _reset_telemetry():
    yield
    telemetry.configure()


def _main_self_sum(phases: dict) -> float:
    return sum(p["self_seconds"] for p in phases.values() if p["thread"] == "main")


# --- the front door alone -------------------------------------------------


def test_nesting_keeps_self_time_and_the_sum_closes_on_the_root():
    c = Counters()
    with c.span("job"):
        with c.span("a"):
            time.sleep(0.02)
            with c.span("b"):
                time.sleep(0.03)
            with c.span("b"):
                time.sleep(0.01)
        time.sleep(0.01)
    ph = c.report(device=False)["phases"]
    assert set(ph) == {"job", "a", "b"}
    assert ph["b"]["calls"] == 2 and ph["a"]["calls"] == 1
    # a span's self time is its duration less what its child spans cover
    assert ph["a"]["self_seconds"] == pytest.approx(ph["a"]["seconds"] - ph["b"]["seconds"], abs=2e-4)
    assert ph["b"]["self_seconds"] == pytest.approx(ph["b"]["seconds"], abs=1e-9)
    assert 0.02 <= ph["a"]["self_seconds"] < 0.03 + 0.02
    assert _main_self_sum(ph) == pytest.approx(ph["job"]["seconds"], rel=0.01)


def test_a_record_written_inside_the_root_counts_the_open_spans_so_far():
    """The workflows write perf_counters.json inside `job`, and a library
    user may reset the counters inside a span: neither may lose the root."""
    c = Counters()
    with c.span("job"):
        with c.span("before_reset"):
            pass
        c.reset()
        with c.span("work"):
            time.sleep(0.02)
        with c.span("writing"):
            ph = c.report(device=False)["phases"]
    assert set(ph) == {"job", "work", "writing"}
    assert ph["job"]["seconds"] >= 0.02 and ph["job"]["calls"] == 1
    assert _main_self_sum(ph) == pytest.approx(ph["job"]["seconds"], rel=0.01)
    # once closed, the root is booked once, with its whole duration
    done = c.report(device=False)["phases"]
    assert done["job"]["calls"] == 1 and done["job"]["seconds"] >= ph["job"]["seconds"]


def test_a_span_on_another_thread_is_kept_apart():
    c = Counters()

    def work():
        with c.span("primary/wait"):
            time.sleep(0.05)

    with c.span("job"):
        t = threading.Thread(target=work)
        t.start()
        with c.span("primary/wait"):
            time.sleep(0.01)
        t.join(timeout=10)
        assert not t.is_alive()
    ph = c.report(device=False)["phases"]
    assert ph["primary/wait"]["thread"] == "main"
    assert ph["primary/wait@other"]["thread"] == "other"
    assert ph["primary/wait@other"]["seconds"] >= 0.05 > ph["primary/wait"]["seconds"]
    # the other thread's time is in nobody's parent and not in the main sum
    assert _main_self_sum(ph) == pytest.approx(ph["job"]["seconds"], rel=0.01)


def test_a_raising_body_still_closes_and_records(tmp_path):
    telemetry.configure(log_dir=str(tmp_path), enabled=True, pid=0)
    c = Counters()
    with c.span("job"):
        with pytest.raises(ValueError):
            with c.span("boom", bi=3):
                raise ValueError("x")
        with c.span("after"):
            time.sleep(0.05)  # the record rounds to 1e-4 s
    telemetry.close()
    ph = c.report(device=False)["phases"]
    assert ph["boom"]["calls"] == 1 and "after" in ph
    # the stack unwound: `after` is a child of `job`, not of `boom`
    assert _main_self_sum(ph) == pytest.approx(ph["job"]["seconds"], rel=0.01)
    with open(tmp_path / "events.p0.jsonl") as f:
        recs = [json.loads(x) for x in f if x.strip()]
    end = next(r for r in recs if r["ev"] == "boom" and r["ph"] == "E")
    assert end["args"]["error"] == "ValueError" and end["args"]["bi"] == 3


def test_one_span_round_a_loop_books_its_units_as_calls():
    c = Counters()
    with c.span("secondary/checkpoint", calls=40):
        pass
    with c.span("secondary/checkpoint", calls=2):
        pass
    assert c.report(device=False)["phases"]["secondary/checkpoint"]["calls"] == 42


def test_stage_keeps_its_signature_and_is_a_phase():
    c = Counters()
    with c.stage("secondary_compare", pairs=7):
        with c.span("secondary/wait"):
            pass
    rep = c.report(device=False)
    assert rep["stages"]["secondary_compare"]["pairs"] == 7
    assert rep["stages"]["secondary_compare"]["calls"] == 1
    assert set(rep["phases"]) == {"stage:secondary_compare", "secondary/wait"}


# --- what the host spent inside a span (ISSUE 52) ---------------------------


# a sandboxed kernel (the chip host's) keeps neither page faults nor context switches: a process
# that has come this far has faulted and slept where they are kept at all
_NOW = resource.getrusage(resource.RUSAGE_SELF)
COUNTS_FAULTS = _NOW.ru_minflt > 0
COUNTS_SWITCHES = _NOW.ru_nvcsw + _NOW.ru_nivcsw > 0


def _burn(seconds: float) -> float:
    """CPU with the GIL released (hashlib drops it for a large buffer), for
    about `seconds`; returns the calling thread's own CPU seconds of it."""
    import hashlib

    block = bytes(1 << 20)
    t0, cpu0 = time.perf_counter(), time.thread_time()
    while time.perf_counter() - t0 < seconds:
        hashlib.sha1(block).digest()
    return time.thread_time() - cpu0


def _touch_fresh(nbytes: int):
    """`nbytes` of anonymous memory touched for the first time, a 4 KiB
    page a fault whatever the host does with huge pages."""
    import mmap

    m = mmap.mmap(-1, nbytes)
    m.madvise(mmap.MADV_NOHUGEPAGE)
    np.frombuffer(m, np.uint8)[:: resource.getpagesize()] = 1
    return m


def test_a_span_round_a_thread_pool_reads_the_cores_it_kept_busy():
    from concurrent.futures import ThreadPoolExecutor

    c = Counters()
    with c.span("job"):
        with c.span("secondary/pack", workers=4), ThreadPoolExecutor(4) as pool:
            burned = sum(pool.map(_burn, [0.3] * 4))  # the workers open no spans
    ph = c.report(device=False)["phases"]["secondary/pack"]
    # the process's CPU inside the span is the workers' own, with no span in a worker: over
    # the span's seconds it is the scaling they reached (the sandbox lends its cores in
    # bursts: four threads get one core or four, and the span says which)
    assert burned > 0.25 and ph["seconds"] > 0.25
    assert ph["cpu_s"] == pytest.approx(burned + ph["self_thread_cpu_s"], rel=0.1, abs=0.03)
    # the opening thread waited: off its CPU, asleep by its own will
    assert ph["cpu_s"] > 4 * ph["self_thread_cpu_s"]
    assert ph["self_seconds"] - ph["self_thread_cpu_s"] > 0.2
    assert ph["self_vol_switches"] >= 1 or not COUNTS_SWITCHES


@pytest.mark.skipif(not COUNTS_FAULTS, reason="this kernel counts no page faults")
def test_a_child_span_that_touches_fresh_memory_books_the_faults_and_its_parent_does_not():
    c = Counters()
    with c.span("job"):
        with c.span("stage:ingest_or_cache"):
            time.sleep(0.005)  # the child opens on a read of its own
            with c.span("load"):
                kept = _touch_fresh(64 << 20)
            time.sleep(0.005)
    ph = c.report(device=False)["phases"]
    assert ph["load"]["self_minor_faults"] >= 16384
    assert ph["stage:ingest_or_cache"]["self_minor_faults"] < ph["load"]["self_minor_faults"] / 10
    assert ph["load"]["sys_s"] > 0 and ph["load"]["self_major_faults"] == 0
    kept.close()


@pytest.mark.parametrize("at", range(len(profiling._HOST_FIELDS)), ids=profiling._HOST_FIELDS)
def test_the_main_threads_self_values_add_up_to_the_jobs_own(at):
    c = Counters()
    with c.span("job"):
        with c.span("a"):
            _burn(0.01)
            with c.span("b"):
                kept = _touch_fresh(4 << 20)
            for _ in range(300):  # shorter than a read lasts: right in the loop's sum
                with c.span("tiny"):
                    pass
        with c.span("stage:cluster"):
            time.sleep(0.004)
            with c.span("writing"):  # the record is written from inside `job`
                inside = c._phases_report()
    kept.close()
    # the record's fields by _HOST_FIELDS' order; `job`'s whole where the record keeps it
    own = ("self_cpu_s", "self_sys_s", "self_minor_faults", "self_major_faults",
           "self_thread_cpu_s", "self_invol_switches", "self_vol_switches")[at]
    whole = {"self_cpu_s": "cpu_s", "self_sys_s": "sys_s"}.get(own)
    for ph in (inside, c._phases_report()):
        assert set(ph) == {"job", "a", "b", "tiny", "stage:cluster", "writing"}
        if whole:
            assert sum(p[own] for p in ph.values()) == pytest.approx(ph["job"][whole], abs=6e-4)
    # closed, each phase holds its spans' whole deltas: the parts add up to the root's
    phases = c.phases
    assert sum(p.self_host[at] for p in phases.values()) == pytest.approx(
        phases[("job", True)].host[at], abs=1e-9)
    assert all(p.self_host[at] >= -1e-9 for p in phases.values())
    assert _main_self_sum(inside) == pytest.approx(inside["job"]["seconds"], rel=0.01)


def test_gc_collect_inside_a_span_is_booked_there_and_not_in_its_sibling():
    import gc

    c = Counters()
    with c.span("job"):
        with c.span("choose/score"):
            cycles = [[] for _ in range(20000)]
            for x in cycles:
                x.append(x)
            del cycles, x
            gc.collect()
        gc.disable()  # none may start by itself inside the sibling
        try:
            with c.span("choose/copy"):
                time.sleep(0.001)
        finally:
            gc.enable()
    ph = c.report(device=False)["phases"]
    assert ph["choose/score"]["gc_collections"] >= 1 and ph["choose/score"]["gc_s"] > 0
    assert ph["choose/score"]["gc_s"] <= ph["choose/score"]["seconds"]
    assert ph["choose/copy"]["gc_collections"] == 0 and ph["choose/copy"]["gc_s"] == 0
    assert ph["job"]["gc_collections"] == 0  # self by construction: the child's is not the parent's


def test_a_span_on_another_thread_carries_the_threads_fields_alone():
    c = Counters()

    def work():
        with c.span("primary/wait"):
            _burn(0.05)

    with c.span("job"):
        t = threading.Thread(target=work)
        t.start()
        t.join(timeout=30)
        assert not t.is_alive()
    ph = c.report(device=False)["phases"]
    other = ph["primary/wait@other"]
    assert not {"cpu_s", "self_cpu_s", "sys_s", "self_sys_s", "self_minor_faults"} & set(other)
    assert other["self_thread_cpu_s"] > 0.02 and "gc_s" in other and "self_invol_switches" in other
    # the process's CPU of that time is booked once, where the main thread was
    assert ph["job"]["cpu_s"] >= other["self_thread_cpu_s"] * 0.9
    assert ph["job"]["self_thread_cpu_s"] < other["self_thread_cpu_s"]


def test_a_boundary_reuses_a_read_younger_than_the_constant(monkeypatch):
    reads = []
    read_host = profiling._read_host
    monkeypatch.setattr(profiling, "_read_host", lambda: (reads.append(1), read_host())[1])
    c = Counters()
    t0 = time.perf_counter()
    with c.span("job"):
        for _ in range(2000):
            with c.span("tiny"):
                pass
    elapsed = time.perf_counter() - t0
    assert 1 <= len(reads) <= elapsed / profiling.HOST_READ_EVERY_S + 2 < 2 * 2001
    c.report(device=False)  # the record's writer reads afresh
    assert len(reads) <= elapsed / profiling.HOST_READ_EVERY_S + 3


def test_secondary_calls_group_by_shape_and_stay_bounded():
    from drep_tpu.utils import profiling

    c = Counters()
    for _ in range(3):
        c.add_secondary_call(clusters=10, rows=40, rows_pad=64, width=128, v_pad=256, useful_pairs=60)
    for i in range(profiling.SECONDARY_SHAPES_MAX + 20):
        c.add_secondary_call(clusters=1, rows=2, rows_pad=128, width=128, v_pad=1024 + i, useful_pairs=1)
    calls = c.report(device=False)["secondary_calls"]
    first = next(e for e in calls if e["rows_pad"] == 64)
    assert first == {"rows_pad": 64, "width": 128, "v_pad": 256, "calls": 3, "clusters": 30,
                     "rows": 120, "useful_pairs": 180}
    assert len(calls) <= profiling.SECONDARY_SHAPES_MAX + 1
    assert sum(e["calls"] for e in calls) == 3 + profiling.SECONDARY_SHAPES_MAX + 20


_NO_JAX = """
import sys
sys.path.insert(0, {repo!r})
import drep_tpu.utils.telemetry
assert "jax" not in sys.modules, "telemetry imported jax"
from drep_tpu.utils.profiling import counters
with counters.span("job"):
    with counters.stage("serve_batch"):
        with counters.span("partition_load", pid=1):
            pass
rep = counters.report(device=False)
assert set(rep["phases"]) == {{"job", "stage:serve_batch", "partition_load"}}, rep
assert "jax" not in sys.modules, "a span imported jax"
print("ok")
"""


def test_a_span_never_imports_jax():
    """`index route`, `index supervise` and chip_smoke.py's parent never
    load JAX: the chip belongs to their children."""
    out = subprocess.run(
        [sys.executable, "-c", _NO_JAX.format(repo=REPO)],
        capture_output=True, text=True, timeout=120,
    )
    assert out.returncode == 0 and out.stdout.strip() == "ok", out.stderr


def _span_calls():
    """(path, line, name node) of every `<x>.span(...)` call in drep_tpu/."""
    for path in glob.glob(os.path.join(REPO, "drep_tpu", "**", "*.py"), recursive=True):
        with open(path) as f:
            tree = ast.parse(f.read())
        for node in ast.walk(tree):
            if (isinstance(node, ast.Call) and isinstance(node.func, ast.Attribute)
                    and node.func.attr in ("span", "Span") and node.args):
                yield os.path.relpath(path, REPO), node


def test_span_names_carry_no_varying_part_and_every_span_uses_the_front_door():
    seen = set()
    for path, node in _span_calls():
        if path == "drep_tpu/utils/profiling.py":
            continue  # the front door itself (`stage` prefixes its own name)
        # no span on one clock only: telemetry.Span is the JSONL sink of the
        # front door, not a second way in
        owner = node.func.value
        assert not (isinstance(owner, ast.Name) and owner.id == "telemetry"), (path, node.lineno)
        name = node.args[0]
        if isinstance(name, ast.BinOp):  # the ring's `ph + "/wait"`: stage by tile kind
            assert isinstance(name.left, ast.Name) and isinstance(name.right, ast.Constant)
            name = name.right
        assert isinstance(name, ast.Constant) and isinstance(name.value, str), (
            f"{path}:{node.lineno}: a span's name is a stable string; what varies goes "
            f"in its keyword arguments")
        assert not any(ch.isdigit() for ch in name.value), (path, node.lineno, name.value)
        seen.add(name.value)
    assert {"job", "stripe", "ring_step", "/wait", "secondary/pack", "tables_io"} <= seen


# --- one toy job: record, profiler trace and event log --------------------


@pytest.fixture(scope="module")
def toy_job(tmp_path_factory, genome_paths):
    from drep_tpu.workflows import compare_wrapper

    wd = str(tmp_path_factory.mktemp("spans_wd"))
    trace_dir = str(tmp_path_factory.mktemp("spans_trace"))
    compare_wrapper(wd, genome_paths, skip_plots=True, profile=trace_dir, events="on")
    telemetry.configure()
    with open(os.path.join(wd, "log", "perf_counters.json")) as f:
        record = json.load(f)
    return {"wd": wd, "trace_dir": trace_dir, "record": record}


def test_toy_compare_record_holds_every_span_its_path_reaches(toy_job):
    rec = toy_job["record"]
    ph = rec["phases"]
    reached = {
        "job", "tables_io", "mdb_build", "stage:cluster", "stage:ingest_or_cache",
        "stage:primary_compare", "primary/pack", "primary/wait", "primary/linkage",
        "primary/similarity",  # ISSUE 52: `1 - dist`, a second N x N matrix first touched
        "stage:secondary", "stage:secondary_compare", "stage:secondary_postprocess",
        "secondary/pack", "secondary/wait", "secondary/post", "secondary/checkpoint",
        "stage:assembly_io", "stage:evaluate",
    }
    assert reached <= set(ph), reached - set(ph)
    assert all(p["thread"] == "main" for p in ph.values())
    # acceptance: the main thread's self seconds add up to the job within 1%
    assert _main_self_sum(ph) == pytest.approx(ph["job"]["seconds"], rel=0.01)
    assert ph["primary/similarity"]["calls"] == 1
    # the stage totals read as before, and the stage spans agree with them
    assert set(rec["stages"]) == {"ingest_or_cache", "primary_compare", "secondary_compare",
                                  "secondary_postprocess", "assembly_io"}
    assert rec["stages"]["secondary_compare"]["seconds"] == pytest.approx(
        ph["stage:secondary_compare"]["seconds"], abs=5e-3)
    assert rec["secondary_paths"] == {"one_shot_clusterlocal": 1}
    # the call shapes: their useful pairs are the pairs the stage counted
    calls = rec["secondary_calls"]
    assert sum(c["useful_pairs"] for c in calls) == rec["stages"]["secondary_compare"]["pairs"] == 4
    assert [(c["calls"], c["clusters"], c["rows"], c["rows_pad"]) for c in calls] == [(1, 2, 5, 64)]
    assert all(c["rows"] <= c["rows_pad"] and c["width"] >= 128 and c["v_pad"] > 0 for c in calls)


@pytest.mark.parametrize("own,whole", [("self_seconds", "seconds"), ("self_cpu_s", "cpu_s"),
                                       ("self_sys_s", "sys_s")])
def test_every_phase_of_the_toy_jobs_record_carries_the_hosts_fields(toy_job, own, whole):
    """ISSUE 52: written from inside `job`, the record holds the fields in
    every entry, and the main thread's self values add up to `job`'s."""
    ph = toy_job["record"]["phases"]
    threads = {"self_thread_cpu_s", "self_invol_switches", "self_vol_switches", "gc_s", "gc_collections"}
    process = {"cpu_s", "self_cpu_s", "sys_s", "self_sys_s", "self_minor_faults", "self_major_faults"}
    for name, p in ph.items():
        want = threads | (process if p["thread"] == "main" else set())
        assert set(p) == {"seconds", "self_seconds", "calls", "thread"} | want, name
        assert name.endswith("@other") == (p["thread"] == "other")
    mains = [p for p in ph.values() if p["thread"] == "main"]
    # each entry is rounded to 1e-4
    assert sum(p[own] for p in mains) == pytest.approx(ph["job"][whole], abs=1e-4 * len(mains))
    assert ph["job"]["cpu_s"] > 0 and (sum(p["self_minor_faults"] for p in mains) > 0 or not COUNTS_FAULTS)


def test_profile_of_the_toy_job_puts_the_spans_on_the_host_plane(toy_job):
    """`--profile` uses the harness's ProfileOptions and wraps the whole
    job: the trace it leaves is one the benchmark's reduction reads, with
    `drep:` events on /host:CPU that idle_gaps picks as labels."""
    from benchmark import tracered

    xplane = tracered.find_xplane(toy_job["trace_dir"])
    assert xplane is not None
    events = tracered.load_xplane(xplane, rehearse=True)
    host = {name for name, _, _ in events["host"]}
    assert {"drep:job", "drep:stage:ingest_or_cache", "drep:primary/wait", "drep:secondary/wait",
            "drep:secondary/pack", "drep:tables_io"} <= host, sorted(n for n in host if "drep" in n)
    # Python frames are off (python_tracer_level 0): a whole job's trace stays small
    assert not any(n.startswith("$") for n in host)
    job = max((d for n, _, d in events["host"] if n == "drep:job"))
    assert job / 1e9 == pytest.approx(toy_job["record"]["phases"]["job"]["seconds"], rel=0.05)
    labels = [label for label, _ in tracered.idle_gaps(events, n=8)]
    assert labels and all(l != "host:unattributed" for l in labels), labels
    assert any(l.startswith("host:drep:") for l in labels), labels


def _trace_report():
    spec = importlib.util.spec_from_file_location(
        "trace_report", os.path.join(REPO, "tools", "trace_report.py"))
    trace_report = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(trace_report)
    return trace_report


def test_events_on_writes_the_same_schema_and_trace_report_reads_the_new_names(toy_job):
    trace_report = _trace_report()
    loaded = trace_report.load_events(os.path.join(toy_job["wd"], "log"))
    assert not loaded["bad_lines"] and not loaded["torn_tails"]
    for r in loaded["events"]:
        assert set(r) >= {"run", "pid", "epoch", "ev", "ph", "mono", "wall"}
    spans, unclosed = trace_report.pair_spans(loaded["events"])
    assert not unclosed
    by_name = {}
    for sp in spans:
        by_name.setdefault(sp["ev"], []).append(sp)
    # every phase of the record is a span of the log, with the same calls
    # unless one span stood for many units (calls=N round a loop)
    ph = toy_job["record"]["phases"]
    assert set(ph) == set(by_name), set(ph) ^ set(by_name)
    assert len(by_name["secondary/wait"]) == ph["secondary/wait"]["calls"]
    assert by_name["stage:secondary"][0]["dur"] == pytest.approx(
        ph["stage:secondary"]["seconds"], abs=5e-3)
    # the two instants this PR removed are gone; the report still renders
    assert not {"stage_open", "stage_close"} & {r["ev"] for r in loaded["events"]}
    text = trace_report.text_report(loaded["events"], toy_job["record"])
    assert "stage:secondary" in text and "stage:primary_compare" in text



def test_primary_pack_says_what_it_ranked(toy_job):
    """ISSUE 28: the `primary/pack` span round `pack_sketches` carries
    `hashes=`, and the record's `primary_pack` counts the genomes, the
    hashes and the distinct ids they became: NumPy's own count. ISSUE 40:
    both say which path ranked them and on how many threads (the toy job
    runs at the wrappers' `processes` of 1)."""
    from drep_tpu import native
    from drep_tpu.ingest import _load
    from drep_tpu.workdir import WorkDirectory

    gs = _load(WorkDirectory(toy_job["wd"]), 21, 1000, 200)
    flat = np.concatenate([b[:1000] for b in gs.bottom])
    is_native = native.get_library() is not None
    want = {"calls": 1, "native_calls": int(is_native), "threads": 1, "genomes": len(gs.names),
            "hashes": len(flat), "distinct_ids": len(np.unique(flat))}
    assert want["genomes"] == 5 and want["distinct_ids"] < want["hashes"]  # hashes are shared
    assert toy_job["record"]["primary_pack"] == want
    trace_report = _trace_report()
    spans, _ = trace_report.pair_spans(
        trace_report.load_events(os.path.join(toy_job["wd"], "log"))["events"])
    with_hashes = [sp["args"] for sp in spans if sp["ev"] == "primary/pack" and "hashes" in sp["args"]]
    assert with_hashes == [
        {"hashes": want["hashes"], "path": "native" if is_native else "numpy", "workers": 1}]


def test_a_compare_from_a_planted_cache_reads_it_back_with_no_span_inside_the_stage(tmp_path, monkeypatch):
    """ISSUE 43: `load_sketches_s` reads the SELF seconds of
    `stage:ingest_or_cache`, so the cache's reader (and its threads) open no
    span there; what it did is the record's `sketch_cache_read`."""
    from benchmark import cells
    from drep_tpu import controller, workdir
    from drep_tpu.utils import hosttools

    monkeypatch.setattr(workdir, "ARRAY_PART_BYTES", 1 << 16)
    monkeypatch.setattr(hosttools, "usable_cores", lambda: 8)
    gen = cells.load_module(os.path.join(REPO, "benchmark", "generators", "planted_sketches.py"))
    cfg = cells.read_json(os.path.join(REPO, "benchmark", "configs", "mags_5k.json"))
    cfg["data"].update({"n": 48, "s_scaled": 2000})
    wd = gen.prepare(cfg, 43, str(tmp_path))["workdir"]
    controller.main(["compare", wd, "--skip_plots", "--events", "on", "-p", "6"])
    telemetry.configure()
    with open(os.path.join(wd, "log", "perf_counters.json")) as f:
        rec = json.load(f)
    arrs = workdir.WorkDirectory(wd).get_arrays("sketches")
    read = rec["sketch_cache_read"]
    assert read["members"] == 2 and read["parts"] > 2  # bottom and scaled, in parts of 64 KiB
    assert read["direct_parts"] + read["fallback_parts"] == read["parts"] and read["fallback_parts"] == 0
    assert read["bytes"] == arrs["bottom"].nbytes + arrs["scaled"].nbytes
    assert read["threads"] == 6 and 0 < read["seconds"]
    ph = rec["phases"]
    stage = ph["stage:ingest_or_cache"]
    assert stage["calls"] == 1 and stage["self_seconds"] == stage["seconds"] >= read["seconds"]
    assert all(p["thread"] == "main" for p in ph.values())  # the reader's threads open none
    trace_report = _trace_report()
    spans, unclosed = trace_report.pair_spans(trace_report.load_events(os.path.join(wd, "log"))["events"])
    assert not unclosed
    (lo, hi), = [(sp["begin"], sp["end"]) for sp in spans if sp["ev"] == "stage:ingest_or_cache"]
    assert [sp["ev"] for sp in spans if lo <= sp["begin"] and sp["end"] <= hi] == ["stage:ingest_or_cache"]
    # the other counters are what they were
    assert rec["primary_pack"]["calls"] == 1 and rec["primary_pack"]["genomes"] == 48
    assert "ingest" not in rec and _main_self_sum(ph) == pytest.approx(ph["job"]["seconds"], rel=0.01)


def test_primary_pack_counter_sums_its_calls_and_counts_no_padding(monkeypatch):
    from drep_tpu import native
    from drep_tpu.cluster.engines import pack_primary
    from drep_tpu.ops import minhash
    from drep_tpu.utils.profiling import counters

    monkeypatch.setattr(minhash, "_usable_cores", lambda: 4)
    monkeypatch.setattr(minhash, "RANK_HASHES_PER_THREAD", 1)
    is_native = native.get_library() is not None
    counters.reset()
    assert "primary_pack" not in counters.report(device=False)
    u = lambda *v: np.array(v, np.uint64)  # noqa: E731
    pack_primary([], [], 4)
    pack_primary([u(), u()], ["a", "b"], 4, 6)  # rows of padding alone: no id, nothing to rank
    pack_primary([u(3, 5, 7, 9, 11), u(), u(5, 2**64 - 1)], ["a", "b", "c"], 4, 3)  # 11 is cut
    pack_primary([u(1, 2)], ["d"], 4, 2)
    # the calls that ranked hashes went through the kernel; `threads` is the widest of them
    assert counters.report(device=False)["primary_pack"] == {
        "calls": 4, "native_calls": 2 if is_native else 0, "threads": 3 if is_native else 1,
        "genomes": 6, "hashes": 8, "distinct_ids": 7}
    monkeypatch.setenv("DREP_TPU_NO_NATIVE", "1")
    pack_primary([u(1, 2)], ["d"], 4, 6)
    assert counters.report(device=False)["primary_pack"]["native_calls"] == (2 if is_native else 0)
    counters.reset()
    assert "primary_pack" not in counters.report(device=False)


@pytest.mark.parametrize("processes", [1, 6])
@pytest.mark.parametrize("route", ["dense", "streaming", "multiround"])
def test_the_controller_hands_its_processes_down_to_the_primary_pack(route, processes, monkeypatch):
    """ISSUE 40: every primary route packs at the job's `-p`, capped by the
    cores: the record's `threads` and the span's `workers=` say so."""
    import pandas as pd

    from drep_tpu import native
    from drep_tpu.cluster.controller import _fill_defaults, _primary_clusters
    from drep_tpu.ingest import GenomeSketches
    from drep_tpu.ops import minhash
    from drep_tpu.utils.profiling import counters

    if native.get_library() is None:
        pytest.skip("native library unavailable (no g++?)")
    monkeypatch.setattr(minhash, "_usable_cores", lambda: 8)
    monkeypatch.setattr(minhash, "RANK_HASHES_PER_THREAD", 16)  # 256 hashes and more a pack: six threads may start
    rng = np.random.default_rng(40)
    n, s = 24, 64
    names = [f"g{i}" for i in range(n)]
    bottom = [np.unique(rng.integers(0, 2**64, size=s, dtype=np.uint64)) for _ in names]
    gdb = pd.DataFrame({"genome": names, "length": 10**6, "N50": 50_000, "contigs": 10,
                        "n_kmers": np.arange(n) + 900_000})
    gs = GenomeSketches(names=names, gdb=gdb, bottom=bottom, scaled=bottom, k=21, sketch_size=s, scale=200)
    kw = _fill_defaults({"processes": processes, **{
        "dense": {}, "streaming": {"streaming_primary": True},
        "multiround": {"multiround_primary_clustering": True, "primary_chunksize": 10},
    }[route]})
    seen = []
    span = counters.span
    monkeypatch.setattr(counters, "span", lambda name, calls=1, **args: (
        seen.append(args) if name == "primary/pack" and "hashes" in args else None,
        span(name, calls, **args))[1])
    counters.reset()
    _primary_clusters(gs, pd.DataFrame({"genome": names, "location": names}), kw)
    booked = counters.report(device=False)["primary_pack"]
    counters.reset()
    assert booked["calls"] == booked["native_calls"] == len(seen) == (4 if route == "multiround" else 1)
    assert booked["threads"] == processes
    assert all(a["path"] == "native" and a["workers"] == processes and a["hashes"] > 0 for a in seen)


def test_the_dense_grid_assemble_says_what_it_transformed(monkeypatch):
    """ISSUE 48: on the Pallas route `primary/assemble` turns the wrapped
    grid's counts into distances a tile at a time and says so: `tiles=`
    the grid's tile pairs (an even t's twice-covered ones once), `cells=`
    the whole matrix once. `mash_distance_matrix` reads no Jaccard matrix,
    so it asks for none."""
    from drep_tpu.cluster.engines import mash_distance_matrix
    from drep_tpu.ops import minhash, pallas_mash
    from drep_tpu.utils.profiling import counters

    rng = np.random.default_rng(48)
    n, s = pallas_mash.TILE + 2, 64  # two tile rows: t = 2, the last wrapped column covered twice
    bottom = [np.unique(rng.integers(0, 2**62, size=s, dtype=np.uint64)) for _ in range(n)]
    bottom[3] = bottom[3][: s // 2]
    packed = minhash.pack_sketches(bottom, [f"g{i}" for i in range(n)], s)
    monkeypatch.setattr(pallas_mash, "pallas_mash_supported", lambda width: True)  # interpreted here
    returned, opened = [], []
    grid_call, span = pallas_mash.all_vs_all_mash_pallas, counters.span
    monkeypatch.setattr(pallas_mash, "all_vs_all_mash_pallas", lambda *a, **kw: (
        returned.append(grid_call(*a, **kw)), returned[-1])[1])
    monkeypatch.setattr(counters, "span", lambda name, calls=1, **args: (
        sp := span(name, calls, **args), opened.append(sp))[0])
    counters.reset()
    dist = mash_distance_matrix(packed, 21, mesh_shape=1)
    grid = counters.report(device=False)["stages"]["primary_compare"]
    counters.reset()
    assert [sp._args for sp in opened if sp.name == "primary/assemble"] == [{"tiles": 3, "cells": n * n}]
    assert (grid["tiles_computed"], grid["tiles_total"]) == (4, 4)  # the grid ran 2 x 2: one pair twice
    assert [jac for _dist, jac in returned] == [None]
    want, _ = minhash.all_vs_all_mash(packed, k=21, tile=64)
    np.testing.assert_allclose(dist, want, atol=1e-7)


_PROFILE_ON_A_POD = """
import os, sys
sys.path.insert(0, {repo!r})
os.environ["JAX_COORDINATOR_ADDRESS"] = "localhost:1"
import jax
from jax._src import xla_bridge
seen = []
jax.distributed.initialize = lambda **kw: seen.append(
    (xla_bridge.backends_are_initialized(), kw["coordinator_address"]))
from drep_tpu.workflows import compare_wrapper
compare_wrapper({wd!r}, {genomes!r}, skip_plots=True, SkipSecondary=True, profile={trace!r})
assert seen == [(False, None)], seen
assert xla_bridge.backends_are_initialized()
print("ok")
"""


def test_profile_opens_after_the_distributed_bring_up(tmp_path, genome_paths):
    """`jax.profiler.start_trace` initialises the backend, and
    `jax.distributed.initialize` must come before any backend use: a pod
    member run with `--profile` that opened its trace first would go on
    alone, silently (mesh.initialize_distributed swallows that error)."""
    script = _PROFILE_ON_A_POD.format(
        repo=REPO, wd=str(tmp_path / "wd"), genomes=list(genome_paths), trace=str(tmp_path / "tr"))
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("JAX_COORDINATOR_ADDRESS", None)
    out = subprocess.run([sys.executable, "-c", script], capture_output=True, text=True,
                         timeout=600, env=env)
    assert out.returncode == 0 and out.stdout.strip().endswith("ok"), out.stderr[-2000:]


def _job_lines(wd):
    with open(os.path.join(wd, "log", "events.p0.jsonl")) as f:
        return [r["ph"] for r in map(json.loads, f) if r["ev"] == "job"]


def test_a_second_job_in_the_process_writes_into_its_own_log(toy_job, tmp_path, genome_paths):
    """`job` opens after `telemetry.configure`: its B line is the crash
    evidence of THIS job, in this job's log, and a later job in the same
    process (library use) leaves the earlier log alone."""
    from drep_tpu.workflows import compare_wrapper

    assert _job_lines(toy_job["wd"]) == ["B", "E"]
    size = os.path.getsize(os.path.join(toy_job["wd"], "log", "events.p0.jsonl"))
    wd2 = str(tmp_path / "second")
    compare_wrapper(wd2, genome_paths, skip_plots=True, SkipSecondary=True, events="on")
    assert _job_lines(wd2) == ["B", "E"]
    # a third job with events off writes nowhere
    wd3 = str(tmp_path / "third")
    compare_wrapper(wd3, genome_paths, skip_plots=True, SkipSecondary=True)
    assert not glob.glob(os.path.join(wd3, "log", "events.*"))
    assert _job_lines(toy_job["wd"]) == ["B", "E"] and _job_lines(wd2) == ["B", "E"]
    assert os.path.getsize(os.path.join(toy_job["wd"], "log", "events.p0.jsonl")) == size


def test_a_failure_mid_batch_loses_one_cluster(tmp_path, genome_paths, monkeypatch):
    """Each cluster of a batch is saved right after its post-process, in a
    `secondary/checkpoint` span of its own: what a kill loses is the
    cluster in flight, not the batch."""
    from drep_tpu.cluster import controller
    from drep_tpu.workflows import compare_wrapper

    wd = str(tmp_path / "wd")
    real, seen = controller._secondary_postprocess, []

    def second_one_dies(gs, indices, pc, *rest):
        seen.append(pc)
        if len(seen) == 2:
            raise RuntimeError("killed mid-batch")
        return real(gs, indices, pc, *rest)

    monkeypatch.setattr(controller, "_secondary_postprocess", second_one_dies)
    with pytest.raises(RuntimeError, match="killed mid-batch"):
        compare_wrapper(wd, genome_paths, skip_plots=True)
    telemetry.configure()
    assert len(seen) == 2  # both clusters rode one batch
    saved = sorted(os.listdir(os.path.join(wd, "data", "secondary_checkpoints")))
    assert [f for f in saved if f.startswith("pc_")] == [f"pc_{seen[0]:06d}.npz"]
    monkeypatch.setattr(controller, "_secondary_postprocess", real)
    compare_wrapper(wd, genome_paths, skip_plots=True)
    with open(os.path.join(wd, "log", "perf_counters.json")) as f:
        rec = json.load(f)
    # the resume counts the lost cluster's pairs alone (fixture: {A,B,C}=3, {D,E}=1)
    assert rec["stages"]["secondary_compare"]["pairs"] in (1, 3)
    assert rec["stages"]["secondary_compare"]["pairs"] < 4
    # open, a look-up and a save a cluster, finish
    assert rec["phases"]["secondary/checkpoint"]["calls"] == 1 + 2 + 1 + 1


def test_streaming_stripes_hold_their_phases(tmp_path):
    """The streaming path's spans: a `stripe` is a container of the
    dispatch / wait / assemble / publish spans inside it."""
    from drep_tpu.ops.minhash import PAD_ID, PackedSketches
    from drep_tpu.parallel.streaming import streaming_mash_edges
    from drep_tpu.utils.profiling import counters

    rng = np.random.default_rng(0)
    n, s = 32, 32
    ids = np.full((n, s), PAD_ID, np.int32)
    for i in range(n):
        ids[i] = np.sort(rng.choice(4096, size=s, replace=False))
    packed = PackedSketches(ids=ids, counts=np.full(n, s, np.int32), names=[f"g{i}" for i in range(n)])
    counters.reset()
    with counters.span("job"):
        streaming_mash_edges(packed, k=21, cutoff=0.2, block=8, checkpoint_dir=str(tmp_path / "ck"))
        ph = counters.report()["phases"]
    stripes = ph["stripe"]["calls"]
    assert stripes == 4
    for name in ("primary/dispatch", "primary/wait"):
        assert ph[name]["calls"] == stripes, (name, ph[name])
    # the store's key and its open, then a shard a stripe
    assert ph["primary/publish"]["calls"] == 2 + stripes
    assert ph["primary/assemble"]["calls"] == stripes + 1  # each stripe's edges, then all
    assert ph["primary/put"]["calls"] == 1 and ph["primary/pack"]["calls"] >= 1
    inside = sum(ph[k]["seconds"] for k in ("primary/dispatch", "primary/wait", "primary/publish",
                                            "primary/put"))
    assert ph["stripe"]["self_seconds"] <= ph["stripe"]["seconds"] - inside + 0.05
    assert _main_self_sum(ph) == pytest.approx(ph["job"]["seconds"], rel=0.01)


# --- a toy job under greedy secondary clustering (ISSUE 34) ----------------


@pytest.fixture(scope="module")
def greedy_job(tmp_path_factory):
    """One `compare --greedy_secondary_clustering --streaming_primary` on a
    planted workdir of 70 genomes: a cluster of 40 for the engine, one of 6
    for the batched route, singletons; with the event log on."""
    from benchmark import cells
    from drep_tpu import controller

    gen = cells.load_module(os.path.join(REPO, "benchmark", "generators", "planted_release.py"))
    cfg = cells.read_json(os.path.join(REPO, "benchmark", "configs", "gtdb_release_6k.json"))
    cfg["data"].update({"n": 70, "s_scaled": 1900, "clusters": [
        {"size": 40, "count": 1, "groups": [28, 12]}, {"size": 6, "count": 1, "groups": [6]},
        {"size": 1, "count": 24, "groups": [1]}]})
    wd = gen.prepare(cfg, 34, str(tmp_path_factory.mktemp("spans_greedy")))["workdir"]
    controller.main(["compare", wd, "--greedy_secondary_clustering", "--streaming_primary",
                     "--skip_plots", "--events", "on"])
    telemetry.configure()
    with open(os.path.join(wd, "log", "perf_counters.json")) as f:
        record = json.load(f)
    trace_report = _trace_report()
    spans, unclosed = trace_report.pair_spans(
        trace_report.load_events(os.path.join(wd, "log"))["events"])
    assert not unclosed
    return {"record": record, "spans": spans}


def _inside(spans, name: str, stage: str) -> list[bool]:
    """For each span `name` of the log: does a span `stage` cover it?"""
    outer = [(sp["begin"], sp["end"]) for sp in spans if sp["ev"] == stage]
    return [any(lo <= sp["begin"] and sp["end"] <= hi for lo, hi in outer)
            for sp in spans if sp["ev"] == name]


@pytest.mark.parametrize("name,stages,calls", [
    # the engine's pack; the batched route's cluster-local pack and its row pad
    ("secondary/pack", ("stage:secondary_compare",), 3),
    ("secondary/greedy_layout", ("stage:secondary_compare",), 1),  # a block (off a TPU: the pad)
    ("secondary/greedy_wait", ("stage:secondary_compare",), 1),
    # a block and the cluster's rows in the engine, then the batched route's one cluster
    ("secondary/greedy_assign", ("stage:secondary_compare", "stage:secondary_postprocess"), 3),
    # ISSUE 52, what `stage:secondary_compare` kept for itself: a block's host pad, and the
    # cluster's vocabulary extent for the counter's entry
    ("secondary/greedy_pad", ("stage:secondary_compare",), 1),
    ("secondary/greedy_extent", ("stage:secondary_compare",), 1),
])
def test_the_greedy_spans_nest_in_their_stages(greedy_job, name, stages, calls):
    ph, spans = greedy_job["record"]["phases"], greedy_job["spans"]
    assert ph[name]["calls"] == calls == sum(sp["ev"] == name for sp in spans)
    assert ph[name]["thread"] == "main" and ph[name]["seconds"] > 0
    covered = [_inside(spans, name, stage) for stage in stages]
    assert all(any(by_stage) for by_stage in zip(*covered)), (name, covered)
    assert all(any(c) for c in covered)  # each stage named holds at least one
    # all inside the secondary stage, and the sum still closes on the root
    assert all(_inside(spans, name, "stage:secondary"))
    assert _main_self_sum(ph) == pytest.approx(ph["job"]["seconds"], rel=0.01)


def test_the_greedy_job_s_record_names_its_routes_and_its_wait_span_carries_the_shape(greedy_job):
    rec, spans = greedy_job["record"], greedy_job["spans"]
    assert rec["secondary_paths"] == {"greedy_gather": 1, "one_shot_clusterlocal": 1}
    (call,) = rec["secondary_greedy_calls"]
    assert (call["rows"], call["blocks"], call["reps"], call["all_pairs"]) == (40, 1, 2, 780)
    assert rec["secondary_greedy_batched"] == {"clusters": 1, "rows": 6, "compared_pairs": 5,
                                               "all_pairs": 15}
    assert rec["stages"]["secondary_compare"]["pairs"] == call["compared_pairs"] + 5
    (wait,) = [sp for sp in spans if sp["ev"] == "secondary/greedy_wait"]
    assert wait["args"] == {"rows": 40, "reps": 0, "rep_pad": call["rep_tile"], "chunks": 0,
                            "devices": 1}
    assert call["mesh_devices"] == 1 and "secondary/greedy_put" not in rec["phases"]  # the gather route
    assert "secondary/wait" in rec["phases"]  # the batched route's one-shot call keeps its own name


# --- the same toy job, stopped with notice and run again (ISSUE 47) ----------


@pytest.fixture(scope="module")
def stopped_job(tmp_path_factory):
    """`greedy_job`'s collection again: a first attempt that drains after the
    engine cluster's checkpoint (the shard store whole, one of two
    checkpoints published), then the same command to the end with the event
    log on. Both attempts' records and the second's spans."""
    from benchmark import cells
    from drep_tpu import controller
    from drep_tpu.parallel import faulttol
    from drep_tpu.utils import faults

    gen = cells.load_module(os.path.join(REPO, "benchmark", "generators", "planted_release.py"))
    cfg = cells.read_json(os.path.join(REPO, "benchmark", "configs", "gtdb_release_6k.json"))
    cfg["data"].update({"n": 70, "s_scaled": 1900, "clusters": [
        {"size": 40, "count": 1, "groups": [28, 12]}, {"size": 6, "count": 1, "groups": [6]},
        {"size": 1, "count": 24, "groups": [1]}]})
    wd = gen.prepare(cfg, 34, str(tmp_path_factory.mktemp("spans_stopped")))["workdir"]
    argv = ["compare", wd, "--greedy_secondary_clustering", "--streaming_primary", "--skip_plots",
            "--streaming_block", "32", "--events", "on"]
    records = []
    for fault in ("secondary_checkpoint:drain", None):
        faults.configure(fault)
        try:
            controller.main(argv)
        except SystemExit as e:
            assert e.code == 0 and fault
        finally:
            faulttol.clear_drain()
            faults.configure(None)
            telemetry.configure()
        with open(os.path.join(wd, "log", "perf_counters.json")) as f:
            records.append(json.load(f))
    trace_report = _trace_report()
    spans, _unclosed = trace_report.pair_spans(
        trace_report.load_events(os.path.join(wd, "log"))["events"])
    return {"drained": records[0], "resumed": records[1], "spans": spans}


def test_a_fresh_job_books_no_resume_load_span_and_a_drained_one_writes_its_record(greedy_job,
                                                                                  stopped_job):
    for fresh in (greedy_job["record"], stopped_job["drained"]):
        assert not [name for name in fresh["phases"] if name.endswith("resume_load")]
        assert "stripes_resumed" not in fresh["resume"] and "clusters_resumed" not in fresh["resume"]
    drained = stopped_job["drained"]
    assert drained["drain"] == {**drained["drain"], "stage": "secondary", "clusters_published": 1}
    # the attempt's spans and counters up to the boundary, the root span as far as it came
    ph = drained["phases"]
    # the store's open, the engine cluster's look-up and its save: the boundary
    assert ph["stage:secondary"]["calls"] == 1 and ph["secondary/checkpoint"]["calls"] == 3
    assert "stage:evaluate" not in ph and "stage:assembly_io" not in ph
    assert _main_self_sum(ph) == pytest.approx(ph["job"]["seconds"], rel=0.01)
    assert drained["resume"] == {"tiles_computed": 6, "clusters_computed": 1}
    assert "secondary_greedy_calls" in drained and "secondary_greedy_batched" not in drained


def test_the_resume_load_spans_lie_inside_the_spans_that_held_their_time(stopped_job):
    ph, spans = stopped_job["resumed"]["phases"], stopped_job["spans"]
    # three stripes' shards read back inside the primary's stage, no tile dispatched
    assert ph["primary/resume_load"]["calls"] == 3 and "primary/wait" not in ph and "stripe" not in ph
    assert all(_inside(spans, "primary/resume_load", "stage:primary_compare"))
    # the engine cluster's checkpoint read back inside a look-up's `secondary/checkpoint`
    assert ph["secondary/resume_load"]["calls"] == 1
    assert _inside(spans, "secondary/resume_load", "secondary/checkpoint") == [True]
    assert ph["secondary/checkpoint"]["seconds"] >= ph["secondary/resume_load"]["seconds"]
    assert ph["secondary/checkpoint"]["self_seconds"] <= (
        ph["secondary/checkpoint"]["seconds"] - ph["secondary/resume_load"]["seconds"] + 1e-3)
    loads = [sp for sp in spans if sp["ev"].endswith("resume_load")]
    assert all(sp["args"].get("bytes", 0) > 0 for sp in loads)
    assert stopped_job["resumed"]["resume"] == {
        **stopped_job["resumed"]["resume"], "stripes_resumed": 3, "tiles_resumed": 6,
        "tiles_computed": 0, "clusters_resumed": 1, "clusters_computed": 1}
    assert _main_self_sum(ph) == pytest.approx(ph["job"]["seconds"], rel=0.01)


def test_greedy_calls_list_a_cluster_each_and_stay_bounded():
    from drep_tpu.utils import profiling

    c = Counters()
    booked = dict(rows=300, blocks=3, blocks_without_reps=1, block_rows=128, reps=5, rep_tile=512,
                  rep_rows_shipped=256,
                  rep_rows_real=9, v_chunk=262144, chunks=2, extent=400_000, widths=49152,
                  hashes=6 * 10**6, id_slots=10**7, device_calls=12, compared_pairs=1200,
                  mesh_devices=4, rep_tiles_replicated=1, partial_tile_ships=2,
                  block_bytes=3 * 10**8, rep_bytes=10**8)
    for i in range(profiling.SECONDARY_SHAPES_MAX + 5):
        # one of the clusters past the cap fell to one device: the summed entry has to say so
        fell = i == profiling.SECONDARY_SHAPES_MAX + 2
        c.add_greedy_call(**{**booked, "rows": 300 + i, "mesh_devices": 1 if fell else 4})
    c.add_greedy_batched(rows=4, compared_pairs=3)
    c.add_greedy_batched(rows=2, compared_pairs=1)
    rep = c.report(device=False)
    calls = rep["secondary_greedy_calls"]
    assert len(calls) == profiling.SECONDARY_SHAPES_MAX
    assert calls[0] == {**booked, "clusters": 1, "all_pairs": 300 * 299 // 2, "bytes_shipped": 4 * 10**7}
    assert [e["rows"] for e in calls[:3]] == [300, 301, 302]  # in the order met
    assert calls[-1]["clusters"] == 6 and calls[-1]["compared_pairs"] == 6 * 1200  # the rest, summed
    assert calls[-1]["block_bytes"] == 6 * 3 * 10**8 and calls[-1]["mesh_devices"] == 1  # the fewest
    assert calls[-2]["mesh_devices"] == 4
    assert sum(e["clusters"] for e in calls) == profiling.SECONDARY_SHAPES_MAX + 5
    assert rep["secondary_greedy_batched"] == {"clusters": 2, "rows": 6, "compared_pairs": 4,
                                               "all_pairs": 7}
    c.reset()
    rep = c.report(device=False)
    assert "secondary_greedy_calls" not in rep and "secondary_greedy_batched" not in rep


@pytest.mark.parametrize("devices", [1, 4])
def test_greedy_put_and_greedy_wait_partition_what_greedy_wait_covered(monkeypatch, devices):
    """ISSUE 42: on the engine's matmul route every put of chunk tensors is a
    `secondary/greedy_put` span inside the span that used to hold it unnamed,
    off a mesh and on one: the block's inside `greedy_wait`, a mesh's filled
    representative tile inside `greedy_layout`. So the wait's seconds are what
    they were, its self seconds and the puts inside it partition them, and
    the three self-second readers still add up to the engine's spans."""
    import pandas as pd

    from drep_tpu.cluster.greedy import greedy_secondary_cluster
    from drep_tpu.ingest import GenomeSketches
    from drep_tpu.utils.profiling import counters

    # 640 genomes a fifth alike: each founds its own group, so on four devices (blocks of 512)
    # the representatives fill a tile, which a mesh replicates inside `greedy_layout`
    rng = np.random.default_rng(42)
    core = rng.integers(0, 1 << 40, size=60, dtype=np.uint64)
    scaled = [np.unique(np.concatenate([core, rng.integers(1 << 41, 1 << 62, size=240, dtype=np.uint64)]))
              for _ in range(640)]
    names = [f"g{i}" for i in range(640)]
    gs = GenomeSketches(names=names, gdb=pd.DataFrame({"genome": names, "n_kmers": range(10_640, 10_000, -1)}),
                        bottom=[s[:100] for s in scaled], scaled=scaled, k=21, sketch_size=100, scale=200)
    monkeypatch.setenv("DREP_TPU_GREEDY_MATMUL", "1")
    monkeypatch.setenv("DREP_TPU_EVENTS", "off")
    counters.reset()
    greedy_secondary_cluster(gs, None, list(range(640)), pc=1,
                             kw={"S_ani": 0.95, "cov_thresh": 0.1, "mesh_shape": devices})
    ph = counters.report(device=False)["phases"]
    put, wait, layout = (ph["secondary/" + n] for n in ("greedy_put", "greedy_wait", "greedy_layout"))
    assert put["self_seconds"] == pytest.approx(put["seconds"], abs=2e-4)  # a put holds no span
    inside_wait = wait["seconds"] - wait["self_seconds"]
    inside_layout = layout["seconds"] - layout["self_seconds"]
    # the record rounds each number to a tenth of a millisecond
    assert inside_wait > 0 and inside_wait + inside_layout == pytest.approx(put["seconds"], abs=5e-4)
    blocks = -(-640 // (128 * devices))
    assert wait["calls"] == blocks  # still a span a block
    if devices == 1:
        assert abs(inside_layout) < 2e-4 and put["calls"] == blocks  # a block crosses once
    else:
        # the first block meets no representative: twice for itself, no tile (ISSUE 55); then
        # the filled tile, inside the layout, and the second block: for the tile, twice for itself
        assert inside_layout > 2e-4 and put["calls"] == 2 + 1 + 3



# --- stage:evaluate: the job's own columns, or the tables read back (ISSUE 35) ---


@pytest.fixture(scope="module")
def evaluate_jobs(tmp_path_factory, genome_paths):
    """A toy `compare`, the same command again on its work directory (the
    cluster stage skipped on resume), and a `dereplicate`. Of each: its
    record, its `warnings.txt`, every pair table opened while `stage:evaluate`
    was open, and a weak reference to whatever `WorkDirectory.hold` kept."""
    import builtins
    import weakref

    import pandas as pd

    from drep_tpu.utils.profiling import counters
    from drep_tpu.workdir import WorkDirectory
    from drep_tpu.workflows import compare_wrapper, dereplicate_wrapper

    opened: list[str] = []
    kept: list = []
    real_read, real_open, real_hold = pd.read_csv, builtins.open, WorkDirectory.hold

    def note(file) -> None:
        inside = any(sp.name == "stage:evaluate" for sp in counters._stack())
        if inside and isinstance(file, str) and os.path.basename(file) in ("Mdb.csv", "Ndb.csv"):
            opened.append(os.path.basename(file))

    def read_csv(file, *a, **k):
        note(file)
        return real_read(file, *a, **k)

    def open_(file, *a, **k):
        note(file)
        return real_open(file, *a, **k)

    def hold(self, name, columns):
        kept.append(weakref.ref(columns))
        real_hold(self, name, columns)

    def run(job, wd, **kwargs) -> dict:
        opened.clear()
        kept.clear()
        job(wd, genome_paths, skip_plots=True, **kwargs)
        with real_open(os.path.join(wd, "log", "perf_counters.json")) as f:
            record = json.load(f)
        with real_open(os.path.join(wd, "log", "warnings.txt"), "rb") as f:
            return {"wd": wd, "record": record, "warnings": f.read(), "opened": list(opened),
                    "kept": list(kept)}

    quality = str(tmp_path_factory.mktemp("evaluate_quality") / "q.csv")
    names = [os.path.basename(p) for p in genome_paths]
    pd.DataFrame({"genome": names, "completeness": [99.0, 90.0, 85.0, 95.0, 94.0],
                  "contamination": [0.5, 1.0, 2.0, 0.1, 0.2]}).to_csv(quality, index=False)
    wd = str(tmp_path_factory.mktemp("evaluate_wd"))
    mp = pytest.MonkeyPatch()
    mp.setattr(pd, "read_csv", read_csv)
    mp.setattr(builtins, "open", open_)
    mp.setattr(WorkDirectory, "hold", hold)
    try:
        return {
            "first": run(compare_wrapper, wd),
            "again": run(compare_wrapper, wd),
            "dereplicate": run(dereplicate_wrapper, str(tmp_path_factory.mktemp("evaluate_dwd")),
                               genomeInfo=quality),
        }
    finally:
        mp.undo()
        telemetry.configure()


def test_the_job_that_wrote_the_pair_tables_does_not_read_them_back(evaluate_jobs):
    import gc

    first = evaluate_jobs["first"]
    booked = first["record"]["evaluate"]
    assert booked["mdb"] == {"source": "job", "rows": 25} and booked["ndb"] == {"source": "job", "rows": 8}
    assert first["opened"] == []
    assert booked["warnings"] == {"primary": 4, "secondary": 0, "coverage": 2}
    assert booked["bytes"] == len(first["warnings"]) and first["warnings"].count(b"\n") == 6
    assert booked["distinct"] == 5 + 4 + 3 + 2  # names and values of the Primary lines, then of the Coverage lines
    ph = first["record"]["phases"]
    assert ph["evaluate/columns"]["calls"] == 2 and ph["evaluate/tables"]["calls"] == 1
    assert _main_self_sum(ph) == pytest.approx(ph["job"]["seconds"], rel=0.01)
    # what was handed over went with the stage: two tables held, neither alive
    gc.collect()
    assert len(first["kept"]) == 2 and all(ref() is None for ref in first["kept"])


def test_a_resumed_work_directory_reads_them_from_disk_and_writes_the_same_bytes(evaluate_jobs):
    first, again = evaluate_jobs["first"], evaluate_jobs["again"]
    booked = again["record"]["evaluate"]
    assert booked["mdb"] == {"source": "disk", "rows": 25} and booked["ndb"] == {"source": "disk", "rows": 8}
    assert set(again["opened"]) == {"Mdb.csv", "Ndb.csv"} and again["kept"] == []
    assert "evaluate/columns" not in again["record"]["phases"]
    assert again["warnings"] == first["warnings"] != b""
    assert {k: v for k, v in booked.items() if k not in ("mdb", "ndb")} \
        == {k: v for k, v in first["record"]["evaluate"].items() if k not in ("mdb", "ndb")}


def test_a_dereplicate_job_writes_the_widb_and_the_warnings_of_the_parents_spelling(evaluate_jobs):
    from test_evaluate_bytes import parent_stage

    from drep_tpu.evaluate import make_widb
    from drep_tpu.workdir import WorkDirectory

    job = evaluate_jobs["dereplicate"]
    assert job["record"]["evaluate"]["mdb"]["source"] == job["record"]["evaluate"]["ndb"]["source"] == "job"
    assert job["opened"] == [] and all(ref() is None for ref in job["kept"])
    wd = WorkDirectory(job["wd"])
    assert job["warnings"] == parent_stage(wd) != b""
    widb = make_widb(wd.get_db("Wdb"), wd.get_db("Cdb"), wd.get_db("genomeInformation"), wd.get_db("genomeInfo"))
    with open(os.path.join(job["wd"], "data_tables", "Widb.csv"), "rb") as f:
        assert f.read() == widb.to_csv(index=False).encode()


def test_the_shard_flush_span_carries_its_genomes_and_the_bytes_it_published(tmp_path, genome_paths, monkeypatch):
    """ISSUE 53: `ingest/shard_flush` notes the published shard's size, so
    an event log shows the flush's MB/s; the shard itself is gone by the
    job's end (the cache supersedes it), so the writer is watched."""
    import drep_tpu.ingest as ingest_mod
    from drep_tpu.workflows import compare_wrapper

    published = []
    real = ingest_mod._save_sketch_shard

    def watched(path, batch):
        real(path, batch)
        published.append((len(batch), os.path.getsize(path)))

    monkeypatch.setattr(ingest_mod, "_save_sketch_shard", watched)
    monkeypatch.setattr(ingest_mod, "INGEST_SHARD", 2)  # 5 genomes: two full shards and the forced one
    wd = str(tmp_path / "wd")
    compare_wrapper(wd, genome_paths, skip_plots=True, events="on")
    telemetry.configure()
    trace_report = _trace_report()
    spans, _ = trace_report.pair_spans(trace_report.load_events(os.path.join(wd, "log"))["events"])
    flushes = [sp["args"] for sp in spans if sp["ev"] == "ingest/shard_flush"]
    assert [(a["genomes"], a["bytes"]) for a in flushes] == published
    assert [n for n, _ in published] == [2, 2, 1] and all(size > 0 for _, size in published)


def test_the_evaluate_spans_carry_their_source_and_what_they_wrote(tmp_path, genome_paths):
    from drep_tpu.workflows import compare_wrapper

    trace_report = _trace_report()
    wd = str(tmp_path / "wd")
    for source in ("job", "disk"):
        compare_wrapper(wd, genome_paths, skip_plots=True, events="on")
        telemetry.configure()
        spans, _ = trace_report.pair_spans(trace_report.load_events(os.path.join(wd, "log"))["events"])
        last = {sp["ev"]: sp for sp in spans}  # the second job's spans follow the first's in the log
        assert last["evaluate/tables"]["args"] == {"source": source}
        size = os.path.getsize(os.path.join(wd, "log", "warnings.txt"))
        assert last["evaluate/warnings"]["args"] == {"winners": 5, "lines": 6, "bytes": size}
