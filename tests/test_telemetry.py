"""Structured event tracing contract (ISSUE 10, utils/telemetry.py):
zero files when off, valid JSONL always — even after SIGKILL mid-run
(torn final line only), run id constant across a resume, epoch stamped
on every line."""

import json
import os
import signal
import subprocess
import sys
import time

import pytest

from drep_tpu.utils import telemetry
from drep_tpu.utils.profiling import counters

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture(autouse=True)
def _reset_telemetry():
    yield
    telemetry.configure()  # disabled, no sink — leave no state behind


def _lines(path):
    with open(path, "rb") as f:
        raw = f.read()
    body, _, tail = raw.rpartition(b"\n")
    return (
        [json.loads(x) for x in body.split(b"\n") if x.strip()],
        tail,
    )


def test_off_is_the_default_and_writes_nothing(tmp_path, monkeypatch):
    monkeypatch.delenv(telemetry.EVENTS_ENV, raising=False)
    assert telemetry.configure(log_dir=str(tmp_path)) is False
    telemetry.event("x", a=1)
    with telemetry.Span("s", {"b": 2}):
        pass
    # the program's spans come through the front door: off writes nothing there either
    with counters.span("s", b=2):
        pass
    telemetry.close()
    assert os.listdir(tmp_path) == [], "events off must create ZERO files"


def test_env_gate_and_explicit_flag_precedence(tmp_path, monkeypatch):
    monkeypatch.setenv(telemetry.EVENTS_ENV, "on")
    assert telemetry.resolve_enabled(None) is True
    assert telemetry.resolve_enabled("off") is False  # explicit flag wins
    monkeypatch.delenv(telemetry.EVENTS_ENV)
    assert telemetry.resolve_enabled(None) is False
    assert telemetry.resolve_enabled("on") is True
    # enabled without a log dir stays off (no sink to write to)
    assert telemetry.configure(log_dir=None, enabled=True) is False


def test_events_are_valid_jsonl_with_core_keys(tmp_path):
    telemetry.configure(log_dir=str(tmp_path), enabled=True, pid=3)
    telemetry.set_epoch(2)
    telemetry.event("fault", kind="retries", n=1)
    with counters.span("stripe", bi=7, epoch=2):
        pass
    telemetry.close()
    lines, tail = _lines(tmp_path / "events.p3.jsonl")
    assert tail == b""  # clean close: no torn tail
    assert [r["ev"] for r in lines] == ["fault", "stripe", "stripe"]
    assert [r["ph"] for r in lines] == ["i", "B", "E"]
    for r in lines:
        # the pinned schema: run/pid/epoch + both clocks on every line
        assert set(r) >= {"run", "pid", "epoch", "ev", "ph", "mono", "wall"}
        assert r["pid"] == 3
        assert r["epoch"] == 2
    assert lines[2]["args"]["dur"] >= 0
    assert len({r["run"] for r in lines}) == 1


@pytest.mark.parametrize("enabled", [True, False], ids=["events_on", "events_off"])
def test_a_span_s_closing_line_carries_what_the_host_spent_inside_it(tmp_path, enabled):
    """ISSUE 52: the span's whole deltas ride on its E line as args (the
    path of `_Span.note`), those that moved; with events off no file exists
    and the record holds the same numbers."""
    import gc
    import hashlib

    from drep_tpu.utils.profiling import HOST_ARGS, Counters

    telemetry.configure(log_dir=str(tmp_path), enabled=enabled, pid=0)
    c = Counters()
    with c.span("job"):
        with c.span("tables_io", rows=3) as sp:
            cpu0 = time.thread_time()
            while time.thread_time() - cpu0 < 0.02:  # by its own CPU: the sandbox's cores come and go
                hashlib.sha1(bytes(1 << 16)).digest()
            gc.collect()
            sp.note(bytes=7)
        with c.span("quick"):
            pass  # shorter than a read lasts: nothing moved, nothing rides
    telemetry.close()
    booked = c.report(device=False)["phases"]["tables_io"]
    assert booked["cpu_s"] >= 0.015 and booked["gc_collections"] >= 1
    if not enabled:
        assert os.listdir(tmp_path) == [], "events off must create ZERO files"
        return
    recs, tail = _lines(tmp_path / "events.p0.jsonl")
    assert tail == b""
    ends = {r["ev"]: r["args"] for r in recs if r["ph"] == "E"}
    begins = {r["ev"]: r.get("args") for r in recs if r["ph"] == "B"}
    assert begins["tables_io"] == {"rows": 3}  # the B line is the entry's
    closing = ends["tables_io"]
    assert closing["rows"] == 3 and closing["bytes"] == 7 and closing["dur"] >= 0.02
    assert closing["cpu_s"] == pytest.approx(booked["cpu_s"], abs=1e-3)
    assert closing["thread_cpu_s"] >= 0.015 and closing["gc_collections"] == booked["gc_collections"]
    assert closing["gc_s"] == pytest.approx(booked["gc_s"], abs=1e-3)
    assert set(closing) - {"rows", "bytes", "dur"} <= set(HOST_ARGS)
    assert not set(ends["quick"]) & set(HOST_ARGS)


def test_run_id_constant_across_resume(tmp_path):
    telemetry.configure(log_dir=str(tmp_path), enabled=True, pid=0)
    telemetry.event("first")
    telemetry.close()
    # a RESUME is a fresh process against the same workdir: reconfigure
    # from scratch (new in-memory state) and the persisted run id holds
    telemetry.configure(log_dir=str(tmp_path), enabled=True, pid=0)
    telemetry.event("resumed")
    telemetry.close()
    lines, _ = _lines(tmp_path / "events.p0.jsonl")
    assert len(lines) == 2
    assert lines[0]["run"] == lines[1]["run"]
    with open(tmp_path / telemetry.RUN_ID_NAME) as f:
        assert f.read().strip() == lines[0]["run"]


_KILL_SCRIPT = """
import os, sys
sys.path.insert(0, {repo!r})
from drep_tpu.utils import telemetry
telemetry.configure(log_dir={log!r}, enabled=True, pid=0)
i = 0
while True:
    with telemetry.Span("stripe", {{"bi": i}}):
        telemetry.event("fault", kind="retries", n=1, pad="x" * 64)
    i += 1
"""


def test_sigkill_mid_run_leaves_valid_jsonl(tmp_path):
    """The crash-safety half of the contract: a writer SIGKILLed mid-loop
    leaves a log whose every COMPLETE line parses — at most the final
    line is torn, which readers (trace_report, scrub_store) classify as
    expected crash evidence."""
    log = str(tmp_path)
    proc = subprocess.Popen(
        [sys.executable, "-c", _KILL_SCRIPT.format(repo=REPO, log=log)],
        stdout=subprocess.DEVNULL, stderr=subprocess.DEVNULL,
    )
    path = tmp_path / "events.p0.jsonl"
    deadline = time.time() + 60
    while time.time() < deadline:
        if path.exists() and path.stat().st_size > 20_000:
            break
        time.sleep(0.02)
    os.kill(proc.pid, signal.SIGKILL)
    proc.wait()
    assert path.exists() and path.stat().st_size > 20_000, "writer never got going"
    lines, _tail = _lines(path)  # raises if any complete line is torn
    assert len(lines) > 50
    evs = {r["ev"] for r in lines}
    assert evs == {"stripe", "fault"}
    # unclosed-span crash evidence: the report surfaces what was in
    # flight when the process died
    sys.path.insert(0, os.path.join(REPO, "tools"))
    import importlib.util

    spec = importlib.util.spec_from_file_location(
        "trace_report", os.path.join(REPO, "tools", "trace_report.py")
    )
    tr = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(tr)
    loaded = tr.load_events(log)
    assert not loaded["bad_lines"], loaded["bad_lines"]
    spans, unclosed = tr.pair_spans(loaded["events"])
    assert len(spans) > 25
    assert len(unclosed) <= 1  # at most the span open at the kill


def test_scrubber_validates_event_logs(tmp_path):
    """tools/scrub_store.py knows the new family: a clean log verifies, a
    torn FINAL line is its own non-damage class, a torn MID-FILE line is
    damage, and metrics.prom is skipped."""
    import importlib.util

    spec = importlib.util.spec_from_file_location(
        "scrub_store", os.path.join(REPO, "tools", "scrub_store.py")
    )
    ss = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(ss)

    telemetry.configure(log_dir=str(tmp_path), enabled=True, pid=1)
    for i in range(5):
        telemetry.event("fault", kind="retries", n=i)
    telemetry.close()
    (tmp_path / "metrics.prom").write_text("drep_tpu_gauge 1\n")
    rep = ss.scrub([str(tmp_path)])
    assert not rep["damaged"] and not rep["torn_tails"]
    assert rep["verified"] >= 1  # the event log counted as verified

    # torn tail: crash evidence, not damage
    path = tmp_path / "events.p1.jsonl"
    with open(path, "ab") as f:
        f.write(b'{"run":"x","pid":1,"ev":"fault","ph":"i"')  # no newline
    rep = ss.scrub([str(tmp_path)])
    assert not rep["damaged"]
    assert rep["torn_tails"] == [str(path)]

    # mid-file rot: damage
    raw = path.read_bytes().split(b"\n")
    raw[1] = raw[1][: len(raw[1]) // 2]
    path.write_bytes(b"\n".join(raw))
    rep = ss.scrub([str(tmp_path)])
    assert rep["damaged"] and rep["damaged"][0][0] == str(path)


def test_set_pid_rehomes_the_stream(tmp_path):
    """The JOIN path's re-home: a joiner configures as pid 0 and learns
    its admitted id later — set_pid must split the stream so the two
    processes' logs never interleave (run id stays shared)."""
    telemetry.configure(log_dir=str(tmp_path), enabled=True, pid=0)
    telemetry.event("before")
    telemetry.set_pid(3)
    telemetry.event("after")
    telemetry.close()
    p0, _ = _lines(tmp_path / "events.p0.jsonl")
    p3, _ = _lines(tmp_path / "events.p3.jsonl")
    assert [r["ev"] for r in p0] == ["before"]
    assert [r["ev"] for r in p3] == ["after"] and p3[0]["pid"] == 3
    assert p0[0]["run"] == p3[0]["run"]


def test_unwritable_log_dir_disables_instead_of_crashing(tmp_path):
    blocked = tmp_path / "blocked"
    blocked.write_text("a file, not a dir")
    assert telemetry.configure(log_dir=str(blocked / "log"), enabled=True)
    telemetry.event("x")  # first emit discovers the unwritable sink
    assert telemetry.enabled() is False  # degraded to off, never crashed
    telemetry.event("y")  # and stays a no-op
