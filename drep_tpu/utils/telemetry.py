"""Structured event tracing: durable, crash-safe, append-only JSONL logs.

The reference dRep pipeline has no tracing at all (wall-time logging and a
comparison-count ETA — SURVEY.md §5.1), and until ISSUE 10 this rebuild
reported only end-of-run TOTALS (utils/profiling.py perf_counters.json):
when a chaos cell or a real pod run goes sideways, the ORDER and TIMING of
events — which stripe stalled, whose heartbeat went stale first, how long
the re-deal took — was unrecoverable. This module is the forensic record:

- one append-only file per process, ``<wd>/log/events.p<N>.jsonl``, one
  JSON object per line: ``{"run", "pid", "epoch", "ev", "ph", "mono",
  "wall", "args"?}``. ``run`` is a workdir-stable run id (persisted in
  ``events.runid`` beside the logs, so a RESUME keeps the same id and the
  merged timeline spans kills); ``epoch`` is the elastic-pod ownership
  epoch current when the line was written (profiling.note_epoch keeps it
  fresh); ``mono``/``wall`` are ``time.monotonic()``/``time.time()``
  seconds — in-process durations come from ``mono``, cross-process
  ordering from ``wall`` (pod members share a host/fleet clock).
- **spans** (``ph`` "B" at enter, "E" at exit with a ``dur`` arg): this
  log is one of the three sinks of the program's one span front door,
  ``profiling.Counters.span`` (the job's record and the profiler's host
  plane are the others), so every span of the program is here: the
  ``job`` root, the stages, streaming stripes, dense-ring steps, per-block
  recovery, and the primary/* and secondary/* phases inside them. A "B"
  with no matching "E" IS the crash evidence — what was in flight when
  the process died.
- **point events** (``ph`` "i") mark faults and protocol verdicts: every
  ``Counters.add_fault`` kind (retries, watchdog trips, quarantines, CPU
  fallbacks, io retries/heals, injected faults), every epoch bump with
  its reason (death/drain/join), heartbeat death verdicts, drain
  announce/adopt, join admit/adopt, done-notes, shard publishes, index
  generation commits, and every program built or loaded (``compile``:
  ``fun_name``, ``dur``, ``cache`` — one a program, none a call;
  profiling.Counters.on_compile_duration).

**Crash safety**: each line is written+flushed whole; a SIGKILL can tear
at most the final line, which readers (tools/trace_report.py,
tools/scrub_store.py) treat as expected crash evidence, never damage.

**Zero overhead when off** (the default): every emit path starts with one
falsy dict lookup (a :class:`Span` gates each of its two lines so), and
no file — not even an empty one — is ever created. Pinned by
tests/test_perf_guards.py by count (zero files and no sink call with events
off; a fixed number of lines a stripe on the 528-tile warm checkpointed
pass with events ON, never one a tile).

Gating: ``--events {off,on}`` on the CLI, or ``DREP_TPU_EVENTS=on`` for
library/worker embeddings. ``configure()`` resolves the sink; without a
``log_dir`` tracing stays off regardless.

This module must stay importable without a JAX backend (the report tools
run host-side); jax is never imported here.
"""

from __future__ import annotations

import json
import os
import threading
import time
import uuid
from typing import Any

from drep_tpu.utils import envknobs

EVENTS_ENV = "DREP_TPU_EVENTS"
RUN_ID_NAME = "events.runid"


def env_enabled() -> bool:
    return envknobs.env_bool(EVENTS_ENV)


def resolve_enabled(flag: str | bool | None) -> bool:
    """The CLI/env gate: an explicit ``--events on/off`` wins; None falls
    through to ``DREP_TPU_EVENTS`` (default off)."""
    if flag is None:
        return env_enabled()
    if isinstance(flag, bool):
        return flag
    return str(flag).strip().lower() in ("1", "on", "true")


# the process-global sink. "enabled" is THE hot-path check (one dict
# lookup); the file handle is opened lazily at the first emit so a run
# with events off never touches the filesystem at all.
_STATE: dict[str, Any] = {
    "enabled": False,
    "log_dir": None,
    "pid": 0,
    "run": None,
    "epoch": 0,
    "sink": None,
}
_LOCK = threading.RLock()


def configure(
    log_dir: str | None = None,
    enabled: str | bool | None = None,
    pid: int | None = None,
    run_id: str | None = None,
) -> bool:
    """Install the process event sink. `enabled` None resolves the env
    gate; tracing needs a `log_dir` to be on. Returns the final enabled
    state. Reconfiguring closes any previous sink first (library users
    may run several workflows per process)."""
    close()
    with _LOCK:
        on = resolve_enabled(enabled)
        if pid is not None:
            _STATE["pid"] = int(pid)
        _STATE["log_dir"] = log_dir
        _STATE["run"] = run_id
        _STATE["epoch"] = 0
        _STATE["enabled"] = bool(on and log_dir)
    return _STATE["enabled"]


def enabled() -> bool:
    return _STATE["enabled"]


def configured_log_dir() -> str | None:
    """The log dir the sink was configured with (set whether or not
    tracing is on): where a wedged run's own event logs are, for
    tools/trace_report.stall_diagnosis."""
    return _STATE["log_dir"]


def set_epoch(epoch: int) -> None:
    """Keep the stamped ownership epoch current (profiling.note_epoch and
    the elastic join path call this — every later line carries it)."""
    _STATE["epoch"] = int(epoch)


def set_pid(pid: int) -> None:
    """Re-home the stream to a new process id: close the current sink so
    later lines land in ``events.p<pid>.jsonl``. The JOIN path needs
    this — a joiner configures telemetry as a single-process run (pid 0)
    and only learns its ADMITTED id from the leader's admit note; without
    the re-home its whole stream would interleave into original member
    0's log and corrupt the merged timeline. Lines already written under
    the old pid (ingest, the pre-admission stage spans) stay there —
    few, and honestly stamped with the id the process believed at the
    time."""
    if int(pid) == _STATE["pid"]:
        return
    close()
    with _LOCK:
        _STATE["pid"] = int(pid)


def _load_run_id(log_dir: str) -> str:
    """The workdir-stable run id: persisted beside the event logs so a
    RESUME (new process, same workdir) keeps the id and the merged
    timeline spans the kill. First writer wins via O_EXCL; losers read
    the winner's id (retrying through the microsecond create->write
    window)."""
    path = os.path.join(log_dir, RUN_ID_NAME)
    rid = uuid.uuid4().hex[:12]
    try:
        fd = os.open(path, os.O_CREAT | os.O_EXCL | os.O_WRONLY)
        try:
            os.write(fd, rid.encode())
        finally:
            os.close(fd)
        return rid
    except FileExistsError:
        pass
    except OSError:
        return rid  # unwritable log dir: a per-process id beats no trace
    for _ in range(20):
        try:
            with open(path, encoding="utf-8") as f:
                got = f.read().strip()
            if got:
                return got
        except OSError:
            pass
        time.sleep(0.02)
    return rid


def _sink():
    s = _STATE["sink"]
    if s is not None or not _STATE["enabled"]:
        return s
    with _LOCK:
        s = _STATE["sink"]
        if s is not None:
            return s
        log_dir = _STATE["log_dir"]
        try:
            os.makedirs(log_dir, exist_ok=True)
            if _STATE["run"] is None:
                _STATE["run"] = _load_run_id(log_dir)
            path = os.path.join(log_dir, f"events.p{_STATE['pid']}.jsonl")
            s = open(path, "a", encoding="utf-8")  # noqa: SIM115 — long-lived sink
        except OSError:
            # an unwritable sink must never take the run down — tracing
            # is observability, not a dependency
            _STATE["enabled"] = False
            return None
        _STATE["sink"] = s
        return s


def _emit(ev: str, ph: str, args: dict | None) -> None:
    s = _sink()
    if s is None:
        return
    rec: dict[str, Any] = {
        "run": _STATE["run"],
        "pid": _STATE["pid"],
        "epoch": _STATE["epoch"],
        "ev": ev,
        "ph": ph,
        "mono": round(time.monotonic(), 6),
        # drep-lint: allow[clock-mono] — the event schema's wall key: trace_report aligns members by it
        "wall": round(time.time(), 6),
    }
    if args:
        rec["args"] = args
    try:
        line = json.dumps(rec, separators=(",", ":"), default=str)
    except (TypeError, ValueError):
        return  # an unserializable arg must never crash the traced path
    with _LOCK:
        try:
            # one write+flush per line: a SIGKILL tears at most the final
            # line — the torn tail readers treat as crash evidence
            s.write(line + "\n")
            s.flush()
        except (OSError, ValueError):
            pass


def event(ev: str, **args) -> None:
    """Emit one point event (``ph`` "i"). Free when tracing is off."""
    if not _STATE["enabled"]:
        return
    _emit(ev, "i", args or None)


class Span:
    """B-at-enter / E-at-exit (E carries ``dur`` from the monotonic
    clock). The B record is deliberate redundancy: it is the crash
    evidence when the process dies inside the span. Each line is gated
    when it is due: with tracing off a span costs two dict lookups and
    creates no file. The program reaches this class through the front door
    alone (``profiling.Counters.span``), so no span is on one clock only."""

    __slots__ = ("ev", "args", "_t0")

    def __init__(self, ev: str, args: dict) -> None:
        self.ev = ev
        self.args = args

    def __enter__(self) -> "Span":
        self._t0 = time.monotonic()
        if _STATE["enabled"]:
            _emit(self.ev, "B", self.args or None)
        return self

    def __exit__(self, exc_type, exc, tb) -> bool:
        if not _STATE["enabled"]:
            return False
        args = dict(self.args)
        args["dur"] = round(time.monotonic() - self._t0, 6)
        if exc_type is not None:
            args["error"] = exc_type.__name__
        _emit(self.ev, "E", args)
        return False


def close() -> None:
    """Flush and close the sink (re-opens lazily if events keep coming —
    a workflow epilogue closing early must not lose late protocol
    events)."""
    with _LOCK:
        s = _STATE["sink"]
        _STATE["sink"] = None
        if s is not None:
            try:
                s.flush()
                s.close()
            except (OSError, ValueError):
                pass
