"""First-class performance counters + JAX profiler hook.

The reference has no tracing/profiling at all — only wall-time logging and a
comparison-count ETA estimate (SURVEY.md §5.1; reference mount empty). The
rebuild's headline metric is genome-pairs/sec/chip (BASELINE.json), so it is
tracked here as a first-class counter: every compare stage records how many
pairwise comparisons it performed and how long it took, and the totals are
written to ``<wd>/log/perf_counters.json`` at the end of every run.

``Counters.span(name)`` is THE front door for a span (ISSUE 24): one
context manager, three sinks. It always accumulates into the record's
``phases`` section (seconds, self seconds = duration less what child spans
cover, calls, per thread, and what the host spent meanwhile: `Host accounting`
below); when ``jax`` is already imported it enters
``jax.profiler.TraceAnnotation("drep:<name>")``, so inside a profiler session
the span lands on ``/host:CPU`` on the profiler's own clock, beside the device
trace; and under ``--events on`` it writes the JSONL ``B``/``E`` lines
(utils/telemetry.py). Names are stable strings: what varies goes in the
keyword arguments.

``trace(dir)`` wraps a job in ``jax.profiler.trace`` with the options the
benchmark harness uses (``--profile`` on the CLI).

Host accounting (ISSUE 52). A span's seconds say how long; what the host was
doing is read at the span's two boundaries (:func:`_read_host`) and booked
with the arithmetic that gives ``self_seconds``, so the main thread's
``self_*`` values over all bare-named phases add up to the ``job`` span's own
deltas. Beside ``seconds`` / ``self_seconds`` / ``calls`` / ``thread`` an
entry of ``phases`` holds, as plain numbers:

- ``cpu_s``, ``self_cpu_s``: user + kernel seconds of ALL threads of the
  process (``RUSAGE_SELF``). Over the span's seconds: the cores kept busy;
  above 1 the worker threads scaled, far under 1 the process waited.
- ``sys_s``, ``self_sys_s``: the kernel's part of it (page faults, file
  creation and rename, ``mmap``). The kernel splits user from kernel time by
  tick samples: right over a phase's sum, coarse for one short span.
- ``self_minor_faults``, ``self_major_faults``: pages first touched (times
  ``resource.getpagesize()`` = bytes) and pages read back from disk, process-wide.
- ``self_thread_cpu_s``: the opening thread's own CPU (``RUSAGE_THREAD``);
  ``self_seconds`` less it is what the thread spent off its CPU: a device
  wait, a file, worker threads, the GIL, or descheduled.
- ``self_invol_switches``, ``self_vol_switches``: the opening thread was
  preempted / went to sleep itself. A sandboxed kernel keeps neither these
  nor the faults (the chip host's reads the faults as 0 for ever and the
  switches nearly so): a job with no fault has no source for them, not a
  reading of zero.
- ``gc_s``, ``gc_collections``: seconds inside the cyclic collector and its
  runs (``gc.callbacks``), booked to the innermost span open on the
  collecting thread: self values by construction.

The process-wide values are the process's, whoever opened the span: a span
on another thread sees the same CPU as the main thread's span open beside it,
so a ``<name>@other`` phase carries the thread's fields and the collector's
alone. The ingest pool's workers are processes and are in none of it (they
carry their own ``busy_seconds``; ``RUSAGE_CHILDREN`` moves only when a
child is reaped).

A boundary reuses its thread's last read while that is younger than
``HOST_READ_EVERY_S``: a read is two system calls, and a job crosses 10^4
boundaries. What accrued since the last real read is booked to the span
innermost at the next one, so phases whose spans are all shorter than the
constant are right in their sum over the loop that alternates them, and
share it by their seconds, not span by span. The record's writer reads
afresh, so the open spans count as far as they have come.

What a process pays before its first warm job (ISSUE 36) is in two sections
of the same record. ``compile``: the programs this job traced, lowered,
compiled or loaded from the persistent cache, booked by the ``jax.monitoring``
listeners of :func:`listen_for_compiles` under the innermost span open on the
compiling thread. ``process`` (:class:`ProcessLedger`): when the process
began this job, on the process's own clock, and the jobs it ran before, the
first of them kept with its programs. ``Counters.reset`` clears the first and
leaves the second.
"""

from __future__ import annotations

import collections
import contextlib
import gc
import json
import os
import resource
import sys
import threading
import time
from dataclasses import dataclass, field
from typing import Any, Iterator

import drep_tpu
from drep_tpu.utils import telemetry

# distinct program shapes the record lists, for the one-shot calls and for
# the chunked calls each (Counters.add_secondary_call, .add_chunked_call),
# and the clusters it lists of the greedy engine's (.add_greedy_call)
SECONDARY_SHAPES_MAX = 64
# jobs the process ledger lists, the newest (ProcessLedger.jobs)
LEDGER_JOBS_MAX = 16

# what one program cost to build, by phase, and how the persistent cache
# answered: the fields of an entry of Counters.built
_BUILT_FIELDS = ("calls", "trace_s", "lower_s", "backend_compile_s", "cache_load_s",
                 "hits", "misses")

# jax.monitoring's names (jax 0.9.0: _src/dispatch.py, _src/compiler.py). The
# trace event carries the function's name, the lowering and backend-compile
# events the module's, `jit(<function>)`: _bare_name folds them.
_TRACE_EVENT = "/jax/core/compile/jaxpr_trace_duration"
_LOWER_EVENT = "/jax/core/compile/jaxpr_to_mlir_module_duration"
_BACKEND_EVENT = "/jax/core/compile/backend_compile_duration"
_RETRIEVAL_EVENT = "/jax/compilation_cache/cache_retrieval_time_sec"
_REQUEST_EVENT = "/jax/compilation_cache/compile_requests_use_cache"
_HIT_EVENT = "/jax/compilation_cache/cache_hits"


def _bare_name(fun_name: str) -> str:
    """The function's name out of a module's: `jit(f)` -> `f`."""
    if fun_name.endswith(")") and "(" in fun_name:
        return fun_name[fun_name.index("(") + 1:-1]
    return fun_name


def _built_seconds(ent: dict[str, Any]) -> float:
    return ent["trace_s"] + ent["lower_s"] + ent["backend_compile_s"] + ent["cache_load_s"]


def _built_rounded(ent: dict[str, Any], count_as: str) -> dict[str, Any]:
    """An entry of _BUILT_FIELDS as the record writes it, its `calls` under
    the name `count_as`."""
    return {count_as if name == "calls" else name:
            round(ent[name], 6) if name.endswith("_s") else int(ent[name])
            for name in _BUILT_FIELDS}


def _process_age_s() -> tuple[float, str]:
    """Seconds since this process started, and the clock that says so: on
    Linux the kernel's start time of the process against CLOCK_BOOTTIME
    (`proc_stat`; the interpreter's own start is inside it), elsewhere the
    first import of the package (`package_import`)."""
    try:
        with open("/proc/self/stat", "rb") as f:
            # the command's name may hold spaces: the fields after it count from ')'
            after_comm = f.read().rsplit(b")", 1)[1].split()
        started = int(after_comm[19]) / os.sysconf("SC_CLK_TCK")  # field 22, starttime
        age = time.clock_gettime(time.CLOCK_BOOTTIME) - started
        if age >= 0.0:
            return age, "proc_stat"
    except (OSError, ValueError, IndexError, AttributeError):
        pass
    return time.perf_counter() - drep_tpu._IMPORTED_AT, "package_import"


class ProcessLedger:
    """The record's ``process`` section: what this process had paid when a
    job began, and the jobs it ran before. Marks are seconds since the
    process started (:func:`_process_age_s`). A job is opened by its
    bring-up (``workflows._bring_up`` / ``_init_index``), which no span
    covers, and entered as the last thing inside `job`
    (:meth:`Counters.finish_job`): ``{verb, began_at_s, bring_up_s, job_s,
    compile}``. The first entry stays for good with its programs: in the
    benchmark harness it is the warm-up job, whose own record is deleted.
    The newest LEDGER_JOBS_MAX entries keep their totals, so a long-lived
    process writes a bounded record."""

    def __init__(self) -> None:
        age, self.clock = _process_age_s()
        self._origin = time.perf_counter() - age
        self.imported_at_s = drep_tpu._IMPORTED_AT - self._origin
        self.n_jobs = 0
        self.first_job: dict[str, Any] | None = None
        self.jobs: collections.deque = collections.deque(maxlen=LEDGER_JOBS_MAX)
        # the job under way: verb, then perf_counter at the bring-up's entry and end
        self.verb: str | None = None
        self._began = self._brought_up = 0.0

    def begin(self, verb: str) -> None:
        self.verb = verb
        self._began = self._brought_up = time.perf_counter()

    def brought_up(self) -> None:
        self._brought_up = time.perf_counter()

    def entry(self, job_t0: float | None, compile_: dict[str, Any]) -> dict[str, Any]:
        """The job under way as far as it has come; `job_t0` is where its
        `job` span opened (a verb without one counts from its bring-up)."""
        t0 = self._brought_up if job_t0 is None else job_t0
        return {
            "verb": self.verb,
            "began_at_s": round(self._began - self._origin, 4),
            "bring_up_s": round(self._brought_up - self._began, 4),
            "job_s": round(time.perf_counter() - t0, 4),
            "compile": compile_,
        }

    def append(self, entry: dict[str, Any]) -> None:
        if self.first_job is None:
            self.first_job = entry
        totals = {k: v for k, v in entry["compile"].items() if k not in ("by_program", "by_span")}
        self.jobs.append({**entry, "compile": totals})
        self.n_jobs += 1
        self.verb = None

    def report(self, under_way: dict[str, Any] | None) -> dict[str, Any]:
        """`under_way`: the entry of the job that writes this record, or
        None outside any. A process's first job is its own `first_job`."""
        job = under_way or {}
        return {
            "clock": self.clock,
            "imported_at_s": round(self.imported_at_s, 4),
            "began_at_s": job.get("began_at_s"),
            "bring_up_s": job.get("bring_up_s"),
            "n_jobs": self.n_jobs,
            "first_job": self.first_job or under_way,
            "jobs": list(self.jobs),
        }


@dataclass
class _Stage:
    pairs: int = 0
    seconds: float = 0.0
    calls: int = 0
    # triangular-schedule proof (ISSUE 1): how many pair-tiles the stage's
    # compute schedule actually ran vs the full N^2 grid it covers. A
    # triangle-only engine reports ~(B+1)/(2B) of the full grid; a silent
    # regression to full-grid scheduling shows up as fraction ~1.0.
    tiles_computed: int = 0
    tiles_total: int = 0
    # LSH-banded candidate pruning (ops/lsh.py): upper-triangle schedule
    # tiles SKIPPED because no candidate pair lands in them. Kept separate
    # from tiles_total (which stays the dense-equivalent grid) so the
    # record reports both the honest dense totals AND how much the sparse
    # schedule saved.
    tiles_skipped: int = 0


# what one read of the host holds (:func:`_read_host`), in this order: the
# first four are the whole process's, the rest the reading thread's
_HOST_FIELDS = ("cpu_s", "sys_s", "minor_faults", "major_faults",
                "thread_cpu_s", "invol_switches", "vol_switches")
_NO_HOST = (0,) * len(_HOST_FIELDS)
# the args a span's E line may carry of it, whole deltas, beside the span's own
HOST_ARGS = (*_HOST_FIELDS, "gc_s", "gc_collections")
# a span boundary reuses its thread's last read of the host while that is
# younger than this (module docstring). On a chip host one read is two system
# calls of 6 us each (12.5-14 us measured, ISSUE 52; the dense job crosses
# 9,146 boundaries in 8.4 s), so at most 100 reads a second stay under 0.15%
# of any job; reading at every boundary was 1.4% of that one
HOST_READ_EVERY_S = 0.01


class _ThreadHost:
    """One thread's side of the host accounting, shared by every Counters:
    its last read of the host (`read`, taken at `read_at` on perf_counter's
    clock), its innermost open span (`span`), and where a collection under
    way began (`gc_t0`)."""

    __slots__ = ("read", "read_at", "span", "gc_t0")

    def __init__(self) -> None:
        self.read: tuple = _NO_HOST
        self.read_at = float("-inf")
        self.span: _Span | None = None
        self.gc_t0: float | None = None

    def take(self, now: float) -> tuple:
        """A new read for a boundary at `now`. A boundary reuses `read`
        while `now - read_at` is under HOST_READ_EVERY_S: the same object,
        so a span that saw no new read books nothing."""
        self.read_at = now
        self.read = read = _read_host()
        return read


_THREADS = threading.local()


def _thread_host() -> _ThreadHost:
    th = getattr(_THREADS, "host", None)
    if th is None:
        th = _THREADS.host = _ThreadHost()
    return th


@dataclass
class _Phase:
    seconds: float = 0.0
    self_seconds: float = 0.0
    calls: int = 0
    # _HOST_FIELDS over the phase's spans: their whole deltas, and what no
    # child span covers
    host: list = field(default_factory=lambda: list(_NO_HOST))
    self_host: list = field(default_factory=lambda: list(_NO_HOST))
    gc_s: float = 0.0
    gc_collections: int = 0


def _plus(a, b) -> list:
    return [x + y for x, y in zip(a, b)]


def _minus(a, b) -> list:
    return [x - y for x, y in zip(a, b)]


def _read_host() -> tuple:
    """_HOST_FIELDS now. Off the main thread the process's part reads zero:
    it is booked once, by the main thread's spans."""
    t = resource.getrusage(resource.RUSAGE_THREAD)
    thread = (t.ru_utime + t.ru_stime, t.ru_nivcsw, t.ru_nvcsw)
    if not _on_main_thread():
        return (0.0, 0.0, 0, 0, *thread)
    p = resource.getrusage(resource.RUSAGE_SELF)
    return (p.ru_utime + p.ru_stime, p.ru_stime, p.ru_minflt, p.ru_majflt, *thread)


def _on_gc(phase: str, _info: dict) -> None:
    """`gc.callbacks`: a collection's seconds go to the innermost span open
    on the thread that collects."""
    th = _thread_host()
    if phase == "start":
        th.gc_t0 = time.perf_counter()
        return
    span, t0 = th.span, th.gc_t0
    if span is not None and t0 is not None:
        span._gc_s += time.perf_counter() - t0
        span._gc_n += 1


def _book_pack(section: dict[str, int], native: bool, threads: int, **counted: int) -> None:
    """One more rank-map pack into a record section (`primary_pack`,
    `secondary_pack`): the calls and what they `counted` add up, `threads`
    keeps the widest call's."""
    for name, value in {"calls": 1, "native_calls": int(native), **counted}.items():
        section[name] = section.get(name, 0) + int(value)
    section["threads"] = max(section.get("threads", 0), int(threads))


def _on_main_thread() -> bool:
    return threading.current_thread() is threading.main_thread()


class _Span:
    """One open span of :meth:`Counters.span`. Its frame sits on the
    opening thread's stack; on exit its duration and what the host spent
    meanwhile are booked to its phase and credited to the parent frame,
    whose self values are what no child covers."""

    __slots__ = ("_counters", "name", "_calls", "_args", "_sinks", "_t0", "_child",
                 "_stack", "_thread", "_host0", "_host_child", "_gc_s", "_gc_n", "_outer")

    def __init__(self, counters: "Counters", name: str, calls: int, args: dict) -> None:
        self._counters = counters
        self.name = name
        self._calls = calls
        self._args = args

    def __enter__(self) -> "_Span":
        self._sinks = [telemetry.Span(self.name, self._args)]
        # the profiler's host plane, only where JAX is loaded already: a
        # span must never be what imports it (index route / supervise)
        prof = getattr(sys.modules.get("jax"), "profiler", None)
        if prof is not None:
            self._sinks.append(prof.TraceAnnotation("drep:" + self.name, **self._args))
        self._child = 0.0
        self._host_child: list | None = None
        self._gc_s, self._gc_n = 0.0, 0
        self._stack, self._thread = stack, th = self._counters._frames()
        self._outer = th.span
        th.span = self
        stack.append(self)
        self._t0 = now = time.perf_counter()
        self._host0 = th.read if now - th.read_at < HOST_READ_EVERY_S else th.take(now)
        for sink in self._sinks:
            sink.__enter__()
        return self

    def note(self, **args) -> None:
        """Args known only when the work is done (the bytes a write came
        to): they ride on the span's E line in the event log. The profiler's
        annotation took its args at entry."""
        self._args.update(args)

    def __exit__(self, exc_type, exc, tb) -> bool:
        th = self._thread
        now = time.perf_counter()
        host1 = th.read if now - th.read_at < HOST_READ_EVERY_S else th.take(now)
        # no new read since the span opened: nothing to book, for it or inside it
        host = None if host1 is self._host0 else _minus(host1, self._host0)
        if (host is not None or self._gc_n) and telemetry.enabled():
            self.note(**self._host_args(host))
        for sink in reversed(self._sinks):
            sink.__exit__(exc_type, exc, tb)
        dur = time.perf_counter() - self._t0
        th.span = self._outer
        stack = self._stack
        stack.pop()
        if stack:
            parent = stack[-1]
            parent._child += dur
            if host is not None:
                covered = parent._host_child
                parent._host_child = host if covered is None else _plus(covered, host)
        self._counters._book(self.name, dur, dur - self._child, self._calls,
                             host, self._host_child, self._gc_s, self._gc_n)
        return False

    def _host_args(self, host: list | None) -> dict:
        """The span's whole deltas as its E line carries them: what moved."""
        args = {name: round(v, 6) for name, v in zip(_HOST_FIELDS, host or ()) if v}
        if self._gc_n:
            args.update(gc_s=round(self._gc_s, 6), gc_collections=self._gc_n)
        return args


class Histogram:
    """Bounded-window latency histogram for long-lived processes (the
    serve daemon, ISSUE 11): a ring buffer of the last `size`
    observations feeds the percentiles (p50/p99 over the recent window —
    what an operator actually wants from a daemon that has been up for
    a week), while count/total/max run unbounded. O(1) observe, O(size)
    summary — summaries are scrape-cadence, observations are per-request."""

    __slots__ = ("size", "ring", "count", "total", "vmax")

    def __init__(self, size: int = 8192):
        self.size = int(size)
        self.ring: list[float] = []
        self.count = 0
        self.total = 0.0
        self.vmax = 0.0

    def observe(self, value: float) -> None:
        v = float(value)
        if len(self.ring) < self.size:
            self.ring.append(v)
        else:
            self.ring[self.count % self.size] = v
        self.count += 1
        self.total += v
        if v > self.vmax:
            self.vmax = v

    @staticmethod
    def _pick(sorted_vals: list[float], q: float) -> float:
        if not sorted_vals:
            return 0.0
        idx = min(len(sorted_vals) - 1, max(0, round(q * (len(sorted_vals) - 1))))
        return sorted_vals[int(idx)]

    def percentile(self, q: float) -> float:
        return self._pick(sorted(self.ring), q)

    def summary(self) -> dict[str, float]:
        vals = sorted(self.ring)
        return {
            "count": self.count,
            "mean": round(self.total / self.count, 4) if self.count else 0.0,
            "p50": round(self._pick(vals, 0.5), 4),
            "p90": round(self._pick(vals, 0.9), 4),
            "p99": round(self._pick(vals, 0.99), 4),
            "max": round(self.vmax, 4),
        }


def device_record() -> dict[str, Any]:
    """What THIS process's JAX backend runs on — the three fields every
    run record carries (perf_counters.json, the serve daemon's ready line
    and status), so that a run can be judged from its own record: a
    compare that quietly ran on the CPU must not read like a chip run.
    Initializes the backend if nothing has yet; a backend that cannot
    initialize raises (it is the run's error, not a default)."""
    import jax

    devices = jax.devices()
    return {
        "platform": devices[0].platform,
        "device_kind": devices[0].device_kind,
        "n_devices": len(devices),
    }


@dataclass
class Counters:
    """Per-stage pair/time accounting. One process-global instance (the
    pipeline is single-process on host; device parallelism happens inside a
    stage) plus independent instances for tests."""

    stages: dict[str, _Stage] = field(default_factory=dict)
    # fault-tolerance accounting (parallel/faulttol.py): retries,
    # watchdog_trips, quarantined_devices, cpu_fallback_tiles,
    # dead_processes / pod_epoch_bumps (elastic pod), ring_step_failures /
    # ring_blocks_recovered (step-wise dense ring, parallel/allpairs.py),
    # plus injected_<site>_<mode> counts from utils/faults.py. A degraded
    # run must be honest about HOW it finished — a completed run that
    # burned 40 retries, benched a chip, or recomputed ring blocks
    # per-tile is not the same measurement as a clean one, and bench
    # records must be able to tell them apart.
    # the durable-I/O layer (utils/durableio.py) adds its own honest
    # counters here: io_retries (transient EIO/ESTALE/ETIMEDOUT retried),
    # corrupt_shards_healed (checksum/truncation detections recomputed
    # into their own path), io_unrecoverable (ops failed past the budget).
    faults: dict[str, int] = field(default_factory=dict)
    # derived operational values (not event counts): e.g. the auto-derived
    # per-dispatch watchdog deadline the run actually used when
    # --dispatch_timeout was left at 0 (parallel/faulttol.py) — reported so
    # an operator can pin an explicit value from evidence.
    gauges: dict[str, float] = field(default_factory=dict)
    # short strings riding beside the gauges (`ingest_path`: which
    # ingest implementation ran) — last write wins, same as gauges.
    notes: dict[str, str] = field(default_factory=dict)
    # elastic-pod membership history (ISSUE 9): one entry per ownership-
    # epoch bump, with WHY it bumped (death / drain / join). The faults
    # counters say how many of each happened; this says in what ORDER —
    # a drain-then-join churn and a join-then-drain churn are different
    # operational stories that the same counter totals would conflate.
    epoch_history: list = field(default_factory=list)
    # per-request latency distributions (ISSUE 11, the serve daemon):
    # gauges hold last-write-wins scalars, but a serving tier's honesty
    # metric is the TAIL — p50/p99 over a bounded recent window, per
    # named series (serve_request_ms, serve_batch_ms, ...).
    hists: dict[str, Histogram] = field(default_factory=dict)
    # which kernel path served each secondary compare call (one_shot,
    # one_shot_clusterlocal, mesh_ring, matmul_chunked, cpu_tiles —
    # cluster/engines.py; greedy_matmul, greedy_gather — one a cluster
    # the greedy engine served, cluster/greedy.py): a run's record must
    # say which regime it exercised, not leave it to be inferred from shapes
    paths: dict[str, int] = field(default_factory=dict)
    # where the host's time went (ISSUE 24): every span of the front door
    # (:meth:`span`), keyed (name, on the main thread?)
    phases: dict[tuple[str, bool], _Phase] = field(default_factory=dict)
    # the one-shot secondary calls by shape (cluster/engines.py): how many
    # calls, clusters and rows went through each [rows_pad, width] x v_pad
    # program, and how many of the rows_pad^2/2 pairs it computed were read
    secondary_calls: dict[tuple[int, int, int], dict[str, int]] = field(default_factory=dict)
    # the vocabulary-chunked secondary calls by shape (ops/containment.py):
    # what each [chunks, rows_pad, width] stacked id tensor shipped, how much
    # of it was padding, and the chunk program it ran `chunks` times
    chunked_calls: dict[tuple[int, int, int, int, str], dict[str, int]] = field(default_factory=dict)
    # the clusters the greedy engine served (cluster/greedy.py), one entry
    # each in the order they were met: rows, blocks, the representatives it
    # ended with and the representative rows its blocks were computed
    # against, the chunk plan, what was shipped, and the pairs the greedy
    # scan consumed beside the cluster's all-pairs
    greedy_calls: list[dict[str, int]] = field(default_factory=list)
    # the clusters `greedy_assign_from_matrices` served from the batched
    # one-shot call's matrices: clusters, rows, pairs consumed, all-pairs
    greedy_batched: dict[str, int] = field(default_factory=dict)
    # what the primary's packs ranked (cluster/engines.py::pack_primary):
    # calls, genomes, hashes and the distinct ids they became, summed. The
    # pack's seconds follow the hashes it sorts (ISSUE 28) and the path that
    # ranked them: `native_calls` of the calls went through native/rank.cc,
    # the widest on `threads` threads (ISSUE 40)
    primary_pack: dict[str, int] = field(default_factory=dict)
    # the same for the secondary's shared-vocabulary packs
    # (containment.pack_secondary, ISSUE 44): calls, native_calls, rows,
    # hashes, threads
    secondary_pack: dict[str, int] = field(default_factory=dict)
    # how the sketch cache was read back (ingest.py::_load, ISSUE 43): the
    # `members` stored in parts, their `parts`, how many were read in place
    # (`direct_parts`) and how many through `load_npz_checked`
    # (`fallback_parts`), their `bytes`, the `threads` of the widest member
    # and the `seconds` of `WorkDirectory.read_arrays`, summed over the reads
    sketch_cache_read: dict[str, Any] = field(default_factory=dict)
    # what the dense primary's linkage did (ops/linkage.py::
    # cluster_by_components, ISSUE 37): genomes, the components of the graph
    # of pairs under the cutoff, how many were singletons, how many were
    # settled with no linkage call (`cliques`), the scipy calls and the
    # genomes they held, the largest component, and whether the whole tree
    # was built beside them (`tree`: built | skipped)
    primary_linkage: dict[str, Any] = field(default_factory=dict)
    # how the streaming primary dealt its tiles over the local devices
    # (parallel/streaming.py, ISSUE 39): the computed `stripes`, their
    # `tiles`, the `turns` they took (ceil(tiles of a stripe / active
    # slots), summed), the `slots`, and `by_slot`, one entry a device slot
    # in slot order: `tiles` first dispatched there, their `pairs`, the
    # `put_bytes` of the pack put there, `finalize_wait_s` the host spent
    # inside `TileExecutor.finalize` for them
    stream_slots: dict[str, Any] = field(default_factory=dict)
    # what `WorkDirectory.store_db` wrote, a table name (ISSUE 30): calls,
    # rows, bytes, the table's values and the distinct texts the columnar
    # writer rendered for them, and the calls that went through pandas'
    # `to_csv` instead, with why (drep_tpu/tablewriter.py)
    tables_write: dict[str, dict[str, Any]] = field(default_factory=dict)
    # what FASTA ingest sketched and kept (ingest.py, ISSUE 31): genomes, the
    # files' bytes, bases, valid k-mers, the hashes of both sketches, and the
    # seconds the workers themselves spent in `sketch_one`, summed; the
    # widest pool and the kernel that served ride beside the sums. In a
    # `dereplicate` job also what the one pass read beside them (ISSUE 32):
    # `stats_only_*`, the genomes the quality table dropped beforehand, read
    # for length, N50 and contigs alone; `sketched_then_dropped*`, those a
    # rule dropped once their length (or CheckM) was known
    ingest: dict[str, Any] = field(default_factory=dict)
    # what `stage:filter` saw and dropped, by reason (filter.py)
    filter: dict[str, int] = field(default_factory=dict)
    # what `stage:evaluate` read and wrote (evaluate.py, ISSUE 35): under
    # `mdb` and `ndb` the `source` of the pair table (`job`: the columns this
    # process held from the stage that wrote it; `disk`: the file read back)
    # and its `rows`; `warnings`, the lines by kind; their `bytes`; and the
    # `distinct` texts rendered for them (names encoded, values formatted)
    evaluate: dict[str, Any] = field(default_factory=dict)
    # what this job found in the stores an earlier, stopped job of the same
    # work directory left, beside what it computed itself (ISSUE 47):
    # `stripes_resumed`, `tiles_resumed`, `shard_bytes` (streaming shards read
    # back and the upper-triangle tiles they stand for) against
    # `tiles_computed`; `clusters_resumed`, `checkpoint_bytes` (secondary
    # checkpoints read back) against `clusters_computed` (primary clusters
    # that went through a device call, published or not). A fresh job reads
    # zeros on the resumed side
    resume: dict[str, int] = field(default_factory=dict)
    # what an index verb did (drep_tpu/index/, ISSUE 50): `n_old` genomes
    # loaded, `admitted`, the `generation` it published; the rectangle's
    # `pairs_compared`, `tiles` and `new_edges`; `components_reclustered`,
    # `clusters_reused`, `clusters_recomputed` with their `members_recomputed`,
    # the `secondary_calls` and `singletons_scored` that took and the
    # `score_calls` (score_and_pick calls: one over all of them); `bytes_loaded`
    # (files read back), `bytes_published`, `files_published` and the
    # `parts_written` among them (index/store.py::write_payload)
    index: dict[str, int] = field(default_factory=dict)
    # the safe boundary at which a one-process job honoured a drain request
    # (faulttol.drain_at_boundary): `stage`, where in it, and when the
    # request was made on time.monotonic()'s clock; empty for a job that ran
    # to its end
    drain: dict[str, Any] = field(default_factory=dict)
    # the programs this job built (ISSUE 36), keyed (function, the innermost
    # span open on the thread that built it): _BUILT_FIELDS. Booked by the
    # jax.monitoring listeners (:func:`listen_for_compiles`)
    built: dict[tuple[str, str], dict[str, float]] = field(default_factory=dict)
    # the process's own ledger: not a job's, so :meth:`reset` leaves it
    process: ProcessLedger = field(default_factory=ProcessLedger, repr=False, compare=False)
    _open: threading.local = field(default_factory=threading.local, repr=False, compare=False)
    _lock: threading.Lock = field(default_factory=threading.Lock, repr=False, compare=False)

    def span(self, name: str, calls: int = 1, **args) -> _Span:
        """THE front door for a span (module docstring): accumulates into
        ``phases``, annotates the profiler's host plane, writes the JSONL
        B/E lines. `name` is a stable string; `args` carry what varies.
        `calls` books one span round a loop as that many units of work."""
        return _Span(self, name, calls, args)

    def _frames(self) -> tuple[list, _ThreadHost]:
        """The calling thread's open spans of these counters, outermost
        first, and its side of the host accounting."""
        frames = getattr(self._open, "frames", None)
        if frames is None:
            frames = self._open.frames = ([], _thread_host())
        return frames

    def _stack(self) -> list:
        return self._frames()[0]

    def _book(self, name: str, seconds: float, self_seconds: float, calls: int,
              host: list | None, host_child: list | None, gc_s: float, gc_collections: int) -> None:
        """One closed span into its phase. `host`: its whole _HOST_FIELDS
        deltas (None: no read of the host fell inside it), `host_child`
        what its child spans covered of them."""
        with self._lock:
            ph = self.phases.setdefault((name, _on_main_thread()), _Phase())
            ph.seconds += seconds
            ph.self_seconds += self_seconds
            ph.calls += calls
            if host is not None:
                ph.host = _plus(ph.host, host)
                ph.self_host = _plus(ph.self_host, host if host_child is None else _minus(host, host_child))
            if gc_collections:
                ph.gc_s += gc_s
                ph.gc_collections += gc_collections

    @contextlib.contextmanager
    def stage(self, name: str, pairs: int = 0) -> Iterator[None]:
        t0 = time.perf_counter()
        # every counted stage block is a span of the front door
        with self.span("stage:" + name):
            try:
                yield
            finally:
                st = self.stages.setdefault(name, _Stage())
                st.pairs += int(pairs)
                st.seconds += time.perf_counter() - t0
                st.calls += 1

    def add(self, name: str, pairs: int, seconds: float) -> None:
        st = self.stages.setdefault(name, _Stage())
        st.pairs += int(pairs)
        st.seconds += float(seconds)
        st.calls += 1

    def add_tiles(self, name: str, computed: int, total: int, skipped: int = 0) -> None:
        """Record one compare schedule's pair-tile accounting: `computed`
        tiles actually dispatched vs `total` tiles of the full N^2 grid the
        output covers, plus `skipped` schedule tiles pruned away by the
        LSH candidate bitmap (0 when pruning is off). Separate from
        add()/stage() on purpose — pairs and seconds are recorded once at
        the pipeline layer (controller), tiles once at the compute layer
        (the engine that knows its schedule), so neither is ever
        double-counted."""
        st = self.stages.setdefault(name, _Stage())
        st.tiles_computed += int(computed)
        st.tiles_total += int(total)
        st.tiles_skipped += int(skipped)

    def add_fault(self, kind: str, n: int = 1) -> None:
        """Count one fault-tolerance event (retry, watchdog trip, device
        quarantine, CPU-fallback tile, pod-member death, or an injected
        fault firing) — and, with event tracing on, stamp WHEN it
        happened into the structured timeline (the counters keep the
        totals; the events keep the order)."""
        with self._lock:  # the sketch cache's reader threads retry on their own
            self.faults[kind] = self.faults.get(kind, 0) + int(n)
        telemetry.event("fault", kind=kind, n=int(n))

    def add_path(self, name: str) -> None:
        """Count one secondary compare call served by kernel path `name`."""
        self.paths[name] = self.paths.get(name, 0) + 1

    def add_secondary_call(
        self, clusters: int, rows: int, rows_pad: int, width: int, v_pad: int,
        useful_pairs: int,
    ) -> None:
        """Book one one-shot secondary call: `clusters` and `rows` went
        through a [`rows_pad`, `width`] x `v_pad` program that computes
        every pair of its padded rows, of which `useful_pairs` (the pairs
        inside a cluster) are read. Grouped by shape, so the list is as
        long as the run has distinct programs; past SECONDARY_SHAPES_MAX
        of them the rest is summed per `rows_pad` with width and v_pad 0."""
        key = (rows_pad, width, v_pad)
        if key not in self.secondary_calls and len(self.secondary_calls) >= SECONDARY_SHAPES_MAX:
            key = (rows_pad, 0, 0)
        ent = self.secondary_calls.setdefault(
            key, {"calls": 0, "clusters": 0, "rows": 0, "useful_pairs": 0}
        )
        ent["calls"] += 1
        ent["clusters"] += int(clusters)
        ent["rows"] += int(rows)
        ent["useful_pairs"] += int(useful_pairs)

    def add_chunked_call(
        self, rows: int, rows_pad: int, v_chunk: int, chunks: int, width: int,
        id_dtype: str, extent: int, hashes: int, id_slots: int, bytes_shipped: int,
    ) -> None:
        """Book one vocabulary-chunked secondary call: `rows` genomes with
        `hashes` real ids over a vocabulary of `extent` went to the device as
        one [`chunks`, `rows_pad`, `width`] tensor of `id_dtype` (`id_slots`
        slots, `bytes_shipped` bytes) and through `chunks` runs of the
        [`rows_pad`, `width`] x `v_chunk` program. Grouped by that shape,
        every other number summed over the shape's calls, and capped like
        :meth:`add_secondary_call`: past SECONDARY_SHAPES_MAX shapes the
        rest is summed per `rows_pad` under zeros."""
        key = (rows_pad, v_chunk, chunks, width, id_dtype)
        if key not in self.chunked_calls and len(self.chunked_calls) >= SECONDARY_SHAPES_MAX:
            key = (rows_pad, 0, 0, 0, "")
        booked = {"calls": 1, "rows": rows, "extent": extent, "hashes": hashes,
                  "id_slots": id_slots, "bytes_shipped": bytes_shipped}
        ent = self.chunked_calls.setdefault(key, dict.fromkeys(booked, 0))
        for name, value in booked.items():
            ent[name] += int(value)

    def add_greedy_call(
        self, rows: int, blocks: int, blocks_without_reps: int, block_rows: int, reps: int,
        rep_tile: int, rep_rows_shipped: int, rep_rows_real: int, v_chunk: int, chunks: int, extent: int,
        widths: int, hashes: int, id_slots: int, device_calls: int, compared_pairs: int,
        mesh_devices: int, rep_tiles_replicated: int, partial_tile_ships: int,
        block_bytes: int, rep_bytes: int,
    ) -> None:
        """Book one primary cluster the greedy engine served: `rows` genomes
        holding `hashes` real ids over a vocabulary of `extent` went through
        `blocks` blocks of `block_rows` rows, each against the
        representatives that existed then (`rep_rows_real`, summed over the
        blocks) padded to whole tiles of at most `rep_tile` rows
        (`rep_rows_shipped`: the rows really computed against, padding
        included, summed likewise) and against itself, over `chunks`
        vocabulary chunks of `v_chunk` ids whose id widths add up to
        `widths`; `id_slots` int32 id slots had to reach the device
        (`bytes_shipped`, each slot counted once) for `device_calls` program
        calls, and the cluster ended with `reps` representatives after
        `compared_pairs` genome-against-representative comparisons (its Ndb
        rows) of the `all_pairs` an all-pairs secondary makes. Off the matmul
        route `v_chunk` and `chunks` are 0. On the matmul route the trailing
        tile is sized to the representatives met (`greedy._rep_tile_rows`,
        ISSUE 55), and `blocks_without_reps` counts the blocks that met none
        and so made no call against representatives: each adds 0 to
        `rep_rows_shipped` and its self comparison alone to `device_calls`.

        Who served, and what really crossed the link: `mesh_devices` the
        devices the blocks were sharded over (1 off a mesh), `block_bytes`
        and `rep_bytes` the bytes of block rows and of representative rows
        put on a device, each counted as often as it crossed (a replicated
        put once a device), `rep_tiles_replicated` the filled representative
        tiles a mesh was handed once, `partial_tile_ships` the trailing tile
        shipped again with a block. On one device `block_bytes + rep_bytes`
        is `bytes_shipped`.

        One entry a cluster (`clusters` 1), in the order met; past
        SECONDARY_SHAPES_MAX entries the rest is summed into the last one,
        whose `clusters` says how many it holds (its `mesh_devices` the
        fewest any of them had)."""
        booked = {name: int(value) for name, value in {
            "clusters": 1, "rows": rows, "blocks": blocks,
            "blocks_without_reps": blocks_without_reps, "block_rows": block_rows,
            "reps": reps, "rep_tile": rep_tile, "rep_rows_shipped": rep_rows_shipped,
            "rep_rows_real": rep_rows_real, "v_chunk": v_chunk, "chunks": chunks,
            "extent": extent, "widths": widths, "hashes": hashes, "id_slots": id_slots,
            "device_calls": device_calls, "compared_pairs": compared_pairs,
            "all_pairs": rows * (rows - 1) // 2, "bytes_shipped": 4 * id_slots,
            "mesh_devices": mesh_devices, "rep_tiles_replicated": rep_tiles_replicated,
            "partial_tile_ships": partial_tile_ships, "block_bytes": block_bytes,
            "rep_bytes": rep_bytes}.items()}
        if len(self.greedy_calls) < SECONDARY_SHAPES_MAX:
            self.greedy_calls.append(booked)
            return
        rest = self.greedy_calls[-1]
        fewest = min(rest["mesh_devices"], booked["mesh_devices"])
        for name, value in booked.items():
            rest[name] += value
        rest["mesh_devices"] = fewest

    def add_greedy_batched(self, rows: int, compared_pairs: int) -> None:
        """Book one primary cluster of `rows` genomes that the greedy rule
        served from the batched one-shot call's matrices
        (`greedy_assign_from_matrices`): it consumed `compared_pairs` of
        the cluster's `all_pairs`."""
        booked = {"clusters": 1, "rows": rows, "compared_pairs": compared_pairs,
                  "all_pairs": rows * (rows - 1) // 2}
        for name, value in booked.items():
            self.greedy_batched[name] = self.greedy_batched.get(name, 0) + int(value)

    def add_primary_pack(
        self, genomes: int, hashes: int, distinct_ids: int, native: bool = False, threads: int = 1
    ) -> None:
        """Book one `pack_sketches` of the primary compare: `hashes` bottom-k
        hashes of `genomes` rows became `distinct_ids` int32 ranks, by the
        native kernel (`native_calls`) or NumPy, on `threads` threads (the
        record keeps the widest of the job's calls)."""
        _book_pack(self.primary_pack, native, threads,
                   genomes=genomes, hashes=hashes, distinct_ids=distinct_ids)

    def add_secondary_pack(
        self, rows: int, hashes: int, native: bool = False, threads: int = 1
    ) -> None:
        """Book one shared-vocabulary `pack_scaled_sketches` of the secondary
        compare (`containment.pack_secondary`): `hashes` scaled hashes of
        `rows` genomes, ranked as `add_primary_pack` says."""
        _book_pack(self.secondary_pack, native, threads, rows=rows, hashes=hashes)

    def add_sketch_cache_read(self, threads: int, seconds: float, **did: int) -> None:
        """Book one `WorkDirectory.read_arrays` of the sketch cache: `did` is
        what it counted (members, parts, direct_parts, fallback_parts, bytes)."""
        booked = self.sketch_cache_read
        for name, value in did.items():
            booked[name] = booked.get(name, 0) + int(value)
        booked["threads"] = max(booked.get("threads", 0), int(threads))
        booked["seconds"] = booked.get("seconds", 0.0) + float(seconds)

    def add_primary_linkage(self, tree: str, **did: int) -> None:
        """Book the primary's linkage: `did` is what `cluster_by_components`
        returned beside the labels (the dense routes) or what
        `sparse_linkage_account` counted over the retained edges (the
        streaming route, whose `uncertified_merges` of 0 certifies the
        partition equal to full-matrix UPGMA's), `tree` whether the job also
        built the whole tree for the dendrogram."""
        self.primary_linkage = {**{name: int(value) for name, value in did.items()}, "tree": tree}

    def add_stream_slots(
        self, stripes: int, turns: int, tiles: list[int], pairs: list[int],
        put_bytes: list[int], finalize_wait_s: list[float],
    ) -> None:
        """Book one streaming edge walk: per-slot lists in slot order, summed
        slot by slot over the walks of a job."""
        booked = self.stream_slots
        by_slot = booked.setdefault("by_slot", [])
        names = ("tiles", "pairs", "put_bytes", "finalize_wait_s")
        for slot, did in enumerate(zip(tiles, pairs, put_bytes, finalize_wait_s)):
            if slot == len(by_slot):
                by_slot.append(dict.fromkeys(names, 0))
            for name, value in zip(names, did):
                by_slot[slot][name] += value
        for name, value in (("stripes", stripes), ("tiles", sum(tiles)), ("turns", turns)):
            booked[name] = booked.get(name, 0) + int(value)
        booked["slots"] = len(by_slot)

    def add_table_write(
        self, table: str, rows: int, bytes: int, values: int, distinct: int, fallback: str | None
    ) -> None:
        """Book one `store_db` of `table`: `rows` rows of `values` fields left
        as `bytes` bytes, for which the columnar writer rendered `distinct`
        texts; or `fallback` says why pandas wrote the frame."""
        ent = self.tables_write.setdefault(
            table, {"calls": 0, "rows": 0, "bytes": 0, "values": 0, "distinct": 0, "fallback": 0}
        )
        booked = {"calls": 1, "rows": rows, "bytes": bytes, "values": values,
                  "distinct": distinct, "fallback": int(fallback is not None)}
        for name, value in booked.items():
            ent[name] += int(value)
        if fallback is not None:
            reasons = ent.setdefault("fallback_reasons", {})
            reasons[fallback] = reasons.get(fallback, 0) + 1

    def add_ingest(self, read: dict, kept: set, workers: int, path: str, for_filter: bool) -> None:
        """Book what one ingest pass read: `read` are the dicts
        `sketch_worker.sketch_one` returned, by genome, each with its own
        `seconds`; `kept` the genomes whose sketches the job keeps; `workers`
        the processes that shared the work, `path` the kernel. `genomes` to
        `busy_seconds` are the kept genomes' alone. The filter's pass
        (`for_filter`) books beside them the genomes it read for their stats
        alone and those it sketched and the rules then dropped."""
        mine: list[dict] = []
        alone: list[dict] = []
        dropped: list[dict] = []
        for name, r in read.items():
            (alone if "bottom" not in r else mine if name in kept else dropped).append(r)
        booked: dict[str, float] = {
            "genomes": len(mine),
            "file_bytes": sum(r["file_bytes"] for r in mine),
            "bases": sum(r["length"] for r in mine),
            "valid_kmers": sum(r["valid_kmers"] for r in mine),
            "bottom_hashes": sum(len(r["bottom"]) for r in mine),
            "scaled_hashes": sum(len(r["scaled"]) for r in mine),
            "busy_seconds": float(sum(r["seconds"] for r in mine)),
        }
        if for_filter:
            booked.update({
                "stats_only_genomes": len(alone),
                "stats_only_bases": sum(r["length"] for r in alone),
                "stats_only_seconds": float(sum(r["seconds"] for r in alone)),
                "sketched_then_dropped": len(dropped),
                "sketched_then_dropped_bases": sum(r["length"] for r in dropped),
                "sketched_then_dropped_seconds": float(sum(r["seconds"] for r in dropped)),
            })
        for name, value in booked.items():
            self.ingest[name] = self.ingest.get(name, 0) + value
        self.ingest["workers"] = max(int(workers), self.ingest.get("workers", 0))
        self.ingest["path"] = path

    def add_filter(self, genomes: int, length: int, completeness: int, contamination: int) -> None:
        """Book one `d_filter_wrapper`: `genomes` came in; the rest are the
        genomes each rule dropped (a genome both rules drop counts in both)."""
        booked = {"genomes": genomes, "dropped_length": length,
                  "dropped_completeness": completeness, "dropped_contamination": contamination}
        for name, value in booked.items():
            self.filter[name] = self.filter.get(name, 0) + int(value)

    def add_resume(self, **counts: int) -> None:
        """Add to the record's `resume`: what was read back from a stopped
        job's stores and what was computed here."""
        for name, value in counts.items():
            self.resume[name] = self.resume.get(name, 0) + int(value)

    def add_index(self, **counts: int) -> None:
        """Add to the record's `index`: what an index verb read, compared,
        reclustered and published (drep_tpu/index/)."""
        for name, value in counts.items():
            self.index[name] = self.index.get(name, 0) + int(value)

    def note_drain(self, stage: str, **where: Any) -> None:
        """This job leaves at a safe boundary of `stage`: the record's
        `drain`, with the seconds the `job` span had run by then."""
        stack = self._stack()
        at = time.perf_counter() - stack[0]._t0 if stack else None
        self.drain = {"stage": stage, "after_s": None if at is None else round(at, 4), **where}

    def add_evaluate_table(self, table: str, source: str, rows: int) -> None:
        """Book where `stage:evaluate` took the pair table `table` (`mdb`,
        `ndb`) from, and its rows."""
        self.evaluate[table] = {"source": source, "rows": int(rows)}

    def add_evaluate_warnings(self, lines: dict[str, int], bytes: int, distinct: int) -> None:
        """Book one rendering of the warnings: `lines` by kind, their
        `bytes`, and the `distinct` texts rendered for them."""
        by_kind = self.evaluate.setdefault("warnings", {})
        for kind, n in lines.items():
            by_kind[kind] = by_kind.get(kind, 0) + int(n)
        self.evaluate["bytes"] = self.evaluate.get("bytes", 0) + int(bytes)
        self.evaluate["distinct"] = self.evaluate.get("distinct", 0) + int(distinct)

    # -- the programs a job builds (ISSUE 36) ------------------------------
    #
    # jax.monitoring calls its listeners on the thread that builds the
    # program, in this order: trace begins (a scalar), trace ends, lowering
    # ends, then inside the backend-compile event the persistent cache's
    # request and, on a hit, the hit and its retrieval seconds, then the
    # backend-compile event itself. What is between two events of one
    # program is kept per thread.

    def _building(self) -> dict[str, Any]:
        b = getattr(self._open, "building", None)
        if b is None:
            b = self._open.building = {"depth": 0, "inside": 0.0, "requested": False,
                                       "hit": False, "load_s": 0.0}
        return b

    def _add_built(self, fun_name: str, **seconds_and_counts: float) -> None:
        stack = self._stack()
        span = stack[-1].name if stack else "thread:" + threading.current_thread().name
        with self._lock:
            ent = self.built.setdefault((_bare_name(fun_name), span), dict.fromkeys(_BUILT_FIELDS, 0))
            for name, value in seconds_and_counts.items():
                ent[name] += value

    def on_compile_scalar(self, event: str) -> None:
        if event == _TRACE_EVENT:
            b = self._building()
            b["depth"] += 1
            if b["depth"] == 1:
                b["inside"] = 0.0

    def on_compile_event(self, event: str) -> None:
        if event == _REQUEST_EVENT:
            self._building()["requested"] = True
        elif event == _HIT_EVENT:
            self._building()["hit"] = True

    def on_compile_duration(self, event: str, seconds: float, fun_name: str) -> None:
        """Book one finished phase of one program. A function traced inside
        another's trace (`jnp.sin` inside a jitted function) is the outer
        one's time: only the outermost trace is booked, less what a program
        built whole inside it (an eager operation at trace time) booked
        itself. The backend-compile event wraps `compile_or_get_cached`, so a
        hit's retrieval lies inside it and is booked apart."""
        if event == _RETRIEVAL_EVENT:
            self._building()["load_s"] += seconds
            return
        if event not in (_TRACE_EVENT, _LOWER_EVENT, _BACKEND_EVENT):
            return
        b = self._building()
        if event == _TRACE_EVENT:
            b["depth"] = max(0, b["depth"] - 1)
            if b["depth"] == 0:
                self._add_built(fun_name, trace_s=max(0.0, seconds - b["inside"]))
            return
        if b["depth"]:
            b["inside"] += seconds
        if event == _LOWER_EVENT:
            self._add_built(fun_name, lower_s=seconds)
            return
        cache = "hit" if b["hit"] else "miss" if b["requested"] else "off"
        load_s = min(b["load_s"], seconds) if b["hit"] else 0.0
        b.update(requested=False, hit=False, load_s=0.0)
        self._add_built(fun_name, calls=1, backend_compile_s=seconds - load_s, cache_load_s=load_s,
                        hits=int(cache == "hit"), misses=int(cache == "miss"))
        # the timeline has the order: one instant a program built, none a call
        telemetry.event("compile", fun_name=_bare_name(fun_name), dur=round(seconds, 6), cache=cache)

    def _compile_report(self) -> dict[str, Any]:
        """The record's ``compile``: totals (`programs`: backend-compile
        events, executables built or loaded; `cache_misses`: requests that
        asked the persistent cache and compiled, among them the programs too
        quick for jax to store), `by_program` (one entry a function, `calls`
        the programs built of it, `span` where most of its seconds went;
        the longest SECONDARY_SHAPES_MAX, the rest summed under an empty
        name) and `by_span`."""
        with self._lock:
            rows = [(key, dict(ent)) for key, ent in self.built.items()]
        total = dict.fromkeys(_BUILT_FIELDS, 0)
        by_program: dict[str, dict[str, Any]] = {}
        by_span: dict[str, dict[str, float]] = {}
        most: dict[str, float] = {}  # a function's seconds in the span it is listed under
        for (fun_name, span), ent in rows:
            prog = by_program.setdefault(fun_name, dict.fromkeys(_BUILT_FIELDS, 0))
            if _built_seconds(ent) > most.get(fun_name, -1.0):
                prog["span"], most[fun_name] = span, _built_seconds(ent)
            for acc in (total, prog, by_span.setdefault(span, dict.fromkeys(_BUILT_FIELDS, 0))):
                for name in _BUILT_FIELDS:
                    acc[name] += ent[name]
        longest = sorted(by_program.items(), key=lambda kv: -_built_seconds(kv[1]))
        listed = [{"fun_name": name, "span": ent["span"], **_built_rounded(ent, "calls")}
                  for name, ent in longest[:SECONDARY_SHAPES_MAX]]
        if len(longest) > SECONDARY_SHAPES_MAX:
            rest = {name: sum(ent[name] for _fn, ent in longest[SECONDARY_SHAPES_MAX:])
                    for name in _BUILT_FIELDS}
            listed.append({"fun_name": "", "span": "", **_built_rounded(rest, "calls")})
        totals = _built_rounded(total, "programs")
        totals["cache_hits"], totals["cache_misses"] = totals.pop("hits"), totals.pop("misses")
        return {**totals, "by_program": listed,
                "by_span": {span: _built_rounded(ent, "programs")
                            for span, ent in sorted(by_span.items())}}

    def _job_entry(self, compile_: dict[str, Any]) -> dict[str, Any] | None:
        """The process ledger's entry of the job under way, or None outside
        any: read on the job's own thread, whose outermost open span is `job`."""
        if self.process.verb is None:
            return None
        stack = self._stack()
        return self.process.entry(stack[0]._t0 if stack else None, compile_)

    def finish_job(self) -> None:
        """Enter the job under way in the process ledger: the last thing
        inside `job`, after the record's write, so a record lists the jobs
        before its own."""
        entry = self._job_entry(self._compile_report())
        if entry is not None:
            self.process.append(entry)

    def set_gauge(self, name: str, value: float) -> None:
        """Record a derived operational value (last write wins)."""
        self.gauges[name] = float(value)

    def set_note(self, name: str, value: str) -> None:
        """Record a short WHY string beside the gauges (last write wins) —
        reasons are strings, gauges are floats; conflating them would
        corrupt the Prometheus export."""
        self.notes[name] = str(value)

    def observe(self, name: str, value: float) -> None:
        """Record one observation into the named latency histogram
        (created on first use). Hot-path cheap: one dict lookup + ring
        write; percentile math happens only at report/flush time."""
        h = self.hists.get(name)
        if h is None:
            h = self.hists[name] = Histogram()
        h.observe(value)

    def note_epoch(self, epoch: int, reason: str) -> None:
        """Record one ownership-epoch bump (reason: death/drain/join) in
        the membership history, and mirror the current epoch into the
        ``pod_epoch`` gauge so a dashboard scraping only gauges still
        sees the membership generation."""
        self.epoch_history.append(
            # drep-lint: allow[clock-mono] — cross-host timeline timestamp (trace_report cross-checks it)
            {"epoch": int(epoch), "reason": str(reason), "at": round(time.time(), 3)}
        )
        self.set_gauge("pod_epoch", float(epoch))
        # keep the event stream's stamped epoch current, and mark the
        # bump itself as a timeline instant (the membership-timeline
        # anchor tools/trace_report.py reconstructs from)
        telemetry.set_epoch(int(epoch))
        telemetry.event("epoch", epoch=int(epoch), reason=str(reason))

    def report(self, device: bool = True) -> dict[str, Any]:
        """The run record. It names the device the process ran on
        (:func:`device_record`; ``n_chips`` is its device count, the
        divisor of the per-chip rates). ``device=False`` is for the
        control-plane processes that never touch JAX (`index route`,
        `index supervise`): on a chip machine a record written at their
        exit must not open the backend — the chip belongs to the replica
        processes — so theirs names no device."""
        out: dict[str, Any] = (
            device_record() if device
            else {"platform": None, "device_kind": None, "n_devices": 0}
        )
        n_chips = max(1, out["n_devices"])
        if device:
            out["n_chips"] = n_chips
        out["stages"] = {}
        total_pairs, total_seconds = 0, 0.0
        for name, st in self.stages.items():
            rate = st.pairs / st.seconds if st.seconds > 0 else 0.0
            out["stages"][name] = {
                "pairs": st.pairs,
                "seconds": round(st.seconds, 4),
                "calls": st.calls,
                "pairs_per_sec": round(rate, 1),
                "pairs_per_sec_per_chip": round(rate / n_chips, 1),
            }
            if st.tiles_total > 0:
                out["stages"][name]["tiles_computed"] = st.tiles_computed
                out["stages"][name]["tiles_total"] = st.tiles_total
                out["stages"][name]["tile_fraction"] = round(
                    st.tiles_computed / st.tiles_total, 4
                )
            if st.tiles_skipped > 0:
                # pruning honesty: dense-equivalent totals above stay as
                # they are; the skipped count and the fraction of the
                # SCHEDULE the bitmap removed ride alongside
                out["stages"][name]["tiles_skipped_pruned"] = st.tiles_skipped
                sched = st.tiles_computed + st.tiles_skipped
                out["stages"][name]["skip_fraction"] = round(
                    st.tiles_skipped / max(sched, 1), 4
                )
            total_pairs += st.pairs
            total_seconds += st.seconds
        total_rate = total_pairs / total_seconds if total_seconds > 0 else 0.0
        out["total"] = {
            "pairs": total_pairs,
            "seconds": round(total_seconds, 4),
            "pairs_per_sec_per_chip": round(total_rate / n_chips, 1),
        }
        if self.faults:
            out["fault_tolerance"] = dict(sorted(self.faults.items()))
        if self.gauges:
            out["gauges"] = dict(sorted(self.gauges.items()))
        if self.notes:
            out["notes"] = dict(sorted(self.notes.items()))
        if self.epoch_history:
            out["epoch_history"] = list(self.epoch_history)
        if self.hists:
            out["histograms"] = {
                name: h.summary() for name, h in sorted(self.hists.items())
            }
        if self.paths:
            out["secondary_paths"] = dict(sorted(self.paths.items()))
        if self.secondary_calls:
            out["secondary_calls"] = [
                {"rows_pad": k[0], "width": k[1], "v_pad": k[2], **v}
                for k, v in sorted(self.secondary_calls.items())
            ]
        if self.chunked_calls:
            out["secondary_chunked_calls"] = [
                {"rows_pad": k[0], "v_chunk": k[1], "chunks": k[2], "width": k[3],
                 "id_dtype": k[4], **v}
                for k, v in sorted(self.chunked_calls.items())
            ]
        if self.greedy_calls:
            out["secondary_greedy_calls"] = [dict(ent) for ent in self.greedy_calls]
        if self.greedy_batched:
            out["secondary_greedy_batched"] = dict(self.greedy_batched)
        if self.primary_pack:
            out["primary_pack"] = dict(self.primary_pack)
        if self.secondary_pack:
            out["secondary_pack"] = dict(self.secondary_pack)
        if self.sketch_cache_read:
            out["sketch_cache_read"] = {**self.sketch_cache_read,
                                        "seconds": round(self.sketch_cache_read["seconds"], 4)}
        if self.primary_linkage:
            out["primary_linkage"] = dict(self.primary_linkage)
        if self.stream_slots:
            out["primary_stream_slots"] = {
                **self.stream_slots,
                "by_slot": [{**ent, "finalize_wait_s": round(ent["finalize_wait_s"], 4)}
                            for ent in self.stream_slots["by_slot"]],
            }
        if self.tables_write:
            out["tables_write"] = {name: dict(ent) for name, ent in sorted(self.tables_write.items())}
        if self.ingest:
            out["ingest"] = {name: round(value, 4) if isinstance(value, float) else value
                             for name, value in self.ingest.items()}
        if self.filter:
            out["filter"] = dict(self.filter)
        if self.evaluate:
            out["evaluate"] = {name: dict(ent) if isinstance(ent, dict) else ent
                               for name, ent in self.evaluate.items()}
        if self.resume:
            out["resume"] = dict(self.resume)
        if self.index:
            out["index"] = dict(self.index)
        if self.drain:
            out["drain"] = dict(self.drain)
        phases = self._phases_report()
        if phases:
            out["phases"] = phases
        out["compile"] = self._compile_report()
        out["process"] = self.process.report(self._job_entry(out["compile"]))
        return out

    def _phases_report(self) -> dict[str, dict[str, Any]]:
        """``{name: {"seconds", "self_seconds", "calls", "thread", ...}}``
        with the host's fields of the module docstring. A span of another
        thread than the main one is kept apart under ``<name>@other`` and
        carries the thread's fields and the collector's alone. The calling
        thread's OPEN spans are counted as far as they have come, on a fresh
        read of the host: the record is written inside ``job``."""
        now = time.perf_counter()
        host_now = _thread_host().take(now)
        with self._lock:
            acc = {k: _Phase(p.seconds, p.self_seconds, p.calls, list(p.host), list(p.self_host),
                             p.gc_s, p.gc_collections) for k, p in self.phases.items()}
        main = _on_main_thread()
        inner, host_inner = 0.0, _NO_HOST  # so far, of the open span one level in
        for sp in reversed(self._stack()):
            dur = now - sp._t0
            host = _minus(host_now, sp._host0)
            a = acc.setdefault((sp.name, main), _Phase())
            a.seconds += dur
            a.self_seconds += dur - sp._child - inner
            a.calls += sp._calls
            a.host = _plus(a.host, host)
            a.self_host = _plus(a.self_host, _minus(_minus(host, sp._host_child or _NO_HOST), host_inner))
            a.gc_s += sp._gc_s
            a.gc_collections += sp._gc_n
            inner, host_inner = dur, host
        out = {}
        for (name, on_main), p in sorted(acc.items()):
            whole, own = dict(zip(_HOST_FIELDS, p.host)), dict(zip(_HOST_FIELDS, p.self_host))
            ent = {"seconds": round(p.seconds, 4), "self_seconds": round(p.self_seconds, 4),
                   "calls": p.calls, "thread": "main" if on_main else "other"}
            if on_main:
                ent.update(cpu_s=round(whole["cpu_s"], 4), self_cpu_s=round(own["cpu_s"], 4),
                           sys_s=round(whole["sys_s"], 4), self_sys_s=round(own["sys_s"], 4),
                           self_minor_faults=int(own["minor_faults"]),
                           self_major_faults=int(own["major_faults"]))
            ent.update(self_thread_cpu_s=round(own["thread_cpu_s"], 4),
                       self_invol_switches=int(own["invol_switches"]),
                       self_vol_switches=int(own["vol_switches"]),
                       gc_s=round(p.gc_s, 4), gc_collections=p.gc_collections)
            out[name if on_main else name + "@other"] = ent
        return out

    def write(self, log_dir: str, device: bool = True) -> str:
        # atomic (utils/durableio.py): a SIGKILL mid-write must not leave
        # a torn perf_counters.json that poisons the next run's tooling —
        # the counters are the honesty record, they get the same
        # durability as the shards they describe
        from drep_tpu.utils.ckptmeta import atomic_write_bytes

        path = os.path.join(log_dir, "perf_counters.json")
        atomic_write_bytes(
            path,
            json.dumps(self.report(device), indent=1, sort_keys=True).encode(),
        )
        return path

    def reset(self) -> None:
        self.stages.clear()
        self.faults.clear()
        self.gauges.clear()
        self.notes.clear()
        self.epoch_history.clear()
        self.hists.clear()
        self.paths.clear()
        self.secondary_calls.clear()
        self.chunked_calls.clear()
        self.greedy_calls.clear()
        self.greedy_batched.clear()
        self.primary_pack.clear()
        self.secondary_pack.clear()
        self.sketch_cache_read.clear()
        self.primary_linkage.clear()
        self.stream_slots.clear()
        self.tables_write.clear()
        self.ingest.clear()
        self.filter.clear()
        self.evaluate.clear()
        self.resume.clear()
        self.index.clear()
        self.drain = {}
        with self._lock:
            self.phases.clear()  # a span open now stays open and books when it closes
            self.built.clear()


counters = Counters()  # the process-global instance used by the pipeline
gc.callbacks.append(_on_gc)

_listening = False


def listen_for_compiles() -> None:
    """Register the jax.monitoring listeners that book the record's
    ``compile`` section into the process-global counters: once a process,
    from the bring-up of a verb that computes (``workflows._bring_up``,
    ``_init_index``). Never at import: the ingest pool's workers and the
    control-plane verbs import this module without JAX."""
    global _listening
    if _listening:
        return
    _listening = True
    import jax

    jax.monitoring.register_scalar_listener(
        lambda event, _value, **_kw: counters.on_compile_scalar(event))
    jax.monitoring.register_event_listener(
        lambda event, **_kw: counters.on_compile_event(event))
    jax.monitoring.register_event_duration_secs_listener(
        lambda event, seconds, **kw: counters.on_compile_duration(
            event, seconds, str(kw.get("fun_name", ""))))


# -- periodic Prometheus-textfile flush (ISSUE 10 satellite) ----------------
#
# Long runs were scrapeable only at exit (Counters.write). With
# DREP_TPU_METRICS_FLUSH_S > 0 (default off — zero threads, zero files),
# a daemon thread publishes the counters/gauges every cadence to
# <wd>/log/metrics.prom in the Prometheus textfile-collector format,
# atomically (utils/durableio.py) so a scrape can never read a torn file.

METRICS_FLUSH_ENV = "DREP_TPU_METRICS_FLUSH_S"
METRICS_NAME = "metrics.prom"

_METRICS: dict[str, Any] = {"stop": None, "thread": None, "log_dir": None}


def metrics_flush_cadence_s() -> float:
    from drep_tpu.utils import envknobs

    try:
        return envknobs.env_float(METRICS_FLUSH_ENV)
    except ValueError:
        return 0.0


def _prom_escape(v: str) -> str:
    return str(v).replace("\\", "\\\\").replace('"', '\\"').replace("\n", "\\n")


def prom_text(c: Counters | None = None) -> str:
    """The counters/gauges as Prometheus textfile-collector lines. Stage
    pair/second/call totals, fault-event totals by kind, every gauge, the
    pod epoch-bump count, and the flush timestamp (staleness detection on
    the scraper side)."""
    c = counters if c is None else c
    lines = [
        "# HELP drep_tpu_stage_pairs_total pair comparisons recorded per stage",
        "# TYPE drep_tpu_stage_pairs_total counter",
    ]
    for name, st in sorted(c.stages.items()):
        tag = f'{{stage="{_prom_escape(name)}"}}'
        lines.append(f"drep_tpu_stage_pairs_total{tag} {st.pairs}")
    lines += [
        "# TYPE drep_tpu_stage_seconds_total counter",
        *(
            f'drep_tpu_stage_seconds_total{{stage="{_prom_escape(n)}"}} '
            f"{round(st.seconds, 6)}"
            for n, st in sorted(c.stages.items())
        ),
        "# TYPE drep_tpu_stage_calls_total counter",
        *(
            f'drep_tpu_stage_calls_total{{stage="{_prom_escape(n)}"}} {st.calls}'
            for n, st in sorted(c.stages.items())
        ),
        "# HELP drep_tpu_fault_events_total fault-tolerance events by kind",
        "# TYPE drep_tpu_fault_events_total counter",
        *(
            f'drep_tpu_fault_events_total{{kind="{_prom_escape(k)}"}} {v}'
            for k, v in sorted(c.faults.items())
        ),
        "# HELP drep_tpu_gauge derived operational values (last write wins)",
        "# TYPE drep_tpu_gauge gauge",
        *(
            f'drep_tpu_gauge{{name="{_prom_escape(g)}"}} {v}'
            for g, v in sorted(c.gauges.items())
        ),
        "# HELP drep_tpu_latency summary stats over the recent observation window",
        "# TYPE drep_tpu_latency gauge",
        *(
            f'drep_tpu_latency{{name="{_prom_escape(n)}",stat="{stat}"}} {v}'
            for n, h in sorted(c.hists.items())
            for stat, v in h.summary().items()
        ),
        "# TYPE drep_tpu_epoch_bumps_total counter",
        f"drep_tpu_epoch_bumps_total {len(c.epoch_history)}",
        "# TYPE drep_tpu_metrics_flush_timestamp_seconds gauge",
        # drep-lint: allow[clock-mono] — Prometheus convention: epoch-seconds gauge
        f"drep_tpu_metrics_flush_timestamp_seconds {round(time.time(), 3)}",
    ]
    return "\n".join(lines) + "\n"


def flush_metrics(log_dir: str, c: Counters | None = None) -> str:
    """One atomic publish of the current counters to
    ``<log_dir>/metrics.prom`` (the durable-I/O rename path — a scrape
    mid-publish reads the previous whole file, never a torn one)."""
    from drep_tpu.utils.durableio import atomic_write_bytes

    path = os.path.join(log_dir, METRICS_NAME)
    atomic_write_bytes(path, prom_text(c).encode())
    return path


def start_metrics_flush(log_dir: str) -> bool:
    """Launch the periodic flusher when ``DREP_TPU_METRICS_FLUSH_S`` > 0
    (default off: no thread, no file). Idempotent per run — a second
    start replaces the first (library users run several workflows per
    process)."""
    stop_metrics_flush()
    cadence = metrics_flush_cadence_s()
    _METRICS["log_dir"] = log_dir
    if cadence <= 0:
        return False
    stop = threading.Event()

    def loop() -> None:
        while not stop.wait(cadence):
            try:
                flush_metrics(log_dir)
            except Exception:  # noqa: BLE001 — a flaky flush must never kill the run
                pass

    t = threading.Thread(target=loop, daemon=True, name="drep-metrics-flush")
    _METRICS["stop"] = stop
    _METRICS["thread"] = t
    t.start()
    return True


def stop_metrics_flush(final: bool = False) -> None:
    """Stop the flusher; with `final`, publish one last snapshot so the
    scrape file agrees with the exit-time perf_counters.json."""
    stop, t = _METRICS["stop"], _METRICS["thread"]
    _METRICS["stop"] = _METRICS["thread"] = None
    if stop is not None:
        stop.set()
    if t is not None:
        t.join(timeout=2.0)
    if final and stop is not None and _METRICS["log_dir"]:
        with contextlib.suppress(Exception):
            flush_metrics(_METRICS["log_dir"])


@contextlib.contextmanager
def trace(trace_dir: str | None) -> Iterator[None]:
    """jax.profiler.trace round a whole job when a directory is given;
    no-op otherwise. The options are the benchmark harness's
    (benchmark/run.py), so an operator's trace is the trace the benchmark
    reduces: Python frames off (they swamp a whole job's trace), host
    TraceMe events on — the ``drep:<span>`` events of :meth:`Counters.span`."""
    if not trace_dir:
        yield
        return
    import jax

    os.makedirs(trace_dir, exist_ok=True)
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 2
    with jax.profiler.trace(trace_dir, profiler_options=opts):
        yield
