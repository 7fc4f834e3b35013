#!/usr/bin/env python
"""Pod-wide timeline reconstruction from structured event logs.

Merges every member's append-only event log (``events.p<N>.jsonl``,
written by drep_tpu/utils/telemetry.py under ``<wd>/log``) into:

- a **Chrome/Perfetto trace-event JSON** (``--chrome``, default
  ``<log_dir>/trace.json``): one track per process, "X" complete events
  for spans (controller stages, streaming stripes, ring steps, per-block
  recovery), instants for faults and membership churn, and explicit
  ``UNCLOSED`` markers for spans a crash left open — load it at
  chrome://tracing or ui.perfetto.dev;
- a **text forensics report** (stdout): per-stage critical path,
  stripe/ring-step latency percentiles, straggler and idle-gap
  detection, the fault timeline, and the membership timeline (every
  epoch bump with its reason, drain/death/join verdicts in causal
  order) — cross-checked against ``perf_counters.json``'s
  ``epoch_history`` when one sits beside the logs.

- a **phases table** (stdout, last): every span name with its seconds,
  self seconds and what the host spent inside it (ISSUE 52: cores kept
  busy, kernel seconds, memory first touched, seconds the opening thread
  was off its CPU, collector seconds), largest self seconds first — from
  the ``perf_counters.json`` beside the logs, else rebuilt from the E
  lines' args; given a ``perf_counters.json`` alone, that table is all.

Usage::

    python tools/trace_report.py <wd>/log                # report + trace.json
    python tools/trace_report.py <wd>/log --chrome /tmp/t.json
    python tools/trace_report.py <wd>/log --no-chrome    # report only
    python tools/trace_report.py <wd>/log/perf_counters.json   # the phases table of a record

Crash evidence is first-class: a torn final line (SIGKILL mid-write) is
expected and reported as such, never an error; an event file that simply
STOPS marks where its process died. CPU-only, no JAX backend required
(utils/profiling.py's counter report falls back the same way).
"""

from __future__ import annotations

import argparse
import glob
import json
import os
import resource
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

from drep_tpu.utils.durableio import atomic_write_bytes  # noqa: E402
from drep_tpu.utils.profiling import HOST_ARGS  # noqa: E402

EVENTS_GLOB = "events.p*.jsonl"

# span names whose durations feed the latency/straggler/gap analysis
WORK_SPANS = ("stripe", "ring_step", "ring_block_recover")
# instants that narrate membership churn, in the causal order the
# protocol produces them
MEMBERSHIP_EVENTS = (
    "drain_announce", "drain_adopted", "death_verdict", "join_admitted",
    "join_adopted", "joined", "epoch", "re_deal", "done", "fenced",
)


def load_events(log_dir: str) -> dict:
    """Parse every member's event log. Returns ``{"events": [...],
    "files": n, "torn_tails": [paths], "bad_lines": [(path, lineno)]}`` —
    events sorted by wall clock (pod members share a host/fleet clock;
    in-process durations always come from the monotonic fields). A torn
    FINAL line is crash evidence (counted, never an error); a torn
    mid-file line is real damage and lands in ``bad_lines``."""
    events: list[dict] = []
    torn: list[str] = []
    bad: list[tuple[str, int]] = []
    paths = sorted(glob.glob(os.path.join(log_dir, EVENTS_GLOB)))
    for path in paths:
        with open(path, "rb") as f:
            raw = f.read()
        body, _, tail = raw.rpartition(b"\n")
        if tail.strip():
            torn.append(path)  # no final newline: the SIGKILL tear
        lines = body.split(b"\n") if body else []
        for i, line in enumerate(lines):
            if not line.strip():
                continue
            try:
                rec = json.loads(line.decode())
            except (ValueError, UnicodeDecodeError):
                bad.append((path, i + 1))
                continue
            if isinstance(rec, dict) and "ev" in rec:
                rec["_file"] = os.path.basename(path)
                events.append(rec)
    events.sort(key=lambda r: (r.get("wall", 0.0), r.get("pid", 0)))
    return {
        "events": events, "files": len(paths), "torn_tails": torn,
        "bad_lines": bad,
    }


def pair_spans(events: list[dict]) -> tuple[list[dict], list[dict]]:
    """Match B/E records per (pid, name) nesting stack. Returns (spans,
    unclosed_B_records); each span dict carries pid/ev/args, begin/end
    wall stamps, the monotonic duration (the E record's ``dur``) and, apart
    from the span's own args, what the host spent inside it (``host``: the
    E record's ``profiling.HOST_ARGS``, whole deltas, those that moved)."""
    stacks: dict[tuple[int, str], list[dict]] = {}
    spans: list[dict] = []
    for rec in events:
        ph = rec.get("ph")
        if ph not in ("B", "E"):
            continue
        key = (rec.get("pid", 0), rec["ev"])
        if ph == "B":
            stacks.setdefault(key, []).append(rec)
            continue
        stack = stacks.get(key)
        begin = stack.pop() if stack else None
        args = dict(rec.get("args") or {})
        dur = args.pop("dur", None)
        host = {name: args.pop(name) for name in HOST_ARGS if name in args}
        if dur is None and begin is not None:
            dur = max(0.0, rec.get("mono", 0.0) - begin.get("mono", 0.0))
        begin_wall = (
            begin.get("wall")
            if begin is not None
            else rec.get("wall", 0.0) - (dur or 0.0)
        )
        spans.append(
            {
                "pid": rec.get("pid", 0),
                "ev": rec["ev"],
                "args": args,
                "epoch": rec.get("epoch", 0),
                "begin": begin_wall,
                "end": rec.get("wall", 0.0),
                "dur": float(dur or 0.0),
                "host": host,
            }
        )
    unclosed = [b for stack in stacks.values() for b in stack]
    unclosed.sort(key=lambda r: r.get("wall", 0.0))
    return spans, unclosed


def phases_from_events(events: list[dict]) -> dict[str, dict]:
    """The record's ``phases`` section rebuilt from an event log, for a job
    that left no record: per name the spans' seconds, calls and the E lines'
    host deltas, with self values by nesting (a span's less what the spans
    closed inside it cover). The log names no thread: a worker thread's span
    nests under whatever its process had open."""
    phases: dict[str, dict] = {}
    open_spans: dict[int, list[dict]] = {}  # per process, outermost first
    for rec in events:
        ph = rec.get("ph")
        if ph not in ("B", "E"):
            continue
        stack = open_spans.setdefault(rec.get("pid", 0), [])
        if ph == "B":
            stack.append({"ev": rec["ev"], "mono": rec.get("mono", 0.0), "child": {}})
            continue
        at = next((i for i in range(len(stack) - 1, -1, -1) if stack[i]["ev"] == rec["ev"]), None)
        if at is None:
            continue  # an E with no B: the log began inside the span
        frame = stack.pop(at)
        args = rec.get("args") or {}
        whole = {name: args[name] for name in HOST_ARGS if name in args}
        whole["seconds"] = args.get("dur", max(0.0, rec.get("mono", 0.0) - frame["mono"]))
        if at > 0:
            covered = stack[at - 1]["child"]
            for name, v in whole.items():
                if not name.startswith("gc_"):  # the collector's are self values already
                    covered[name] = covered.get(name, 0) + v
        ent = phases.setdefault(rec["ev"], {"calls": 0})
        ent["calls"] += 1
        for name, v in whole.items():
            if name in ("seconds", "cpu_s", "sys_s") or name.startswith("gc_"):
                ent[name] = ent.get(name, 0) + v
            if not name.startswith("gc_"):
                ent["self_" + name] = ent.get("self_" + name, 0) + v - frame["child"].get(name, 0)
    return phases


def phases_table(phases: dict[str, dict]) -> str:
    """One line a phase, largest self seconds first: calls, seconds, self
    seconds, cores (the process's CPU over the span's seconds: above 1 its
    worker threads scaled, far under 1 it waited), the kernel's self
    seconds, MiB first touched (4 KiB faults; a floor under transparent
    huge pages; ``-`` where the kernel counts none in any phase), seconds
    the opening thread was off its CPU, collector
    seconds. A worker thread's phase (``@other``) has the thread's alone."""
    page_mib = resource.getpagesize() / 2**20
    # a sandboxed kernel counts no page faults: zero in every phase is no source, not 0.0 MiB
    counts_faults = any(p.get("self_minor_faults") for p in phases.values())
    head = (f"  {'phase':<34} {'calls':>7} {'seconds':>9} {'self':>9} {'cores':>6} "
            f"{'sys':>8} {'MiB new':>9} {'off-CPU':>8} {'gc':>7}")
    lines = [head]

    def cell(value, width: int, digits: int) -> str:
        return f"{'-':>{width}}" if value is None else f"{value:>{width}.{digits}f}"

    for name, p in sorted(phases.items(), key=lambda kv: -kv[1].get("self_seconds", 0.0)):
        seconds, own = p.get("seconds", 0.0), p.get("self_seconds", 0.0)
        cores = p["cpu_s"] / seconds if "cpu_s" in p and seconds > 0 else None
        faults = p.get("self_minor_faults") if counts_faults else None
        off_cpu = own - p["self_thread_cpu_s"] if "self_thread_cpu_s" in p else None
        lines.append(
            f"  {name:<34} {p.get('calls', 0):>7} {seconds:>9.3f} {own:>9.3f} {cell(cores, 6, 2)} "
            f"{cell(p.get('self_sys_s'), 8, 3)} {cell(None if faults is None else faults * page_mib, 9, 1)} "
            f"{cell(off_cpu, 8, 3)} {cell(p.get('gc_s'), 7, 3)}")
    return "\n".join(lines)


def membership_timeline(events: list[dict]) -> list[dict]:
    """The pod's epoch history reconstructed from the merged stream:
    one entry per (epoch, reason), stamped with the EARLIEST wall time
    any member noted the bump (every member emits its own ``epoch``
    instant; the timeline is the deduplicated union). Equals an ORIGINAL
    member's ``perf_counters.json`` ``epoch_history`` exactly — same
    epochs, same reasons, same order; a joiner's (or early-drained
    member's) history is a contiguous run of it
    (:func:`timeline_matches_history` accepts both)."""
    seen: dict[tuple[int, str], float] = {}
    for rec in events:
        if rec.get("ev") != "epoch" or rec.get("ph") != "i":
            continue
        args = rec.get("args") or {}
        key = (int(args.get("epoch", rec.get("epoch", 0))), str(args.get("reason", "?")))
        wall = rec.get("wall", 0.0)
        if key not in seen or wall < seen[key]:
            seen[key] = wall
    return [
        {"epoch": e, "reason": r, "at": round(w, 3)}
        for (e, r), w in sorted(seen.items(), key=lambda kv: (kv[0][0], kv[1]))
    ]


def timeline_matches_history(events: list[dict], counters_doc: dict) -> bool:
    """Does the merged membership timeline agree with one process's
    ``epoch_history`` (epoch numbers + reasons, in order)?

    An ORIGINAL member's history must equal the timeline exactly. A
    member with a legitimately PARTIAL view — a joiner never notes the
    bumps that predate its admission, a drained member misses the bumps
    after its exit — is accepted when its history is a contiguous run of
    the merged timeline (the view the protocol gave it); anything else
    is a real disagreement between the counters and the event stream."""
    want = [
        (int(h["epoch"]), str(h["reason"]))
        for h in counters_doc.get("epoch_history", [])
    ]
    got = [(t["epoch"], t["reason"]) for t in membership_timeline(events)]
    if got == want:
        return True
    if not want:
        return False  # a churned timeline vs an empty history: disagree
    return any(
        got[i : i + len(want)] == want for i in range(len(got) - len(want) + 1)
    )


def chrome_trace(events: list[dict]) -> dict:
    """The merged stream as Chrome trace-event JSON: per-process tracks,
    X events for spans, instants for point events, UNCLOSED markers for
    crash-open spans. Timestamps are wall-clock microseconds rebased to
    the earliest event."""
    if not events:
        return {"traceEvents": [], "displayTimeUnit": "ms"}
    t0 = min(r.get("wall", 0.0) for r in events)

    def ts(wall: float) -> float:
        return round((wall - t0) * 1e6, 1)

    out: list[dict] = []
    for pid in sorted({r.get("pid", 0) for r in events}):
        out.append(
            {
                "name": "process_name", "ph": "M", "pid": pid, "tid": 0,
                "args": {"name": f"drep-tpu p{pid}"},
            }
        )
    spans, unclosed = pair_spans(events)
    for sp in spans:
        out.append(
            {
                "name": sp["ev"], "ph": "X", "pid": sp["pid"], "tid": 0,
                "ts": ts(sp["begin"]), "dur": round(sp["dur"] * 1e6, 1),
                "args": {**sp["args"], "epoch": sp["epoch"]},
            }
        )
    for rec in events:
        if rec.get("ph") != "i":
            continue
        out.append(
            {
                "name": rec["ev"], "ph": "i", "s": "p",
                "pid": rec.get("pid", 0), "tid": 0,
                "ts": ts(rec.get("wall", t0)),
                "args": {**(rec.get("args") or {}), "epoch": rec.get("epoch", 0)},
            }
        )
    for b in unclosed:
        out.append(
            {
                "name": f"UNCLOSED {b['ev']}", "ph": "i", "s": "p",
                "pid": b.get("pid", 0), "tid": 0,
                "ts": ts(b.get("wall", t0)),
                "args": {
                    **(b.get("args") or {}),
                    "note": "span open at end of log — crash evidence",
                },
            }
        )
    run = next((r.get("run") for r in events if r.get("run")), None)
    return {
        "traceEvents": out, "displayTimeUnit": "ms",
        "metadata": {"run": run},
    }


def stall_diagnosis(log_dir: str) -> dict | None:
    """Name a wedged run's stall site from its own event logs: WHERE a
    traced run that overran its watchdog stalled, not just that it did.
    Returns None when there are no events to read.

    The diagnosis is the crash-forensics triple:

    - ``stall_site``: the most recently OPENED still-open span — what
      was in flight when the log went quiet (the "B" with no "E" that
      telemetry.py documents as the crash evidence);
    - ``open_spans``: every unclosed span, oldest first (nesting shows
      the stage -> stripe containment);
    - ``last_event`` + ``idle_gaps``: where the stream stopped, and any
      silent stretches between work spans before it did.
    """
    loaded = load_events(log_dir)
    events = loaded["events"]
    if not events:
        return None
    spans, unclosed = pair_spans(events)
    t_lo = min(r.get("wall", 0.0) for r in events)
    t_hi = max(r.get("wall", 0.0) for r in events)
    last = events[-1]
    out: dict = {
        "log_dir": os.path.abspath(log_dir),
        "n_events": len(events),
        "wall_span_s": round(t_hi - t_lo, 3),
        "last_event": {
            "ev": last.get("ev"), "ph": last.get("ph"),
            "pid": last.get("pid", 0),
            "at_s": round(last.get("wall", t_lo) - t_lo, 3),
        },
        "open_spans": [
            {
                "pid": b.get("pid", 0), "ev": b.get("ev"),
                "args": b.get("args") or {},
                "opened_at_s": round(b.get("wall", t_lo) - t_lo, 3),
                "open_for_s": round(t_hi - b.get("wall", t_lo), 3),
            }
            for b in unclosed
        ],
        "torn_tails": [os.path.basename(p) for p in loaded["torn_tails"]],
    }
    if unclosed:
        # the INNERMOST in-flight work: the latest-opened unclosed span
        out["stall_site"] = out["open_spans"][-1]
    work = [sp for sp in spans if sp["ev"] in WORK_SPANS]
    if work:
        med = _median([sp["dur"] for sp in work])
        gap_floor = max(1.0, 3 * med)
        gaps = []
        by_pid: dict[int, list] = {}
        for sp in work:
            by_pid.setdefault(sp["pid"], []).append(sp)
        for pid, mine in by_pid.items():
            mine.sort(key=lambda s: s["begin"])
            for a, b in zip(mine, mine[1:]):
                gap = b["begin"] - a["end"]
                if gap > gap_floor:
                    gaps.append(
                        {"pid": pid, "gap_s": round(gap, 3),
                         "after_s": round(a["end"] - t_lo, 3)}
                    )
        if gaps:
            out["idle_gaps"] = sorted(
                gaps, key=lambda g: -g["gap_s"]
            )[:8]
    return out


def _percentile(sorted_vals: list[float], q: float) -> float:
    if not sorted_vals:
        return 0.0
    idx = min(len(sorted_vals) - 1, max(0, round(q * (len(sorted_vals) - 1))))
    return sorted_vals[int(idx)]


def _median(vals: list[float]) -> float:
    return _percentile(sorted(vals), 0.5)


def text_report(events: list[dict], counters_doc: dict | None = None) -> str:
    """The operator-facing forensics: stage critical path, work-span
    latency percentiles + stragglers, idle-gap detection, the fault
    timeline, and the membership timeline (cross-checked against
    ``epoch_history`` when perf counters are given)."""
    lines: list[str] = []
    if not events:
        return "trace report: no events\n"
    spans, unclosed = pair_spans(events)
    pids = sorted({r.get("pid", 0) for r in events})
    t_lo = min(r.get("wall", 0.0) for r in events)
    t_hi = max(r.get("wall", 0.0) for r in events)
    run = next((r.get("run") for r in events if r.get("run")), "?")
    lines.append(
        f"run {run}: {len(events)} events from {len(pids)} process(es) "
        f"{pids}, wall span {t_hi - t_lo:.2f}s"
    )

    # -- per-stage critical path ------------------------------------------
    stages: dict[str, list[dict]] = {}
    for sp in spans:
        if sp["ev"].startswith("stage:"):
            stages.setdefault(sp["ev"], []).append(sp)
    if stages:
        lines.append("\nstage critical path (earliest open -> latest close, all processes):")
        order = sorted(stages.items(), key=lambda kv: min(s["begin"] for s in kv[1]))
        for name, sps in order:
            begin = min(s["begin"] for s in sps)
            end = max(s["end"] for s in sps)
            busy = sum(s["dur"] for s in sps)
            lines.append(
                f"  {name:<28} wall {end - begin:>9.2f}s  "
                f"busy {busy:>9.2f}s over {len(sps)} span(s)"
            )

    # -- work-span latencies + stragglers ---------------------------------
    for ev in WORK_SPANS:
        durs = sorted(sp["dur"] for sp in spans if sp["ev"] == ev)
        if not durs:
            continue
        med = _percentile(durs, 0.5)
        lines.append(
            f"\n{ev} latency over {len(durs)} span(s): "
            f"p50 {med:.3f}s  p90 {_percentile(durs, 0.9):.3f}s  "
            f"p99 {_percentile(durs, 0.99):.3f}s  max {durs[-1]:.3f}s"
        )
        if med > 0:
            stragglers = [
                sp for sp in spans if sp["ev"] == ev and sp["dur"] > 3 * med
            ]
            for sp in sorted(stragglers, key=lambda s: -s["dur"])[:8]:
                lines.append(
                    f"  straggler: p{sp['pid']} {sp['args']} "
                    f"{sp['dur']:.3f}s ({sp['dur'] / med:.1f}x median)"
                )

    # -- idle-gap detection ------------------------------------------------
    work = [sp for sp in spans if sp["ev"] in WORK_SPANS]
    if work:
        med = _median([sp["dur"] for sp in work])
        gap_floor = max(1.0, 3 * med)
        gaps: list[tuple[float, int, float]] = []
        for pid in pids:
            mine = sorted(
                (sp for sp in work if sp["pid"] == pid), key=lambda s: s["begin"]
            )
            for a, b in zip(mine, mine[1:]):
                gap = b["begin"] - a["end"]
                if gap > gap_floor:
                    gaps.append((gap, pid, a["end"]))
        if gaps:
            lines.append(f"\nidle gaps > {gap_floor:.1f}s between work spans:")
            for gap, pid, at in sorted(gaps, reverse=True)[:8]:
                lines.append(f"  p{pid}: {gap:.2f}s idle starting +{at - t_lo:.2f}s")
        else:
            lines.append(f"\nno idle gaps > {gap_floor:.1f}s between work spans")

    # -- fault timeline ----------------------------------------------------
    faults = [r for r in events if r.get("ev") == "fault" and r.get("ph") == "i"]
    if faults:
        by_kind: dict[str, int] = {}
        for r in faults:
            kind = (r.get("args") or {}).get("kind", "?")
            by_kind[kind] = by_kind.get(kind, 0) + int((r.get("args") or {}).get("n", 1))
        lines.append("\nfault events: " + ", ".join(
            f"{k}={v}" for k, v in sorted(by_kind.items())
        ))

    # -- membership timeline ----------------------------------------------
    churn = [
        r for r in events
        if r.get("ph") == "i" and r.get("ev") in MEMBERSHIP_EVENTS
    ]
    if churn:
        lines.append("\nmembership timeline (wall order):")
        for r in churn:
            args = r.get("args") or {}
            detail = " ".join(f"{k}={v}" for k, v in args.items())
            lines.append(
                f"  +{r.get('wall', t_lo) - t_lo:>8.3f}s  p{r.get('pid', 0)}  "
                f"{r['ev']:<16} {detail}"
            )
    timeline = membership_timeline(events)
    if timeline:
        lines.append("\nepoch history (deduplicated across members):")
        for t in timeline:
            lines.append(f"  epoch {t['epoch']}: {t['reason']}")
        if counters_doc is not None:
            ok = timeline_matches_history(events, counters_doc)
            lines.append(
                "epoch history vs perf_counters.json: "
                + ("MATCH" if ok else "MISMATCH — counters disagree with the event stream")
            )

    if unclosed:
        lines.append("\ncrash evidence — spans open at end of log:")
        for b in unclosed:
            lines.append(
                f"  p{b.get('pid', 0)}: {b['ev']} {b.get('args') or {}} "
                f"(+{b.get('wall', t_lo) - t_lo:.3f}s)"
            )

    # -- where the host's time went ----------------------------------------
    recorded = (counters_doc or {}).get("phases")
    phases = recorded or phases_from_events(events)
    if phases:
        lines.append("\nphases, largest self seconds first ("
                     + ("perf_counters.json" if recorded else "rebuilt from the event log") + "):")
        lines.append(phases_table(phases))
    return "\n".join(lines) + "\n"


def main(argv: list[str] | None = None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("log_dir", help="directory holding events.p*.jsonl (e.g. <wd>/log), or a "
                                    "perf_counters.json for its phases table alone")
    ap.add_argument("--chrome", default=None,
                    help="write the Chrome trace-event JSON here "
                         "(default <log_dir>/trace.json)")
    ap.add_argument("--no-chrome", action="store_true",
                    help="text report only")
    ap.add_argument("--counters", default=None,
                    help="perf_counters.json to cross-check the membership "
                         "timeline against (default: one beside the logs)")
    args = ap.parse_args(argv)

    if os.path.isfile(args.log_dir):
        with open(args.log_dir, encoding="utf-8") as f:
            phases = json.load(f).get("phases") or {}
        print(f"phases of {args.log_dir}, largest self seconds first:")
        print(phases_table(phases))
        return 0
    # a workdir was given instead of its log dir: follow the layout
    log_dir = args.log_dir
    if not glob.glob(os.path.join(log_dir, EVENTS_GLOB)) and os.path.isdir(
        os.path.join(log_dir, "log")
    ):
        log_dir = os.path.join(log_dir, "log")
    loaded = load_events(log_dir)
    if not loaded["events"]:
        print(
            f"trace report: no {EVENTS_GLOB} under {log_dir} — was the run "
            f"traced? (--events on / DREP_TPU_EVENTS=on)", file=sys.stderr,
        )
        return 1
    for path in loaded["torn_tails"]:
        print(
            f"note: torn final line in {path} (crash evidence — the process "
            f"died mid-write)", file=sys.stderr,
        )
    for path, lineno in loaded["bad_lines"]:
        print(f"WARNING: unparseable mid-file line {path}:{lineno}", file=sys.stderr)

    counters_doc = None
    cpath = args.counters or os.path.join(log_dir, "perf_counters.json")
    if os.path.exists(cpath):
        try:
            with open(cpath, encoding="utf-8") as f:
                counters_doc = json.load(f)
        except (OSError, ValueError) as e:
            print(f"WARNING: unreadable counters {cpath}: {e}", file=sys.stderr)

    sys.stdout.write(text_report(loaded["events"], counters_doc))
    if not args.no_chrome:
        out = args.chrome or os.path.join(log_dir, "trace.json")
        # atomic publish: a kill mid-dump must not leave a torn trace a
        # later `chrome://tracing` load half-parses (PR 5 funnel)
        # drep-lint: allow[reader-purity] — the tool's OWN output artifact (trace.json beside the logs it read); the store/logs themselves are never touched
        atomic_write_bytes(out, json.dumps(chrome_trace(loaded["events"])).encode())
        print(f"chrome trace written to {out} (load at chrome://tracing)")
    return 0


if __name__ == "__main__":
    sys.exit(main())
