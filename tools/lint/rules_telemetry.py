"""Rule ``telemetry-gate``: event emission only through the gated
telemetry API; no ad-hoc writes into the run's ``<wd>/log/`` sink."""

from __future__ import annotations

import ast

from .engine import Finding, Rule
from .model import RepoModel, iter_calls, write_call_kind

RULE_ID = "telemetry-gate"

# the sink's own modules may touch its files and private surface
ALLOWED = frozenset({
    "drep_tpu/utils/telemetry.py",
    "drep_tpu/utils/profiling.py",
})

# path fragments that identify the observability sink's namespace
_SINK_MARKERS = ("events.p", ".jsonl", "metrics.prom", "events.runid")

EXPLAIN = """\
PR 10's observability contract has two halves this rule protects. The
zero-overhead-off guarantee: every emission site is one falsy dict
lookup when --events is off — code that writes into <wd>/log/ directly
(instead of telemetry.event()/counters.span()) bypasses the gate and costs I/O
on every run. And the crash-forensics format: the sink appends whole
flushed JSONL lines so a SIGKILL tears at most the final line, which
every reader (trace_report, scrub_store) classifies as expected crash
evidence — an ad-hoc writer into events.p*.jsonl / metrics.prom
produces interleaved or torn MID-FILE bytes that turn forensics into
damage reports. Telemetry's private surface (_emit/_sink/_STATE) is
off-limits outside the module for the same reason.

Fix: emit instants through telemetry.event() and spans through the one
front door, profiling.counters.span() (the event log is one of its three
sinks); counters through profiling.Counters. New durable observability
artifacts belong in the telemetry/profiling modules, not at call sites.
"""


def _mentions_sink_path(call: ast.Call) -> str | None:
    for node in ast.walk(call):
        if isinstance(node, ast.Constant) and isinstance(node.value, str):
            s = node.value
            for marker in _SINK_MARKERS:
                if marker in s:
                    return s
            if s == "log" or "/log/" in s or s.endswith("/log"):
                return s
    return None


def run(model: RepoModel) -> list[Finding]:
    out: list[Finding] = []
    for sf in model.prod_files():
        if sf.path in ALLOWED:
            continue
        telemetry_aliases = {
            alias for alias, mod in sf.import_aliases.items()
            if mod == "drep_tpu.utils.telemetry"
        }
        for alias, (mod, orig) in sf.from_imports.items():
            if mod == "drep_tpu.utils" and orig == "telemetry":
                telemetry_aliases.add(alias)
        for node in ast.walk(sf.tree):
            if (
                isinstance(node, ast.Attribute)
                and isinstance(node.value, ast.Name)
                and node.value.id in telemetry_aliases
                and node.attr.startswith("_")
            ):
                out.append(Finding(
                    rule=RULE_ID, path=sf.path, line=node.lineno,
                    message=f"private telemetry member telemetry.{node.attr} "
                            f"used outside the module",
                    hint="use the public gated API: telemetry.event()/"
                         "configure(), counters.span()",
                ))
            # one front door: the log's Span is a sink of counters.span,
            # never a second way in (a span on one clock only)
            if (
                isinstance(node, ast.Attribute)
                and isinstance(node.value, ast.Name)
                and node.value.id in telemetry_aliases
                and node.attr == "Span"
            ):
                out.append(Finding(
                    rule=RULE_ID, path=sf.path, line=node.lineno,
                    message="telemetry.Span used outside the front door",
                    hint="open the span with profiling.counters.span(): it "
                         "feeds the record, the profiler's host plane and "
                         "this log together",
                ))
            # the other spelling: from drep_tpu.utils.telemetry import _emit
            if (
                isinstance(node, ast.ImportFrom)
                and node.module == "drep_tpu.utils.telemetry"
            ):
                for alias in node.names:
                    if alias.name.startswith("_"):
                        out.append(Finding(
                            rule=RULE_ID, path=sf.path, line=node.lineno,
                            message=f"private telemetry member "
                                    f"{alias.name} from-imported outside "
                                    f"the module",
                            hint="use the public gated API: telemetry."
                                 "event()/configure(), counters.span()",
                        ))
        for call in iter_calls(sf.tree):
            kind = write_call_kind(call)
            if kind is None:
                continue
            hit = _mentions_sink_path(call)
            if hit is not None:
                out.append(Finding(
                    rule=RULE_ID, path=sf.path, line=call.lineno,
                    message=f"ad-hoc write ({kind}) targeting the "
                            f"observability sink namespace ({hit!r})",
                    hint="emit through telemetry.event()/counters.span() or extend "
                         "utils/telemetry.py — direct writes bypass the "
                         "--events gate and the crash-safe append format",
                ))
    return out


RULES = [Rule(id=RULE_ID, title="telemetry gating", run=run, explain=EXPLAIN)]
