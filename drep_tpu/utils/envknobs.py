"""Central registry of every ``DREP_TPU_*`` environment knob.

Nineteen-odd knobs grew organically across PRs 2-11, each read at its
call site with bespoke parsing (``== "0"``, ``not in ("", "0",
"false")``, bare truthiness) — a typo'd export (``DREP_TPU_HEARBEAT_S``)
silently configured nothing, and nothing said which knobs even existed.
This module is the single source of truth: every knob is declared ONCE
(name, type, default, one-line doc) and read through a typed accessor
(:func:`env_str` / :func:`env_int` / :func:`env_float` /
:func:`env_bool`). The static-analysis suite (tools/lint, rule
``env-knob``) enforces the funnel both ways: a ``DREP_TPU_*`` string
literal anywhere in the tree that is not declared here is a violation
(dead/typo'd knob), and a direct ``os.environ`` read of one outside this
module is a violation (bespoke-parse drift).

Accessor semantics, pinned by tests/test_lint.py:

- unset        -> the declared default (which may be ``None`` for str).
- empty/blank  -> the declared default (int/float/bool; ``env_str``
  returns the raw value so spec-string knobs keep "" == unset).
- bool strings -> ``1/true/on/yes`` are True, ``0/false/off/no`` are
  False (case/whitespace-insensitive); anything else raises ``ValueError``
  naming the knob — a typo must never silently flip a safety default
  (the old inline parsers mapped garbage to true OR false depending on
  the site).
- int/float    -> parsed with ``int()``/``float()``; a malformed value
  raises ``ValueError`` naming the knob (same failure the old inline
  ``int(os.environ.get(...))`` reads produced, now with context).

Per-call default overrides (``env_float(name, default=...)``) exist for
knobs whose effective default is context-dependent (the collective
timeout: 900 s at a stage-open barrier, 6 h at the allgather).

This module must stay stdlib-only and importable with no JAX backend —
durableio, the scrubber, and host-side tools all read knobs.
"""

from __future__ import annotations

import os
from dataclasses import dataclass

__all__ = [
    "Knob", "KNOBS", "env_str", "env_int", "env_float", "env_bool",
    "knob", "describe",
]


@dataclass(frozen=True)
class Knob:
    name: str
    kind: str  # "str" | "int" | "float" | "bool"
    default: object
    doc: str
    test_only: bool = False  # read only by the test harness, never by the pipeline


KNOBS: dict[str, Knob] = {}


def _declare(
    name: str, kind: str, default, doc: str, test_only: bool = False
) -> None:
    if name in KNOBS:
        raise ValueError(f"duplicate env-knob declaration: {name}")
    KNOBS[name] = Knob(name, kind, default, doc, test_only)


# -- fault injection / chaos -------------------------------------------------
_declare(
    "DREP_TPU_FAULTS", "str", "",
    "Deterministic fault-injection spec, `site:mode[:prob][:k=v]` comma-list "
    "(utils/faults.py). Empty = zero-overhead off.",
)
# -- elastic pod protocol ----------------------------------------------------
_declare(
    "DREP_TPU_HEARTBEAT_S", "float", 5.0,
    "Per-process heartbeat cadence (s) for the elastic-pod protocol; 0 "
    "disables heartbeats and epoch-coordinated re-dealing entirely.",
)
_declare(
    "DREP_TPU_COLLECTIVE_TIMEOUT_S", "float", 900.0,
    "Watchdog for multi-host collective waits (s); call sites override the "
    "default where healthy skew differs (6 h at the allgather). <=0 disables.",
)
_declare(
    "DREP_TPU_POD_JOIN", "str", "",
    "Mid-run join request on a NEW process: 'auto' derives an id from the "
    "pod's notes, an integer pins one. Empty = not a joiner.",
)
# -- single-chip kernels -----------------------------------------------------
_declare(
    "DREP_TPU_INDICATOR_DTYPE", "str", None,
    "Force the indicator matmul accumulator dtype (ops/containment.py); "
    "unset = heuristic choice.",
)
_declare(
    "DREP_TPU_MASH_ROWS_PER_ITER", "int", 1,
    "Rows per grid iteration for the Pallas mash kernel "
    "(ops/pallas_mash.py): 1, 2 or 4.",
)
_declare(
    "DREP_TPU_GREEDY_MATMUL", "bool", False,
    "Set 1 to force the greedy secondary onto the MXU matmul path "
    "(cluster/greedy.py).",
)
_declare(
    "DREP_TPU_NO_NATIVE", "bool", False,
    "Set 1 to disable the native (g++) ingest extension and use the pure-"
    "python fallback (native/__init__.py).",
)
# -- durable I/O -------------------------------------------------------------
_declare(
    "DREP_TPU_IO_RETRIES", "int", 3,
    "Transient-I/O retry budget (EIO/ESTALE/ETIMEDOUT) per durable op "
    "(utils/durableio.py); the CLI --io_retries overrides.",
)
_declare(
    "DREP_TPU_IO_BACKOFF_S", "float", 0.05,
    "First retry backoff (s); doubles per attempt.",
)
_declare(
    "DREP_TPU_FSYNC", "bool", False,
    "Set 1 to fsync tmp file + directory around every atomic publish "
    "(power-loss durability); the CLI --fsync overrides.",
)
_declare(
    "DREP_TPU_IO_CRC", "bool", True,
    "Set 0 to disable in-band checksum embed+verify on npz payloads and "
    "JSON notes (perf-guard baseline / escape hatch).",
)
# -- observability -----------------------------------------------------------
_declare(
    "DREP_TPU_EVENTS", "bool", False,
    "Set 1/on to enable structured event tracing (utils/telemetry.py); "
    "zero overhead off.",
)
_declare(
    "DREP_TPU_METRICS_FLUSH_S", "float", 0.0,
    "Prometheus textfile flush cadence (s) for <wd>/log/metrics.prom; "
    "0 = off.",
)
# -- federated index ---------------------------------------------------------
_declare(
    "DREP_TPU_FED_PODS", "int", 0,
    "Federated `index update`: run per-partition updates as up to this many "
    "CONCURRENT subprocess pods (index/federation.py); 0 = in-process, one "
    "partition at a time. The CLI --fed_pods overrides.",
)
_declare(
    "DREP_TPU_FED_SHARD_MAX", "int", 4096,
    "Boundary-bucket cross-partition join: max repacked band-code bucket "
    "width per range shard (pow2; rangepart.partition_by_range). Execution "
    "knob only — the candidate set is identical for every value.",
)
# -- index maintenance (split/merge/compaction, ISSUE 18) --------------------
_declare(
    "DREP_TPU_SPLIT_GC_GRACE_S", "float", 0.0,
    "Partition split/merge: delay (s) between the federation.json commit "
    "and the parent-store gc, so live serve replicas on the old meta "
    "hot-swap before the parents vanish (index/maintenance.py). A "
    "straggler past it is contained by the ordinary partition quarantine.",
)
_declare(
    "DREP_TPU_COMPACT_GC_GRACE_S", "float", 0.0,
    "Generation compaction: delay (s) between the meta publish and the "
    "superseded-shard gc (index/maintenance.py) — same hot-swap grace as "
    "DREP_TPU_SPLIT_GC_GRACE_S.",
)
_declare(
    "DREP_TPU_COMPACT_MIN_SHARDS", "int", 4,
    "Maintenance scheduler: propose compaction for a partition holding at "
    "least this many sketch/edge shard-family generations "
    "(autoscale/policy.py maintenance_decide; `index compact` without "
    "--pid uses it as its default threshold via --min_generations).",
)
_declare(
    "DREP_TPU_SPLIT_MAX_GENOMES", "int", 0,
    "Maintenance scheduler: propose splitting a partition past this many "
    "genomes (skew containment); 0 disables split proposals.",
)
# -- partition-scoped federated serving --------------------------------------
_declare(
    "DREP_TPU_SERVE_DEVICE_RESIDENT", "bool", True,
    "Serve fast path: keep the resident sketch matrix device-resident "
    "across classify batches (index/resident_device.py — one upload per "
    "generation/hot-swap instead of a per-batch union repack). Set 0 to "
    "pin the classic per-batch rect compare; verdicts are byte-identical "
    "either way.",
)
_declare(
    "DREP_TPU_SERVE_RESIDENT_MB", "int", 0,
    "Streaming federated serve: byte budget (MiB) for resident partition "
    "sketch payloads (index/federation.py FederatedResident — LRU eviction "
    "past it); 0 = unlimited. The CLI `index serve --resident_mb` overrides.",
)
_declare(
    "DREP_TPU_SERVE_PROBE_BACKOFF_S", "float", 1.0,
    "First reload-probe delay after a partition quarantine (streaming "
    "federated serve); doubles per failed probe.",
)
_declare(
    "DREP_TPU_SERVE_PROBE_MAX_S", "float", 60.0,
    "Cap on the partition reload-probe backoff (s).",
)
# -- fleet router (ISSUE 17) -------------------------------------------------
_declare(
    "DREP_TPU_ROUTER_LEG_TIMEOUT_S", "float", 30.0,
    "Fleet router (serve/router.py): per-leg socket deadline for one "
    "scatter/forward dispatch to a replica. A leg past it is abandoned "
    "(the attempt reroutes; exhaustion degrades to a PARTIAL verdict). "
    "The CLI `index route --leg_timeout_s` overrides.",
)
_declare(
    "DREP_TPU_ROUTER_HEDGE_DELAY_S", "float", 2.0,
    "Fleet router: straggler hedge — when a leg's first attempt has not "
    "answered after this long, a duplicate dispatch goes to a second "
    "capable replica and the first answer wins (the loser is discarded, "
    "never double-merged). The CLI `index route --hedge_delay_s` overrides.",
)
_declare(
    "DREP_TPU_ROUTER_PROBE_BACKOFF_S", "float", 1.0,
    "Fleet router: first reprobe delay after a replica is EJECTED by the "
    "health poller (healthy->suspect->ejected); doubles per failed "
    "reprobe up to DREP_TPU_SERVE_PROBE_MAX_S — the PR 14 partition "
    "containment ladder, one layer up.",
)
_declare(
    "DREP_TPU_ROUTER_MAX_INFLIGHT", "int", 256,
    "Fleet router: bounded admission — max queued classify requests "
    "before the router sheds load with a backpressure refusal "
    "(retry_after_s) instead of queueing to death. The CLI "
    "`index route --max_inflight` overrides.",
)
# -- serve-tier deadlines + wire hardening (ISSUE 19) ------------------------
_declare(
    "DREP_TPU_SERVE_DEADLINE_DEFAULT_MS", "float", 30000.0,
    "Serve tier: default end-to-end deadline budget (ms) stamped onto "
    "requests that carry no `deadline_ms` of their own (legacy clients). "
    "A queued request whose budget expires before dispatch is SHED with a "
    "`deadline_exceeded` refusal instead of wasting a device slot; 0 "
    "disables the default (legacy requests then wait indefinitely).",
)
_declare(
    "DREP_TPU_WIRE_CRC", "bool", True,
    "Set 0 to disable the per-line CRC on NDJSON serve frames (the PR 5 "
    "in-band-checksum idiom extended to the wire). Verification is "
    "presence-gated on the receiver, so mixed fleets interoperate.",
)
_declare(
    "DREP_TPU_ROUTER_BREAKER_ERRS", "int", 5,
    "Fleet router circuit breaker: leg errors within "
    "DREP_TPU_ROUTER_BREAKER_WINDOW_S that trip a replica's breaker OPEN "
    "(routing skips it without eating a leg timeout). Successes do not "
    "clear the window — a flapping replica still trips. 0 disables.",
)
_declare(
    "DREP_TPU_ROUTER_BREAKER_WINDOW_S", "float", 30.0,
    "Fleet router circuit breaker: sliding error-rate window (s).",
)
_declare(
    "DREP_TPU_ROUTER_BREAKER_HALFOPEN_S", "float", 5.0,
    "Fleet router circuit breaker: seconds an OPEN breaker holds before "
    "moving to HALF-OPEN and admitting exactly one bounded probe leg "
    "(success closes + clears the window; failure re-opens).",
)
# -- autoscaling controller --------------------------------------------------
_declare(
    "DREP_TPU_AUTOSCALE_INTERVAL_S", "float", 5.0,
    "Autoscaling controller (tools/pod_autoscale.py): seconds between "
    "pod_status.collect() snapshots / decide() calls. The CLI --interval "
    "overrides.",
)
_declare(
    "DREP_TPU_AUTOSCALE_COOLDOWN_S", "float", 30.0,
    "Autoscaling controller: minimum seconds between two SCALING decisions "
    "(holds are free) — the anti-flap window a just-spawned joiner needs to "
    "show up in the snapshot. The CLI --cooldown overrides.",
)
_declare(
    "DREP_TPU_AUTOSCALE_MAX_SPAWN", "int", 1,
    "Autoscaling controller: max joiner processes spawned per scale-up "
    "decision (the per-decision clamp on top of --max_procs). The CLI "
    "--max_spawn overrides.",
)
_declare(
    "DREP_TPU_AUTOSCALE_SPAWNED", "bool", False,
    "Set by the autoscaling controller on processes IT spawns/drains: the "
    "join/drain notes such a process publishes carry an `autoscale` stamp, "
    "so every pod member books `autoscale_churn` in its run record. "
    "Never set by hand.",
)
# -- fleet supervisor --------------------------------------------------------
_declare(
    "DREP_TPU_SUP_HEARTBEAT_S", "float", 1.0,
    "Fleet supervisor (serve/supervisor.py): seconds between liveness "
    "heartbeats against each healthy slot — a pid poll plus a /healthz "
    "probe over the existing serve wire. A dead pid or failed probe books "
    "a death and moves the slot to BACKOFF.",
)
_declare(
    "DREP_TPU_SUP_BACKOFF_MAX_S", "float", 30.0,
    "Fleet supervisor: cap on the decorrelated-jitter exponential restart "
    "backoff. Each death resamples delay = uniform(base, prev*3) clamped "
    "to this, so respawn storms decorrelate instead of thundering.",
)
_declare(
    "DREP_TPU_SUP_CRASHLOOP_K", "int", 3,
    "Fleet supervisor crash-loop detector: this many deaths inside "
    "DREP_TPU_SUP_CRASHLOOP_WINDOW_S moves the slot to QUARANTINED — no "
    "further respawns, durable reason in fleet.json; routed traffic over "
    "the missing coverage degrades to stamped PARTIAL.",
)
_declare(
    "DREP_TPU_SUP_CRASHLOOP_WINDOW_S", "float", 60.0,
    "Fleet supervisor crash-loop detector: sliding window (s) the death "
    "count is evaluated over. Deaths older than the window never count "
    "toward quarantine.",
)
_declare(
    "DREP_TPU_SUP_DRAIN_DEADLINE_S", "float", 30.0,
    "Fleet supervisor graceful drain: seconds after SIGTERM a draining "
    "replica gets to finish in-flight work before escalation to SIGKILL "
    "(escalations are counted separately in the manifest slot).",
)
_declare(
    "DREP_TPU_SUP_STARTUP_DEADLINE_S", "float", 120.0,
    "Fleet supervisor startup probe: seconds a freshly spawned replica "
    "gets to print its JSON ready line before the spawn is declared dead "
    "(books a death like any other — feeds backoff and crash-loop).",
)
# -- ingest ------------------------------------------------------------------
_declare(
    "DREP_TPU_INGEST_BARRIER_S", "float", 600.0,
    "Multi-host ingest assembly: max wait (s) with no new sketch shard "
    "appearing before declaring a peer dead.",
)
# -- test harness only -------------------------------------------------------
_declare(
    "DREP_TPU_TEST_MAX_JOINS", "int", 0,
    "Chaos-test worker: --max_joins for the in-worker controller.",
    test_only=True,
)
_declare(
    "DREP_TPU_TEST_MAX_DEAD", "int", 1,
    "Chaos-test worker: --max_dead_processes for the in-worker controller.",
    test_only=True,
)
_declare(
    "DREP_TPU_TEST_WAIT_JOIN", "str", "",
    "Chaos-test worker: block at a gate until a join-request note exists "
    "(deterministic admission ordering).",
    test_only=True,
)
_declare(
    "DREP_TPU_TEST_JOIN_AFTER_DRAIN", "str", "",
    "Chaos-test joiner: hold the join request until a departure note "
    "exists (drain-then-join churn cell).",
    test_only=True,
)
_declare(
    "DREP_TPU_TEST_CPU_DEVICES", "int", 2,
    "Chaos-test worker: forced host CPU devices per process (the D=3 "
    "ring-phase JOIN cell runs 3 processes x 1 device).",
    test_only=True,
)


def knob(name: str) -> Knob:
    try:
        return KNOBS[name]
    except KeyError:
        raise KeyError(
            f"undeclared env knob {name!r} — declare it in "
            f"drep_tpu/utils/envknobs.py (the registry tools/lint enforces)"
        ) from None


def _raw(name: str) -> str | None:
    knob(name)  # undeclared reads must fail loudly even at runtime
    return os.environ.get(name)


def env_str(name: str, default: str | None = None):
    """String knob. Unset -> declared default (per-call `default` wins
    when given). A SET-but-empty value is returned as-is: spec-string
    knobs (DREP_TPU_FAULTS, DREP_TPU_POD_JOIN) treat "" as off."""
    raw = _raw(name)
    if raw is None:
        return default if default is not None else KNOBS[name].default
    return raw


def env_int(name: str, default: int | None = None) -> int:
    raw = _raw(name)
    if raw is None or not raw.strip():
        return int(default if default is not None else KNOBS[name].default)
    try:
        return int(raw.strip())
    except ValueError:
        raise ValueError(f"{name}={raw!r}: expected an integer") from None


def env_float(name: str, default: float | None = None) -> float:
    raw = _raw(name)
    if raw is None or not raw.strip():
        return float(default if default is not None else KNOBS[name].default)
    try:
        return float(raw.strip())
    except ValueError:
        raise ValueError(f"{name}={raw!r}: expected a number") from None


_TRUE = frozenset({"1", "true", "on", "yes"})
_FALSE = frozenset({"0", "false", "off", "no"})


def env_bool(name: str, default: bool | None = None) -> bool:
    raw = _raw(name)
    fallback = bool(default if default is not None else KNOBS[name].default)
    if raw is None or not raw.strip():
        return fallback
    v = raw.strip().lower()
    if v in _TRUE:
        return True
    if v in _FALSE:
        return False
    # loud, like env_int/env_float: silently mapping `FSYNC=enable` or a
    # typo'd `ture` to the default would downgrade a safety knob with no
    # trace (the old inline parsers did exactly that, inconsistently)
    raise ValueError(
        f"{name}={raw!r}: expected one of "
        f"{sorted(_TRUE)} / {sorted(_FALSE)}"
    )


def describe() -> str:
    """Human-readable registry dump (`python -m tools.lint --knobs`)."""
    width = max(len(k) for k in KNOBS)
    lines = []
    for k in sorted(KNOBS.values(), key=lambda k: (k.test_only, k.name)):
        tag = " [test-only]" if k.test_only else ""
        lines.append(
            f"{k.name:<{width}}  {k.kind:<5} default={k.default!r}{tag}\n"
            f"{'':<{width}}  {k.doc}"
        )
    return "\n".join(lines)
