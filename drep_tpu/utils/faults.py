"""Deterministic, env/config-driven fault injection for device hot paths.

The pipeline's crash story (atomic shard checkpoints, Cdb resume) is
testable on CPU because kills are external; its LIVE-failure story — a
wedged dispatch, an XLA runtime error on one chip, a hung collective —
is not, unless the failures themselves can be manufactured on CPU in CI.
This registry is that manufacturing layer: named injection points are
threaded through every device-dispatch hot path (streaming tile waits,
dense ring dispatch, secondary batched calls, shard writes, the edge
allgather, the checkpoint barrier), and a spec string decides which of
them misbehave, how, and how often — deterministically, so a failing
chaos run replays.

Spec syntax (``DREP_TPU_FAULTS`` env var, or :func:`configure`)::

    site:mode[:prob][:key=value ...]  [, site:mode ...]

    DREP_TPU_FAULTS="streaming_tile:raise:0.05:seed=7,shard_write:torn,allgather:hang"

- ``site``   — injection-point name (see SITES).
- ``mode``   — ``raise`` (InjectedFault), ``hang`` (sleep ``secs``,
  default 3600 — trips watchdogs/collective timeouts), ``sleep``
  (sleep ``secs`` then continue — paces a run so a chaos test can kill
  it mid-flight), ``torn`` (write sites only: publish a truncated file
  in place of the atomic write), and the ``io``-site storage modes
  (``io_error``/``stale_read``/``enospc``/``corrupt`` — see MODES and
  utils/durableio.py).
- ``prob``   — per-call fire probability (default 1.0), drawn from a
  per-rule ``random.Random(seed)`` stream, so runs are reproducible.
- ``key=value`` — ``seed=N`` (default 0), ``secs=F`` (sleep duration),
  ``device=N`` (fire only when the caller reports that device slot),
  ``max=N`` (stop after N fires — e.g. tear exactly two shards),
  ``proc=N`` (fire only on jax process N of a pod — one spec can be
  shared by every pod member), ``skip=N`` (ignore the first N matching
  calls — e.g. let a process finish two stripes before killing it),
  ``path=S`` (fire only when the target path contains S — e.g.
  ``path=.e01`` corrupts only an epoch-1-stamped shard; on the ``wire``
  site the "path" is the chaos proxy's peer label, so ``path=replica0``
  garbles exactly one hop; I/O + wire sites only).

The ``kill`` mode (``process_death`` site, fired per streaming stripe;
``ring_step`` site, fired per dense-ring step boundary) SIGKILLs the
calling process — the pod-member death the elastic protocols survive,
made deterministic for chaos tests (indistinguishable from an external
SIGKILL: no cleanup, no atexit, heartbeats simply stop). The ``drain``
mode at the same two sites is the GRACEFUL counterpart: it flags the
process for a planned departure (faulttol.request_drain — the SIGTERM
path minus the signal), consumed at that very boundary: departure note
published, PodDrained raised, exit 0. A one-process job consumes it too,
with no note (faulttol.drain_at_boundary): at ``process_death``, and at
``secondary_checkpoint``, fired after each primary cluster's secondary
checkpoint is published.

Zero overhead when unset: the spec parses once (lazily, from the env);
every :func:`fire` call thereafter is a no-op behind one falsy check.
"""

from __future__ import annotations

import os
import random
import time
from dataclasses import dataclass, field

ENV = "DREP_TPU_FAULTS"

# the named injection points currently threaded through the pipeline —
# unknown sites in a spec raise at parse time so a typo'd chaos run
# cannot silently inject nothing and "pass"
SITES = (
    "streaming_tile",  # per-tile watchdog'd wait, parallel/streaming.py
    "ring_dispatch",  # ring step/recovery dispatch waits, parallel/allpairs.py
    "ring_step",  # per-ring-step host boundary, parallel/allpairs.py (kill)
    "secondary_batch",  # secondary engine calls, cluster/controller.py
    "secondary_checkpoint",  # after each primary cluster's secondary
    # checkpoint is published, cluster/controller.py (drain: a one-process
    # job leaves there, exit 0, and the rerun looks every published
    # cluster up; skip=N lets N clusters publish first)
    "shard_write",  # atomic shard publish, utils/durableio.py (torn)
    "allgather",  # multi-host edge allgather, parallel/streaming.py
    "barrier",  # checkpoint-dir open barrier, utils/ckptmeta.py
    "process_death",  # per-stripe suicide point, parallel/streaming.py (kill)
    "io",  # durable read/write paths, utils/durableio.py (io modes below)
    "index_update",  # per-update-batch points, drep_tpu/index/update.py
    # (fires at batch admission AND again just before the manifest
    # publish — skip=1 targets the pre-publish point deterministically)
    "partition_update",  # per-partition point of a federated update,
    # drep_tpu/index/federation.py (fires once before EACH dirty
    # partition's update dispatch — skip=N targets partition N+1)
    "meta_publish",  # just before the federation meta-manifest's atomic
    # publish, drep_tpu/index/federation.py (the federation commit point)
    "partition_load",  # a serve replica's lazy partition-residency load,
    # drep_tpu/index/federation.py FederatedResident (fires before the
    # sketch-payload read — the containment boundary: a raise here must
    # quarantine the partition and yield PARTIAL verdicts, never kill
    # the daemon)
    "partition_classify",  # the per-partition rect compare of a routed
    # query batch, drep_tpu/index/federation.py (mid-classify partition
    # failure: same quarantine containment as partition_load)
    "autoscale_decide",  # the autoscaling controller's per-tick decision
    # point, drep_tpu/autoscale/controller.py (fires BEFORE the snapshot
    # + decide; raise/hang/kill take the controller down — which must be
    # harmless: workers never depend on it — and sleep paces the loop)
    "router_leg",  # the fleet router's per-leg dispatch point,
    # drep_tpu/serve/router.py (fires as a scatter leg leaves for a
    # replica: raise -> the leg books a failure and reroutes/degrades to
    # PARTIAL, hang -> the per-leg deadline contains it, sleep -> paces
    # a scatter so chaos can kill the replica mid-gather)
    "replica_health",  # the router's per-replica health probe,
    # drep_tpu/serve/router.py (fires inside one /healthz poll: raise ->
    # the probe books a failure and the healthy->suspect->ejected
    # machine advances — a probe fault must eject the replica, never
    # the router)
    "partition_split",  # the split/merge meta-manifest transaction's
    # phase boundaries, drep_tpu/index/maintenance.py (fires after
    # STAGE, before COMMIT, and before GC — kill with skip=0/1/2
    # targets each phase; a killed transaction must either leave the
    # old meta fully live or be rolled forward by the next pass)
    "compaction",  # the generation-compaction transaction's phase
    # boundaries, drep_tpu/index/maintenance.py (same skip discipline:
    # staged / pre-commit / pre-gc — a kill between a partition's
    # manifest publish and the meta publish must be adopted by
    # roll_forward, and the gc must resume idempotently)
    "wire",  # the serve tier's NDJSON wire itself, polled per REPLY line
    # by the in-process chaos proxy (drep_tpu/serve/wirechaos.py) sitting
    # between any client/router/replica pair. Modes are wire-only (see
    # WIRE_MODES); ``path=S`` targets a peer LABEL (the proxy's name for
    # its upstream, e.g. path=replica0) the way io rules target a shard
    # path — one spec can garble exactly one hop of a fleet.
    "supervisor_spawn",  # the fleet supervisor's per-spawn point,
    # drep_tpu/serve/supervisor.py (fires AFTER the manifest records the
    # intent but BEFORE the replica process is forked: kill -> the
    # supervisor dies mid-spawn and its successor must adopt every
    # still-live replica from fleet.json without double-spawning;
    # raise -> the spawn books a death and feeds backoff; sleep paces)
    "supervisor_tick",  # the top of each supervision heartbeat tick,
    # drep_tpu/serve/supervisor.py (kill/raise/hang take the supervisor
    # down — which must be harmless: replicas keep serving, the manifest
    # stays adoptable; sleep paces the loop so chaos can interleave)
)

# io-site modes (fired via fire_io/corrupt_write inside utils/durableio.py):
# io_error = transient OSError(EIO) on read AND write (retried by the
# bounded-backoff loop); stale_read = OSError(ESTALE) on read only;
# enospc = OSError(ENOSPC) on write only (degrades into the actionable
# StoreFullError); corrupt = flip one bit of the published npz AFTER the
# atomic rename — the post-write rot the in-band checksum self-heals.
IO_MODES = ("io_error", "stale_read", "enospc", "corrupt")
# wire-site modes (polled via wire_fault inside serve/wirechaos.py — the
# chaos proxy ACTS on the byte stream, nothing raises): reset = abort the
# connection mid-reply (RST, no FIN); stall = hold the reply `secs`
# (default 3600 — trips the client's deadline, never a daemon thread);
# slow = delay each reply line `secs` (default 0.05) then deliver intact;
# short_read = deliver a truncated reply line then close (EOF mid-frame);
# garble = flip bytes inside the reply frame (the per-line CRC must catch
# it); dup = deliver the reply line twice (request-id echo must dedupe).
WIRE_MODES = ("reset", "stall", "slow", "short_read", "garble", "dup")
MODES = ("raise", "hang", "sleep", "torn", "kill", "drain") + IO_MODES + WIRE_MODES
# where a drain request is looked at right after the fire point
DRAIN_SITES = ("process_death", "ring_step", "secondary_checkpoint")


class InjectedFault(RuntimeError):
    """An artificial failure fired by the registry — retried/quarantined
    exactly like a real device error (nothing downstream knows it is
    synthetic except the counters that label it injected)."""


class FaultSpecError(ValueError):
    """Malformed DREP_TPU_FAULTS spec (bad site/mode/field)."""


@dataclass
class _Rule:
    site: str
    mode: str
    prob: float = 1.0
    seed: int = 0
    secs: float | None = None
    device: int | None = None
    proc: int | None = None
    skip: int = 0
    max_fires: int | None = None
    path_sub: str | None = None
    fired: int = 0
    seen: int = 0
    rng: random.Random = field(init=False)

    def __post_init__(self) -> None:
        self.rng = random.Random(self.seed)

    def should_fire(self, device: int | None, path: str | None = None) -> bool:
        if self.max_fires is not None and self.fired >= self.max_fires:
            return False
        if self.device is not None and device != self.device:
            return False
        if self.path_sub is not None and (path is None or self.path_sub not in path):
            return False
        if self.proc is not None:
            import jax  # lazy: the registry must import without a backend

            if jax.process_index() != self.proc:
                return False
        self.seen += 1
        if self.seen <= self.skip:
            return False
        # draw unconditionally so the stream position depends only on the
        # number of matching calls, not on earlier rules' outcomes
        return self.rng.random() < self.prob


def _parse(spec: str) -> dict[str, list[_Rule]]:
    rules: dict[str, list[_Rule]] = {}
    for entry in filter(None, (e.strip() for e in spec.split(","))):
        fields = entry.split(":")
        if len(fields) < 2:
            raise FaultSpecError(f"fault entry needs site:mode, got {entry!r}")
        site, mode = fields[0], fields[1]
        if site not in SITES:
            raise FaultSpecError(f"unknown fault site {site!r} (known: {', '.join(SITES)})")
        if mode not in MODES:
            raise FaultSpecError(f"unknown fault mode {mode!r} (known: {', '.join(MODES)})")
        if mode in IO_MODES and site != "io":
            # a typo like barrier:enospc would parse, book its injected_*
            # counter at fire() time, then act on nothing — the chaos run
            # would silently test nothing while claiming it injected
            raise FaultSpecError(
                f"mode {mode!r} is io-site-only (got site {site!r}); "
                f"storage faults fire inside utils/durableio.py via the "
                f"'io' site"
            )
        if site == "io" and mode in ("torn", "kill"):
            # the symmetric no-op: fire_io skips these outright (torn is
            # the shard_write site's poll, kill belongs to the death
            # sites), so io:torn would claim coverage and inject nothing
            raise FaultSpecError(
                f"mode {mode!r} has no 'io' site semantics — use "
                f"shard_write:torn for torn publishes, or "
                f"process_death/ring_step:kill for deaths"
            )
        if mode == "drain" and site not in DRAIN_SITES:
            # the drain request is consumed at the safe boundaries, which
            # are exactly these sites' fire points — anywhere else the flag
            # would be set but never honored and the chaos run would claim
            # coverage while testing nothing
            raise FaultSpecError(
                f"mode 'drain' fires only at the safe-boundary sites "
                f"{'/'.join(DRAIN_SITES)} (got site {site!r})"
            )
        if mode in WIRE_MODES and site != "wire":
            # the proxy is the only consumer: router_leg:garble would
            # parse, book nothing at fire() (which has no garble arm),
            # and the chaos run would claim wire coverage it never ran
            raise FaultSpecError(
                f"mode {mode!r} is wire-site-only (got site {site!r}); "
                f"wire faults act inside serve/wirechaos.py via the "
                f"'wire' site"
            )
        if site == "wire" and mode not in WIRE_MODES:
            # symmetric: wire:raise would parse but the proxy only polls
            # wire_fault() for the byte-stream modes — nothing would fire
            raise FaultSpecError(
                f"the 'wire' site takes only the wire modes "
                f"{', '.join(WIRE_MODES)} (got {mode!r})"
            )
        if mode == "torn" and site != "shard_write":
            # tearing is an action the WRITER polls (torn_write), and only
            # the shard_write site is ever polled — a spec like
            # index_update:torn would parse, then silently inject nothing
            # while the chaos run claims coverage
            raise FaultSpecError(
                f"mode 'torn' is shard_write-only (got site {site!r}); "
                f"only the atomic shard publish polls torn_write()"
            )
        rule = _Rule(site=site, mode=mode)
        for f in fields[2:]:
            if "=" in f:
                key, _, val = f.partition("=")
                if key == "seed":
                    rule.seed = int(val)
                elif key == "secs":
                    rule.secs = float(val)
                elif key == "device":
                    rule.device = int(val)
                elif key == "proc":
                    rule.proc = int(val)
                elif key == "skip":
                    rule.skip = int(val)
                elif key == "max":
                    rule.max_fires = int(val)
                elif key == "path":
                    # substring match on the target path — deterministic
                    # targeting of ONE shard family (e.g. path=.e01 hits
                    # only epoch-1-stamped shards). Only the durable-I/O
                    # call sites supply a path (fire_io/corrupt_write for
                    # 'io', torn_write for 'shard_write'); on any other
                    # site should_fire would see path=None and the rule
                    # would silently never fire — reject the spec instead
                    if site not in ("io", "shard_write", "wire"):
                        raise FaultSpecError(
                            f"path= is only meaningful on the io/"
                            f"shard_write/wire sites (got {site!r}); "
                            f"other sites never supply a target path, so "
                            f"the rule would never fire"
                        )
                    rule.path_sub = val
                else:
                    raise FaultSpecError(f"unknown fault field {key!r} in {entry!r}")
            else:
                rule.prob = float(f)
        rule.__post_init__()  # re-seed after the seed= field landed
        rules.setdefault(site, []).append(rule)
    return rules


# None = not parsed yet (parse lazily from the env on first use); {} =
# parsed, nothing injected — the common case, one falsy check per call
_RULES: dict[str, list[_Rule]] | None = None


def configure(spec: str | None) -> None:
    """Install a spec programmatically (tests). ``None``/"" disables."""
    global _RULES
    _RULES = _parse(spec) if spec else {}


def reset() -> None:
    """Forget any parsed spec; the env var is re-read on next use."""
    global _RULES
    _RULES = None


def _rules() -> dict[str, list[_Rule]]:
    global _RULES
    if _RULES is None:
        from drep_tpu.utils import envknobs

        _RULES = _parse(envknobs.env_str(ENV))
    return _RULES


def active() -> bool:
    return bool(_rules())


def _record(rule: _Rule) -> None:
    rule.fired += 1
    from drep_tpu.utils.profiling import counters

    counters.add_fault(f"injected_{rule.site}_{rule.mode}")


def fire(site: str, device: int | None = None) -> None:
    """Run any matching rules for `site`: raise, hang, or sleep.

    Called on the execution path being protected — for watchdog'd sites
    the caller must invoke this INSIDE the watched region, so a ``hang``
    rule trips the watchdog instead of wedging the main thread.
    """
    rules = _RULES
    if rules is None:
        rules = _rules()
    if not rules:
        return
    for rule in rules.get(site, ()):
        if not rule.should_fire(device):
            continue
        _record(rule)
        if rule.mode == "raise":
            raise InjectedFault(f"injected fault at {site} (device={device})")
        if rule.mode == "hang":
            time.sleep(3600.0 if rule.secs is None else rule.secs)
            raise InjectedFault(f"injected hang at {site} woke up (device={device})")
        if rule.mode == "sleep":
            time.sleep(0.05 if rule.secs is None else rule.secs)
        if rule.mode == "kill":
            # SIGKILL self: the chaos-test stand-in for a pod member dying
            # (preemption, OOM-kill, host loss) — no cleanup runs, exactly
            # like the real event. Counters die with the process.
            import signal

            os.kill(os.getpid(), signal.SIGKILL)
        if rule.mode == "drain":
            # graceful-preemption stand-in: flag the process for a planned
            # departure, consumed at this very boundary (the elastic loops
            # check right after their fire point) — the SIGTERM path minus
            # the signal, deterministic for chaos tests
            from drep_tpu.parallel.faulttol import request_drain

            request_drain()
        # 'torn' rules are polled via torn_write(), never fired here


def torn_write(site: str = "shard_write", path: str | None = None) -> bool:
    """Should the caller tear this write? (write sites poll this instead
    of fire(): tearing is an action the WRITER performs, not an
    exception)."""
    rules = _RULES
    if rules is None:
        rules = _rules()
    if not rules:
        return False
    for rule in rules.get(site, ()):
        if rule.mode == "torn" and rule.should_fire(None, path=path):
            _record(rule)
            return True
    return False


def corrupt_write(site: str = "io", path: str | None = None) -> bool:
    """Should the caller bit-flip this freshly-PUBLISHED payload? (the
    ``io:corrupt`` mode — like torn_write, corruption is an action the
    writer performs after the atomic rename, not an exception)."""
    rules = _RULES
    if rules is None:
        rules = _rules()
    if not rules:
        return False
    for rule in rules.get(site, ()):
        if rule.mode == "corrupt" and rule.should_fire(None, path=path):
            _record(rule)
            return True
    return False


def wire_fault(peer: str | None = None):
    """Poll the ``wire`` site for one reply frame about to cross `peer`'s
    hop (serve/wirechaos.py calls this per reply line). Returns the
    matching :class:`_Rule` — the proxy ACTS on the byte stream itself
    (reset/stall/slow/short_read/garble/dup), so like torn_write this is
    a poll, not an exception. ``path=`` rules target the peer label."""
    rules = _RULES
    if rules is None:
        rules = _rules()
    if not rules:
        return None
    for rule in rules.get("wire", ()):
        if rule.should_fire(None, path=peer):
            _record(rule)
            return rule
    return None


def fire_io(op: str, path: str | None = None) -> None:
    """Run the ``io`` site's error-raising rules for one durable I/O
    attempt (utils/durableio.py calls this INSIDE its retried regions, so
    injected transient errors exercise the real backoff loop). `op` is
    ``"read"`` or ``"write"``: ``stale_read`` fires on reads only,
    ``enospc`` on writes only, ``io_error`` on both; ``corrupt`` is
    polled via :func:`corrupt_write`, never raised here."""
    import errno as _errno

    rules = _RULES
    if rules is None:
        rules = _rules()
    if not rules:
        return
    for rule in rules.get("io", ()):
        if rule.mode in ("corrupt", "torn", "kill"):
            continue  # corrupt is polled via corrupt_write; torn/kill have no io semantics
        if rule.mode == "stale_read" and op != "read":
            continue
        if rule.mode == "enospc" and op != "write":
            continue
        if not rule.should_fire(None, path=path):
            continue
        _record(rule)
        if rule.mode == "io_error":
            raise OSError(_errno.EIO, f"injected EIO at io ({op}: {path})")
        if rule.mode == "stale_read":
            raise OSError(_errno.ESTALE, f"injected ESTALE at io (read: {path})")
        if rule.mode == "enospc":
            raise OSError(_errno.ENOSPC, f"injected ENOSPC at io (write: {path})")
        if rule.mode == "raise":
            raise InjectedFault(f"injected fault at io ({op}: {path})")
        if rule.mode == "hang":
            # a wedged NFS call: sleep the hang, then surface as EIO so
            # the retry/backoff layer (not a watchdog) handles it
            time.sleep(3600.0 if rule.secs is None else rule.secs)
            raise OSError(_errno.EIO, f"injected hang at io woke up ({op}: {path})")
        if rule.mode == "sleep":
            time.sleep(0.05 if rule.secs is None else rule.secs)
