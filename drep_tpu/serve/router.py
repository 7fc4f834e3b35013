"""The `index route` fleet front door (ISSUE 17 tentpole): a STATELESS
scatter/gather router over N `index serve` replicas.

One router process speaks the exact serve protocol (serve/protocol.py —
NDJSON + the HTTP shim, byte-compatible with every existing client) in
front of a fleet of replicas, each holding a subset of a federated
root's partitions resident. The router holds the CHEAP half of the same
root — the union spine and routing bitmaps, zero sketch payloads at
startup — and farms every per-partition rectangular compare out to the
fleet:

- **routing**: each query's coarse code summary
  (`rangepart.code_summary_bitmap`, recall 1.0 by construction) names
  its candidate partitions; the replica table routes each leg to a
  replica with cache AFFINITY for that partition (resident beats
  evicted, shallow queue beats deep).
- **forward fast path**: a query whose whole candidate set one replica
  covers is forwarded as a plain `classify` (the replica's batch window
  coalesces concurrent forwards — the fleet bench's 2x path).
- **scatter/gather**: multi-partition queries fan out as
  `classify_part` legs and merge through the EXACT recluster path the
  replicas themselves run (`classify_batch_federated` with the router's
  pre-gathered legs injected via ``partition_compare``) — routed
  verdicts are byte-identical to a single daemon's union classify,
  oracle-pinned in tests/test_router.py.
- **generation fencing**: every leg is stamped with the router's
  federation generation and a replica at any OTHER generation refuses
  the leg (carrying its own), so a mixed-generation gather can never
  merge silently. A replica AHEAD of the router triggers one bounded
  synchronous reload-and-retry of the whole gather; exhaustion degrades
  honestly.
- **robustness is the contract**: per-leg timeouts; straggler HEDGING
  (a duplicate dispatch to a second capable replica after
  ``hedge_delay_s`` — first answer wins, the loser is discarded without
  a double merge); leg failure -> reroute -> else a stamped PARTIAL
  verdict (`--strict` converts it to a ``partial_coverage`` refusal
  with ``retry_after_s``, exactly the PR 14 semantics one layer down);
  bounded admission with overload SPILL to PARTIAL answers instead of
  queueing to death; SIGTERM drain; replica join/leave mid-traffic
  (the ``fleet`` op) without a dropped query.
- **replica containment** mirrors PR 14's partition machine one layer
  up: /healthz probes drive healthy -> suspect (immediate reprobe) ->
  ejected (bounded exponential reprobe backoff,
  DREP_TPU_ROUTER_PROBE_BACKOFF_S doubling to
  DREP_TPU_SERVE_PROBE_MAX_S); a recovered probe rejoins the replica
  seamlessly. Layered ON that table (ISSUE 19), a per-replica
  error-rate CIRCUIT BREAKER: leg errors inside a sliding window trip
  closed -> open (no legs route there), and after a cooldown exactly
  ONE half-open probe leg decides closed (success) or reopen
  (failure) — catching the flapping replica whose interleaved
  successes keep resetting the health machine's failure streak
  (DREP_TPU_ROUTER_BREAKER_ERRS / DREP_TPU_ROUTER_BREAKER_WINDOW_S /
  DREP_TPU_ROUTER_BREAKER_HALFOPEN_S).
- **deadline propagation** (ISSUE 19): when a batch carries a budget
  (the tightest remaining deadline among its requests, stashed by the
  daemon's batch loop), every leg is stamped with the DECREMENTED
  remainder at its own launch instant — elapsed time at this hop is
  subtracted, never re-granted — hedges launch only within the
  remaining budget, and the losing hedge leg is cooperatively
  CANCELLED (the serve protocol's ``cancel`` op) so it stops consuming
  its replica's queue the moment the winner answers.

The router is STATELESS by construction — no durable state, nothing
written anywhere (it inherits the daemon's pure-reader contract and the
reader-purity lint walks it): kill it and restart it and the fleet
re-forms from the replica specs + probes.
"""

from __future__ import annotations

import itertools
import os
import queue as queue_mod
import threading
import time
from collections import OrderedDict
from dataclasses import dataclass, field

import numpy as np

from drep_tpu.errors import UserInputError
from drep_tpu.serve import protocol
from drep_tpu.serve.client import ServeClient
from drep_tpu.serve.daemon import _RETRY_AFTER_FLOOR_S, IndexServer, ServeConfig
from drep_tpu.utils import durableio, faults, telemetry
from drep_tpu.utils.logger import get_logger
from drep_tpu.utils.profiling import counters

REPLICA_HEALTHY = "healthy"
REPLICA_SUSPECT = "suspect"
REPLICA_EJECTED = "ejected"

BREAKER_CLOSED = "closed"
BREAKER_OPEN = "open"
BREAKER_HALF_OPEN = "half_open"

# entries the router's sketch cache keeps (a sketch is ~KBs; the cap is
# a leak bound, not a memory budget)
_SKETCH_CACHE_CAP = 4096

# leg request ids (the cancel handle for a losing hedge leg) — unique
# per router process; itertools.count.__next__ is atomic under the GIL
_LEG_SEQ = itertools.count()


def decrement_budget_ms(
    budget_ms: float | None, elapsed_s: float
) -> float | None:
    """The per-hop budget decrement rule (ISSUE 19): what remains of a
    request's end-to-end budget after ``elapsed_s`` burned at this hop,
    clamped at zero — a leg is never granted MORE time than its parent
    has left, and an exhausted budget propagates as 0.0 (an immediate
    shed at the replica), never as a negative grant. None (no budget)
    stays None: unbounded in, unbounded out."""
    if budget_ms is None:
        return None
    return max(0.0, float(budget_ms) - float(elapsed_s) * 1000.0)


def remaining_budget_ms(
    deadline: float | None, now: float | None = None
) -> float | None:
    """:func:`decrement_budget_ms` phrased against an ABSOLUTE monotonic
    deadline — the form the dispatch paths carry (the deadline does the
    elapsed-subtraction implicitly, so a leg launched late inherits
    exactly what is left, not the original grant)."""
    if deadline is None:
        return None
    if now is None:
        now = time.monotonic()
    return max(0.0, (deadline - now) * 1000.0)


class FleetUnavailableError(RuntimeError):
    """No usable replica in the fleet — surfaced to clients as a
    ``no_replicas`` refusal with the soonest-reprobe retry hint (the
    daemon's per-path error isolation forwards ``reason`` /
    ``retry_after_s`` attributes verbatim)."""

    def __init__(self, msg: str, retry_after_s: float):
        super().__init__(msg)
        self.reason = "no_replicas"
        self.retry_after_s = retry_after_s


def parse_replica_spec(spec: str) -> tuple[str, frozenset | None]:
    """``ADDR`` or ``ADDR=PIDS`` where PIDS is a comma list of ids and
    inclusive ranges (``0-2,5``). No assignment = the replica serves
    every partition."""
    addr, sep, rest = spec.partition("=")
    addr = addr.strip()
    if not addr:
        raise UserInputError(f"bad replica spec {spec!r}: empty address")
    if not sep:
        return addr, None
    pids: set[int] = set()
    for part in filter(None, (p.strip() for p in rest.split(","))):
        lo, dash, hi = part.partition("-")
        try:
            if dash:
                pids.update(range(int(lo), int(hi) + 1))
            else:
                pids.add(int(part))
        except ValueError as e:
            raise UserInputError(
                f"bad replica spec {spec!r}: partition list must be ids/"
                f"ranges like 0-2,5 (got {part!r})"
            ) from e
    if not pids:
        raise UserInputError(
            f"bad replica spec {spec!r}: '=' given but no partitions named"
        )
    return addr, frozenset(pids)


@dataclass
class RouterConfig(ServeConfig):
    """ServeConfig + the fleet surface. ``replicas`` are
    :func:`parse_replica_spec` strings; None knobs resolve from the
    router section of the env registry (utils/envknobs.py)."""

    replicas: list[str] = field(default_factory=list)
    leg_timeout_s: float | None = None
    hedge_delay_s: float | None = None
    probe_interval_s: float = 1.0
    probe_backoff_s: float | None = None
    probe_max_s: float | None = None
    max_inflight: int | None = None  # wins over max_queue when set
    # durable membership (ISSUE 20): path to the supervisor's fleet.json.
    # A restarted router rebuilds its replica table from it instead of
    # forgetting every `fleet join`; the router only ever READS it (the
    # supervisor is the sole writer — reader purity holds).
    fleet_manifest: str | None = None


@dataclass
class ReplicaSlot:
    """One replica's containment record — the partition slot machine of
    PR 14, promoted to a whole process."""

    address: str
    assigned: frozenset | None = None  # None = serves all partitions
    state: str = REPLICA_HEALTHY
    failures: int = 0
    probes: int = 0
    recoveries: int = 0
    backoff_s: float = 0.0
    next_probe: float = 0.0  # monotonic: earliest reprobe when ejected
    last_ok: float | None = None
    last_err: str | None = None
    generation: int | None = None
    n_genomes: int | None = None
    queue_depth: int = 0
    inflight: int = 0  # router-side legs/forwards currently on the wire
    draining: bool = False
    resident: frozenset = frozenset()  # pids with sketches resident
    left: bool = False  # fleet leave: no NEW legs, record kept
    # error-rate circuit breaker (ISSUE 19), layered on the health
    # machine above: recent error instants (monotonic, pruned to the
    # breaker window), the breaker state, and the instant it opened
    err_times: list = field(default_factory=list)
    breaker: str = BREAKER_CLOSED
    breaker_opened: float = 0.0
    breaker_trips: int = 0


class ReplicaTable:
    """The router's only mutable state: per-replica health + affinity,
    fed by the /healthz poller and by leg outcomes. Thread-safe (probe
    thread, leg threads, and fleet-op handler threads all book here)."""

    def __init__(
        self, specs: list[str], probe_backoff_s: float, probe_max_s: float,
        breaker_errs: int = 5, breaker_window_s: float = 30.0,
        breaker_halfopen_s: float = 5.0,
    ):
        self._lock = threading.Lock()
        self._slots: dict[str, ReplicaSlot] = {}
        self.probe_backoff_s = float(probe_backoff_s)
        self.probe_max_s = float(probe_max_s)
        self.breaker_errs = int(breaker_errs)
        self.breaker_window_s = float(breaker_window_s)
        self.breaker_halfopen_s = float(breaker_halfopen_s)
        for spec in specs:
            addr, assigned = parse_replica_spec(spec)
            self.join(addr, assigned)

    def __len__(self) -> int:
        with self._lock:
            return sum(1 for s in self._slots.values() if not s.left)

    # ---- membership (fleet op + CLI specs) ------------------------------
    def join(self, address: str, assigned: frozenset | None = None) -> ReplicaSlot:
        with self._lock:
            slot = self._slots.get(address)
            if slot is None:
                slot = ReplicaSlot(address=address, assigned=assigned)
                self._slots[address] = slot
            else:
                # rejoin: routable again immediately; probes re-earn trust
                slot.left = False
                slot.state = REPLICA_HEALTHY
                slot.failures = 0
                slot.backoff_s = 0.0
                slot.next_probe = 0.0
                slot.err_times.clear()
                slot.breaker = BREAKER_CLOSED
                if assigned is not None:
                    slot.assigned = assigned
            return slot

    # ---- in-flight accounting --------------------------------------------
    def lease(self, address: str) -> None:
        """Book one router-side dispatch onto a replica. The /healthz
        ``queue_depth`` refreshes only at probe cadence — within a
        probe interval the lease count is the ONLY load signal, and
        without it every equally-good target ties and the address
        tiebreak funnels a whole batch at one replica."""
        with self._lock:
            slot = self._slots.get(address)
            if slot is not None:
                slot.inflight += 1

    def release(self, address: str) -> None:
        with self._lock:
            slot = self._slots.get(address)
            if slot is not None and slot.inflight > 0:
                slot.inflight -= 1

    def leave(self, address: str) -> bool:
        """No new legs route here; in-flight legs finish on their open
        sockets — the no-dropped-query half of the leave contract."""
        with self._lock:
            slot = self._slots.get(address)
            if slot is None:
                return False
            slot.left = True
            return True

    # ---- outcome booking -------------------------------------------------
    def _book_breaker_error(self, slot: ReplicaSlot, now: float) -> bool:
        """Book one error into the breaker window (lock held). Errors
        accumulate WHETHER OR NOT successes interleave — a flapping
        replica (ok, error, ok, error, ...) never resets this window the
        way each success resets the health machine's failure streak,
        which is exactly the pathology the breaker exists to catch.
        Returns True when this error tripped (or re-tripped) the
        breaker open."""
        slot.err_times.append(now)
        cutoff = now - self.breaker_window_s
        slot.err_times[:] = [t for t in slot.err_times if t > cutoff]
        if slot.breaker == BREAKER_HALF_OPEN:
            # the half-open probe leg itself failed: reopen for a full
            # cooldown — trust is re-earned one probe at a time
            slot.breaker = BREAKER_OPEN
            slot.breaker_opened = now
            return True
        if (
            slot.breaker == BREAKER_CLOSED
            and len(slot.err_times) >= self.breaker_errs
        ):
            slot.breaker = BREAKER_OPEN
            slot.breaker_opened = now
            slot.breaker_trips += 1
            return True
        return False

    def book_failure(self, address: str, err: BaseException | str) -> None:
        now = time.monotonic()
        tripped = False
        with self._lock:
            slot = self._slots.get(address)
            if slot is None or slot.left:
                return
            slot.failures += 1
            slot.last_err = f"{err}"
            tripped = self._book_breaker_error(slot, now)
            if slot.state == REPLICA_HEALTHY:
                slot.state = REPLICA_SUSPECT
                slot.next_probe = now  # one immediate reprobe: a blip is
                # not an ejection (the partition machine's grace, one up)
                state = REPLICA_SUSPECT
            elif slot.state == REPLICA_SUSPECT:
                slot.state = REPLICA_EJECTED
                slot.backoff_s = self.probe_backoff_s
                slot.next_probe = now + slot.backoff_s
                state = REPLICA_EJECTED
            else:
                slot.backoff_s = min(
                    self.probe_max_s, max(self.probe_backoff_s, slot.backoff_s * 2)
                )
                slot.next_probe = now + slot.backoff_s
                state = REPLICA_EJECTED
        counters.add_fault(f"router_replica_{state}")
        telemetry.event(
            f"replica_{state}", address=address, error=f"{err}"[:200]
        )
        if tripped:
            counters.add_fault("router_breaker_open")
            telemetry.event("replica_breaker_open", address=address)

    def book_success(self, address: str, status: dict | None = None) -> None:
        breaker_closed = False
        with self._lock:
            slot = self._slots.get(address)
            if slot is None:
                return
            if status is None and slot.breaker != BREAKER_CLOSED:
                # a real LEG answered (the half-open probe, or a leg that
                # raced the trip): close the breaker and forget the error
                # window. /healthz probes (status != None) deliberately
                # do NOT close it — a replica can answer /healthz fine
                # while erroring on every leg, and the breaker gates on
                # the leg error rate, not liveness.
                slot.breaker = BREAKER_CLOSED
                slot.err_times.clear()
                breaker_closed = True
            recovered = slot.state != REPLICA_HEALTHY
            if recovered:
                slot.recoveries += 1
            slot.state = REPLICA_HEALTHY
            slot.failures = 0
            slot.backoff_s = 0.0
            slot.last_ok = time.monotonic()
            slot.last_err = None
            if status:
                slot.probes += 1
                slot.generation = status.get("generation")
                slot.n_genomes = status.get("n_genomes")
                slot.queue_depth = int(status.get("queue_depth") or 0)
                slot.draining = bool(status.get("draining"))
                per = (status.get("partitions") or {}).get("partitions") or {}
                try:
                    slot.resident = frozenset(
                        int(p) for p, info in per.items() if info.get("resident")
                    )
                except (TypeError, ValueError):
                    slot.resident = frozenset()
        if recovered:
            counters.add_fault("router_replica_recovered")
            telemetry.event("replica_recovered", address=address)
        if breaker_closed:
            counters.add_fault("router_breaker_closed")
            telemetry.event("replica_breaker_closed", address=address)

    # ---- routing views ---------------------------------------------------
    def _breaker_allows(self, s: ReplicaSlot, now: float) -> bool:
        """The breaker gate (lock held). Open blocks every leg until the
        half-open instant, when exactly ONE bounded probe leg may pass:
        the transition to half-open happens here, and the in-flight
        lease count bounds the probe — a second leg arriving while the
        probe is out sees ``inflight > 0`` and routes elsewhere. The
        probe's outcome (book_success / book_failure) closes or reopens
        the breaker."""
        if s.breaker == BREAKER_OPEN:
            if now < s.breaker_opened + self.breaker_halfopen_s:
                return False
            s.breaker = BREAKER_HALF_OPEN
        return not (s.breaker == BREAKER_HALF_OPEN and s.inflight > 0)

    def _routable(self) -> list[ReplicaSlot]:
        now = time.monotonic()
        return [
            s for s in self._slots.values()
            if not s.left and not s.draining and s.state != REPLICA_EJECTED
            and self._breaker_allows(s, now)
        ]

    def eligible(self, pid: int) -> list[ReplicaSlot]:
        """Replicas capable of partition ``pid``, best first: sketch
        affinity, then health, then shallow queues (deterministic
        address tiebreak)."""
        with self._lock:
            slots = [
                s for s in self._routable()
                if s.assigned is None or pid in s.assigned
            ]
            slots.sort(key=lambda s: (
                0 if pid in s.resident else 1,
                0 if s.state == REPLICA_HEALTHY else 1,
                s.queue_depth + s.inflight, s.address,
            ))
            return slots

    def cover_targets(self, pids: set[int]) -> list[ReplicaSlot]:
        """Replicas whose assignment covers EVERY pid in ``pids`` (the
        forward fast path), best first by affinity overlap."""
        with self._lock:
            slots = [
                s for s in self._routable()
                if s.assigned is None or pids <= s.assigned
            ]
            slots.sort(key=lambda s: (
                -len(pids & s.resident),
                0 if s.state == REPLICA_HEALTHY else 1,
                s.queue_depth + s.inflight, s.address,
            ))
            return slots

    def usable(self) -> bool:
        with self._lock:
            return bool(self._routable())

    def probe_due(self, now: float) -> list[tuple[str, str]]:
        """(address, state) of every replica the poller should probe
        this tick: healthy/suspect always, ejected only past their
        backoff deadline, left never."""
        with self._lock:
            return [
                (s.address, s.state) for s in self._slots.values()
                if not s.left
                and (s.state != REPLICA_EJECTED or now >= s.next_probe)
            ]

    def retry_hint_s(self) -> float:
        """The soonest instant anything could change — the refusal hint
        when no replica is usable."""
        now = time.monotonic()
        with self._lock:
            waits = [
                max(_RETRY_AFTER_FLOOR_S, s.next_probe - now)
                for s in self._slots.values()
                if not s.left and s.state == REPLICA_EJECTED
            ]
        return min(waits) if waits else self.probe_backoff_s

    def health_map(self) -> dict:
        with self._lock:
            replicas = {
                s.address: {
                    "state": "left" if s.left else s.state,
                    "assigned": sorted(s.assigned) if s.assigned is not None else None,
                    "generation": s.generation,
                    "n_genomes": s.n_genomes,
                    "queue_depth": s.queue_depth,
                    "inflight": s.inflight,
                    "draining": s.draining,
                    "resident": sorted(s.resident),
                    "failures": s.failures,
                    "recoveries": s.recoveries,
                    "probes": s.probes,
                    "last_error": s.last_err,
                    "breaker": s.breaker,
                    "breaker_trips": s.breaker_trips,
                    "breaker_errors": len(s.err_times),
                }
                for s in sorted(self._slots.values(), key=lambda s: s.address)
            }
            suspect = sorted(
                s.address for s in self._slots.values()
                if not s.left and s.state == REPLICA_SUSPECT
            )
            ejected = sorted(
                s.address for s in self._slots.values()
                if not s.left and s.state == REPLICA_EJECTED
            )
            breaker_open = sorted(
                s.address for s in self._slots.values()
                if not s.left and s.breaker != BREAKER_CLOSED
            )
        return {
            "replicas": replicas, "suspect": suspect, "ejected": ejected,
            "breaker_open": breaker_open,
        }


class RouterServer(IndexServer):
    """IndexServer whose classify core routes to a fleet instead of
    rect-comparing locally. Everything else — bounded admission, dynamic
    batching, the strict/PARTIAL refusal branch, generation hot-swap
    polling, SIGTERM drain, /healthz — is inherited unchanged, so the
    two tiers cannot drift."""

    def __init__(self, cfg: RouterConfig, classify_fn=None):
        from drep_tpu.utils import envknobs

        self.leg_timeout_s = (
            envknobs.env_float("DREP_TPU_ROUTER_LEG_TIMEOUT_S")
            if cfg.leg_timeout_s is None else float(cfg.leg_timeout_s)
        )
        self.hedge_delay_s = (
            envknobs.env_float("DREP_TPU_ROUTER_HEDGE_DELAY_S")
            if cfg.hedge_delay_s is None else float(cfg.hedge_delay_s)
        )
        probe_backoff = (
            envknobs.env_float("DREP_TPU_ROUTER_PROBE_BACKOFF_S")
            if cfg.probe_backoff_s is None else float(cfg.probe_backoff_s)
        )
        probe_max = (
            envknobs.env_float("DREP_TPU_SERVE_PROBE_MAX_S")
            if cfg.probe_max_s is None else float(cfg.probe_max_s)
        )
        if cfg.max_inflight is None:
            cfg.max_inflight = envknobs.env_int("DREP_TPU_ROUTER_MAX_INFLIGHT")
        cfg.max_queue = int(cfg.max_inflight)
        super().__init__(cfg, classify_fn=classify_fn)
        self.table = ReplicaTable(
            list(cfg.replicas), probe_backoff, probe_max,
            breaker_errs=envknobs.env_int("DREP_TPU_ROUTER_BREAKER_ERRS"),
            breaker_window_s=envknobs.env_float(
                "DREP_TPU_ROUTER_BREAKER_WINDOW_S"
            ),
            breaker_halfopen_s=envknobs.env_float(
                "DREP_TPU_ROUTER_BREAKER_HALFOPEN_S"
            ),
        )
        # durable membership rebuild (ISSUE 20): merge the supervisor's
        # manifest into the table BEFORE the first leg — a restarted
        # router recovers its whole fleet with zero join replays
        self._rebuilt_members = self._rebuild_membership()
        self.router_stats = {
            "forwarded": 0,  # queries answered via the forward fast path
            "scattered": 0,  # queries answered via scatter/gather merge
            "legs_total": 0,
            "leg_failures": 0,
            "reroutes": 0,
            "hedges": 0,
            "hedge_wins": 0,
            "hedge_cancels": 0,  # losing hedge legs cooperatively cancelled
            "fence_retries": 0,  # gathers retried after a generation fence
            "fence_reloads": 0,  # synchronous reloads the fence forced
            "overload_spills": 0,  # legs abandoned on fleet-wide backpressure
            "partial_verdicts": 0,
        }
        self._swap_lock = threading.Lock()  # fence reload vs poller swap
        self._sketch_lock = threading.Lock()
        self._sketch_cache: OrderedDict[tuple, dict] = OrderedDict()

    def _device_fields(self) -> dict:
        # the router computes nothing on a device and must never open a
        # backend: on a chip machine the chip belongs to its replicas
        return {}

    # ---- lifecycle -------------------------------------------------------
    def start(self) -> str:
        address = super().start()
        if not hasattr(self._resident, "route_candidates"):
            self.close()
            raise UserInputError(
                f"index route needs a FEDERATED root (got a monolithic "
                f"store at {self.cfg.index_loc}) — the router scatters "
                f"per-partition legs; a monolithic index has nothing to "
                f"scatter. Serve it with `index serve` instead."
            )
        prober = threading.Thread(
            target=self._probe_loop, daemon=True, name="drep-route-probe"
        )
        self._threads.append(prober)
        prober.start()
        telemetry.event(
            "route_start", address=address, replicas=len(self.table),
            generation=int(self._resident.generation),
        )
        return address

    # ---- replica health polling -----------------------------------------
    def _probe_once(self) -> None:
        for addr, _state in self.table.probe_due(time.monotonic()):
            try:
                faults.fire("replica_health")
                with ServeClient(
                    addr, timeout_s=min(5.0, self.leg_timeout_s)
                ) as c:
                    status = c.status()
                self.table.book_success(addr, status)
            except Exception as e:  # noqa: BLE001 — a probe failure is DATA
                # (it advances the slot machine), never a router crash
                self.table.book_failure(addr, e)

    def _probe_loop(self) -> None:
        cfg: RouterConfig = self.cfg  # type: ignore[assignment]
        interval = max(0.05, float(cfg.probe_interval_s))
        while True:
            self._probe_once()
            if self._stop_poll.wait(interval):
                return

    # ---- fleet membership op --------------------------------------------
    def _handle_line(self, line, send, reply_classify, state, wlock) -> None:
        try:
            req = protocol.parse_request(line)
        except protocol.ProtocolError:
            # let the base handler produce the canonical protocol error
            return super()._handle_line(line, send, reply_classify, state, wlock)
        if req["op"] == "fleet":
            self._handle_fleet(req, send)
            return
        return super()._handle_line(line, send, reply_classify, state, wlock)

    def _handle_fleet(self, req: dict, send) -> None:
        action, addr = req["action"], req["address"]
        parts = req.get("partitions")
        assigned = (
            frozenset(int(p) for p in parts) if parts is not None else None
        )
        if action == "join":
            self.table.join(addr, assigned)
            known = True
            # sketch prefetch hint (ISSUE 18 satellite): tell the joiner
            # which partitions it was assigned so it warms those sketch
            # payloads BEFORE its first scatter leg — synchronous (the
            # join ack IS "ready for legs") but contained: a failed hint
            # only logs; the ordinary lazy load still covers every leg
            self._prewarm_joiner(addr, assigned)
        else:
            known = self.table.leave(addr)
        get_logger().info(
            "route: fleet %s %s%s (%d replica(s) routable)",
            action, addr,
            f" partitions={sorted(assigned)}" if assigned is not None else "",
            len(self.table),
        )
        telemetry.event(
            "fleet_" + action, address=addr,
            partitions=sorted(assigned) if assigned is not None else None,
        )
        send({
            "ok": True, "op": "fleet", "action": action, "address": addr,
            "known": known, "replicas": len(self.table),
            "id": req.get("id"),
        })

    def _prewarm_joiner(self, addr: str, assigned: frozenset | None) -> None:
        """Dispatch one bounded prewarm turn to a joining replica with
        its assigned partition ids (all routable pids when the joiner is
        unscoped). Best-effort by contract: any failure logs and the
        join proceeds — the hint only removes the first-leg cold-load
        spike, it never gates membership."""
        from drep_tpu.serve.client import ServeClient

        resident = self._resident
        if assigned is not None:
            pids = sorted(assigned)
        elif hasattr(resident, "_slots"):
            pids = sorted(getattr(resident, "_slots"))
        else:
            pids = []
        if not pids:
            return
        try:
            with ServeClient(addr, timeout_s=self.leg_timeout_s) as client:
                report = client.prewarm(pids)
        except Exception as e:  # noqa: BLE001 — a hint must never fail the join
            get_logger().warning(
                "route: prewarm hint to joining replica %s failed (%s) — "
                "its first legs lazy-load instead", addr, e,
            )
            return
        get_logger().info(
            "route: prewarmed joining replica %s — partitions %s resident"
            "%s", addr, report.get("warmed"),
            f", {report['failed']} failed" if report.get("failed") else "",
        )
        telemetry.event(
            "fleet_prewarm", address=addr, warmed=report.get("warmed"),
            failed=report.get("failed"),
        )

    # ---- durable membership (ISSUE 20) -----------------------------------
    def _rebuild_membership(self) -> list[str]:
        """Join every routable slot recorded in the supervisor's
        fleet.json into the replica table. Read-only and best-effort: a
        missing manifest is an empty fleet, a rotted one is a loud
        warning (the router still starts with its --replica list — the
        supervisor's next publish heals the file)."""
        cfg: RouterConfig = self.cfg  # type: ignore[assignment]
        if not cfg.fleet_manifest:
            return []
        from drep_tpu.serve import supervisor as sup

        path = cfg.fleet_manifest
        if os.path.isdir(path):
            path = sup.manifest_path(path)
        try:
            doc = sup.load_manifest(os.path.dirname(path)) \
                if os.path.basename(path) == sup.MANIFEST_NAME \
                else durableio.read_json_checked(path, what="fleet manifest")
        except Exception as e:  # noqa: BLE001 — degraded start beats no start
            get_logger().warning(
                "route: fleet manifest %s unreadable (%r) — starting "
                "with explicit replicas only", cfg.fleet_manifest, e,
            )
            return []
        joined = []
        for slot in (doc.get("slots") or {}).values():
            addr = slot.get("address")
            # starting/backoff slots have no routable address yet (or a
            # stale one); the supervisor re-joins them when they come up
            if not addr or slot.get("state") not in ("healthy",):
                continue
            parts = slot.get("partitions")
            assigned = (
                frozenset(int(p) for p in parts) if parts is not None
                else None
            )
            self.table.join(addr, assigned)
            joined.append(addr)
        if joined:
            get_logger().info(
                "route: rebuilt %d replica(s) from fleet manifest %s",
                len(joined), cfg.fleet_manifest,
            )
        return joined

    def _supervision_view(self) -> dict | None:
        """The manifest's slot table, for /healthz consumers
        (tools/pod_status.py renders the supervision tree from it).
        None when no manifest is configured; an error marker when it is
        configured but unreadable."""
        cfg: RouterConfig = self.cfg  # type: ignore[assignment]
        if not cfg.fleet_manifest:
            return None
        from drep_tpu.serve import supervisor as sup

        path = cfg.fleet_manifest
        if os.path.isdir(path):
            path = sup.manifest_path(path)
        try:
            doc = durableio.read_json_checked(path, what="fleet manifest")
        except FileNotFoundError:
            return {"slots": {}, "generation": 0, "supervisor_pid": None}
        except Exception as e:  # noqa: BLE001 — status must answer regardless
            return {"error": f"fleet manifest unreadable: {e!r}"}
        return {
            "slots": doc.get("slots") or {},
            "generation": doc.get("generation"),
            "supervisor_pid": doc.get("supervisor_pid"),
            "supervisor_alive": sup.pid_alive(doc.get("supervisor_pid")),
        }

    # ---- status ----------------------------------------------------------
    def snapshot(self) -> dict:
        out = super().snapshot()
        out["role"] = "router"
        out["replicas"] = self.table.health_map()
        sup_view = self._supervision_view()
        if sup_view is not None:
            out["supervision"] = sup_view
        with self._lock:
            out["router"] = dict(self.router_stats)
        return out

    # ---- generation fence ------------------------------------------------
    def _fence_reload(self):
        """Synchronous reload when a gather proves the fleet is AHEAD of
        this router's resident generation (the poller would catch up
        within poll_generation_s; the fence cannot wait). Returns the
        freshest resident."""
        from drep_tpu.index import resident_device
        from drep_tpu.index.classify import load_resident_index

        with self._swap_lock:
            current = self._resident
            try:
                fresh = load_resident_index(
                    self.cfg.index_loc, resident_mb=self.cfg.resident_mb
                )
            except Exception as e:  # noqa: BLE001 — keep the current generation
                get_logger().warning("route: fence reload failed (%s)", e)
                return current
            if current is not None and int(fresh.generation) <= int(
                current.generation
            ):
                return current
            resident_device.prewarm_resident(fresh)
            old = int(current.generation) if current is not None else -1
            self._resident = fresh
            with self._lock:
                self.stats.swaps_total += 1
                self.router_stats["fence_reloads"] += 1
            counters.set_gauge("serve_generation", float(fresh.generation))
            telemetry.event(
                "generation_swap", old=old, new=int(fresh.generation),
                n=fresh.n, fenced=True,
            )
            get_logger().info(
                "route: generation fence reload %d -> %d", old, fresh.generation
            )
            return fresh

    # ---- the routed classify core ---------------------------------------
    def _bump(self, key: str, n: int = 1) -> None:
        with self._lock:
            self.router_stats[key] += n

    def _classify_paths(self, resident, paths: list[str]) -> dict:
        """The router's replacement for the daemon's local classify
        core: sketch (cached), route, forward/scatter, merge. Returns
        verdicts keyed by display name — the inherited batch loop does
        admission, batching, strict conversion, and reply plumbing.
        ``self._batch_deadline`` (stashed by that loop: the tightest
        remaining deadline among the batch's requests) bounds every
        downstream leg — the per-hop budget decrement."""
        budget_deadline = self._batch_deadline
        queries = self._sketch_batch(resident, paths)
        out: dict[str, dict] = {v["genome"]: v for v in queries.dropped}
        if not queries.n:
            return out
        if not self.table.usable():
            raise FleetUnavailableError(
                "no usable replica in the fleet (all ejected or left)",
                retry_after_s=self.table.retry_hint_s(),
            )
        q_names = list(queries.admitted["genome"])
        disp = [
            n[len("query:"):] if n.startswith("query:") else n for n in q_names
        ]
        q_bottoms = [
            np.asarray(queries.results[g]["bottom"], np.uint64) for g in q_names
        ]
        cand = resident.route_candidates(q_bottoms)
        path_of = {os.path.basename(p): p for p in paths}

        # partition the batch: forward what one replica fully covers,
        # scatter the rest. Queries assigned earlier in THIS batch count
        # as load on their target (the `local` ledger): the table's
        # queue_depth only refreshes at probe cadence, and without the
        # ledger every query of a batch would tie-break onto one
        # replica's address while its twin idles
        forward: dict[str, list[int]] = {}
        scatter_ts: list[int] = []
        local: dict[str, int] = {}
        for t in range(len(q_names)):
            targets = self.table.cover_targets(cand[t]) if cand[t] else []
            if targets:
                best = min(
                    enumerate(targets),
                    key=lambda it: (
                        it[1].queue_depth + it[1].inflight
                        + local.get(it[1].address, 0),
                        it[0],  # affinity order breaks load ties
                    ),
                )[1]
                local[best.address] = local.get(best.address, 0) + 1
                forward.setdefault(best.address, []).append(t)
            else:
                scatter_ts.append(t)

        fwd_results: dict[int, dict] = {}
        threads = []
        for addr, ts in forward.items():
            th = threading.Thread(
                target=self._forward_group,
                args=(addr, ts, [path_of[disp[t]] for t in ts],
                      set(cand[ts[0]]) if len(ts) == 1 else
                      set().union(*(cand[t] for t in ts)), fwd_results,
                      budget_deadline),
                daemon=True, name="drep-route-fwd",
            )
            threads.append(th)
            th.start()
        deadline = time.monotonic() + self._leg_budget_s() + 1.0
        if budget_deadline is not None:
            deadline = min(deadline, budget_deadline + 1.0)
        for th in threads:
            th.join(max(0.0, deadline - time.monotonic()))

        gen = int(resident.generation)
        for addr, ts in forward.items():
            for t in ts:
                resp = fwd_results.get(t)
                if resp is not None and resp.get("ok") and resp.get("verdict"):
                    if resp.get("generation") != gen:
                        # a forwarded verdict is COMPLETE at whichever
                        # generation stamped it — honest to return, worth
                        # counting (scatter legs, by contrast, hard-fence)
                        self._bump("fence_retries")
                    out[disp[t]] = resp["verdict"]
                    self._bump("forwarded")
                else:
                    scatter_ts.append(t)  # reroute through the merge path

        if scatter_ts:
            sub = self._subset_queries(queries, sorted(scatter_ts))
            for v in self._classify_scatter(resident, sub, budget_deadline):
                out[v["genome"]] = v
                self._bump("scattered")
                if v.get("partitions_unavailable"):
                    self._bump("partial_verdicts")
        return out

    def _subset_queries(self, queries, ts: list[int]):
        from drep_tpu.index.classify import SketchedQueries

        return SketchedQueries(
            admitted=queries.admitted.iloc[ts].reset_index(drop=True),
            results=queries.results, dropped=[],
        )

    def _classify_scatter(self, fed, queries, budget_deadline=None) -> list[dict]:
        """Scatter legs, gather, and run the EXACT federated merge with
        the remote results injected — one bounded generation-fence
        retry when the fleet proves to be ahead. ``budget_deadline``
        (absolute monotonic, or None) bounds every leg AND the merge's
        per-partition consults: once it passes, remaining partitions
        book unavailable and the verdict goes out honestly PARTIAL."""
        from drep_tpu.index.federation import classify_batch_federated

        for attempt in (0, 1):
            gen = int(fed.generation)
            q_names = list(queries.admitted["genome"])
            q_bottoms = [
                np.asarray(queries.results[g]["bottom"], np.uint64)
                for g in q_names
            ]
            cand = fed.route_candidates(q_bottoms)
            legs, ahead = self._gather_legs(
                fed, gen, cand, q_names, q_bottoms, budget_deadline
            )
            if ahead and attempt == 0:
                self._bump("fence_retries")
                fresh = self._fence_reload()
                if fresh is not None and int(fresh.generation) > gen:
                    fed = fresh
                    continue  # re-route + re-scatter on the new generation
            # drep-lint: allow[reader-purity] — the routed merge is the same storeless federated classify the daemon waives (classify.py): joint=False runs every rect compare with no checkpoint_dir, partition legs are remote, residency loads are checked reads; byte-for-byte pinned by the router oracle tests
            return classify_batch_federated(
                fed, queries, processes=self.cfg.processes,
                prune_cfg=self.cfg.prune_cfg, joint=False,
                partition_compare=lambda pid, _names, _bottoms: legs.get(pid),
                consult_check=(
                    None if budget_deadline is None
                    else lambda: time.monotonic() < budget_deadline
                ),
            )
        raise AssertionError("unreachable")  # pragma: no cover

    def _leg_budget_s(self) -> float:
        return 2.0 * self.leg_timeout_s + self.hedge_delay_s

    def _gather_legs(self, fed, gen, cand, q_names, q_bottoms, budget_deadline=None):
        """Dispatch one classify_part leg per candidate partition, all
        concurrent, each internally rerouted/hedged/deadlined (and
        budget-bounded when the batch carries a deadline). Returns
        ({pid: (ui, qi, dd)}, fleet_is_ahead)."""
        pids = sorted(set().union(*cand)) if cand else []
        legs: dict[int, tuple] = {}
        ahead = threading.Event()
        threads = []
        for pid in pids:
            cols = [t for t in range(len(q_names)) if pid in cand[t]]
            names = [q_names[t] for t in cols]
            bottoms = [[int(x) for x in q_bottoms[t]] for t in cols]
            th = threading.Thread(
                target=self._run_leg,
                args=(pid, gen, names, bottoms, legs, ahead, budget_deadline),
                daemon=True, name=f"drep-route-leg-{pid}",
            )
            threads.append(th)
            th.start()
        # backstop join deadline: each leg bounds itself, but a hang
        # fault fired at the router_leg site (chaos) must be contained
        # HERE — an expired leg merges as unavailable, never a wedge
        deadline = time.monotonic() + self._leg_budget_s() + 1.0
        if budget_deadline is not None:
            deadline = min(deadline, budget_deadline + 1.0)
        for th in threads:
            th.join(max(0.0, deadline - time.monotonic()))
        return legs, ahead.is_set()

    def _run_leg(self, pid, gen, names, bottoms, legs, ahead,
                 budget_deadline=None) -> None:
        try:
            faults.fire("router_leg")
            res = self._leg_dispatch(
                pid, gen, names, bottoms, ahead, budget_deadline
            )
        except Exception as e:  # noqa: BLE001 — a leg NEVER raises out of
            # the router: failure degrades to a stamped PARTIAL
            get_logger().warning("route: leg pid=%d failed: %s", pid, e)
            res = None
        if res is None:
            self._bump("leg_failures")
        else:
            legs[pid] = res

    def _leg_dispatch(self, pid, gen, names, bottoms, ahead,
                      budget_deadline=None):
        """One leg's full lifecycle: affinity-ordered targets, per-attempt
        socket deadline, straggler hedge to a second capable replica
        (first answer wins, the loser's socket is abandoned — a
        once-latch on the return path makes a double merge impossible),
        reroute on failure/refusal, overall deadline. Returns
        (ui, qi, dd) arrays or None.

        Deadline propagation (ISSUE 19): with a batch budget, each
        attempt's request is stamped with the DECREMENTED remainder at
        its own launch instant (elapsed time at this hop is subtracted,
        never re-granted — the replica sheds it if the rest expires in
        its queue), the leg's overall deadline shrinks to the budget,
        and a hedge launches only while the remaining budget exceeds
        the hedge delay. When any attempt wins, the still-in-flight
        losers are cooperatively CANCELLED so they stop consuming their
        replicas' queues."""
        deadline = time.monotonic() + self._leg_budget_s()
        if budget_deadline is not None:
            deadline = min(deadline, budget_deadline)
        base = {
            "op": "classify_part", "pid": int(pid), "generation": int(gen),
            "names": names, "bottoms": bottoms, "prune": self.cfg.prune_cfg,
        }
        results: queue_mod.Queue = queue_mod.Queue()
        on_wire: dict[str, str] = {}  # addr -> leg id currently in flight

        def attempt(addr: str, leg_id: str) -> None:
            self.table.lease(addr)
            try:
                req = dict(base, id=leg_id)
                left = remaining_budget_ms(budget_deadline)
                if left is not None:
                    req["deadline_ms"] = left  # the per-hop decrement
                with ServeClient(addr, timeout_s=self.leg_timeout_s) as c:
                    results.put((addr, c.request(req), None))
            except Exception as e:  # noqa: BLE001 — routed to the loop below
                results.put((addr, None, e))
            finally:
                self.table.release(addr)

        def launch(addr: str) -> None:
            leg_id = f"leg{next(_LEG_SEQ)}-p{pid}"
            on_wire[addr] = leg_id
            threading.Thread(
                target=attempt, args=(addr, leg_id), daemon=True,
                name="drep-route-attempt",
            ).start()

        def cancel_stragglers() -> None:
            # the consumed attempt was already popped from on_wire, so
            # everything left is a loser still occupying a replica
            for loser, lid in on_wire.items():
                self._cancel_leg(loser, lid)

        tried: list[str] = []
        hedge_addrs: set[str] = set()
        pending = 0
        saw_busy = False

        def next_target() -> str | None:
            for slot in self.table.eligible(pid):
                if slot.address not in tried:
                    return slot.address
            return None

        self._bump("legs_total")
        while True:
            now = time.monotonic()
            if now >= deadline:
                break
            if pending == 0:
                addr = next_target()
                if addr is None:
                    break  # every capable replica tried and failed
                if tried:
                    self._bump("reroutes")
                tried.append(addr)
                launch(addr)
                pending += 1
                wait_until = min(deadline, now + self.hedge_delay_s)
            elif pending == 1 and not hedge_addrs:
                # the hedge window elapsed with the primary still out:
                # duplicate to a second capable replica, first answer
                # wins — but only within the remaining budget: a hedge
                # that cannot answer before the deadline is pure fleet
                # load, so a nearly-spent budget suppresses it
                addr = None
                if (budget_deadline is None
                        or budget_deadline - now > self.hedge_delay_s):
                    addr = next_target()
                if addr is not None:
                    tried.append(addr)
                    hedge_addrs.add(addr)
                    self._bump("hedges")
                    counters.add_fault("router_leg_hedged")
                    launch(addr)
                    pending += 1
                wait_until = deadline
            else:
                wait_until = deadline
            try:
                addr, resp, err = results.get(
                    timeout=max(0.0, wait_until - time.monotonic())
                )
            except queue_mod.Empty:
                continue  # loop re-decides: hedge, reroute, or expire
            pending -= 1
            on_wire.pop(addr, None)
            if err is not None or resp is None:
                self.table.book_failure(addr, err or "empty leg response")
                continue
            if resp.get("ok"):
                self.table.book_success(addr)
                if addr in hedge_addrs:
                    self._bump("hedge_wins")
                cancel_stragglers()
                return (
                    np.asarray(resp.get("ui", ()), np.int64),
                    np.asarray(resp.get("qi", ()), np.int64),
                    np.asarray(resp.get("dist", ()), np.float32),
                )
            reason = resp.get("reason")
            if reason == "generation_mismatch":
                rgen = resp.get("generation")
                if rgen is not None and int(rgen) > gen:
                    ahead.set()  # the batch-level fence retry takes over
                    cancel_stragglers()  # the whole gather re-scatters
                    return None
                continue  # replica BEHIND: another target may be current
            if reason in ("backpressure", "draining"):
                saw_busy = True  # overload: spill to other replicas,
                continue  # never queue the leg behind a saturated one
            if reason == "partition_unavailable":
                # the replica itself quarantined this partition (PR 14) —
                # its OTHER partitions are fine, so no failure booking
                continue
            self.table.book_failure(addr, resp.get("error") or reason or "leg error")
        if saw_busy:
            self._bump("overload_spills")
            counters.add_fault("router_overload_spill")
        return None

    def _cancel_leg(self, addr: str, leg_id: str) -> None:
        """Best-effort cooperative cancel of a losing hedge leg on a
        FRESH short-lived connection (the leg's own socket is blocked in
        its reply wait — it cannot carry the cancel). The replica either
        drops the still-queued leg outright (its compute slot freed
        before any dispatch) or flags the id so the computed result is
        discarded at reply time; either way the loser stops consuming
        replica capacity. Fire-and-forget by contract: a failed cancel
        only means the leg runs to waste, exactly the pre-cancel world."""
        self._bump("hedge_cancels")
        counters.add_fault("router_hedge_cancelled")

        def _send() -> None:
            try:
                with ServeClient(
                    addr, timeout_s=min(2.0, self.leg_timeout_s)
                ) as c:
                    c.cancel(leg_id)
            except Exception as e:  # noqa: BLE001 — best-effort by contract
                get_logger().debug(
                    "route: hedge cancel of %s at %s failed: %s",
                    leg_id, addr, e,
                )

        threading.Thread(
            target=_send, daemon=True, name="drep-route-cancel"
        ).start()

    # ---- forward fast path ----------------------------------------------
    def _forward_group(self, addr, ts, paths, pids, results,
                       budget_deadline=None) -> None:
        """Forward whole queries (one pipelined connection — the
        replica's batch window coalesces them) with the same
        reroute + hedge envelope as a scatter leg. Failures leave the
        queries' slots empty; the caller falls back to the scatter
        merge, which degrades per-partition instead of per-query. A
        batch budget bounds the group like a leg (each attempt carries
        the decremented remainder; the hedge is budget-gated); no
        cancel here — classify_many owns its request ids, so the
        router has no handle on the loser's frames."""
        try:
            faults.fire("router_leg")
        except Exception as e:  # noqa: BLE001 — injected: same contract
            get_logger().warning("route: forward to %s failed: %s", addr, e)
            return
        deadline = time.monotonic() + self._leg_budget_s()
        if budget_deadline is not None:
            deadline = min(deadline, budget_deadline)
        rq: queue_mod.Queue = queue_mod.Queue()

        def attempt(a: str) -> None:
            self.table.lease(a)
            try:
                with ServeClient(a, timeout_s=self.leg_timeout_s) as c:
                    rq.put((a, c.classify_many(
                        paths,
                        deadline_ms=remaining_budget_ms(budget_deadline),
                    ), None))
            except Exception as e:  # noqa: BLE001
                rq.put((a, None, e))
            finally:
                self.table.release(a)

        tried = [addr]
        hedge_addrs: set[str] = set()
        pending = 1
        threading.Thread(
            target=attempt, args=(addr,), daemon=True, name="drep-route-fwd-try"
        ).start()

        def next_target() -> str | None:
            for slot in self.table.cover_targets(pids):
                if slot.address not in tried:
                    return slot.address
            return None

        while True:
            now = time.monotonic()
            if now >= deadline:
                return
            if pending == 0:
                nxt = next_target()
                if nxt is None:
                    return
                self._bump("reroutes")
                tried.append(nxt)
                threading.Thread(
                    target=attempt, args=(nxt,), daemon=True,
                    name="drep-route-fwd-try",
                ).start()
                pending += 1
                wait_until = min(deadline, now + self.hedge_delay_s)
            elif pending == 1 and not hedge_addrs:
                nxt = None
                if (budget_deadline is None
                        or budget_deadline - now > self.hedge_delay_s):
                    nxt = next_target()
                if nxt is not None:
                    tried.append(nxt)
                    hedge_addrs.add(nxt)
                    self._bump("hedges")
                    counters.add_fault("router_leg_hedged")
                    threading.Thread(
                        target=attempt, args=(nxt,), daemon=True,
                        name="drep-route-fwd-try",
                    ).start()
                    pending += 1
                wait_until = deadline
            else:
                wait_until = deadline
            try:
                a, resps, err = rq.get(
                    timeout=max(0.0, wait_until - time.monotonic())
                )
            except queue_mod.Empty:
                continue
            pending -= 1
            if err is not None or resps is None:
                self.table.book_failure(a, err or "empty forward response")
                self._bump("leg_failures")
                continue
            self.table.book_success(a)
            if a in hedge_addrs:
                self._bump("hedge_wins")
            # once-latch: the FIRST complete group wins; a loser arriving
            # later hits the results-already-set check and is discarded
            for t, resp in zip(ts, resps):
                if t not in results:
                    results[t] = resp
            return

    # ---- sketch cache ----------------------------------------------------
    def _sketch_key(self, path: str) -> tuple | None:
        try:
            st = os.stat(path)
        except OSError:
            return None
        return (os.path.abspath(path), st.st_size, st.st_mtime_ns)

    def _sketch_batch(self, resident, paths: list[str]):
        """sketch_queries with a per-file LRU keyed by (path, size,
        mtime): a loadgen's hot set sketches once at the router, so the
        forward fast path adds routing — not re-sketching — on top of
        the replica's work. Byte-identical to the uncached path (the
        admission rule is re-applied per batch from the pinned params;
        only the sketch payload is reused)."""
        import pandas as pd

        from drep_tpu.index.classify import SketchedQueries, sketch_queries

        basenames = [os.path.basename(p) for p in paths]
        if len(set(basenames)) != len(basenames):
            # the batcher never co-batches basename colliders; stay
            # correct anyway if a caller bypasses it
            return sketch_queries(resident, paths, processes=self.cfg.processes)
        cached: dict[str, dict] = {}
        misses: list[str] = []
        keys = {p: self._sketch_key(p) for p in paths}
        with self._sketch_lock:
            for p in paths:
                ent = self._sketch_cache.get(keys[p]) if keys[p] else None
                if ent is None:
                    misses.append(p)
                else:
                    self._sketch_cache.move_to_end(keys[p])
                    cached[p] = ent
        if misses:
            sq = sketch_queries(resident, misses, processes=self.cfg.processes)
            with self._sketch_lock:
                for p in misses:
                    r = sq.results.get(f"query:{os.path.basename(p)}")
                    if r is None:
                        continue  # pragma: no cover — sketch_paths raises instead
                    cached[p] = r
                    if keys[p] is not None:
                        self._sketch_cache[keys[p]] = r
                while len(self._sketch_cache) > _SKETCH_CACHE_CAP:
                    self._sketch_cache.popitem(last=False)
        min_len = int(resident.params.get("filter_length", 0))
        gen = int(resident.generation)
        rows: dict[str, list] = {"genome": [], "location": []}
        results: dict[str, dict] = {}
        dropped: list[dict] = []
        for p in paths:
            base = os.path.basename(p)
            qn = f"query:{base}"
            r = cached[p]
            results[qn] = r
            if int(r["length"]) >= min_len:
                rows["genome"].append(qn)
                rows["location"].append(os.path.abspath(p))
            else:
                dropped.append({
                    "genome": base, "filtered": True,
                    "reason": f"below the index's filter length {min_len}",
                    "generation": gen,
                })
        return SketchedQueries(
            admitted=pd.DataFrame(rows), results=results, dropped=dropped,
        )
