"""The autoscaling controller loop: watch, decide, actuate, record.

A long-lived external process (``tools/pod_autoscale.py``) that governs
a RUNNING elastic pod without the workers knowing it exists:

- WATCH — every tick is one read-only ``tools/pod_status.collect()``
  snapshot of the pod's shared checkpoint dir (the byte-for-byte reader
  contract ``--follow`` and the serve daemon's /healthz already share;
  pinned by a digest test here too: the controller never writes a byte
  INTO the checkpoint dir).
- DECIDE — the snapshot feeds the pure policy
  (:func:`drep_tpu.autoscale.policy.decide`); the controller owns the
  clock and the history, the policy owns the verdict.
- ACTUATE — only through the existing pod protocol: scale-up spawns
  joiner processes (the operator's ``--spawn`` command) with
  ``DREP_TPU_POD_JOIN=auto`` + ``DREP_TPU_AUTOSCALE_SPAWNED=1`` in their
  environment; scale-down SIGTERMs the most recently spawned still-live
  joiner (the graceful-drain path — the departure note publishes, peers
  re-deal with no staleness wait). The controller only ever retires
  capacity IT added: original members' OS pids are unknowable from the
  store, and killing operator-owned processes is not this tool's call.
- RECORD — every decision lands twice: an ``autoscale_decision``
  telemetry instant (merged by tools/trace_report.py next to the
  membership timeline) and one JSON line in the durable decision log
  (``autoscale.jsonl`` beside — never inside — the checkpoint dir;
  telemetry-sink idiom: whole-line append+flush, a torn tail reads as
  crash evidence).

FAILURE MODEL: the controller is advisory. Workers never wait on it,
never read its log, never know it exists — SIGKILL it at any instant and
the pod finishes exactly as it would have (spawned joiners are admitted
members by then; un-spawned capacity simply never arrives). That is why
``autoscale_decide`` fault modes that take the controller down are a
legitimate chaos cell, not a survivability hole.
"""

from __future__ import annotations

import json
import os
import shlex
import signal
import subprocess
import time

from drep_tpu.autoscale.policy import Decision, Targets, decide
from drep_tpu.utils import envknobs, faults, telemetry
from drep_tpu.utils.logger import get_logger

__all__ = ["AutoscaleController", "AUTOSCALE_TELEMETRY_PID", "default_decision_log"]

# the controller's telemetry stream id: far above any plausible pod
# member/joiner id, so its events.p999.jsonl can never collide with a
# worker's log in the merged trace
AUTOSCALE_TELEMETRY_PID = 999


def default_decision_log(ckpt_dir: str) -> str:
    """``autoscale.jsonl`` BESIDE the watched checkpoint dir (its parent
    directory) — the controller's zero-writes-into-the-store contract is
    byte-for-byte, so the log must live outside it."""
    return os.path.join(
        os.path.dirname(os.path.abspath(ckpt_dir)), "autoscale.jsonl"
    )


def _append_decision(path: str, record: dict) -> None:
    """One whole JSON line per decision, flushed — the telemetry sink's
    crash-safety idiom (a SIGKILL tears at most the final line, which
    every JSONL reader in this repo classifies as crash evidence)."""
    line = json.dumps(record, separators=(",", ":"), default=str)
    # drep-lint: allow[durable-funnel] — append-only crash-safe decision log (telemetry-sink idiom: whole-line write+flush; atomic-replace would re-write the whole history per tick)
    with open(path, "a", encoding="utf-8") as f:
        f.write(line + "\n")
        f.flush()


class AutoscaleController:
    """One watch/decide/actuate loop bound to one checkpoint dir.

    `targets` is the resolved :class:`Targets`; `spawn_cmd` is the full
    joiner command line (None = recommend-only: decisions are logged and
    traced but nothing spawns); `decision_log` defaults beside the
    checkpoint dir. `interval_s` falls back to
    ``DREP_TPU_AUTOSCALE_INTERVAL_S``.
    """

    def __init__(
        self,
        ckpt_dir: str,
        targets: Targets,
        spawn_cmd: str | None = None,
        interval_s: float | None = None,
        decision_log: str | None = None,
        spawn_env: dict | None = None,
        idle_exit_s: float = 300.0,
    ) -> None:
        self.ckpt_dir = ckpt_dir
        self.targets = targets
        self.spawn_cmd = spawn_cmd
        self.interval_s = (
            envknobs.env_float("DREP_TPU_AUTOSCALE_INTERVAL_S")
            if interval_s is None
            else float(interval_s)
        )
        self.decision_log = (
            default_decision_log(ckpt_dir) if decision_log is None else decision_log
        )
        self._spawn_env = spawn_env
        # continuous seconds of "nothing to govern" (snapshot errors, or
        # no live members without completion) before run() gives up — a
        # SIGKILLed pod or a deleted checkpoint dir must not leave the
        # controller polling forever (it is advisory: exiting is always
        # safe). Generous default: pod members take a while to start
        # beating, and a brief shared-FS outage must heal, not exit.
        self.idle_exit_s = float(idle_exit_s)
        self.history: list[dict] = []
        self.spawned: list[subprocess.Popen] = []
        self.decisions = 0
        self._log = get_logger()
        self._last_warned: tuple | None = None

    # -- actuation --------------------------------------------------------
    def _spawn_joiners(self, count: int) -> str:
        if not self.spawn_cmd:
            return "skipped: no --spawn command (recommend-only mode)"
        # the policy already clamped delta by targets.max_spawn (the CLI
        # resolved the env knob into Targets) — re-reading the raw knob
        # here would silently override an explicit --max_spawn and make
        # the actuation contradict the logged decision
        count = min(count, self.targets.max_spawn)
        if count <= 0:
            return "skipped: max_spawn is 0"
        env = dict(self._spawn_env if self._spawn_env is not None else os.environ)
        # the whole actuation surface: the joiner self-registers through
        # the pod protocol (join note + heartbeat, leader admission) and
        # stamps its churn notes as autoscale-driven so the governed
        # run's records book `autoscale_churn`
        env["DREP_TPU_POD_JOIN"] = "auto"
        env["DREP_TPU_AUTOSCALE_SPAWNED"] = "1"
        argv = shlex.split(self.spawn_cmd)
        for _ in range(count):
            self.spawned.append(subprocess.Popen(argv, env=env))
        return f"spawned {count} joiner(s) (pids {[p.pid for p in self.spawned[-count:]]})"

    def _drain_joiners(self, count: int) -> str:
        alive = [p for p in self.spawned if p.poll() is None]
        if not alive:
            return "skipped: no controller-spawned capacity left to drain"
        victims = alive[-count:] if count else alive[-1:]
        for p in victims:
            try:
                p.send_signal(signal.SIGTERM)
            except OSError:
                pass
        return f"SIGTERMed joiner pid(s) {[p.pid for p in victims]} (graceful drain)"

    def _actuate(self, decision: Decision) -> str:
        try:
            if decision.verdict == "scale_up":
                return self._spawn_joiners(decision.delta)
            if decision.verdict == "scale_down":
                return self._drain_joiners(-decision.delta)
        except Exception as e:  # noqa: BLE001 — a broken --spawn command
            # (typo'd binary, bad quoting) must not take the controller
            # down BEFORE the decision records: the decision log is the
            # operator's evidence of what was attempted and why it failed
            self._log.warning("autoscale: actuation failed: %r", e)
            return f"FAILED: {e!r}"
        return ""

    # -- the loop ---------------------------------------------------------
    def poll_once(self) -> Decision:
        """One tick: snapshot -> decide -> actuate -> record. Read-only
        against the checkpoint dir by the same contract as pod_status
        (digest-asserted in tests/test_autoscale.py)."""
        from drep_tpu.utils.hosttools import pod_status_collect

        faults.fire("autoscale_decide")
        collect = pod_status_collect()
        snapshot = (
            collect(self.ckpt_dir)
            if collect is not None
            else {"error": "tools/pod_status.py unreachable (installed "
                           "package without the repo checkout)"}
        )
        decision = decide(snapshot, self.targets, self.history)
        self.decisions += 1
        at = snapshot.get("observed_at")
        actuation = self._actuate(decision)
        # the cooldown history holds only ATTEMPTED scaling decisions: a
        # SKIPPED one (futile drain with nothing controller-owned left,
        # recommend-only spawn) re-arming the cooldown would starve a
        # genuinely needed scale_up for a full window after every no-op —
        # and holds never gate anything (the decision log keeps the full
        # record), so keeping them here would only grow an unbounded list
        # decide() rescans every tick
        if (
            at is not None
            and decision.verdict != "hold"
            and not actuation.startswith("skipped")
        ):
            self.history.append(
                {"at": at, "verdict": decision.verdict, "delta": decision.delta}
            )
        record = {
            "at": at,
            "ckpt": os.path.abspath(self.ckpt_dir),
            "verdict": decision.verdict,
            "delta": decision.delta,
            "reason": decision.reason,
            "inputs": decision.inputs,
            "actuation": actuation,
        }
        self._append_record(record)  # drep-lint: allow[reader-purity] — the ONE write this entrypoint owns: the append-only decision log, which lives BESIDE (never inside) the watched checkpoint dir; the dir itself stays byte-for-byte untouched (digest-pinned in tests/test_autoscale.py)
        telemetry.event(
            "autoscale_decision",
            verdict=decision.verdict,
            delta=decision.delta,
            reason=decision.reason,
            **decision.inputs,
        )
        if decision.verdict != "hold":
            sig = (decision.verdict, decision.reason, actuation)
            if not (actuation.startswith("skipped") and sig == self._last_warned):
                # a futile decision repeating every tick (recommend-only
                # mode, nothing left to drain) is logged/traced once per
                # change, not once per interval
                self._log.warning(
                    "autoscale: %s %+d (%s) — %s",
                    decision.verdict, decision.delta, decision.reason, actuation,
                )
                self._last_warned = sig
        return decision

    def _append_record(self, record: dict) -> None:
        try:
            _append_decision(self.decision_log, record)
        except OSError as e:  # the log is observability, never a dependency
            self._log.warning("autoscale: decision log unwritable: %s", e)

    def finished(self, decision: Decision) -> bool:
        """The pod ran to completion: every shard published and nobody
        live — the controller's natural exit."""
        return decision.reason in ("finished", "no-live-members") and bool(
            decision.inputs.get("shards_total")
        ) and decision.inputs.get("shards_published", 0) >= decision.inputs.get(
            "shards_total", 0
        )

    def run(self, count: int = 0) -> int:
        """Poll until the pod finishes (or `count` ticks, for tests).
        Returns 0; a dying pod is a report, not a controller failure."""
        n = 0
        idle_since = None
        try:
            while True:
                decision = self.poll_once()
                n += 1
                if count and n >= count:
                    break
                if self.finished(decision):
                    self._log.info(
                        "autoscale: pod finished after %d decision(s) — exiting",
                        self.decisions,
                    )
                    break
                if decision.reason in ("snapshot-error", "no-live-members"):
                    # nothing to govern: a pod that died mid-run (members
                    # gone, shards incomplete) or a vanished checkpoint
                    # dir would otherwise poll forever
                    now = time.monotonic()
                    if idle_since is None:
                        idle_since = now
                    elif now - idle_since > self.idle_exit_s:
                        self._log.warning(
                            "autoscale: no governable pod for %.0fs (%s) — "
                            "exiting (the controller is advisory; restart "
                            "it with the pod)",
                            self.idle_exit_s, decision.reason,
                        )
                        break
                else:
                    idle_since = None
                time.sleep(max(0.05, self.interval_s))
        except KeyboardInterrupt:
            pass
        finally:
            # reap what we spawned, never kill it: a live joiner is a pod
            # MEMBER now — taking it down would be a death, not a drain
            for p in self.spawned:
                if p.poll() is None:
                    self._log.info(
                        "autoscale: leaving spawned joiner pid %d running "
                        "(it is a pod member; the pod owns its lifecycle)",
                        p.pid,
                    )
        return 0
