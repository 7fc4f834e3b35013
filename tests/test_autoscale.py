"""Autoscaling controller (ISSUE 15) — the fast tier-1 surface.

The policy contract first: ``decide()`` is PURE (snapshot in, decision
out — no clock, no env, no I/O), so every verdict class is pinned here
over synthetic snapshots without any pod: hysteresis dead band, cooldown,
min/max clamps, deadline-met hold, ETA-miss scale-up with the capacity
math, the cost-miss drain pick. Then the controller's read-only contract
(byte-for-byte digest over a planted checkpoint dir — the pod_status
idiom), the decision log, the ``autoscale_decide`` fault site, the
``pod_status --follow --json`` NDJSON stream, and the provenance story
(autoscale-stamped join/drain notes -> ``autoscale_churn``).

Multi-process cells (a controller governing a REAL pod under --deadline
pressure; the ring-phase JOIN speedup) live in
tests/test_autoscale_chaos.py (slow+chaos, chaos_matrix --autoscale).
"""

import io
import json
import os
import sys
import time

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

from drep_tpu.autoscale.controller import (  # noqa: E402
    AutoscaleController,
    default_decision_log,
)
from drep_tpu.autoscale.policy import Decision, Targets, decide  # noqa: E402
from drep_tpu.parallel import faulttol as ft  # noqa: E402
from drep_tpu.utils import envknobs, faults  # noqa: E402
from drep_tpu.utils.profiling import counters  # noqa: E402

NOW = 1_000_000.0


@pytest.fixture(autouse=True)
def _clean_state():
    ft.reset_pod()
    counters.reset()
    faults.reset()
    yield
    ft.reset_pod()
    counters.reset()
    faults.reset()


def _snap(n_live=3, eta=None, done=4, total=9, at=NOW, pending=0, **kw):
    s = {
        "checkpoint_dir": "/pod/ckpt",
        "observed_at": at,
        "live": list(range(n_live)),
        "pending_joins": list(range(100, 100 + pending)),
        "shards_published": done,
        "shards_total": total,
        "eta_s": eta,
    }
    s.update(kw)
    return s


def _targets(remaining=None, cost=None, **kw):
    kw.setdefault("min_procs", 1)
    kw.setdefault("max_procs", 8)
    kw.setdefault("cooldown_s", 30.0)
    kw.setdefault("hysteresis", 0.1)
    kw.setdefault("max_spawn", 1)
    return Targets(
        deadline_at=(NOW + remaining if remaining is not None else None),
        cost_proc_s=cost, **kw,
    )


# --- decide(): purity + every verdict class --------------------------------


def test_decide_pure_and_deterministic():
    snap = _snap(eta=300.0)
    t = _targets(remaining=100.0)
    before = json.dumps(snap, sort_keys=True)
    d1 = decide(snap, t, [])
    d2 = decide(snap, t, [])
    assert d1 == d2  # same inputs -> byte-same Decision (frozen dataclass)
    assert json.dumps(snap, sort_keys=True) == before  # snapshot untouched
    assert isinstance(d1, Decision) and d1.verdict == "scale_up"


def test_holds_without_evidence_or_targets():
    t = _targets(remaining=100.0)
    assert decide({"error": "cannot list"}, t, []).reason == "snapshot-error"
    assert decide(_snap(n_live=0), t, []).reason == "no-live-members"
    assert decide(_snap(done=9, total=9), t, []).reason == "finished"
    assert decide(_snap(eta=5.0), _targets(), []).reason == "no-targets"
    # deadline set but too little publish-rate signal for an ETA yet
    assert decide(_snap(eta=None), t, []).reason == "warming"


def test_scale_up_on_eta_miss_with_capacity_math():
    # 3 procs project 300s of work into a 100s window: ideal scaling says
    # 9 procs; capacity clamps (max_spawn, then max_procs) apply in turn
    d = decide(_snap(n_live=3, eta=300.0), _targets(remaining=100.0, max_spawn=2), [])
    assert (d.verdict, d.delta, d.reason) == ("scale_up", 2, "eta-misses-deadline")
    assert d.inputs["needed_procs"] == 9
    d = decide(_snap(n_live=3, eta=300.0),
               _targets(remaining=100.0, max_spawn=16, max_procs=5), [])
    assert (d.verdict, d.delta) == ("scale_up", 2)  # max_procs clamp


def test_scale_up_all_in_when_deadline_already_passed():
    d = decide(_snap(n_live=2, eta=50.0), _targets(remaining=-10.0, max_spawn=3), [])
    assert (d.verdict, d.delta, d.reason) == ("scale_up", 3, "deadline-passed")
    # a BLOWN deadline needs no ETA: warming must not starve the all-in
    # path when the rescue is already overdue
    d = decide(_snap(n_live=2, eta=None), _targets(remaining=-10.0, max_spawn=3), [])
    assert (d.verdict, d.delta, d.reason) == ("scale_up", 3, "deadline-passed")


def test_at_max_procs_counts_pending_joins_as_capacity():
    t = _targets(remaining=10.0, max_procs=4)
    d = decide(_snap(n_live=3, pending=1, eta=300.0), t, [])
    assert (d.verdict, d.reason) == ("hold", "at-max-procs")
    # one seat left once the pending join is gone
    assert decide(_snap(n_live=3, eta=300.0), t, []).verdict == "scale_up"


def test_cooldown_gates_scaling_not_holds():
    t = _targets(remaining=100.0)
    hist = [{"at": NOW - 5.0, "verdict": "scale_up", "delta": 1}]
    d = decide(_snap(eta=300.0), t, hist)
    assert (d.verdict, d.reason) == ("hold", "cooldown")
    assert d.inputs["cooldown_remaining_s"] == pytest.approx(25.0)
    # hold entries never gate; an aged scaling decision releases
    hist = [
        {"at": NOW - 45.0, "verdict": "scale_up", "delta": 1},
        {"at": NOW - 1.0, "verdict": "hold", "delta": 0},
    ]
    assert decide(_snap(eta=300.0), t, hist).verdict == "scale_up"


def test_hysteresis_dead_band_holds():
    # eta inside (remaining, remaining*(1+h)]: over the line but inside
    # the band — the policy must NOT flap
    t = _targets(remaining=100.0, hysteresis=0.2)
    assert decide(_snap(eta=115.0), t, []).reason == "deadline-met"
    assert decide(_snap(eta=121.0), t, []).verdict == "scale_up"


def test_cost_miss_picks_a_drain():
    # deadline comfortable even one proc down; projected proc-seconds
    # (3 * 200 = 600) over the 500 budget -> shed one
    d = decide(_snap(n_live=3, eta=200.0), _targets(remaining=1000.0, cost=500.0), [])
    assert (d.verdict, d.delta, d.reason) == ("scale_down", -1, "cost-over-budget")
    assert d.inputs["projected_cost_proc_s"] == pytest.approx(600.0)


def test_pending_joins_covering_the_projection_hold_not_pile_on():
    # needed = ceil(2*30/20) = 3; 2 live + 1 pending = 3 covers it — the
    # policy must wait for the admission, not spawn a 4th
    d = decide(_snap(n_live=2, pending=1, eta=30.0), _targets(remaining=20.0), [])
    assert (d.verdict, d.reason) == ("hold", "pending-covers")
    assert d.inputs["needed_procs"] == 3


def test_min_procs_zero_cannot_divide_by_zero():
    # --min_procs 0 with a single live member: the shrink floor is 1, so
    # the shrunk-eta projection never divides by zero
    d = decide(_snap(n_live=1, eta=200.0),
               _targets(cost=10.0, min_procs=0), [])
    assert d.verdict == "hold"


def test_cost_only_mode_respects_the_budget():
    # no deadline at all: the budget alone decides — within it, hold
    # (capacity is doing no harm); over it, shed
    d = decide(_snap(n_live=3, eta=100.0), _targets(cost=600.0), [])
    assert (d.verdict, d.reason) == ("hold", "within-cost")
    d = decide(_snap(n_live=3, eta=300.0), _targets(cost=600.0), [])
    assert (d.verdict, d.delta, d.reason) == ("scale_down", -1, "cost-over-budget")


def test_scale_down_clamps_and_headroom():
    # at min_procs: never drain below
    d = decide(_snap(n_live=2, eta=200.0),
               _targets(remaining=1000.0, cost=10.0, min_procs=2), [])
    assert (d.verdict, d.reason) == ("hold", "deadline-met")
    # over cost but the shrunk pod would bust the deadline: hold
    d = decide(_snap(n_live=3, eta=200.0), _targets(remaining=310.0, cost=500.0), [])
    assert (d.verdict, d.reason) == ("hold", "deadline-met")


# --- the controller: read-only contract, decision log, fault site ----------


def _plant_pod(ckpt, now=None):
    """A mid-run pod frozen in time: 3 live members, 4 of 9 stripes
    published with a measurable publish rate (the pod_status planted-
    store idiom, tests/test_trace_report.py)."""
    import numpy as np

    from drep_tpu.utils.ckptmeta import atomic_savez
    from drep_tpu.utils.durableio import atomic_write_json

    now = time.time() if now is None else now
    os.makedirs(ckpt, exist_ok=True)
    atomic_write_json(os.path.join(ckpt, "meta.json"),
                      {"n": 72, "block": 8, "n_blocks": 9})
    empty = np.empty(0, np.int64)
    for bi in range(4):
        p = os.path.join(ckpt, f"row_{bi:05d}.npz")
        atomic_savez(p, ii=empty, jj=empty, dist=np.empty(0, np.float32))
        os.utime(p, (now - 9 + 3 * bi, now - 9 + 3 * bi))
    for pid in (0, 1, 2):
        with open(os.path.join(ckpt, f".pod-hb.p{pid}"), "wb") as f:
            f.write(b"1")


def _dir_digest(root):
    import hashlib

    out = {}
    for dirpath, _dirs, files in os.walk(root):
        for name in files:
            p = os.path.join(dirpath, name)
            st = os.stat(p)
            with open(p, "rb") as f:
                out[os.path.relpath(p, root)] = (
                    st.st_size, st.st_mtime_ns, hashlib.sha256(f.read()).hexdigest()
                )
    return out


def test_controller_is_byte_for_byte_read_only_and_logs_decisions(tmp_path):
    ckpt = str(tmp_path / "pod" / "ckpt")
    _plant_pod(ckpt)
    before = _dir_digest(ckpt)
    ctl = AutoscaleController(
        ckpt, Targets(deadline_at=time.time() + 1e6), spawn_cmd=None,
        interval_s=0.01,
    )
    d1 = ctl.poll_once()
    d2 = ctl.poll_once()
    assert _dir_digest(ckpt) == before, "controller wrote into the checkpoint dir"
    assert d1.verdict == "hold" and d2.verdict == "hold"
    assert d1.reason == "deadline-met", d1
    # the decision log lives BESIDE the dir, one JSON line per decision
    log = default_decision_log(ckpt)
    assert os.path.dirname(log) == os.path.dirname(ckpt)
    with open(log, encoding="utf-8") as f:
        lines = [json.loads(ln) for ln in f.read().splitlines()]
    assert len(lines) == 2
    assert lines[0]["verdict"] == "hold" and "inputs" in lines[0]
    assert lines[0]["ckpt"] == os.path.abspath(ckpt)  # attributable per pod
    # holds never enter the cooldown history (only attempted scaling
    # decisions gate; the decision log keeps the full record)
    assert ctl.history == [] and ctl.decisions == 2


def test_controller_recommend_only_scale_up_is_logged_not_actuated(tmp_path):
    ckpt = str(tmp_path / "pod" / "ckpt")
    _plant_pod(ckpt)
    # deadline already passed -> scale_up; no --spawn command -> the
    # decision is recorded with the skip, nothing launches
    ctl = AutoscaleController(
        ckpt, Targets(deadline_at=time.time() - 5.0), spawn_cmd=None,
    )
    d = ctl.poll_once()
    assert d.verdict == "scale_up" and d.reason == "deadline-passed"
    with open(default_decision_log(ckpt), encoding="utf-8") as f:
        rec = json.loads(f.read().splitlines()[-1])
    assert rec["verdict"] == "scale_up"
    assert "no --spawn" in rec["actuation"]
    assert not ctl.spawned


def test_controller_spawn_env_carries_the_protocol_knobs(tmp_path):
    ckpt = str(tmp_path / "pod" / "ckpt")
    _plant_pod(ckpt)
    probe = tmp_path / "probe.py"
    out = tmp_path / "joiner_env.json"
    probe.write_text(
        "import json, os, sys\n"
        "json.dump({k: os.environ.get(k) for k in\n"
        "           ('DREP_TPU_POD_JOIN', 'DREP_TPU_AUTOSCALE_SPAWNED')},\n"
        "          open(sys.argv[1], 'w'))\n"
    )
    # max_spawn=2 ABOVE the env knob's default of 1: the resolved Targets
    # govern actuation, never a silent re-read of the raw knob
    ctl = AutoscaleController(
        ckpt, Targets(deadline_at=time.time() - 5.0, max_spawn=2),
        spawn_cmd=f"{sys.executable} {probe} {out}",
    )
    d = ctl.poll_once()
    assert d.verdict == "scale_up" and d.delta == 2
    assert len(ctl.spawned) == 2
    assert all(p.wait(timeout=60) == 0 for p in ctl.spawned)
    got = json.loads(out.read_text())
    # THE actuation surface: the joiner self-registers via the pod
    # protocol and stamps its churn notes autoscale-driven
    assert got["DREP_TPU_POD_JOIN"] == "auto"
    assert got["DREP_TPU_AUTOSCALE_SPAWNED"] == "1"


def test_max_spawn_zero_decides_but_never_spawns(tmp_path):
    # the policy side: delta clamps to 0 -> hold, never a scale_up whose
    # actuation would contradict the clamp
    d = decide(_snap(eta=300.0), _targets(remaining=100.0, max_spawn=0), [])
    assert (d.verdict, d.reason) == ("hold", "spawn-clamped")
    # the controller side: even a hand-built delta cannot spawn past it
    ckpt = str(tmp_path / "ckpt")
    _plant_pod(ckpt)
    ctl = AutoscaleController(
        ckpt, Targets(deadline_at=time.time() - 5.0, max_spawn=0),
        spawn_cmd=f"{sys.executable} -c pass",
    )
    assert ctl.poll_once().verdict == "hold"
    assert not ctl.spawned


def test_broken_spawn_command_records_the_failure_not_a_crash(tmp_path):
    ckpt = str(tmp_path / "ckpt")
    _plant_pod(ckpt)
    ctl = AutoscaleController(
        ckpt, Targets(deadline_at=time.time() - 5.0),
        spawn_cmd="/nonexistent-binary-xyzzy --flag",
    )
    d = ctl.poll_once()  # must not raise: the decision is the evidence
    assert d.verdict == "scale_up"
    with open(default_decision_log(ckpt), encoding="utf-8") as f:
        rec = json.loads(f.read().splitlines()[-1])
    assert rec["actuation"].startswith("FAILED:"), rec
    assert not ctl.spawned


def test_controller_exits_when_there_is_no_pod_to_govern(tmp_path):
    """A SIGKILLed pod (or a vanished checkpoint dir) must not leave the
    controller polling forever: after idle_exit_s of continuous
    nothing-to-govern it exits 0 — it is advisory, exiting is safe."""
    ctl = AutoscaleController(
        str(tmp_path / "never_created"), Targets(deadline_at=time.time() + 60),
        interval_s=0.01, idle_exit_s=0.05,
    )
    t0 = time.monotonic()
    assert ctl.run() == 0
    assert time.monotonic() - t0 < 10.0
    assert ctl.decisions >= 2  # it genuinely polled before giving up


def test_autoscale_decide_fault_site_registered_and_validated(tmp_path):
    ckpt = str(tmp_path / "ckpt")
    _plant_pod(ckpt)
    faults.configure("autoscale_decide:raise")
    ctl = AutoscaleController(ckpt, Targets())
    # the controller does NOT contain the fault: its death is harmless by
    # design (workers never depend on it), so the chaos mode takes the
    # loop down loudly instead of pretending to govern
    with pytest.raises(faults.InjectedFault):
        ctl.poll_once()
    assert counters.faults.get("injected_autoscale_decide_raise") == 1
    faults.configure(None)
    # spec validation: modes with no semantics at this site refuse at
    # parse time (a chaos run must never silently inject nothing)
    with pytest.raises(faults.FaultSpecError):
        faults.configure("autoscale_decide:drain")
    with pytest.raises(faults.FaultSpecError):
        faults.configure("autoscale_decide:torn")
    with pytest.raises(faults.FaultSpecError):
        faults.configure("autoscale_decide:io_error")


def test_autoscale_knobs_registered():
    for name, kind in (
        ("DREP_TPU_AUTOSCALE_INTERVAL_S", "float"),
        ("DREP_TPU_AUTOSCALE_COOLDOWN_S", "float"),
        ("DREP_TPU_AUTOSCALE_MAX_SPAWN", "int"),
        ("DREP_TPU_AUTOSCALE_SPAWNED", "bool"),
    ):
        assert envknobs.knob(name).kind == kind
    assert envknobs.env_float("DREP_TPU_AUTOSCALE_INTERVAL_S") == 5.0
    assert envknobs.env_int("DREP_TPU_AUTOSCALE_MAX_SPAWN") == 1
    assert envknobs.env_bool("DREP_TPU_AUTOSCALE_SPAWNED") is False


# --- pod_status --follow --json: the NDJSON stream -------------------------


def test_follow_json_emits_one_ndjson_snapshot_per_interval(tmp_path):
    from tools import pod_status

    ckpt = str(tmp_path / "ckpt")
    _plant_pod(ckpt)
    buf = io.StringIO()
    rc = pod_status.follow(ckpt, interval_s=0.01, count=3, out=buf, as_json=True)
    assert rc == 0
    lines = buf.getvalue().splitlines()
    assert len(lines) == 3, lines
    for ln in lines:
        snap = json.loads(ln)  # every line parses alone — the NDJSON contract
        assert snap["shards_published"] == 4 and snap["shards_total"] == 9
        assert "\n" not in ln
    assert "--- poll" not in buf.getvalue()  # no banners in machine mode
    assert "\x1b[" not in buf.getvalue()  # no ANSI in machine mode


# --- provenance: autoscale-stamped churn -> counters -> refusal ------------


def _member(note_dir, pid, pc=2, max_joins=0):
    ft._HB_SEQ[os.path.abspath(str(note_dir))] = 0
    hb = ft.HeartbeatManager(
        str(note_dir), 0.2, max_dead=1, pc=pc, pid=pid, max_joins=max_joins
    )
    hb.start()
    return hb


def test_autoscale_stamped_join_books_churn_on_every_member(tmp_path):
    from drep_tpu.utils.ckptmeta import atomic_write_bytes
    from drep_tpu.utils.durableio import atomic_write_json

    hb0 = _member(tmp_path, 0, max_joins=1)
    hb1 = _member(tmp_path, 1)
    try:
        # a controller-spawned joiner's request: beating, stamped
        atomic_write_bytes(str(tmp_path / ".pod-hb.p2"), b"join-candidate:x")
        atomic_write_json(
            str(tmp_path / ".pod-join.p2"),
            {"token": "x", "at": time.time(), "autoscale": True},
        )
        assert hb0.check()  # leader admits
        assert hb0.joined == [2]
        assert counters.faults.get("autoscale_churn") == 1
        # the admit note relays the stamp, so adopters book it too
        note = ft.read_pod_note(str(tmp_path / ".pod-admit.p2"))
        assert note and note.get("autoscale") is True
        assert hb1.check()  # peer adopts the published admit note
        assert counters.faults.get("autoscale_churn") == 2
        assert counters.faults.get("pod_joins") == 2
    finally:
        hb0.close()
        hb1.close()


def test_autoscale_stamped_drain_books_churn(tmp_path, monkeypatch):
    hb0 = _member(tmp_path, 0)
    hb1 = _member(tmp_path, 1)
    try:
        monkeypatch.setenv("DREP_TPU_AUTOSCALE_SPAWNED", "1")
        hb1.announce_drain(pairs=7)
        monkeypatch.delenv("DREP_TPU_AUTOSCALE_SPAWNED")
        note = ft.read_pod_note(hb1.drain_path(1))
        assert note and note.get("autoscale") is True
        assert hb0.check()
        assert hb0.drained == [1]
        assert counters.faults.get("autoscale_churn") == 1
        assert counters.faults.get("planned_departures") == 1
    finally:
        hb0.close()
        hb1.close()


def test_unstamped_churn_books_no_autoscale_provenance(tmp_path):
    hb0 = _member(tmp_path, 0)
    hb1 = _member(tmp_path, 1)
    try:
        hb1.announce_drain(pairs=7)
        assert hb0.check()
        assert "autoscale_churn" not in counters.faults
    finally:
        hb0.close()
        hb1.close()
