"""Unit coverage for the grow-and-drain half of the elastic pod protocol
(ISSUE 9) — the note mechanics, verdict classes, and dealing invariants
that the multi-process cells in tests/test_elastic_updown.py exercise
end-to-end. Everything here is in-process and seconds-fast (tier-1);
separate HeartbeatManagers over one shared note dir stand in for pod
members (their call sequence is process-scoped, so each "member" resets
it — see _member)."""

import json
import os
import signal
import threading
import time

import numpy as np
import pytest

from drep_tpu.parallel import faulttol as ft
from drep_tpu.parallel.streaming import (
    deal_stripes,
    stripe_owner_live,
    stripe_weights,
)
from drep_tpu.utils.profiling import Counters, counters

CADENCE = 0.2


def _member(note_dir, pid, pc=2, max_dead=1, max_joins=0):
    """A pod member's manager with ITS OWN stage-sequence view (each real
    member is a separate process; in-process tests must not let one
    member's start() bump the sequence another member will read)."""
    ft._HB_SEQ[os.path.abspath(str(note_dir))] = 0
    hb = ft.HeartbeatManager(
        str(note_dir), CADENCE, max_dead=max_dead, pc=pc, pid=pid,
        max_joins=max_joins,
    )
    hb.start()
    return hb


@pytest.fixture(autouse=True)
def _clean_pod_state():
    ft.reset_pod()
    ft.clear_drain()
    counters.reset()
    yield
    ft.reset_pod()
    ft.clear_drain()
    counters.reset()


# --- drain: the planned-departure verdict class ---------------------------


def test_drain_adopted_without_staleness_wait(tmp_path):
    hb0 = _member(tmp_path, 0)
    hb1 = _member(tmp_path, 1)
    try:
        hb1.announce_drain(pairs=11)
        t_note = os.stat(hb1.drain_path()).st_mtime
        hb0._last_check = 0
        assert hb0.check() is True
        # immediate adoption: no 5x-cadence staleness window elapsed
        assert time.time() - t_note < ft.HEARTBEAT_MISS_FACTOR * CADENCE
        assert hb0.live == [0] and hb0.drained == [1] and hb0.dead == []
        assert counters.faults.get("planned_departures") == 1
        assert counters.faults.get("pod_epoch_bumps") == 1
        assert "dead_processes" not in counters.faults
        assert counters.gauges["drain_adopt_latency_s"] < (
            ft.HEARTBEAT_MISS_FACTOR * CADENCE
        )
        assert [e["reason"] for e in counters.epoch_history] == ["drain"]
        # the departing member's honest partial count rides the note
        assert hb0.drain_payload(1)["pairs"] == 11
    finally:
        hb0.close()
        hb1.close()


def test_drained_member_going_stale_is_not_double_counted(tmp_path):
    """The ISSUE-9 satellite regression: a drain immediately followed by
    the drained process's notes going stale must NOT be counted against
    --max_dead_processes. max_dead=0 makes any accidental death verdict
    raise, so the pass/fail is binary."""
    hb0 = _member(tmp_path, 0, max_dead=0)
    hb1 = _member(tmp_path, 1, max_dead=0)
    hb1.announce_drain(pairs=3)
    hb1.close()  # beat writer stops: the beats now go stale, like a real exit
    try:
        hb0._last_check = 0
        assert hb0.check() is True  # the drain bump
        # wait out the FULL staleness window, then re-check repeatedly:
        # the departed member must never mature into a death
        time.sleep(ft.HEARTBEAT_MISS_FACTOR * CADENCE + 0.3)
        for _ in range(3):
            hb0._last_check = 0
            hb0.check()  # max_dead=0: a death verdict would raise here
        assert hb0.dead == [] and hb0.drained == [1]
        assert "dead_processes" not in counters.faults
    finally:
        hb0.close()


def test_drain_note_is_seq_gated(tmp_path):
    """A previous stage's drain note must not depart a restarted member."""
    hb0 = _member(tmp_path, 0)
    hb1 = _member(tmp_path, 1)
    hb1.announce_drain()
    hb1.close()
    hb0.close()
    # next stage: hb1's incarnation restarts (start() clears its own
    # stale drain note) — and even a note that survived the cleanup is
    # rejected by its stale sequence number
    ft.reset_pod()
    hb0b = _member(tmp_path, 0)
    try:
        assert hb0b.seq == 1  # fresh member view of the same store
        stale = {"seq": 0, "epoch": 0, "pairs": 0, "at": time.time()}
        from drep_tpu.utils.durableio import atomic_write_json

        atomic_write_json(hb0b.drain_path(1), stale)
        hb0b._last_check = 0
        hb0b.check()
        assert hb0b.drained == [] and hb0b.live == [0, 1]
    finally:
        hb0b.close()


def test_request_drain_flag_and_sigterm_handler():
    assert not ft.drain_requested()
    ft.request_drain()
    assert ft.drain_requested()
    ft.clear_drain()
    # the SIGTERM wiring (--drain_grace_s): handler sets the flag; the
    # generous grace keeps the force-exit timer from firing in-test
    assert ft.install_drain_handler(grace_s=600.0) is True
    try:
        os.kill(os.getpid(), signal.SIGTERM)
        deadline = time.time() + 5
        while not ft.drain_requested() and time.time() < deadline:
            time.sleep(0.01)
        assert ft.drain_requested()
    finally:
        signal.signal(signal.SIGTERM, signal.SIG_DFL)
        ft.clear_drain()


def test_drain_fault_mode_is_site_restricted():
    from drep_tpu.utils import faults

    with pytest.raises(faults.FaultSpecError):
        faults._parse("streaming_tile:drain")
    rules = faults._parse("process_death:drain:1.0:proc=1")
    assert rules["process_death"][0].mode == "drain"
    rules = faults._parse("ring_step:drain")
    assert rules["ring_step"][0].mode == "drain"


# --- join: admission, adoption, budget ------------------------------------


def _request_join(note_dir, jid, token="tok"):
    from drep_tpu.utils.ckptmeta import atomic_write_bytes
    from drep_tpu.utils.durableio import atomic_write_json

    atomic_write_bytes(os.path.join(str(note_dir), f".pod-hb.p{jid}"), b"x")
    atomic_write_json(
        os.path.join(str(note_dir), f".pod-join.p{jid}"),
        {"token": token, "at": time.time()},
    )


def test_leader_admits_join_and_peer_adopts(tmp_path):
    hb0 = _member(tmp_path, 0, max_joins=1)
    hb1 = _member(tmp_path, 1, max_joins=1)
    try:
        _request_join(tmp_path, 5)
        # only the lowest-live leader admits; hb1's scan must not
        hb1._last_check = 0
        hb1.check()
        assert hb1.live == [0, 1]
        hb0._last_check = 0
        assert hb0.check() is True
        assert hb0.live == [0, 1, 5] and hb0.joined == [5]
        admit = json.loads(
            _strip_crc(open(os.path.join(str(tmp_path), ".pod-admit.p5")).read())
        )
        assert admit["pc"] == 2 and admit["token"] == "tok"
        assert admit["live"] == [0, 1, 5]
        # the peer adopts the published admit note (convergence without
        # any collective), regardless of its own join budget
        hb1._last_check = 0
        assert hb1.check() is True
        assert hb1.live == [0, 1, 5] and hb1.joined == [5]
        assert counters.faults.get("pod_joins") == 2  # counted per member
        # a pure join leaves the DOWNSTREAM pod state healthy (later
        # barriers keep the whole-pod collective path) but records the
        # admission for provenance
        assert ft.pod_live() is None
        assert ft.pod_joined() == [5]
    finally:
        hb0.close()
        hb1.close()


def test_join_budget_is_enforced(tmp_path):
    hb0 = _member(tmp_path, 0, max_joins=1)
    try:
        _request_join(tmp_path, 5, token="a")
        hb0._last_check = 0
        hb0.check()
        _request_join(tmp_path, 6, token="b")
        hb0._last_check = 0
        hb0.check()
        assert hb0.live == [0, 1, 5]
        assert not os.path.exists(os.path.join(str(tmp_path), ".pod-admit.p6"))
    finally:
        hb0.close()


def test_join_requires_fresh_candidate_beat(tmp_path):
    """Admitting a corpse would hand it stripes nobody computes until the
    staleness verdict claws them back — no beat, no admission."""
    from drep_tpu.utils.durableio import atomic_write_json

    hb0 = _member(tmp_path, 0, max_joins=1)
    try:
        atomic_write_json(
            os.path.join(str(tmp_path), ".pod-join.p5"),
            {"token": "t", "at": time.time()},
        )
        hb0._last_check = 0
        hb0.check()
        assert hb0.live == [0, 1] and hb0.joined == []
    finally:
        hb0.close()


def test_join_elastic_pod_handshake(tmp_path, monkeypatch):
    """The joiner-side entrypoint end to end (in-process: a thread plays
    the admitting leader): id derivation, admission, sequence adoption,
    membership wiring."""
    monkeypatch.setenv(ft.POD_JOIN_ENV, "auto")
    monkeypatch.setenv(ft.COLLECTIVE_TIMEOUT_ENV, "30")
    hb0 = _member(tmp_path, 0, max_joins=2)
    stop = threading.Event()

    def leader():
        while not stop.wait(0.05):
            hb0._last_check = 0
            hb0.check()

    t = threading.Thread(target=leader, daemon=True)
    t.start()
    try:
        ft._HB_SEQ[os.path.abspath(str(tmp_path))] = 0  # "another process"
        hb_j = ft.join_elastic_pod(
            str(tmp_path), CADENCE, config=ft.FaultTolConfig(max_joins=2),
        )
        try:
            assert hb_j.pid >= hb_j.pc == 2
            assert hb_j.pid in hb_j.live and 0 in hb_j.live
            assert hb_j.seq == hb0.seq  # adopted the pod's stage sequence
            assert hb_j.joined == [hb_j.pid]
            assert counters.faults.get("pod_join_accepted") == 1
        finally:
            hb_j.close()
    finally:
        stop.set()
        t.join()
        hb0.close()


def test_join_times_out_without_a_pod(tmp_path, monkeypatch):
    monkeypatch.setenv(ft.POD_JOIN_ENV, "7")
    with pytest.raises(ft.CollectiveTimeout):
        ft.join_elastic_pod(str(tmp_path), CADENCE, timeout_s=0.6)
    # the unadmitted request withdrew its notes: a later leader check can
    # never admit this corpse
    assert not os.path.exists(os.path.join(str(tmp_path), ".pod-join.p7"))
    assert not os.path.exists(os.path.join(str(tmp_path), ".pod-hb.p7"))


def test_stale_admit_note_never_resurrects_a_ghost_joiner(tmp_path):
    """Across a pod RESTART the stage sequence starts over, so the seq
    gate alone cannot reject a previous run's admit note — the fresh-beat
    requirement is what keeps the ghost out (a joiner with no live beat
    is adopted by nobody and consumes neither stripes nor the death
    budget)."""
    from drep_tpu.utils.durableio import atomic_write_json

    # "previous run": an admit note for joiner 5, whose beat is long gone
    atomic_write_json(
        os.path.join(str(tmp_path), ".pod-admit.p5"),
        {"pid": 5, "epoch": 1, "live": [0, 1, 5], "pc": 2, "seq": 1,
         "token": "t", "at": time.time()},
    )
    hb0 = _member(tmp_path, 0, max_dead=0, max_joins=1)
    try:
        assert hb0.seq == 1  # the restart's sequence COLLIDES with the note's
        hb0._last_check = 0
        hb0.check()  # max_dead=0: a ghost maturing into a death would raise
        assert hb0.live == [0, 1] and hb0.joined == [], (hb0.live, hb0.joined)
    finally:
        hb0.close()


def test_admission_freshness_uses_server_clock_reference(tmp_path):
    """Candidate freshness is judged against the leader's OWN beat mtime
    (server-clock-to-server-clock, the staleness verdicts' skew defense) —
    a shared-FS server clock lagging the host clock must not make every
    live candidate look stale and silently disable scale-up."""
    hb0 = _member(tmp_path, 0, max_joins=1)
    try:
        # freeze the beat writer FIRST so it cannot refresh the own-beat
        # mtime after the skew is staged
        hb0._stop.set()
        if hb0._thread is not None:
            hb0._thread.join(timeout=5)
        _request_join(tmp_path, 5)
        # simulate a server clock far behind the host clock: every beat
        # (the leader's own AND the candidate's) carries an old mtime
        lag = time.time() - 60.0
        os.utime(hb0.beat_path(), (lag, lag))
        os.utime(hb0.beat_path(5), (lag + 0.05, lag + 0.05))
        hb0._last_check = 0
        hb0.check()
        assert hb0.joined == [5], (hb0.live, hb0.joined)
    finally:
        hb0.close()


def test_admitted_joiner_that_never_validates_departs_as_drain(tmp_path, monkeypatch):
    """An operator pointing a joiner at the wrong inputs is admitted (the
    leader only sees a live candidate) but must leave as a PLANNED
    DEPARTURE when validation times out — not as a future death verdict
    charged against --max_dead_processes on a healthy pod."""
    monkeypatch.setenv(ft.POD_JOIN_ENV, "9")
    hb0 = _member(tmp_path, 0, pc=1, max_dead=0, max_joins=1)
    stop = threading.Event()

    def leader():
        while not stop.wait(0.05):
            hb0._last_check = 0
            hb0.check()

    t = threading.Thread(target=leader, daemon=True)
    t.start()
    try:
        with pytest.raises(ft.CollectiveTimeout, match="never matched"):
            ft.join_elastic_pod(
                str(tmp_path), CADENCE, config=ft.FaultTolConfig(max_joins=1),
                timeout_s=3.0, validate=lambda: False,
            )
        # the departure note is out: the pod re-deals immediately and the
        # ghost never matures into a death (max_dead=0 would raise)
        assert os.path.exists(os.path.join(str(tmp_path), ".pod-drain.p9"))
        time.sleep(ft.HEARTBEAT_MISS_FACTOR * CADENCE + 0.3)
        hb0._last_check = 0
        stop.set()
        t.join()
        hb0.check()
        assert 9 in hb0.drained and 9 not in hb0.live, (hb0.drained, hb0.live)
        assert "dead_processes" not in counters.faults
    finally:
        stop.set()
        t.join()
        hb0.close()


def test_join_request_without_heartbeats_refuses_loudly(tmp_path, monkeypatch):
    """DREP_TPU_POD_JOIN with the protocol unavailable must refuse, never
    degrade into an independent run racing the pod's live store."""
    from drep_tpu.errors import UserInputError
    from drep_tpu.ops.minhash import PackedSketches
    from drep_tpu.parallel.streaming import streaming_mash_edges

    ids = np.sort(
        np.random.default_rng(0).choice(2**20, size=(4, 16), replace=False)
    ).astype(np.int32)
    packed = PackedSketches(
        ids=np.sort(ids, axis=1), counts=np.full(4, 16, np.int32),
        names=[f"g{i}" for i in range(4)],
    )
    monkeypatch.setenv(ft.POD_JOIN_ENV, "auto")
    # no checkpoint dir at all: nothing to join through
    with pytest.raises(UserInputError, match="POD_JOIN"):
        streaming_mash_edges(packed, k=21, cutoff=0.2, block=4)
    # heartbeats disabled: admission cannot ride the protocol
    monkeypatch.setenv(ft.HEARTBEAT_ENV, "0")
    with pytest.raises(UserInputError, match="POD_JOIN"):
        streaming_mash_edges(
            packed, k=21, cutoff=0.2, block=4,
            checkpoint_dir=str(tmp_path / "ckpt"),
        )


# --- dealing invariants (satellites 1 + 3) --------------------------------


def _balanced_pairs(owners, n_blocks, live):
    """Mirror-paired balance: each member's PAIR count within +/-1."""
    pair_count = {p: 0.0 for p in live}
    for bi in range(n_blocks):
        pair_count[owners[bi]] += 0.5  # each mirror pair contributes 2 stripes
    vals = sorted(pair_count.values())
    return vals[-1] - vals[0] <= 1.0


@pytest.mark.parametrize("n_blocks", [1, 2, 5, 9, 16, 33])
@pytest.mark.parametrize(
    "live", [[0], [0, 1], [0, 2], [1, 2, 5], [0, 1, 2, 3], [0, 2, 3, 7, 9]]
)
def test_unweighted_deal_partitions_and_matches_mirror_pairing(n_blocks, live):
    owners = deal_stripes(n_blocks, live)
    assert len(owners) == n_blocks
    assert set(owners) <= set(live)  # partition: every stripe has a live owner
    assert owners == [stripe_owner_live(bi, n_blocks, live) for bi in range(n_blocks)]
    assert _balanced_pairs(owners, n_blocks, live)


@pytest.mark.parametrize("seed", range(6))
def test_weighted_deal_partitions_and_balances(seed):
    rng = np.random.default_rng(seed)
    n_blocks = int(rng.integers(3, 40))
    live = sorted(
        int(p) for p in rng.choice(12, size=int(rng.integers(2, 6)), replace=False)
    )
    weights = rng.integers(0, 50, size=n_blocks).astype(np.int64)
    owners = deal_stripes(n_blocks, live, weights)
    assert len(owners) == n_blocks and set(owners) <= set(live)
    loads = {p: 0 for p in live}
    for bi in range(n_blocks):
        loads[owners[bi]] += int(weights[bi])
    # greedy-LPT bound: spread never exceeds the heaviest single stripe
    spread = max(loads.values()) - min(loads.values())
    assert spread <= int(weights.max(initial=0)), (loads, weights.tolist())
    # deterministic: every member derives the identical deal
    assert owners == deal_stripes(n_blocks, live, weights)


@pytest.mark.parametrize("grown", [[0, 1, 2, 3], [0, 2, 3, 4, 9]])
def test_deal_under_live_set_growth_partitions_and_spares_published(grown):
    """Re-deal over a GROWN live set (mid-run join): still a partition,
    still balanced — and stripes that already have a published shard are
    never reassigned to compute (the loop only acts on MISSING stripes,
    whatever the new deal says)."""
    n_blocks = 9
    before = deal_stripes(n_blocks, [0, 1, 2])
    owners = deal_stripes(n_blocks, grown)
    assert set(owners) <= set(grown)
    assert _balanced_pairs(owners, n_blocks, grown)
    # simulate: stripes finished before the join keep their shards
    finished = {bi for bi in range(n_blocks) if before[bi] == 0}  # p0's done
    missing = [bi for bi in range(n_blocks) if bi not in finished]
    for pid in grown:
        to_compute = [bi for bi in missing if owners[bi] == pid]
        assert set(to_compute).isdisjoint(finished)
    # every missing stripe is still covered by exactly one member
    covered = [bi for pid in grown for bi in missing if owners[bi] == pid]
    assert sorted(covered) == missing


def test_stripe_weights_counts_occupied_tiles():
    occ = np.zeros((4, 4), dtype=bool)
    occ[0, 0] = occ[0, 3] = occ[2, 3] = True
    w = stripe_weights(occ, first_col_block=0)
    assert w.tolist() == [2, 0, 1, 0]
    # rectangular walks never count tiles left of the column restriction
    w2 = stripe_weights(occ, first_col_block=2)
    assert w2.tolist() == [1, 0, 1, 0]


# --- provenance + tooling honesty (satellite 5) ---------------------------


def test_scrub_recognizes_membership_notes_as_checked_json(tmp_path):
    import importlib.util

    from drep_tpu.utils.durableio import atomic_write_json

    spec = importlib.util.spec_from_file_location(
        "scrub_store",
        os.path.join(os.path.dirname(os.path.dirname(__file__)), "tools", "scrub_store.py"),
    )
    ss = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(ss)
    for name in (".pod-drain.p1", ".pod-join.p3", ".pod-admit.p3"):
        atomic_write_json(os.path.join(str(tmp_path), name), {"seq": 1})
    rep = ss.scrub([str(tmp_path)], out=open(os.devnull, "w"))
    # all three families are verified payloads — never orphans, never damage
    assert rep["verified"] == 3 and not rep["damaged"], rep
    # and a truncated membership note IS damage (not silently ignored)
    loc = os.path.join(str(tmp_path), ".pod-drain.p1")
    with open(loc, "w") as f:
        f.write('{"seq":')
    rep = ss.scrub([str(tmp_path)], out=open(os.devnull, "w"))
    assert any(loc in p for p, _ in rep["damaged"]), rep


def test_meta_provenance_keys_cover_membership_churn(tmp_path):
    from drep_tpu.utils.ckptmeta import (
        checkpoint_meta_matches,
        open_checkpoint_dir,
        stamp_checkpoint_meta,
    )

    meta = {"n": 4, "k": 21}
    open_checkpoint_dir(str(tmp_path), meta, clear_suffixes=(".npz",))
    stamp_checkpoint_meta(
        str(tmp_path),
        {"pod_epochs": 3, "dead_processes": [], "planned_departures": [1],
         "pod_joins": 2},
    )
    # churn provenance never invalidates a resume of the shards it describes
    assert checkpoint_meta_matches(str(tmp_path), meta)


def test_epoch_history_rides_perf_report():
    c = Counters()
    c.note_epoch(1, "drain")
    c.note_epoch(2, "join")
    assert [e["reason"] for e in c.epoch_history] == ["drain", "join"]
    assert c.gauges["pod_epoch"] == 2.0
    c.reset()
    assert c.epoch_history == []


def _strip_crc(text: str) -> str:
    """Admit notes carry the in-band durable-I/O crc — drop it for plain
    json.loads comparisons."""
    body = json.loads(text)
    body.pop("crc", None)
    return json.dumps(body)
