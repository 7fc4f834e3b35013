"""Scale-UP elasticity end-to-end (ISSUE 9): mid-run JOIN and graceful
DRAIN on real `jax.distributed` CPU pods — the four `--elastic` cells of
tools/chaos_matrix.py.

Every cell pins BIT-IDENTITY of the final edges/matrix against a
fixed-membership oracle: joiners take ids past the original process
count and the file-based gather assembles in the canonical epoch-0
order, so membership churn may change who computes, never what comes
out. The drain cell additionally pins the degradation-latency contract
on the re-deal timestamp (the drain-note-to-adoption gauge), not on
wall-clock sleeps: a planned departure costs ~one liveness check, never
the 5x-cadence staleness window a death costs.

Marked `slow` (each needs a pod launch + interpreter startups) — tier-1
runs the in-process protocol tests (tests/test_elastic_protocol.py);
chaos_matrix --elastic runs these by explicit id."""

import glob
import json
import os
import signal
import socket
import subprocess
import sys
import time

import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
WORKER = os.path.join(os.path.dirname(os.path.abspath(__file__)), "_multihost_worker.py")

CADENCE_S = 0.25
MISS_S = 5 * CADENCE_S  # the staleness window a DEATH would have cost

pytestmark = [pytest.mark.chaos, pytest.mark.slow]


def _free_port() -> int:
    s = socket.socket()
    s.bind(("localhost", 0))
    port = s.getsockname()[1]
    s.close()
    return port


def _base_env(faults=None, extra=None):
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=2"
    env["PYTHONPATH"] = REPO + os.pathsep + env.get("PYTHONPATH", "")
    env["DREP_TPU_HEARTBEAT_S"] = str(CADENCE_S)
    env["DREP_TPU_COLLECTIVE_TIMEOUT_S"] = "90"
    env.pop("DREP_TPU_FAULTS", None)
    env.pop("DREP_TPU_POD_JOIN", None)
    if faults:
        env["DREP_TPU_FAULTS"] = faults
    if extra:
        env.update(extra)
    return env


def _launch_pod(outdir, ckpt, mode, nproc, faults=None, extra_env=None):
    port = _free_port()
    env = _base_env(faults, extra_env)
    os.makedirs(outdir, exist_ok=True)
    return [
        subprocess.Popen(
            [
                sys.executable, WORKER, str(i), str(nproc),
                f"localhost:{port}", str(outdir), mode, str(ckpt),
            ],
            env=env, stdout=subprocess.PIPE, stderr=subprocess.STDOUT, cwd=REPO,
        )
        for i in range(nproc)
    ]


def _launch_joiner(outdir, ckpt, mode, join_id, after_drain=False):
    extra = {"DREP_TPU_POD_JOIN": str(join_id)}
    if after_drain:
        extra["DREP_TPU_TEST_JOIN_AFTER_DRAIN"] = "1"
    return subprocess.Popen(
        [
            sys.executable, WORKER, "0", "1", "localhost:0",
            str(outdir), mode, str(ckpt),
        ],
        env=_base_env(extra=extra),
        stdout=subprocess.PIPE, stderr=subprocess.STDOUT, cwd=REPO,
    )


def _reap(procs, timeout=300):
    outs = []
    try:
        for p in procs:
            out, _ = p.communicate(timeout=timeout)
            outs.append(out.decode(errors="replace"))
    finally:
        for p in procs:
            if p.poll() is None:
                p.kill()
                p.communicate()
    return outs


def _edges(outdir, who):
    with np.load(os.path.join(str(outdir), f"edges_{who}.npz")) as z:
        return z["ii"].copy(), z["jj"].copy(), z["dd"].copy(), int(z["pairs"])


def _ctr(outdir, who) -> dict:
    with open(os.path.join(str(outdir), f"counters_{who}.json")) as f:
        return json.load(f)


def _meta(ckpt) -> dict:
    with open(os.path.join(str(ckpt), "meta.json")) as f:
        return json.load(f)


@pytest.fixture(scope="module")
def healthy_edges(tmp_path_factory):
    """The fixed-membership oracle: one healthy 3-process elastic pod,
    shared by every streaming cell (the canonical epoch-0 assembly order
    is a function of (n_blocks, pc=3) alone, so any churned pod's output
    must match these BYTES exactly)."""
    base = tmp_path_factory.mktemp("healthy")
    outdir, ckpt = str(base / "out"), str(base / "ckpt")
    outs = _reap(_launch_pod(outdir, ckpt, "elastic", nproc=3))
    for i in range(3):
        assert os.path.exists(os.path.join(outdir, f"ok_{i}")), (
            f"healthy worker {i}:\n{outs[i]}"
        )
    return _edges(outdir, 0)


def test_join_mid_streaming_bit_identical(tmp_path, healthy_edges):
    """Mid-run JOIN into a streaming pod: a 4th process (its own
    single-process jax runtime — NOT part of the jax.distributed pod)
    is admitted by the leader, computes re-dealt stripes, and every
    member INCLUDING the joiner assembles edges byte-identical to the
    fixed-membership oracle. The pod is gated on the join-request note
    (DREP_TPU_TEST_WAIT_JOIN) so admission deterministically lands while
    work remains."""
    outdir, ckpt = str(tmp_path / "out"), str(tmp_path / "ckpt")
    pod = _launch_pod(
        outdir, ckpt, "elastic", nproc=3,
        # pace each stripe so the grown-set re-deal has work left to deal
        faults="process_death:sleep:1.0:secs=0.3",
        extra_env={
            "DREP_TPU_TEST_MAX_JOINS": "2",
            "DREP_TPU_TEST_WAIT_JOIN": "1",
        },
    )
    joiner = _launch_joiner(outdir, ckpt, "join_streaming", join_id=3)
    outs = _reap(pod + [joiner])
    for i, p in enumerate(pod):
        assert p.returncode == 0, f"pod worker {i} failed:\n{outs[i]}"
        assert os.path.exists(os.path.join(outdir, f"ok_{i}")), outs[i]
    assert joiner.returncode == 0, f"joiner failed:\n{outs[-1]}"
    assert os.path.exists(os.path.join(outdir, "ok_joiner")), outs[-1]

    h = healthy_edges
    for who in (0, 1, 2, "joiner"):
        e = _edges(outdir, who)
        assert all(
            a.tobytes() == b.tobytes() for a, b in zip(e[:3], h[:3])
        ), f"member {who}'s edges differ from the fixed-membership oracle"
    # the joiner genuinely computed re-dealt work (the wait-join gate
    # guarantees admission before the first re-deal pass completes)
    jc = _ctr(outdir, "joiner")
    assert jc.get("pod_join_accepted") == 1, jc
    assert _edges(outdir, "joiner")[3] > 0, "joiner was admitted but computed nothing"
    # every ORIGINAL member adopted the admission (leader admits, the
    # rest follow the admit note) and counted it honestly
    for i in range(3):
        assert _ctr(outdir, i).get("pod_joins", 0) >= 1, _ctr(outdir, i)
    # membership churn is stamped into the store's provenance
    meta = _meta(ckpt)
    assert meta.get("pod_joins", 0) >= 1, meta
    assert meta.get("dead_processes") == [], meta
    # no member ever computed the same pairs twice per the totals: the
    # member-set totals all equal the full pair count (done-notes cover
    # every member including the joiner)
    assert _edges(outdir, 0)[3] >= h[3]


def test_drain_mid_streaming_bit_identical(tmp_path, healthy_edges):
    """Graceful DRAIN mid-streaming: process 1 receives the drain fault
    at its second owned stripe, finishes it, publishes the planned-
    departure note, and exits 0; the survivors bump the epoch with NO
    staleness wait (pinned on the adoption-latency gauge, i.e. the
    re-deal timestamp relative to the note — not wall-clock sleeps),
    re-deal the rest, and finish byte-identical to the oracle. max_dead
    is pinned to 0 so any mis-classification of the drain as a death
    aborts the run loudly (the satellite regression)."""
    outdir, ckpt = str(tmp_path / "out"), str(tmp_path / "ckpt")
    pod = _launch_pod(
        outdir, ckpt, "elastic", nproc=3,
        faults=(
            "process_death:drain:1.0:proc=1:skip=1,"
            "process_death:sleep:1.0:secs=0.15"
        ),
        extra_env={"DREP_TPU_TEST_MAX_DEAD": "0"},
    )
    outs = _reap(pod)
    for i, p in enumerate(pod):
        assert p.returncode == 0, f"worker {i} failed:\n{outs[i]}"
    # the drained member leaves a drained marker + counters, never an ok
    assert os.path.exists(os.path.join(outdir, "drained_1")), outs[1]
    assert not os.path.exists(os.path.join(outdir, "ok_1"))
    c1 = _ctr(outdir, 1)
    assert c1.get("drain_announced") == 1, c1
    assert c1.get("injected_process_death_drain") == 1, c1

    h = healthy_edges
    for pid in (0, 2):
        e = _edges(outdir, pid)
        assert all(
            a.tobytes() == b.tobytes() for a, b in zip(e[:3], h[:3])
        ), f"survivor {pid}'s edges differ from the fixed-membership oracle"
        # honest accounting: the drained member's partial pairs ride its
        # departure note, so NO pairs are lost (a death takes its
        # unreported pairs with it: the killed cell pins e[3] < h[3]).
        # The total may EXCEED the oracle's: the modulo re-deal can move
        # a still-live survivor's unstarted stripe mid-flight, and the
        # protocol prefers a duplicated stripe over an ownership hole.
        assert e[3] >= h[3], (e[3], h[3])
        ctr = _ctr(outdir, pid)
        assert ctr.get("planned_departures") == 1, ctr
        assert ctr.get("pod_epoch_bumps") == 1, ctr
        # the drain was never double-counted as a death (max_dead=0
        # would have aborted; the counter must agree)
        assert "dead_processes" not in ctr, ctr
        # THE latency contract: adoption (== the re-deal pass that
        # follows it in the same tick) happened within the liveness-check
        # cadence of the note's publish — far inside the staleness window
        # a death would have burned
        lat = ctr.get("gauges", {}).get("drain_adopt_latency_s")
        assert lat is not None and lat < MISS_S, (lat, MISS_S)
    # the re-dealt stripes carry the bumped epoch in their shard names
    shards = sorted(
        f for f in os.listdir(ckpt) if f.startswith("row_") and ".e01." in f
    )
    assert shards, os.listdir(ckpt)
    meta = _meta(ckpt)
    assert meta.get("pod_epochs") == 2, meta
    assert meta.get("planned_departures") == [1], meta
    assert meta.get("dead_processes") == [], meta


def test_join_mid_ring_bit_identical(tmp_path):
    """Mid-run JOIN into the step-wise dense ring: the pod (2 processes,
    4-device mesh) is gated on the join note; admission lands during the
    monitored step waits and — the ring-phase JOIN upgrade (ISSUE 15) —
    the pod KEEPS its pipelined collective schedule (a pure-join epoch
    bump is join-tolerant, never an abandon) while the joiner consumes
    whole ring steps from the schedule TAIL under the POD's geometry
    (D from the store meta, not its own 2-device mesh). Every member's
    assembled matrix is byte-identical to a fixed-membership ppermute
    oracle."""
    from drep_tpu.parallel.allpairs import configure_ring, sharded_mash_allpairs
    from drep_tpu.parallel.mesh import make_mesh

    sys.path.insert(0, os.path.dirname(WORKER))
    import _multihost_worker as w

    configure_ring()  # oracle: store-less, in THIS process
    oracle = sharded_mash_allpairs(w._elastic_packed(), k=21, mesh=make_mesh(4))

    outdir, ckpt = str(tmp_path / "out"), str(tmp_path / "ring")
    pod = _launch_pod(
        outdir, ckpt, "ring", nproc=2,
        # pace each step wide enough that the (already-admitted, gated)
        # joiner lands tail blocks while the pod's collective ring is
        # still working the head — the upgrade keeps the pod FAST, so the
        # old 0.6s pacing would let it finish before the joiner's first
        # jit compile lands
        faults="ring_step:sleep:1.0:secs=1.2",
        extra_env={
            "DREP_TPU_TEST_MAX_JOINS": "1",
            "DREP_TPU_TEST_WAIT_JOIN": "1",
        },
    )
    joiner = _launch_joiner(outdir, ckpt, "join_ring", join_id=2)
    outs = _reap(pod + [joiner])
    for i, p in enumerate(pod):
        assert p.returncode == 0, f"pod worker {i} failed:\n{outs[i]}"
    assert joiner.returncode == 0, f"joiner failed:\n{outs[-1]}"

    for who in (0, 1, "joiner"):
        got = np.load(os.path.join(outdir, f"ring_{who}.npy"))
        assert got.tobytes() == oracle.tobytes(), (
            f"member {who}'s ring matrix differs from the oracle"
        )
    # the joiner computed blocks under the pod's geometry — and as STEP
    # participation (tail consumption), not only standalone recovery
    jc = _ctr(outdir, "joiner")
    assert jc.get("pod_join_accepted") == 1, jc
    assert jc.get("ring_blocks_recovered", 0) >= 1, jc
    assert jc.get("ring_join_tail_blocks", 0) >= 1, jc
    for i in range(2):
        assert _ctr(outdir, i).get("pod_joins", 0) >= 1, _ctr(outdir, i)
    blocks = sorted(f for f in os.listdir(ckpt) if f.startswith("blk_"))
    assert len(blocks) == 4 * 5 // 2, blocks  # D*(D+1)/2 half-ring blocks
    assert any(".e" in f for f in blocks), blocks  # post-bump stamps
    meta = _meta(ckpt)
    assert meta.get("pod_joins", 0) >= 1, meta


def test_drain_then_join_churn_bit_identical(tmp_path, healthy_edges):
    """Membership churn both ways in ONE stage: process 1 drains at its
    second stripe, and a joiner — holding its request until the departure
    note exists (ordering pinned) — is admitted afterwards. Survivors +
    joiner finish byte-identical to the fixed-membership oracle with
    both churn classes counted and stamped."""
    outdir, ckpt = str(tmp_path / "out"), str(tmp_path / "ckpt")
    pod = _launch_pod(
        outdir, ckpt, "elastic", nproc=3,
        faults=(
            "process_death:drain:1.0:proc=1:skip=1,"
            "process_death:sleep:1.0:secs=1.0"
        ),
        extra_env={
            "DREP_TPU_TEST_MAX_JOINS": "1",
            "DREP_TPU_TEST_MAX_DEAD": "0",
        },
    )
    joiner = _launch_joiner(
        outdir, ckpt, "join_streaming", join_id=3, after_drain=True
    )
    outs = _reap(pod + [joiner])
    for i, p in enumerate(pod):
        assert p.returncode == 0, f"pod worker {i} failed:\n{outs[i]}"
    assert joiner.returncode == 0, f"joiner failed:\n{outs[-1]}"
    assert os.path.exists(os.path.join(outdir, "drained_1")), outs[1]
    assert os.path.exists(os.path.join(outdir, "ok_joiner")), outs[-1]

    h = healthy_edges
    for who in (0, 2, "joiner"):
        e = _edges(outdir, who)
        assert all(
            a.tobytes() == b.tobytes() for a, b in zip(e[:3], h[:3])
        ), f"member {who}'s edges differ from the fixed-membership oracle"
    for pid in (0, 2):
        ctr = _ctr(outdir, pid)
        assert ctr.get("planned_departures") == 1, ctr
        assert ctr.get("pod_joins", 0) >= 1, ctr
        assert "dead_processes" not in ctr, ctr
        # churn ordering is visible in the membership generation: the
        # drain bump plus the join bump
        assert ctr.get("pod_epoch_bumps", 0) >= 2, ctr
        assert ctr.get("gauges", {}).get("pod_epoch", 0) >= 2, ctr
    meta = _meta(ckpt)
    assert meta.get("planned_departures") == [1], meta
    assert meta.get("pod_joins", 0) >= 1, meta
    assert meta.get("dead_processes") == [], meta
