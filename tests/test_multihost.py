"""Real `jax.distributed` CPU processes must agree with single-process.

The reference has no multi-node story at all (SURVEY.md §2c); this is the
rebuild's v5e-pod contract (SURVEY.md §5.8) tested the only way it can be
without a pod: 2 and 4 OS processes, two forced-host CPU devices each, a
real coordinator handshake, and the assertions that (a) the mesh-sharded
ring all-pairs and the striped streaming path reproduce the dense
single-process numbers exactly, and (b) the streaming+greedy north-star
combo over one SHARED workdir — every process owning >= 2 interleaved
row-block stripes — yields the same Cdb partition as a single-process run,
and resumes from the shared shards without rewriting them.
"""

import json
import os
import signal
import socket
import subprocess
import sys

import numpy as np
import pandas as pd
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
WORKER = os.path.join(os.path.dirname(os.path.abspath(__file__)), "_multihost_worker.py")


def _free_port() -> int:
    s = socket.socket()
    s.bind(("localhost", 0))
    port = s.getsockname()[1]
    s.close()
    return port


@pytest.fixture(scope="session")
def single_cdb(tmp_path_factory):
    """The single-process streaming+greedy oracle Cdb — computed once for
    every nproc parametrization (the planted data is identical)."""
    sys.path.insert(0, os.path.dirname(WORKER))
    import _multihost_worker as w

    return w.run_combo_wrapper(str(tmp_path_factory.mktemp("single_wd")))


@pytest.mark.parametrize("nproc", [2, pytest.param(4, marks=pytest.mark.slow)])
def test_distributed_matches_single(tmp_path, nproc, single_cdb):
    port = _free_port()
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=2"
    env["PYTHONPATH"] = REPO + os.pathsep + env.get("PYTHONPATH", "")
    procs = [
        subprocess.Popen(
            [sys.executable, WORKER, str(i), str(nproc), f"localhost:{port}", str(tmp_path)],
            env=env,
            stdout=subprocess.PIPE,
            stderr=subprocess.STDOUT,
            cwd=REPO,
        )
        for i in range(nproc)
    ]
    outs = []
    try:
        for p in procs:
            out, _ = p.communicate(timeout=600)
            outs.append(out.decode(errors="replace"))
    finally:
        # a dead worker leaves its peer blocked in a collective — always
        # reap all so a failure can't leak orphans holding the port
        for p in procs:
            if p.poll() is None:
                p.kill()
                p.communicate()
    for i, p in enumerate(procs):
        assert p.returncode == 0, f"worker {i} failed:\n{outs[i]}"
        assert (tmp_path / f"ok_{i}").exists(), f"worker {i} wrote no ok-file:\n{outs[i]}"

    # sharded ingest: every process must have assembled the IDENTICAL
    # sketch set from the pod's interleaved stripes
    digests = {(tmp_path / f"ingest_digest_{i}").read_text() for i in range(nproc)}
    assert len(digests) == 1, f"ingest assembly diverged across processes: {digests}"

    # the shared-workdir Cdb the pod produced must match a single-process
    # run of the same planted data, as a cluster partition (labels may
    # permute; membership may not)
    import _multihost_worker as w

    pod_cdb = pd.read_csv(tmp_path / "combo_wd" / "data_tables" / "Cdb.csv")
    assert w.partition(pod_cdb, "secondary_cluster") == w.partition(
        single_cdb, "secondary_cluster"
    )
    assert w.partition(pod_cdb, "primary_cluster") == w.partition(
        single_cdb, "primary_cluster"
    )


def _run_elastic_pod(
    outdir, ckpt=None, faults=None, expect_dead=None, nproc=3, mode="elastic",
    expect_exit0=(), extra_env=None,
):
    """Launch an nproc-process jax.distributed CPU pod running an elastic
    worker mode against a shared checkpoint dir. Returns the per-worker
    outputs; asserts exit codes (the `expect_dead` member must die by
    SIGKILL, `expect_exit0` members exit 0 without artifacts — the
    pre-barrier early-exit cases — everyone else must succeed and leave
    artifacts)."""
    port = _free_port()
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=2"
    env["PYTHONPATH"] = REPO + os.pathsep + env.get("PYTHONPATH", "")
    # fast cadence so death detection (5x cadence staleness) is ~1.25 s,
    # and a bounded collective timeout so a protocol bug fails the test
    # quickly instead of wedging it for the default 15 minutes
    env["DREP_TPU_HEARTBEAT_S"] = "0.25"
    env["DREP_TPU_COLLECTIVE_TIMEOUT_S"] = "90"
    if faults:
        env["DREP_TPU_FAULTS"] = faults
    if extra_env:
        env.update(extra_env)
    os.makedirs(outdir, exist_ok=True)
    args = [str(outdir), mode] + ([str(ckpt)] if ckpt is not None else [])
    procs = [
        subprocess.Popen(
            [sys.executable, WORKER, str(i), str(nproc), f"localhost:{port}", *args],
            env=env,
            stdout=subprocess.PIPE,
            stderr=subprocess.STDOUT,
            cwd=REPO,
        )
        for i in range(nproc)
    ]
    outs = []
    try:
        for p in procs:
            out, _ = p.communicate(timeout=420)
            outs.append(out.decode(errors="replace"))
    finally:
        for p in procs:
            if p.poll() is None:
                p.kill()
                p.communicate()
    for i, p in enumerate(procs):
        if expect_dead is not None and i == expect_dead:
            assert p.returncode == -signal.SIGKILL, (
                f"worker {i} should have been SIGKILLed:\n{outs[i]}"
            )
            assert not os.path.exists(os.path.join(outdir, f"ok_{i}"))
            continue
        assert p.returncode == 0, f"worker {i} failed:\n{outs[i]}"
        if i in expect_exit0:
            continue  # early-exit member: clean exit, no artifacts expected
        assert os.path.exists(os.path.join(outdir, f"ok_{i}")), (
            f"worker {i} wrote no ok-file:\n{outs[i]}"
        )
    return outs


def _elastic_edges(outdir, pid):
    with np.load(os.path.join(outdir, f"edges_{pid}.npz")) as z:
        return z["ii"].copy(), z["jj"].copy(), z["dd"].copy(), int(z["pairs"])


def _elastic_counters(outdir, pid) -> dict:
    with open(os.path.join(outdir, f"counters_{pid}.json")) as f:
        return json.load(f)


@pytest.mark.chaos
def test_elastic_pod_survives_sigkilled_member(tmp_path):
    """The elastic-pod tentpole, end to end on a 3-process CPU pod:

    1. healthy pod — the oracle run (every process returns the full edge
       set, all shards epoch-0-named, no deaths diagnosed);
    2. killed pod — process 1 SIGKILLs itself (process_death:kill fault)
       at its SECOND owned stripe, mid-streaming: the survivors must
       detect the death by heartbeat staleness, bump the ownership epoch,
       re-deal the two unfinished stripes, reuse the dead member's
       FINISHED shard, complete — with edges bit-identical to the healthy
       pod — and stamp the degradation into the store's meta; a follow-up
       checkpoint-store open must coordinate over the survivor set;
    3. resume pod — a fresh healthy 3-process pod over the degraded run's
       checkpoint dir: resumes every shard (including the epoch-stamped
       ones) computing nothing, reproduces the edges bit-for-bit, and —
       the stale-note lifecycle — never diagnoses the PREVIOUS run's dead
       process from its leftover heartbeat/sentinel files."""
    healthy_dir, killed_dir, resume_dir = (
        str(tmp_path / d) for d in ("healthy", "killed", "resume")
    )
    ckpt_a, ckpt_b = str(tmp_path / "ckpt_a"), str(tmp_path / "ckpt_b")

    _run_elastic_pod(healthy_dir, ckpt_a)
    h = _elastic_edges(healthy_dir, 0)
    for pid in (1, 2):  # every process assembled the identical full set
        e = _elastic_edges(healthy_dir, pid)
        assert all(a.tobytes() == b.tobytes() for a, b in zip(e[:3], h[:3]))
        assert e[3] == h[3]
    from _multihost_worker import ELASTIC_N

    assert h[3] == ELASTIC_N * (ELASTIC_N - 1) // 2
    assert not any(
        ".e" in f for f in os.listdir(ckpt_a) if f.startswith("row_")
    ), "healthy run produced epoch-stamped shards"
    for pid in range(3):
        assert "dead_processes" not in _elastic_counters(healthy_dir, pid)

    # 2) SIGKILL process 1 mid-streaming (after its first owned stripe)
    _run_elastic_pod(
        killed_dir, ckpt_b,
        faults="process_death:kill:1.0:proc=1:skip=1", expect_dead=1,
    )
    for pid in (0, 2):
        e = _elastic_edges(killed_dir, pid)
        assert all(
            a.tobytes() == b.tobytes() for a, b in zip(e[:3], h[:3])
        ), f"survivor {pid}'s edges differ from the healthy pod"
        # the dead member's dispatched-but-unreported pairs die with it;
        # its FINISHED shard is reused, so survivors computed strictly
        # fewer pairs than the full grid (and more than none)
        assert 0 < e[3] < h[3], (e[3], h[3])
        ctr = _elastic_counters(killed_dir, pid)
        assert ctr.get("dead_processes") == 1, ctr
        assert ctr.get("pod_epoch_bumps") == 1, ctr
    shards_b = sorted(f for f in os.listdir(ckpt_b) if f.startswith("row_"))
    assert any(".e01." in f for f in shards_b), shards_b  # re-dealt stripes
    with open(os.path.join(ckpt_b, "meta.json")) as f:
        meta_b = json.load(f)
    assert meta_b.get("pod_epochs") == 2, meta_b
    assert meta_b.get("dead_processes") == [1], meta_b

    # 3) fresh healthy pod resumes the degraded run's store
    _run_elastic_pod(resume_dir, ckpt_b)
    for pid in range(3):
        e = _elastic_edges(resume_dir, pid)
        assert all(a.tobytes() == b.tobytes() for a, b in zip(e[:3], h[:3]))
        assert e[3] == 0, "resume recomputed stripes despite complete shards"
        # the previous run's stale heartbeat/sentinel notes (including the
        # dead process 1's) must never be diagnosed as a CURRENT death
        assert "dead_processes" not in _elastic_counters(resume_dir, pid)


@pytest.mark.chaos
def test_elastic_pod_heals_corrupt_shard_after_epoch_bump(tmp_path):
    """Storage + elastic failure COMPOSED (ISSUE 5 acceptance): process 1
    SIGKILLs itself mid-streaming (the epoch-bump case), and survivor 0's
    first re-dealt, epoch-1-stamped shard (``row_00004.e01.npz`` — the
    dead member's unfinished stripe, deterministically re-dealt to p0)
    is bit-rotted AFTER its atomic publish (``io:corrupt`` targeted via
    ``path=.e01``). Survivor 2's canonical assembly reads that shard,
    must detect the rot via the in-band checksum, recompute the stripe
    into its own path, and finish with edges BIT-IDENTICAL to a healthy
    pod — corrupt_shards_healed reported honestly by the healer, the
    injection by the corruptor."""
    healthy_dir, rot_dir = str(tmp_path / "healthy"), str(tmp_path / "rot")
    ckpt_a, ckpt_b = str(tmp_path / "ckpt_a"), str(tmp_path / "ckpt_b")

    _run_elastic_pod(healthy_dir, ckpt_a)
    h = _elastic_edges(healthy_dir, 0)

    _run_elastic_pod(
        rot_dir, ckpt_b,
        faults=(
            "process_death:kill:1.0:proc=1:skip=1,"
            "io:corrupt:1.0:proc=0:path=.e01"
        ),
        expect_dead=1,
    )
    for pid in (0, 2):
        e = _elastic_edges(rot_dir, pid)
        assert all(
            a.tobytes() == b.tobytes() for a, b in zip(e[:3], h[:3])
        ), f"survivor {pid}'s edges differ from the healthy pod"
    ctr0 = _elastic_counters(rot_dir, 0)
    ctr2 = _elastic_counters(rot_dir, 2)
    assert ctr0.get("injected_io_corrupt", 0) >= 1, ctr0
    # p0 holds its own stripes in memory — the HEAL happens on the peer
    # whose assembly read the rotted shard from the store
    assert ctr2.get("corrupt_shards_healed", 0) >= 1, ctr2
    assert any(c.get("dead_processes") == 1 for c in (ctr0, ctr2))
    shards = sorted(f for f in os.listdir(ckpt_b) if f.startswith("row_"))
    assert any(".e01." in f for f in shards), shards
    # the store is healed in place: a scrub of the finished store is clean
    import importlib.util

    spec = importlib.util.spec_from_file_location(
        "scrub_store", os.path.join(REPO, "tools", "scrub_store.py")
    )
    ss = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(ss)
    rep = ss.scrub([ckpt_b])
    assert not rep["damaged"], rep["damaged"]
    with open(os.path.join(ckpt_b, "meta.json")) as f:
        meta_b = json.load(f)
    assert meta_b.get("pod_epochs") == 2, meta_b


def _ring_matrix(outdir, pid):
    return np.load(os.path.join(outdir, f"ring_{pid}.npy"))


@pytest.mark.chaos
def test_elastic_ring_survives_sigkilled_member(tmp_path):
    """The step-wise dense-ring tentpole, end to end on a 3-process CPU
    pod (6-device mesh):

    1. healthy pod — the oracle ring (every process assembles the full
       distance matrix from the shared block store, all blocks epoch-0,
       no deaths);
    2. killed pod — process 1 SIGKILLs itself at a ring-step boundary
       (``ring_step:kill`` with skip=1: its FIRST step's blocks are
       already durable in the store): the survivors must detect the death
       by heartbeat staleness between steps, bump the ownership epoch,
       recompute the missing blocks per-tile across themselves (reusing
       the dead member's durable step-0 blocks), and assemble a matrix
       BIT-IDENTICAL to the healthy pod — with the degradation stamped
       into the store's meta and honest counters."""
    healthy_dir, killed_dir = str(tmp_path / "healthy"), str(tmp_path / "killed")
    ckpt_a, ckpt_b = str(tmp_path / "ring_a"), str(tmp_path / "ring_b")

    _run_elastic_pod(healthy_dir, ckpt_a, mode="ring")
    h = _ring_matrix(healthy_dir, 0)
    for pid in (1, 2):
        assert _ring_matrix(healthy_dir, pid).tobytes() == h.tobytes()
    blocks_a = sorted(f for f in os.listdir(ckpt_a) if f.startswith("blk_"))
    assert len(blocks_a) == 6 * 7 // 2, blocks_a  # D*(D+1)/2 half-ring blocks
    assert not any(".e" in f for f in blocks_a), blocks_a
    for pid in range(3):
        ctr = _elastic_counters(healthy_dir, pid)
        assert "dead_processes" not in ctr, ctr

    _run_elastic_pod(
        killed_dir, ckpt_b,
        faults="ring_step:kill:1.0:proc=1:skip=1", expect_dead=1, mode="ring",
    )
    for pid in (0, 2):
        got = _ring_matrix(killed_dir, pid)
        assert got.tobytes() == h.tobytes(), (
            f"survivor {pid}'s ring matrix differs from the healthy pod"
        )
    # pod-level verdicts, not per-survivor: a survivor can legitimately
    # finish WITHOUT ever diagnosing the death (its peer detected first
    # and covered the missing blocks before its next liveness check) —
    # the protocol converges either way. At least one survivor must have
    # diagnosed it, and the dead member's unfinished blocks must have
    # been recomputed per-tile by someone.
    ctrs = [_elastic_counters(killed_dir, pid) for pid in (0, 2)]
    assert any(c.get("dead_processes") == 1 for c in ctrs), ctrs
    assert any(c.get("pod_epoch_bumps") == 1 for c in ctrs), ctrs
    recovered = sum(c.get("ring_blocks_recovered", 0) for c in ctrs)
    assert recovered >= 1, "no blocks recovered despite a mid-ring death"
    blocks_b = sorted(f for f in os.listdir(ckpt_b) if f.startswith("blk_"))
    assert any(".e01." in f for f in blocks_b), blocks_b
    with open(os.path.join(ckpt_b, "meta.json")) as f:
        meta_b = json.load(f)
    assert meta_b.get("pod_epochs") == 2, meta_b
    assert meta_b.get("dead_processes") == [1], meta_b


@pytest.mark.chaos
def test_streaming_prebarrier_death_continues_degraded(tmp_path):
    """Death BEFORE the stage-open barrier (the ROADMAP hard case): a pod
    member that exits before ever heartbeating or reaching
    open_checkpoint_dir's barrier is diagnosed from its missing heartbeat
    note during the barrier wait; the survivors continue degraded and
    compute the FULL edge set between them — bit-identical to a healthy
    pod's — instead of aborting at the collective timeout."""
    healthy_dir, pre_dir = str(tmp_path / "healthy"), str(tmp_path / "pre")
    ckpt_a, ckpt_b = str(tmp_path / "ckpt_a"), str(tmp_path / "ckpt_b")

    _run_elastic_pod(healthy_dir, ckpt_a)
    h = _elastic_edges(healthy_dir, 0)

    _run_elastic_pod(
        pre_dir, ckpt_b, mode="elastic_prebarrier", expect_exit0=(1,),
    )
    for pid in (0, 2):
        e = _elastic_edges(pre_dir, pid)
        assert all(a.tobytes() == b.tobytes() for a, b in zip(e[:3], h[:3])), (
            f"survivor {pid}'s edges differ from the healthy pod"
        )
        # the dead member never computed anything: the survivors between
        # them did ALL the pair work
        ctr = _elastic_counters(pre_dir, pid)
        assert ctr.get("dead_processes") == 1, ctr
        assert ctr.get("pod_epoch_bumps") == 1, ctr
    pairs_total = _elastic_edges(pre_dir, 0)[3]
    assert pairs_total == h[3], (pairs_total, h[3])


@pytest.mark.chaos
def test_secondary_batch_retries_locally_on_pod(tmp_path):
    """The retryable sharded secondary: on a pod the secondary mesh is
    live-clamped to each process's local devices (asserted in the
    worker), so an injected mid-batch failure on ONE process retries
    locally and completes — instead of desyncing the pod — with
    bit-identical ANI matrices everywhere and honest retry counters on
    the injected member only."""
    outdir = str(tmp_path / "sec")
    _run_elastic_pod(
        outdir, mode="secondary_retry",
        faults="secondary_batch:raise:1.0:max=1:proc=1",
    )
    mats = {}
    for pid in range(3):
        with np.load(os.path.join(outdir, f"secondary_{pid}.npz")) as z:
            mats[pid] = (z["ani"].copy(), z["cov"].copy())
    for pid in (1, 2):
        assert mats[pid][0].tobytes() == mats[0][0].tobytes()
        assert mats[pid][1].tobytes() == mats[0][1].tobytes()
    ctr1 = _elastic_counters(outdir, 1)
    assert ctr1.get("retries", 0) >= 1, ctr1
    assert ctr1.get("injected_secondary_batch_raise") == 1, ctr1
    for pid in (0, 2):
        ctr = _elastic_counters(outdir, pid)
        assert "injected_secondary_batch_raise" not in ctr, ctr
        assert "retries" not in ctr, ctr


@pytest.mark.chaos
def test_dead_peer_barrier_raises_actionable_timeout(tmp_path):
    """A peer that dies BEFORE open_checkpoint_dir's barrier must produce
    an actionable CollectiveTimeout on the survivor — naming the missing
    process — within the configured timeout, not an infinite hang (ISSUE 2
    multi-host hardening). Process 1 exits right after distributed init;
    process 0 opens the checkpoint dir and asserts on the error text."""
    nproc = 2
    port = _free_port()
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=2"
    env["PYTHONPATH"] = REPO + os.pathsep + env.get("PYTHONPATH", "")
    env["DREP_TPU_COLLECTIVE_TIMEOUT_S"] = "15"
    procs = [
        subprocess.Popen(
            [
                sys.executable, WORKER, str(i), str(nproc),
                f"localhost:{port}", str(tmp_path), "barrier_timeout",
            ],
            env=env,
            stdout=subprocess.PIPE,
            stderr=subprocess.STDOUT,
            cwd=REPO,
        )
        for i in range(nproc)
    ]
    outs = []
    try:
        for p in procs:
            # generous: worker startup (jax import + distributed init)
            # dominates; the barrier itself must fail within ~15 s
            out, _ = p.communicate(timeout=240)
            outs.append(out.decode(errors="replace"))
    finally:
        for p in procs:
            if p.poll() is None:
                p.kill()
                p.communicate()
    for i, p in enumerate(procs):
        assert p.returncode == 0, f"worker {i} failed:\n{outs[i]}"
    ok = tmp_path / "ok_0"
    assert ok.exists(), f"survivor produced no verdict:\n{outs[0]}"
    msg = ok.read_text()
    assert "[1]" in msg and "checkpoint barrier" in msg, msg
