"""Chaos suite: live-failure behavior of the fault-tolerance layer.

Three failure families, all manufactured on CPU (ISSUE 2):

- external kills — SIGKILL a streaming subprocess mid-run; the resumed
  run must be BIT-identical to an uninterrupted one (the crash story).
- injected device failures — per-tile raises/hangs via DREP_TPU_FAULTS;
  runs must complete with honest retry/watchdog/quarantine counters and
  unchanged results (the live story).
- torn durable state — a shard published half-written; resume must
  detect, recompute, and heal it.

Everything here is seconds-scale and tier-1 (marker `chaos`); the
multi-host dead-peer case lives in test_multihost.py (same marker).
"""

import json
import logging
import os
import signal
import subprocess
import sys
import time
from contextlib import contextmanager

import numpy as np
import pytest

import _chaos_worker as cw
from drep_tpu.ops.minhash import PAD_ID, PackedSketches
from drep_tpu.parallel import faulttol
from drep_tpu.parallel.faulttol import FaultTolConfig, FaultTolError
from drep_tpu.parallel.streaming import (
    streaming_mash_edges,
    stripe_owner,
    stripe_owner_live,
)
from drep_tpu.utils import faults
from drep_tpu.utils.logger import get_logger
from drep_tpu.utils.profiling import counters

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
WORKER = os.path.join(os.path.dirname(os.path.abspath(__file__)), "_chaos_worker.py")

pytestmark = pytest.mark.chaos


@pytest.fixture(autouse=True)
def _clean_faults():
    """Every test starts and ends with injection disabled, counters clean,
    the elastic pod state healthy, and the ring + durable-I/O configs
    reset — a leaked spec, a unit-test 'degraded pod', or an earlier
    controller test's workdir-scoped ring store base would poison the
    rest of the suite."""
    from drep_tpu.parallel.allpairs import configure_ring
    from drep_tpu.utils.durableio import configure as configure_io

    faults.configure(None)
    counters.reset()
    faulttol.reset_pod()
    faulttol._HB_SEQ.clear()
    configure_ring()
    configure_io()
    yield
    faults.configure(None)
    counters.reset()
    faulttol.reset_pod()
    faulttol._HB_SEQ.clear()
    configure_ring()
    configure_io()


@contextmanager
def _capture_log(level=logging.WARNING):
    """Capture drep_tpu log records regardless of propagate (setup_logger
    disables propagation, so caplog can miss records depending on test
    order within the session)."""
    records: list[logging.LogRecord] = []

    class H(logging.Handler):
        def emit(self, record):
            records.append(record)

    h = H(level=level)
    logger = get_logger()
    old_level = logger.level
    logger.setLevel(min(level, old_level) if old_level else level)
    logger.addHandler(h)
    try:
        yield records
    finally:
        logger.removeHandler(h)
        logger.setLevel(old_level)


def _packed(n=120, s=64, seed=0):
    rng = np.random.default_rng(seed)
    ids = np.full((n, s), PAD_ID, dtype=np.int32)
    cts = np.zeros(n, dtype=np.int32)
    pools = [
        np.sort(rng.choice(2**20, size=s * 2, replace=False).astype(np.int32))
        for _ in range(5)
    ]
    for i in range(n):
        ids[i] = np.sort(rng.choice(pools[i % 5], size=s, replace=False))
        cts[i] = s
    return PackedSketches(ids=ids, counts=cts, names=[f"g{i}" for i in range(n)])


def _assert_edges_equal(got, want):
    """Bit-for-bit: indices AND float payload (the fault layer must not
    shift results by a single ulp when every tile ultimately computes)."""
    for g, w in zip(got[:3], want[:3]):
        assert np.asarray(g).tobytes() == np.asarray(w).tobytes()


# --- external kill: SIGKILL mid-run, resume bit-identical ----------------


def test_sigkill_mid_streaming_run_resumes_bit_identical(tmp_path):
    n_blocks = -(-cw.N // cw.BLOCK)
    ckpt = str(tmp_path / "ckpt")

    # uninterrupted oracle (separate checkpoint dir, same planted data)
    oracle = cw.run(str(tmp_path / "oracle_ckpt"))

    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env["PYTHONPATH"] = REPO + os.pathsep + env.get("PYTHONPATH", "")
    # pace every tile so the parent can reliably kill between shard
    # writes; determinism of the RESULT is untouched (sleep-only rule)
    env["DREP_TPU_FAULTS"] = "streaming_tile:sleep:1.0:secs=0.25"
    out_npz = str(tmp_path / "killed.npz")
    proc = subprocess.Popen(
        [sys.executable, WORKER, ckpt, out_npz],
        env=env, cwd=REPO,
        stdout=subprocess.PIPE, stderr=subprocess.STDOUT,
    )
    try:
        deadline = time.time() + 180
        while time.time() < deadline:
            shards = [f for f in os.listdir(ckpt)] if os.path.isdir(ckpt) else []
            if sum(f.startswith("row_") and f.endswith(".npz") for f in shards) >= 2:
                break
            if proc.poll() is not None:
                out = proc.communicate()[0].decode(errors="replace")
                pytest.fail(f"worker finished before the kill (pacing broken?):\n{out}")
            time.sleep(0.02)
        else:
            proc.kill()
            out = proc.communicate()[0].decode(errors="replace")
            pytest.fail(f"no shards appeared within the deadline:\n{out}")
        proc.send_signal(signal.SIGKILL)
        proc.communicate(timeout=60)
    finally:
        if proc.poll() is None:
            proc.kill()
            proc.communicate()

    assert proc.returncode == -signal.SIGKILL
    assert not os.path.exists(out_npz), "worker published results despite the kill"
    done = sorted(
        f for f in os.listdir(ckpt) if f.startswith("row_") and f.endswith(".npz")
    )
    assert 1 <= len(done) < n_blocks, f"kill was not mid-run: {done}"

    # resume in-process with injection off: must complete the missing
    # stripes and agree with the oracle bit-for-bit, computing only the
    # unfinished work
    ii, jj, dd, pairs, labels = cw.run(ckpt)
    _assert_edges_equal((ii, jj, dd), oracle[:3])
    assert np.array_equal(labels, oracle[4])
    assert 0 < pairs < oracle[3], (pairs, oracle[3])


def test_sigkill_mid_pruned_streaming_resumes_bit_identical(tmp_path):
    """The pruned schedule's crash story (ISSUE 7, chaos_matrix --prune
    cell): SIGKILL a --primary_prune lsh run mid-flight; the pruned
    resume completes the missing stripes and the result is bit-identical
    to an uninterrupted DENSE run on the same data — kill/resume and
    pruning compose, with recall 1.0 intact across the crash."""
    ckpt = str(tmp_path / "ckpt")

    # the oracle is the DENSE schedule on the same contiguous-group data:
    # equality proves the pruned resume dropped nothing
    oracle = cw.run(str(tmp_path / "oracle_ckpt"), prune=False, contiguous=True)

    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env["PYTHONPATH"] = REPO + os.pathsep + env.get("PYTHONPATH", "")
    env["DREP_TPU_FAULTS"] = "streaming_tile:sleep:1.0:secs=0.25"
    out_npz = str(tmp_path / "killed.npz")
    proc = subprocess.Popen(
        [sys.executable, WORKER, ckpt, out_npz, "prune"],
        env=env, cwd=REPO,
        stdout=subprocess.PIPE, stderr=subprocess.STDOUT,
    )
    try:
        deadline = time.time() + 180
        while time.time() < deadline:
            shards = [f for f in os.listdir(ckpt)] if os.path.isdir(ckpt) else []
            if sum(f.startswith("row_") and f.endswith(".npz") for f in shards) >= 2:
                break
            if proc.poll() is not None:
                out = proc.communicate()[0].decode(errors="replace")
                pytest.fail(f"worker finished before the kill (pacing broken?):\n{out}")
            time.sleep(0.02)
        else:
            proc.kill()
            out = proc.communicate()[0].decode(errors="replace")
            pytest.fail(f"no shards appeared within the deadline:\n{out}")
        proc.send_signal(signal.SIGKILL)
        proc.communicate(timeout=60)
    finally:
        if proc.poll() is None:
            proc.kill()
            proc.communicate()

    assert proc.returncode == -signal.SIGKILL
    assert not os.path.exists(out_npz), "worker published results despite the kill"

    counters.reset()
    ii, jj, dd, pairs, labels = cw.run(ckpt, prune=True)
    _assert_edges_equal((ii, jj, dd), oracle[:3])
    assert np.array_equal(labels, oracle[4])
    assert pairs < oracle[3], (pairs, oracle[3])  # resumed stripes: 0 pairs
    # the pruned resume kept skipping: the schedule stayed sparse
    assert counters.gauges.get("skip_fraction", 0.0) > 0.0


# --- injected per-tile failures: retries, quarantine, watchdog ----------


def test_injected_tile_failures_retry_to_completion():
    packed = _packed()
    want = streaming_mash_edges(packed, k=21, cutoff=0.2, block=8)
    counters.reset()
    # the acceptance shape: 5% per-tile failure, deterministic stream.
    # 120 genomes / block 8 -> 15 stripes, 120 upper-triangle tiles, so
    # seed 7 fires several times (asserted via the honest counters)
    faults.configure("streaming_tile:raise:0.05:seed=7")
    got = streaming_mash_edges(packed, k=21, cutoff=0.2, block=8)
    _assert_edges_equal(got, want)
    assert got[3] == want[3]
    assert counters.faults.get("retries", 0) > 0
    assert counters.faults.get("injected_streaming_tile_raise", 0) > 0
    rep = counters.report()
    assert rep["fault_tolerance"]["retries"] > 0  # surfaces in the report


def test_single_bad_device_is_quarantined_and_run_completes():
    import jax

    if len(jax.local_devices()) < 2:
        pytest.skip("quarantine needs >= 2 devices (conftest forces 8)")
    packed = _packed()
    want = streaming_mash_edges(packed, k=21, cutoff=0.2, block=8)
    counters.reset()
    # one fake device fails EVERY dispatch; the run must finish on the
    # remaining devices with the quarantine recorded in counters + log
    faults.configure("streaming_tile:raise:1.0:device=1")
    with _capture_log() as records:
        got = streaming_mash_edges(packed, k=21, cutoff=0.2, block=8)
    _assert_edges_equal(got, want)
    assert counters.faults.get("quarantined_devices", 0) >= 1
    assert counters.faults.get("retries", 0) > 0
    # the benched device's resident pack copy must be freed the moment it
    # is quarantined (ROADMAP follow-up): ids + counts buffers dropped
    assert counters.faults.get("pack_buffers_freed", 0) >= 2
    assert any("quarantining device slot 1" in r.getMessage() for r in records)
    assert any("finished with device slot(s) [1] quarantined" in r.getMessage() for r in records)


def test_watchdog_trips_on_injected_hang():
    packed = _packed(n=60)
    want = streaming_mash_edges(packed, k=21, cutoff=0.2, block=8)
    counters.reset()
    faults.configure("streaming_tile:hang:1.0:device=2:secs=30")
    got = streaming_mash_edges(
        packed, k=21, cutoff=0.2, block=8,
        ft_config=FaultTolConfig(dispatch_timeout_s=0.5),
    )
    _assert_edges_equal(got, want)
    assert counters.faults.get("watchdog_trips", 0) > 0


def test_cpu_fallback_when_every_retry_fails():
    """All devices failing every dispatch: retries exhaust, quarantine
    can't help (it always keeps one device), and each tile must be
    recomputed by the host CPU fallback — completing the run with
    identical edges and honest cpu_fallback_tiles accounting."""
    packed = _packed(n=32)
    want = streaming_mash_edges(packed, k=21, cutoff=0.2, block=8)
    counters.reset()
    faults.configure("streaming_tile:raise:1.0")
    got = streaming_mash_edges(
        packed, k=21, cutoff=0.2, block=8,
        ft_config=FaultTolConfig(max_retries=1, backoff_s=0.0),
    )
    _assert_edges_equal(got, want)
    assert counters.faults.get("cpu_fallback_tiles", 0) == 4 * 5 // 2  # all tiles


# --- torn durable state: detect, recompute, heal ------------------------


def test_torn_shard_write_is_recomputed_on_resume(tmp_path):
    packed = _packed(n=48)
    ckpt = str(tmp_path / "ckpt")
    faults.configure("shard_write:torn:1.0:max=2")
    r1 = streaming_mash_edges(packed, k=21, cutoff=0.2, block=8, checkpoint_dir=ckpt)
    faults.configure(None)
    # run 1's RESULTS are unaffected (tearing happens at publish time);
    # the first two shards on disk are truncated
    assert counters.faults.get("injected_shard_write_torn") == 2

    with _capture_log() as records:
        r2 = streaming_mash_edges(packed, k=21, cutoff=0.2, block=8, checkpoint_dir=ckpt)
    _assert_edges_equal(r2, r1)
    corrupt_warnings = [r for r in records if "corrupt shard" in r.getMessage()]
    assert len(corrupt_warnings) == 2, [r.getMessage() for r in records]
    # only the two torn stripes recomputed — and their shards are healed:
    assert 0 < r2[3] < r1[3]
    r3 = streaming_mash_edges(packed, k=21, cutoff=0.2, block=8, checkpoint_dir=ckpt)
    assert r3[3] == 0  # fully resumed now
    _assert_edges_equal(r3, r1)


# --- registry semantics --------------------------------------------------


def test_fault_spec_parsing_and_env_activation(monkeypatch):
    with pytest.raises(faults.FaultSpecError):
        # drep-lint: allow[fault-site] — negative test: asserts the registry rejects unknown sites
        faults.configure("not_a_site:raise")
    with pytest.raises(faults.FaultSpecError):
        # drep-lint: allow[fault-site] — negative test: asserts the registry rejects unknown modes
        faults.configure("streaming_tile:not_a_mode")
    with pytest.raises(faults.FaultSpecError):
        faults.configure("streaming_tile:raise:0.5:bogus=1")
    # env route: reset() re-reads the env on next use
    monkeypatch.setenv(faults.ENV, "streaming_tile:raise:1.0")
    faults.reset()
    assert faults.active()
    with pytest.raises(faults.InjectedFault):
        faults.fire("streaming_tile", device=0)
    monkeypatch.delenv(faults.ENV)
    faults.reset()
    assert not faults.active()
    faults.fire("streaming_tile", device=0)  # no-op when unset


def test_fault_rule_filters():
    faults.configure("streaming_tile:raise:1.0:device=3:max=2")
    faults.fire("streaming_tile", device=1)  # other device: no-op
    for _ in range(2):
        with pytest.raises(faults.InjectedFault):
            faults.fire("streaming_tile", device=3)
    faults.fire("streaming_tile", device=3)  # max=2 exhausted: no-op
    assert counters.faults["injected_streaming_tile_raise"] == 2


def test_retrying_call_exhaustion_raises_faulttol_error():
    from drep_tpu.parallel.faulttol import retrying_call

    faults.configure("secondary_batch:raise:1.0")
    with pytest.raises(FaultTolError, match="secondary_batch"):
        retrying_call(
            lambda: 1, site="secondary_batch",
            config=FaultTolConfig(max_retries=1, backoff_s=0.0),
        )
    faults.configure("secondary_batch:raise:1.0:max=1")
    assert retrying_call(
        lambda: 42, site="secondary_batch",
        config=FaultTolConfig(max_retries=1, backoff_s=0.0),
    ) == 42  # first attempt injected, retry succeeds
    assert counters.faults.get("retries", 0) >= 1


# --- build errors are bugs, not device faults (ISSUE 21) ------------------


def _assert_nothing_hidden():
    for k in ("retries", "cpu_fallback_tiles", "quarantined_devices",
              "ring_step_failures", "ring_blocks_recovered"):
        assert counters.faults.get(k, 0) == 0, counters.faults


def test_envelope_propagates_build_errors_and_still_absorbs_device_faults():
    """TileExecutor / retrying_call: a `compute` that raises while being
    BUILT (anything that is not a device fault) propagates at once — no
    retry, no CPU fallback — while an injected run-time fault still
    retries and recovers as before."""
    import jax.numpy as jnp

    from drep_tpu.parallel.faulttol import TileExecutor, is_device_fault, retrying_call

    assert not is_device_fault(TypeError("bad trace"))
    assert not is_device_fault(NotImplementedError("Unimplemented primitive"))
    assert is_device_fault(faults.InjectedFault("x"))
    assert is_device_fault(faulttol.WatchdogTimeout("x"))

    def broken(slot):
        raise TypeError("cannot trace this")

    cfg = FaultTolConfig(max_retries=2, backoff_s=0.0)
    ft = TileExecutor([object(), object()], cfg)
    fell_back = []
    with pytest.raises(TypeError, match="cannot trace"):
        ft.finalize(ft.submit(broken), cpu_fallback=lambda: fell_back.append(1))
    with pytest.raises(TypeError, match="cannot trace"):
        retrying_call(lambda: broken(0), site="secondary_batch", config=cfg)
    assert not fell_back
    _assert_nothing_hidden()

    # a device fault on the same executor: retried on the other slot
    calls = []

    def flaky(slot):
        calls.append(slot)
        if len(calls) == 1:
            raise faults.InjectedFault("transient")
        return jnp.zeros(())

    ft.finalize(ft.submit(flaky), cpu_fallback=lambda: fell_back.append(1))
    assert len(calls) == 2 and not fell_back
    assert counters.faults["retries"] == 1


def test_tile_program_that_fails_to_trace_raises_from_streaming_and_ring(monkeypatch):
    """The acceptance pin: a tile program that raises while tracing makes
    `streaming_mash_edges` AND the dense ring raise; no retries, no
    cpu_fallback_tiles, no ring_blocks_recovered is booked. (Before, both
    finished "successfully" on the CPU-recompute / per-block recovery
    paths with bit-equal output.)"""
    import jax

    from drep_tpu.ops import minhash
    from drep_tpu.parallel import allpairs, streaming
    from drep_tpu.parallel.mesh import make_mesh

    def bad_pair(a, b, na, nb):
        raise TypeError("tile body cannot be traced")

    # fresh jit caches: an earlier test may have compiled these signatures
    jax.clear_caches()
    allpairs._ring_step_fn.cache_clear()
    allpairs._block_tile_fn.cache_clear()
    monkeypatch.setattr(minhash, "_pair_shared", bad_pair)
    packed = _packed(n=48, s=32)
    try:
        with pytest.raises(TypeError, match="cannot be traced"):
            streaming.streaming_mash_edges(packed, 21, 0.2, block=16, use_pallas=False)
        with pytest.raises(TypeError, match="cannot be traced"):
            allpairs.sharded_mash_allpairs(packed, k=21, mesh=make_mesh(3))
        _assert_nothing_hidden()
    finally:
        monkeypatch.undo()
        jax.clear_caches()
        allpairs._ring_step_fn.cache_clear()
        allpairs._block_tile_fn.cache_clear()
    # and with the program intact, an injected RUN-TIME fault still recovers
    clean = streaming_mash_edges(packed, 21, 0.2, block=16, use_pallas=False)
    faults.configure("streaming_tile:raise:1.0:max=1")
    got = streaming_mash_edges(packed, 21, 0.2, block=16, use_pallas=False)
    _assert_edges_equal(got, clean)
    assert counters.faults["retries"] == 1


# --- stripe->process balance (ROADMAP open item) -------------------------


def test_stripe_owner_balances_tile_load():
    """Pairing stripe bi with n_blocks-1-bi must bound the per-process
    tile-load spread by one pair's weight (n_blocks+1) — the old bi%pc
    dealing had a ~2x spread at large n_blocks."""
    for n_blocks in (9, 16, 40, 97):
        for pc in (2, 3, 4, 8):
            loads = [0] * pc
            for bi in range(n_blocks):
                loads[stripe_owner(bi, n_blocks, pc)] += n_blocks - bi
            assert all(0 <= o < pc for o in map(lambda b: stripe_owner(b, n_blocks, pc), range(n_blocks)))
            assert max(loads) - min(loads) <= n_blocks + 1, (
                n_blocks, pc, loads,
            )
            # every stripe owned exactly once (partition, no gaps)
            total = sum(loads)
            assert total == n_blocks * (n_blocks + 1) // 2


def test_resume_log_reports_owned_stripes(tmp_path):
    packed = _packed(n=48)
    ckpt = str(tmp_path / "ckpt")
    streaming_mash_edges(packed, k=21, cutoff=0.2, block=8, checkpoint_dir=ckpt)
    with _capture_log(level=logging.INFO) as records:
        streaming_mash_edges(packed, k=21, cutoff=0.2, block=8, checkpoint_dir=ckpt)
    msgs = [r.getMessage() for r in records]
    assert any("resumed 6/6 owned row-block shards (process 0/1)" in m for m in msgs), msgs


# --- elastic pod: epoch-scoped ownership + note lifecycle ----------------
# (the 3-process SIGKILL end-to-end case lives in test_multihost.py)


def test_stripe_owner_live_redeal_balances_and_partitions():
    """The epoch-scoped deal must (a) reduce to the healthy stripe_owner
    on the full live list and (b) keep the mirror-pair balance bound over
    ANY survivor subset — the re-deal after a death is as balanced as the
    original deal over the remaining processes."""
    for n_blocks in (9, 16, 40):
        for pc in (1, 2, 3, 4):
            live = list(range(pc))
            for bi in range(n_blocks):
                assert stripe_owner(bi, n_blocks, pc) == stripe_owner_live(
                    bi, n_blocks, live
                )
        for live in ([0, 2], [1, 3, 5], [2], [0, 1, 3]):
            loads = {p: 0 for p in live}
            for bi in range(n_blocks):
                o = stripe_owner_live(bi, n_blocks, live)
                assert o in live  # every stripe owned by a survivor
                loads[o] += n_blocks - bi
            assert max(loads.values()) - min(loads.values()) <= n_blocks + 1, (
                n_blocks, live, loads,
            )
            assert sum(loads.values()) == n_blocks * (n_blocks + 1) // 2


def test_heartbeat_note_lifecycle(tmp_path):
    """The note protocol itself, single-process with planted peers: beats
    appear, stale peers die (epoch bump + honest counters), done-notes
    immunize however stale the beat, max_dead aborts, close removes the
    beat but leaves the done-note, and a NEW run's start() cleans this
    process's stale notes — a crashed-then-restarted pod must never
    diagnose a previous run's state."""
    from drep_tpu.parallel.faulttol import HeartbeatManager

    d = str(tmp_path)
    hb = HeartbeatManager(d, cadence=0.1, max_dead=1, pc=3, pid=0)
    hb.start()
    try:
        assert os.path.exists(hb.beat_path(0))
        for p in (1, 2):
            with open(hb.beat_path(p), "w") as f:
                f.write("1")
        assert hb.check() is False
        assert hb.live == [0, 1, 2] and hb.epoch == 0

        old = time.time() - 60
        os.utime(hb.beat_path(1), (old, old))
        # staleness must be CONFIRMED across a cadence before the verdict
        # (one transient failed stat must never fence a healthy member)
        assert hb.check() is False
        time.sleep(0.25)
        assert hb.check() is True
        assert hb.live == [0, 2] and hb.dead == [1] and hb.epoch == 1
        assert counters.faults["dead_processes"] == 1
        assert counters.faults["pod_epoch_bumps"] == 1
        assert faulttol.pod_live() == [0, 2]  # published for barrier routing

        # a peer with a CURRENT done-note is finished, never dead
        with open(hb.done_path(2), "w") as f:
            f.write('{"pairs": 5, "epoch": 1, "seq": 1}')
        os.utime(hb.beat_path(2), (old, old))
        assert hb.check() is False
        assert hb.live == [0, 2]
        assert hb.peer_finished(2) and hb.done_payload(2)["pairs"] == 5
        # a PREVIOUS call's leftover note does not count as finished...
        with open(hb.done_path(2), "w") as f:
            f.write('{"pairs": 5, "epoch": 0, "seq": 0}')
        assert not hb.peer_finished(2)
        # ...a racing-ahead peer's NEXT-call note does (it finished ours)
        with open(hb.done_path(2), "w") as f:
            f.write('{"pairs": 0, "epoch": 0, "seq": 2}')
        assert hb.peer_finished(2)
        with open(hb.done_path(2), "w") as f:
            f.write('{"pairs": 5, "epoch": 1, "seq": 1}')

        # a second death exceeds max_dead=1: abort, not silent shrink
        os.remove(hb.done_path(2))
        hb.check()  # first observation only suspects
        time.sleep(0.25)
        with pytest.raises(FaultTolError, match="max_dead_processes"):
            hb.check()

        hb.mark_done(7)
        with open(hb.done_path(0)) as f:
            assert json.load(f)["pairs"] == 7
    finally:
        hb.close()
    assert not os.path.exists(hb.beat_path(0))  # close removes the beat
    assert os.path.exists(hb.done_path(0))  # done-note stays for peers

    # a LATER call of the same run keeps the previous call's note (a peer
    # may still be consuming it — deleting it deadlocked real pods) and
    # ignores it as not-current
    faulttol.reset_pod()
    hb2 = HeartbeatManager(d, cadence=0.1, max_dead=1, pc=3, pid=0)
    hb2.start()
    try:
        assert hb2.seq == 2
        assert os.path.exists(hb2.done_path(0)), (
            "an earlier call's own done-note must survive start()"
        )
        assert not hb2.peer_finished(0)  # but it is not current
    finally:
        hb2.close()

    # a RESTARTED process (fresh sequence counter) clears its previous
    # incarnation's note at start, so a crashed-then-restarted pod never
    # trusts previous-run state
    faulttol.reset_pod()
    faulttol._HB_SEQ.clear()  # what a process restart does implicitly
    hb3 = HeartbeatManager(d, cadence=0.1, max_dead=1, pc=3, pid=0)
    hb3.start()
    try:
        assert hb3.seq == 1
        assert not os.path.exists(hb3.done_path(0)), (
            "start() must clean the previous incarnation's done-note"
        )
        assert os.path.exists(hb3.beat_path(0))
    finally:
        hb3.close()


def test_death_verdicts_converge_and_fence(tmp_path):
    """The first detector PUBLISHES its death verdict as a sentinel note;
    peers adopt it (the survivor view converges even when their own view
    of the beat mtimes disagrees — NFS attribute caching), and the
    subject itself fences on a verdict naming it instead of continuing
    as a zombie. A restarted process clears its stale verdict at start."""
    from drep_tpu.parallel.faulttol import HeartbeatManager

    d = str(tmp_path)
    a = HeartbeatManager(d, cadence=0.1, max_dead=2, pc=3, pid=0)
    a.start()
    b = HeartbeatManager(d, cadence=0.1, max_dead=2, pc=3, pid=2)
    b.start()
    try:
        for p in (1, 2):
            with open(a.beat_path(p), "w") as f:
                f.write("1")
        old = time.time() - 60
        os.utime(a.beat_path(1), (old, old))
        assert a.check() is False  # suspected, not yet confirmed
        time.sleep(0.25)
        assert a.check() is True
        assert os.path.exists(a.verdict_path(1))  # verdict published
        # B's own view of 1's beat is FRESH — it adopts A's verdict anyway
        with open(b.beat_path(1), "w") as f:
            f.write("2")
        assert b.check() is True
        assert b.live == [0, 2] and b.dead == [1]
        # the subject fences on a verdict naming itself (mid-run check)
        c = HeartbeatManager(d, cadence=0.1, max_dead=2, pc=3, pid=1)
        with pytest.raises(FaultTolError, match="fencing"):
            c.check()
        # restart path: start() clears the previous incarnation's verdict
        faulttol._HB_SEQ.clear()
        c2 = HeartbeatManager(d, cadence=0.1, max_dead=2, pc=3, pid=1)
        c2.start()
        try:
            assert not os.path.exists(c2.verdict_path(1))
            c2.check()  # no fence, no deaths
        finally:
            c2.close()
    finally:
        a.close()
        b.close()


def test_heartbeat_start_inherits_degraded_pod(tmp_path):
    """A heartbeat-managed stage starting on an ALREADY-degraded pod
    (e.g. the resume leg of a run whose first leg lost a member) must
    keep the survivor view — resetting to the full pod would route its
    barriers over the corpse."""
    from drep_tpu.parallel.faulttol import HeartbeatManager, mark_pod_degraded

    mark_pod_degraded(1, [0, 2], [1])
    faulttol._POD["t0"] = time.time() - 5
    hb = HeartbeatManager(str(tmp_path), cadence=0.1, max_dead=2, pc=3, pid=0)
    hb.start()
    try:
        assert hb.live == [0, 2] and hb.dead == [1] and hb.epoch == 1
        assert faulttol.pod_live() == [0, 2]
    finally:
        hb.close()


def test_auto_dispatch_timeout_derivation():
    """--dispatch_timeout 0 + auto: the executor derives the watchdog from
    its own finalize-wait latencies (warmup-excluded, floored); explicit
    positive values stay authoritative; nothing trips on a healthy run."""
    import jax
    import jax.numpy as jnp

    from drep_tpu.parallel.faulttol import (
        AUTO_TIMEOUT_FLOOR_S,
        AUTO_TIMEOUT_WARMUP,
        AUTO_TIMEOUT_WARMUP_CAP_S,
        TileExecutor,
    )

    ft = TileExecutor(jax.local_devices()[:1], FaultTolConfig(auto_timeout=True))
    assert ft.derived_timeout_s() is None  # still warming up — nothing
    # derived yet, but NOT unprotected: an early wedge runs under the cap
    assert ft._effective_timeout() == AUTO_TIMEOUT_WARMUP_CAP_S
    for _ in range(AUTO_TIMEOUT_WARMUP + 8):
        ft.finalize(ft.submit(lambda slot: jnp.zeros(())))
    # pipelined waits are ~0 ms -> the floor IS the derived deadline
    assert ft.derived_timeout_s() == AUTO_TIMEOUT_FLOOR_S
    assert counters.faults.get("watchdog_trips", 0) == 0

    ft2 = TileExecutor(
        jax.local_devices()[:1],
        FaultTolConfig(dispatch_timeout_s=0.5, auto_timeout=True),
    )
    assert ft2.derived_timeout_s() is None  # explicit value governs
    assert ft2._effective_timeout() == 0.5

    ft3 = TileExecutor(jax.local_devices()[:1], FaultTolConfig())  # auto off
    assert ft3._effective_timeout() == 0.0 and ft3.derived_timeout_s() is None


def test_streaming_reports_derived_watchdog_gauge():
    packed = _packed()
    streaming_mash_edges(
        packed, k=21, cutoff=0.2, block=8,
        ft_config=FaultTolConfig(auto_timeout=True),
    )
    from drep_tpu.parallel.faulttol import AUTO_TIMEOUT_FLOOR_S

    assert counters.gauges.get("derived_dispatch_timeout_s", 0) >= AUTO_TIMEOUT_FLOOR_S
    assert counters.faults.get("watchdog_trips", 0) == 0
    assert counters.report()["gauges"]["derived_dispatch_timeout_s"] >= AUTO_TIMEOUT_FLOOR_S


def test_quarantine_invokes_free_callback():
    """The executor must tell its caller WHICH slot was benched, exactly
    once, so per-slot device-resident operands can be freed."""
    import jax.numpy as jnp

    from drep_tpu.parallel.faulttol import TileExecutor

    freed: list[int] = []

    def compute(slot):
        if slot == 0:
            raise faults.InjectedFault("boom")  # a device fault, not a bug
        return jnp.zeros(())

    ft = TileExecutor(
        [object(), object()],
        FaultTolConfig(max_retries=1, backoff_s=0.0, quarantine_after=1),
        on_quarantine=freed.append,
    )
    ft.finalize(ft.submit(compute))  # slot 0 fails -> benched; retry on 1
    assert freed == [0]
    assert ft.quarantined() == [0]


def test_degraded_pod_clamps_secondary_mesh_to_local_devices():
    """On a degraded pod the secondary engines must never build a global
    mesh (a sharded dispatch over it would wait on the dead member's
    chips forever) — only this process's local devices qualify."""
    import jax

    from drep_tpu.cluster.engines import MESH_MIN_GENOMES, _mesh_or_none
    from drep_tpu.parallel.faulttol import mark_pod_degraded

    healthy = _mesh_or_none(None, MESH_MIN_GENOMES)
    assert healthy is not None  # conftest forces 8 virtual devices
    mark_pod_degraded(1, [0], [1])
    degraded = _mesh_or_none(None, MESH_MIN_GENOMES)
    assert degraded is not None
    assert set(degraded.devices.flat) == set(jax.local_devices())
    assert _mesh_or_none(None, 2) is None  # small clusters: no mesh at all


def test_checkpoint_meta_subset_match_and_stamp(tmp_path):
    """Degradation provenance stamped into a completed store's meta
    (pod_epochs / dead_processes) must never invalidate a resume of the
    very shards it describes; changed EXPECTED keys still mismatch."""
    from drep_tpu.utils.ckptmeta import (
        checkpoint_meta_matches,
        open_checkpoint_dir,
        stamp_checkpoint_meta,
    )

    d = str(tmp_path / "store")
    meta = {"n": 3, "fingerprint": "abc"}
    assert open_checkpoint_dir(d, meta, clear_suffixes=(".npz",)) is False
    assert open_checkpoint_dir(d, meta, clear_suffixes=(".npz",)) is True
    stamp_checkpoint_meta(d, {"pod_epochs": 2, "dead_processes": [1]})
    assert checkpoint_meta_matches(d, meta)
    assert open_checkpoint_dir(d, meta, clear_suffixes=(".npz",)) is True
    with open(os.path.join(d, "meta.json")) as f:
        stored = json.load(f)
    assert stored["pod_epochs"] == 2 and stored["dead_processes"] == [1]
    assert not checkpoint_meta_matches(d, {"n": 4, "fingerprint": "abc"})
    # ONLY the known provenance keys are tolerated: a store written by a
    # version that pinned an extra parameter must invalidate, not resume
    stamp_checkpoint_meta(d, {"future_pinned_param": 7})
    assert not checkpoint_meta_matches(d, meta)


def test_epoch_stamped_shards_resume(tmp_path):
    """A shard written under a bumped epoch (row_XXXXX.eNN.npz) must be
    found and resumed by a later healthy run exactly like an epoch-0
    shard — a resume that crosses the epoch bump replays deterministically."""
    packed = _packed(n=48)
    ckpt = str(tmp_path / "ckpt")
    r1 = streaming_mash_edges(packed, k=21, cutoff=0.2, block=8, checkpoint_dir=ckpt)
    # rename one shard to its epoch-1 name (what a degraded run's re-deal
    # would have produced — identical content by construction)
    os.replace(
        os.path.join(ckpt, "row_00002.npz"),
        os.path.join(ckpt, "row_00002.e01.npz"),
    )
    r2 = streaming_mash_edges(packed, k=21, cutoff=0.2, block=8, checkpoint_dir=ckpt)
    _assert_edges_equal(r2, r1)
    assert r2[3] == 0  # nothing recomputed: the .e01 shard resumed


def test_process_death_spec_fields():
    """proc= targets one pod member (no-op elsewhere); skip= defers the
    fire past the first N matching calls (kill after K stripes)."""
    faults.configure("process_death:kill:1.0:proc=7:skip=1")  # parses
    faults.fire("process_death")  # proc 7 != this process: no-op
    faults.fire("process_death")
    assert counters.faults.get("injected_process_death_kill", 0) == 0
    faults.configure("process_death:raise:1.0:skip=2")
    faults.fire("process_death")  # skipped
    faults.fire("process_death")  # skipped
    with pytest.raises(faults.InjectedFault):
        faults.fire("process_death")
    with pytest.raises(faults.FaultSpecError):
        faults.configure("process_death:kill:1.0:bogus=1")


# --- elastic dense ring: step-wise schedule, block store, recovery -------


def _ring_packed(n=21, s=64, seed=3):
    from drep_tpu.ops.minhash import pack_sketches

    rng = np.random.default_rng(seed)
    base = np.unique(rng.integers(0, 2**62, size=6 * s * n, dtype=np.uint64))
    rng.shuffle(base)
    sk = []
    for i in range(n):
        own = base[s * (i + 1) : s * (i + 2)]
        mix = int(s * rng.random() * 0.8)
        sk.append(np.sort(np.unique(np.concatenate([base[:mix], own[: s - mix]]))[:s]))
    return pack_sketches(sk, [f"g{i}" for i in range(n)], s)


def test_ring_block_store_resume_and_heal(tmp_path):
    """The step-wise ring's redoable unit: a run with a block store
    publishes one shard per schedule block; deleting (or truncating) a
    block makes the next run recompute ONLY it — via the per-block tile
    executor, bit-identically — and heal the store."""
    from drep_tpu.parallel.allpairs import sharded_mash_allpairs
    from drep_tpu.parallel.mesh import make_mesh

    packed = _ring_packed()
    mesh = make_mesh(3)
    ckpt = str(tmp_path / "ring")
    r1 = sharded_mash_allpairs(packed, k=21, mesh=mesh, checkpoint_dir=ckpt)
    blocks = sorted(f for f in os.listdir(ckpt) if f.startswith("blk_"))
    assert len(blocks) == 3 * 4 // 2, blocks  # D*(D+1)/2 half-ring blocks

    # full resume: nothing recomputed, bit-identical assembly from shards
    counters.reset()
    r2 = sharded_mash_allpairs(packed, k=21, mesh=mesh, checkpoint_dir=ckpt)
    assert r2.tobytes() == r1.tobytes()
    assert counters.faults.get("ring_blocks_recovered", 0) == 0

    # gap resume: one block deleted -> exactly one per-block recompute
    os.remove(os.path.join(ckpt, blocks[1]))
    counters.reset()
    r3 = sharded_mash_allpairs(packed, k=21, mesh=mesh, checkpoint_dir=ckpt)
    assert r3.tobytes() == r1.tobytes()
    assert counters.faults.get("ring_blocks_recovered") == 1, counters.faults

    # torn block: detected as corrupt at assembly, recomputed into its
    # own path (the streaming shard store's healing contract)
    loc = os.path.join(ckpt, blocks[2])
    data = open(loc, "rb").read()
    with open(loc, "wb") as f:
        f.write(data[: len(data) // 2])
    counters.reset()
    with _capture_log() as records:
        r4 = sharded_mash_allpairs(packed, k=21, mesh=mesh, checkpoint_dir=ckpt)
    assert r4.tobytes() == r1.tobytes()
    assert any("corrupt block shard" in r.getMessage() for r in records)
    r5 = sharded_mash_allpairs(packed, k=21, mesh=mesh, checkpoint_dir=ckpt)
    assert r5.tobytes() == r1.tobytes()
    assert counters.faults.get("ring_blocks_recovered") == 1  # healed once


def test_ring_step_failure_recovers_per_block():
    """An injected failure inside a ring step's wait aborts the collective
    schedule and recomputes the remaining blocks per-tile — completing
    with a bit-identical matrix and honest counters."""
    from drep_tpu.parallel.allpairs import sharded_mash_allpairs
    from drep_tpu.parallel.mesh import make_mesh

    packed = _ring_packed()
    mesh = make_mesh(3)
    want = sharded_mash_allpairs(packed, k=21, mesh=mesh)
    counters.reset()
    faults.configure("ring_dispatch:raise:1.0:max=1")
    got = sharded_mash_allpairs(packed, k=21, mesh=mesh)
    assert got.tobytes() == want.tobytes()
    assert counters.faults.get("ring_step_failures", 0) >= 1, counters.faults
    assert counters.faults.get("ring_blocks_recovered", 0) >= 1, counters.faults


def test_ring_step_watchdog_trips_into_recovery():
    """A hung ring step trips the per-step watchdog (explicit timeout
    config here; the auto-derivation shares AutoTimeout with streaming)
    and the run completes via per-block recovery."""
    from drep_tpu.parallel.allpairs import sharded_mash_allpairs
    from drep_tpu.parallel.mesh import make_mesh

    packed = _ring_packed()
    mesh = make_mesh(3)
    want = sharded_mash_allpairs(packed, k=21, mesh=mesh)
    counters.reset()
    faults.configure("ring_dispatch:hang:1.0:max=1:secs=30")
    got = sharded_mash_allpairs(
        packed, k=21, mesh=mesh,
        ft_config=FaultTolConfig(dispatch_timeout_s=0.5),
    )
    assert got.tobytes() == want.tobytes()
    assert counters.faults.get("watchdog_trips", 0) >= 1
    assert counters.faults.get("ring_blocks_recovered", 0) >= 1


def test_ring_step_site_spec_fields():
    """ring_step parses like every other site (the kill chaos test's
    proc=/skip= shape) and unknown fields still raise."""
    faults.configure("ring_step:kill:1.0:proc=7:skip=1")  # parses
    faults.fire("ring_step")  # proc 7 != this process: no-op
    assert counters.faults.get("injected_ring_step_kill", 0) == 0
    faults.configure("ring_step:raise:1.0:skip=1")
    faults.fire("ring_step")  # skipped
    with pytest.raises(faults.InjectedFault):
        faults.fire("ring_step")
    with pytest.raises(faults.FaultSpecError):
        faults.configure("ring_step:kill:1.0:bogus=1")


def test_auto_timeout_shared_rule():
    """AutoTimeout (the factored derivation) must reproduce the executor
    constants: warmup cap before enough samples, floor after, explicit
    authority, off when auto is off."""
    from drep_tpu.parallel.faulttol import (
        AUTO_TIMEOUT_FLOOR_S,
        AUTO_TIMEOUT_MIN_SAMPLES,
        AUTO_TIMEOUT_WARMUP,
        AUTO_TIMEOUT_WARMUP_CAP_S,
        AutoTimeout,
    )

    auto = AutoTimeout(FaultTolConfig(auto_timeout=True))
    assert auto.derived() is None
    assert auto.effective() == AUTO_TIMEOUT_WARMUP_CAP_S
    for _ in range(AUTO_TIMEOUT_WARMUP + AUTO_TIMEOUT_MIN_SAMPLES):
        auto.note(0.001)
    assert auto.derived() == AUTO_TIMEOUT_FLOOR_S
    assert auto.effective() == AUTO_TIMEOUT_FLOOR_S
    assert AutoTimeout(FaultTolConfig(dispatch_timeout_s=2.0)).effective() == 2.0
    assert AutoTimeout(FaultTolConfig()).effective() == 0.0


# --- durable storage (ISSUE 5): checksums, retries, scrubber -------------


def test_zero_byte_and_truncated_row_shards_heal_on_resume(tmp_path):
    """The no-registry real-world case: a zero-byte and a truncated
    ``row_*.npz`` planted DIRECTLY on disk (no fault injection — the way
    a real NFS outage or disk-full rot actually presents) must be
    classified exactly like missing shards at resume: recomputed,
    bit-identical to a clean run, healed in place, and counted honestly
    (``corrupt_shards_healed``)."""
    packed = _packed(n=48)
    ckpt = str(tmp_path / "ckpt")
    r1 = streaming_mash_edges(packed, k=21, cutoff=0.2, block=8, checkpoint_dir=ckpt)
    shards = sorted(f for f in os.listdir(ckpt) if f.startswith("row_"))
    zero, trunc = os.path.join(ckpt, shards[0]), os.path.join(ckpt, shards[2])
    with open(zero, "wb"):
        pass  # zero-byte
    data = open(trunc, "rb").read()
    with open(trunc, "wb") as f:
        f.write(data[: len(data) // 3])  # truncated
    counters.reset()
    with _capture_log() as records:
        r2 = streaming_mash_edges(packed, k=21, cutoff=0.2, block=8, checkpoint_dir=ckpt)
    _assert_edges_equal(r2, r1)
    assert 0 < r2[3] < r1[3]  # only the two damaged stripes recomputed
    assert counters.faults.get("corrupt_shards_healed") == 2, counters.faults
    assert sum("corrupt shard" in r.getMessage() for r in records) == 2
    # the heal is real: a third run resumes everything, computing nothing
    r3 = streaming_mash_edges(packed, k=21, cutoff=0.2, block=8, checkpoint_dir=ckpt)
    assert r3[3] == 0
    _assert_edges_equal(r3, r1)
    # honest reporting: the heal surfaces in the perf_counters report
    assert counters.report()["fault_tolerance"]["corrupt_shards_healed"] == 2


def test_zero_byte_and_truncated_ring_blocks_heal_on_resume(tmp_path):
    """Same no-registry case for the dense ring's block store: a
    zero-byte and a truncated ``blk_*.npz`` are recomputed per-block at
    resume, bit-identical, with honest heal counters."""
    from drep_tpu.parallel.allpairs import sharded_mash_allpairs
    from drep_tpu.parallel.mesh import make_mesh

    packed = _ring_packed()
    mesh = make_mesh(3)
    ckpt = str(tmp_path / "ring")
    r1 = sharded_mash_allpairs(packed, k=21, mesh=mesh, checkpoint_dir=ckpt)
    blocks = sorted(f for f in os.listdir(ckpt) if f.startswith("blk_"))
    with open(os.path.join(ckpt, blocks[0]), "wb"):
        pass  # zero-byte
    loc = os.path.join(ckpt, blocks[3])
    data = open(loc, "rb").read()
    with open(loc, "wb") as f:
        f.write(data[: len(data) // 3])  # truncated
    counters.reset()
    r2 = sharded_mash_allpairs(packed, k=21, mesh=mesh, checkpoint_dir=ckpt)
    assert r2.tobytes() == r1.tobytes()
    assert counters.faults.get("corrupt_shards_healed") == 2, counters.faults
    assert counters.faults.get("ring_blocks_recovered") == 2, counters.faults
    r3 = sharded_mash_allpairs(packed, k=21, mesh=mesh, checkpoint_dir=ckpt)
    assert r3.tobytes() == r1.tobytes()

    # injected post-publish bit rot on ONE block write (io:corrupt,
    # path-targeted at the block namespace) heals identically at resume
    counters.reset()
    faults.configure("io:corrupt:1.0:path=blk_:max=1")
    ckpt2 = str(tmp_path / "ring2")
    r4 = sharded_mash_allpairs(packed, k=21, mesh=mesh, checkpoint_dir=ckpt2)
    faults.configure(None)
    assert r4.tobytes() == r1.tobytes()  # run 1's results are unaffected
    assert counters.faults.get("injected_io_corrupt") == 1
    counters.reset()
    r5 = sharded_mash_allpairs(packed, k=21, mesh=mesh, checkpoint_dir=ckpt2)
    assert r5.tobytes() == r1.tobytes()
    assert counters.faults.get("corrupt_shards_healed") == 1, counters.faults


def test_bit_rotted_shard_detected_by_checksum_and_healed(tmp_path):
    """Post-write corruption the zip container alone might miss: the
    ``io:corrupt`` injection flips one bit of a PUBLISHED shard (the
    atomic rename already succeeded); the resume must detect it — in-band
    ``__crc__`` or container CRC, whichever trips first — recompute the
    stripe, and end bit-identical with corrupt_shards_healed reported."""
    packed = _packed(n=48)
    ckpt = str(tmp_path / "ckpt")
    want = streaming_mash_edges(packed, k=21, cutoff=0.2, block=8)
    faults.configure("io:corrupt:1.0:max=1")
    r1 = streaming_mash_edges(packed, k=21, cutoff=0.2, block=8, checkpoint_dir=ckpt)
    faults.configure(None)
    _assert_edges_equal(r1, want)  # run 1's RESULTS are unaffected
    assert counters.faults.get("injected_io_corrupt") == 1
    counters.reset()
    r2 = streaming_mash_edges(packed, k=21, cutoff=0.2, block=8, checkpoint_dir=ckpt)
    _assert_edges_equal(r2, want)
    assert counters.faults.get("corrupt_shards_healed") == 1, counters.faults
    assert 0 < r2[3] < r1[3]
    r3 = streaming_mash_edges(packed, k=21, cutoff=0.2, block=8, checkpoint_dir=ckpt)
    assert r3[3] == 0  # healed: full resume
    _assert_edges_equal(r3, want)


def test_transient_io_errors_retry_with_honest_counters(tmp_path):
    """EIO on write and ESTALE on read are retried with bounded backoff
    (DREP_TPU_IO_RETRIES) — the run completes bit-identical with
    io_retries counted, and nothing is recorded when nothing fails."""
    packed = _packed(n=48)
    want = streaming_mash_edges(packed, k=21, cutoff=0.2, block=8)

    # write-side EIO, twice transient
    ckpt = str(tmp_path / "ckpt_w")
    faults.configure("io:io_error:1.0:max=2")
    r1 = streaming_mash_edges(packed, k=21, cutoff=0.2, block=8, checkpoint_dir=ckpt)
    faults.configure(None)
    _assert_edges_equal(r1, want)
    assert counters.faults.get("io_retries", 0) >= 2, counters.faults
    assert counters.faults.get("injected_io_io_error") == 2

    # read-side ESTALE at resume
    counters.reset()
    faults.configure("io:stale_read:1.0:max=1")
    r2 = streaming_mash_edges(packed, k=21, cutoff=0.2, block=8, checkpoint_dir=ckpt)
    faults.configure(None)
    _assert_edges_equal(r2, want)
    assert r2[3] == 0  # the retried read SUCCEEDED: no recompute
    assert counters.faults.get("io_retries", 0) >= 1, counters.faults

    # exhausted budget on SHARD reads (path= keeps the meta readable):
    # the op books io_unrecoverable and the shard read path degrades to
    # recompute — but the on-disk shard is NOT deleted and NOT counted
    # as a heal (it may be perfectly intact; a filesystem brownout must
    # never destroy a fully-computed store). The store survives a
    # persistently sick read side at the price of recompute, never a
    # crash, and the counters tell the truth: unrecoverable, not corrupt.
    counters.reset()
    faults.configure("io:stale_read:1.0:path=row_")
    r3 = streaming_mash_edges(packed, k=21, cutoff=0.2, block=8, checkpoint_dir=ckpt)
    faults.configure(None)
    _assert_edges_equal(r3, want)
    assert counters.faults.get("io_unrecoverable", 0) >= 1, counters.faults
    assert counters.faults.get("corrupt_shards_healed", 0) == 0, counters.faults
    import glob as _glob

    assert _glob.glob(os.path.join(ckpt, "row_*.npz")), "brownout deleted intact shards"


def test_enospc_degrades_into_actionable_store_full_error(tmp_path):
    """Quota exhaustion must not burn the retry budget or print a bare
    errno: the error names the store and the bytes the write needed."""
    from drep_tpu.utils.durableio import StoreFullError

    packed = _packed(n=48)
    ckpt = str(tmp_path / "ckpt")
    faults.configure("io:enospc:1.0")
    with pytest.raises(StoreFullError, match="ENOSPC") as ei:
        streaming_mash_edges(packed, k=21, cutoff=0.2, block=8, checkpoint_dir=ckpt)
    assert str(tmp_path) in str(ei.value)  # names the store
    assert "bytes" in str(ei.value)  # names the need
    assert counters.faults.get("io_retries", 0) == 0  # never retried


def test_checked_payload_roundtrip_and_json_notes(tmp_path):
    """The durable-I/O contract at the unit level: npz payloads carry an
    in-band __crc__ verified on read (legacy payloads without one stay
    readable), JSON notes carry a "crc" key stripped by the reader, and
    a checkpoint meta survives the checksum round-trip without the crc
    ever counting as a pinned parameter."""
    import json as _json

    import zipfile

    from drep_tpu.utils import durableio
    from drep_tpu.utils.ckptmeta import checkpoint_meta_matches, open_checkpoint_dir

    p = str(tmp_path / "row_00000.npz")
    durableio.atomic_savez(p, ii=np.arange(4), jj=np.arange(4))
    assert f"{durableio.CRC_KEY}.npy" in zipfile.ZipFile(p).namelist()
    z = durableio.load_npz_checked(p)
    assert durableio.CRC_KEY not in z  # stripped after verification
    durableio._flip_bit(p)
    with pytest.raises(durableio.CorruptPayloadError):
        durableio.load_npz_checked(p)

    # legacy npz (pre-checksum) stays readable
    legacy = str(tmp_path / "legacy.npz")
    np.savez(legacy, a=np.arange(3))
    assert list(durableio.load_npz_checked(legacy)) == ["a"]

    # JSON notes: crc embedded, verified, stripped; legacy accepted
    note = str(tmp_path / ".pod-done.p0")
    durableio.atomic_write_json(note, {"pairs": 7, "seq": 1})
    raw = _json.load(open(note))
    assert durableio.JSON_CRC_KEY in raw
    assert durableio.read_json_checked(note) == {"pairs": 7, "seq": 1}
    with open(note, "w") as f:
        f.write('{"pairs": 7, "seq": 1}')  # legacy, no crc
    assert durableio.read_json_checked(note) == {"pairs": 7, "seq": 1}
    with open(note, "w") as f:
        f.write('{"pairs": 7, "seq": 1, "crc": 12345}')  # rotted
    with pytest.raises(durableio.CorruptPayloadError):
        durableio.read_json_checked(note)
    # a rotted CHECKSUM VALUE (null / garbage) classifies, never crashes
    with open(note, "w") as f:
        f.write('{"pairs": 7, "crc": null}')
    with pytest.raises(durableio.CorruptPayloadError):
        durableio.read_json_checked(note)
    # an npz whose __crc__ member itself rotted to empty classifies too
    rotted = str(tmp_path / "rotted.npz")
    np.savez(rotted, a=np.arange(3), **{durableio.CRC_KEY: np.empty(0, np.uint32)})
    with pytest.raises(durableio.CorruptPayloadError):
        durableio.load_npz_checked(rotted)
    # the in-band key is reserved — a colliding payload raises loudly
    # instead of silently dropping the caller's value
    with pytest.raises(ValueError, match="reserved"):
        durableio.atomic_write_json(str(tmp_path / "x.json"), {"crc": 1, "a": 2})

    # meta round-trip: the embedded crc never pins the meta match
    store = str(tmp_path / "store")
    meta = {"n": 3, "fingerprint": "abc"}
    assert open_checkpoint_dir(store, meta, clear_suffixes=(".npz",)) is False
    assert checkpoint_meta_matches(store, meta)
    assert open_checkpoint_dir(store, meta, clear_suffixes=(".npz",)) is True
    # a bit-rotted meta classifies as corrupt -> not resumable (reopen
    # clears + rewrites instead of trusting rotted pins)
    durableio._flip_bit(os.path.join(store, "meta.json"))
    assert not checkpoint_meta_matches(store, meta)


def test_durableio_knobs_fsync_and_configure(tmp_path, monkeypatch):
    """The policy knobs: DREP_TPU_FSYNC routes publishes through the
    fsync path (content identical), configure() overrides beat the env
    (the CLI wiring), and a bare configure() resets to env resolution."""
    from drep_tpu.utils import durableio

    monkeypatch.setenv(durableio.FSYNC_ENV, "1")
    assert durableio.fsync_enabled()
    p = str(tmp_path / "row_00000.npz")
    durableio.atomic_savez(p, a=np.arange(4))  # fsync'd publish
    assert list(durableio.load_npz_checked(p)) == ["a"]
    monkeypatch.delenv(durableio.FSYNC_ENV)
    assert not durableio.fsync_enabled()

    monkeypatch.setenv(durableio.IO_RETRIES_ENV, "7")
    assert durableio.io_retries() == 7
    durableio.configure(retries=1, fsync=True)  # the CLI's installer
    try:
        assert durableio.io_retries() == 1 and durableio.fsync_enabled()
    finally:
        durableio.configure()  # full reset: env resolution again
    assert durableio.io_retries() == 7


def test_corrupt_done_note_reads_as_absent(tmp_path):
    """A half-written/rotted done-note must read as ABSENT (the peer's
    heartbeat staleness then decides) — never crash the survivor."""
    from drep_tpu.parallel.faulttol import HeartbeatManager

    hb = HeartbeatManager(str(tmp_path), cadence=0.1, max_dead=1, pc=2, pid=0)
    hb.start()
    try:
        with open(hb.done_path(1), "w") as f:
            f.write('{"pairs": 5, "seq": 1, "crc": 99}')  # checksum mismatch
        assert hb.read_done(1) is None
        assert not hb.peer_finished(1)
        with open(hb.done_path(1), "w") as f:
            f.write('{"pairs": 5, "se')  # torn
        assert hb.read_done(1) is None
    finally:
        hb.close()


def test_scrub_store_detects_deletes_and_resume_heals(tmp_path):
    """The standalone verifier: clean store -> exit 0; planted damage
    (hand truncation) -> nonzero exit naming the shard; --delete removes
    it; the next resume recomputes it bit-identically (the acceptance
    loop: scrub-then-resume)."""
    import importlib.util

    spec = importlib.util.spec_from_file_location(
        "scrub_store", os.path.join(REPO, "tools", "scrub_store.py")
    )
    ss = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(ss)

    packed = _packed(n=48)
    ckpt = str(tmp_path / "ckpt")
    r1 = streaming_mash_edges(packed, k=21, cutoff=0.2, block=8, checkpoint_dir=ckpt)
    assert ss.main([ckpt]) == 0  # clean store: exit 0, CLI path exercised
    rep = ss.scrub([ckpt])
    assert rep["verified"] > 0 and not rep["damaged"]

    shard = sorted(f for f in os.listdir(ckpt) if f.startswith("row_"))[1]
    loc = os.path.join(ckpt, shard)
    data = open(loc, "rb").read()
    with open(loc, "wb") as f:
        f.write(data[: len(data) // 2])
    assert ss.main([ckpt]) == 1  # damage: nonzero exit
    rep = ss.scrub([ckpt], delete=True)
    assert [p for p, _ in rep["damaged"]] == [loc]
    assert not os.path.exists(loc)

    r2 = streaming_mash_edges(packed, k=21, cutoff=0.2, block=8, checkpoint_dir=ckpt)
    _assert_edges_equal(r2, r1)
    assert os.path.exists(loc), "resume did not heal the scrubbed shard"
    assert ss.main([ckpt]) == 0


def test_io_fault_spec_fields_and_path_targeting():
    """The io site parses like every other site; op filtering (stale_read
    fires on reads only, enospc on writes only) and the new path=
    substring targeting are deterministic."""
    import errno as _errno

    faults.configure("io:stale_read:1.0")
    faults.fire_io("write")  # read-only mode: no-op on writes
    with pytest.raises(OSError) as ei:
        faults.fire_io("read")
    assert ei.value.errno == _errno.ESTALE

    faults.configure("io:enospc:1.0")
    faults.fire_io("read")  # write-only mode: no-op on reads
    with pytest.raises(OSError) as ei:
        faults.fire_io("write")
    assert ei.value.errno == _errno.ENOSPC

    faults.configure("io:corrupt:1.0:path=.e01")
    assert not faults.corrupt_write(path="/store/row_00004.npz")
    assert faults.corrupt_write(path="/store/row_00004.e01.npz")
    faults.configure("io:io_error:1.0:proc=7")
    faults.fire_io("write", path="/x")  # other process: no-op
    assert counters.faults.get("injected_io_io_error", 0) == 0
    with pytest.raises(faults.FaultSpecError):
        # drep-lint: allow[fault-site] — negative test: asserts the io site rejects unknown modes
        faults.configure("io:not_a_mode")
    with pytest.raises(faults.FaultSpecError):
        faults.configure("io:corrupt:1.0:bogus=1")
