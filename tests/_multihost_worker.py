"""Worker process for the 2-process `jax.distributed` equality test.

Run via subprocess by tests/test_multihost.py — NOT collected by pytest.
Each process owns 2 forced-host CPU devices; together they form a
4-device, 2-process "pod" over which the ring all-pairs and streaming
paths must produce results identical to the local dense oracle
(SURVEY.md §5.8: the multi-host gather/placement contract).
"""

import os
import sys

import numpy as np


def main() -> None:
    pid = int(sys.argv[1])
    nproc = int(sys.argv[2])
    coord = sys.argv[3]
    outdir = sys.argv[4]
    mode = sys.argv[5] if len(sys.argv) > 5 else "full"

    os.environ["JAX_PLATFORMS"] = "cpu"

    import jax

    from drep_tpu.utils import envknobs

    # the config knob multiplies CPU devices, and must be set before the
    # backend initializes
    ndev = envknobs.env_int("DREP_TPU_TEST_CPU_DEVICES")
    jax.config.update("jax_platforms", "cpu")
    jax.config.update("jax_num_cpu_devices", ndev)
    if mode in ("join_streaming", "join_ring"):
        # mid-run JOINER (ISSUE 9): NOT a member of the jax.distributed
        # pod at all — a separate single-process jax runtime that joins
        # the pod's elastic stage through the checkpoint-dir protocol
        # alone (DREP_TPU_POD_JOIN set by the parent test).
        _joiner_case(outdir, mode, sys.argv[6])
        return

    init_kwargs = {}
    if mode in ("elastic", "elastic_prebarrier", "ring", "secondary_retry"):
        # these cases kill (or early-exit) a pod member ON PURPOSE: the jax
        # coordination service's own death detection must stay far beyond
        # the test horizon, or it broadcasts the death as a fatal error
        # and the client layer abort()s the very survivors under test
        # (client.h: "Terminating process..."). The repo's heartbeat
        # protocol is the detector being exercised, not jax's.
        init_kwargs = dict(heartbeat_timeout_seconds=6000)
    jax.distributed.initialize(
        coordinator_address=coord, num_processes=nproc, process_id=pid,
        **init_kwargs,
    )
    assert jax.process_count() == nproc, jax.process_count()
    assert len(jax.devices()) == ndev * nproc, jax.devices()
    assert len(jax.local_devices()) == ndev

    if mode == "barrier_timeout":
        _barrier_timeout_case(pid, nproc, outdir)
        return
    if mode == "elastic":
        _elastic_case(pid, nproc, outdir, sys.argv[6])
        return
    if mode == "elastic_prebarrier":
        _elastic_case(pid, nproc, outdir, sys.argv[6], die_prebarrier=True)
        return
    if mode == "ring":
        _ring_case(pid, nproc, outdir, sys.argv[6])
        return
    if mode == "secondary_retry":
        _secondary_retry_case(pid, nproc, outdir)
        return

    from drep_tpu.ops.minhash import all_vs_all_mash, pack_sketches
    from drep_tpu.parallel.allpairs import sharded_mash_allpairs
    from drep_tpu.parallel.mesh import make_mesh
    from drep_tpu.parallel.streaming import streaming_mash_edges

    # same seed on every process — host-replicated ingest, as in production
    rng = np.random.default_rng(7)
    s, n = 48, 13  # n deliberately not a multiple of 4 devices (padding path)
    base = np.unique(rng.integers(0, 2**62, size=8 * s * n, dtype=np.uint64))
    rng.shuffle(base)
    shared = base[:s]
    sketches = []
    for i in range(n):
        own = base[s * (i + 1) : s * (i + 2)]
        mix = (i % 4) * s // 8
        sketches.append(np.sort(np.unique(np.concatenate([shared[:mix], own[: s - mix]]))[:s]))
    packed = pack_sketches(sketches, [f"g{i}" for i in range(n)], s)

    # dense oracle runs locally (unsharded jit on this process's devices)
    want, _ = all_vs_all_mash(packed, k=21, tile=8)

    got = sharded_mash_allpairs(packed, k=21, mesh=make_mesh())
    assert got.shape == (n, n), got.shape
    assert np.allclose(got, want, atol=1e-6), "ring all-pairs != dense oracle"

    # streaming path: cutoff > 1 keeps every edge; block striping divides
    # row blocks between the two processes and allgathers the edges back
    ii, jj, dd, pairs = streaming_mash_edges(packed, k=21, cutoff=2.0, block=4)
    dense = np.full((n, n), np.inf, np.float32)
    dense[ii, jj] = dd
    iu = np.triu_indices(n, 1)
    assert np.allclose(dense[iu], want[iu].astype(np.float32), atol=1e-6), (
        "streaming edges != dense oracle"
    )
    assert pairs == n * (n - 1) // 2, pairs  # striped counts sum to all pairs

    # shared-checkpoint-dir path: process 0 opens/clears, peers wait; shards
    # are written per-stripe, then a second call must resume every shard
    # (pairs_computed sums to 0 across processes) with identical edges
    ckpt = os.path.join(outdir, "ckpt")
    ii1, jj1, dd1, pairs1 = streaming_mash_edges(
        packed, k=21, cutoff=2.0, block=4, checkpoint_dir=ckpt
    )
    assert pairs1 == n * (n - 1) // 2, pairs1
    ii2, jj2, dd2, pairs2 = streaming_mash_edges(
        packed, k=21, cutoff=2.0, block=4, checkpoint_dir=ckpt
    )
    assert pairs2 == 0, pairs2  # fully resumed from the shared shards
    o1, o2 = np.lexsort((jj1, ii1)), np.lexsort((jj2, ii2))
    assert np.array_equal(ii1[o1], ii2[o2])
    assert np.array_equal(jj1[o1], jj2[o2])
    assert np.array_equal(dd1[o1], dd2[o2])

    _sharded_ingest_check(pid, nproc, outdir)
    _combo_shared_workdir(pid, nproc, outdir)

    with open(os.path.join(outdir, f"ok_{pid}"), "w") as f:
        f.write("ok")


# 9 row blocks at the effective block of 8 (streaming clamps the requested
# block to a multiple the kernels accept — _effective_block): every process
# of 4 owns >= 2 interleaved stripes (3/2/2/2)
COMBO_N = 68
COMBO_BLOCK = 8
COMBO_SIZES = [12, 9, 8, 7, 6, 6, 5, 4, 4, 3, 2, 1, 1]  # heavy-ish tail, sums to 68
COMBO_S_BOTTOM = 48  # planted bottom-sketch width == the wrapper's MASH_sketch


def plant_combo_sketches():
    """Deterministic cluster-structured GenomeSketches — the SAME recipe in
    every worker process and in the pytest process's single-process oracle
    run (seeded, so all builds see identical sketches)."""
    import pandas as pd

    from drep_tpu.ingest import DEFAULT_SCALE, GenomeSketches

    assert sum(COMBO_SIZES) == COMBO_N
    rng = np.random.default_rng(21)
    s_bottom, s_scaled = COMBO_S_BOTTOM, 300
    names, bottoms, scaleds = [], [], []
    gi = 0
    for size in COMBO_SIZES:
        pool_b = np.unique(rng.integers(0, 2**62, size=2 * s_bottom, dtype=np.uint64))
        pool_s = np.unique(rng.integers(0, 2**62, size=int(1.2 * s_scaled), dtype=np.uint64))
        for _ in range(size):
            keep_b = pool_b[rng.random(len(pool_b)) < 0.90]
            own_b = np.unique(rng.integers(0, 2**62, size=s_bottom // 6, dtype=np.uint64))
            bottoms.append(np.sort(np.concatenate([keep_b, own_b]))[:s_bottom])
            keep_s = pool_s[rng.random(len(pool_s)) < 0.97]
            own_s = np.unique(rng.integers(0, 2**62, size=s_scaled // 25, dtype=np.uint64))
            scaleds.append(np.sort(np.concatenate([keep_s, own_s])))
            names.append(f"combo_{gi}.fasta")
            gi += 1
    gdb = pd.DataFrame(
        {
            "genome": names,
            "length": np.full(COMBO_N, 1_000_000, np.int64),
            "N50": np.full(COMBO_N, 50_000, np.int64),
            "contigs": np.full(COMBO_N, 10, np.int64),
            "n_kmers": np.full(COMBO_N, 970_000, np.int64),
        }
    )
    return GenomeSketches(
        names=names, gdb=gdb, bottom=bottoms, scaled=scaleds,
        k=21, sketch_size=s_bottom, scale=DEFAULT_SCALE,
    )


def run_combo_wrapper(wd_path: str):
    """The streaming+greedy north-star combo against a (possibly shared)
    workdir; returns the Cdb. Used by the workers (shared workdir, 2-4
    processes) AND by the pytest process (private workdir, 1 process)."""
    import pandas as pd

    from drep_tpu.cluster.controller import d_cluster_wrapper
    from drep_tpu.ingest import DEFAULT_SCALE, _save, sketch_args_snapshot
    from drep_tpu.workdir import WorkDirectory

    gs = plant_combo_sketches()
    wd = WorkDirectory(wd_path)
    bdb = pd.DataFrame(
        {"genome": gs.names, "location": [f"/nonexistent/{g}" for g in gs.names]}
    )
    _save(wd, gs)
    wd.store_arguments(
        "sketch",
        sketch_args_snapshot(bdb["genome"], 21, gs.sketch_size, DEFAULT_SCALE, "splitmix64"),
    )
    cdb = d_cluster_wrapper(
        wd, bdb,
        streaming_primary=True,
        streaming_block=COMBO_BLOCK,
        greedy_secondary_clustering=True,
        # the sketch-cache compatibility key includes the sketch size; the
        # planted bottom sketches are 48-wide, so the wrapper must ask for
        # 48 or it will miss the cache and try to read /nonexistent FASTAs
        MASH_sketch=COMBO_S_BOTTOM,
    )
    return cdb


def partition(cdb, column: str) -> set[frozenset]:
    groups: dict = {}
    for g, c in zip(cdb["genome"], cdb[column]):
        groups.setdefault(c, set()).add(g)
    return {frozenset(v) for v in groups.values()}


def truth_partition() -> set[frozenset]:
    out, gi = [], 0
    for size in COMBO_SIZES:
        out.append(frozenset(f"combo_{g}.fasta" for g in range(gi, gi + size)))
        gi += size
    return set(out)


def _barrier_timeout_case(pid: int, nproc: int, outdir: str) -> None:
    """Dead-peer barrier diagnosis (ISSUE 2 multi-host hardening): every
    process except 0 exits BEFORE reaching open_checkpoint_dir's barrier;
    process 0 must raise the actionable CollectiveTimeout NAMING the
    missing process(es) within the (test-shortened) collective timeout,
    instead of hanging in sync_global_devices forever."""
    if pid != 0:
        # die before the barrier — but after distributed init, so the
        # survivor's collective layer genuinely waits on a vanished peer
        os._exit(0)

    from drep_tpu.parallel.faulttol import CollectiveTimeout
    from drep_tpu.utils.ckptmeta import open_checkpoint_dir

    ckpt = os.path.join(outdir, "barrier_ckpt")
    try:
        open_checkpoint_dir(ckpt, {"probe": 1}, clear_suffixes=(".npz",))
    except CollectiveTimeout as e:
        msg = str(e)
        missing = [p for p in range(1, nproc)]
        assert f"{missing}" in msg, f"error does not name missing process(es): {msg}"
        with open(os.path.join(outdir, "ok_0"), "w") as f:
            f.write(msg)
        # the abandoned watchdog thread is still parked inside the dead
        # collective; normal interpreter teardown can wedge on the
        # distributed client — exit hard, the ok-file is the verdict
        os._exit(0)
    raise AssertionError("open_checkpoint_dir returned despite a dead peer")


# --- elastic pod: epoch-coordinated stripe re-assignment ------------------

# 9 row blocks at block 8: under the mirror-paired epoch-0 deal over 3
# processes, p0 owns {0,3,5,8}, p1 owns {1,4,7}, p2 owns {2,6} — killing
# p1 at its SECOND stripe leaves one finished shard (stripe 1, the
# survivors must reuse it) and two unfinished stripes (4, 7) that re-deal
# one to each survivor under live=[0, 2].
ELASTIC_N, ELASTIC_S, ELASTIC_BLOCK = 72, 64, 8


def _elastic_packed():
    """Deterministic group-structured sketches, identical in every process
    (the replicated-ingest contract the stripe deal assumes)."""
    from drep_tpu.ops.minhash import PAD_ID, PackedSketches

    rng = np.random.default_rng(5)
    ids = np.full((ELASTIC_N, ELASTIC_S), PAD_ID, dtype=np.int32)
    counts = np.full(ELASTIC_N, ELASTIC_S, dtype=np.int32)
    pools = [
        np.sort(rng.choice(2**20, size=ELASTIC_S * 2, replace=False).astype(np.int32))
        for _ in range(5)
    ]
    for i in range(ELASTIC_N):
        ids[i] = np.sort(rng.choice(pools[i % 5], size=ELASTIC_S, replace=False))
    return PackedSketches(
        ids=ids, counts=counts, names=[f"g{i}" for i in range(ELASTIC_N)]
    )


def _dump_counters(outdir: str, who) -> None:
    """Fault counters + gauges + the ordered epoch history for the
    parent's assertions (gauges carry the drain-adoption latency the
    ISSUE-9 tests pin; epoch_history anchors the ISSUE-10
    trace-report-vs-counters membership-timeline check)."""
    import json

    from drep_tpu.utils.profiling import counters

    with open(os.path.join(outdir, f"counters_{who}.json"), "w") as f:
        json.dump(
            {
                **counters.faults,
                "gauges": dict(counters.gauges),
                "epoch_history": list(counters.epoch_history),
            },
            f,
        )


def _maybe_events(outdir: str, pid: int) -> None:
    """Structured event tracing for the pod chaos cells (ISSUE 10): when
    the parent test exports DREP_TPU_EVENTS=on, each member appends to
    <outdir>/log/events.p<pid>.jsonl for the tools/trace_report.py
    timeline assertions. A no-op (zero files) otherwise."""
    from drep_tpu.utils import telemetry

    telemetry.configure(log_dir=os.path.join(outdir, "log"), pid=pid)


def _maybe_install_test_knobs(ckpt_dir: str | None) -> None:
    """Test-only env knobs for the elastic up/down cases:

    - DREP_TPU_TEST_MAX_JOINS / DREP_TPU_TEST_MAX_DEAD: install a process
      FaultTolConfig with that join budget / death budget (the CLI's
      --max_joins / --max_dead_processes path, minus the CLI). MAX_DEAD=0
      is the drain tests' tripwire: any mis-classification of a planned
      departure as a death aborts the run loudly.
    - DREP_TPU_TEST_WAIT_JOIN: block until a join-request note exists in
      the checkpoint dir before starting the stage — deterministic
      ordering for the join tests (admission lands at the very first
      liveness check instead of racing the joiner's interpreter startup).
    """
    mj = int(os.environ.get("DREP_TPU_TEST_MAX_JOINS", "0"))
    md = os.environ.get("DREP_TPU_TEST_MAX_DEAD")
    if mj or md is not None:
        from drep_tpu.parallel.faulttol import FaultTolConfig, configure_defaults

        configure_defaults(
            FaultTolConfig(
                max_joins=mj,
                max_dead_processes=int(md) if md is not None else 1,
            )
        )
    if os.environ.get("DREP_TPU_TEST_WAIT_JOIN") and ckpt_dir is not None:
        import glob
        import time

        deadline = time.time() + 120
        while time.time() < deadline:
            if glob.glob(os.path.join(ckpt_dir, ".pod-join.p*")):
                return
            time.sleep(0.05)
        raise AssertionError("no join-request note appeared within 120s")


def _joiner_case(outdir: str, mode: str, ckpt_dir: str) -> None:
    """Run ONE elastic stage as a mid-run joiner: request admission via
    the checkpoint-dir protocol, compute the work re-dealt to this
    process, and publish the assembled result + counters for the parent's
    bit-identity assertions. DREP_TPU_TEST_JOIN_AFTER_DRAIN delays the
    join request until a departure note exists (the drain-then-join churn
    cell's deterministic ordering)."""
    import glob
    import time

    if os.environ.get("DREP_TPU_TEST_JOIN_AFTER_DRAIN"):
        deadline = time.time() + 120
        while time.time() < deadline and not glob.glob(
            os.path.join(ckpt_dir, ".pod-drain.p*")
        ):
            time.sleep(0.05)
    join_req = os.environ.get("DREP_TPU_POD_JOIN", "").strip()
    _maybe_events(outdir, int(join_req) if join_req.isdigit() else 99)
    packed = _elastic_packed()
    if mode == "join_streaming":
        from drep_tpu.parallel.streaming import streaming_mash_edges

        ii, jj, dd, pairs = streaming_mash_edges(
            packed, k=21, cutoff=0.2, block=ELASTIC_BLOCK, checkpoint_dir=ckpt_dir
        )
        np.savez(
            os.path.join(outdir, "edges_joiner.npz"), ii=ii, jj=jj, dd=dd, pairs=pairs
        )
    else:
        from drep_tpu.parallel.allpairs import sharded_mash_allpairs
        from drep_tpu.parallel.mesh import make_mesh

        dist = sharded_mash_allpairs(
            packed, k=21, mesh=make_mesh(), checkpoint_dir=ckpt_dir
        )
        np.save(os.path.join(outdir, "ring_joiner.npy"), dist)
    _dump_counters(outdir, "joiner")
    with open(os.path.join(outdir, "ok_joiner"), "w") as f:
        f.write("ok")


def _finish_pod_case(pid: int, nproc: int, outdir: str) -> None:
    """Shared pod-case epilogue: write the ok-file, keep process 0 (the
    jax coordination service host) alive until every still-live peer has
    published its ok-file, then exit hard — a killed peer leaves the
    coordination service in an error state and interpreter teardown can
    wedge on the distributed client; the artifacts are the verdict."""
    with open(os.path.join(outdir, f"ok_{pid}"), "w") as f:
        f.write("ok")
    if pid == 0:
        # process 0 hosts the jax coordination service: it must exit LAST,
        # or every still-running peer's error poll sees the service socket
        # close and abort()s. Wait for the ok-file of every process the
        # pod still believes alive, then linger past their write->exit
        # window. The deadline must sit WELL BELOW the jax coordination
        # service's own ~100s unhealthy-task horizon: this process may
        # legitimately finish without ever learning of a peer's death (a
        # survivor can detect and cover the dead member's work before this
        # one's next liveness check, so pod_dead() here can be empty) and
        # would then wait for an ok-file that never comes — past the
        # horizon the service aborts THIS process and fails the test.
        import time

        from drep_tpu.parallel.faulttol import pod_dead, pod_drained

        # drained members exit 0 WITHOUT an ok-file (their verdict is the
        # drained_N marker) — waiting for one would burn the whole linger
        # deadline on every drain test
        gone = set(pod_dead()) | set(pod_drained())
        want = [p for p in range(nproc) if p != 0 and p not in gone]
        deadline = time.time() + 45
        while time.time() < deadline and not all(
            os.path.exists(os.path.join(outdir, f"ok_{p}")) for p in want
        ):
            time.sleep(0.05)
        time.sleep(1.0)
    os._exit(0)


def _elastic_case(
    pid: int, nproc: int, outdir: str, ckpt_dir: str, die_prebarrier: bool = False
) -> None:
    """One checkpointed streaming edge pass under the elastic-pod protocol
    (heartbeat cadence from the parent's DREP_TPU_HEARTBEAT_S env; the
    killed run's parent also installs a process_death:kill fault on one
    member). Publishes this process's final edges + fault counters for
    the parent to compare bit-for-bit against the healthy pod.

    ``die_prebarrier``: process 1 exits BEFORE the streaming call — i.e.
    before it ever starts heartbeating or reaches the stage-open barrier.
    The survivors must diagnose it from the missing heartbeat note during
    the barrier wait (pre-barrier death admission, utils/ckptmeta.py),
    continue degraded, and compute the FULL edge set between them."""
    from drep_tpu.parallel.faulttol import PodDrained
    from drep_tpu.parallel.streaming import streaming_mash_edges
    from drep_tpu.utils.ckptmeta import open_checkpoint_dir

    if die_prebarrier and pid == 1:
        # "dead before the stage-open barrier" FROM THE PROTOCOL'S VIEW:
        # this process never writes a heartbeat note and never reaches the
        # barrier, which is everything the admission path diagnoses (a
        # missing/stale note). It stays OS-alive, parked, because the jax
        # coordination service on this jax version has no tunable service
        # heartbeat horizon (the init kwargs fall back via TypeError) and
        # would otherwise declare the task unhealthy after ~100 s and
        # abort() the very survivors under test — jax's detector is not
        # the one being exercised. Exit 0 the moment the survivors have
        # published their verdict artifacts (before process 0, the service
        # host, exits — lingering past it would abort this process too).
        import time

        deadline = time.time() + 300
        while time.time() < deadline and not all(
            os.path.exists(os.path.join(outdir, f"ok_{p}")) for p in (0, 2)
        ):
            time.sleep(0.05)
        os._exit(0)
    _maybe_install_test_knobs(ckpt_dir)
    _maybe_events(outdir, pid)
    packed = _elastic_packed()
    try:
        ii, jj, dd, pairs = streaming_mash_edges(
            packed, k=21, cutoff=0.2, block=ELASTIC_BLOCK, checkpoint_dir=ckpt_dir
        )
    except PodDrained:
        # the graceful-preemption exit (ISSUE 9): departure note is out,
        # peers re-deal immediately — this process's verdict artifact is
        # the drained marker + its honest counters, then exit 0
        with open(os.path.join(outdir, f"drained_{pid}"), "w") as f:
            f.write("drained")
        _dump_counters(outdir, pid)
        os._exit(0)
    # degraded-pod plumbing downstream of the streaming stage: the next
    # checkpoint-store open (the secondary loop's shape) must coordinate
    # over the survivor set — file barrier, lowest-live leader — instead
    # of hanging on the dead member until the collective timeout
    open_checkpoint_dir(
        os.path.join(outdir, "sec_store"), {"probe": 1}, clear_suffixes=(".npz",)
    )
    np.savez(
        os.path.join(outdir, f"edges_{pid}.npz"), ii=ii, jj=jj, dd=dd, pairs=pairs
    )
    _dump_counters(outdir, pid)
    _finish_pod_case(pid, nproc, outdir)


def _ring_case(pid: int, nproc: int, outdir: str, ckpt_dir: str) -> None:
    """One dense mash ring over the FULL pod mesh with a shared block
    store — the step-wise elastic ring (parallel/allpairs.py). The killed
    run's parent installs ``ring_step:kill`` on one member: it dies at a
    step boundary with its first step's blocks durable; the survivors
    must detect the death between steps, re-deal the missing blocks, and
    assemble a distance matrix bit-identical to the healthy pod's."""
    from drep_tpu.parallel.allpairs import sharded_mash_allpairs
    from drep_tpu.parallel.faulttol import PodDrained
    from drep_tpu.parallel.mesh import make_mesh

    _maybe_install_test_knobs(ckpt_dir)
    _maybe_events(outdir, pid)
    packed = _elastic_packed()
    try:
        dist = sharded_mash_allpairs(
            packed, k=21, mesh=make_mesh(), checkpoint_dir=ckpt_dir
        )
    except PodDrained:
        with open(os.path.join(outdir, f"drained_{pid}"), "w") as f:
            f.write("drained")
        _dump_counters(outdir, pid)
        os._exit(0)
    np.save(os.path.join(outdir, f"ring_{pid}.npy"), dist)
    _dump_counters(outdir, pid)
    _finish_pod_case(pid, nproc, outdir)


def _secondary_retry_case(pid: int, nproc: int, outdir: str) -> None:
    """The retryable sharded secondary (ISSUE 4): on a pod the secondary
    mesh is clamped to THIS process's devices (engines._mesh_or_none
    local_only — asserted), so a mid-batch failure is a process-local
    event that retrying_call can retry without desyncing the pod. The
    parent injects ``secondary_batch:raise`` on process 1 only: its first
    attempt fails, the retry completes, and every process ends with
    bit-identical ANI matrices."""
    import json

    import jax

    from drep_tpu.cluster.engines import MESH_MIN_GENOMES, _mesh_or_none
    from drep_tpu.ops.containment import pack_scaled_sketches
    from drep_tpu.parallel.allpairs import sharded_containment_allpairs
    from drep_tpu.parallel.faulttol import FaultTolConfig, retrying_call
    from drep_tpu.utils.profiling import counters

    mesh = _mesh_or_none(None, MESH_MIN_GENOMES, local_only=True)
    assert mesh is not None, "pod worker has 2 local devices — expected a mesh"
    assert all(
        d.process_index == jax.process_index() for d in mesh.devices.flat
    ), "secondary mesh must be live-clamped to local devices on a pod"

    rng = np.random.default_rng(11)
    n, s = 72, 96
    base = np.unique(rng.integers(0, 2**62, size=6 * s * n, dtype=np.uint64))
    rng.shuffle(base)
    sketches = []
    for i in range(n):
        own = base[s * (i + 1) : s * (i + 2)]
        mix = int(s * 0.4)
        sketches.append(np.sort(np.unique(np.concatenate([base[:mix], own[: s - mix]]))[:s]))
    packed = pack_scaled_sketches(sketches, [f"s{i}" for i in range(n)], pad_multiple=32)

    ani, cov = retrying_call(
        lambda: sharded_containment_allpairs(packed, k=21, mesh=mesh),
        site="secondary_batch",
        config=FaultTolConfig(backoff_s=0.0),
        local_only=True,
    )
    np.savez(os.path.join(outdir, f"secondary_{pid}.npz"), ani=ani, cov=cov)
    with open(os.path.join(outdir, f"counters_{pid}.json"), "w") as f:
        json.dump(counters.faults, f)
    _finish_pod_case(pid, nproc, outdir)


INGEST_N = 12
INGEST_MB = 1


def _sharded_ingest_check(pid: int, nproc: int, outdir: str) -> None:
    """Per-process sharded ingest (SURVEY.md §7 hard part (f)): real FASTA
    files on the shared filesystem, each jax.distributed process sketches
    ONLY its interleaved stripe (asserted by counting _sketch_one calls),
    every process assembles the identical full sketch set (digest-compared
    by the harness), and the pod's aggregate MB/s is recorded."""
    import glob
    import hashlib
    import time

    from jax.experimental import multihost_utils as mhu

    import drep_tpu.ingest as ingest_mod
    from drep_tpu.ingest import make_bdb, sketch_genomes
    from drep_tpu.workdir import WorkDirectory

    fdir = os.path.join(outdir, "ingest_fastas")
    if pid == 0:
        os.makedirs(fdir, exist_ok=True)
        rng = np.random.default_rng(3)
        bases = np.frombuffer(b"ACGT", dtype=np.uint8)
        for i in range(INGEST_N):
            seq = bases[rng.integers(0, 4, size=INGEST_MB * 1_000_000)].tobytes().decode()
            with open(os.path.join(fdir, f"g{i:02d}.fasta"), "w") as f:
                f.write(f">g{i}\n")
                for o in range(0, len(seq), 80):
                    f.write(seq[o : o + 80] + "\n")
    mhu.sync_global_devices("ingest_fastas_ready")

    paths = sorted(glob.glob(os.path.join(fdir, "*.fasta")))
    assert len(paths) == INGEST_N
    bdb = make_bdb(paths)
    names = list(bdb["genome"])

    calls: list[str] = []
    orig = ingest_mod._sketch_one

    def counting(job):
        calls.append(job[0])
        return orig(job)

    ingest_mod._sketch_one = counting
    try:
        t0 = time.perf_counter()
        gs = sketch_genomes(bdb, wd=WorkDirectory(os.path.join(outdir, "ingest_wd")))
        dt = time.perf_counter() - t0
    finally:
        ingest_mod._sketch_one = orig

    # stripe-only work: exactly this process's interleave, nothing else
    assert calls == names[pid::nproc], (pid, nproc, calls)
    # full assembly on every process
    assert gs.names == names
    assert all(len(s) > 0 for s in gs.scaled) and all(len(b) > 0 for b in gs.bottom)
    digest = hashlib.sha256()
    for arr in (*gs.bottom, *gs.scaled):
        digest.update(np.ascontiguousarray(arr).tobytes())
    with open(os.path.join(outdir, f"ingest_digest_{pid}"), "w") as f:
        f.write(digest.hexdigest())
    agg = INGEST_N * INGEST_MB / dt
    print(
        f"ingest_sharded: pid {pid}/{nproc} sketched {len(calls)}/{INGEST_N} "
        f"genomes, wall {dt:.2f}s -> pod aggregate {agg:.1f} MB/s",
        flush=True,
    )
    mhu.sync_global_devices("ingest_done")


def _combo_shared_workdir(pid: int, nproc: int, outdir: str) -> None:
    """The production multi-host deployment shape (SURVEY.md §5.8): every
    process runs the streaming+greedy combo against ONE shared-filesystem
    workdir. Stripe ownership must interleave (each process owns >= 2 row
    blocks), the replicated table writes must coexist (atomic store_db),
    and a table-dropped re-run must resume from the shared shards without
    rewriting any of them."""
    from jax.experimental import multihost_utils as mhu

    from drep_tpu.parallel.streaming import stripe_owner

    n_blocks = -(-COMBO_N // COMBO_BLOCK)
    my_stripes = [
        bi for bi in range(n_blocks) if stripe_owner(bi, n_blocks, nproc) == pid
    ]
    assert len(my_stripes) >= 2, (
        f"pid {pid}/{nproc}: only {len(my_stripes)} stripes — the test is "
        "not exercising interleaved multi-stripe ownership"
    )

    wd_path = os.path.join(outdir, "combo_wd")
    cdb = run_combo_wrapper(wd_path)
    assert partition(cdb, "secondary_cluster") == truth_partition(), "combo clusters"

    shard_dir = os.path.join(wd_path, "data", "streaming_primary")
    shards = sorted(f for f in os.listdir(shard_dir) if f.startswith("row_"))
    assert len(shards) == n_blocks, (shards, n_blocks)
    mtimes = {f: os.stat(os.path.join(shard_dir, f)).st_mtime_ns for f in shards}

    # drop the assembled tables (kill between secondary and Cdb assembly);
    # shard-level state stays. pid 0 deletes, everyone re-runs after the
    # barrier — the resume must rebuild identical clusters from shards.
    mhu.sync_global_devices("combo_tables_drop")
    if pid == 0:
        for tbl in ("Cdb", "Ndb", "Mdb"):
            p = os.path.join(wd_path, "data_tables", f"{tbl}.csv")
            assert os.path.exists(p), f"workdir layout changed? missing {p}"
            os.remove(p)
    mhu.sync_global_devices("combo_resume")
    cdb2 = run_combo_wrapper(wd_path)
    assert partition(cdb2, "secondary_cluster") == truth_partition(), "resume clusters"
    mtimes2 = {f: os.stat(os.path.join(shard_dir, f)).st_mtime_ns for f in shards}
    assert mtimes == mtimes2, "resume rewrote streaming shards instead of loading them"
    mhu.sync_global_devices("combo_done")


if __name__ == "__main__":
    main()
