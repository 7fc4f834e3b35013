#!/usr/bin/env python
"""Executable survivability matrix: site x mode over fault-injection specs.

The README "Failure model" section claims a survivability verdict per
(injection site, failure mode) cell — this tool RUNS those cells and
prints a pass/fail grid, so the documented matrix can never silently
drift from what the code actually survives.

Two tiers:

- in-process cells (default): single-process scenarios over the real
  engines (streaming tiles, the step-wise dense ring, retrying secondary
  calls, torn shard writes) with ``utils/faults.py`` specs installed —
  seconds each, CPU-only, no pod required.
- pod cells (``--pod``): the multi-process kill/death cells (SIGKILL
  mid-streaming / mid-ring, pre-barrier death, dead-peer barrier
  diagnosis, mid-secondary-batch retry, post-bump shard corruption)
  delegate to their pytest chaos tests in tests/test_multihost.py —
  minutes, still CPU-only.
- storage cells (``--io``): the durable-I/O layer (ISSUE 5,
  utils/durableio.py) — transient EIO retries, post-write bit rot healed
  on resume, ENOSPC degrading into the actionable StoreFullError, and
  the scrub-then-resume loop (tools/scrub_store.py detects, ``--delete``
  quarantines, the next run recomputes) — seconds each, in-process.
- pruned-schedule cells (``--prune``): the LSH-banded candidate pruning
  (ISSUE 7, ops/lsh.py) — SIGKILL mid-pruned-run resuming bit-identical
  to the DENSE oracle (pytest-delegated), a banding-param mismatch on
  resume refusing with an actionable error (shards untouched), and
  ``io:corrupt`` bit rot on a pruned shard healing through the existing
  recompute path. CPU-only, seconds each.
- elastic membership cells (``--elastic``): the grow-and-drain half of
  the pod protocol (ISSUE 9) — a mid-run JOIN admitted into a streaming
  pod and into a stepwise ring (unfinished work re-dealt over the GROWN
  live set, final edges/matrix bit-identical), a graceful DRAIN
  mid-streaming (planned-departure note, immediate epoch bump — no
  staleness wait, exit 0), and a drain-then-join churn. Delegate to
  their pytest chaos tests (tests/test_elastic_updown.py), CPU-only.
- index cells (``--index``): the incremental service mode (ISSUE 6,
  drep_tpu/index/) — SIGKILL mid-``index update`` (pre-publish and
  mid-rect-compare) followed by a rerun converging on the uninterrupted
  result, and ``io:corrupt`` bit rot on index shards self-healing
  through recompute/re-sketch on the next update. Delegate to their
  pytest chaos tests (tests/test_index_chaos.py), CPU-only.
- federated-index cells (``--federated``): the range-partitioned
  federation (ISSUE 13, drep_tpu/index/federation.py) — SIGKILL
  mid-partition-update (a partition published ahead of the meta; the
  stale meta keeps readers at the old federation generation and the
  rerun converges byte-identical to an uninterrupted control) and
  SIGKILL mid-meta-publish (every partition ahead, the meta publish
  itself the only missing piece — readers still see the old union, the
  rerun recomputes the federation families deterministically and
  publishes). Delegate to tests/test_federation_chaos.py, CPU-only.
- federated-serving cells (``--serve-federated``): partition-scoped
  fault containment under the STREAMING federated serve path (ISSUE 14,
  index/federation.py FederatedResident) — corrupt one partition's
  manifest under a live daemon (daemon stays up, affected queries
  return stamped PARTIAL verdicts, strict clients are refused with
  retry_after, unaffected partitions' verdicts stay byte-identical,
  and after heal the next bounded-backoff probe restores full coverage
  with a ``partition_recovered`` trace event), and a deterministic
  ``partition_load`` fault mid-classify (same containment + recovery
  once the injected fires exhaust). Delegate to
  tests/test_fed_serve_chaos.py, CPU-only.
- serve cells (``--serve``): the resident serving tier (ISSUE 11,
  drep_tpu/serve/) — SIGKILL the `index serve` daemon mid-batch: every
  connected client gets a clean disconnection error (never a hang or a
  half-written line), a restarted daemon serves the SAME generation,
  and the index directory stays byte-for-byte untouched through kill
  and restart. Delegates to its pytest chaos test (tests/test_serve.py),
  CPU-only.
- event-tracing cells (``--events``): the observability layer (ISSUE 10,
  utils/telemetry.py + tools/trace_report.py) — the drain-mid-streaming
  and kill-mid-streaming pods re-run with ``DREP_TPU_EVENTS=on``,
  asserting the MERGED timeline holds the drain/death verdict, the
  epoch bump, and the re-deal spans in causal order, the Chrome trace
  loads, and the membership timeline equals every survivor's
  ``epoch_history`` exactly. Delegate to tests/test_trace_report.py,
  CPU-only.

- router cells (``--router``): the fleet front door (ISSUE 17,
  drep_tpu/serve/router.py) — SIGKILL a replica mid-scatter (the router
  survives, affected queries return stamped PARTIAL verdicts while
  unaffected legs stay byte-identical, a rejoined replica restores full
  coverage), a generation-TORN fan-out (replicas hot-swap to a new
  index generation while the router still routes the old one — the
  generation fence retries the gather once over a fenced reload and
  converges), and overload spill (a saturated replica's backpressure
  refusals spill the leg to honest PARTIAL degradation instead of
  queueing behind it). Delegate to tests/test_router_chaos.py, CPU-only.

- supervisor cells (``--supervisor``): the fleet supervisor's lifecycle
  contract (ISSUE 20, drep_tpu/serve/supervisor.py driving the
  ``supervisor_spawn``/``supervisor_tick`` fault sites) — SIGKILL the
  supervisor mid-spawn (its successor ADOPTS every still-live replica
  recorded in fleet.json, re-probes each over /healthz, and never
  double-spawns — verdicts stay byte-identical to the one-daemon
  oracle), a replica rigged to die at startup (QUARANTINED after
  exactly DREP_TPU_SUP_CRASHLOOP_K deaths inside the window; the fleet
  serves honest stamped PARTIAL over the missing coverage and strict
  clients are refused, never a hang), and a router restart (full
  membership rebuilt from the durable manifest with zero ``fleet``
  join replays, full-coverage verdicts oracle-identical). Delegate to
  tests/test_supervisor_chaos.py, CPU-only.

- wire cells (``--wire``): the serve tier's NDJSON wire itself
  (ISSUE 19, drep_tpu/serve/wirechaos.py driving the ``wire`` fault
  site) — a connection RESET mid-reply surfaces as an honest
  ``disconnected`` error (daemon clean, never a hang), a reply STALLED
  past the request's deadline budget ends in a clean stamped
  ``deadline_exceeded`` refusal, a GARBLED reply frame is detected by
  the per-line CRC and the retried verdict is byte-identical to a
  clean wire's, a DUPLICATED reply is merged exactly-once via the
  request-id echo, and a SHORT READ (EOF mid-frame) reports honestly.
  Delegate to tests/test_wire_chaos.py, CPU-only, seconds each.

- maintenance cells (``--maintenance``): the transactional index
  lifecycle (ISSUE 18, drep_tpu/index/maintenance.py) — SIGKILL the
  real `index split` / `index merge` / `index compact` CLI at EVERY
  phase boundary of the staged meta-manifest transaction (STAGED /
  PRE-COMMIT / PRE-GC, via the deterministic ``partition_split`` and
  ``compaction`` fault sites): pre-commit kills leave the old meta
  fully live, post-commit kills roll forward, and a rerun converges
  byte-identical to an uninterrupted control. Plus the gc-honesty cell
  (a corrupt superseded shard is deleted without being read and the
  fold's heal tally is never double-counted), the record-less
  compaction adoption cell, and the live-traffic cell (a split commits
  under a replica+router as an ordinary hot-swap with zero daemon
  exceptions). Delegate to tests/test_maintenance_chaos.py, CPU-only.

- autoscaling cells (``--autoscale``): the deadline-driven controller
  (ISSUE 15, drep_tpu/autoscale/ + tools/pod_autoscale.py) — a real pod
  under ``--deadline`` pressure gains a CONTROLLER-spawned joiner
  mid-run (edges bit-identical, ``autoscale_decision`` instants merged
  into the trace, churn provenance booked by every member), and the
  ring-phase JOIN upgrade at D=3 (the pod keeps its collective step
  schedule; the joiner consumes the step tail) pins bit-identity
  against the monolithic fixed-membership reference. Delegate to
  tests/test_autoscale_chaos.py, CPU-only.

Usage::

    JAX_PLATFORMS=cpu python tools/chaos_matrix.py           # in-process grid
    JAX_PLATFORMS=cpu python tools/chaos_matrix.py --io      # + storage cells
    JAX_PLATFORMS=cpu python tools/chaos_matrix.py --index   # + index cells
    JAX_PLATFORMS=cpu python tools/chaos_matrix.py --federated # + federation cells
    JAX_PLATFORMS=cpu python tools/chaos_matrix.py --elastic # + join/drain cells
    JAX_PLATFORMS=cpu python tools/chaos_matrix.py --serve   # + serving-tier cells
    JAX_PLATFORMS=cpu python tools/chaos_matrix.py --serve-federated # + partition containment
    JAX_PLATFORMS=cpu python tools/chaos_matrix.py --events  # + traced-pod cells
    JAX_PLATFORMS=cpu python tools/chaos_matrix.py --autoscale # + controller cells
    JAX_PLATFORMS=cpu python tools/chaos_matrix.py --router  # + fleet front-door cells
    JAX_PLATFORMS=cpu python tools/chaos_matrix.py --supervisor # + fleet lifecycle cells
    JAX_PLATFORMS=cpu python tools/chaos_matrix.py --wire    # + wire-damage cells
    JAX_PLATFORMS=cpu python tools/chaos_matrix.py --maintenance # + index lifecycle cells
    JAX_PLATFORMS=cpu python tools/chaos_matrix.py --pod     # + pod cells
"""

from __future__ import annotations

import os
import subprocess
import sys

os.environ.setdefault("JAX_PLATFORMS", "cpu")
flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (
        flags + " --xla_force_host_platform_device_count=8"
    ).strip()

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)


def _packed(n=48, s=64, seed=0):
    import numpy as np

    from drep_tpu.ops.minhash import PAD_ID, PackedSketches

    rng = np.random.default_rng(seed)
    ids = np.full((n, s), PAD_ID, dtype=np.int32)
    cts = np.full(n, s, dtype=np.int32)
    pools = [
        np.sort(rng.choice(2**20, size=s * 2, replace=False).astype(np.int32))
        for _ in range(5)
    ]
    for i in range(n):
        ids[i] = np.sort(rng.choice(pools[i % 5], size=s, replace=False))
    return PackedSketches(ids=ids, counts=cts, names=[f"g{i}" for i in range(n)])


def _streaming(spec, ft_config=None, checkpoint_dir=None):
    from drep_tpu.parallel.streaming import streaming_mash_edges
    from drep_tpu.utils import faults

    packed = _packed()
    want = streaming_mash_edges(packed, k=21, cutoff=0.2, block=8)
    faults.configure(spec)
    try:
        got = streaming_mash_edges(
            packed, k=21, cutoff=0.2, block=8,
            ft_config=ft_config, checkpoint_dir=checkpoint_dir,
        )
    finally:
        faults.configure(None)
    assert all(
        a.tobytes() == b.tobytes() for a, b in zip(got[:3], want[:3])
    ), "edges differ under injection"


def _ring(spec, ft_config=None):
    from drep_tpu.parallel.allpairs import sharded_mash_allpairs
    from drep_tpu.parallel.mesh import make_mesh
    from drep_tpu.utils import faults

    packed = _packed(n=21)
    mesh = make_mesh(3)
    want = sharded_mash_allpairs(packed, k=21, mesh=mesh)
    faults.configure(spec)
    try:
        got = sharded_mash_allpairs(packed, k=21, mesh=mesh, ft_config=ft_config)
    finally:
        faults.configure(None)
    assert got.tobytes() == want.tobytes(), "ring matrix differs under injection"


def _torn_shard(spec):
    import tempfile

    from drep_tpu.parallel.streaming import streaming_mash_edges
    from drep_tpu.utils import faults

    packed = _packed()
    with tempfile.TemporaryDirectory() as td:
        ckpt = os.path.join(td, "ckpt")
        faults.configure(spec)
        try:
            r1 = streaming_mash_edges(packed, k=21, cutoff=0.2, block=8, checkpoint_dir=ckpt)
        finally:
            faults.configure(None)
        r2 = streaming_mash_edges(packed, k=21, cutoff=0.2, block=8, checkpoint_dir=ckpt)
        assert all(a.tobytes() == b.tobytes() for a, b in zip(r1[:3], r2[:3]))


def _secondary_retry(spec, retries=2):
    from drep_tpu.parallel.faulttol import FaultTolConfig, retrying_call
    from drep_tpu.utils import faults

    faults.configure(spec)
    try:
        out = retrying_call(
            lambda: 42, site="secondary_batch",
            config=FaultTolConfig(max_retries=retries, backoff_s=0.0),
        )
    finally:
        faults.configure(None)
    assert out == 42


def _ft(**kw):
    from drep_tpu.parallel.faulttol import FaultTolConfig

    return FaultTolConfig(**kw)


# (site, mode, scenario label, expected, runner) — expected "survive"
# means the cell must complete with results identical to a clean run;
# "abort" means it must raise (loudly, with the documented error type)
def _cells():
    from drep_tpu.parallel.faulttol import FaultTolError

    return [
        ("streaming_tile", "raise", "5% tile failures -> retries",
         "survive", lambda: _streaming("streaming_tile:raise:0.05:seed=7")),
        ("streaming_tile", "raise", "one dead device -> quarantine",
         "survive", lambda: _streaming("streaming_tile:raise:1.0:device=1")),
        ("streaming_tile", "raise", "all devices failing -> CPU fallback",
         "survive", lambda: _streaming(
             "streaming_tile:raise:1.0", _ft(max_retries=1, backoff_s=0.0))),
        ("streaming_tile", "hang", "wedged dispatch -> watchdog retry",
         "survive", lambda: _streaming(
             "streaming_tile:hang:1.0:device=2:secs=30",
             _ft(dispatch_timeout_s=0.5))),
        ("shard_write", "torn", "truncated shard -> resume heals",
         "survive", lambda: _torn_shard("shard_write:torn:1.0:max=2")),
        ("ring_dispatch", "raise", "failed ring step -> per-block recovery",
         "survive", lambda: _ring("ring_dispatch:raise:1.0:max=1")),
        ("ring_dispatch", "hang", "wedged ring step -> watchdog + recovery",
         "survive", lambda: _ring(
             "ring_dispatch:hang:1.0:max=1:secs=30", _ft(dispatch_timeout_s=0.5))),
        ("secondary_batch", "raise", "one failed batch -> local retry",
         "survive", lambda: _secondary_retry("secondary_batch:raise:1.0:max=1")),
        ("secondary_batch", "raise", "beyond retry budget -> abort",
         "abort", lambda: _expect_raise(
             FaultTolError,
             lambda: _secondary_retry("secondary_batch:raise:1.0", retries=1))),
    ]


def _expect_raise(exc_type, fn):
    try:
        fn()
    except exc_type:
        return
    raise AssertionError(f"expected {exc_type.__name__}, nothing raised")


# --- storage cells (--io): the durable-I/O layer, ISSUE 5 -----------------


def _streaming_ckpt(spec, td):
    """Clean oracle vs (injected run -> clean resume) over a shard store;
    both runs' edges must match the oracle bit-for-bit."""
    import os as _os

    from drep_tpu.parallel.streaming import streaming_mash_edges
    from drep_tpu.utils import faults

    packed = _packed()
    want = streaming_mash_edges(packed, k=21, cutoff=0.2, block=8)
    ckpt = _os.path.join(td, "ckpt")
    faults.configure(spec)
    try:
        r1 = streaming_mash_edges(packed, k=21, cutoff=0.2, block=8, checkpoint_dir=ckpt)
    finally:
        faults.configure(None)
    assert all(a.tobytes() == b.tobytes() for a, b in zip(r1[:3], want[:3]))
    r2 = streaming_mash_edges(packed, k=21, cutoff=0.2, block=8, checkpoint_dir=ckpt)
    assert all(a.tobytes() == b.tobytes() for a, b in zip(r2[:3], want[:3]))
    return ckpt


def _io_transient(spec):
    import tempfile

    from drep_tpu.utils.profiling import counters as _c

    with tempfile.TemporaryDirectory() as td:
        _streaming_ckpt(spec, td)
        assert _c.faults.get("io_retries", 0) >= 1, _c.faults


def _io_corrupt(spec):
    import tempfile

    from drep_tpu.utils.profiling import counters as _c

    with tempfile.TemporaryDirectory() as td:
        # run 1 publishes one bit-rotted shard; the resume must detect it
        # via the in-band checksum, recompute it, and heal the store
        _streaming_ckpt(spec, td)
        assert _c.faults.get("corrupt_shards_healed", 0) >= 1, _c.faults


def _io_enospc(spec):
    import tempfile

    from drep_tpu.parallel.streaming import streaming_mash_edges
    from drep_tpu.utils import faults
    from drep_tpu.utils.durableio import StoreFullError

    with tempfile.TemporaryDirectory() as td:
        faults.configure(spec)
        try:
            streaming_mash_edges(
                _packed(), k=21, cutoff=0.2, block=8,
                checkpoint_dir=os.path.join(td, "ckpt"),
            )
        except StoreFullError as e:
            assert "ENOSPC" in str(e) and td in str(e), e
            return
        finally:
            faults.configure(None)
    raise AssertionError("expected StoreFullError, nothing raised")


def _scrub_then_resume():
    import importlib.util
    import tempfile

    from drep_tpu.parallel.streaming import streaming_mash_edges

    spec = importlib.util.spec_from_file_location(
        "scrub_store", os.path.join(REPO, "tools", "scrub_store.py")
    )
    ss = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(ss)
    with tempfile.TemporaryDirectory() as td:
        packed = _packed()
        ckpt = os.path.join(td, "ckpt")
        want = streaming_mash_edges(packed, k=21, cutoff=0.2, block=8, checkpoint_dir=ckpt)
        assert not ss.scrub([ckpt])["damaged"], "clean store reported damaged"
        shard = sorted(f for f in os.listdir(ckpt) if f.startswith("row_"))[1]
        loc = os.path.join(ckpt, shard)
        data = open(loc, "rb").read()
        # drep-lint: allow[durable-funnel] — deliberate chaos: plants the torn shard the scrubber cell must detect
        with open(loc, "wb") as f:
            f.write(data[: len(data) // 2])
        assert ss.scrub([ckpt])["damaged"], "scrub missed a truncated shard"
        ss.scrub([ckpt], delete=True)
        assert not os.path.exists(loc)
        got = streaming_mash_edges(packed, k=21, cutoff=0.2, block=8, checkpoint_dir=ckpt)
        assert all(a.tobytes() == b.tobytes() for a, b in zip(got[:3], want[:3]))
        assert os.path.exists(loc), "resume did not heal the deleted shard"


# (site, mode, scenario, expected, runner) — appended under --io
def _io_cells():
    return [
        ("io", "io_error", "transient EIO on shard write -> retries",
         "survive", lambda: _io_transient("io:io_error:1.0:max=2")),
        ("io", "stale_read", "transient ESTALE on read -> retries",
         "survive", lambda: _io_transient("io:stale_read:1.0:max=1")),
        ("io", "corrupt", "bit-rot after publish -> checksum heal on resume",
         "survive", lambda: _io_corrupt("io:corrupt:1.0:max=1")),
        ("io", "enospc", "filesystem full -> actionable StoreFullError",
         "abort", lambda: _io_enospc("io:enospc:1.0")),
        ("io", "scrub", "scrub detects damage; --delete + resume heals",
         "survive", _scrub_then_resume),
    ]


# --- pruned-schedule cells (--prune): ISSUE 7 --------------------------


def _prune_packed(n=48, s=64, seed=0):
    """Group-CONTIGUOUS clusterable sketches — the layout where the LSH
    candidate bitmap actually skips tiles (the shared planting recipe,
    utils/synth.py)."""
    from drep_tpu.utils.synth import planted_group_sketches

    return planted_group_sketches(n=n, s=s, groups=5, seed=seed)


def _prune_mismatch_refuses():
    """Changed banding params on resume must refuse with the actionable
    error — never silently clear or mix shards."""
    import tempfile

    from drep_tpu.errors import UserInputError
    from drep_tpu.ops.lsh import build_candidates
    from drep_tpu.parallel.streaming import streaming_mash_edges

    packed = _prune_packed()
    with tempfile.TemporaryDirectory() as td:
        ckpt = os.path.join(td, "ckpt")
        cand = build_candidates(packed, keep=0.2, k=21)
        streaming_mash_edges(
            packed, k=21, cutoff=0.2, block=8, checkpoint_dir=ckpt, prune=cand
        )
        shards = sorted(f for f in os.listdir(ckpt) if f.endswith(".npz"))
        cand16 = build_candidates(packed, keep=0.2, k=21, bands=16)
        _expect_raise(
            UserInputError,
            lambda: streaming_mash_edges(
                packed, k=21, cutoff=0.2, block=8, checkpoint_dir=ckpt, prune=cand16
            ),
        )
        assert sorted(
            f for f in os.listdir(ckpt) if f.endswith(".npz")
        ) == shards, "refusal cleared shards"


def _prune_corrupt_heals(spec):
    """io:corrupt bit rot on a PRUNED run's shard: the resume must heal
    it through the existing recompute path, with edges bit-equal to the
    dense oracle."""
    import tempfile

    from drep_tpu.ops.lsh import build_candidates
    from drep_tpu.parallel.streaming import streaming_mash_edges
    from drep_tpu.utils import faults
    from drep_tpu.utils.profiling import counters as _c

    packed = _prune_packed()
    want = streaming_mash_edges(packed, k=21, cutoff=0.2, block=8)
    cand = build_candidates(packed, keep=0.2, k=21)
    with tempfile.TemporaryDirectory() as td:
        ckpt = os.path.join(td, "ckpt")
        faults.configure(spec)
        try:
            streaming_mash_edges(
                packed, k=21, cutoff=0.2, block=8, checkpoint_dir=ckpt, prune=cand
            )
        finally:
            faults.configure(None)
        got = streaming_mash_edges(
            packed, k=21, cutoff=0.2, block=8, checkpoint_dir=ckpt, prune=cand
        )
        assert all(
            a.tobytes() == b.tobytes() for a, b in zip(got[:3], want[:3])
        ), "healed pruned edges differ from the dense oracle"
        assert _c.faults.get("corrupt_shards_healed", 0) >= 1, _c.faults


def _prune_cells():
    return [
        ("prune_meta", "mismatch", "banding params changed on resume -> refuse",
         "abort", _prune_mismatch_refuses),
        ("io", "corrupt", "bit-rot on a pruned shard -> heal, dense-equal",
         "survive", lambda: _prune_corrupt_heals("io:corrupt:1.0:max=1:path=row_")),
    ]


# the SIGKILL cell needs a subprocess victim — delegate to its pytest test
PRUNE_PYTEST_CELLS = [
    ("process_death", "kill", "SIGKILL mid-pruned-run -> resume bit-identical to dense",
     "survive", "tests/test_chaos.py::test_sigkill_mid_pruned_streaming_resumes_bit_identical"),
]


# index cells (--index): the incremental service mode's crash/rot story
# (ISSUE 6). Both delegate to their pytest chaos tests — the SIGKILL cell
# needs a subprocess victim, and the corrupt cell shares its oracle
# machinery — CPU-only, seconds-to-minutes.
INDEX_CELLS = [
    ("index_update", "kill", "SIGKILL before manifest publish -> rerun converges",
     "survive", "tests/test_index_chaos.py::test_sigkill_mid_update_rerun_is_identical"),
    ("index_update", "kill", "SIGKILL mid rect-compare -> pending shards resume",
     "survive", "tests/test_index_chaos.py::test_sigkill_mid_rect_compare_resumes"),
    ("io", "corrupt", "bit-rot on an index edge shard -> update heals via recompute",
     "survive", "tests/test_index_chaos.py::test_corrupt_edge_shard_heals_on_update"),
    ("io", "corrupt", "bit-rot on an index sketch shard -> update re-sketches",
     "survive", "tests/test_index_chaos.py::test_corrupt_sketch_shard_heals_on_update"),
]


# federated-index cells (--federated, ISSUE 13): the range-partitioned
# federation's crash story. Kill cells need a subprocess victim (the
# real CLI on a federated root) — delegate to their pytest chaos tests.
FED_CELLS = [
    ("partition_update", "kill", "SIGKILL mid-partition-update -> stale meta hides it; rerun converges",
     "survive", "tests/test_federation_chaos.py::test_sigkill_mid_partition_update_rerun_converges"),
    ("meta_publish", "kill", "SIGKILL mid-meta-publish -> old generation served; rerun converges",
     "survive", "tests/test_federation_chaos.py::test_sigkill_mid_meta_publish_resumes"),
    ("partition_update", "raise", "one partition fails -> honest partial meta publish",
     "survive", "tests/test_federation_chaos.py::test_partition_failure_publishes_honest_partial"),
    ("partition_load", "damage", "quarantined partition at update time -> degraded meta "
     "(partitions_unavailable stamped, old generation retained), heal pass clears",
     "survive", "tests/test_federation.py::test_partial_update_contract_with_unavailable_partition"),
]


# autoscaling cells (--autoscale, ISSUE 15): a REAL pod governed from
# outside by tools/pod_autoscale.py — the controller watches the
# checkpoint dir read-only, decides against --deadline, and actuates
# purely through the pod protocol (DREP_TPU_POD_JOIN=auto spawns,
# SIGTERM drains). Both delegate to multi-process pytest chaos cells.
AUTOSCALE_CELLS = [
    ("autoscale_decide", "scale_up",
     "deadline pressure -> controller-spawned joiner admitted mid-run, "
     "edges bit-identical, decisions in the merged trace",
     "survive",
     "tests/test_autoscale_chaos.py::test_controller_spawned_joiner_meets_deadline_bit_identical"),
    ("autoscale_decide", "join",
     "ring-phase JOIN at D=3 -> pod keeps its collective schedule, joiner "
     "consumes step tail, bit-identical to the monolithic reference",
     "survive",
     "tests/test_autoscale_chaos.py::test_ring_phase_join_tail_participation_d3_bit_identical"),
]


# elastic membership-churn cells (--elastic, ISSUE 9): the grow-and-drain
# half of the pod protocol. All four delegate to their multi-process
# pytest chaos tests (tests/test_elastic_updown.py — each needs a real
# jax.distributed CPU pod plus, for the join cells, a separate
# single-process joiner), CPU-only, tens of seconds each.
ELASTIC_CELLS = [
    ("pod_join", "join", "mid-streaming JOIN -> grown-set re-deal, bit-identical",
     "survive", "tests/test_elastic_updown.py::test_join_mid_streaming_bit_identical"),
    ("pod_join", "join", "mid-ring JOIN -> per-block re-deal over grown set",
     "survive", "tests/test_elastic_updown.py::test_join_mid_ring_bit_identical"),
    ("pod_drain", "drain", "DRAIN mid-streaming -> immediate re-deal, exit 0",
     "survive", "tests/test_elastic_updown.py::test_drain_mid_streaming_bit_identical"),
    ("pod_churn", "drain+join", "drain THEN join churn -> bit-identical",
     "survive", "tests/test_elastic_updown.py::test_drain_then_join_churn_bit_identical"),
]


# federated-serving cells (--serve-federated, ISSUE 14): partition
# fault containment under streaming per-partition classify. Both need a
# subprocess daemon with live clients + events on — delegate to their
# pytest chaos cells. CPU-only, tens of seconds.
FED_SERVE_CELLS = [
    ("partition_load", "corrupt",
     "corrupt partition manifest under serve -> daemon up, PARTIAL stamped, "
     "strict refused, heal+probe recovers (partition_recovered traced)",
     "survive",
     "tests/test_fed_serve_chaos.py::test_corrupt_partition_manifest_under_serve"),
    ("partition_load", "raise",
     "injected partition-load failure mid-classify -> containment, then "
     "probe recovery once fires exhaust",
     "survive",
     "tests/test_fed_serve_chaos.py::test_partition_load_fault_injection_under_serve"),
    ("partition_classify", "raise",
     "in-process mid-compare partition failure -> suspect/quarantine, "
     "PARTIAL verdict, unaffected partitions byte-identical",
     "survive",
     "tests/test_fed_serve.py::test_partition_fault_containment_partial_verdict"),
]


# router cells (--router, ISSUE 17): the fleet front door's containment
# story. Every cell needs subprocess replicas behind a subprocess router
# with live clients — delegate to their pytest chaos tests. CPU-only,
# tens of seconds each.
ROUTER_CELLS = [
    ("router_leg", "kill",
     "SIGKILL replica mid-scatter -> router up, PARTIAL stamped, unaffected "
     "legs byte-identical; rejoin restores full coverage",
     "survive",
     "tests/test_router_chaos.py::test_sigkill_replica_mid_scatter_partial_contained"),
    ("router_leg", "torn",
     "generation-TORN fan-out (replicas swap ahead of the router) -> "
     "fenced gather retry converges on the new generation",
     "survive",
     "tests/test_router_chaos.py::test_generation_torn_fanout_fence_converges"),
    ("router_leg", "overload",
     "saturated replica's backpressure -> leg spills to PARTIAL, never "
     "queues behind it",
     "survive",
     "tests/test_router_chaos.py::test_overload_spill_under_saturated_replica"),
    ("router_front", "kill",
     "SIGKILL one of two routers fronting the same fleet mid-scatter -> "
     "clean client disconnection, survivor serves oracle verdicts, "
     "replicas untouched",
     "survive",
     "tests/test_router_chaos.py::test_router_ha_handoff_survivor_serves_through_sigkill"),
    ("fleet_join", "prewarm",
     "join with assigned partitions -> prewarm lands before the ack "
     "(loads==1), first scatter leg adds no cold load",
     "survive",
     "tests/test_router_chaos.py::test_fleet_join_prewarm_no_cold_load_spike"),
]


# supervisor cells (--supervisor, ISSUE 20): the fleet supervisor's
# lifecycle contract — durable membership, crash-loop quarantine, and
# orphan adoption. Every cell runs real `index supervise`/`index route`
# subprocesses against a shared federation and ends in byte-identical
# verdicts vs the one-daemon oracle — delegate to their pytest chaos
# tests. CPU-only, tens of seconds each.
SUPERVISOR_CELLS = [
    ("supervisor_spawn", "kill",
     "SIGKILL supervisor mid-spawn -> successor ADOPTS every still-live "
     "replica from fleet.json, zero duplicate spawns, verdicts oracle-"
     "identical",
     "survive",
     "tests/test_supervisor_chaos.py::test_sigkill_supervisor_midspawn_successor_adopts"),
    ("supervisor_tick", "kill",
     "replica rigged to die at startup -> QUARANTINED after exactly "
     "CRASHLOOP_K deaths, fleet serves stamped PARTIAL (strict refused), "
     "never hangs",
     "survive",
     "tests/test_supervisor_chaos.py::test_crashloop_replica_quarantined_partial_served"),
    ("supervisor_tick", "raise",
     "router restart -> full membership rebuilt from fleet.json with "
     "zero fleet-join replays, full-coverage verdicts oracle-identical",
     "survive",
     "tests/test_supervisor_chaos.py::test_router_restart_rebuilds_membership_from_manifest"),
]


# wire cells (--wire, ISSUE 19): the NDJSON wire under the chaos proxy.
# Every cell needs a subprocess daemon behind an in-process WireChaos
# proxy with a fault spec installed — delegate to their pytest tests.
# CPU-only, seconds each.
WIRE_CELLS = [
    ("wire", "reset",
     "connection RST mid-reply -> honest disconnected error, daemon clean",
     "survive", "tests/test_wire_chaos.py::test_wire_reset_mid_reply_clean_error"),
    ("wire", "stall",
     "reply stalled past the deadline budget -> clean stamped "
     "deadline_exceeded refusal, never a hang",
     "survive", "tests/test_wire_chaos.py::test_wire_stall_past_budget_deadline_refusal"),
    ("wire", "garble",
     "garbled reply frame -> CRC detects, retried verdict byte-identical",
     "survive", "tests/test_wire_chaos.py::test_wire_garble_detected_and_retried"),
    ("wire", "dup",
     "duplicated reply frame -> request-id echo merges exactly-once",
     "survive", "tests/test_wire_chaos.py::test_wire_dup_reply_exactly_once"),
    ("wire", "short_read",
     "truncated reply then EOF -> honest error, never a partial merge",
     "survive", "tests/test_wire_chaos.py::test_wire_short_read_honest_error"),
]


# maintenance cells (--maintenance, ISSUE 18): the transactional index
# lifecycle — split/merge/compaction as staged meta-manifest
# transactions. Every kill cell runs the real CLI as a subprocess
# victim with a deterministic fault spec (partition_split / compaction
# fired at skip=0 STAGED, skip=1 PRE-COMMIT, skip=2 PRE-GC) and pins
# rerun convergence byte-identical to an uninterrupted control.
# CPU-only, seconds to tens of seconds each.
MAINTENANCE_CELLS = [
    ("partition_split", "kill",
     "SIGKILL `index split` STAGED -> old meta live, rerun converges",
     "survive",
     "tests/test_maintenance_chaos.py::test_sigkill_split_rerun_converges[staged]"),
    ("partition_split", "kill",
     "SIGKILL `index split` PRE-COMMIT -> old meta live, rerun converges",
     "survive",
     "tests/test_maintenance_chaos.py::test_sigkill_split_rerun_converges[precommit]"),
    ("partition_split", "kill",
     "SIGKILL `index split` PRE-GC -> committed, roll-forward finishes gc",
     "survive",
     "tests/test_maintenance_chaos.py::test_sigkill_split_rerun_converges[pregc]"),
    ("partition_split", "kill",
     "SIGKILL `index merge` STAGED -> old meta live, rerun converges",
     "survive",
     "tests/test_maintenance_chaos.py::test_sigkill_merge_rerun_converges[staged]"),
    ("partition_split", "kill",
     "SIGKILL `index merge` PRE-COMMIT -> old meta live, rerun converges",
     "survive",
     "tests/test_maintenance_chaos.py::test_sigkill_merge_rerun_converges[precommit]"),
    ("partition_split", "kill",
     "SIGKILL `index merge` PRE-GC -> committed, roll-forward finishes gc",
     "survive",
     "tests/test_maintenance_chaos.py::test_sigkill_merge_rerun_converges[pregc]"),
    ("compaction", "kill",
     "SIGKILL `index compact` STAGED -> folded shards invisible, rerun converges",
     "survive",
     "tests/test_maintenance_chaos.py::test_sigkill_compact_rerun_converges[staged]"),
    ("compaction", "kill",
     "SIGKILL `index compact` PRE-COMMIT (manifests ahead-by-one) -> "
     "roll-forward completes the commit",
     "survive",
     "tests/test_maintenance_chaos.py::test_sigkill_compact_rerun_converges[precommit]"),
    ("compaction", "kill",
     "SIGKILL `index compact` PRE-GC -> committed, gc resumes idempotently",
     "survive",
     "tests/test_maintenance_chaos.py::test_sigkill_compact_rerun_converges[pregc]"),
    ("compaction", "kill",
     "transaction record LOST after pre-commit kill -> ahead-by-one "
     "unchanged-n partitions adopted, meta republished",
     "survive",
     "tests/test_maintenance_chaos.py::test_recordless_compaction_interrupt_adopted"),
    ("compaction", "corrupt",
     "corrupt superseded shard after pre-gc kill -> gc deletes without "
     "reading, heal tally never double-counted",
     "survive",
     "tests/test_maintenance_chaos.py::test_compaction_gc_honesty_no_reread_no_double_heal"),
    ("partition_split", "live",
     "split commits under replica+router traffic -> ordinary hot-swap, "
     "zero daemon exceptions, post-split oracle verdicts",
     "survive",
     "tests/test_maintenance_chaos.py::test_split_under_live_router_traffic"),
]


# serve cells (--serve, ISSUE 11): the resident serving tier's crash
# story. SIGKILL needs a subprocess daemon + live clients — delegate to
# the pytest chaos cell. CPU-only, tens of seconds.
SERVE_CELLS = [
    ("serve", "kill", "SIGKILL daemon mid-batch -> clean client error; restart serves same generation, index untouched",
     "survive", "tests/test_serve.py::test_sigkill_daemon_clean_error_restart_same_generation"),
    ("serve", "drain", "SIGTERM mid-traffic -> in-flight answered, admissions refused, exit 0",
     "survive", "tests/test_serve.py::test_daemon_sigterm_drains_cleanly"),
]


# event-tracing cells (--events, ISSUE 10): the elastic drain/death pods
# re-run with DREP_TPU_EVENTS=on; the tests merge every member's event
# log (tools/trace_report.py), pin the causal order (drain note -> epoch
# bump -> re-deal spans; death verdict -> epoch bump), require a loadable
# Chrome trace, and check the membership timeline against epoch_history.
EVENTS_CELLS = [
    ("events", "drain", "drain mid-streaming, events on -> causal merged timeline",
     "survive", "tests/test_trace_report.py::test_drain_pod_events_timeline_causal"),
    ("events", "kill", "SIGKILL mid-streaming, events on -> verdict timeline + crash evidence",
     "survive", "tests/test_trace_report.py::test_death_pod_events_timeline"),
]


# pod cells delegate to the pytest chaos tests (site x mode -> test id)
POD_CELLS = [
    ("process_death", "kill", "SIGKILL mid-streaming -> epoch re-deal",
     "survive", "tests/test_multihost.py::test_elastic_pod_survives_sigkilled_member"),
    ("ring_step", "kill", "SIGKILL between ring steps -> block re-deal",
     "survive", "tests/test_multihost.py::test_elastic_ring_survives_sigkilled_member"),
    ("barrier", "death", "death BEFORE the stage-open barrier -> admission",
     "survive", "tests/test_multihost.py::test_streaming_prebarrier_death_continues_degraded"),
    ("secondary_batch", "raise", "mid-batch failure on a pod -> local retry",
     "survive", "tests/test_multihost.py::test_secondary_batch_retries_locally_on_pod"),
    ("barrier", "death", "dead peer, NO heartbeats -> named diagnosis + abort",
     "abort", "tests/test_multihost.py::test_dead_peer_barrier_raises_actionable_timeout"),
    ("io", "corrupt", "survivor shard bit-rotted after epoch bump -> peer heals",
     "survive", "tests/test_multihost.py::test_elastic_pod_heals_corrupt_shard_after_epoch_bump"),
]


def main() -> int:
    pod = "--pod" in sys.argv
    io_cells = "--io" in sys.argv
    index_cells = "--index" in sys.argv
    federated_cells = "--federated" in sys.argv
    prune_cells = "--prune" in sys.argv
    elastic_cells = "--elastic" in sys.argv
    serve_cells = "--serve" in sys.argv
    fed_serve_cells = "--serve-federated" in sys.argv
    router_cells = "--router" in sys.argv
    supervisor_cells = "--supervisor" in sys.argv
    wire_cells = "--wire" in sys.argv
    events_cells = "--events" in sys.argv
    autoscale_cells = "--autoscale" in sys.argv
    maintenance_cells = "--maintenance" in sys.argv
    from drep_tpu.parallel import faulttol
    from drep_tpu.utils.profiling import counters

    cells = _cells()
    if io_cells:
        cells += _io_cells()
    if prune_cells:
        cells += _prune_cells()
    rows = []
    failures = 0
    for site, mode, label, expected, run in cells:
        counters.reset()
        faulttol.reset_pod()
        try:
            run()
            verdict = "PASS"
        except Exception as e:  # noqa: BLE001 — the grid reports, never dies
            verdict = f"FAIL ({type(e).__name__}: {e})"
            failures += 1
        rows.append((site, mode, label, expected, verdict))

    def _pytest_cells(cell_list, flag: str, enabled: bool) -> None:
        nonlocal failures
        if not enabled:
            for site, mode, label, expected, test_id in cell_list:
                rows.append((site, mode, label, expected, f"SKIP ({flag} runs {test_id})"))
            return
        env = dict(os.environ)
        env["JAX_PLATFORMS"] = "cpu"
        for site, mode, label, expected, test_id in cell_list:
            rc = subprocess.call(
                [sys.executable, "-m", "pytest", test_id, "-q", "-p", "no:cacheprovider"],
                cwd=REPO, env=env,
                stdout=subprocess.DEVNULL, stderr=subprocess.DEVNULL,
            )
            verdict = "PASS" if rc == 0 else f"FAIL (pytest rc={rc})"
            failures += rc != 0
            rows.append((site, mode, label, expected, verdict))

    _pytest_cells(PRUNE_PYTEST_CELLS, "--prune", prune_cells)
    _pytest_cells(INDEX_CELLS, "--index", index_cells)
    _pytest_cells(FED_CELLS, "--federated", federated_cells)
    _pytest_cells(ELASTIC_CELLS, "--elastic", elastic_cells)
    _pytest_cells(SERVE_CELLS, "--serve", serve_cells)
    _pytest_cells(FED_SERVE_CELLS, "--serve-federated", fed_serve_cells)
    _pytest_cells(ROUTER_CELLS, "--router", router_cells)
    _pytest_cells(SUPERVISOR_CELLS, "--supervisor", supervisor_cells)
    _pytest_cells(WIRE_CELLS, "--wire", wire_cells)
    _pytest_cells(MAINTENANCE_CELLS, "--maintenance", maintenance_cells)
    _pytest_cells(EVENTS_CELLS, "--events", events_cells)
    _pytest_cells(AUTOSCALE_CELLS, "--autoscale", autoscale_cells)
    _pytest_cells(POD_CELLS, "--pod", pod)

    w_site = max(len(r[0]) for r in rows)
    w_mode = max(len(r[1]) for r in rows)
    w_label = max(len(r[2]) for r in rows)
    print(f"{'site':<{w_site}}  {'mode':<{w_mode}}  {'scenario':<{w_label}}  expected  verdict")
    print("-" * (w_site + w_mode + w_label + 24))
    for site, mode, label, expected, verdict in rows:
        print(f"{site:<{w_site}}  {mode:<{w_mode}}  {label:<{w_label}}  {expected:<8}  {verdict}")
    print(
        f"\n{sum(1 for r in rows if r[4] == 'PASS')} passed, {failures} failed, "
        f"{sum(1 for r in rows if r[4].startswith('SKIP'))} skipped"
    )
    return 1 if failures else 0


if __name__ == "__main__":
    sys.exit(main())
