"""Built-in comparison engines registered with the dispatch.

`jax_mash` / `jax_ani` are the TPU-native paths (BASELINE.json north star);
`mash` / `fastANI` subprocess fallbacks live in cluster/external.py and are
registered lazily there.

Both engines pick their execution layout automatically: single-device tiled
loops on one chip, ring-sharded ``shard_map`` all-pairs (parallel/allpairs)
when the mesh has more than one device and the problem is big enough to
amortize the collectives. The primary computes ONE estimator on every
layout (union-bottom-s, the reference Mash): the layouts differ in
execution, and `dense_primary_route` names the one a run takes.

Every dense path is TRIANGLE-ONLY (ISSUE 1): Mash distance and the raw
MinHash/FracMinHash intersection size are symmetric, so each engine
computes only the canonical upper-triangle pair tiles (single chip: blocked
(bi <= bj) schedules or the wrapped symmetric Pallas grids; mesh: the
half-ring, parallel/allpairs.py) and mirrors the transposed blocks on host
— ~2x genome-pairs/sec/chip on the same hardware. The schedules record
``tiles_computed / tiles_total`` into utils/profiling counters so the
triangular engagement is observable in perf_counters.json.
"""

from __future__ import annotations

import numpy as np

from drep_tpu.cluster.dispatch import (
    register_primary,
    register_secondary,
    register_secondary_batched,
)
from drep_tpu.ingest import GenomeSketches
from drep_tpu.ops.containment import all_vs_all_containment, pack_secondary
from drep_tpu.ops.minhash import all_vs_all_mash, pack_sketches, rank_route
from drep_tpu.utils.profiling import counters

# below this many genomes a multi-device ring costs more in collective
# latency + padding than it saves in compute
MESH_MIN_GENOMES = 64


def _mesh_or_none(mesh_shape: int | None, n: int, local_only: bool = False):
    import jax

    from drep_tpu.parallel.faulttol import pod_live
    from drep_tpu.parallel.mesh import make_local_mesh, make_mesh

    if pod_live() is not None or (local_only and jax.process_count() > 1):
        # LOCAL-mesh regimes: (a) degraded pod (elastic protocol lost a
        # member) — a global mesh spans the dead process's chips and a
        # sharded dispatch over it would wait on the corpse forever, no
        # timeout guards the collective itself; (b) `local_only` on any
        # multi-process pod — the SECONDARY engines run their dispatches
        # process-local BY CONTRACT (ISSUE 4), which is what makes every
        # per-batch call independently retryable (retrying_call
        # local_only in cluster/controller.py): a per-process retry of a
        # process-local program cannot desync the pod. Either way the
        # work runs REPLICATED on each process's chips: slower than a
        # pod-wide ring, never hung, same numbers.
        local = len(jax.local_devices())
        if local > 1 and n >= MESH_MIN_GENOMES:
            return make_local_mesh()
        return None
    n_avail = len(jax.devices())
    n_dev = mesh_shape if mesh_shape is not None else n_avail
    if n_dev > 1 and n >= MESH_MIN_GENOMES:
        return make_mesh(n_dev)
    return None


def dense_primary_route(n: int, mesh_shape: int | None):
    """(route name, mesh | None) of a dense primary over `n` genomes on
    THIS host: 'ring_sort' on the mesh the ring will run on, else 'sort'.
    The name is what the cluster snapshot records
    (controller._primary_route); :func:`mash_distance_matrix` runs what it
    says. Every dense route computes the one estimator (union-bottom-s,
    the reference Mash) — they differ in execution alone."""
    mesh = _mesh_or_none(mesh_shape, n)
    return ("ring_sort" if mesh is not None else "sort"), mesh


def mash_distance_matrix(
    packed,
    k: int,
    mesh_shape: int | None = None,
    tile: int = 256,
) -> np.ndarray:
    """[N, N] Mash distance with automatic single-chip / mesh selection.

    Shared by the jax_mash engine and the multiround chunked path so both
    honor `mesh_shape` identically.

    All dispatch targets are triangle-only: the mesh ring runs the
    half-ring schedule (ceil((D+1)/2) of D steps + host mirror), the
    Pallas path its wrapped symmetric grid, the sort tiles an upper-
    triangle walk — each exactly equal to its full-grid twin at ~half the
    tile work.

    A mesh -> the ring; one device -> the Pallas kernel where
    `pallas_mash_supported` (a TPU, the sketch within the kernel's VMEM
    width); else the jnp sort tiles (`all_vs_all_mash`: the tests'
    reference, and what a CPU runs). One estimator at any N: the three
    differ in execution, never in numerics family.
    """
    route, mesh = dense_primary_route(packed.n, mesh_shape)
    if route == "ring_sort":
        from drep_tpu.parallel.allpairs import sharded_mash_allpairs

        return sharded_mash_allpairs(packed, k=k, mesh=mesh)
    from drep_tpu.ops.pallas_mash import all_vs_all_mash_pallas, pallas_mash_supported

    if pallas_mash_supported(packed.sketch_size):
        dist, _jac = all_vs_all_mash_pallas(packed, k=k)
        return dist
    # the tile loop ships, dispatches and reads back tile by tile: the
    # whole call is the host waiting on the device
    with counters.span("primary/wait"):
        dist, _jac = all_vs_all_mash(packed, k=k, tile=tile)
    return dist


def pack_primary(bottom: list[np.ndarray], names: list[str], sketch_size: int, processes: int = 1):
    """`pack_sketches` for a primary compare, under the `primary/pack` span
    and booked in the record's `primary_pack`: the phase's seconds beside
    what they were spent on. The span's args say what the pack sorts
    (`hashes=`) and how: `path=` native | numpy, on `workers=` threads of
    the job's `-p` (`processes`)."""
    hashes = sum(min(len(b), sketch_size) for b in bottom)
    path, threads = rank_route(hashes, processes)
    with counters.span("primary/pack", hashes=hashes, path=path, workers=threads):
        packed = pack_sketches(bottom, names, sketch_size, workers=processes)
    # rows ascend and every rank is used, so the largest id is some row's
    # last real entry: the vocabulary's size without a pass over the matrix
    full = packed.counts > 0
    last = packed.ids[full, packed.counts[full] - 1]
    counters.add_primary_pack(
        genomes=packed.n, hashes=hashes, distinct_ids=int(last.max()) + 1 if last.size else 0,
        native=path == "native", threads=threads,
    )
    return packed


@register_primary("jax_mash")
def primary_jax_mash(
    gs: GenomeSketches,
    tile: int = 256,
    mesh_shape: int | None = None,
    processes: int = 1,
    **_,
) -> tuple[np.ndarray, np.ndarray]:
    """All-vs-all Mash distance from bottom-k sketches on device.

    Returns (dist [N,N], similarity [N,N]) where similarity = 1 - dist
    (the Mdb convention).
    """
    packed = pack_primary(gs.bottom, gs.names, gs.sketch_size, processes)
    dist = mash_distance_matrix(packed, gs.k, mesh_shape=mesh_shape, tile=tile)
    # a second N x N matrix, first touched here: 0.4 s at 10,000 genomes
    with counters.span("primary/similarity", cells=dist.size):
        similarity = 1.0 - dist
    return dist, similarity


def _count_path(path: str) -> None:
    """Book which kernel path served this containment call into the run
    record (perf_counters.json `secondary_paths`): a measurement must be
    able to PROVE which regime (one-shot vs beyond-budget, device vs CPU)
    it exercised, not infer it from planted-vocabulary arithmetic."""
    counters.add_path(path)


def _count_one_shot_call(packed, v_pad: int, cluster_sizes: list[int]) -> None:
    """Book one one-shot call's shape beside its path (`secondary_calls`):
    the program computes every pair of its padded rows, and only the pairs
    inside a cluster are read."""
    from drep_tpu.ops.containment import matmul_rows_pad

    counters.add_secondary_call(
        clusters=len(cluster_sizes),
        rows=packed.n,
        rows_pad=matmul_rows_pad(packed.n),
        width=packed.ids.shape[1],
        v_pad=v_pad,
        useful_pairs=sum(m * (m - 1) // 2 for m in cluster_sizes),
    )


def containment_matrices(
    packed,
    k: int,
    mesh_shape: int | None = None,
    tile: int = 128,
    local_only: bool = True,
):
    """(symmetric max-containment ani, directional cov) with automatic
    path selection.

    ``local_only`` (default) clamps the mesh to THIS process's devices on
    multi-process pods — the retryable-sharded-secondary contract
    (ISSUE 4): a secondary batch whose dispatch is process-local can be
    retried by retrying_call without desyncing the pod, so a transient
    device failure mid-batch costs one retry instead of the whole run.
    Pass ``local_only=False`` only for a caller that is NOT wrapped in a
    per-process retry and genuinely wants the pod-wide ring.

    Every path is triangle-only (intersection counts are symmetric; the
    directional cov derives from counts on host): the matmul paths run
    canonical (bi <= bj) blocks, the mesh ring the half-ring schedule,
    the CPU reference an upper-triangle tile walk — all mirror-exact vs
    their full grids.

    The choice, from what the call can observe (the one-shot budget, the
    mesh, the platform), each path booked in `secondary_paths`:
    1. `one_shot`: the MXU indicator matmul, exact, whenever the
       [m, vocab] int8 indicator fits the one-shot budget.
    2. `mesh_ring`: beyond the budget with more than one device, the
       ring-sharded half-ring (parallel/allpairs.py).
    3. beyond the budget on one device: on a TPU `matmul_chunked`, the
       same matmul over vocabulary chunks (ops/rangepart.py; cost/pair ∝
       v_pad); off a TPU `cpu_tiles`, the tiled searchsorted walk — the
       plain reference the tests compare the kernels against (gathers
       are fine off-TPU).
    """
    import jax

    from drep_tpu.ops.containment import (
        all_vs_all_containment_matmul,
        all_vs_all_containment_matmul_chunked,
        matmul_vocab_pad,
        one_shot_fits,
    )

    v_pad = matmul_vocab_pad(packed)  # one scan; budget uses the REAL width
    if one_shot_fits(packed.n, v_pad):
        _count_path("one_shot")
        _count_one_shot_call(packed, v_pad, [packed.n])
        return all_vs_all_containment_matmul(packed, k=k, v_pad=v_pad)
    mesh = _mesh_or_none(mesh_shape, packed.n, local_only=local_only)
    if mesh is not None:
        from drep_tpu.parallel.allpairs import sharded_containment_allpairs

        _count_path("mesh_ring")
        return sharded_containment_allpairs(packed, k=k, mesh=mesh)
    if jax.devices()[0].platform == "tpu":
        # no fallback: a kernel that fails to compile or run raises
        _count_path("matmul_chunked")
        return all_vs_all_containment_matmul_chunked(packed, k=k)
    _count_path("cpu_tiles")
    return all_vs_all_containment(packed, k=k, tile=tile)


@register_secondary("jax_ani")
def secondary_jax_ani(
    gs: GenomeSketches,
    indices: list[int],
    tile: int = 128,
    mesh_shape: int | None = None,
    processes: int = 1,
    **_,
) -> tuple[np.ndarray, np.ndarray]:
    """(symmetric max-containment ani, directional cov) for a genome
    subset. `indices` index into gs.names; matrices are [m, m] in that
    order. `processes` (dRep's `-p`) bounds the threads the pack ranks
    on; results do not depend on it."""
    packed = pack_secondary(
        [gs.scaled[i] for i in indices], [gs.names[i] for i in indices], processes
    )
    return containment_matrices(packed, gs.k, mesh_shape=mesh_shape, tile=tile)


@register_secondary_batched("jax_ani")
def secondary_jax_ani_batched(
    gs: GenomeSketches,
    clusters: list[list[int]],
    tile: int = 128,
    mesh_shape: int | None = None,
    processes: int = 1,
    **_,
) -> list[tuple[np.ndarray, np.ndarray]]:
    """One device call for MANY small primary clusters.

    At production scale most primary clusters hold a handful of genomes;
    one dispatch per cluster pays the host<->device round-trip latency
    hundreds of times. Only each cluster's DIAGONAL block of the pairwise
    matrices is ever read, so the pack uses per-cluster-LOCAL dense id
    spaces (ops/containment.py::pack_scaled_sketches_clusterlocal): the
    joint vocabulary extent is the max single-cluster vocabulary, not the
    union — at production sketch depth (20k-wide sketches, mostly private
    hash space across unrelated clusters) the union pack measured 8.4M
    ids and forced the chunked kernels, while the cluster-local pack stays
    in the one-shot indicator regime. The cluster-local one-shot is preferred
    even when a mesh is available: a <=512-row batch over a cluster-max
    vocabulary is a single small matmul, and sharding it over a ring is
    collective-latency-dominated for zero compute win — the mesh earns
    its keep on the per-cluster path for big single clusters, not here.
    Falls back to the shared-vocabulary pack + full path dispatch (which
    may pick the mesh ring) when even the local extent exceeds the
    one-shot budget. `processes` (dRep's `-p`) bounds the threads the
    pack ranks clusters on; results do not depend on it."""
    from drep_tpu.ops.containment import (
        all_vs_all_containment_matmul,
        clusterlocal_pack_workers,
        matmul_vocab_pad_extent,
        one_shot_fits,
        pack_scaled_sketches_clusterlocal,
    )

    flat = [i for cl in clusters for i in cl]
    names = [gs.names[i] for i in flat]
    ani_all = cov_all = None
    # the span's args say whether the pool engaged and what a hash cost
    with counters.span(
        "secondary/pack",
        calls=len(clusters),
        workers=clusterlocal_pack_workers(processes, len(clusters)),
        hashes=sum(len(gs.scaled[i]) for i in flat),
    ):
        packed_l, v_extent = pack_scaled_sketches_clusterlocal(
            [[gs.scaled[i] for i in cl] for cl in clusters], names, processes=processes
        )
    v_pad = matmul_vocab_pad_extent(v_extent)
    if one_shot_fits(packed_l.n, v_pad):
        _count_path("one_shot_clusterlocal")
        _count_one_shot_call(packed_l, v_pad, [len(cl) for cl in clusters])
        # full-matrix ani/cov over the cluster-local pack: diagonal
        # blocks are exact; cross blocks are id-collision garbage the
        # slicing below never reads
        ani_all, cov_all = all_vs_all_containment_matmul(
            packed_l, k=gs.k, v_pad=v_pad
        )
    if ani_all is None:
        packed = pack_secondary([gs.scaled[i] for i in flat], names, processes, calls=len(clusters))
        ani_all, cov_all = containment_matrices(
            packed, gs.k, mesh_shape=mesh_shape, tile=tile
        )
    out: list[tuple[np.ndarray, np.ndarray]] = []
    with counters.span("secondary/post", calls=len(clusters)):
        o = 0
        for cl in clusters:
            m = len(cl)
            out.append((ani_all[o : o + m, o : o + m], cov_all[o : o + m, o : o + m]))
            o += m
    return out


# subprocess fallbacks register themselves on import
from drep_tpu.cluster import anim as _anim  # noqa: E402,F401
from drep_tpu.cluster import external as _external  # noqa: E402,F401
