"""Out-of-core streaming primary comparison — the 100k-genome path.

The dense engines (ops/minhash.py, parallel/allpairs.py) materialize the
full [N, N] distance matrix; at N=100k that is 40 GB per output and cannot
live on host or device. The reference handles this regime by chunked
multiround clustering (drep/d_cluster/compare_utils.py::
multiround_primary_clustering, SURVEY.md §2; reference mount empty). This
module is the TPU-native supersession (SURVEY.md §7 step 8 / §5.4):

- the (i, j) row-block tile grid is walked host-side; each tile is computed
  on device (round-robined over all local chips — JAX dispatch is async, so
  D tiles are in flight at once) and immediately **thresholded on host**:
  only edges with ``dist <= cutoff`` survive (callers pass
  max(1-P_ani, warn_dist) so the sparse Mdb keeps evaluate-stage
  near-threshold pairs; clustering re-filters to <= 1-P_ani). Memory is
  O(edges), never O(N^2).
- every finished row-block appends a checkpoint shard
  (``row_XXXXX.npz`` with its surviving edges) under the work directory;
  a preempted run resumes by skipping finished shards — the shard-level
  checkpointing the reference's CSV-only resume cannot do mid-stage.
- primary clusters come from the RETAINED SPARSE EDGE GRAPH, honoring
  --clusterAlg: 'average' (the reference default) runs sparse UPGMA with
  unobserved pairs at their retention lower bound
  (ops/linkage.py::sparse_average_linkage — exact whenever no accepted
  merge touches an unobserved pair, and loudly counted when one does);
  'single' runs host union-find connected components, which at a distance
  cutoff is EXACTLY single-linkage fcluster(t=cutoff).

Ingest/compute overlap (SURVEY.md §2c PP row, §7 hard part (f)): the tile
loop deliberately does NOT consume genome blocks as they are sketched.
The estimator compares int32 ids whose order must agree across every pair
(bottom-s of the union), and the dense rank remap that guarantees this
(ops/minhash.py::pack_sketches) needs the full sketch set — the exact
alternative, per-tile local remaps, would preserve order within each tile
but re-transfer packed ids per tile: ~8 MB x ~4800 tiles ≈ 38 GB across
the link at 100k genomes vs ~400 MB once for the global pack. With the
native ingest at ~92 MB/s/core (an earlier round's measurement — ~78
core-minutes per 100k genomes, so minutes of wall on a real multi-core
TPU-VM host with `-p`), ingest is small next to the tile compute, and the
one overlap that is exact AND free is taken instead:
:func:`warmup_streaming_compile` runs the cold compile of the tile
programs on a background thread while the host ingests
(cluster/controller.py wires it; nothing executes, so results cannot
change).
"""

from __future__ import annotations

import os
import time

import numpy as np

from drep_tpu.ops.minhash import PackedSketches, mash_distance_tile, pad_packed_rows
from drep_tpu.utils import telemetry
from drep_tpu.utils.logger import get_logger
from drep_tpu.utils.profiling import counters

DEFAULT_BLOCK = 1024

# per-tile device->host edge budget for the compact threshold path: the
# retained edge graph is very sparse at scale (~0.02% dense in an earlier
# 50k-genome chip run), yet the dense [block, block] f32 tile is 4 MB.
# Thresholding ON DEVICE and shipping up to this many (i, j, dist)
# triples per tile cuts readback bytes ~20x; a tile with more survivors
# falls back to the dense readback (correctness never depends on the
# budget). The value is from an earlier chip run, not re-measured: whether
# readback still dominates on the current machine is ROADMAP S1/D2.
EDGE_BUDGET = 16384

# the sort-merge HBM-temp budget rule lives beside the merge itself
# (ops/merge.py::cap_merge_tile)
from drep_tpu.ops.merge import cap_merge_tile  # noqa: E402


def _compact_tile_jit_factory():
    """Build the jit'd device-side threshold+compact once (import-time jax
    use is avoided module-wide; streaming may be imported before the
    platform guard runs)."""
    import functools

    import jax
    import jax.numpy as jnp

    from drep_tpu.ops.minhash import mash_distance_from_jaccard

    @functools.partial(
        jax.jit, static_argnames=("budget", "from_counts", "s_orig", "k", "diag")
    )
    def compact(out, ca, cb, cutoff, *, budget, from_counts, s_orig, k, diag):
        with jax.named_scope("drep_tile_threshold"):
            if from_counts:
                # the Pallas kernel ships raw shared counts; THE shared
                # count->distance transform runs on device (xp=jnp) so only
                # survivors cross the link
                from drep_tpu.ops.pallas_mash import shared_counts_to_distance

                d, _j = shared_counts_to_distance(out, ca, cb, s_orig, k, xp=jnp)
            else:
                d = out
            keep = d <= cutoff
            # padding rows carry count 0 (every real genome has >= 1 k-mer);
            # masking on counts reproduces the host path's gi/gj < n filter
            keep &= (ca > 0)[:, None] & (cb > 0)[None, :]
            if diag:
                ri = jax.lax.broadcasted_iota(jnp.int32, keep.shape, 0)
                rj = jax.lax.broadcasted_iota(jnp.int32, keep.shape, 1)
                keep &= rj > ri  # i < j only on the diagonal tile
        with jax.named_scope("drep_tile_compact"):
            count = keep.sum(dtype=jnp.int32)
            ki, kj = jnp.nonzero(keep, size=budget, fill_value=0)
            # d rides along so a budget-overflow readback reuses the SAME
            # device-computed values — the edge set must not depend on
            # device-vs-host libm ulps at the cutoff boundary
            return ki.astype(jnp.int32), kj.astype(jnp.int32), d[ki, kj], count, d

    return compact


_COMPACT_TILE = None


def _compact_tile():
    global _COMPACT_TILE
    if _COMPACT_TILE is None:
        _COMPACT_TILE = _compact_tile_jit_factory()
    return _COMPACT_TILE


def connected_components(n: int, ii: np.ndarray, jj: np.ndarray) -> np.ndarray:
    """Edge graph -> labels 1..C numbered by first member index
    (deterministic; partitions match single-linkage fcluster at the cutoff).

    scipy's C union-find: tens of millions of edges at the 100k-genome scale
    this path exists for must not be walked one Python iteration at a time.
    """
    from scipy.sparse import coo_matrix
    from scipy.sparse.csgraph import connected_components as _cc

    graph = coo_matrix(
        (np.ones(len(ii), dtype=np.int8), (ii, jj)), shape=(n, n)
    )
    _, raw = _cc(graph, directed=False)
    # relabel to first-occurrence order, vectorized: scipy labels are 0..C-1,
    # so remap[raw_label] = 1 + rank of that label's first member index
    _, first_idx = np.unique(raw, return_index=True)
    remap = np.empty(len(first_idx), dtype=np.int64)
    remap[np.argsort(first_idx)] = np.arange(1, len(first_idx) + 1)
    return remap[raw]


def stripe_owner(bi: int, n_blocks: int, pc: int) -> int:
    """Which process owns row-block stripe `bi` (balanced dealing).

    Stripe `bi` of the upper-triangle walk carries ``n_blocks - bi``
    tiles, so the old ``bi % pc`` dealing loaded early processes ~2x
    heavier than late ones and multi-host wall-clock tracked the heaviest
    stripe chain. Pairing stripe `bi` with its mirror ``n_blocks-1-bi``
    makes every pair carry a constant ``n_blocks + 1`` tiles (the odd
    middle stripe is its own half-weight pair), so dealing PAIRS
    round-robin balances total tiles per process to within one stripe.

    This is the EPOCH-0 deal: :func:`stripe_owner_live` generalizes it to
    the survivor set after a pod-member death.
    """
    return min(bi, n_blocks - 1 - bi) % pc


def stripe_owner_live(bi: int, n_blocks: int, live: list[int]) -> int:
    """Epoch-scoped stripe ownership: the same mirror-paired dealing, over
    an explicit live-process list instead of ``range(pc)``. With the full
    pod alive this IS :func:`stripe_owner`; after an ownership-epoch bump
    the dead members drop out of `live` — or new members JOIN it (ids >=
    the original process count) — and every stripe still missing a shard
    re-deals across the CURRENT set with the same balance bound. Pure
    scheduling: shard names/content and the canonical epoch-0 assembly
    order never depend on who computed a stripe."""
    return live[min(bi, n_blocks - 1 - bi) % len(live)]


def stripe_weights(occ: np.ndarray, first_col_block: int) -> np.ndarray:
    """Per-stripe OCCUPIED-tile counts under a pruned schedule: the tiles
    stripe `bi` will actually dispatch (candidate-occupied, within the
    triangular/rect walk). The dealing weight for
    :func:`deal_stripes` — under ``--primary_prune lsh`` the mirror-paired
    stripe pairing no longer balances (skip-heavy stripes carry almost no
    work), so the deal balances what is actually computed instead."""
    n_blocks = occ.shape[0]
    return np.array(
        [
            int(occ[bi, max(bi, first_col_block):n_blocks].sum())
            for bi in range(n_blocks)
        ],
        dtype=np.int64,
    )


def deal_stripes(
    n_blocks: int, live: list[int], weights: np.ndarray | None = None
) -> list[int]:
    """Owner per stripe over the CURRENT live set.

    ``weights=None`` is exactly the mirror-paired
    :func:`stripe_owner_live` deal (pinned by property tests — the dense
    schedule's balance story is unchanged). With per-stripe weights
    (occupied-tile counts from a pruned schedule, :func:`stripe_weights`)
    the deal switches to deterministic greedy LPT: stripes in descending
    weight order (ties by index), each to the currently-lightest member
    (ties by id) — so every member's computed-tile load is within one
    stripe's weight of the mean regardless of how skewed the skip pattern
    is. Deterministic for identical inputs, which every member has
    (candidates derive from the replicated pack), so the pod agrees on
    ownership without any exchange. Dealing never reassigns work that is
    already durable — callers deal only the stripes still MISSING a
    shard, whoever computed the rest."""
    if weights is None:
        return [stripe_owner_live(bi, n_blocks, live) for bi in range(n_blocks)]
    members = sorted(live)
    load = {p: 0 for p in members}
    owners = [members[0]] * n_blocks
    order = sorted(range(n_blocks), key=lambda b: (-int(weights[b]), b))
    for b in order:
        p = min(members, key=lambda m: (load[m], m))
        owners[b] = p
        load[p] += int(weights[b])
    return owners


def _shard_name(bi: int, epoch: int) -> str:
    """Stripe `bi`'s checkpoint shard filename, epoch-stamped: healthy
    (epoch-0) shards stay ``row_XXXXX.npz``; a stripe computed after an
    ownership-epoch bump carries the epoch in its name — resume-visible
    forensics for which shards a degraded run produced. Content is
    identical whichever process/epoch computed it (deterministic tiles),
    so a resume replays identically across the bump."""
    return f"row_{bi:05d}.npz" if epoch == 0 else f"row_{bi:05d}.e{epoch:02d}.npz"


def _find_shard(checkpoint_dir: str, bi: int) -> str | None:
    """Existing shard for stripe `bi` under ANY ownership epoch."""
    loc = os.path.join(checkpoint_dir, f"row_{bi:05d}.npz")
    if os.path.exists(loc):
        return loc
    import glob

    hits = sorted(glob.glob(os.path.join(checkpoint_dir, f"row_{bi:05d}.e*.npz")))
    return hits[0] if hits else None


def _load_shard(path: str):
    """(ii, jj, dist) from a checkpoint shard, or None when it reads
    corrupt — warned, counted (``corrupt_shards_healed``), and best-effort
    removed (the remove itself may fail on EACCES/flaky NFS; callers
    recompute regardless). The checked read (utils/durableio.py) retries
    transient I/O errors and verifies the in-band ``__crc__`` — a
    zero-byte, truncated, or bit-rotted shard classifies exactly like a
    MISSING one and the store self-heals. ONE implementation for the
    resume loop and the elastic assembly so the corruption contract
    cannot drift."""
    from drep_tpu.utils import durableio

    return durableio.load_npz_or_none(
        path, what="row shard",
        convert=lambda z: (z["ii"], z["jj"], z["dist"]),
        warn="streaming primary: corrupt shard %s — recomputing",
    )


def _shard_epoch(path: str) -> int:
    """The ownership epoch stamped in a shard filename (0 for bare names).
    Healing a corrupt shard recomputes INTO its own path — the pre-elastic
    self-heal invariant: even when the remove of the corrupt file fails
    (EACCES, flaky NFS), the atomic rewrite replaces it."""
    name = os.path.basename(path)
    if ".e" in name:
        try:
            return int(name.split(".e")[1].split(".")[0])
        except ValueError:
            return 0
    return 0


def _real_pairs_in_tile(i0: int, j0: int, block: int, n: int) -> int:
    """Unique real (unpadded, i<j) pairs a tile covers."""
    ra = max(0, min(i0 + block, n) - i0)
    rb = max(0, min(j0 + block, n) - j0)
    if i0 == j0:
        return ra * (ra - 1) // 2
    return ra * rb


def _pallas_tile_layout(ids: np.ndarray, counts: np.ndarray):
    """(ids_pal, ids_rev, counts_col) — the exact host layout
    _mash_shared_grid consumes (pow2 PAD-padded columns, reversed
    contiguous copy, column-vector counts)."""
    from drep_tpu.ops.merge import next_pow2
    from drep_tpu.ops.minhash import PAD_ID

    width = ids.shape[1]
    s2 = max(128, next_pow2(width))
    ids_pal = (
        np.pad(ids, ((0, 0), (0, s2 - width)), constant_values=PAD_ID)
        if s2 != width
        else ids
    )
    return (
        ids_pal,
        np.ascontiguousarray(ids_pal[:, ::-1]),
        np.ascontiguousarray(counts[:, None]),
    )


def _effective_block(block: int, sketch_width: int, use_pallas: bool) -> int:
    """The tile block the edge loop will actually run: 128-multiples for
    the Pallas grid, HBM-temp-capped for the jnp merge. One rule shared
    with warmup_streaming_compile so the warmed compile cache key always
    matches the real run's shapes."""
    if use_pallas:
        from drep_tpu.ops.pallas_mash import TILE as _PTILE

        return max(_PTILE, -(-block // _PTILE) * _PTILE)
    return cap_merge_tile(block, sketch_width)


def _build_tile_programs(
    width: int, block: int, k: int, cutoff, use_pallas: bool, device
) -> None:
    """Compile — never execute — the tile programs of one streaming walk
    for `device`, at exactly the signature the edge loop dispatches
    (`block` is the EFFECTIVE block, `width` the packed sketch width,
    `cutoff` the very object the loop passes): the tile kernel plus both
    `diag` variants of the threshold+compact. Whatever the compiler says
    raises here, outside the retry envelope (parallel/faulttol.py)."""
    import jax
    from jax.sharding import SingleDeviceSharding

    from drep_tpu.parallel.faulttol import build_program

    sharding = SingleDeviceSharding(device)

    def spec(shape, dtype=np.int32):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)

    counts1d = spec((block,))
    if use_pallas:
        from drep_tpu.ops.merge import next_pow2
        from drep_tpu.ops.pallas_mash import (
            _mash_shared_grid,
            _use_interpret,
            rows_per_iter,
        )

        s2 = max(128, next_pow2(width))
        build_program(
            _mash_shared_grid,
            spec((block, s2)), spec((block, 1)), spec((block, s2)), spec((block, 1)),
            s_orig=width, r_iter=rows_per_iter(s2), interpret=_use_interpret(),
        )
        tile_out = spec((block, block))  # raw shared counts
    else:
        ids = spec((block, width))
        build_program(mash_distance_tile, ids, counts1d, ids, counts1d, k=k)
        tile_out = spec((block, block), np.float32)
    for diag in (True, False):
        build_program(
            _compact_tile(), tile_out, counts1d, counts1d, cutoff,
            budget=min(EDGE_BUDGET, block * block), from_counts=use_pallas,
            s_orig=width, k=k, diag=diag,
        )


def warmup_streaming_compile(
    sketch_width: int,
    block: int = DEFAULT_BLOCK,
    k: int = 21,
    cutoff: float = 0.1,
    use_pallas: bool | None = None,
) -> None:
    """Compile the streaming tile programs at the shapes a run will use —
    fired on a background thread while host ingest runs, so the cold
    compile costs no wall-clock (the one exact-and-free ingest/compute
    overlap; module docstring has the analysis of why tile-level overlap
    is rejected). Nothing executes: the edge loop's own build step
    (:func:`_build_tile_programs`, before its first dispatch) then finds
    the programs in the compile cache. A compiler error raises."""
    import jax

    from drep_tpu.ops.pallas_mash import pallas_mash_supported

    if use_pallas is None:
        use_pallas = pallas_mash_supported(sketch_width)
    _build_tile_programs(
        sketch_width, _effective_block(block, sketch_width, use_pallas), k,
        cutoff, use_pallas, jax.local_devices()[0],
    )


def retention_bound(cutoff: float, keep_dist: float, cluster_alg: str) -> float:
    """THE edge-retention bound shared by the streaming primary and the
    incremental genome index (drep_tpu/index): edges survive up to
    max(cutoff, keep_dist), widened for average linkage when the band
    would degenerate to the cutoff (sparse UPGMA's discriminating
    information IS the beyond-cutoff band — see
    streaming_primary_clusters). One rule, so an index built today and a
    from-scratch streaming rerun tomorrow retain the identical edge set.
    """
    keep = max(cutoff, keep_dist)
    if cluster_alg == "average" and keep <= cutoff:
        keep = min(1.0, 2.5 * cutoff)
    return keep


def _prune_meta_conflict(checkpoint_dir: str, meta: dict) -> tuple | None:
    """Does the existing store differ from `meta` ONLY in its banding
    parameters? Then a resume must REFUSE, never silently clear: the
    shards themselves are bit-identical across banding configs (recall
    1.0), but the store may hold hours of finished stripes, and the
    operator changing a prune knob mid-run is far more likely a mistake
    than an intent to recompute — and a silent clear would also launder
    the new config's skip accounting over the old run's shards. Returns
    (stored_prune, wanted_prune) on conflict, None otherwise (missing,
    unreadable, or differently-keyed metas fall through to the normal
    open-and-clear path)."""
    from drep_tpu.utils.ckptmeta import META_NAME, META_PROVENANCE_KEYS

    loc = os.path.join(checkpoint_dir, META_NAME)
    if not os.path.exists(loc):
        return None
    try:
        from drep_tpu.utils.durableio import read_json_checked

        stored = read_json_checked(loc, what="checkpoint meta")
    except Exception:
        return None  # corrupt/unreadable meta: open_checkpoint_dir decides
    if not isinstance(stored, dict):
        return None
    prune_keys = ("prune_scheme", "prune_bands", "prune_min_shared", "prune_keep")
    drop = set(prune_keys) | set(META_PROVENANCE_KEYS)
    stored_rest = {k: v for k, v in stored.items() if k not in drop}
    meta_rest = {k: v for k, v in meta.items() if k not in prune_keys}
    if stored_rest != meta_rest:
        return None  # different inputs entirely: the normal clear applies
    sp = {k: stored.get(k) for k in prune_keys}
    mp = {k: meta.get(k) for k in prune_keys}
    return (sp, mp) if sp != mp else None


def streaming_mash_edges(
    packed: PackedSketches,
    k: int,
    cutoff: float,
    block: int = DEFAULT_BLOCK,
    checkpoint_dir: str | None = None,
    use_pallas: bool | None = None,
    ft_config=None,
    min_col: int = 0,
    prune=None,
) -> tuple[np.ndarray, np.ndarray, np.ndarray, int]:
    """All unordered pairs (i < j) with Mash distance <= cutoff.

    `min_col` restricts the tile walk to column blocks containing indices
    >= min_col — the RECTANGULAR schedule the incremental genome index
    uses for "K new genomes vs N indexed" compares: with the new genomes
    appended at the tail, only tiles whose column block reaches the tail
    are dispatched (every row stripe still runs, so old-row x new-col
    pairs are covered), turning the O(N^2) triangle into O(K*N) work.
    Tiles at the boundary block still emit a few old-old pairs; callers
    filter on jj >= their true first-new index. Per-pair results are
    identical to the full triangle's (the estimator is pair-local).

    `prune` (ops/lsh.py CandidateSet) makes the walk SPARSE: only tiles
    containing at least one candidate pair are dispatched. Candidates
    must have been built at (or beyond) this call's `cutoff` — then the
    skipped tiles hold no retained pair by the recall-1.0 derivation and
    the returned edges (and every checkpoint shard) are BIT-IDENTICAL to
    the dense walk's. Accounting stays honest: `tiles_total` keeps the
    dense-equivalent grid, pruned schedule tiles land in a separate
    `tiles_skipped_pruned` counter plus a `skip_fraction` gauge, and
    `pairs_computed` counts only dispatched tiles. The banding params are
    pinned in the checkpoint meta — resuming a store whose only
    difference is the banding config REFUSES with an actionable error
    (never silently mixes or clears shards across configs). Composes
    unchanged with `min_col` and the elastic protocol (the skip happens
    inside the per-stripe tile loop; stripe ownership, re-dealing, and
    shard names are untouched).

    Returns (ii, jj, dist, pairs_computed) — `pairs_computed` counts pair
    comparisons actually executed this call (resumed shards contribute 0),
    so perf counters stay honest across resumes. Never materializes more
    than one row-block stripe of the distance matrix on host; sketches are
    device-resident (one transfer per device) and tiles round-robin over
    every local device.

    Tile dispatch is fault-tolerant (parallel/faulttol.py, `ft_config` —
    defaults to the process config set by the CLI flags): failed or
    watchdog-tripped tiles retry with backoff on the surviving devices, a
    repeatedly-failing device is quarantined out of the round-robin (its
    HBM copy of the genome pack is freed the moment it is benched), and
    a tile no device can produce is recomputed on the host CPU via the
    jnp path. The CPU fallback thresholds against the SAME distance array
    it ships, so a fallback tile's edge set is self-consistent at the
    cutoff boundary (no mixed device/host provenance inside one tile).

    Multi-process pods with a checkpoint dir additionally run the ELASTIC
    protocol (heartbeats + ownership epochs, parallel/faulttol.py
    HeartbeatManager): a pod member that dies mid-stage is detected by
    heartbeat staleness, the survivors bump the ownership epoch and
    re-deal its unfinished stripes (:func:`stripe_owner_live`), and the
    stage completes with the final edge list bit-identical to a healthy
    run — assembled in the canonical healthy-run order from the shared
    shard store, which needs no full-pod collective after the death.
    ``DREP_TPU_HEARTBEAT_S=0`` disables the protocol (a dead member then
    aborts at the collective timeout, the pre-elastic behavior).
    """
    import jax

    from drep_tpu.parallel.faulttol import (
        PodDrained,
        TileExecutor,
        drain_at_boundary,
        heartbeat_cadence_s,
    )
    from drep_tpu.utils import faults as _faults

    logger = get_logger()
    n = packed.n
    block = max(1, min(block, max(8, n)))
    # on TPU the VMEM-resident Pallas union-bottom-s kernel computes the
    # tiles; the jnp merge (which bounces [T,T,2S] temps through HBM, and
    # measured several times slower in an earlier chip run) stays for CPU
    # and over-wide sketches, with its HBM-temp cap.
    from drep_tpu.ops.pallas_mash import pallas_mash_supported

    if use_pallas is None:  # override exists so CPU tests can force the
        use_pallas = pallas_mash_supported(packed.sketch_size)  # interpret path
    block = _effective_block(block, packed.sketch_size, use_pallas)
    with counters.span("primary/pack"):
        ids, counts = pad_packed_rows(packed.ids, packed.counts, block)
    nt = ids.shape[0]
    n_blocks = nt // block
    # rectangular schedule: first column block the walk may touch (0 =
    # the classic upper triangle). Computed AFTER the effective block so
    # callers think in genome indices, not tile units.
    first_col_block = max(0, min(int(min_col), max(n - 1, 0))) // block
    # sparse schedule: the block-level tile-occupancy bitmap, built AFTER
    # the effective block is known (candidates are genome-indexed, tiles
    # are block-indexed). None = dense walk, bitmap untouched code path.
    occ = prune.occupancy(block, n_blocks) if prune is not None else None
    width = ids.shape[1]  # the estimator's `s` (pre-pow2-pad sketch width)
    if use_pallas:
        from drep_tpu.ops.pallas_mash import rows_per_iter

        with counters.span("primary/pack"):
            ids_pal, ids_rev, counts_col = _pallas_tile_layout(ids, counts)
        # env read + clamp ONCE per run: per-tile re-reads would let a
        # mid-run env change flip the jit signature and recompile between
        # tiles (thousands of dispatches per run)
        r_iter = rows_per_iter(ids_pal.shape[1])
    # local devices only: on a multi-host pod jax.devices() includes remote
    # chips, and device_put to a non-addressable device raises. Row-block
    # stripes are instead divided across processes (the mirror-paired
    # stripe_owner dealing) and the surviving edges gathered at the end.
    devices = jax.local_devices()
    pc = jax.process_count()
    pid = jax.process_index()

    # the full padded pack lives on every device (N=100k, s=1000 -> ~400 MB,
    # well within HBM); tiles are sliced on device, so each block crosses
    # PCIe exactly once per device instead of once per tile. Deferred until
    # a stripe actually computes — a fully-resumed run transfers nothing.
    ids_on: list | None = None
    rev_on: list | None = None
    counts_on: list | None = None
    counts1d_on: list | None = None

    def _free_pack_slot(slot: int) -> None:
        # quarantine callback: a benched device never receives another
        # dispatch, so its resident pack copy is dead weight — drop the
        # references and let the runtime reclaim the HBM (ROADMAP
        # follow-up; ~400 MB per quarantined chip at the 100k scale)
        freed = 0
        for arrs in (ids_on, rev_on, counts_on, counts1d_on):
            if arrs is not None and arrs[slot] is not None:
                arrs[slot] = None
                freed += 1
        if freed:
            counters.add_fault("pack_buffers_freed", freed)

    # the retrying dispatcher: round-robins over non-quarantined devices,
    # watchdogs each wait, retries on survivors, CPU-recomputes last
    ft = TileExecutor(
        devices, ft_config, fault_site="streaming_tile",
        on_quarantine=_free_pack_slot,
    )

    # elastic-pod liveness: heartbeat notes in the shared checkpoint dir.
    # Started BEFORE the stage-open barrier so every process's stale-note
    # cleanup is ordered ahead of every peer's monitoring — a restarted
    # pod can never diagnose a previous run's dead process. The writer
    # runs even single-process (negligible: one tiny file per cadence) so
    # the zero-overhead guard exercises it; monitoring/epochs need peers.
    hb = None
    cadence = heartbeat_cadence_s() if checkpoint_dir is not None else 0.0
    # mid-run JOIN (ISSUE 9): this process is NOT a pod member — it was
    # started against a running pod's checkpoint dir (DREP_TPU_POD_JOIN)
    # to add capacity. It never opens the store (the pod did), never runs
    # the stage barrier; it requests admission, adopts the pod's
    # membership, and enters the elastic stripe loop as a grown-set
    # member — unfinished stripes re-deal to it, finished shards are
    # reused, and the canonical epoch-0 assembly keeps the final edges
    # bit-identical to a fixed-membership run.
    from drep_tpu.parallel.faulttol import join_requested

    joining = join_requested() is not None
    if joining and (checkpoint_dir is None or cadence <= 0):
        # a join request that cannot run the protocol must refuse LOUDLY:
        # falling through would make this process an independent pc=1 run
        # against the pod's LIVE store — open_checkpoint_dir could clear
        # the running pod's shards on any meta skew, and even an exact
        # match silently duplicates every stripe instead of joining
        from drep_tpu.errors import UserInputError

        raise UserInputError(
            "DREP_TPU_POD_JOIN is set but the elastic join protocol cannot "
            "run: "
            + (
                "this streaming call has no shared checkpoint dir to join "
                "through"
                if checkpoint_dir is None
                else "heartbeats are disabled (DREP_TPU_HEARTBEAT_S=0) and "
                "admission rides the heartbeat protocol"
            )
            + ". Unset DREP_TPU_POD_JOIN to run standalone, or point this "
            "process at the pod's checkpoint dir with heartbeats enabled."
        )
    if checkpoint_dir is not None and cadence > 0 and not joining:
        from drep_tpu.parallel.faulttol import HeartbeatManager

        hb = HeartbeatManager(
            checkpoint_dir, cadence,
            max_dead=ft.config.max_dead_processes,
            max_joins=ft.config.max_joins,
        )
        hb.start()
    elastic = hb is not None and pc > 1

    resume = False
    if checkpoint_dir is not None:
        from drep_tpu.utils.ckptmeta import content_fingerprint, open_checkpoint_dir

        with counters.span("primary/publish"):  # the shard store's key: SHA-1 over the pack
            fingerprint = content_fingerprint(packed.names, packed.counts, packed.ids)
        meta = {
            "n": n,
            "block": block,
            "k": k,
            "cutoff": round(float(cutoff), 12),
            "sketch_size": int(packed.sketch_size),
            "n_blocks": n_blocks,
            # shards from a different genome set/order are meaningless even
            # at identical N (the int32 ids are a run-specific vocab remap)
            "fingerprint": fingerprint,
        }
        if first_col_block:
            # rectangular walks pin their column restriction — shards from
            # a full-triangle pass must not resume a rect one (or vice
            # versa); the key is omitted at 0 so pre-rect stores stay
            # resumable unchanged
            meta["min_col_block"] = first_col_block
        if prune is not None:
            # banding params pinned (keys absent when pruning is off, so
            # pre-prune stores stay resumable); a store differing ONLY in
            # these refuses below instead of silently clearing/mixing
            meta.update(prune.params)
        if joining:
            from drep_tpu.parallel.faulttol import join_elastic_pod
            from drep_tpu.utils.ckptmeta import checkpoint_meta_matches

            # the join note goes out first (a pod gated on arriving
            # capacity may open its store only after seeing it); the meta
            # match is polled alongside admission — a joiner must never
            # compute against a store built from different inputs
            hb = join_elastic_pod(
                checkpoint_dir, cadence, config=ft.config,
                what="streaming primary (mid-run join)",
                validate=lambda: checkpoint_meta_matches(checkpoint_dir, meta),
            )
            pc, pid = hb.pc, hb.pid
            elastic = True
            resume = True
        else:
            conflict = _prune_meta_conflict(checkpoint_dir, meta)
            if conflict is not None:
                stored_p, wanted_p = conflict
                from drep_tpu.errors import UserInputError

                if hb is not None:
                    hb.close()  # never leak the beat writer on a refusing open
                raise UserInputError(
                    f"streaming checkpoint store {checkpoint_dir} was written "
                    f"under different candidate-pruning parameters "
                    f"({ {k: v for k, v in stored_p.items() if v is not None} or 'pruning off'}) "
                    f"than this run requests "
                    f"({ {k: v for k, v in wanted_p.items() if v is not None} or 'pruning off'}). "
                    f"Refusing to resume: shards must never mix banding configs. "
                    f"Either rerun with the original --primary_prune/--prune_bands/"
                    f"--prune_min_shared knobs, or delete the store directory to "
                    f"recompute under the new ones."
                )
            # leader-only clear + barrier on >1 process lives inside
            # open_checkpoint_dir (shared with the secondary shard store).
            # Because the heartbeat manager above started BEFORE this open,
            # the barrier is heartbeat-aware (utils/ckptmeta.py): a peer that
            # dies before ever reaching it — even the leader — is admitted as
            # a pod death within --max_dead_processes, the open completes
            # over the survivor set, and the elastic loop below starts
            # DEGRADED instead of this call aborting (ISSUE 4; previously any
            # pre-barrier death raised at the collective timeout). A raising
            # open (death budget exceeded, heartbeats disabled, wedged peer)
            # must not leak the beat writer: a zombie beat would keep this
            # process looking alive in the store forever.
            try:
                with counters.span("primary/publish"):
                    resume = open_checkpoint_dir(
                        checkpoint_dir, meta, clear_suffixes=(".npz",)
                    )
            except BaseException:
                if hb is not None:
                    hb.close()
                raise

    all_ii: list[np.ndarray] = []
    all_jj: list[np.ndarray] = []
    all_dd: list[np.ndarray] = []
    n_owned = sum(1 for b in range(n_blocks) if stripe_owner(b, n_blocks, pc) == pid)
    pairs_computed = 0
    tiles_done = 0  # upper-triangle tiles actually dispatched this call
    tiles_full = 0  # full-grid tiles of the same stripes (resumed: 0/0)
    tiles_skipped = 0  # schedule tiles pruned by the candidate bitmap
    # per local device slot (the record's `primary_stream_slots`): pairs of
    # the tiles first dispatched there, bytes of the pack put there, host
    # seconds inside `ft.finalize` for those tiles; and over the computed
    # stripes the turns they took: ceil(tiles / active slots), summed
    slot_pairs = [0] * len(devices)
    slot_put_bytes = [0] * len(devices)
    slot_wait_s = [0.0] * len(devices)
    stripes_computed = 0
    turns = 0
    # per-tile device->host budget for the compact threshold path
    budget = min(EDGE_BUDGET, block * block)
    compact = _compact_tile()

    def _ensure_pack_on_devices() -> None:
        nonlocal ids_on, rev_on, counts_on, counts1d_on
        if ids_on is not None:
            return
        operands = (ids_pal, ids_rev, counts_col, counts) if use_pallas else (ids, counts)
        slot_put_bytes[:] = [sum(a.nbytes for a in operands)] * len(devices)
        # build BEFORE the first dispatch, outside the retry envelope: a
        # tile program that does not compile must end the run, not turn it
        # into retries and CPU-recomputed tiles (parallel/faulttol.py). For
        # every device the walk can reach: an executable is bound to its
        # device, so a slot's first tile would otherwise build its own
        # inside the envelope; the round-robin gives slot s the walk's
        # (s+1)-th tile, so a walk of fewer tiles than devices builds fewer.
        with counters.span("primary/put", devices=len(devices), bytes=sum(slot_put_bytes)):
            for dev in devices[: n_blocks * (n_blocks + 1) // 2]:
                _build_tile_programs(width, block, k, cutoff, use_pallas, dev)
            if use_pallas:
                ids_on = [jax.device_put(ids_pal, dev) for dev in devices]
                rev_on = [jax.device_put(ids_rev, dev) for dev in devices]
                counts_on = [jax.device_put(counts_col, dev) for dev in devices]
                counts1d_on = [jax.device_put(counts, dev) for dev in devices]
            else:
                ids_on = [jax.device_put(ids, dev) for dev in devices]
                counts_on = [jax.device_put(counts, dev) for dev in devices]
                counts1d_on = counts_on

    def _compute_stripe(bi: int, epoch: int = 0) -> tuple[np.ndarray, np.ndarray, np.ndarray]:
        """Dispatch + finalize one row-block stripe inside a traced span
        (ISSUE 10: an unclosed stripe "B" record is the crash evidence —
        the stripe in flight when a member died); publishes its shard
        under the epoch-stamped name when checkpointing. Returns the
        stripe's surviving edges."""
        with counters.span("stripe", bi=bi, epoch=epoch):
            # the elastic chaos tests SIGKILL a pod member here — at a
            # stripe boundary, with its finished shards already durable
            _faults.fire("process_death")
            if pc == 1 and checkpoint_dir is not None:
                # every earlier stripe's shard is published: a one-process job
                # with a drain pending leaves here (a pod member at its own
                # loop's boundaries, where it also tells its peers)
                drain_at_boundary("primary", stripes_published=len(all_ii), next_stripe=bi)
            return _compute_stripe_tiles(bi, epoch)

    def _publish_shard(bi: int, epoch: int, s_ii, s_jj, s_dd, **note) -> None:
        from drep_tpu.utils.ckptmeta import atomic_savez

        with counters.span("primary/publish"):
            atomic_savez(
                os.path.join(checkpoint_dir, _shard_name(bi, epoch)),
                ii=s_ii, jj=s_jj, dist=s_dd,
            )
        telemetry.event(
            "shard_publish", shard=_shard_name(bi, epoch), edges=len(s_ii), **note
        )

    def _compute_stripe_tiles(bi: int, epoch: int) -> tuple[np.ndarray, np.ndarray, np.ndarray]:
        nonlocal pairs_computed, tiles_done, tiles_full, tiles_skipped, stripes_computed, turns
        if occ is not None and not occ[bi, max(bi, first_col_block):n_blocks].any():
            # fully-pruned stripe: no tile holds a candidate, so the dense
            # walk would retain nothing here — publish the (empty) shard
            # WITHOUT touching a device; the pack transfer itself is
            # deferred until some stripe actually computes
            tiles_skipped += n_blocks - max(bi, first_col_block)
            tiles_full += n_blocks
            empty = (np.empty(0, np.int64), np.empty(0, np.int64), np.empty(0, np.float32))
            if checkpoint_dir is not None:
                _publish_shard(bi, epoch, *empty, pruned=True)
            return empty
        _ensure_pack_on_devices()
        i0 = bi * block
        # dispatch the whole stripe asynchronously, one tile per device
        # turn; each tile's threshold+compact also dispatches here, so
        # only ~EDGE_BUDGET survivors per tile cross the link at the sync
        # points below (the dense [block, block] readback measured as the
        # composite bottleneck on slow d2h links)
        tiles = []
        cols = [
            bj for bj in range(max(bi, first_col_block), n_blocks)
            if occ is None or occ[bi, bj]  # else: no candidate pair in this tile
        ]
        tiles_skipped += n_blocks - max(bi, first_col_block) - len(cols)
        stripes_computed += 1
        turns += -(-len(cols) // len(ft.active))
        with counters.span("primary/dispatch", bi=bi, tiles=len(cols)):
            for bj in cols:
                j0 = bj * block
                diag = j0 == i0

                def dispatch(slot, i0=i0, j0=j0, diag=diag):
                    # async dispatch on device slot `slot` (the executor's
                    # round-robin pick; retries may re-call with another slot)
                    if use_pallas:
                        from drep_tpu.ops.pallas_mash import (
                            _mash_shared_grid,
                            _use_interpret,
                        )

                        out = _mash_shared_grid(
                            rev_on[slot][i0 : i0 + block],
                            counts_on[slot][i0 : i0 + block],
                            ids_on[slot][j0 : j0 + block],
                            counts_on[slot][j0 : j0 + block],
                            s_orig=width,
                            r_iter=r_iter,
                            interpret=_use_interpret(),
                        )
                    else:
                        out, _j = mash_distance_tile(
                            ids_on[slot][i0 : i0 + block],
                            counts_on[slot][i0 : i0 + block],
                            ids_on[slot][j0 : j0 + block],
                            counts_on[slot][j0 : j0 + block],
                            k=k,
                        )
                    return compact(
                        out,
                        counts1d_on[slot][i0 : i0 + block],
                        counts1d_on[slot][j0 : j0 + block],
                        cutoff,
                        budget=budget,
                        from_counts=use_pallas,
                        s_orig=width,
                        k=k,
                        diag=diag,
                    )

                pending = ft.submit(dispatch)
                tiles.append((j0, diag, pending))
                real = _real_pairs_in_tile(i0, j0, block, n)
                slot_pairs[pending[1]] += real
                pairs_computed += real
                tiles_done += 1
        tiles_full += n_blocks

        row_ii: list[np.ndarray] = []
        row_jj: list[np.ndarray] = []
        row_dd: list[np.ndarray] = []
        # the host blocks here: each tile's watchdog-bounded wait, its
        # survivor count (a scalar sync) and the readback of its edges
        with counters.span("primary/wait", bi=bi, tiles=len(tiles)):
            for j0, diag, pending in tiles:
                t_fin = time.perf_counter()
                ki_d, kj_d, dd_d, cnt_d, d_full = ft.finalize(
                    pending,
                    cpu_fallback=lambda i0=i0, j0=j0, diag=diag: _cpu_fallback_tile(
                        ids, counts, i0, j0, block, k, cutoff, diag
                    ),
                )
                slot_wait_s[pending[1]] += time.perf_counter() - t_fin
                cnt = int(cnt_d)  # sync point for this tile (scalar)
                if cnt <= budget:
                    ki = np.asarray(ki_d)[:cnt]
                    kj = np.asarray(kj_d)[:cnt]
                    if cnt:
                        # device-side masks already excluded pad rows and the
                        # diagonal tile's lower triangle
                        row_ii.append(ki.astype(np.int64) + i0)
                        row_jj.append(kj.astype(np.int64) + j0)
                        row_dd.append(np.asarray(dd_d)[:cnt].astype(np.float32))
                    continue
                # budget overflow (denser tile than the edge model assumes):
                # fall back to reading back the SAME device-computed dense
                # distances — correctness never depends on the budget, only
                # readback bytes do, and the edge set cannot shift by
                # device-vs-host libm ulps at the cutoff boundary
                d = np.asarray(d_full)
                keep = d <= cutoff
                if j0 == i0:
                    keep &= np.triu(np.ones_like(keep, dtype=bool), 1)  # i < j only
                ki, kj = np.nonzero(keep)
                if len(ki):
                    gi = ki + i0
                    gj = kj + j0
                    valid = (gi < n) & (gj < n)
                    row_ii.append(gi[valid])
                    row_jj.append(gj[valid])
                    row_dd.append(d[ki, kj][valid].astype(np.float32))

        with counters.span("primary/assemble"):
            s_ii = np.concatenate(row_ii) if row_ii else np.empty(0, np.int64)
            s_jj = np.concatenate(row_jj) if row_jj else np.empty(0, np.int64)
            s_dd = np.concatenate(row_dd) if row_dd else np.empty(0, np.float32)
        if checkpoint_dir is not None:
            _publish_shard(bi, epoch, s_ii, s_jj, s_dd)
        return s_ii, s_jj, s_dd

    def _book_walk() -> None:
        """The walk's own accounting, at its end or where a drain ends it."""
        if tiles_full:
            counters.add_tiles(
                "primary_compare", computed=tiles_done, total=tiles_full,
                skipped=tiles_skipped,
            )
        counters.add_resume(tiles_computed=tiles_done)
        if prune is not None:
            # the headline pruning gauge: fraction of the triangle/rect
            # SCHEDULE the candidate bitmap removed this call (resumed
            # stripes contribute to neither side — honest across resumes)
            sched = tiles_done + tiles_skipped
            counters.set_gauge(
                "skip_fraction", round(tiles_skipped / sched, 4) if sched else 0.0
            )
        if tiles_done:
            # how many local devices the round-robin actually reached: a
            # multi-chip run whose tiles all landed on one chip must not
            # read like one that used the host's four
            counters.set_gauge(
                "streaming_devices_used", float(sum(1 for d in ft.dispatched if d))
            )
            counters.add_stream_slots(
                stripes_computed, turns, ft.dispatched, slot_pairs, slot_put_bytes, slot_wait_s
            )
        derived = ft.derived_timeout_s()
        if derived is not None:
            # the watchdog deadline the run actually derived from its own
            # tile latencies (--dispatch_timeout left at 0) — reported so
            # an operator can pin an explicit value from evidence
            counters.set_gauge("derived_dispatch_timeout_s", round(derived, 3))

    try:
        if not elastic:
            n_resumed = 0
            for bi in range(n_blocks):
                if stripe_owner(bi, n_blocks, pc) != pid:
                    continue  # another process owns this row stripe
                found = _find_shard(checkpoint_dir, bi) if resume else None
                loaded = None
                if found is not None:
                    # a stopped job's shard: found, verified and read, no tile dispatched
                    size = os.path.getsize(found)
                    with counters.span("primary/resume_load", bi=bi, shards=1, bytes=size):
                        loaded = _load_shard(found)
                if loaded is not None:
                    all_ii.append(loaded[0])
                    all_jj.append(loaded[1])
                    all_dd.append(loaded[2])
                    n_resumed += 1
                    counters.add_resume(
                        stripes_resumed=1, shard_bytes=size,
                        tiles_resumed=n_blocks - max(bi, first_col_block),
                    )
                    continue
                s_ii, s_jj, s_dd = _compute_stripe(bi)
                all_ii.append(s_ii)
                all_jj.append(s_jj)
                all_dd.append(s_dd)
            if n_resumed:
                # report against the stripes THIS process owns: on multi-
                # process runs the global n_blocks would understate resume
                # progress ~pc-fold
                telemetry.event("resume", stripes=n_resumed, owned=n_owned)
                logger.info(
                    "streaming primary: resumed %d/%d owned row-block shards (process %d/%d)",
                    n_resumed, n_owned, pid, pc,
                )
        else:
            all_ii, all_jj, all_dd, pairs_computed = _elastic_stripe_loop(
                hb, checkpoint_dir, n_blocks, pc, pid, n_owned,
                _compute_stripe, lambda: pairs_computed, resume, logger,
                # candidate-aware dealing (ROADMAP LSH follow-on (c)):
                # under a pruned schedule the mirror-paired balance is
                # skewed by skip-heavy stripes — deal by occupied-tile
                # count instead (deal_stripes; ownership is pure
                # scheduling, so shards/assembly are untouched)
                weights=(
                    stripe_weights(occ, first_col_block)
                    if occ is not None
                    else None
                ),
            )

        if ft.quarantined():
            logger.warning(
                "streaming primary: finished with device slot(s) %s quarantined "
                "(of %d local devices) — see fault_tolerance counters",
                ft.quarantined(), len(devices),
            )
        _book_walk()
        with counters.span("primary/assemble"):
            ii = np.concatenate(all_ii) if all_ii else np.empty(0, np.int64)
            jj = np.concatenate(all_jj) if all_jj else np.empty(0, np.int64)
            dd = np.concatenate(all_dd) if all_dd else np.empty(0, np.float32)
        if pc > 1 and not elastic:
            ii, jj, dd, pairs_computed = _allgather_edges(ii, jj, dd, pairs_computed)
        return ii, jj, dd, pairs_computed
    except PodDrained as drained:
        # the attempt's record holds what it did up to the boundary
        drained.pairs = pairs_computed
        _book_walk()
        raise
    finally:
        if hb is not None:
            hb.close()


def _elastic_stripe_loop(
    hb,
    checkpoint_dir: str,
    n_blocks: int,
    pc: int,
    pid: int,
    n_owned: int,
    compute_stripe,
    own_pairs,
    resume: bool,
    logger,
    weights=None,
) -> tuple[list, list, list, int]:
    """The epoch-aware stripe loop + survivor-set gather (the elastic-pod
    tentpole). Returns (ii_parts, jj_parts, dd_parts, pairs_total) — the
    per-stripe edge arrays in the canonical healthy-run ordering, and the
    member-set pair total (this process's dispatched pairs plus every
    current done-note's — and, for members that left via a planned
    departure, their drain note's honest partial count; `own_pairs` reads
    the caller's running count, which `compute_stripe` advances).

    Every stripe's edges are durable in the shared shard store the moment
    it finishes, so completion needs no full-pod collective: each process
    (1) computes the missing stripes it owns under the CURRENT epoch's
    live list (:func:`deal_stripes` — mirror-paired, or occupied-tile-
    weighted under a pruned schedule; `weights`), re-dealing on every
    membership bump — deaths and DRAINS shrink the set, JOINS grow it —
    (2) publishes a done-note, (3) waits until every stripe has a shard
    and every live peer is done, and (4) reads the shards back in
    process-major epoch-0 order — the exact order the healthy jax
    allgather concatenates, so the final edge list is bit-identical to a
    fixed-membership run by construction (joiners take ids past the
    original process count precisely so this order never shifts).

    A drain request on THIS process (SIGTERM via install_drain_handler,
    or the chaos fault mode) is honored at stripe boundaries: the
    in-flight stripe's shard is already durable, the planned-departure
    note goes out with the honest pair count, and :class:`PodDrained`
    unwinds to an exit-0 — peers re-deal the rest with no staleness
    wait."""
    import time

    from drep_tpu.parallel.faulttol import (
        DEFAULT_ALLGATHER_TIMEOUT_S,
        CollectiveTimeout,
        PodDrained,
        collective_timeout_s,
        drain_requested,
    )

    def _maybe_drain() -> None:
        if not drain_requested():
            return
        hb.announce_drain(pairs=own_pairs())
        raise PodDrained(
            f"streaming primary: process {pid} drained at a stripe "
            f"boundary (planned-departure note published; peers re-deal "
            f"its unfinished stripes immediately)"
        )

    stall_budget = collective_timeout_s(DEFAULT_ALLGATHER_TIMEOUT_S)
    done_written = False
    last_progress = time.monotonic()
    progress_sig = None
    # stripes this process computed THIS call stay in memory (assembly
    # reads only peers'/resumed shards from the shared store — bit-equal
    # either way, the npz round-trip is lossless); FINISHED stripes are
    # cached so they are never re-statted, and the still-missing set is
    # re-probed once per cadence-scaled tick (bounded shared-FS traffic)
    mem: dict[int, tuple[np.ndarray, np.ndarray, np.ndarray]] = {}
    shard_of: dict[int, str] = {}

    def _missing_stripes() -> list[int]:
        out = []
        for b in range(n_blocks):
            if b in shard_of:
                continue
            p = _find_shard(checkpoint_dir, b)
            if p is not None:
                shard_of[b] = p
            else:
                out.append(b)
        return out

    if resume:
        _missing_stripes()  # one scan, kept: seeds shard_of for the loop
        n_resumed = sum(
            1 for b in shard_of if stripe_owner(b, n_blocks, pc) == pid
        )
        if n_resumed:
            telemetry.event("resume", stripes=n_resumed, owned=n_owned)
            logger.info(
                "streaming primary: resumed %d/%d owned row-block shards (process %d/%d)",
                n_resumed, n_owned, pid, pc,
            )

    last_deal_epoch = -1
    while True:
        _maybe_drain()
        live = list(hb.live)
        # ownership under the CURRENT membership: only stripes still
        # missing a shard are ever acted on, so a membership change can
        # never reassign (or recompute) work that is already durable
        owners = deal_stripes(n_blocks, live, weights)
        missing = _missing_stripes()  # ONE shared-FS scan per tick
        if hb.epoch != last_deal_epoch:
            if hb.epoch > 0:
                # the re-deal instant: this tick deals the still-missing
                # stripes under the CHANGED membership (causally after
                # the drain/death/join verdict and its epoch instant)
                telemetry.event(
                    "re_deal", unit="stripe", live=live, missing=len(missing)
                )
            last_deal_epoch = hb.epoch
        computed = False
        for bi in list(missing):
            if owners[bi] != pid:
                continue
            computed = True
            mem[bi] = compute_stripe(bi, epoch=hb.epoch)
            shard_of[bi] = os.path.join(checkpoint_dir, _shard_name(bi, hb.epoch))
            missing.remove(bi)
            _maybe_drain()  # the in-flight stripe is durable — safe exit
            if hb.maybe_check():
                break  # epoch bumped mid-pass: re-deal promptly
        if not missing and not done_written:
            # publish completion + honest pairs BEFORE anyone could see
            # this process's beats stop: a done-note peer is never dead.
            # (Once published this is final: the note only exists when
            # EVERY stripe has a shard, so no later death can reopen
            # compute work in this wait loop.)
            hb.mark_done(own_pairs())
            done_written = True
        waiting = (
            []
            if missing
            else [p for p in hb.live if p != pid and not hb.peer_finished(p)]
        )
        sig = (len(missing), tuple(hb.live), len(waiting))
        if computed or sig != progress_sig:
            progress_sig = sig
            last_progress = time.monotonic()
        if not missing and not waiting:
            break
        if hb.maybe_check():  # cadence-gated: detection latency is the
            continue  # miss window anyway; deaths re-deal with no sleep
        if time.monotonic() - last_progress > stall_budget:
            raise CollectiveTimeout(
                f"streaming elastic completion stalled for {stall_budget:.0f}s: "
                f"stripe(s) {missing[:8]}{'...' if len(missing) > 8 else ''} "
                f"unfinished, waiting on process(es) {waiting} of live set "
                f"{hb.live} whose heartbeats are still fresh — a peer is "
                f"wedged, not dead. Restart the pod; shard-level checkpoints "
                f"will resume finished work. (Timeout via "
                f"DREP_TPU_COLLECTIVE_TIMEOUT_S; heartbeat cadence via "
                f"DREP_TPU_HEARTBEAT_S.)"
            )
        if not computed:
            # pure wait (no owned work): still-missing stripes are
            # re-probed once per tick, so the tick scales with the
            # heartbeat cadence to bound shared-FS metadata traffic while
            # the slowest peer computes
            time.sleep(min(5.0, max(0.05, hb.cadence)))

    # canonical assembly: own computed stripes from memory, the rest from
    # the shard store. A shard that reads corrupt (disk trouble) — or
    # vanishes because a peer is healing the same corruption — is
    # recomputed locally INTO ITS OWN PATH (idempotent; heals even when
    # the remove fails) and assembly restarts.
    healed = False
    while True:
        all_ii: list[np.ndarray] = []
        all_jj: list[np.ndarray] = []
        all_dd: list[np.ndarray] = []
        bad = None  # (bi, corrupt path | None when a peer removed it)
        for p in range(pc):
            for bi in range(n_blocks):
                if stripe_owner(bi, n_blocks, pc) != p:
                    continue
                if bi in mem:
                    s_ii, s_jj, s_dd = mem[bi]
                else:
                    path = shard_of.get(bi) or _find_shard(checkpoint_dir, bi)
                    if path is None:
                        bad = (bi, None)
                        break
                    loaded = _load_shard(path)  # warns + removes on corrupt
                    if loaded is None:
                        bad = (bi, path)
                        break
                    s_ii, s_jj, s_dd = loaded
                all_ii.append(s_ii)
                all_jj.append(s_jj)
                all_dd.append(s_dd)
            if bad is not None:
                break
        if bad is None:
            break
        bi_bad, path_bad = bad
        shard_of.pop(bi_bad, None)
        # recompute INTO the corrupt shard's own path (heals even when its
        # remove failed); a vanished path means a peer is healing it —
        # recompute too, idempotently, at the current epoch
        heal_epoch = _shard_epoch(path_bad) if path_bad is not None else hb.epoch
        mem[bi_bad] = compute_stripe(bi_bad, epoch=heal_epoch)
        shard_of[bi_bad] = os.path.join(
            checkpoint_dir, _shard_name(bi_bad, heal_epoch)
        )
        healed = True

    if healed:
        # healing dispatched pairs AFTER the done-note was published —
        # refresh it so every survivor's pairs total converges on the
        # same numbers (peers that already summed keep the smaller count:
        # best-effort honesty, never an overcount)
        hb.mark_done(own_pairs())

    if hb.epoch > 0 and pid == min(hb.live):
        # the lowest live process stamps membership-churn provenance into
        # the store's meta: a later resume sees HOW these shards were
        # produced — deaths, planned departures, admitted joiners (extra
        # keys never invalidate the subset meta match)
        from drep_tpu.utils.ckptmeta import stamp_checkpoint_meta

        stamp = {"pod_epochs": hb.epoch + 1, "dead_processes": hb.dead}
        if hb.drained:
            stamp["planned_departures"] = hb.drained
        if hb.joined:
            stamp["pod_joins"] = len(hb.joined)
        stamp_checkpoint_meta(checkpoint_dir, stamp)
    if hb.epoch > 0:
        logger.warning(
            "streaming primary: completed with MEMBERSHIP CHURN — dead %s, "
            "drained %s, joined %s; final members %s finished the stripes "
            "across %d ownership epoch(s)",
            hb.dead, hb.drained, hb.joined, hb.live, hb.epoch + 1,
        )
    # member-set total: own dispatched pairs + every CURRENT done-note's,
    # plus the honest partial counts drained members left in their
    # departure notes (a member that DIED mid-stage takes its
    # uncheckpointed pair count with it — the counter stays honest about
    # who computed; previous-call notes never count). Joiners' done-notes
    # ride in all_members().
    def _peer_pairs(p: int) -> int:
        note = hb.done_payload(p)
        if note is None:
            note = hb.drain_payload(p)
        return int((note or {}).get("pairs", 0))

    pairs_total = own_pairs() + sum(
        _peer_pairs(p) for p in hb.all_members() if p != pid
    )
    return all_ii, all_jj, all_dd, pairs_total


def _cpu_fallback_tile(
    ids: np.ndarray,
    counts: np.ndarray,
    i0: int,
    j0: int,
    block: int,
    k: int,
    cutoff: float,
    diag: bool,
) -> tuple:
    """Recompute one tile on the host CPU via the jnp path — the last
    resort when retries are exhausted on every surviving device. Returns
    the same (ki, kj, dd, cnt, d_full) contract as the device compact.
    Edge membership and shipped distances derive from ONE CPU-computed
    array, so a fallback tile is self-consistent at the cutoff boundary
    (no mixed device/host libm provenance inside a tile)."""
    import jax

    a_counts = counts[i0 : i0 + block]
    b_counts = counts[j0 : j0 + block]
    with jax.default_device(jax.local_devices(backend="cpu")[0]):
        d, _j = mash_distance_tile(
            ids[i0 : i0 + block], a_counts, ids[j0 : j0 + block], b_counts, k=k
        )
        d = np.asarray(d)
    keep = d <= cutoff
    # pad rows carry count 0 — same mask the device compact applies
    keep &= (a_counts > 0)[:, None] & (b_counts > 0)[None, :]
    if diag:
        keep &= np.triu(np.ones_like(keep, dtype=bool), 1)  # i < j only
    ki, kj = np.nonzero(keep)
    return ki.astype(np.int32), kj.astype(np.int32), d[ki, kj], np.int32(len(ki)), d


def _allgather_edges(
    ii: np.ndarray, jj: np.ndarray, dd: np.ndarray, pairs_computed: int
) -> tuple[np.ndarray, np.ndarray, np.ndarray, int]:
    """Exchange per-process edge stripes so every process ends with the full
    edge set (clustering is replicated host work, each process needs all
    edges). process_allgather needs equal shapes across processes, so pad
    each stripe to the global max length, stack, and trim per true length.

    Dtype care: jax canonicalizes int64 host arrays to int32 (x64 is off),
    which would silently wrap `pairs_computed` (~5e9 at N=100k > 2^31) and
    downcast ii/jj. So 64-bit scalars ride as two uint32 halves, and ii/jj
    ride as uint32 (indices < N <= 2^31 by the packed-int32 id space; a
    per-process stripe of 2^32 edges is orders of magnitude past host
    memory, so lengths fit too).
    """
    from jax.experimental import multihost_utils as mhu

    from drep_tpu.parallel.faulttol import (
        DEFAULT_ALLGATHER_TIMEOUT_S,
        collective_timeout_s,
        run_with_timeout,
    )

    def _gather(arr: np.ndarray, what: str) -> np.ndarray:
        # watchdog'd collective: a peer that died must produce an
        # actionable error, not leave every survivor wedged forever. The
        # first-to-arrive process legitimately waits out its peers'
        # remaining STRIPE COMPUTE here (asymmetric resume; quarantine
        # slowdown), so the default timeout is the generous allgather one
        # — only a truly dead pod trips it (faulttol.py has the analysis)
        return np.array(
            run_with_timeout(
                lambda: mhu.process_allgather(arr),
                what=f"streaming edge allgather ({what})",
                site="allgather",
                timeout_s=collective_timeout_s(DEFAULT_ALLGATHER_TIMEOUT_S),
            )
        )

    def _split64(v: int) -> list[int]:
        return [v & 0xFFFFFFFF, v >> 32]

    def _join64(lo: int, hi: int) -> int:
        return int(lo) | (int(hi) << 32)

    header = np.array(_split64(len(ii)) + _split64(pairs_computed), np.uint32)
    g_head = _gather(header, "header")  # [pc, 4]
    lengths = [_join64(r[0], r[1]) for r in g_head]
    total_pairs = sum(_join64(r[2], r[3]) for r in g_head)
    m = max(lengths)
    if m == 0:
        return (
            np.empty(0, np.int64),
            np.empty(0, np.int64),
            np.empty(0, np.float32),
            total_pairs,
        )

    def _pad(a: np.ndarray) -> np.ndarray:
        out = np.zeros(m, a.dtype)
        out[: len(a)] = a
        return out

    g_ii, g_jj, g_dd = (
        _gather(_pad(a), what)
        for a, what in (
            (ii.astype(np.uint32), "ii"),
            (jj.astype(np.uint32), "jj"),
            (dd, "dist"),
        )
    )
    return (
        np.concatenate([g_ii[p][:c] for p, c in enumerate(lengths)]).astype(np.int64),
        np.concatenate([g_jj[p][:c] for p, c in enumerate(lengths)]).astype(np.int64),
        np.concatenate([g_dd[p][:c] for p, c in enumerate(lengths)]),
        total_pairs,
    )


def streaming_primary_clusters(
    packed: PackedSketches,
    k: int,
    p_ani: float,
    block: int = DEFAULT_BLOCK,
    checkpoint_dir: str | None = None,
    keep_dist: float = 0.0,
    cluster_alg: str = "average",
    ft_config=None,
    primary_prune: str = "off",
    prune_bands: int = 0,
    prune_min_shared: int = 0,
    prune_join_chunk: int = 0,
) -> tuple[np.ndarray, tuple[np.ndarray, np.ndarray, np.ndarray], int]:
    """Streaming primary clustering: (labels 1..C, retained edges, pairs
    actually computed this call).

    `primary_prune="lsh"` builds the LSH-banded candidate set at THIS
    call's retention bound (ops/lsh.py — candidates and edge retention
    derive from the same `keep`, so the recall-1.0 contract holds by
    construction) and hands the sparse tile bitmap to the edge walk;
    retained edges are bit-identical to the dense schedule's.

    Edges are retained up to max(1 - P_ani, keep_dist) — pass the evaluate
    stage's warn_dist so near-threshold winner pairs stay visible in the
    sparse Mdb. `cluster_alg`: 'average' (the reference default) clusters
    the retained edge graph with sparse UPGMA — every retained edge,
    including the (cutoff, keep] band, informs the averages, and
    unobserved pairs enter at their lower bound `keep`
    (ops/linkage.py::sparse_average_linkage — no silent single-linkage
    switch at scale); 'single' uses connected
    components at the cutoff (exactly single-linkage fcluster). Other
    scipy methods need the dense matrix — actionable error.
    """
    if cluster_alg not in ("single", "average"):
        # validate BEFORE the O(N^2) edge pass — the error must cost
        # nothing, not hours of streamed tiles
        raise ValueError(
            f"streaming primary supports --clusterAlg average or single, not "
            f"{cluster_alg!r} (other scipy methods need the dense distance "
            f"matrix — raise --streaming_threshold or drop --streaming_primary "
            f"to use the dense path)"
        )
    cutoff = 1.0 - p_ani
    keep = retention_bound(cutoff, keep_dist, cluster_alg)
    if keep > max(cutoff, keep_dist):
        # UPGMA's discriminating information IS the retention band beyond
        # the cutoff: with keep == cutoff every candidate's bound is
        # <= cutoff and the partition silently degenerates to connected
        # components (exactly the single-linkage over-merge this linkage
        # exists to prevent). retention_bound widened it (shared rule with
        # the incremental index) — warn so the operator knows why.
        get_logger().warning(
            "streaming average linkage needs edge retention beyond the "
            "%.3f cutoff to discriminate merges (--warn_dist was <= the "
            "cutoff); widening retention to %.3f",
            cutoff, keep,
        )
    if primary_prune not in ("off", "lsh"):
        raise ValueError(
            f"--primary_prune supports off or lsh, not {primary_prune!r}"
        )
    prune = None
    if primary_prune == "lsh":
        from drep_tpu.ops.lsh import build_candidates

        with counters.span("primary/lsh_join"):
            prune = build_candidates(
                packed, keep=keep, k=k, bands=prune_bands,
                min_shared=prune_min_shared, join_chunk=prune_join_chunk,
            )
    ii, jj, dd, pairs_computed = streaming_mash_edges(
        packed, k, keep, block=block, checkpoint_dir=checkpoint_dir,
        ft_config=ft_config, prune=prune,
    )
    from drep_tpu.ops.linkage import sparse_average_linkage, sparse_linkage_account

    with counters.span("primary/linkage", genomes=packed.n, tree="skipped") as sp:
        if cluster_alg == "single":
            in_cluster = dd <= cutoff
            labels = connected_components(packed.n, ii[in_cluster], jj[in_cluster])
            approx_merges = 0
        else:
            labels, approx_merges = sparse_average_linkage(
                packed.n, ii, jj, dd, cutoff, keep
            )
        # the record says what the linkage met, as the dense route's does:
        # a cell holds `uncertified_merges` to 0, a user reads the warning
        t0 = time.perf_counter()
        did = sparse_linkage_account(packed.n, ii, jj, dd, labels, cutoff, approx_merges)
        counters.add_primary_linkage(tree="skipped", **did)
        sp.note(
            account_s=round(time.perf_counter() - t0, 4),
            **{name: did[name] for name in ("components", "loose_components", "largest")},
        )
    if approx_merges:
        get_logger().warning(
            "streaming average linkage: %d accepted merges involved pairs "
            "beyond the %.3f retention bound (entered the averages at that "
            "lower bound) — the partition may over-merge relative to "
            "full-matrix UPGMA; raise --warn_dist to widen retention if "
            "this matters",
            approx_merges, keep,
        )
    return labels, (ii, jj, dd), pairs_computed
