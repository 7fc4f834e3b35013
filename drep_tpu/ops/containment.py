"""Containment ANI on device — the `jax_ani` secondary engine.

Replaces the reference's per-primary-cluster fastANI subprocess fan-out
(drep/d_cluster/external.py::run_pairwise_fastANI over multiprocessing.Pool,
SURVEY.md §3.2 hot loop #3; reference mount empty) with a sketch-based
containment estimator computed entirely on device:

- host: FracMinHash ("scaled") sketches — all k-mer hashes below 2^64/scale
  — so sketch size tracks genome size and containment |A∩B|/|A| is estimable.
  Hashes are mapped to a dense int32 id space (see ops/minhash.py for why
  that is exact on a 64-bit-hash / 32-bit-device gap).
- device: per pair, intersection size via ``searchsorted`` of row A's sorted
  ids in row B's (O(S log S), static shapes, vmapped over pair tiles).

ANI model: containment C = |A∩B|/|A| estimates (1-p)^k under the iid
substitution model, so ``ANI = max(C(A,B), C(B,A))^(1/k)`` — MAX
containment (cf. sourmash ANI). The max matters under genome-size
asymmetry: when B carries content A lacks, the smaller side's containment
reflects the substitution divergence while the larger side's is diluted by
the extra content; fastANI's fragment-identity ANI tracks the former, so
concordance requires the max. The resulting ani matrix is symmetric —
exactly the reference's ANIn contract (one nucmer run, shared ani, two
coverages). C itself stays DIRECTIONAL as the alignment-fraction proxy for
the reference's two-sided ``cov_thresh`` gate (pairs with coverage <
cov_thresh in either direction get similarity zeroed, as in the
reference's Ndb post-processing).

Triangle-only execution (ISSUE 1): every all-vs-all path here ships the
SYMMETRIC raw intersection size |A∩B| from the device and derives both
cov directions (and the ani) from ``counts`` on host — so each engine
computes only canonical upper-triangle tiles/blocks and host-mirrors the
transposed rest, exactly equal to the full grid at ~half the device work.
"""

from __future__ import annotations

import functools
from concurrent.futures import ThreadPoolExecutor

import jax
import jax.numpy as jnp
import numpy as np

from drep_tpu.ops.minhash import (
    PAD_ID,
    PackedSketches,
    _fill_padded_rows,
    _usable_cores,
    pad_packed_rows,
    rank_route,
    rank_rows_padded,
)
from drep_tpu.utils.profiling import counters


def pack_scaled_sketches(
    sketches: list[np.ndarray], names: list[str], pad_multiple: int = 128, workers: int = 1
) -> PackedSketches:
    """Ragged uint64 scaled sketches -> padded int32 id matrix [N, S].

    S = max sketch length rounded up to a power of two (>= `pad_multiple`):
    lane-friendly AND compile-stable — a linear pad multiple gave every
    batch its own width and thus its own XLA compilation (see
    :func:`_pow2_bucket`).

    An id is a hash's rank in the sorted vocabulary of ALL rows. The ranks
    come from the primary pack's kernel (native/rank.cc, by
    :func:`~drep_tpu.ops.minhash.rank_route`) on up to `workers` threads,
    the width the caller grants (the job's `-p`); the matrix is the same
    bytes at any width and by the NumPy lines below, which serve without
    the library and are the tests' oracle.
    """
    if not sketches:
        raise ValueError("no sketches to pack")
    lens = np.array([len(s) for s in sketches], dtype=np.int64)
    width = _pow2_bucket(max(int(lens.max()), 1), pad_multiple)
    path, threads = rank_route(int(lens.sum()), workers)
    # (rows of another dtype are NumPy's to promote and order, as they were)
    if path == "native" and all(s.dtype == np.uint64 for s in sketches):
        # one `np.unique` and one binary search a hash into its result
        # (every level a cache miss once the vocabulary leaves the cache)
        # took 3.0 s at 2.6e7 hashes of 1.6M ids on one core (ISSUE 44)
        ids = rank_rows_padded(sketches, width, threads)
        return PackedSketches(ids=ids, counts=lens.astype(np.int32), names=list(names))
    flat = np.concatenate(sketches)
    vocab = np.unique(flat)
    if vocab.size >= np.iinfo(np.int32).max:
        raise ValueError("id space overflow: >2^31 distinct sketch hashes")
    ids = np.full((len(sketches), width), PAD_ID, dtype=np.int32)
    # ONE searchsorted over the concatenation — a per-row SEARCH was a
    # measured hot spot at thousands of clusters/batches per run. The fill
    # below does loop over rows: a slice copy per row costs microseconds
    _fill_padded_rows(ids, np.searchsorted(vocab, flat), lens)
    return PackedSketches(ids=ids, counts=lens.astype(np.int32), names=list(names))


def pack_secondary(
    sketches: list[np.ndarray], names: list[str], processes: int = 1, calls: int = 1
) -> PackedSketches:
    """`pack_scaled_sketches` for a secondary compare's shared-vocabulary
    pack (the per-cluster engine, the greedy engine, the batched route's
    fallback), under the `secondary/pack` span and booked in the record's
    `secondary_pack`. The span's args say what the pack ranks (`hashes=`)
    and how: `path=` native | numpy, on `workers=` threads of the job's
    `-p` (`processes`); `calls` is the clusters the one pack serves."""
    hashes = sum(len(s) for s in sketches)
    path, threads = rank_route(hashes, processes)
    with counters.span("secondary/pack", calls=calls, hashes=hashes, path=path, workers=threads):
        packed = pack_scaled_sketches(sketches, names, workers=processes)
    counters.add_secondary_pack(
        rows=packed.n, hashes=hashes, native=path == "native", threads=threads
    )
    return packed


def clusterlocal_pack_workers(processes: int, n_groups: int) -> int:
    """Threads :func:`pack_scaled_sketches_clusterlocal` ranks clusters on:
    dRep's own `-p/--processes`, capped by the clusters there are and the
    cores this process may use. 1 means inline, no pool."""
    return max(1, min(int(processes), n_groups, _usable_cores()))


def _rank_cluster(group: list[np.ndarray]) -> tuple[np.ndarray, int]:
    """(dense ranks of every hash of `group` into the cluster's own sorted
    vocabulary, in member order back to back; the vocabulary's size).
    A pure function of the cluster's sketches: clusters rank side by side
    on a thread pool (numpy's sort, searchsorted and copies release the
    GIL) and the result cannot depend on the pool."""
    flat = np.concatenate(group) if group else np.zeros(0, np.uint64)
    # the vocabulary is np.unique(flat), spelled so that the sort is the
    # stable one: it merges the members' already-sorted runs. Of the
    # spellings timed on the chip machine's host (PERF.md section 6,
    # PR 25) this is the fastest at the CLI's six workers.
    # NOT native/rank.cc, which ranks the shared-vocabulary pack above
    # (ROADMAP D16: two spellings, one kernel, one oracle): the clusters
    # already rank one a pool thread, so the kernel would run on one
    # thread each, and there it loses at the few rows most clusters hold
    # and gains little above (chip host, rows of 25,800 hashes, PERF.md
    # section 6, PR 44: 4 rows 3.0 against these lines' 2.6 ms, 16 rows
    # 11.5 against 13.3, 32 rows 23.2 against 31.2); and it writes a
    # padded int32 matrix where this pack narrows to uint16
    srt = np.sort(flat, kind="stable")
    first = np.ones(len(srt), dtype=bool)
    np.not_equal(srt[1:], srt[:-1], out=first[1:])
    vocab = srt[first]
    if vocab.size >= np.iinfo(np.int32).max:
        raise ValueError("id space overflow: >2^31 distinct sketch hashes")
    ranks = np.searchsorted(vocab, flat)
    # narrow in the worker, so that what waits for the fill is 2 bytes a
    # rank and the fill of a uint16 matrix is a plain copy
    return ranks.astype(np.uint16 if vocab.size < 0xFFFF else np.int32), int(vocab.size)


def pack_scaled_sketches_clusterlocal(
    sketch_groups: list[list[np.ndarray]],
    names: list[str],
    pad_multiple: int = 128,
    processes: int = 1,
) -> tuple[PackedSketches, int]:
    """Pack MANY clusters into one id matrix with per-cluster-LOCAL dense
    id spaces: cluster c's ids are ranks into c's OWN vocabulary, so every
    cluster shares the same narrow [0, v_extent) range.

    This is the production-depth fix for the batched small-cluster
    secondary: a
    shared-vocabulary pack of 512 rows of ~20k-wide sketches unions to a
    multi-million-id vocabulary (mostly private hash space across
    unrelated clusters) and forces the chunked kernels, yet only the
    per-cluster DIAGONAL blocks of the intersection matrix are ever read.
    With cluster-local remapping the joint vocabulary extent is the MAX
    single-cluster vocabulary (~tens of thousands: primary clustering
    guarantees members are Mash-similar, so their sketches overlap), and
    one one-shot indicator matmul serves the whole batch. Cross-cluster
    blocks contain id collisions and are GARBAGE by construction — callers
    must read diagonal blocks only.

    `processes` (dRep's `-p`) bounds the thread pool the clusters are
    ranked on (:func:`clusterlocal_pack_workers`); the output does not
    depend on it.

    Returns (packed, v_extent): `v_extent` = max cluster vocabulary size
    (the honest extent for budget checks; `vocab_extent(packed.ids)` would
    under-report when the widest cluster's top ids are unused).
    """
    if not sketch_groups:
        raise ValueError("no clusters to pack")
    # rank first (the matrix's dtype depends on every cluster's vocabulary),
    # then allocate and fill: one search per GROUP over its concatenation
    # (a per-row search was a measured hot spot at production cluster counts)
    workers = clusterlocal_pack_workers(processes, len(sketch_groups))
    if workers > 1:
        with ThreadPoolExecutor(max_workers=workers) as pool:
            ranked = list(pool.map(_rank_cluster, sketch_groups))
    else:
        ranked = [_rank_cluster(group) for group in sketch_groups]
    v_extent = max(1, max(v for _, v in ranked))
    lens_arr = np.array([len(s) for group in sketch_groups for s in group], dtype=np.int64)
    n = len(lens_arr)
    width = _pow2_bucket(max(int(lens_arr.max()) if n else 1, 1), pad_multiple)
    # link compression: ranks < v_extent, so when every cluster vocabulary
    # fits 16 bits the pack ships as uint16 (0xFFFF pad) — HALF the
    # host->device bytes of the production batched secondary, widened on
    # device by _intersect_matmul. 0xFFFE bound keeps the sentinel free.
    if v_extent < 0xFFFF:
        ids = np.full((n, width), np.uint16(0xFFFF), dtype=np.uint16)
    else:
        ids = np.full((n, width), PAD_ID, dtype=np.int32)
    r = 0
    for group, (ranks, _) in zip(sketch_groups, ranked):
        # ranks of a sorted-unique sketch are sorted
        _fill_padded_rows(ids[r : r + len(group)], ranks, lens_arr[r : r + len(group)])
        r += len(group)
    return (
        PackedSketches(ids=ids, counts=lens_arr.astype(np.int32), names=list(names)),
        v_extent,
    )


def _pair_intersection(a: jnp.ndarray, b: jnp.ndarray) -> jnp.ndarray:
    """|A ∩ B| for two sorted, PAD_ID-padded int32 rows (static shapes)."""
    idx = jnp.searchsorted(b, a)
    idx = jnp.clip(idx, 0, b.shape[0] - 1)
    hit = (b[idx] == a) & (a != PAD_ID)
    return jnp.sum(hit.astype(jnp.int32))


@jax.jit
def containment_inter_tile(a_ids, b_ids):
    """SYMMETRIC intersection-size tile between sketch blocks:
    inter[i,j] = |A_i ∩ B_j| (int32, exact). This is the payload the
    triangular schedules ship — tile(A, B) == tile(B, A).T bit-exactly
    (set intersection is symmetric), so mirrored blocks are transposed
    copies, never recomputed. cov/ani derive from counts on host
    (:func:`ani_cov_from_intersections`)."""
    row = jax.vmap(_pair_intersection, in_axes=(None, 0))
    return jax.vmap(row, in_axes=(0, None))(a_ids, b_ids)


def containment_to_ani(c, k: int, xp=np):
    """Elementwise containment -> ANI transform (c^(1/k); 0 stays 0). ONE
    formula for every engine path and the greedy row math (`xp` selects
    jnp on device, np on host) so the estimators cannot drift."""
    return xp.where(c > 0.0, xp.exp(xp.log(xp.maximum(c, 1e-30)) / k), 0.0).astype(
        xp.float32
    )


def max_containment_ani(cov: np.ndarray, k: int) -> np.ndarray:
    """Symmetric ANI matrix from directional containment (see module
    docstring for why MAX): ani[i,j] = max(cov[i,j], cov[j,i])^(1/k),
    diagonal pinned to 1."""
    ani = containment_to_ani(np.maximum(cov, cov.T), k)
    np.fill_diagonal(ani, 1.0)
    return ani


@functools.partial(jax.jit, static_argnames=("k",))
def containment_cov_tile(a_ids, a_counts, b_ids, *, k: int = 21):
    """Directional coverage tile between sketch blocks: cov[i,j] =
    C(A_i, B_j) = |A∩B|/|A| (query side i). ANI derives from the FULL cov
    matrix afterwards (max_containment_ani needs both directions, which a
    single rectangular tile does not hold). `k` rides along only to keep
    one cache key shape with the other tile kernels."""
    del k

    def one_pair(a, na, b):
        inter = _pair_intersection(a, b)
        return jnp.where(na > 0, inter / jnp.maximum(na, 1), 0.0).astype(jnp.float32)

    row = jax.vmap(one_pair, in_axes=(None, None, 0))
    tile = jax.vmap(row, in_axes=(0, 0, None))
    return tile(a_ids, a_counts, b_ids)


# budget for the dense indicator matrix [m, V] in int8 (elements, ~512 MB —
# small next to 16 GB HBM; int8 halved the per-element cost of the old bf16
# indicator, so the budget doubled with it. It exists to bound the
# indicator's HBM footprint + zero-fill, not the MXU: a realistic 512-genome
# production cluster at width 32768 has a ~400k-id vocabulary and must stay
# on the one-shot path)
MATMUL_BUDGET_ELEMS = 1 << 29
_VOCAB_BUCKET_MIN = 8192


def _pow2_bucket(x: int, minimum: int) -> int:
    """Round up to a power of two (>= minimum). Shape buckets are pow2, not
    linear: every distinct (rows, width, vocab) triple is a fresh XLA
    compilation at ~5-10 s on TPU, which dominated end-to-end wall-clock
    when thousands of per-cluster batches each got their own shapes. Pow2
    wastes <=2x MXU work (microseconds) to cap compiles at a handful."""
    return max(minimum, 1 << (max(x, 1) - 1).bit_length())

# cap on tile*tile*row_width elements for batched-gather tiles: oversized
# gathers have been observed to hard-crash the TPU runtime (not OOM — a
# worker fault), so every gather-tile path must respect this
GATHER_BUDGET_ELEMS = 1 << 26


def cap_gather_tile(row_width: int, tile: int, budget: int = GATHER_BUDGET_ELEMS) -> int:
    """Largest power-of-two tile with tile^2 * row_width <= budget (min 8)."""
    cap = max(8, int((float(budget) / max(row_width, 1)) ** 0.5))
    return min(tile, 1 << (cap.bit_length() - 1))


def matmul_vocab_pad_extent(extent: int) -> int:
    """Bucketed indicator width for a known vocabulary extent — THE
    pow2/floor rule every caller that already holds an extent (the
    cluster-local batched pack) must share with :func:`matmul_vocab_pad`."""
    return _pow2_bucket(max(extent, 1), _VOCAB_BUCKET_MIN)


def matmul_vocab_pad(packed: PackedSketches) -> int:
    """Bucketed indicator width for the MXU path (one scan of packed.ids).

    The budget check and the kernel must use the SAME padded width — the
    raw vocab can be far below the bucket size.
    """
    from drep_tpu.ops.rangepart import vocab_extent

    return matmul_vocab_pad_extent(vocab_extent(packed.ids))


def one_shot_fits(n_rows: int, v_pad: int) -> bool:
    """Whether the [rows, v_pad(+trash)] indicator fits the one-shot
    budget — THE dispatch inequality (containment_matrices and the
    batched engine read this one definition so the budget rule cannot
    drift between them)."""
    return matmul_rows_pad(n_rows) * (v_pad + 1) <= MATMUL_BUDGET_ELEMS


@functools.partial(jax.jit, static_argnames=("v_pad", "dtype"))
def _intersect_matmul_jit(ids, *, v_pad: int, dtype):
    from drep_tpu.ops.minhash import widen_ids_device

    ind = _indicator(widen_ids_device(ids), v_pad, dtype)
    return _int_dot(ind, ind)


def _intersect_matmul(ids, *, v_pad: int):
    """Intersection counts as an MXU matmul of 0/1 indicator rows.

    inter[i,j] = |A_i ∩ A_j| = <ind_i, ind_j> over the id vocabulary —
    exact integer counts on both backends (dtype dispatch and exactness
    bounds in :func:`_indicator_dtype`). This is where
    the systolic array earns its keep: one [m, V] x [V, m] matmul
    replaces m^2 searchsorted passes. Returns int32 counts: the device
    ships ONE integer matrix and the cov/ani elementwise math runs on host.
    """
    dtype = _indicator_dtype(ids.shape[1])
    return _intersect_matmul_jit(ids, v_pad=v_pad, dtype=dtype)


def tri_row_block(m_pad: int) -> int:
    """Row-block size of the triangular (upper-block) matmul schedule:
    a power of two dividing the pow2-bucketed `m_pad`, targeting 8 block
    rows. 8 blocks put the canonical-block FLOPs at (8*9/2)/64 ≈ 56% of
    the full grid while keeping the per-call dot count single-digit (the
    asymptotic 50% needs many narrow matmuls, which trade MXU efficiency
    for diminishing block savings)."""
    return max(ROW_BUCKET_MIN, m_pad // 8)


@functools.partial(jax.jit, static_argnames=("v_pad", "dtype", "tb"))
def _intersect_matmul_tri_jit(ids, *, v_pad: int, dtype, tb: int):
    """Upper-block-triangle variant of :func:`_intersect_matmul_jit`:
    ONE indicator build, then per canonical row block `bi` a single rect
    dot against all columns from that block onward — exactly the
    (bi <= bj) blocks, ~half the MXU FLOPs. Intersections are symmetric,
    so the skipped lower blocks are transposes the HOST mirrors in
    (:func:`mirror_lower_blocks`); counts are exact integers, so the
    mirrored matrix is bit-equal to the full matmul's."""
    from drep_tpu.ops.minhash import widen_ids_device

    ind = _indicator(widen_ids_device(ids), v_pad, dtype)
    m = ind.shape[0]
    out = jnp.zeros((m, m), jnp.int32)
    for lo in range(0, m, tb):
        out = out.at[lo : lo + tb, lo:].set(_int_dot(ind[lo : lo + tb], ind[lo:]))
    return out


def _intersect_matmul_tri(ids, *, v_pad: int):
    """Triangular-schedule twin of :func:`_intersect_matmul`: returns the
    upper-block-triangle count matrix (lower blocks zero — callers mirror
    with :func:`mirror_lower_blocks`)."""
    dtype = _indicator_dtype(ids.shape[1])
    return _intersect_matmul_tri_jit(
        ids,
        v_pad=v_pad,
        dtype=dtype,
        tb=tri_row_block(ids.shape[0]),
    )


def mirror_lower_blocks(mat: np.ndarray, tb: int) -> np.ndarray:
    """Fill the strictly-lower block triangle of a block-upper-triangular
    symmetric matrix with the transposed upper blocks, in place (the host
    half of the triangular matmul schedule)."""
    for lo in range(tb, mat.shape[0], tb):
        mat[lo : lo + tb, :lo] = mat[:lo, lo : lo + tb].T
    return mat


def _count_tri_tiles(m_pad: int, tb: int) -> None:
    """Record the triangular matmul schedule into the secondary-stage tile
    counters: B*(B+1)/2 canonical blocks of the B^2 grid."""
    b = m_pad // tb
    counters.add_tiles("secondary_compare", computed=b * (b + 1) // 2, total=b * b)


def ani_cov_from_intersections(
    inter: np.ndarray, counts: np.ndarray, k: int
) -> tuple[np.ndarray, np.ndarray]:
    """Host: (symmetric max-containment ani, directional cov) from
    intersection counts. cov = |A∩B|/|A|; diagonals pinned to 1."""
    na = np.maximum(counts.astype(np.float32), 1.0)
    cov = (inter.astype(np.float32) / na[:, None]).astype(np.float32)
    ani = max_containment_ani(cov, k)
    np.fill_diagonal(cov, 1.0)
    return ani, cov


ROW_BUCKET_MIN = 64  # smallest row bucket (pow2 above; see _pow2_bucket)


def matmul_rows_pad(n: int) -> int:
    """Row count the MXU path actually allocates for n genomes — THE
    definition the dispatch budget check must use (kept next to the kernel
    so the two cannot drift)."""
    return _pow2_bucket(n, ROW_BUCKET_MIN)


def all_vs_all_containment_matmul(
    packed: PackedSketches, k: int = 21, v_pad: int | None = None,
    triangular: bool = True,
) -> tuple[np.ndarray, np.ndarray]:
    """MXU path for the directional (ani, cov) matrices. Use when
    m * (v_pad+1) fits MATMUL_BUDGET_ELEMS; exact-equal to the searchsorted
    path (verified in tests). Pass a precomputed `v_pad` (from
    :func:`matmul_vocab_pad`) to avoid rescanning packed.ids.

    `triangular` (default) runs only the canonical (bi <= bj) row blocks
    of the intersection matmul and mirrors the rest on host — bit-equal
    output (integer counts are symmetric) at ~half the MXU FLOPs; False
    keeps the one-shot full matmul as the equality reference.

    Rows are padded to a pow2 bucket before the jit call: the secondary
    stage runs once per primary cluster/batch, and without bucketing every
    distinct cluster size would trigger a fresh XLA compilation (~5-10 s
    each on TPU). Sketch width is already bucketed by
    pack_scaled_sketches, the vocab by matmul_vocab_pad."""
    if v_pad is None:
        v_pad = matmul_vocab_pad(packed)
    m = packed.n
    # padding to the matmul_rows_pad target itself (>= m) gives that exact
    # row count — the same number the dispatch budget check used
    with counters.span("secondary/pack"):
        ids, _ = pad_packed_rows(packed.ids, packed.counts, matmul_rows_pad(m))
    shape = {"rows": ids.shape[0], "width": ids.shape[1], "v_pad": v_pad}
    if triangular:
        # transfer, dispatch and blocking readback of the one call.
        # np.array (not asarray): the host mirror mutates, and a device
        # array's __array__ view is not guaranteed writable
        with counters.span("secondary/wait", **shape):
            inter_pad = np.array(_intersect_matmul_tri(jnp.asarray(ids), v_pad=v_pad))
        with counters.span("secondary/post"):
            tb = tri_row_block(ids.shape[0])
            mirror_lower_blocks(inter_pad, tb)
            _count_tri_tiles(ids.shape[0], tb)
            return ani_cov_from_intersections(inter_pad[:m, :m], packed.counts, k)
    with counters.span("secondary/wait", **shape):
        inter = np.asarray(_intersect_matmul(jnp.asarray(ids), v_pad=v_pad))[:m, :m]
    with counters.span("secondary/post"):
        return ani_cov_from_intersections(inter, packed.counts, k)


def matmul_vocab_chunk(m_pad: int) -> int:
    """Widest pow2 vocabulary chunk whose [m_pad, chunk+1] int8 indicator
    fits MATMUL_BUDGET_ELEMS (>= _VOCAB_BUCKET_MIN)."""
    fit = max(MATMUL_BUDGET_ELEMS // max(m_pad, 1) - 1, 1)
    return max(_VOCAB_BUCKET_MIN, 1 << (fit.bit_length() - 1))




def _indicator_dtype(width: int):
    """Indicator element dtype: int8 on EVERY backend.

    TPU: the v5e int8 MXU runs 2x its bf16 rate (measured 24% faster end
    to end than bf16 at the production chunk shape, scatter included);
    int32 accumulation is exact at any count.

    CPU: int8 also wins — a negative result worth recording. A GEMM-only
    microbenchmark shows XLA:CPU's f32 GEMM 5.4x FASTER than its int8 GEMM
    on a pre-built [256, 65536] indicator, which suggested dispatching f32
    off-TPU; but the kernel the engine actually runs fuses the indicator
    SCATTER with the dot, and the f32 indicator's 4x bytes make the fused
    kernel 4-7x slower than int8 at every shape measured (17M..268M
    elements, r4 session). Don't re-split this by platform without timing
    the fused kernel, not the GEMM.

    `DREP_TPU_INDICATOR_DTYPE` overrides for experiments; the float32
    override is exact only while counts (bounded by the packed row width)
    stay below 2^24, checked here (a real raise, not an assert — -O must
    not turn an exactness violation into silent wrong counts).
    """
    from drep_tpu.utils import envknobs

    forced = envknobs.env_str("DREP_TPU_INDICATOR_DTYPE")
    if forced in (None, "", "int8"):
        return jnp.int8
    if forced == "float32":
        if width >= (1 << 24):
            raise ValueError(
                f"packed width {width} overflows exact f32 indicator accumulation"
            )
        return jnp.float32
    # an unknown value must not silently measure the int8 path
    raise ValueError(
        f"DREP_TPU_INDICATOR_DTYPE={forced!r}: expected 'int8' or 'float32'"
    )


def _indicator(ids, v_pad: int, dtype):
    """[m, v_pad] 0/1 indicator from PAD-padded id rows — THE build every
    MXU intersection kernel shares: an XLA scatter into a trash column
    (ids >= v_pad, PAD_ID included, contribute nothing). On a v5e the
    build is nearly all of a one-shot call — 0.120 s of 0.121 s at
    [512, 32768] ids / 65536 vocabulary (PERF.md, PR 21); a Pallas VMEM
    scatter kernel written to replace it measured slower (0.165 s) and
    was removed (ROADMAP S2)."""
    m, s = ids.shape
    # the scope names the scatter (and the sort XLA lowers it through) in
    # an operator's trace viewer; PERF.md section 3 says where it shows
    with jax.named_scope("drep_indicator_scatter"):
        rows = jax.lax.broadcasted_iota(jnp.int32, (m, s), 0)
        cols = jnp.where(ids != PAD_ID, ids, v_pad)
        return jnp.zeros((m, v_pad + 1), dtype).at[rows, cols].set(1)[:, :v_pad]


def _int_dot(a, b_t):
    """Exact int32 intersection counts from two indicator matrices,
    contracting the vocabulary axis — int32 accumulation for int8 inputs,
    f32 dot + cast for f32 inputs (exact under _indicator_dtype's width
    bound)."""
    with jax.named_scope("drep_indicator_dot"):
        if a.dtype == jnp.int8:
            return jax.lax.dot_general(
                a, b_t, (((1,), (1,)), ((), ())), preferred_element_type=jnp.int32
            )
        return jax.lax.dot_general(
            a, b_t, (((1,), (1,)), ((), ()))
        ).astype(jnp.int32)


@functools.partial(jax.jit, static_argnames=("v_pad", "dtype"))
def _intersect_matmul_rect_jit(a_ids, b_ids, *, v_pad: int, dtype):
    return _int_dot(_indicator(a_ids, v_pad, dtype), _indicator(b_ids, v_pad, dtype))


def _intersect_matmul_rect(a_ids, b_ids, *, v_pad: int):
    """Rectangular intersection counts |A_i ∩ B_j| — two indicator
    builds, one MXU matmul contracting the vocabulary axis. The greedy
    path's block-vs-representatives comparisons run here on TPU instead of
    through gather tiles (batched gathers serialize on the scalar unit —
    the measured ~70x penalty noted in ops/minhash.py)."""
    from drep_tpu.ops.minhash import require_int32_ids

    # dtype-only checks: no host pull of device operands
    require_int32_ids(a_ids, "_intersect_matmul_rect")
    require_int32_ids(b_ids, "_intersect_matmul_rect")
    dt = _indicator_dtype(max(a_ids.shape[1], b_ids.shape[1]))
    return _intersect_matmul_rect_jit(a_ids, b_ids, v_pad=v_pad, dtype=dt)


class VocabChunkGeometry:
    """Per-cluster vocabulary-chunk layout for incremental rectangular
    intersections (the greedy path's working set).

    The chunk boundaries, per-chunk widths, and every row's chunk slices
    are fixed up front from the FULL cluster id matrix, so any subset of
    rows can be repacked into aligned chunk tensors in O(rows) host work —
    and an append-only subset (the greedy representative set) can live as
    device-resident per-chunk tensors that only ever receive NEW rows
    (host->device traffic O(total reps), not O(reps x blocks); rebuilding
    and re-shipping the whole rep set each block was the measured waste
    this class removes).
    """

    def __init__(self, ids: np.ndarray, max_rows_per_call: int):
        from drep_tpu.ops.minhash import require_int32_ids
        from drep_tpu.ops.rangepart import MIN_BUCKET_WIDTH, bucket_starts, vocab_extent

        require_int32_ids(ids, "VocabChunkGeometry")
        self.ids = ids
        extent = vocab_extent(ids)
        # budget covers BOTH operands of a rectangular call at the stated
        # row bound — callers must tile anything larger (greedy tiles its
        # representative side at a fixed row count for exactly this)
        fit = max(MATMUL_BUDGET_ELEMS // max(2 * matmul_rows_pad(max_rows_per_call), 1) - 1, 1)
        self.v_chunk = max(_VOCAB_BUCKET_MIN, 1 << (fit.bit_length() - 1))
        self.n_chunks = max(1, -(-extent // self.v_chunk))
        self.starts = bucket_starts(ids, self.v_chunk, self.n_chunks)
        hist = np.diff(self.starts, axis=1)
        # per-chunk width = max count over ALL cluster rows: any subset
        # fits, so chunk tensors never need re-widening
        self.widths = [
            _pow2_bucket(int(hist[:, c].max()), MIN_BUCKET_WIDTH)
            for c in range(self.n_chunks)
        ]
        self.hist = hist

    def rows_chunks(self, rows: list[int] | np.ndarray) -> list[np.ndarray]:
        """[len(rows), W_c] rebased chunk tensor per chunk, for any subset."""
        from drep_tpu.ops.rangepart import repack_bucket

        sub = self.ids[rows] if len(rows) else self.ids[:0]
        out = []
        for c in range(self.n_chunks):
            out.append(
                repack_bucket(
                    sub,
                    self.starts[rows, c] if len(rows) else np.zeros(0, np.int64),
                    self.hist[rows, c] if len(rows) else np.zeros(0, np.int64),
                    self.widths[c],
                    rebase=c * self.v_chunk,
                )
            )
        return out


def rect_from_chunks(a_chunks, b_chunks, v_chunk: int) -> np.ndarray:
    """Σ_c |A∩B| over aligned chunk tensors (device arrays or numpy);
    partials accumulate on device, one transfer returns int32 [na, nb]."""
    acc = None
    for a_c, b_c in zip(a_chunks, b_chunks):
        part = _intersect_matmul_rect(jnp.asarray(a_c), jnp.asarray(b_c), v_pad=v_chunk)
        acc = part if acc is None else acc + part
    return np.asarray(acc)


def self_from_chunks(chunks, v_chunk: int) -> np.ndarray:
    """Σ_c |A∩A| over one side's chunk tensors — ONE indicator build per
    chunk instead of rect_from_chunks' two (the operands are identical;
    the greedy block self-comparison was paying a second build per block
    for no information)."""
    acc = None
    for c in chunks:
        part = _intersect_matmul(jnp.asarray(c), v_pad=v_chunk)
        acc = part if acc is None else acc + part
    return np.asarray(acc)


@functools.lru_cache(maxsize=None)
def _rect_sharded_fn(v_pad: int, dtype_name: str, mesh):
    """One jitted shard_map program per (v_pad, dtype, mesh):
    A rows sharded over the mesh axis, B replicated, each device building
    its shard's indicators locally and contracting on its own MXU — no
    collectives at all (the output stays row-sharded until the host
    gather). Follows parallel/allpairs.py's per-mesh lru_cache pattern."""
    import jax
    from jax.sharding import PartitionSpec as P

    from drep_tpu.parallel.mesh import AXIS

    dtype = {"int8": jnp.int8, "float32": jnp.float32}[dtype_name]

    def body(a, b):
        return _int_dot(_indicator(a, v_pad, dtype), _indicator(b, v_pad, dtype))

    return jax.jit(
        jax.shard_map(
            body, mesh=mesh, in_specs=(P(AXIS, None), P(None, None)),
            out_specs=P(AXIS, None),
        )
    )


def replicate_on_mesh(arr: np.ndarray, mesh):
    """Device-put a host array replicated across every mesh device — for
    append-only operand caches (greedy's filled rep tiles) that should
    cross the link once, not once per block."""
    from jax.sharding import NamedSharding, PartitionSpec as P

    from drep_tpu.parallel.allpairs import put_global

    return put_global(arr, NamedSharding(mesh, P(None, None)))


def shard_rows_on_mesh(arr: np.ndarray, mesh):
    """Device-put a host array with its rows split over the mesh devices —
    the layout of `rect_from_chunks_sharded`'s A side (the row count must
    divide the mesh size)."""
    from jax.sharding import NamedSharding, PartitionSpec as P

    from drep_tpu.parallel.allpairs import put_global
    from drep_tpu.parallel.mesh import AXIS

    return put_global(arr, NamedSharding(mesh, P(AXIS, None)))


def rect_from_chunks_sharded(a_chunks, b_chunks, v_chunk: int, mesh) -> np.ndarray:
    """`rect_from_chunks` with the A rows sharded across a device mesh and
    B replicated — the greedy engine's candidate-block parallelism
    (BASELINE config 5: 100k greedy dereplicate on a multi-chip mesh).
    A's row count must divide the mesh size (callers pad blocks to a
    device multiple). Either side's chunks may be host arrays (shipped
    here: A row-sharded, B replicated) or device arrays already laid out
    so (:func:`shard_rows_on_mesh`, :func:`replicate_on_mesh`; zero link
    traffic, and a caller that books its transfers makes them itself). The
    result gathers via the multi-host-safe allgather path, not np.asarray
    (remote shards have no local buffers on a pod)."""
    import jax

    from drep_tpu.parallel.allpairs import gather_global

    dt = _indicator_dtype(max(a_chunks[0].shape[1], b_chunks[0].shape[1]))
    fn = _rect_sharded_fn(v_chunk, str(np.dtype(dt)), mesh)
    acc = None
    for a_c, b_c in zip(a_chunks, b_chunks):
        a_d = a_c if isinstance(a_c, jax.Array) else shard_rows_on_mesh(np.asarray(a_c), mesh)
        b_d = b_c if isinstance(b_c, jax.Array) else replicate_on_mesh(np.asarray(b_c), mesh)
        part = fn(a_d, b_d)
        acc = part if acc is None else acc + part
    return gather_global(acc)


def intersect_counts_matmul_rect(a_ids: np.ndarray, b_ids: np.ndarray) -> np.ndarray:
    """|A_i ∩ B_j| for sorted PAD-padded id rows sharing one id space,
    chunking the vocabulary when the joint indicator exceeds the budget
    (same additivity as the self path; one shared geometry keeps the
    chunks aligned across both sides). Returns int32 [na, nb]."""
    from drep_tpu.ops.minhash import require_int32_ids

    require_int32_ids(a_ids, "intersect_counts_matmul_rect")
    require_int32_ids(b_ids, "intersect_counts_matmul_rect")
    na, nb = a_ids.shape[0], b_ids.shape[0]
    if na == 0 or nb == 0:
        return np.zeros((na, nb), np.int32)
    joint = np.full(
        (na + nb, max(a_ids.shape[1], b_ids.shape[1])), PAD_ID, np.int32
    )
    joint[:na, : a_ids.shape[1]] = a_ids
    joint[na:, : b_ids.shape[1]] = b_ids
    geom = VocabChunkGeometry(joint, max_rows_per_call=max(na, nb))
    a_chunks = geom.rows_chunks(np.arange(na))
    b_chunks = geom.rows_chunks(np.arange(na, na + nb))
    return rect_from_chunks(a_chunks, b_chunks, geom.v_chunk)


def _chunk_plan(ids: np.ndarray, v_chunk: int, extent: int):
    """(n_chunks, starts, hist, width) for a vocab-chunk layout — shared
    by the byte comparison and the materialization so they cannot drift."""
    from drep_tpu.ops.merge import next_pow2
    from drep_tpu.ops.rangepart import MIN_BUCKET_WIDTH, bucket_starts

    n_chunks = -(-extent // v_chunk)
    starts = bucket_starts(ids, v_chunk, n_chunks)
    hist = np.diff(starts, axis=1)
    width = max(MIN_BUCKET_WIDTH, next_pow2(int(hist.max())))
    return n_chunks, starts, hist, width


def _stacked_vocab_chunks(
    ids: np.ndarray, v_chunk: int, m_pad: int, plan=None
) -> np.ndarray:
    """[R, m_pad, W] stacked rebased vocab-chunk matrices, ready for ONE
    host->device transfer.

    Chunk r holds each row's ids within [r*v_chunk, (r+1)*v_chunk),
    rebased to the chunk origin, repacked to the shared pow2 width W (max
    per-chunk per-row count). Narrow repack keeps total indicator-scatter
    work at one pass over the real ids — scattering full-width rows per
    chunk instead measured 4.7x slower at the 512x32768 production shape
    (an earlier chip run, not re-measured); one stacked tensor is also one
    transfer instead of one per chunk.

    When `v_chunk < 2^16` (strict: at 2^16 a rebased id of 65535 would
    collide with the sentinel) the rebased values fit uint16, and the
    stacked tensor ships at HALF the bytes (U16_PAD sentinel; the matmul
    jit widens on device) — `all_vs_all_containment_matmul_chunked` picks
    the chunk size by comparing actual plan bytes.

    `plan`: a precomputed `_chunk_plan(ids, v_chunk, extent)` so callers
    that already planned (the byte comparison) don't pay the per-row
    searchsorted pass twice.
    """
    from drep_tpu.ops.minhash import U16_PAD, pad_sentinel
    from drep_tpu.ops.rangepart import MIN_BUCKET_WIDTH, repack_bucket, vocab_extent

    extent = vocab_extent(ids)
    if extent == 0:
        return np.full((0, m_pad, MIN_BUCKET_WIDTH), PAD_ID, np.int32)
    n_chunks, starts, hist, width = plan if plan is not None else _chunk_plan(
        ids, v_chunk, extent
    )
    dtype = np.uint16 if v_chunk < (1 << 16) else np.int32
    out = np.full((n_chunks, m_pad, width), pad_sentinel(dtype), dtype)
    for r in range(n_chunks):
        blk = repack_bucket(ids, starts[:, r], hist[:, r], width, rebase=r * v_chunk)
        if dtype == np.uint16:
            out[r, : ids.shape[0]] = np.where(blk == PAD_ID, U16_PAD, blk).astype(
                np.uint16
            )
        else:
            out[r, : ids.shape[0]] = blk
    return out


def all_vs_all_containment_matmul_chunked(
    packed: PackedSketches, k: int = 21
) -> tuple[np.ndarray, np.ndarray]:
    """MXU path for vocabularies past the single-indicator budget.

    Intersection counts are additive over disjoint hash ranges, so the
    vocabulary splits into pow2 chunks each fitting the [m_pad, chunk]
    indicator budget; chunks cross the link as ONE stacked tensor, every
    chunk runs the same jit'd indicator matmul on its device-side slice,
    and the int32 partial counts accumulate ON DEVICE (one result
    transfer at the end — chunk dispatches stay async, so link latency
    overlaps compute). This is the production-width secondary engine
    (4 Mb genomes at scale=200 are ~20k-wide sketches with multi-million-
    id vocabularies — SURVEY.md §7 hard part (c)): exact like the
    one-shot matmul (int8 0/1 inputs, int32 accumulation — exact at any
    count).
    """
    from drep_tpu.ops.minhash import require_int32_ids
    from drep_tpu.ops.rangepart import vocab_extent

    require_int32_ids(packed.ids, "all_vs_all_containment_matmul_chunked")
    m = packed.n
    m_pad = matmul_rows_pad(m)
    # the host's layout work: both plans and the stacked repack
    with counters.span("secondary/chunks"):
        v_chunk = matmul_vocab_chunk(m_pad)
        # uint16 alternative: cap chunks below 2^16 so the rebased stacked
        # tensor ships at 2 bytes/element. More, narrower chunks cost extra
        # per-chunk dispatches but identical total indicator/matmul work;
        # padding skew at narrow widths can lose, so compare ACTUAL plan
        # bytes and keep the smaller operand. The matmul jit widens u16 on
        # device (ops/minhash.widen_ids_device).
        extent = vocab_extent(packed.ids)
        u16_chunk = 1 << 15
        plan = None
        if v_chunk > u16_chunk and extent > 0:
            plan32 = _chunk_plan(packed.ids, v_chunk, extent)
            plan16 = _chunk_plan(packed.ids, u16_chunk, extent)
            if plan16[0] * plan16[3] * 2 < plan32[0] * plan32[3] * 4:
                v_chunk, plan = u16_chunk, plan16
            else:
                plan = plan32
        stacked_host = _stacked_vocab_chunks(packed.ids, v_chunk, m_pad, plan=plan)
    n_chunks, _, width = stacked_host.shape
    counters.add_chunked_call(
        rows=m, rows_pad=m_pad, v_chunk=v_chunk, chunks=n_chunks, width=width,
        id_dtype=stacked_host.dtype.name, extent=extent,
        hashes=int(packed.counts.sum()), id_slots=int(stacked_host.size),
        bytes_shipped=int(stacked_host.nbytes),
    )
    # triangular schedule per chunk: counts are additive over disjoint hash
    # ranges AND symmetric, so each chunk contributes only its canonical
    # (bi <= bj) blocks; the partials accumulate ON DEVICE and ONE host
    # mirror after the final transfer completes the matrix — ~half the MXU
    # FLOPs of the full per-chunk matmuls, same single-result-transfer
    # dispatch pattern. The span holds the transfer, the chunk loop and the
    # one blocking readback
    with counters.span(
        "secondary/wait", rows=m_pad, v_chunk=v_chunk, chunks=n_chunks,
        id_dtype=stacked_host.dtype.name,
    ):
        stacked = jnp.asarray(stacked_host)
        acc = None
        for r in range(n_chunks):
            part = _intersect_matmul_tri(stacked[r], v_pad=v_chunk)
            acc = part if acc is None else acc + part
        inter_pad = None if acc is None else np.array(acc)
    with counters.span("secondary/post"):
        if inter_pad is None:
            inter = np.zeros((m, m), dtype=np.int32)
        else:
            tb = tri_row_block(m_pad)
            inter = mirror_lower_blocks(inter_pad, tb)[:m, :m]
            _count_tri_tiles(m_pad, tb)
        return ani_cov_from_intersections(inter, packed.counts, k)


def all_vs_all_containment(
    packed: PackedSketches, k: int = 21, tile: int = 128, triangular: bool = True
) -> tuple[np.ndarray, np.ndarray]:
    """Full [N, N] (symmetric max-containment ani, directional cov) via
    fixed-shape intersection tiles.

    `triangular` (default) walks only the canonical (i0 <= j0) tile blocks:
    the tile payload is the SYMMETRIC |A∩B| (containment_inter_tile), so
    the lower blocks are host-transposed copies — ~2x fewer device tiles,
    bit-equal output. Both cov directions and the ANI transform derive from
    the full intersection matrix + counts on host (one shared formula,
    :func:`ani_cov_from_intersections`)."""
    from drep_tpu.ops.minhash import require_int32_ids

    require_int32_ids(packed.ids, "all_vs_all_containment")
    n = packed.n
    tile = cap_gather_tile(packed.sketch_size, tile)
    ids, counts = pad_packed_rows(packed.ids, packed.counts, tile)
    nt = ids.shape[0]
    nb = nt // tile

    inter = np.zeros((nt, nt), dtype=np.int32)
    for i0 in range(0, nt, tile):
        for j0 in range(i0 if triangular else 0, nt, tile):
            t = np.asarray(
                containment_inter_tile(ids[i0 : i0 + tile], ids[j0 : j0 + tile])
            )
            inter[i0 : i0 + tile, j0 : j0 + tile] = t
            if triangular and j0 != i0:
                inter[j0 : j0 + tile, i0 : i0 + tile] = t.T
    counters.add_tiles(
        "secondary_compare",
        computed=nb * (nb + 1) // 2 if triangular else nb * nb,
        total=nb * nb,
    )
    return ani_cov_from_intersections(inter[:n, :n], packed.counts, k)
