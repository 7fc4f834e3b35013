"""Mesh-sharded all-pairs comparison — the distributed compute core.

Replaces the reference's multiprocessing.Pool fan-out of pairwise subprocess
jobs (SURVEY.md §2c, §3.2) with the canonical TPU pattern (SURVEY.md §7
step 7, SNIPPETS.md ring patterns): genomes are row-sharded over a 1-D
mesh; each device holds 1/D of the sketches and computes its stripe of the
distance matrix while the "B" operand ring-rotates over the mesh axis with
``lax.ppermute`` — never materializing more than 2/D of the sketches per
device.

Half-ring schedule (ISSUE 1): every registered tile kernel is SYMMETRIC in
its pair — Mash distance and the raw MinHash intersection size both satisfy
``tile(A, B) == tile(B, A).T`` bit-exactly (integer shared/intersection
counts, identical merged unions) — so the full D-step ring does every
unordered block pair twice. The half ring runs only ``D//2 + 1`` of the D
steps (= ceil((D+1)/2)): at step ``i`` device ``m`` computes block
``(m, (m-i) mod D)``, and the redundant mirror of that block would only
arrive at step ``D-i``. For even D the middle step ``i = D/2`` is
self-paired (device ``m`` and ``m + D/2`` compute mirror tiles of the same
unordered pair), so it is split across device halves: only devices
``m < D/2`` keep their middle-step tile. Net effect: ``D*(D+1)/2`` unique
block tiles instead of ``D^2`` — ~2x less tile compute AND ~2x fewer
``lax.ppermute`` ICI hops — and the host mirrors the transposed blocks
into the uncomputed triangle after ``gather_global``. The containment ring
ships the symmetric raw intersection size (not the directional
``cov = |A∩B|/|A|``) precisely so it can ride this schedule; both cov
directions derive from ``counts`` on host.

The jitted shard_map programs are cached per (kernel kind, k, mesh,
schedule), so repeated calls — e.g. one per large primary cluster during
secondary clustering — recompile only when shapes actually change.

Step-wise execution (ISSUE 4): the DEFAULT ring is host-stepped — one
shard_map dispatch per ring step instead of one monolithic
``fori_loop`` program — which gives the dense engine a REDOABLE UNIT:
every step's per-device block tile can be checkpointed to a shard store
(``blk_AAA_BBB.npz``, epoch-stamped ``.eNN`` after a pod degradation,
utils/ckptmeta.py machinery) and any block can be recomputed
independently by the per-block tile executor (parallel/faulttol.py
TileExecutor) on the local devices — bit-identically, because the tile
kernels are pure fixed-shape functions whose results do not depend on
which program dispatched them (pinned by tests/test_triangular.py). On a
multi-process pod this is what makes the dense ring ELASTIC: a
HeartbeatManager death verdict between steps makes the survivors abandon
the (now unusable) full-pod collective, re-deal every missing block
across the live set, and assemble a distance matrix bit-identical to a
healthy run from the shared shard store. The monolithic single-program
ring is kept behind the ``monolithic=True`` argument as the bit-equality
reference the tests compare against; no flag or knob selects it.

Every step runs the one shard_map program (:func:`_ring_step_fn`: the
tile, then the ``lax.ppermute`` hop). A step that FAULTS at run time
falls into the per-block (collective-free) recovery path, while a step
program that does not BUILD ends the run (parallel/faulttol.py).
"""

from __future__ import annotations

import functools
import os
import time
from typing import Callable

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.sharding import NamedSharding, PartitionSpec as P

from drep_tpu.ops.containment import ani_cov_from_intersections, containment_inter_tile
from drep_tpu.ops.minhash import PackedSketches, mash_distance_tile, pad_packed_rows
from drep_tpu.parallel.mesh import AXIS, make_mesh
from drep_tpu.utils import telemetry
from drep_tpu.utils.logger import get_logger
from drep_tpu.utils.profiling import counters

# the stage a ring's spans are booked to, by the kind of its tile
# ("primary/wait", "secondary/assemble", ...)
_STAGE_OF_KIND = {"mash": "primary", "containment": "secondary"}

# per-ring-step AutoTimeout warmup: exclude exactly the FIRST step's wait
# from the rolling median — it absorbs whatever is still cold after the
# up-front build (executable load, first DMA), and the default
# TileExecutor warmup (8) would discard the entire half-ring schedule at
# production D (gauges.derived_ring_step_timeout_s never derived).
RING_STEP_WARMUP = 1

# process-wide ring execution config, set once per run by the cluster
# controller from the CLI flags (same pattern as faulttol's
# configure_defaults): engines call ring_allpairs deep inside replicated
# control flow and cannot thread a workdir down to it.
_RING_CONFIG: dict = {"checkpoint_base": None}


def configure_ring(checkpoint_base: str | None = None) -> None:
    """Install the run-wide ring default: `checkpoint_base` roots the
    step-wise ring's per-call block shard stores (one subdirectory per
    distinct input fingerprint, created lazily when a ring actually runs).

    This REPLACES the config — an omitted argument resets it to its
    default (None), it does not preserve the previous value; a bare
    ``configure_ring()`` is the full reset (tests rely on it)."""
    _RING_CONFIG["checkpoint_base"] = checkpoint_base


def half_ring_steps(n_devices: int) -> int:
    """Ring steps the triangular schedule runs: ceil((D+1)/2) of D."""
    return n_devices // 2 + 1


def ring_tiles_computed(n_devices: int, half: bool) -> int:
    """Unique block tiles the schedule produces (D*(D+1)/2 when half: the
    even-D middle step contributes only its canonical device half)."""
    if half:
        return n_devices * (n_devices + 1) // 2
    return n_devices * n_devices


def _ring_allpairs_shard(a_ids, a_counts, tile_fn, n_outputs: int, half: bool):
    """Per-shard body (runs under shard_map): local A block vs ring-rotating
    B block. Returns [n_local, N_global] stripes for each tile output.

    With ``half`` (symmetric kernels only) the loop runs ``D//2 + 1`` steps
    instead of D, and for even D the final step's store is masked to the
    canonical device half ``my < D/2`` — the other half's blocks are
    mirrored on host from their transposed twins (see module docstring).
    """
    n_devices = lax.psum(1, AXIS)
    my = lax.axis_index(AXIS)
    n_local = a_ids.shape[0]
    n_steps = half_ring_steps(n_devices) if half else n_devices
    # even-D half ring: the middle step is self-paired across device halves
    split_mid = half and n_devices % 2 == 0 and n_devices > 1

    b_ids, b_counts = a_ids, a_counts
    # mark the accumulators as device-varying so the scan carry type is
    # stable (the updates are derived from axis_index and vary over the mesh)
    outs = [
        lax.pcast(jnp.zeros((n_local, n_local * n_devices), jnp.float32), (AXIS,), to="varying")
        for _ in range(n_outputs)
    ]
    perm = [(j, (j + 1) % n_devices) for j in range(n_devices)]

    def step(i, carry):
        b_ids, b_counts, *outs = carry
        tiles = tile_fn(a_ids, a_counts, b_ids, b_counts)
        if not isinstance(tiles, tuple):
            tiles = (tiles,)
        # after i rotations device m holds block (m - i) mod D
        src = jnp.remainder(my - i, n_devices)
        col0 = src * n_local
        updated = [
            lax.dynamic_update_slice(out, tile.astype(jnp.float32), (0, col0))
            for out, tile in zip(outs, tiles)
        ]
        if split_mid:
            # keep the middle-step tile only on the canonical half; the
            # predicate is data-flow (where), not control-flow, so SPMD
            # lockstep and replication checking are untouched
            keep = jnp.logical_or(i < n_steps - 1, my < n_devices // 2)
            outs = [jnp.where(keep, u, o) for u, o in zip(updated, outs)]
        else:
            outs = updated

        def rotate(ops):
            bi, bc = ops
            return lax.ppermute(bi, AXIS, perm), lax.ppermute(bc, AXIS, perm)

        # the final iteration's rotation result is never read — skip the
        # ICI traffic (the predicate is uniform across devices). Under the
        # half schedule this saves D - n_steps ADDITIONAL hops per call.
        b_ids, b_counts = lax.cond(
            i < n_steps - 1, rotate, lambda ops: ops, (b_ids, b_counts)
        )
        return (b_ids, b_counts, *outs)

    carry = lax.fori_loop(0, n_steps, step, (b_ids, b_counts, *outs))
    return tuple(carry[2:])


def _mash_tile(k: int):
    def tile(a_ids, a_counts, b_ids, b_counts):
        from drep_tpu.ops.pallas_mash import (
            mash_distance_tile_device,
            pallas_mash_supported,
        )

        if pallas_mash_supported(a_ids.shape[1]):
            # on a TPU a ring block goes through the VMEM-resident kernel
            # (same estimator, bit-equal shared counts): the jnp merge
            # below materializes [Ta, Tb, 2*S2] temporaries in HBM — 12 GB
            # apiece for a 1250-row block of 1000-wide sketches, which XLA
            # refuses to compile for a 16 GB chip
            return mash_distance_tile_device(a_ids, a_counts, b_ids, b_counts, k=k)
        d, _j = mash_distance_tile(a_ids, a_counts, b_ids, b_counts, k=k)
        return d

    return tile


def _containment_tile(k: int):
    del k  # |A∩B| is count-free; k rides only in the cache key

    def tile(a_ids, a_counts, b_ids, b_counts):
        del a_counts, b_counts  # symmetric raw intersections need no counts
        return containment_inter_tile(a_ids, b_ids)

    return tile


# containment ships ONE output stripe: the SYMMETRIC raw intersection size
# |A∩B| (int counts, exact in f32 below 2^24 — far above any packed sketch
# width). Both cov directions and the max-containment ani derive from the
# gathered full matrix + counts on host (ani_cov_from_intersections); the
# symmetric payload is what lets containment ride the half-ring schedule,
# and it halves the result traffic vs shipping both cov directions.
# Every kind must keep tile(A,B) == tile(B,A).T bit-exact — the half-ring
# host mirror DEPENDS on it (asymmetric kernels would need the full ring).
_TILE_KINDS: dict[str, tuple[Callable[[int], Callable], int]] = {
    "mash": (_mash_tile, 1),
    "containment": (_containment_tile, 1),
}


def put_global(arr: np.ndarray, sharding) -> jax.Array:
    """Host numpy -> globally-sharded jax.Array, multi-host safe.

    Ingest is host-replicated (every process sketches the same genome list),
    so each process holds the full array and contributes only its
    addressable shards. ``jax.device_put`` of a host array onto a sharding
    that spans other processes' devices is not portable; the callback form
    is the documented multi-host construction path (SURVEY.md §5.8).
    """
    return jax.make_array_from_callback(arr.shape, sharding, lambda idx: arr[idx])


def gather_global(x: jax.Array) -> np.ndarray:
    """Globally-sharded jax.Array -> full numpy array on every process.

    ``np.array`` on a non-fully-addressable array raises on >1 process
    (remote shards have no local buffers); ``process_allgather`` reshards
    to fully-replicated first (ICI/DCN collective), then reads local data.
    Single-process keeps the direct copy (no resharding dispatch).
    """
    if jax.process_count() > 1:
        from jax.experimental import multihost_utils

        # tiled=True is required for global arrays; the result is the fully
        # replicated value (no extra stacking axis), identical on every host
        return np.array(multihost_utils.process_allgather(x, tiled=True))
    return np.array(x)


def _ring_block_computed(a: int, b: int, n_devices: int) -> bool:
    """Whether the half-ring schedule stored block (row a, col b): device a
    computes column block (a - i) mod D at step i, steps 0..n_steps-1, with
    the even-D middle step kept only on devices a < D/2."""
    i = (a - b) % n_devices
    n_steps = half_ring_steps(n_devices)
    if i >= n_steps:
        return False
    if n_devices % 2 == 0 and n_devices > 1 and i == n_devices // 2:
        return a < n_devices // 2
    return True


def mirror_half_ring(mat: np.ndarray, n_devices: int) -> None:
    """Fill the blocks the half-ring schedule skipped with the transpose of
    their computed twins, in place. `mat` is the gathered [n_pad, n_pad]
    matrix (n_pad a multiple of n_devices)."""
    n_local = mat.shape[0] // n_devices
    for a in range(n_devices):
        for b in range(n_devices):
            if a == b or _ring_block_computed(a, b, n_devices):
                continue
            assert _ring_block_computed(b, a, n_devices), "schedule hole"
            ra = slice(a * n_local, (a + 1) * n_local)
            rb = slice(b * n_local, (b + 1) * n_local)
            mat[ra, rb] = mat[rb, ra].T


@functools.lru_cache(maxsize=None)
def _ring_fn(kind: str, k: int, mesh, half: bool) -> tuple[Callable, int]:
    """One jitted shard_map program per (kernel kind, k, mesh, schedule);
    jax.jit then caches per input shape, so same-shape calls are
    compile-free."""
    make_tile, n_outputs = _TILE_KINDS[kind]
    fn = jax.jit(
        jax.shard_map(
            functools.partial(
                _ring_allpairs_shard,
                tile_fn=make_tile(k),
                n_outputs=n_outputs,
                half=half,
            ),
            mesh=mesh,
            in_specs=(P(AXIS, None), P(AXIS)),
            out_specs=tuple(P(AXIS, None) for _ in range(n_outputs)),
        )
    )
    return fn, n_outputs


# -- step-wise (host-stepped) ring: the redoable-unit schedule ------------


def ring_schedule(n_devices: int, half: bool) -> list[tuple[int, int]]:
    """The ordered block list the schedule stores: (row block a, col block
    b) pairs, canonical (a-major) order. This order is the assembly order
    AND the deterministic recovery-ownership index, so every process
    derives identical ownership from it."""
    return [
        (a, b)
        for a in range(n_devices)
        for b in range(n_devices)
        if not half or _ring_block_computed(a, b, n_devices)
    ]


def ring_step_of(a: int, b: int, n_devices: int) -> int:
    """The ring step that produces block (a, b): device `a` computes
    column block ``(a - i) mod D`` at step `i`. The ring-phase JOIN
    upgrade deals by STEP through this — a joiner eats whole steps from
    the schedule tail while the pod's collective ring works the head."""
    return (a - b) % n_devices


def _ring_step_shard(a_ids, a_counts, b_ids, b_counts, tile_fn, n_devices, rotate):
    """One ring step under shard_map: compute this step's tile from the
    resident A block and the CURRENT B operand, then rotate B one hop.
    The tile lands as a direct program output (not a dynamic_update_slice
    into a carry), which is exactly what keeps its bits identical to a
    standalone per-block recompute — the recovery path depends on it."""
    with jax.named_scope("drep_ring_tile"):
        tiles = tile_fn(a_ids, a_counts, b_ids, b_counts)
        if not isinstance(tiles, tuple):
            tiles = (tiles,)
        tiles = tuple(t.astype(jnp.float32) for t in tiles)
    if rotate:
        with jax.named_scope("drep_ring_rotate"):
            perm = [(j, (j + 1) % n_devices) for j in range(n_devices)]
            b_ids = lax.ppermute(b_ids, AXIS, perm)
            b_counts = lax.ppermute(b_counts, AXIS, perm)
    return (*tiles, b_ids, b_counts)


@functools.lru_cache(maxsize=None)
def _ring_step_fn(kind: str, k: int, mesh, rotate: bool) -> Callable:
    """One jitted per-step program per (kind, k, mesh, rotate) — two
    compilations per schedule (the last step skips the dead rotation's
    ICI hop, same optimization as the monolithic program's lax.cond)."""
    make_tile, n_outputs = _TILE_KINDS[kind]
    return jax.jit(
        jax.shard_map(
            functools.partial(
                _ring_step_shard,
                tile_fn=make_tile(k),
                n_devices=mesh.devices.size,
                rotate=rotate,
            ),
            mesh=mesh,
            in_specs=(P(AXIS, None), P(AXIS), P(AXIS, None), P(AXIS)),
            out_specs=(
                *[P(AXIS, None) for _ in range(n_outputs)],
                P(AXIS, None),
                P(AXIS),
            ),
        )
    )


@functools.lru_cache(maxsize=None)
def _block_tile_fn(kind: str, k: int) -> tuple[Callable, int]:
    """Standalone jitted per-block tile — the step-wise ring's REDOABLE
    UNIT, used to recompute any missing block (resume gaps, a dead pod
    member's unfinished work, failed steps) on a local device. Applies the
    same f32 cast as the step program so a recovered block is bit-
    identical to its in-ring twin (pinned by test_triangular)."""
    make_tile, n_outputs = _TILE_KINDS[kind]
    tile_fn = make_tile(k)

    @jax.jit
    def fn(a_ids, a_counts, b_ids, b_counts):
        tiles = tile_fn(a_ids, a_counts, b_ids, b_counts)
        if not isinstance(tiles, tuple):
            tiles = (tiles,)
        return tuple(t.astype(jnp.float32) for t in tiles)

    return fn, n_outputs


def _block_name(a: int, b: int, epoch: int) -> str:
    """Block (a, b)'s checkpoint shard filename, epoch-stamped exactly
    like the streaming row shards: ``blk_AAA_BBB.npz`` healthy, the
    ownership epoch in the name once a degraded run (or a local heal)
    produced it under a bump. Content is identical whichever
    process/epoch computed it (deterministic tiles)."""
    base = f"blk_{a:03d}_{b:03d}"
    return f"{base}.npz" if epoch == 0 else f"{base}.e{epoch:02d}.npz"


def _find_block(checkpoint_dir: str, a: int, b: int) -> str | None:
    """Existing shard for block (a, b) under ANY ownership epoch."""
    loc = os.path.join(checkpoint_dir, _block_name(a, b, 0))
    if os.path.exists(loc):
        return loc
    import glob

    hits = sorted(
        glob.glob(os.path.join(checkpoint_dir, f"blk_{a:03d}_{b:03d}.e*.npz"))
    )
    return hits[0] if hits else None


def _load_block(path: str, n_outputs: int):
    """Tuple of `n_outputs` arrays from a block shard, or None when it
    reads corrupt — warned, counted (``corrupt_shards_healed``), and
    best-effort removed; callers recompute into the same path (the
    streaming shard store's healing contract). The checked read
    (utils/durableio.py) retries transient I/O errors and verifies the
    in-band ``__crc__``, so a zero-byte/truncated/bit-rotted block
    classifies exactly like a missing one."""
    from drep_tpu.utils import durableio

    return durableio.load_npz_or_none(
        path, what="ring block shard",
        convert=lambda z: tuple(z[f"o{i}"] for i in range(n_outputs)),
        warn="dense ring: corrupt block shard %s — recomputing",
    )


def _ring_store_dir(kind: str, k: int, n_devices: int, fingerprint: str) -> str | None:
    """The per-call block store under the configured base (None when no
    base is configured): one subdirectory per distinct (kind, D, input
    fingerprint), so interleaved ring calls — e.g. per-cluster secondary
    rings — never invalidate each other's shards."""
    base = _RING_CONFIG["checkpoint_base"]
    if base is None:
        return None
    return os.path.join(base, f"ring_{kind}_k{k}_d{n_devices}_{fingerprint[:12]}")


def ring_allpairs(
    packed: PackedSketches,
    kind: str,
    k: int,
    mesh=None,
    full_grid: bool = False,
    monolithic: bool = False,
    checkpoint_dir: str | None = None,
    ft_config=None,
) -> tuple[np.ndarray, ...]:
    """Run the `kind` tile kernel over every pair of rows, sharded over the
    mesh. Returns full [N, N] float32 matrices (one per kernel output),
    gathered to host and trimmed to the real N.

    The half-ring (triangular) schedule is the default — every registered
    kernel is symmetric (see _TILE_KINDS). ``full_grid=True`` forces the
    original D-step ring; it exists as the equality reference for tests
    and for any future asymmetric kernel.

    Execution is HOST-STEPPED by default (one dispatch per ring step,
    per-step block tiles checkpointable and individually redoable — the
    elastic dense engine, module docstring); ``monolithic=True`` runs the
    original single collective program, kept as the bit-equality
    reference. `checkpoint_dir` overrides the configured per-call block
    store location (None + no configured base = in-memory only).
    """
    if mesh is None:
        mesh = make_mesh()
    n_devices = mesh.devices.size
    half = not full_grid
    n = packed.n
    if not monolithic:
        # honest accounting: the step-wise path reports the block tiles
        # THIS process actually computed this call — a full store resume
        # reports 0, a pod member reports only its share — against the
        # full-grid total (the monolithic reference genuinely computes
        # its whole schedule every call and books it). The grid total
        # comes back from the stepwise path too: a mid-run JOINER runs
        # the pod's block geometry (from the store meta), not its own
        # local mesh's.
        outs, tiles_computed, grid_d = _ring_allpairs_stepwise(
            packed, kind, k, mesh, half, checkpoint_dir, ft_config
        )
    else:
        outs = _ring_allpairs_monolithic(packed, kind, k, mesh, half)
        tiles_computed = ring_tiles_computed(n_devices, half)
        grid_d = n_devices
    counters.add_tiles(
        "primary_compare" if kind == "mash" else "secondary_compare",
        computed=tiles_computed,
        total=grid_d * grid_d,
    )
    return tuple(g[:n, :n] for g in outs)


def _ring_allpairs_monolithic(packed, kind, k, mesh, half):
    """The original one-program ring (the bit-equality reference the
    step-wise schedule is pinned against)."""
    n_devices = mesh.devices.size
    ph = _STAGE_OF_KIND[kind]
    with counters.span(ph + "/pack"):
        ids, counts = pad_packed_rows(packed.ids, packed.counts, n_devices)
    with counters.span(ph + "/put"):
        ids_d = put_global(ids, NamedSharding(mesh, P(AXIS, None)))
        counts_d = put_global(counts, NamedSharding(mesh, P(AXIS)))

    fn, _ = _ring_fn(kind, k, mesh, half)
    # bounded-retry dispatch (parallel/faulttol.py): the ring is one
    # shard_map program, so the retry unit is the whole schedule — inputs
    # are still device-resident, so a retry costs compute, not transfer.
    # On a >1-process pod retrying_call runs the dispatch BARE: a
    # per-process retry of a collective program would desync the pod
    # (see its docstring); multi-host live failures abort loudly via the
    # collective timeouts instead. The step-wise default has a redoable
    # unit and survives those deaths — this reference path does not.
    from drep_tpu.parallel.faulttol import build_program, retrying_call

    with counters.span(ph + "/dispatch"):
        build_program(fn, ids_d, counts_d)  # a compile error is not a device fault
    # one program: its enqueue, its run and its readback are one wait
    with counters.span(ph + "/wait"):
        outs = retrying_call(
            lambda: jax.block_until_ready(fn(ids_d, counts_d)),
            site="ring_dispatch",
        )
        # copy to host (np.array copies): buffers are read-only and callers
        # fill diagonals; gather_global handles the >1-process reshard
        gathered = [gather_global(o) for o in outs]
    if half:
        with counters.span(ph + "/assemble"):
            for g in gathered:
                mirror_half_ring(g, n_devices)
    return gathered


def _exchange_rows_no_store(
    mem: dict, mesh, schedule, n_outputs: int, n_local: int, n_pad: int,
    pid: int, kind: str,
) -> None:
    """Store-less pod completion: allgather each process's computed block
    rows (host arrays, equal shapes — the mesh spans the pod with equal
    local device counts) and place peers' blocks into `mem`. Values are
    the same host copies a shard store would have round-tripped, so the
    assembly stays bit-identical to both the store path and the
    monolithic gather."""
    from jax.experimental import multihost_utils as mhu

    from drep_tpu.parallel.faulttol import (
        DEFAULT_ALLGATHER_TIMEOUT_S,
        collective_timeout_s,
        run_with_timeout,
    )

    proc_rows: dict[int, list[int]] = {}
    for m, d in enumerate(mesh.devices.flat):
        proc_rows.setdefault(d.process_index, []).append(m)
    counts = {len(v) for v in proc_rows.values()}
    if len(counts) != 1:
        raise ValueError(
            f"dense ring: uneven device rows per process {proc_rows} — the "
            f"store-less pod exchange needs equal shapes; configure a block "
            f"store instead"
        )
    mine = proc_rows.get(pid, [])
    blocks_by_row: dict[int, list[tuple[int, int]]] = {}
    for a, b in schedule:
        blocks_by_row.setdefault(a, []).append((a, b))
    gathered: dict[tuple[int, int], list] = {}
    for oi in range(n_outputs):
        rows_mat = np.zeros((len(mine), n_local, n_pad), np.float32)
        for ri, m in enumerate(mine):
            for a, b in blocks_by_row.get(m, ()):
                rows_mat[ri][:, b * n_local : (b + 1) * n_local] = mem[(a, b)][oi]
        g = np.asarray(
            run_with_timeout(
                lambda rows_mat=rows_mat: mhu.process_allgather(rows_mat),
                what=f"dense ring row exchange ({kind} output {oi})",
                site="allgather",
                timeout_s=collective_timeout_s(DEFAULT_ALLGATHER_TIMEOUT_S),
            )
        )  # [pc, rows_per_proc, n_local, n_pad], rebuilt per output
        for p, rows_p in sorted(proc_rows.items()):
            if p == pid:
                continue
            for ri, m in enumerate(rows_p):
                for a, b in blocks_by_row.get(m, ()):
                    tile = g[p, ri][:, b * n_local : (b + 1) * n_local].copy()
                    gathered.setdefault((a, b), [None] * n_outputs)[oi] = tile
    for blk, tiles in gathered.items():
        mem[blk] = tuple(tiles)


def _read_ring_meta(store: str) -> dict | None:
    """The block store's meta.json, or None while it is missing/corrupt
    (a joiner polls this: the pod writes it at its store open). Same
    corruption contract as every membership note."""
    from drep_tpu.parallel.faulttol import read_pod_note

    return read_pod_note(os.path.join(store, "meta.json"), what="ring store meta")


def _ring_allpairs_stepwise(
    packed, kind, k, mesh, half, checkpoint_dir, ft_config
) -> tuple[list[np.ndarray], int, int]:
    """The host-stepped elastic ring (module docstring): one dispatch per
    ring step, per-step block tiles checkpointed to a shard store, missing
    blocks individually redoable via the per-block tile executor, and —
    on a multi-process pod — a HeartbeatManager death verdict between
    steps re-dealing the dead member's blocks across the survivors with a
    bit-identical final matrix. Membership also GROWS and DRAINS
    (ISSUE 9): an admitted joiner (``DREP_TPU_POD_JOIN`` against the same
    block store) enters the per-block completion under the pod's block
    geometry (D from the store meta, never its own local mesh), and a
    drain request is honored at step/block boundaries via a planned-
    departure note + :class:`PodDrained`. Returns (full padded matrices,
    block tiles this process actually computed — the honest
    tiles_computed — and the schedule's device-grid D)."""
    from drep_tpu.parallel.faulttol import (
        DEFAULT_ALLGATHER_TIMEOUT_S,
        DEFAULT_CONFIG,
        AutoTimeout,
        CollectiveTimeout,
        FaultTolError,
        HeartbeatManager,
        PodDrained,
        TileExecutor,
        WatchdogTimeout,
        _wait_ready,
        build_program,
        collective_timeout_s,
        drain_requested,
        heartbeat_cadence_s,
        is_device_fault,
        join_elastic_pod,
        join_requested,
        wait_elastic,
    )
    from drep_tpu.utils import faults
    from drep_tpu.utils.ckptmeta import atomic_savez, content_fingerprint

    logger = get_logger()
    cfg = ft_config if ft_config is not None else DEFAULT_CONFIG
    ph = _STAGE_OF_KIND[kind]
    D = mesh.devices.size
    _make_tile, n_outputs = _TILE_KINDS[kind]
    pid, pc = jax.process_index(), jax.process_count()
    local_mesh = all(d.process_index == pid for d in mesh.devices.flat)

    # fingerprint only when a store exists — SHA-1 over the full pack is
    # wasted work for the store-less (memory-only) execution
    fp = None
    store = checkpoint_dir
    if store is not None or _RING_CONFIG["checkpoint_base"] is not None:
        with counters.span(ph + "/publish"):  # the block store's key: SHA-1 over the pack
            fp = content_fingerprint(packed.names, packed.counts, packed.ids)
        if store is None:
            store = _ring_store_dir(kind, k, D, fp)
    if store is not None and pc > 1 and local_mesh:
        # replicated LOCAL ring on a multi-process pod (the degraded-pod
        # secondary shape, engines._mesh_or_none): a shared store would
        # put pod barriers inside per-process retry scopes (retrying_call
        # local_only) and desync the barrier sequence — run memory-only;
        # every survivor computes the same numbers on its own chips
        store = None

    hb = None
    resume = False
    # join is honored only for an EXPLICIT checkpoint_dir (the pod's
    # shared block store): a joiner process also runs replicated local
    # work — per-cluster secondary rings with config-derived stores —
    # and those must compute normally, not chase admission into every
    # store the run creates
    joining = checkpoint_dir is not None and join_requested() is not None
    if joining and heartbeat_cadence_s() <= 0:
        # refuse LOUDLY: falling through would run this process as an
        # independent participant against the pod's live store (the
        # streaming path has the same guard and the full rationale)
        from drep_tpu.errors import UserInputError

        raise UserInputError(
            "DREP_TPU_POD_JOIN is set but heartbeats are disabled "
            "(DREP_TPU_HEARTBEAT_S=0) — ring admission rides the "
            "heartbeat protocol. Unset DREP_TPU_POD_JOIN to run "
            "standalone, or re-enable heartbeats."
        )
    if joining:
        # mid-run JOIN: this process is NOT part of the pod mesh — it
        # contributes through the per-block completion only, under the
        # POD's block geometry. The join request goes out first (a pod
        # gated on arriving capacity may open its store after seeing
        # it); the store meta — which carries D — is validated alongside
        # the admission wait, and a geometry/input mismatch refuses.
        cadence = heartbeat_cadence_s()
        want = {
            "kind": kind, "k": k, "n": packed.n, "half": half,
            "schedule": "stepwise1", "fingerprint": fp,
        }

        def _meta_ok() -> bool:
            stored = _read_ring_meta(store)
            return stored is not None and all(
                stored.get(kk) == vv for kk, vv in want.items()
            )

        hb = join_elastic_pod(
            store, cadence, config=cfg,
            what="dense ring (mid-run join)", validate=_meta_ok,
        )
        stored_meta = _read_ring_meta(store)
        if stored_meta is None:  # vanished between validate and here
            hb.close()
            raise FaultTolError(
                f"dense ring join: block store meta at {store} disappeared "
                f"after admission — the pod's store was cleared mid-join"
            )
        D = int(stored_meta["n_devices"])
        pid, pc = hb.pid, hb.pc
        resume = True

    with counters.span(ph + "/pack"):
        ids, counts = pad_packed_rows(packed.ids, packed.counts, D)
    n_pad = ids.shape[0]
    n_local = n_pad // D
    n_steps = half_ring_steps(D) if half else D
    schedule = ring_schedule(D, half)
    sched_idx = {blk: i for i, blk in enumerate(schedule)}

    if store is not None and not joining:
        cadence = heartbeat_cadence_s()
        if cadence > 0:
            # started BEFORE the store-open barrier (the stale-note
            # cleanup ordering the heartbeat protocol requires) — which
            # also makes the barrier itself heartbeat-aware: a peer that
            # dies before ever reaching it is admitted as a pod death
            # (utils/ckptmeta.py), not a CollectiveTimeout abort
            hb = HeartbeatManager(
                store, cadence,
                max_dead=cfg.max_dead_processes, max_joins=cfg.max_joins,
            )
            hb.start()
        meta = {
            "kind": kind,
            "k": k,
            "n": packed.n,
            "n_devices": D,
            "half": half,
            "schedule": "stepwise1",
            "fingerprint": fp,
        }
        from drep_tpu.utils.ckptmeta import open_checkpoint_dir

        try:
            with counters.span(ph + "/publish"):
                resume = open_checkpoint_dir(store, meta, clear_suffixes=(".npz",))
        except BaseException:
            if hb is not None:
                hb.close()
            raise

    elastic = joining or (hb is not None and pc > 1 and not local_mesh)

    def _maybe_drain() -> None:
        if hb is None or not drain_requested():
            return
        # the departure note's count is this process's computed BLOCKS —
        # the same unit the ring's done-note reports (hb.mark_done(len(
        # mem))), so the member-set accounting stays consistent across
        # finished and drained members
        hb.announce_drain(pairs=n_computed)
        raise PodDrained(
            f"dense ring: process {pid} drained at a step/block boundary "
            f"(planned-departure note published with {n_computed} computed "
            f"block(s); peers re-deal its unfinished blocks immediately)"
        )

    # blocks this call computed stay in memory; the rest resolve from the
    # shard store (found blocks cached so they are never re-statted).
    # n_computed counts the block tiles THIS process actually produced
    # (ring steps + per-block recovery) for the honest tiles_computed
    # accounting — a resume reports 0, never the full schedule.
    mem: dict[tuple[int, int], tuple] = {}
    shard_of: dict[tuple[int, int], str] = {}
    n_computed = 0

    def _missing_blocks() -> list[tuple[int, int]]:
        out = []
        for blk in schedule:
            if blk in mem or blk in shard_of:
                continue
            if store is not None:
                loc = _find_block(store, *blk)
                if loc is not None:
                    shard_of[blk] = loc
                    continue
            out.append(blk)
        return out

    def _save_block(blk: tuple[int, int], tiles: tuple, epoch: int) -> None:
        if store is None:
            return
        path = os.path.join(store, _block_name(blk[0], blk[1], epoch))
        with counters.span(ph + "/publish"):
            atomic_savez(path, **{f"o{oi}": t for oi, t in enumerate(tiles)})
        shard_of[blk] = path
        telemetry.event(
            "blk_publish", shard=_block_name(blk[0], blk[1], epoch)
        )

    def _store_step(i: int, outs) -> None:
        """Host copies of this process's addressable shards of step `i`,
        placed at their (row block, col block) coordinates and published
        to the store. The even-D half-ring middle step keeps only the
        canonical device half (the mirrored twin owns the unordered pair)."""
        rows: dict[int, list] = {}
        for oi, o in enumerate(outs):
            for sh in o.addressable_shards:
                m = (sh.index[0].start or 0) // n_local
                rows.setdefault(m, [None] * n_outputs)[oi] = np.asarray(sh.data)
        nonlocal n_computed
        for m, tiles in sorted(rows.items()):
            if half and D % 2 == 0 and D > 1 and i == D // 2 and m >= D // 2:
                continue
            blk = (m, (m - i) % D)
            mem[blk] = tuple(tiles)
            n_computed += 1
            _save_block(blk, mem[blk], hb.epoch if hb is not None else 0)

    def _join_covered_tail(step_i: int) -> bool:
        """Has an admitted joiner made every block PAST `step_i` durable?
        (The ring-phase JOIN shortcut's exit test — cheap: one cached
        store lookup per still-unseen tail block, only once a join has
        actually been admitted with no deaths/drains in the mix.)"""
        if (
            hb is None or not hb.joined or hb.dead or hb.drained
            or store is None or step_i >= n_steps - 1
        ):
            return False
        for blk in schedule:
            if ring_step_of(*blk, D) <= step_i:
                continue
            if blk in mem or blk in shard_of:
                continue
            loc = _find_block(store, *blk)
            if loc is None:
                return False
            shard_of[blk] = loc
        return True

    # recovery executor (lazy): the per-block redoable unit — round-robin
    # retrying dispatch over the LOCAL devices, CPU recompute last
    ex: TileExecutor | None = None
    devices = jax.local_devices()
    tile_jit, _ = _block_tile_fn(kind, k)

    def _compute_block(blk: tuple[int, int], tail_step: int | None = None) -> tuple:
        nonlocal ex, n_computed
        n_computed += 1
        if ex is None:
            # the per-block program is built once, outside the retry
            # envelope: a tile that does not compile ends the run
            # (parallel/faulttol.py), it is never "recovered" on the CPU
            from jax.sharding import SingleDeviceSharding

            sh = SingleDeviceSharding(devices[0])
            blk_ids = jax.ShapeDtypeStruct((n_local, ids.shape[1]), ids.dtype, sharding=sh)
            blk_cts = jax.ShapeDtypeStruct((n_local,), counts.dtype, sharding=sh)
            build_program(tile_jit, blk_ids, blk_cts, blk_ids, blk_cts)
            ex = TileExecutor(devices, cfg, fault_site="ring_dispatch")
        a, b = blk
        if tail_step is not None:
            # ring-phase JOIN (ISSUE 15): this block is a joiner's share
            # of ring step `tail_step` — traced as step PARTICIPATION
            # (the scaling timeline shows the joiner working the same
            # step axis as the pod), not as failure recovery
            with counters.span(
                "ring_step", step=tail_step, steps=n_steps, joiner=True,
                block=f"{a},{b}",
            ):
                out = _compute_block_tiles(a, b)
            counters.add_fault("ring_join_tail_blocks")
            return out
        with counters.span("ring_block_recover", a=a, b=b):
            return _compute_block_tiles(a, b)

    def _compute_block_tiles(a: int, b: int) -> tuple:
        asl = slice(a * n_local, (a + 1) * n_local)
        bsl = slice(b * n_local, (b + 1) * n_local)

        def dispatch(slot: int):
            dev = devices[slot]
            return tile_jit(
                jax.device_put(ids[asl], dev),
                jax.device_put(counts[asl], dev),
                jax.device_put(ids[bsl], dev),
                jax.device_put(counts[bsl], dev),
            )

        def cpu_fallback():
            cpu = jax.local_devices(backend="cpu")[0]
            with jax.default_device(cpu):
                return tile_jit(ids[asl], counts[asl], ids[bsl], counts[bsl])

        with counters.span(ph + "/wait"):
            out = ex.finalize(ex.submit(dispatch), cpu_fallback=cpu_fallback)
            counters.add_fault("ring_blocks_recovered")
            return tuple(np.asarray(t) for t in out)

    try:
        missing0 = _missing_blocks() if resume else list(schedule)
        # the collective step loop is entered only when EVERY process will
        # (fresh store scan is replicated state) and the pod is whole — a
        # partial resume, an inherited degradation, or a JOINER (whose
        # devices are outside the pod mesh by definition) goes straight
        # to the per-block path, which needs no full-pod collective at all
        run_ring = (
            len(missing0) == len(schedule)
            and (hb is None or not hb.dead)
            and not joining
        )
        aborted = None
        if run_ring:
            with counters.span(ph + "/put"):
                ids_d = put_global(ids, NamedSharding(mesh, P(AXIS, None)))
                counts_d = put_global(counts, NamedSharding(mesh, P(AXIS)))

            # build every distinct step program BEFORE the first dispatch,
            # outside the recovery envelope (parallel/faulttol.py): a step
            # that does not trace, lower or compile ends the run with the
            # compiler's message — it must never read as a failed step
            # whose blocks get "recovered" one by one. Every step's B
            # operand has the A operand's shape and sharding.
            with counters.span(ph + "/dispatch", steps=n_steps):
                # the final step skips the dead rotation's ICI hop
                steps = [
                    _ring_step_fn(kind, k, mesh, i < n_steps - 1) for i in range(n_steps)
                ]
                for fn in dict.fromkeys(steps):
                    build_program(fn, ids_d, counts_d, ids_d, counts_d)
            # only the first step's wait still absorbs anything cold
            # (executable load, first DMA): exclude exactly that one from
            # the rolling median — the TileExecutor-style warmup
            # exclusion, sized for a ring whose whole schedule is only
            # half_ring_steps(D) samples
            auto = AutoTimeout(cfg, warmup=RING_STEP_WARMUP)
            # dispatch every step up front: JAX dispatch is async and each
            # step consumes the previous step's device-resident B operand,
            # so the queue keeps the devices as busy as the monolithic
            # program's fori_loop did — the host only pays one python
            # round per step
            def _dispatch_all() -> list[tuple[int, list]]:
                out_pending: list[tuple[int, list]] = []
                b_ids, b_counts = ids_d, counts_d
                for i, fn in enumerate(steps):
                    *outs, b_ids, b_counts = fn(ids_d, counts_d, b_ids, b_counts)
                    out_pending.append((i, outs))
                return out_pending

            def _member_left() -> bool:
                """One cadence-gated liveness look between waits. A wait
                that completes inside wait_elastic's first poll never
                reaches its own check — and with the step programs built
                up front every wait can be that fast — so the loop also
                looks before the dispatch and at each step boundary: a
                join request is admitted (and adopted), a death or drain
                noticed, however fast the steps are. True when a member
                LEFT; a pure join keeps the schedule."""
                gone = (len(hb.dead), len(hb.drained))
                return hb.maybe_check() and (len(hb.dead), len(hb.drained)) != gone

            pending: list[tuple[int, list]] = []
            with counters.span(ph + "/dispatch", steps=n_steps):
                if elastic and _member_left():
                    aborted = "pod membership changed before the first step"
                elif elastic:
                    # the enqueue itself can block inside the collective
                    # transport when a peer dies mid-rendezvous (observed:
                    # a survivor wedged INSIDE dispatch, never reaching the
                    # monitored finalize loop) — so the dispatch loop runs
                    # under heartbeat monitoring too; on a confirmed death
                    # everything falls to per-block recovery. Pure-JOIN
                    # admissions do NOT abandon (join_tolerant, ISSUE 15):
                    # the pod mesh is whole — the joiner works the schedule
                    # tail beside the collective instead
                    ok, res = wait_elastic(
                        _dispatch_all,
                        hb,
                        collective_timeout_s(),
                        what=f"dense ring step dispatch ({kind}, {n_steps} steps)",
                        site="ring_dispatch",
                        join_tolerant=True,
                    )
                    if ok:
                        pending = res
                    else:
                        aborted = "pod membership changed during step dispatch"
                else:
                    try:
                        pending = _dispatch_all()
                    except Exception as e:  # noqa: BLE001 — recovery recomputes
                        if not is_device_fault(e):
                            raise
                        aborted = e
            for i, outs in pending:
                if aborted is not None:
                    break
                # the step span opens BEFORE the chaos fire so a member
                # killed at the boundary leaves its unclosed "B" as crash
                # evidence; the elastic chaos tests SIGKILL a pod member
                # here — with finished steps' blocks already durable
                with counters.span("ring_step", step=i, steps=n_steps):
                    faults.fire("ring_step")
                    t0 = time.perf_counter()
                    try:
                        with counters.span(ph + "/wait", step=i):
                            if elastic:
                                def wait(outs=outs):
                                    faults.fire("ring_dispatch")
                                    jax.block_until_ready(outs)

                                ok, _ = wait_elastic(
                                    wait,
                                    hb,
                                    collective_timeout_s(),
                                    what=f"dense ring step {i + 1}/{n_steps} ({kind})",
                                    site="ring_dispatch",
                                    join_tolerant=True,
                                )
                                if not ok:
                                    aborted = "pod membership changed"
                                    break
                            else:
                                _wait_ready(outs, auto.effective(), "ring_dispatch", None)
                    except WatchdogTimeout as e:
                        counters.add_fault("ring_step_failures")
                        logger.warning(
                            "dense ring: step %d/%d tripped the %ss watchdog — "
                            "recomputing its blocks per-tile",
                            i + 1, n_steps, round(auto.effective(), 1),
                        )
                        aborted = e
                        break
                    except (CollectiveTimeout, FaultTolError):
                        raise  # wedged peer / max_dead exceeded: abort loudly
                    except Exception as e:  # noqa: BLE001 — per-block recovery
                        if not is_device_fault(e):
                            raise
                        counters.add_fault("ring_step_failures")
                        logger.warning(
                            "dense ring: step %d/%d failed (%s) — recomputing "
                            "its blocks per-tile", i + 1, n_steps, e,
                        )
                        aborted = e
                        break
                    auto.note(time.perf_counter() - t0)
                    # the readback of the step's tiles; their saves are
                    # the publish spans inside it
                    with counters.span(ph + "/wait", step=i):
                        _store_step(i, outs)
                    # a drain request is honored at the step boundary: this
                    # step's blocks are durable, the departure note goes
                    # out, and the peers re-deal the rest with no
                    # staleness wait
                    _maybe_drain()
                    if elastic and _member_left():
                        aborted = "pod membership changed"
                        break
                if aborted is None and _join_covered_tail(i):
                    # ring-phase JOIN shortcut (ISSUE 15): admitted
                    # joiner(s) eat whole steps from the schedule TAIL
                    # while this collective works the head — the moment
                    # every later step's blocks are durable in the store,
                    # the remaining waits are dead weight (their tiles
                    # exist; the queued device work completes harmlessly
                    # in the background) and the dense phase ENDS here.
                    counters.add_fault("ring_join_shortcuts")
                    logger.info(
                        "dense ring: joiner(s) %s covered every block past "
                        "step %d/%d — ending the collective schedule early",
                        hb.joined, i + 1, n_steps,
                    )
                    break
            derived = auto.derived()
            if derived is not None:
                # the per-step watchdog deadline the run derived from its
                # own step latencies (same rule as the streaming tiles)
                counters.set_gauge("derived_ring_step_timeout_s", round(derived, 3))

        if pc > 1 and not local_mesh and store is None:
            # store-less pod ring: peers' rows cannot come from a shard
            # store, and recomputing them locally would be D x redundant —
            # exchange host rows once instead (the monolithic gather's
            # equivalent; bit-identical values, same bytes over the wire).
            # A failed step cannot be recovered here (no shared medium to
            # coordinate per-block re-deals): abort with guidance.
            if aborted is not None:
                raise FaultTolError(
                    f"dense ring: a ring step failed on a multi-process pod "
                    f"with no shared block store — per-block recovery needs "
                    f"one (configure_ring / checkpoint_dir). Original "
                    f"failure: {aborted!r}"
                ) from (aborted if isinstance(aborted, BaseException) else None)
            with counters.span(ph + "/wait"):
                _exchange_rows_no_store(
                    mem, mesh, schedule, n_outputs, n_local, n_pad, pid, kind
                )

        # per-block completion: anything still missing — resume gaps, an
        # aborted ring, a dead member's unfinished rows — is recomputed
        # block-by-block. Elastic pods deal missing blocks across the
        # CURRENT live set (re-dealing on every epoch bump) and need no
        # full-pod collective; completion is file-based over the store.
        if not elastic:
            for blk in _missing_blocks():
                mem[blk] = _compute_block(blk)
                _save_block(blk, mem[blk], hb.epoch if hb is not None else 0)
                _maybe_drain()  # the finished block is durable — safe exit
        else:
            stall_budget = collective_timeout_s(DEFAULT_ALLGATHER_TIMEOUT_S)
            done_written = False
            last_progress = time.monotonic()
            progress_sig = None
            last_deal_epoch = -1
            while True:
                _maybe_drain()
                hb.maybe_check()  # adopt what the fast step loop may have missed
                live = list(hb.live)
                missing = _missing_blocks()
                if hb.epoch != last_deal_epoch:
                    if hb.epoch > 0:
                        telemetry.event(
                            "re_deal", unit="ring_block", live=live,
                            missing=len(missing),
                        )
                    last_deal_epoch = hb.epoch
                computed = False
                # ring-phase JOIN (ISSUE 15): while the pod is WHOLE
                # (pure-join churn only) its original members never enter
                # this per-block path — they are still inside the
                # collective step loop, producing blocks in STEP order —
                # so a joiner deals itself blocks from the schedule TAIL
                # (reverse order, split across joiners by rank) and meets
                # the advancing ring in the middle; the pod exits its
                # schedule early the moment the tail is covered (the
                # ring-join shortcut). Any death/drain collapses everyone
                # back to the standard forward schedule-index deal.
                tail_mode = joining and not hb.dead and not hb.drained
                if tail_mode:
                    joiners = sorted(p for p in live if p >= pc) or [pid]
                    rank = joiners.index(pid) if pid in joiners else 0
                    claim = [
                        blk
                        for r, blk in enumerate(reversed(missing))
                        if r % len(joiners) == rank
                    ]
                else:
                    # schedule-index dealing over the CURRENT live set —
                    # deaths and drains shrink it, admitted joiners grow
                    # it, and only still-missing blocks are ever dealt
                    claim = [
                        blk for blk in missing
                        if live[sched_idx[blk] % len(live)] == pid
                    ]
                for blk in claim:
                    computed = True
                    mem[blk] = _compute_block(
                        blk,
                        tail_step=ring_step_of(*blk, D) if tail_mode else None,
                    )
                    missing.remove(blk)
                    _save_block(blk, mem[blk], hb.epoch)
                    _maybe_drain()
                    if tail_mode or hb.maybe_check():
                        # tail mode re-scans after EVERY block: the pod is
                        # publishing the head concurrently, and a stale
                        # claim list would duplicate its work
                        break
                if not missing and not done_written:
                    # publish completion BEFORE leaving: a done-note peer
                    # is never declared dead however stale its beats go
                    hb.mark_done(len(mem))
                    done_written = True
                sig = (len(missing), tuple(hb.live))
                if computed or sig != progress_sig:
                    progress_sig = sig
                    last_progress = time.monotonic()
                if not missing:
                    break
                if hb.maybe_check():
                    continue
                if time.monotonic() - last_progress > stall_budget:
                    raise CollectiveTimeout(
                        f"dense ring completion stalled for {stall_budget:.0f}s:"
                        f" block(s) {missing[:8]}{'...' if len(missing) > 8 else ''}"
                        f" unfinished on live set {hb.live} whose heartbeats are"
                        f" still fresh — a peer is wedged, not dead. Restart the"
                        f" pod; block-level checkpoints will resume finished"
                        f" work."
                    )
                if not computed:
                    time.sleep(min(5.0, max(0.05, hb.cadence)))

        # canonical assembly: schedule order, own blocks from memory, the
        # rest from the store; a corrupt/vanished shard is recomputed INTO
        # ITS OWN PATH (idempotent heal, streaming's contract)
        with counters.span(ph + "/assemble"):
            mats = [np.zeros((n_pad, n_pad), np.float32) for _ in range(n_outputs)]
            for blk in schedule:
                tiles = mem.get(blk)
                if tiles is None:
                    path = shard_of.get(blk) or (
                        _find_block(store, *blk) if store is not None else None
                    )
                    tiles = _load_block(path, n_outputs) if path is not None else None
                    if tiles is None:
                        from drep_tpu.parallel.streaming import _shard_epoch

                        heal_epoch = (
                            _shard_epoch(path)
                            if path is not None
                            else (hb.epoch if hb is not None else 0)
                        )
                        tiles = _compute_block(blk)
                        mem[blk] = tiles
                        _save_block(blk, tiles, heal_epoch)
                a, b = blk
                for oi in range(n_outputs):
                    mats[oi][
                        a * n_local : (a + 1) * n_local, b * n_local : (b + 1) * n_local
                    ] = tiles[oi]
            if half:
                for g in mats:
                    mirror_half_ring(g, D)

        if hb is not None and hb.epoch > 0:
            if elastic:
                # stamped by EVERY survivor that observed the degradation,
                # not a designated leader: a survivor can legitimately
                # finish without ever learning of the death (a peer
                # detected and covered the missing blocks first), so the
                # "lowest live process" may hold a healthy view and never
                # stamp. Concurrent stampers write the same keys — the
                # read-modify-atomic-write race is benign.
                from drep_tpu.utils.ckptmeta import stamp_checkpoint_meta

                stamp = {"pod_epochs": hb.epoch + 1, "dead_processes": hb.dead}
                if hb.drained:
                    stamp["planned_departures"] = hb.drained
                if hb.joined:
                    stamp["pod_joins"] = len(hb.joined)
                stamp_checkpoint_meta(store, stamp)
            logger.warning(
                "dense ring: completed with MEMBERSHIP CHURN — dead %s, "
                "drained %s, joined %s; final members %s covered the "
                "missing blocks per-tile across %d ownership epoch(s)",
                hb.dead, hb.drained, hb.joined, hb.live, hb.epoch + 1,
            )
        return mats, n_computed, D
    finally:
        if hb is not None:
            hb.close()


def sharded_mash_allpairs(
    packed: PackedSketches,
    k: int = 21,
    mesh=None,
    full_grid: bool = False,
    monolithic: bool = False,
    checkpoint_dir: str | None = None,
    ft_config=None,
) -> np.ndarray:
    """[N, N] Mash distance matrix, ring-sharded over the mesh (half-ring
    triangular schedule unless ``full_grid``; host-stepped elastic
    execution unless ``monolithic``)."""
    (dist,) = ring_allpairs(
        packed, "mash", k, mesh=mesh, full_grid=full_grid,
        monolithic=monolithic, checkpoint_dir=checkpoint_dir, ft_config=ft_config,
    )
    with counters.span("primary/assemble"):
        np.fill_diagonal(dist, 0.0)
    return dist


def sharded_containment_allpairs(
    packed: PackedSketches,
    k: int = 21,
    mesh=None,
    full_grid: bool = False,
    monolithic: bool = False,
    checkpoint_dir: str | None = None,
    ft_config=None,
) -> tuple[np.ndarray, np.ndarray]:
    """([N,N] symmetric max-containment ani, [N,N] directional cov),
    ring-sharded over the mesh. The ring ships symmetric raw intersection
    sizes (half-ring schedule); both cov directions derive from `counts`
    on host — same directional-cov contract as every other containment
    path."""
    (inter,) = ring_allpairs(
        packed, "containment", k, mesh=mesh, full_grid=full_grid,
        monolithic=monolithic, checkpoint_dir=checkpoint_dir, ft_config=ft_config,
    )
    with counters.span("secondary/post"):
        return ani_cov_from_intersections(inter, packed.counts, k)
