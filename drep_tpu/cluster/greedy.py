"""Greedy-incremental secondary clustering — the 100k-genome scale path.

Reference parity: `--greedy_secondary_clustering` (drep/d_cluster/
controller.py; SURVEY.md §3.2 — "compare each genome only to existing
cluster representatives; new rep if all < S_ani"; reference mount empty).
Reduces the per-primary-cluster cost from O(m^2) comparisons to O(m·reps).

TPU-shaped execution: genomes are processed in blocks. One device pass
computes the [block, reps] containment numbers plus the [block, block]
within-block numbers; the strictly-sequential assignment logic (a genome
can become a rep mid-block) then runs on host over those precomputed
values — the device sees large fixed-shape batches, never a per-genome
launch. On TPU the comparisons run as rectangular int8 indicator matmuls
over a per-cluster vocabulary-chunk geometry, with the representative set
device-resident and append-only (ops/containment.py::VocabChunkGeometry);
off-TPU they run as searchsorted gather tiles (gathers are fine there).
"""

from __future__ import annotations

from itertools import accumulate
from typing import Any

import numpy as np
import pandas as pd

from drep_tpu.cluster.pairs import NdbColumns, empty_ndb_columns
from drep_tpu.ingest import GenomeSketches
from drep_tpu.ops.containment import (
    VocabChunkGeometry,
    cap_gather_tile,
    containment_cov_tile,
    containment_to_ani,
    pack_secondary,
    rect_from_chunks,
    rect_from_chunks_sharded,
    replicate_on_mesh,
    self_from_chunks,
    shard_rows_on_mesh,
)
from drep_tpu.ops.minhash import PAD_ID
from drep_tpu.ops.rangepart import vocab_extent
from drep_tpu.utils.profiling import counters

def _cov_from_inter(inter: np.ndarray, denom: np.ndarray) -> np.ndarray:
    """cov = inter / denom with zero-count rows/cols pinned to 0 (matches
    the gather tile's where(n>0, ...) contract)."""
    d = np.maximum(denom.astype(np.float32), 1.0)
    return np.where(denom > 0, inter / d, 0.0).astype(np.float32)


def _pad_pack(ids: np.ndarray, counts: np.ndarray, rows: list[int], pad_to: int):
    out_ids = np.full((pad_to, ids.shape[1]), PAD_ID, dtype=np.int32)
    out_counts = np.zeros(pad_to, dtype=np.int32)
    if rows:
        out_ids[: len(rows)] = ids[rows]
        out_counts[: len(rows)] = counts[rows]
    return out_ids, out_counts


def _rep_tile_rows(n_reps: int, base_block: int) -> list[int]:
    """Row counts of the representative tiles a block's program calls run
    against on the matmul route, from the representatives that exist when
    the block is visited: none for none (the block is compared with itself
    alone), a tile of `4 * base_block` rows for every one the
    representatives fill, then the trailing ones padded to the smallest of
    `base_block`, `2 * base_block`, `4 * base_block` that holds them. Three
    shapes a width set at most, so representatives that accumulate inside a
    bucket compile nothing; the floor is `base_block` and no lower, so two
    jobs whose blocks meet 26 and 30 representatives run the same programs."""
    rep_tile = 4 * base_block
    full, rest = divmod(n_reps, rep_tile)
    tiles = [rep_tile] * full
    if rest:
        tiles.append(next(b for b in (base_block, 2 * base_block, rep_tile) if b >= rest))
    return tiles


def _put_chunks(chunks: list[np.ndarray], booked: dict, side: str, mesh=None, replicated=False):
    """Chunk tensors onto the device, or onto a mesh (row-sharded, or a copy
    a device when `replicated`), under the span `secondary/greedy_put` and
    booked in `booked[side]` by the bytes that cross the link: a replicated
    put crosses once a device. The span ends when the arrays are there."""
    import jax
    import jax.numpy as jnp

    n_dev = 1 if mesh is None else int(mesh.devices.size)
    nbytes = sum(c.nbytes for c in chunks) * (n_dev if replicated else 1)
    with counters.span("secondary/greedy_put", bytes=nbytes, devices=n_dev):
        if mesh is None:
            out = [jnp.asarray(c) for c in chunks]
        else:
            put = replicate_on_mesh if replicated else shard_rows_on_mesh
            out = [put(c, mesh) for c in chunks]
        jax.block_until_ready(out)
    booked[side] += nbytes
    return out


def _ndb_from_rows(ndb_rows: list[dict], pc: int, names: list[str]) -> NdbColumns:
    """THE greedy Ndb assembly, shared by both comparison sources: each
    visited genome's rows against the representatives it met, the two name
    columns as positions in `names`."""
    if not ndb_rows:
        return empty_ndb_columns()
    cols = {key: np.concatenate([r[key] for r in ndb_rows]) for key in ndb_rows[0]}
    cols["primary_cluster"] = np.full(len(cols["ani"]), pc, dtype=np.int64)
    return NdbColumns(cols, names)


def greedy_assign_from_matrices(
    gs: GenomeSketches,
    indices: list[int],
    pc: int,
    kw: dict[str, Any],
    ani: np.ndarray,
    cov: np.ndarray,
) -> tuple[NdbColumns, np.ndarray]:
    """Greedy representative assignment from PRECOMPUTED (ani, cov)
    matrices — the small-cluster path when `--greedy_secondary_clustering`
    is on. Semantics identical to :func:`greedy_secondary_cluster`
    (largest-first visiting order, same two-sided coverage gate, same Ndb
    rows: each genome vs the representatives existing when it was
    visited); only the comparison source differs — one batched device call
    covering MANY clusters already produced the matrices, instead of a
    per-cluster engine invocation. At the 100k scale most primary clusters
    are tiny, and a per-cluster greedy call apiece (device dispatches,
    block padding to 128 rows for a 3-genome cluster) was measured
    pathologically slower than the batch route — the exact fan-out cost
    the batched path exists to avoid (cluster/controller.py
    SMALL_CLUSTER_MAX rationale).

    Books the span `secondary/greedy_assign` (the engine's name for the
    same work) and the cluster into the record's `secondary_greedy_batched`."""
    with counters.span("secondary/greedy_assign"):
        ndb, labels = _assign_from_matrices(gs, indices, pc, kw, ani, cov)
    counters.add_greedy_batched(rows=len(indices), compared_pairs=len(ndb))
    return ndb, labels


def _assign_from_matrices(gs, indices, pc, kw, ani, cov) -> tuple[NdbColumns, np.ndarray]:
    s_ani, cov_thresh = kw["S_ani"], kw["cov_thresh"]
    m = len(indices)
    n_kmers = [int(gs.gdb["n_kmers"].iloc[i]) for i in indices]
    order = sorted(range(m), key=lambda t: -n_kmers[t])
    names = [gs.names[i] for i in indices]
    labels = np.zeros(m, dtype=np.int64)
    reps: list[int] = []
    ndb_rows: list[dict] = []
    for t in order:
        if reps:
            r = np.asarray(reps)
            cov_row = cov[t, r].astype(np.float64)
            cov_rev = cov[r, t].astype(np.float64)
            ani_row = ani[t, r].astype(np.float64)
            ndb_rows.append(
                {
                    "reference": r,
                    "querry": np.full(len(r), t),
                    "ani": ani_row,
                    "alignment_coverage": cov_row,
                    "ref_coverage": cov_rev,
                    "querry_coverage": cov_row,
                }
            )
            ok = (ani_row >= s_ani) & (cov_row >= cov_thresh) & (cov_rev >= cov_thresh)
            if ok.any():
                labels[t] = int(np.argmax(np.where(ok, ani_row, -1.0))) + 1
                continue
        reps.append(t)
        labels[t] = len(reps)
    return _ndb_from_rows(ndb_rows, pc, names), labels


def greedy_secondary_cluster(
    gs: GenomeSketches,
    bdb: pd.DataFrame,
    indices: list[int],
    pc: int,
    kw: dict[str, Any],
    block: int = 128,
) -> tuple[NdbColumns, np.ndarray]:
    """Returns (Ndb rows for the comparisons performed, labels 1..R).

    Genomes are visited largest-first (most k-mers), the reference's
    heuristic that big complete genomes make good representatives.

    Books the route that served in `secondary_paths` (`greedy_matmul` on a
    TPU or under DREP_TPU_GREEDY_MATMUL, `greedy_gather` off it), one
    entry of the record's `secondary_greedy_calls`, and the spans
    `secondary/pack` (the cluster's shared-vocabulary pack, ranked by
    native/rank.cc on the job's `-p` threads, `kw["processes"]`: args
    `hashes=`, `path=` native | numpy, `workers=`; also booked in the
    record's `secondary_pack`), `secondary/greedy_layout` (the chunk geometry, every
    block's and every new representative's repack and pad, the
    representatives' shipment), `secondary/greedy_wait` (one a block: its
    tiles against the representatives, its self comparison, the readbacks;
    `devices=` says how many served, `rep_pad=` the representative rows it
    was computed against), `secondary/greedy_assign`, and since
    ISSUE 52 `secondary/greedy_pad` (a block's rows copied into a padded
    block on the host) and `secondary/greedy_extent` (the cluster's
    vocabulary extent, computed for the counter's entry alone). On the
    matmul route every put of chunk tensors is a `secondary/greedy_put`
    (`bytes=`, `devices=`) inside the span it belongs to: a block's chunks
    inside `greedy_wait` (on a mesh once a representative tile and twice
    for the self comparison, ROADMAP S3), a mesh's representative tiles
    inside `greedy_layout` (a filled one, once) or `greedy_wait` (the
    trailing one, once a block). The cluster's entry says who served and
    what crossed (`mesh_devices`, `block_bytes`, `rep_bytes`,
    `rep_tiles_replicated`, `partial_tile_ships`).

    The representative side of a block's program calls on the matmul route
    is sized by the representatives that exist when the block is visited
    (:func:`_rep_tile_rows`; ISSUE 55): a block that meets none (the first
    of every cluster) makes no call against representatives and is compared
    with itself alone (`blocks_without_reps` in the entry), a tile the
    representatives fill is `rep_tile` = 4 x 128 rows (on a mesh replicated
    once and cached), and the trailing tile is padded to 128, 256 or 512
    rows: on one chip a `jnp.pad` of the resident rows, on a mesh the host
    pad and one replicated put a block. `rep_rows_shipped` sums the rows
    really computed against. The gather route keeps whole tiles of one
    block, at least one.
    """
    s_ani, cov_thresh = kw["S_ani"], kw["cov_thresh"]
    m = len(indices)
    order = sorted(range(m), key=lambda t: -int(gs.gdb["n_kmers"].iloc[indices[t]]))

    packed = pack_secondary(
        [gs.scaled[indices[t]] for t in order], [gs.names[indices[t]] for t in order],
        kw.get("processes", 1),
    )
    ids, counts = packed.ids, packed.counts
    import jax

    # DREP_TPU_GREEDY_MATMUL=1 forces the matmul path off-TPU so the CPU
    # test mesh can exercise the sharded route (gathers are otherwise the
    # better CPU kernel)
    from drep_tpu.utils import envknobs

    use_matmul = (
        jax.devices()[0].platform == "tpu"
        or envknobs.env_bool("DREP_TPU_GREEDY_MATMUL")
    )
    counters.add_path("greedy_matmul" if use_matmul else "greedy_gather")
    mesh = None
    base_block = block
    if use_matmul:
        from drep_tpu.cluster.engines import _mesh_or_none

        # secondary work: live-clamped to local devices on pods (the
        # retryable-secondary contract — engines._mesh_or_none)
        mesh = _mesh_or_none(kw.get("mesh_shape"), m, local_only=True)
        if mesh is not None:
            # candidate blocks shard over the mesh rows (reps replicate —
            # they are the small append-only side); a D-device mesh
            # processes D single-chip blocks' worth of candidates per
            # pass, so scale the block to keep per-device tiles full
            block = block * int(mesh.devices.size)
    if not use_matmul:
        # cap the [block, block, S] gather working set (TPU-crash guard —
        # the matmul path has its own vocabulary-chunk budget instead)
        block = cap_gather_tile(ids.shape[1], block)

    labels_ordered = np.zeros(m, dtype=np.int64)
    reps: list[int] = []  # positions (in `order` space) of representatives
    ndb_rows: list[dict] = []
    # what the cluster's counter entry sums over its blocks
    # (Counters.add_greedy_call)
    booked = dict.fromkeys(
        ("blocks", "blocks_without_reps", "rep_rows_shipped", "rep_rows_real", "id_slots",
         "device_calls", "block_bytes", "rep_bytes", "rep_tiles_replicated",
         "partial_tile_ships"), 0
    )
    n_dev = 1 if mesh is None else int(mesh.devices.size)

    if use_matmul:
        import jax.numpy as jnp

        # chunk geometry fixed ONCE from the full cluster: any row subset
        # repacks in O(rows), and the append-only representative set lives
        # as device-resident per-chunk tensors that only receive NEW rows
        # (host->device traffic O(total reps), not O(reps x blocks)).
        # The rep side is consumed in row tiles of a few FIXED sizes
        # (_rep_tile_rows: `rep_tile` rows at most, the trailing tile in a
        # bucket, none while there are no representatives): stable jit
        # shapes (no recompile as reps grow inside a bucket), a bounded
        # [tile, v_chunk] indicator however many representatives
        # accumulate, and an indicator build (a scatter whose cost is the id
        # slots, padding included) sized to what the block really meets.
        # The tile rides the UNSCALED block: under a mesh the candidate
        # block grows by D but the replicated rep side should not.
        rep_tile = 4 * base_block
        with counters.span("secondary/greedy_layout"):
            geom = VocabChunkGeometry(ids, max_rows_per_call=max(rep_tile, block))
            if mesh is None:
                rep_chunks_dev = [
                    jnp.asarray(np.full((0, w), PAD_ID, np.int32)) for w in geom.widths
                ]
            else:
                # mesh mode: reps stay HOST-side (appending to a replicated
                # device array is not incremental); FILLED rep tiles are
                # replicated once and cached — only the trailing partial tile
                # re-crosses the link per block
                rep_chunks_host = [np.full((0, w), PAD_ID, np.int32) for w in geom.widths]
                rep_tiles_cached: list[list] = []  # per filled tile: replicated chunks
        n_shipped = 0  # reps already resident on device / in the host store
        shape = {"rep_tile": rep_tile, "v_chunk": geom.v_chunk, "chunks": geom.n_chunks,
                 "widths": int(sum(geom.widths))}
    else:
        rep_tile = block
        shape = {"rep_tile": rep_tile, "v_chunk": 0, "chunks": 0, "widths": int(ids.shape[1])}

    for b0 in range(0, m, block):
        rows = list(range(b0, min(b0 + block, m)))
        nb = len(rows)
        with counters.span("secondary/greedy_pad", rows=nb, pad_to=block):
            b_ids, b_counts = _pad_pack(ids, counts, rows, block)
        if use_matmul:
            tiles = _rep_tile_rows(len(reps), base_block)
        else:
            tiles = [rep_tile] * max(-(-len(reps) // rep_tile), 1)
        rep_pad = sum(tiles)
        booked["blocks"] += 1
        booked["blocks_without_reps"] += not tiles
        booked["rep_rows_shipped"] += rep_pad
        booked["rep_rows_real"] += len(reps)

        # block vs existing reps (padded to whole tiles for shape reuse);
        # both coverage directions — the gate, like the default all-pairs
        # path, requires cov >= cov_thresh in BOTH, and the ANI estimate is
        # max-containment (see ops/containment.py module docstring).
        # One intersection-count matrix yields BOTH directions (the sets
        # are symmetric; only the denominators differ): on TPU it comes
        # from the rectangular chunked MXU matmul (gather tiles serialize
        # on the scalar unit there); off-TPU the gather tiles are fine.
        if use_matmul:
            with counters.span("secondary/greedy_layout"):
                if n_shipped < len(reps):
                    new_chunks = geom.rows_chunks(np.array(reps[n_shipped:]))
                    if mesh is None:
                        rep_chunks_dev = [
                            jnp.concatenate([old, jnp.asarray(nc)]) if old.shape[0] else jnp.asarray(nc)
                            for old, nc in zip(rep_chunks_dev, new_chunks)
                        ]
                        booked["rep_bytes"] += sum(nc.nbytes for nc in new_chunks)
                    else:
                        rep_chunks_host = [
                            np.concatenate([old, nc])
                            for old, nc in zip(rep_chunks_host, new_chunks)
                        ]
                        # replicate newly-FILLED tiles once; they never
                        # change again (reps are append-only)
                        while (len(rep_tiles_cached) + 1) * rep_tile <= len(reps):
                            t = len(rep_tiles_cached)
                            rep_tiles_cached.append(_put_chunks(
                                [rc[t * rep_tile : (t + 1) * rep_tile] for rc in rep_chunks_host],
                                booked, "rep_bytes", mesh, replicated=True,
                            ))
                            booked["rep_tiles_replicated"] += 1
                    booked["id_slots"] += (len(reps) - n_shipped) * shape["widths"]
                    n_shipped = len(reps)
                r_counts = np.zeros(rep_pad, np.int32)
                r_counts[: len(reps)] = counts[reps]
                blk_chunks = [
                    np.pad(bc, ((0, block - nb), (0, 0)), constant_values=PAD_ID)
                    for bc in geom.rows_chunks(np.array(rows))
                ]
                booked["id_slots"] += block * shape["widths"]
            with counters.span(
                "secondary/greedy_wait", rows=nb, reps=len(reps), rep_pad=rep_pad,
                chunks=geom.n_chunks, devices=n_dev,
            ):
                # one program call a chunk: each rep tile, then the self comparison
                booked["device_calls"] += (len(tiles) + 1) * geom.n_chunks
                if mesh is None:
                    # the block's chunk tensors go to device ONCE and serve both
                    # the vs-reps tiles and the self comparison
                    blk_dev = _put_chunks(blk_chunks, booked, "block_bytes")
                inter = np.empty((block, rep_pad), np.float32)
                for t0, tile_rows in zip(accumulate(tiles, initial=0), tiles):
                    # the tile's own rows of every chunk tensor, padded to the tile
                    pad = ((0, tile_rows - min(len(reps) - t0, tile_rows)), (0, 0))
                    if mesh is not None:
                        ti = t0 // rep_tile
                        if ti < len(rep_tiles_cached):
                            tile_chunks = rep_tiles_cached[ti]  # replicated, cached
                        else:
                            # trailing partial tile: host pad, shipped this block
                            tile_chunks = _put_chunks(
                                [
                                    np.pad(rc[t0 : t0 + tile_rows], pad, constant_values=PAD_ID)
                                    for rc in rep_chunks_host
                                ],
                                booked, "rep_bytes", mesh, replicated=True,
                            )
                            booked["partial_tile_ships"] += 1
                        # on a mesh the block's chunks cross the link again for
                        # every representative tile (and twice more below)
                        inter[:, t0 : t0 + tile_rows] = rect_from_chunks_sharded(
                            _put_chunks(blk_chunks, booked, "block_bytes", mesh),
                            tile_chunks, geom.v_chunk, mesh,
                        )
                    else:
                        tile_chunks = [
                            jnp.pad(rc[t0 : t0 + tile_rows], pad, constant_values=PAD_ID)
                            for rc in rep_chunks_dev
                        ]
                        inter[:, t0 : t0 + tile_rows] = rect_from_chunks(
                            blk_dev, tile_chunks, geom.v_chunk
                        )
                cov_vs_reps = _cov_from_inter(inter, b_counts[:, None])
                cov_rev_reps = _cov_from_inter(inter, r_counts[None, :])
                # self comparison: symmetric, ONE indicator build (the
                # rect call built two identical ones per block)
                if mesh is not None:
                    inter_self = rect_from_chunks_sharded(
                        _put_chunks(blk_chunks, booked, "block_bytes", mesh),
                        _put_chunks(blk_chunks, booked, "block_bytes", mesh, replicated=True),
                        geom.v_chunk, mesh,
                    ).astype(np.float32)
                else:
                    inter_self = self_from_chunks(blk_dev, geom.v_chunk).astype(np.float32)
                c_blk = _cov_from_inter(inter_self, b_counts[:, None])
        else:
            with counters.span("secondary/greedy_layout"):
                r_ids, r_counts = _pad_pack(ids, counts, reps, rep_pad)
                booked["id_slots"] += (block + rep_pad) * shape["widths"]
            with counters.span(
                "secondary/greedy_wait", rows=nb, reps=len(reps), rep_pad=rep_pad, chunks=0,
                devices=1,
            ):
                # two gather tiles a representative tile, one for the block itself;
                # each call ships both its operands (a jitted call on host arrays)
                booked["device_calls"] += 2 * (rep_pad // rep_tile) + 1
                booked["block_bytes"] += (2 * (rep_pad // rep_tile) + 2) * b_ids.nbytes
                booked["rep_bytes"] += 2 * r_ids.nbytes
                cov_vs_reps = np.zeros((block, rep_pad), np.float32)
                cov_rev_reps = np.zeros((block, rep_pad), np.float32)
                for r0 in range(0, rep_pad, block):
                    c = containment_cov_tile(
                        b_ids, b_counts, r_ids[r0 : r0 + block], k=gs.k
                    )
                    c_rev = containment_cov_tile(
                        r_ids[r0 : r0 + block], r_counts[r0 : r0 + block], b_ids, k=gs.k
                    )
                    cov_vs_reps[:, r0 : r0 + block] = np.asarray(c)
                    cov_rev_reps[:, r0 : r0 + block] = np.asarray(c_rev).T

                # block vs itself (for genomes that become reps mid-block)
                c_blk = np.asarray(containment_cov_tile(b_ids, b_counts, b_ids, k=gs.k))

        # assignment: sequential over genomes (a genome can become a rep
        # mid-block) but VECTORIZED over reps — the O(reps) inner work is
        # numpy row math, never a Python pair loop (100k-scale requirement)
        with counters.span("secondary/greedy_assign"):
            n_pre = len(reps)  # reps existing before this block (all < b0)
            in_block: list[int] = []  # block-local positions of mid-block reps
            for t, pos in enumerate(rows):
                cov_row = np.concatenate([cov_vs_reps[t, :n_pre], c_blk[t, in_block]])
                cov_rev = np.concatenate([cov_rev_reps[t, :n_pre], c_blk[in_block, t]])
                ani_row = containment_to_ani(np.maximum(cov_row, cov_rev), gs.k)
                if len(ani_row):
                    rep_pos_arr = np.array(reps, dtype=np.int64)
                    ndb_rows.append(
                        {
                            "reference": rep_pos_arr,
                            "querry": np.full(len(ani_row), pos),
                            "ani": ani_row.astype(np.float64),
                            "alignment_coverage": cov_row.astype(np.float64),
                            "ref_coverage": cov_rev.astype(np.float64),
                            "querry_coverage": cov_row.astype(np.float64),
                        }
                    )
                    ok = (ani_row >= s_ani) & (cov_row >= cov_thresh) & (cov_rev >= cov_thresh)
                    if ok.any():
                        masked = np.where(ok, ani_row, -1.0)
                        labels_ordered[pos] = int(np.argmax(masked)) + 1
                        continue
                reps.append(pos)
                in_block.append(pos - b0)
                labels_ordered[pos] = len(reps)

    with counters.span("secondary/greedy_assign"):
        # back to the original `indices` order
        labels = np.zeros(m, dtype=np.int64)
        for t in range(m):
            labels[order[t]] = labels_ordered[t]
        ndb = _ndb_from_rows(ndb_rows, pc, packed.names)
    # the record's own cost: a mask and a copy of every real id for one number
    with counters.span("secondary/greedy_extent", rows=m):
        extent = vocab_extent(ids)
    counters.add_greedy_call(
        rows=m, block_rows=block, reps=len(reps), extent=extent,
        hashes=int(counts.sum()), compared_pairs=len(ndb), mesh_devices=n_dev,
        **shape, **booked,
    )
    return ndb, labels
