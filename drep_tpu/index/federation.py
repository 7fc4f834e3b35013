"""Federated genome index: range-partitioned stores under one meta-manifest.

The single-manifest index (ISSUE 6) tops out at one host's bucket join
and one store's shard families. This module is the multi-pod scale path
(ISSUE 13): the genome space is split into P range partitions keyed by a
sketch-derived code (index/meta.py — the splitmix64-finalized min-hash,
bisected over equal uint64 ranges pinned at creation), each partition a
FULL existing index store (own ``manifest.json``, own sketch/edge/state
families, self-healing exactly as today), with one federation layer
above them::

    federation.json               -- THE meta-manifest (index/meta.py):
                                     every partition's (range, generation,
                                     manifest checksum), the cross-shard
                                     list, and the union state pointer.
                                     The federation-level commit point.
    part_000/ ... part_NNN/       -- one complete index store each.
    cross/cross_g%06d.npz         -- per-federation-generation CROSS-
                                     partition retained edges in union
                                     coordinates (jj in [lo, hi)), plus
                                     the (pid, local) mapping for that
                                     union range — the mapping's
                                     redundant copy (heal anchor when
                                     the union state rots).
    state/fedstate_g%06d.npz      -- the union derived state: the
                                     append-only (pid, local) admission
                                     order, union primary/secondary
                                     labels, scores, and the winner
                                     table.

Update protocol (``index update`` on a federated root): new genomes are
sketched once, routed to partitions by range code, and each dirty
partition runs its OWN K x N rect compare as an INDEPENDENT unit —
in-process one at a time, or as concurrent subprocess pods
(``--fed_pods`` / ``DREP_TPU_FED_PODS``; each pod is the ordinary
``index update`` CLI on one partition store, crash-resumable on its own
pending checkpoint exactly as today). A partition-level failure leaves
that partition at its old generation and the run publishes an HONEST
PARTIAL meta-manifest (the failed partitions and their unadmitted
genomes named in the summary and in the meta's ``partial`` note) — never
a torn federation generation.

Only boundary LSH buckets cross partitions: partition packs rank ids
locally (two stores' packed ids cannot be joined), so the cross join
bands the RAW bottom hashes into a shared 2^30 code space
(rangepart.hash_code_matrix), range-shards that code space with
``rangepart.partition_by_range`` (band-key-sharded: every shard's
(pair-code, count) partial is independently computable), and folds the
partials through ``ops.lsh.merge_code_counts`` — the multi-process
generalization of the single-host ``--prune_join_chunk`` fold. A
retained cross-partition pair shares at least one band code (the lsh.py
recall derivation with a many-to-one monotone key map), so candidates
have recall 1.0; exact distances then run through the real streaming
engine over just the candidate-involved subset (pair distances are
pack-independent, so the values are bit-identical to a union run's).

Commit order per federation generation: partitions first (each its own
atomic manifest publish), then the cross shard and union state under
deterministic generation-stamped names, then ``federation.json`` LAST.
A SIGKILL anywhere leaves readers at the old federation generation —
``load_federated`` TRUNCATES every partition to the genome count the
meta records, so a partition that published ahead of a killed meta
publish is invisible until the rerun converges (chaos-tested; the
``partition_update`` and ``meta_publish`` fault sites make the worst
points deterministic).

Pinned invariant (property-tested like PR 6's): federated ==
from-scratch dereplicate on the union — labels up to renumbering and
winner sets — across partition counts, split schedules including the
K=1 trickle, and near-boundary pairs the routing separates.

Serving (ISSUE 14): union assembly is the ORACLE path; a serve replica
runs the streaming per-partition classify instead — see
:class:`FederatedResident` below (coarse-code routing, LRU partition
residency, partition health state machine, PARTIAL verdicts), pinned
identical to the union path's verdicts.
"""

from __future__ import annotations

import os
import subprocess
import sys
import time
from dataclasses import dataclass

import numpy as np
import pandas as pd

from drep_tpu.errors import UserInputError
from drep_tpu.index import meta as fedmeta
from drep_tpu.index.store import IndexStore, LoadedIndex, empty_index, load_index, read_payload
from drep_tpu.index.update import (
    _admit_batch,
    _retention,
    index_update,
    recluster,
    sketch_batch,
)
from drep_tpu.utils.logger import get_logger

_STAT_COLS = ("length", "N50", "contigs", "n_kmers")
_EMPTY_EDGES = lambda: (  # noqa: E731 — one-line triple used five times
    np.empty(0, np.int64), np.empty(0, np.int64), np.empty(0, np.float32)
)


class FederationStore:
    """Path bookkeeping + federation-level shard (de)serialization."""

    def __init__(self, location: str):
        self.location = os.path.abspath(location)

    # ---- paths -----------------------------------------------------------
    @property
    def meta_path(self) -> str:
        return fedmeta.meta_path(self.location)

    def exists(self) -> bool:
        return fedmeta.is_federated(self.location)

    def partition_dir(self, pid: int) -> str:
        return os.path.join(self.location, fedmeta.partition_dir_name(pid))

    def cross_shard_name(self, gen: int) -> str:
        return os.path.join("cross", f"cross_g{gen:06d}.npz")

    def fedstate_name(self, gen: int) -> str:
        return os.path.join("state", f"fedstate_g{gen:06d}.npz")

    def routing_name(self, gen: int) -> str:
        return os.path.join("routing", f"summary_g{gen:06d}.npz")

    def abspath(self, rel: str) -> str:
        return os.path.join(self.location, rel)

    def ensure_dirs(self) -> None:
        for sub in ("cross", "state", "routing", "log"):
            os.makedirs(os.path.join(self.location, sub), exist_ok=True)

    # ---- meta ------------------------------------------------------------
    def read_meta(self) -> dict:
        return fedmeta.read_meta(self.location)

    def publish_meta(self, meta: dict) -> None:
        fedmeta.publish_meta(self.location, meta)

    # ---- federation shard families --------------------------------------
    def write_cross_shard(
        self, rel: str, ii, jj, dd, map_pid, map_local
    ) -> None:
        """One federation generation's cross-partition edges (union
        coords, canonically sorted) + the (pid, local) mapping of the
        union range the generation admitted — the mapping's redundant
        copy, like state's redundant names for sketch shards."""
        from drep_tpu.utils.ckptmeta import atomic_savez

        order = np.lexsort((jj, ii))
        os.makedirs(os.path.dirname(self.abspath(rel)), exist_ok=True)
        atomic_savez(
            self.abspath(rel),
            ii=np.asarray(ii, np.int64)[order],
            jj=np.asarray(jj, np.int64)[order],
            dist=np.asarray(dd, np.float32)[order],
            map_pid=np.asarray(map_pid, np.int64),
            map_local=np.asarray(map_local, np.int64),
        )

    def write_fedstate(
        self, rel: str, idx: LoadedIndex, part_of: np.ndarray, local_of: np.ndarray
    ) -> None:
        from drep_tpu.utils.ckptmeta import atomic_savez

        os.makedirs(os.path.dirname(self.abspath(rel)), exist_ok=True)
        atomic_savez(
            self.abspath(rel),
            part_of=np.asarray(part_of, np.int64),
            local_of=np.asarray(local_of, np.int64),
            admitted_generation=np.asarray(idx.admitted, np.int64),
            primary=np.asarray(idx.primary, np.int64),
            suffix=np.asarray(idx.suffix, np.int64),
            score=np.asarray(idx.score, np.float64),
            winner_cluster=idx.winners["cluster"].to_numpy().astype(str),
            winner_genome=idx.winners["genome"].to_numpy().astype(str),
            winner_score=idx.winners["score"].to_numpy().astype(np.float64),
        )

    def write_routing_summary(
        self, rel: str, bottoms: list[np.ndarray], part_of: np.ndarray,
        n_partitions: int,
    ) -> None:
        """The partition routing summaries (ISSUE 14): one coarse-code
        bitmap per partition (rangepart.code_summary_bitmap) over the
        CURRENT union — what lets a serve replica route a query batch to
        only the partitions whose genomes can share a band code with it,
        without holding any sketch payload resident. Deterministic per
        union content, so a killed run's rerun rewrites it identically."""
        from drep_tpu.ops import rangepart
        from drep_tpu.utils.ckptmeta import atomic_savez

        part_of = np.asarray(part_of, np.int64)
        bitmaps = np.stack(
            [
                rangepart.code_summary_bitmap(
                    [bottoms[int(i)] for i in np.nonzero(part_of == p)[0]]
                )
                for p in range(int(n_partitions))
            ]
        ) if n_partitions else np.zeros((0, 1), np.uint64)
        os.makedirs(os.path.dirname(self.abspath(rel)), exist_ok=True)
        atomic_savez(
            self.abspath(rel),
            bitmaps=bitmaps,
            bits=np.int64(rangepart.ROUTE_SUMMARY_BITS),
        )

    def gc_states(self, keep_rel: str, keep_routing_rel: str | None = None) -> None:
        """Best-effort removal of superseded union states (and routing
        summaries) — strictly AFTER the meta publish (same rule as
        IndexStore.gc_states)."""
        import contextlib

        families = [("state", "fedstate_g", os.path.basename(keep_rel))]
        if keep_routing_rel is not None:
            families.append(
                ("routing", "summary_g", os.path.basename(keep_routing_rel))
            )
        for sub, prefix, keep in families:
            fam_dir = os.path.join(self.location, sub)
            if os.path.isdir(fam_dir):
                for f in os.listdir(fam_dir):
                    if f != keep and f.startswith(prefix) and f.endswith(".npz"):
                        with contextlib.suppress(OSError):
                            os.remove(os.path.join(fam_dir, f))


# ---------------------------------------------------------------------------
# boundary-bucket cross-partition join
# ---------------------------------------------------------------------------


def cross_candidates(
    bottoms: list[np.ndarray], part_of: np.ndarray, min_col: int = 0
) -> tuple[np.ndarray, np.ndarray]:
    """Every cross-partition pair that can survive the retention bound:
    band the raw bottom hashes into the shared 2^30 code space, range-
    shard the code space (``rangepart.partition_by_range`` — boundary
    buckets are exactly the band codes present in more than one
    partition), join within each shard, and fold the per-shard
    (pair-code, count) partials through ``lsh.merge_code_counts``.

    `min_col` keeps only pairs reaching the union's new-genome tail
    (the federated update's rectangular restriction). Returns union-
    coordinate (ii, jj) with ii < jj. Recall 1.0: a retained pair shares
    a raw bottom hash inside both sketches (the lsh.py derivation), and
    the code map is many-to-one — shared hash implies shared code."""
    from drep_tpu.ops import rangepart
    from drep_tpu.ops.lsh import _iter_pair_codes, merge_code_counts
    from drep_tpu.ops.minhash import PAD_ID
    from drep_tpu.utils import envknobs

    n = len(bottoms)
    part_of = np.asarray(part_of, np.int64)
    empty = (np.empty(0, np.int64), np.empty(0, np.int64))
    if n < 2 or len(np.unique(part_of)) < 2:
        return empty
    codes = rangepart.hash_code_matrix(bottoms)
    shard_max = envknobs.env_int("DREP_TPU_FED_SHARD_MAX")
    mats: list[np.ndarray] = []
    owners: list[np.ndarray] = []
    for p in np.unique(part_of):
        rows = np.nonzero(part_of == p)[0]
        mats.append(codes[rows])
        owners.append(rows)

    def shard_partials():
        # one iteration = one disjoint band-code range = one join shard;
        # a multi-process deployment computes these partials on separate
        # hosts and folds them through the same accumulator
        for _origin, buckets in rangepart.partition_by_range(mats, shard_max):
            flat_codes: list[np.ndarray] = []
            flat_owner: list[np.ndarray] = []
            for b, own in zip(buckets, owners):
                r, c = np.nonzero(b != PAD_ID)
                flat_codes.append(b[r, c])
                flat_owner.append(own[r])
            fc = np.concatenate(flat_codes)
            fo = np.concatenate(flat_owner)
            order = np.argsort(fc, kind="stable")
            ks, gs = fc[order], fo[order]
            starts = np.flatnonzero(np.r_[True, ks[1:] != ks[:-1]])
            sizes = np.diff(np.r_[starts, len(ks)])
            for batch in _iter_pair_codes(starts, sizes, gs, n, 1 << 20):
                lo, hi = batch // n, batch % n
                sel = part_of[lo] != part_of[hi]
                if min_col > 0:
                    sel &= hi >= min_col
                if sel.any():
                    yield batch[sel]

    uniq, _counts = merge_code_counts(shard_partials())
    if not len(uniq):
        return empty
    return uniq // n, uniq % n


def cross_edges(
    union: LoadedIndex,
    part_of: np.ndarray,
    cand_ii: np.ndarray,
    cand_jj: np.ndarray,
    min_col: int = 0,
) -> tuple[np.ndarray, np.ndarray, np.ndarray, int]:
    """Exact retained cross-partition edges for the candidate pairs:
    pack ONLY the candidate-involved genomes and run the real streaming
    engine over candidate-occupied tiles (pair distances are pack-
    independent, so values are bit-identical to a union run's). Returns
    (ii, jj, dist, pairs_compared) in union coords, canonically sorted,
    filtered to cross-partition pairs with jj >= min_col."""
    from drep_tpu.ops.lsh import CandidateSet
    from drep_tpu.ops.minhash import pack_sketches
    from drep_tpu.parallel.streaming import streaming_mash_edges

    if not len(cand_ii):
        return (*_EMPTY_EDGES(), 0)
    p = union.params
    _, keep = _retention(p)
    subset = np.unique(np.concatenate([cand_ii, cand_jj]))
    li = np.searchsorted(subset, cand_ii)
    lj = np.searchsorted(subset, cand_jj)
    packed = pack_sketches(
        [union.bottom[int(u)] for u in subset],
        [union.names[int(u)] for u in subset],
        int(p["sketch_size"]),
    )
    prune = CandidateSet(
        ii=li, jj=lj, n=len(subset), params={"prune_scheme": "fed_boundary"}
    )
    ii, jj, dd, pairs = streaming_mash_edges(
        packed, int(p["kmer_size"]), keep,
        block=int(p["streaming_block"]), prune=prune,
    )
    ui, uj = subset[ii], subset[jj]
    # candidate-occupied tiles also emit co-resident intra-partition and
    # old-old pairs — both already stored elsewhere; keep only the
    # shard's own slice of the union edge set
    sel = np.asarray(part_of)[ui] != np.asarray(part_of)[uj]
    if min_col > 0:
        sel &= uj >= min_col
    ui, uj, dd = ui[sel], uj[sel], dd[sel]
    order = np.lexsort((uj, ui))
    return ui[order], uj[order], dd[order], int(pairs)


# ---------------------------------------------------------------------------
# federated load (the union view every reader consumes)
# ---------------------------------------------------------------------------


def _truncate_partition(pidx: LoadedIndex, n_p: int) -> LoadedIndex:
    """The partition AS OF the meta's recorded generation: its first
    `n_p` genomes and the edges among them. Partition stores are append-
    only in genome-index space, so the prefix IS the old generation's
    content — this is how a stale meta never exposes a half-published
    federation generation."""
    if pidx.n <= n_p:
        return pidx
    ii, jj, dd = pidx.edges
    sel = jj < n_p  # ii < jj, so both endpoints are inside the prefix
    return LoadedIndex(
        location=pidx.location, params=pidx.params, generation=pidx.generation,
        names=pidx.names[:n_p], locations=pidx.locations[:n_p],
        gdb=pidx.gdb.iloc[:n_p].reset_index(drop=True),
        admitted=pidx.admitted[:n_p],
        bottom=pidx.bottom[:n_p], scaled=pidx.scaled[:n_p],
        edges=(ii[sel], jj[sel], dd[sel]),
        primary=pidx.primary[:n_p], suffix=pidx.suffix[:n_p],
        score=pidx.score[:n_p], winners=pidx.winners,
        healed=pidx.healed,
    )


def _read_npz_or_refuse(path: str, what: str, location: str, heal: bool):
    """corrupt-vs-missing classification for the federation families,
    heal-mode aware — the store.py `_read_or_none` contract at the
    federation level."""
    from drep_tpu.utils import durableio

    if heal:
        return durableio.load_npz_or_none(
            path, what=what, convert=lambda z: z,
            warn=f"federated index {what}: corrupt %s — healing via recompute",
        )
    try:
        return durableio.load_npz_checked(path, what=what)
    except FileNotFoundError:
        return None
    except durableio.CorruptPayloadError as e:
        raise UserInputError(
            f"federated index {what} {path} is corrupt ({e}). classify/serve "
            f"are read-only; run `drep-tpu index update {location}` (no "
            f"genomes needed) to heal it"
        ) from e


def partition_refusal(pid: int, rng, gen: int, err: BaseException) -> str:
    """THE unreadable-partition message (ISSUE 14 fix): the refusal names
    the partition id and its recorded (range, generation) — not just the
    underlying OSError — and the streaming path's quarantine instant
    carries this exact text, so the union-assembly refusal and the
    containment verdict can never describe the same fault differently."""
    lo, hi = (int(rng[0]), int(rng[1])) if rng is not None else (0, 0)
    return (
        f"federated index: partition {pid} (range [{lo:#x}, {hi:#x}), "
        f"meta-recorded generation {gen}) is unreadable: "
        f"{type(err).__name__}: {err} — scope the damage with "
        f"`python tools/scrub_store.py <root> --partition {pid}` and heal "
        f"with `drep-tpu index update <root>` (no genomes needed)"
    )


def load_federated(location: str, heal: bool = False) -> LoadedIndex:
    """The whole federation at its meta-manifest generation, assembled
    as ONE union ``LoadedIndex`` — what classify/serve consume
    transparently (store.load_index delegates here). Every partition is
    loaded through the ordinary store loader (its own heal matrix
    applies) and TRUNCATED to the genome count the meta records; union
    labels/scores/winners come from the federation state; edges are the
    partitions' intra edges translated to union coordinates plus the
    cross shards.

    Heal matrix at the federation level (update-time; read-only refuses):

    - union state rotted -> mapping recovered from the cross shards'
      redundant copies; the caller re-clusters the whole union
      (``state_missing``), exactly the store's state-rot path.
    - cross shard rotted -> its candidate join + distances recompute
      deterministically for the shard's union range (pair distances are
      pack-independent) and the shard rewrites byte-identically.
    - union state AND a cross shard both rotted -> fatal: the double
      fault the redundancy cannot cover.

    The returned index carries ``fed_part_of`` / ``fed_local_of`` /
    ``fed_meta`` attributes for the federation machinery."""
    logger = get_logger()
    store = FederationStore(location)
    m = store.read_meta()
    params = m["params"]
    gen = int(m["generation"])
    healed: list[str] = []
    if gen < 0:
        if not heal:
            raise UserInputError(
                f"federated index at {location} is an empty skeleton "
                f"(generation -1) — finish the initial `drep-tpu index "
                f"update {location} -g ...` before serving from it"
            )
        idx = empty_index(params, location=store.location)
        idx.fed_part_of = np.empty(0, np.int64)  # type: ignore[attr-defined]
        idx.fed_local_of = np.empty(0, np.int64)  # type: ignore[attr-defined]
        idx.fed_meta = m  # type: ignore[attr-defined]
        return idx

    # 1. partitions, each at the meta's recorded generation ---------------
    loaded: dict[int, LoadedIndex | None] = {}
    for e in m["partitions"]:
        pid = int(e["pid"])
        n_p = int(e["n_genomes"])
        if n_p <= 0:
            loaded[pid] = None
            continue
        # honor the meta's recorded dir: after a split/merge the dense
        # pid renumbering decouples pid from the part_### store name
        pdir = store.abspath(e["dir"])
        try:
            pidx = load_index(pdir, heal=heal)
        except Exception as err:  # noqa: BLE001 — a bare OSError (and even
            # the store's own UserInputError) used to surface naming only
            # the failing path; the federated refusal must name WHICH
            # partition and its recorded (range, generation) — and the
            # streaming path's quarantine instant carries this same text.
            # The machine-readable partition id rides the exception
            # (fed_partition) so the update path's PARTIAL contract
            # (ISSUE 15 satellite) can stamp a degraded meta instead of
            # refusing outright.
            refusal = UserInputError(
                partition_refusal(pid, e.get("range"), int(e["generation"]), err)
            )
            refusal.fed_partition = pid  # type: ignore[attr-defined]
            raise refusal from err
        healed.extend(f"{e['dir']}/{h}" for h in pidx.healed)
        g_meta = int(e["generation"])
        if pidx.generation < g_meta:
            raise UserInputError(
                f"federated index: partition {pid} is at generation "
                f"{pidx.generation} but the meta-manifest recorded "
                f"{g_meta} — the partition store was rolled back or "
                f"restored out of band; restore a matching backup pair"
            )
        if pidx.generation > g_meta + 1:
            raise UserInputError(
                f"federated index: partition {pid} is {pidx.generation - g_meta} "
                f"generations ahead of the meta-manifest — partitions of a "
                f"federation must only be updated THROUGH `index update` on "
                f"the federation root"
            )
        if pidx.generation == g_meta and e.get("manifest_crc") is not None:
            crc = fedmeta.manifest_crc(pdir)
            if crc is not None and int(crc) != int(e["manifest_crc"]):
                raise UserInputError(
                    f"federated index: partition {pid}'s manifest checksum "
                    f"does not match what the meta-manifest was published "
                    f"against — the partition was swapped out from under "
                    f"the federation"
                )
        if pidx.n < n_p:
            raise UserInputError(
                f"federated index: partition {pid} holds {pidx.n} genomes "
                f"but the meta-manifest records {n_p}"
            )
        loaded[pid] = _truncate_partition(pidx, n_p)

    # 2. union state (mapping + labels) -----------------------------------
    n = int(m["n_genomes"])
    state = None
    if m.get("state"):
        state = _read_npz_or_refuse(
            store.abspath(m["state"]), "union state", location, heal
        )
        if state is None and not heal:
            raise UserInputError(
                f"federated index union state {store.abspath(m['state'])} is "
                f"missing; run `drep-tpu index update {location}` to heal"
            )

    cross_entries = list(m.get("cross_shards", ()))
    cross_payloads = [
        _read_npz_or_refuse(store.abspath(e["file"]), "cross shard", location, heal)
        for e in cross_entries
    ]
    for e, z in zip(cross_entries, cross_payloads):
        if z is None and not heal:
            raise UserInputError(
                f"federated index cross shard {store.abspath(e['file'])} is "
                f"missing; classify/serve are read-only — run `drep-tpu "
                f"index update {location}` to heal the store first"
            )

    if state is not None:
        part_of = state["part_of"].astype(np.int64)
        local_of = state["local_of"].astype(np.int64)
    else:
        # heal: the mapping's redundant copy lives range-sliced in the
        # cross shards — all of them must be readable, or it is the
        # double fault the redundancy cannot cover
        parts_map: list[np.ndarray] = []
        locals_map: list[np.ndarray] = []
        for e, z in zip(cross_entries, cross_payloads):
            if z is None:
                raise UserInputError(
                    f"federated index at {location}: the union state AND "
                    f"cross shard {e['file']} are both unreadable — the "
                    f"double fault the federation's redundancy cannot "
                    f"cover. Rebuild the federation."
                )
            parts_map.append(z["map_pid"].astype(np.int64))
            locals_map.append(z["map_local"].astype(np.int64))
        part_of = np.concatenate(parts_map) if parts_map else np.empty(0, np.int64)
        local_of = (
            np.concatenate(locals_map) if locals_map else np.empty(0, np.int64)
        )
    if len(part_of) != n:
        raise UserInputError(
            f"federated index at {location}: union mapping covers "
            f"{len(part_of)} genomes but the meta-manifest records {n}"
        )

    # 3. union assembly ----------------------------------------------------
    names: list = [None] * n
    locations_l: list = [None] * n
    bottom: list = [None] * n
    scaled: list = [None] * n
    admitted = np.zeros(n, np.int64)
    stats = {c: np.zeros(n, np.int64) for c in _STAT_COLS}
    l2u: dict[int, np.ndarray] = {}
    for pid, pidx in loaded.items():
        if pidx is None:
            continue
        sel = np.nonzero(part_of == pid)[0]
        locs = local_of[sel]
        arr = np.full(pidx.n, -1, np.int64)
        arr[locs] = sel
        l2u[pid] = arr
        for c in _STAT_COLS:
            stats[c][sel] = pidx.gdb[c].to_numpy()[locs]
        for u, loc in zip(sel, locs):
            names[u] = pidx.names[loc]
            locations_l[u] = pidx.locations[loc]
            bottom[u] = pidx.bottom[loc]
            scaled[u] = pidx.scaled[loc]
    missing = [g for g in range(n) if names[g] is None]
    if missing:
        raise UserInputError(
            f"federated index at {location}: union slot(s) {missing[:5]} "
            f"resolve to no partition genome — meta/mapping mismatch"
        )

    parts_ii: list[np.ndarray] = []
    parts_jj: list[np.ndarray] = []
    parts_dd: list[np.ndarray] = []
    for pid in sorted(loaded):
        pidx = loaded[pid]
        if pidx is None or not len(pidx.edges[0]):
            continue
        ii, jj, dd = pidx.edges
        parts_ii.append(l2u[pid][ii])
        parts_jj.append(l2u[pid][jj])
        parts_dd.append(dd)

    idx = LoadedIndex(
        location=store.location, params=params, generation=gen,
        names=[str(x) for x in names],
        locations=[str(x) for x in locations_l],
        gdb=pd.DataFrame({"genome": [str(x) for x in names], **stats}),
        admitted=admitted, bottom=bottom, scaled=scaled,
        edges=_EMPTY_EDGES(),
        primary=np.zeros(n, np.int64), suffix=np.zeros(n, np.int64),
        score=np.zeros(n, np.float64),
        winners=pd.DataFrame({"cluster": [], "genome": [], "score": []}),
        healed=healed,
    )
    idx.fed_part_of = part_of  # type: ignore[attr-defined]
    idx.fed_local_of = local_of  # type: ignore[attr-defined]
    idx.fed_meta = m  # type: ignore[attr-defined]

    # 4. cross shards (healing rotted ones now that bottoms are resident) -
    for e, z in zip(cross_entries, cross_payloads):
        lo, hi = int(e["lo"]), int(e["hi"])
        if z is None:
            logger.warning(
                "federated index: recomputing cross range [%d, %d) to heal %s",
                lo, hi, e["file"],
            )
            ci, cj = cross_candidates(bottom, part_of, min_col=lo)
            keep_range = cj < hi
            ui, uj, dd, _pairs = cross_edges(
                idx, part_of, ci[keep_range], cj[keep_range], min_col=lo
            )
            store.write_cross_shard(
                e["file"], ui, uj, dd, part_of[lo:hi], local_of[lo:hi]
            )
            healed.append(e["file"])
        else:
            ui = z["ii"].astype(np.int64)
            uj = z["jj"].astype(np.int64)
            dd = z["dist"].astype(np.float32)
        parts_ii.append(ui)
        parts_jj.append(uj)
        parts_dd.append(dd)

    # canonical union edge order: ONE global lexsort, identical however
    # the shards were produced (the federation's own convention)
    if parts_ii:
        ii = np.concatenate(parts_ii)
        jj = np.concatenate(parts_jj)
        dd = np.concatenate(parts_dd)
        order = np.lexsort((jj, ii))
        idx.edges = (ii[order], jj[order], dd[order])

    # 5. union derived state ----------------------------------------------
    if state is not None:
        idx.admitted = state["admitted_generation"].astype(np.int64)
        idx.primary = state["primary"].astype(np.int64)
        idx.suffix = state["suffix"].astype(np.int64)
        idx.score = state["score"].astype(np.float64)
        idx.winners = pd.DataFrame(
            {
                "cluster": [str(x) for x in state["winner_cluster"]],
                "genome": [str(x) for x in state["winner_genome"]],
                "score": state["winner_score"].astype(np.float64),
            }
        )
    else:
        # admission generations recoverable per cross-shard range
        for e in cross_entries:
            idx.admitted[int(e["lo"]): int(e["hi"])] = int(e["generation"])
        idx.state_missing = True  # caller (fed_update) re-clusters the union
    return idx


# ---------------------------------------------------------------------------
# streaming per-partition serving (ISSUE 14)
# ---------------------------------------------------------------------------
#
# ``load_federated`` assembles the whole union in one process's memory —
# the right shape for update machinery (which mutates the union anyway)
# and for the oracle, but the WRONG shape for a serve replica: it pays
# O(total sketch bytes) residency, and one damaged partition fails the
# entire load. ``FederatedResident`` is the serving view: it loads only
# the cheap SPINE (meta + union state + cross shards + per-partition
# names/stats/intra-edges — O(N) metadata, no sketch payloads), routes
# each query to the partitions whose genomes can share a band code with
# it (rangepart coarse-code summaries, recall 1.0 by the same monotone
# many-to-one derivation as the boundary join), lazily loads ONLY the
# consulted partitions' sketch payloads (LRU residency under a byte
# budget), runs an ordinary per-partition rect compare against each,
# and merges per-partition edges into per-query verdicts through the
# exact recluster machinery one-shot classify runs — so streaming
# verdicts are IDENTICAL to union-assembled classify (oracle-pinned).
#
# Fault containment is partition-scoped: a partition that fails to
# load, fails mid-compare, or is truncated/swapped under a stale meta
# moves through a health state machine (healthy -> suspect ->
# quarantined, bounded-backoff reload probes) and the affected queries
# return honest PARTIAL verdicts stamped with ``partitions_consulted``
# / ``partitions_unavailable`` — never an exception out of the daemon.

PARTITION_HEALTHY = "healthy"
PARTITION_SUSPECT = "suspect"
PARTITION_QUARANTINED = "quarantined"


def partition_heal_hint(pid: int) -> str:
    """The quarantine instant's scrub-informed heal hint: the cheap
    partition-scoped probe an operator (or orchestrator) shells to."""
    return (
        f"python tools/scrub_store.py <root> --partition {pid} "
        f"(then `drep-tpu index update <root>` to heal)"
    )


@dataclass
class _PartitionSlot:
    """One partition's health + residency bookkeeping in a serve replica."""

    pid: int
    dir: str
    range: tuple[int, int]
    meta_generation: int
    n: int  # genome count AT the federation generation (meta-recorded)
    state: str = PARTITION_HEALTHY
    reason: str | None = None  # quarantine/suspect cause (partition_refusal text)
    failures: int = 0  # consecutive
    backoff_s: float = 0.0
    next_probe_mono: float = 0.0
    last_probe_mono: float | None = None
    # spine (loaded once, cheap): union slots in partition-local order
    u_of_local: np.ndarray | None = None
    intra: tuple | None = None  # union-coord intra edges (ii, jj, dd)
    # resident sketch payload (the heavy, lazily-loaded part)
    resident: bool = False
    resident_bytes: int = 0
    last_used: int = 0
    loads: int = 0


class FederatedResident:
    """The streaming serving view of a federated index (ISSUE 14).

    Quacks like the resident ``LoadedIndex`` where the serve tier needs
    it (``.params`` / ``.generation`` / ``.n`` / ``.location``), but
    holds sketch payloads per-partition under an LRU byte budget and
    contains partition failure at the partition boundary. Construction
    refuses (read-only, like ``load_resident_index``) only on faults
    that leave NOTHING answerable — a corrupt meta-manifest or union
    state; any per-partition damage quarantines that partition instead.

    State machine per partition: ``healthy`` -> (one load/compare
    failure) ``suspect`` (retried immediately on next consult) -> (a
    second consecutive failure, or any spine-level failure at startup)
    ``quarantined`` (consulted again only by bounded-backoff reload
    probes; a successful probe emits ``partition_recovered`` and goes
    straight back to ``healthy``). Every failure's recorded reason is
    the same :func:`partition_refusal` text the union-assembly path
    raises — one message per fault, wherever it surfaces.
    """

    def __init__(
        self,
        location: str,
        resident_mb: int | None = None,
        probe_backoff_s: float | None = None,
        probe_max_s: float | None = None,
    ):
        from drep_tpu.utils import envknobs

        logger = get_logger()
        self.store = FederationStore(location)
        self.location = self.store.location
        m = self.store.read_meta()
        if int(m["generation"]) < 0:
            raise UserInputError(
                f"federated index at {location} is an empty skeleton "
                f"(generation -1) — finish the initial `drep-tpu index "
                f"update {location} -g ...` before serving from it"
            )
        self.fed_meta = m
        self.params = m["params"]
        self.generation = int(m["generation"])
        if resident_mb is None:
            resident_mb = envknobs.env_int("DREP_TPU_SERVE_RESIDENT_MB")
        self.budget_bytes = int(resident_mb) << 20 if resident_mb else 0
        self.probe_backoff_s = (
            envknobs.env_float("DREP_TPU_SERVE_PROBE_BACKOFF_S")
            if probe_backoff_s is None else float(probe_backoff_s)
        )
        self.probe_max_s = (
            envknobs.env_float("DREP_TPU_SERVE_PROBE_MAX_S")
            if probe_max_s is None else float(probe_max_s)
        )
        self.stats = {
            "loads": 0, "evictions": 0, "recoveries": 0,
            "peak_resident_partitions": 0,
        }
        self._tick = 0
        self._resident_total = 0
        self._edge_cache: dict[frozenset, tuple] = {}

        # -- union state: the spine nothing can be answered without ---------
        n = int(m["n_genomes"])
        state = _read_npz_or_refuse(
            self.store.abspath(m["state"]), "union state", location, heal=False
        ) if m.get("state") else None
        if state is None:
            raise UserInputError(
                f"federated index union state under {location} is missing or "
                f"was never published; serve is read-only — run `drep-tpu "
                f"index update {location}` to heal the store first"
            )
        self.part_of = state["part_of"].astype(np.int64)
        self.local_of = state["local_of"].astype(np.int64)
        if len(self.part_of) != n:
            raise UserInputError(
                f"federated index at {location}: union mapping covers "
                f"{len(self.part_of)} genomes but the meta-manifest records {n}"
            )

        # -- cross shards (federation-level, required like the state) -------
        cross_ii: list[np.ndarray] = []
        cross_jj: list[np.ndarray] = []
        cross_dd: list[np.ndarray] = []
        for e in m.get("cross_shards", ()):
            z = _read_npz_or_refuse(
                self.store.abspath(e["file"]), "cross shard", location, heal=False
            )
            if z is None:
                raise UserInputError(
                    f"federated index cross shard {self.store.abspath(e['file'])} "
                    f"is missing; serve is read-only — run `drep-tpu index "
                    f"update {location}` to heal the store first"
                )
            cross_ii.append(z["ii"].astype(np.int64))
            cross_jj.append(z["jj"].astype(np.int64))
            cross_dd.append(z["dist"].astype(np.float32))
        self._cross = (
            np.concatenate(cross_ii) if cross_ii else np.empty(0, np.int64),
            np.concatenate(cross_jj) if cross_jj else np.empty(0, np.int64),
            np.concatenate(cross_dd) if cross_dd else np.empty(0, np.float32),
        )
        self._cross_pi = self.part_of[self._cross[0]] if len(self._cross[0]) else (
            np.empty(0, np.int64)
        )
        self._cross_pj = self.part_of[self._cross[1]] if len(self._cross[1]) else (
            np.empty(0, np.int64)
        )

        # -- routing summaries (optional: absent/corrupt -> consult-all) ----
        self._route_bitmaps = self._route_bits = None
        if m.get("routing"):
            try:
                from drep_tpu.utils import durableio

                z = durableio.load_npz_checked(
                    self.store.abspath(m["routing"]), what="routing summary"
                )
                self._route_bitmaps = z["bitmaps"].astype(np.uint64)
                self._route_bits = int(z["bits"])
            except Exception as err:  # noqa: BLE001 — routing is an
                # optimization: losing it degrades to consult-all, honestly
                logger.warning(
                    "federated serve: routing summary unreadable (%s) — "
                    "every query consults every partition until the next "
                    "`index update` rewrites it", err,
                )

        # -- per-partition spine (contained: failure -> quarantine) ---------
        self._stats_arrays = {c: np.zeros(n, np.int64) for c in _STAT_COLS}
        names: list[str] = [f"?part?:{int(p)}:{int(l)}" for p, l in zip(
            self.part_of, self.local_of
        )]
        locations: list[str] = [""] * n
        self._slots: dict[int, _PartitionSlot] = {}
        for e in m["partitions"]:
            pid = int(e["pid"])
            slot = _PartitionSlot(
                pid=pid, dir=e["dir"],
                range=(int(e["range"][0]), int(e["range"][1])),
                meta_generation=int(e["generation"]),
                n=int(e["n_genomes"]),
            )
            self._slots[pid] = slot
            if slot.n <= 0:
                continue
            try:
                self._load_spine(slot, names, locations)
            except Exception as err:  # noqa: BLE001 — THE containment
                # boundary: one damaged partition must not take the
                # replica down with it
                self._book_failure(slot, err, during="spine")

        admitted = np.zeros(n, np.int64)
        for e in m.get("cross_shards", ()):
            admitted[int(e["lo"]): int(e["hi"])] = int(e["generation"])
        self.union = LoadedIndex(
            location=self.location, params=self.params, generation=self.generation,
            names=names, locations=locations,
            gdb=pd.DataFrame({"genome": list(names), **self._stats_arrays}),
            admitted=admitted,
            bottom=[None] * n, scaled=[None] * n,
            edges=_EMPTY_EDGES(),
            primary=state["primary"].astype(np.int64),
            suffix=state["suffix"].astype(np.int64),
            score=state["score"].astype(np.float64),
            winners=pd.DataFrame(
                {
                    "cluster": [str(x) for x in state["winner_cluster"]],
                    "genome": [str(x) for x in state["winner_genome"]],
                    "score": state["winner_score"].astype(np.float64),
                }
            ),
        )
        quarantined = sorted(
            p for p, s in self._slots.items() if s.state == PARTITION_QUARANTINED
        )
        logger.info(
            "federated serve: generation %d spine resident (%d genomes over "
            "%d partitions, 0 sketch payloads loaded%s)",
            self.generation, n, len(self._slots),
            f"; QUARANTINED at startup: {quarantined}" if quarantined else "",
        )

    # ---- LoadedIndex-compatible surface ---------------------------------
    @property
    def n(self) -> int:
        return len(self.union.names)

    @property
    def names(self) -> list[str]:
        return self.union.names

    # ---- spine / residency loads ----------------------------------------
    def _partition_manifest(self, slot: _PartitionSlot) -> dict:
        """The partition's CURRENT manifest, re-read on every residency
        load (not cached) with the same identity checks the union
        assembly applies — a rollback, an out-of-band swap, or rot lands
        here, at consult time, as a containable failure."""
        pdir = os.path.join(self.location, slot.dir)
        manifest = IndexStore(pdir).read_manifest()
        g_meta = slot.meta_generation
        actual = int(manifest["generation"])
        if actual < g_meta:
            raise UserInputError(
                f"partition store is at generation {actual} but the "
                f"meta-manifest recorded {g_meta} — rolled back or restored "
                f"out of band"
            )
        if actual > g_meta + 1:
            raise UserInputError(
                f"partition store is {actual - g_meta} generations ahead of "
                f"the meta-manifest — updated outside `index update` on the "
                f"federation root"
            )
        e = next(
            e for e in self.fed_meta["partitions"] if int(e["pid"]) == slot.pid
        )
        if actual == g_meta and e.get("manifest_crc") is not None:
            crc = fedmeta.manifest_crc(pdir)
            if crc is not None and int(crc) != int(e["manifest_crc"]):
                raise UserInputError(
                    "partition manifest checksum does not match what the "
                    "meta-manifest was published against — swapped out from "
                    "under the federation"
                )
        if int(manifest["n_genomes"]) < slot.n:
            raise UserInputError(
                f"partition holds {manifest['n_genomes']} genomes but the "
                f"meta-manifest records {slot.n} — truncated by a stale meta"
            )
        return manifest

    def _load_spine(self, slot: _PartitionSlot, names: list, locations: list) -> None:
        """Names/locations/stats + intra edges for one partition —
        O(n_p) metadata, NO sketch payloads (those load lazily on first
        consult)."""

        pdir = os.path.join(self.location, slot.dir)
        manifest = self._partition_manifest(slot)
        state = read_payload(
            os.path.join(pdir, manifest["state"]), "partition state"
        )
        sel = np.nonzero(self.part_of == slot.pid)[0]
        locs = self.local_of[sel]
        u_of_local = np.full(slot.n, -1, np.int64)
        u_of_local[locs] = sel
        if (u_of_local < 0).any():
            raise UserInputError(
                "union mapping does not cover every partition-local genome"
            )
        p_names = [str(x) for x in state["names"][: slot.n]]
        p_locs = [str(x) for x in state["locations"][: slot.n]]
        for loc in range(slot.n):
            names[int(u_of_local[loc])] = p_names[loc]
            locations[int(u_of_local[loc])] = p_locs[loc]
        for c in _STAT_COLS:
            self._stats_arrays[c][sel] = state[c].astype(np.int64)[locs]
        ii_l: list[np.ndarray] = []
        jj_l: list[np.ndarray] = []
        dd_l: list[np.ndarray] = []
        for e in manifest["edge_shards"]:
            if int(e["lo"]) >= slot.n:
                continue  # published ahead of the meta: truncated out
            z = read_payload(
                os.path.join(pdir, e["file"]), "partition edge shard"
            )
            ii, jj, dd = (
                z["ii"].astype(np.int64), z["jj"].astype(np.int64),
                z["dist"].astype(np.float32),
            )
            keep = jj < slot.n  # ii < jj: both endpoints inside the prefix
            ii_l.append(u_of_local[ii[keep]])
            jj_l.append(u_of_local[jj[keep]])
            dd_l.append(dd[keep])
        slot.u_of_local = u_of_local
        slot.intra = (
            np.concatenate(ii_l) if ii_l else np.empty(0, np.int64),
            np.concatenate(jj_l) if jj_l else np.empty(0, np.int64),
            np.concatenate(dd_l) if dd_l else np.empty(0, np.float32),
        )
        self._edge_cache.clear()

    def _load_sketches(self, slot: _PartitionSlot) -> None:
        from drep_tpu.ingest import unpack_ragged

        pdir = os.path.join(self.location, slot.dir)
        manifest = self._partition_manifest(slot)
        # STAGE everything before installing anything: a mid-way shard
        # failure (second shard corrupt) must leave union.bottom exactly
        # as it was — a partial install would hold bytes outside the
        # residency accounting forever (the budget contract would leak)
        staged: list[tuple[int, np.ndarray, np.ndarray]] = []
        nbytes = 0
        for e in manifest["sketch_shards"]:
            lo = int(e["lo"])
            if lo >= slot.n:
                continue
            hi = min(int(e["hi"]), slot.n)
            z = read_payload(
                os.path.join(pdir, e["file"]), "partition sketch shard"
            )
            m = int(e["hi"]) - lo
            bot = unpack_ragged(z["bottom"], z["bottom_offsets"], m)
            sca = unpack_ragged(z["scaled"], z["scaled_offsets"], m)
            for loc in range(lo, hi):
                staged.append(
                    (int(slot.u_of_local[loc]), bot[loc - lo], sca[loc - lo])
                )
                nbytes += bot[loc - lo].nbytes + sca[loc - lo].nbytes
        for u, b, s in staged:
            self.union.bottom[u] = b
            self.union.scaled[u] = s
        slot.resident_bytes = nbytes

    # ---- health state machine -------------------------------------------
    def _book_failure(self, slot: _PartitionSlot, err: BaseException, during: str) -> None:
        from drep_tpu.utils import telemetry
        from drep_tpu.utils.profiling import counters

        msg = partition_refusal(slot.pid, slot.range, slot.meta_generation, err)
        now = time.monotonic()
        slot.failures += 1
        slot.reason = msg
        slot.last_probe_mono = now
        self._drop_residency(slot)
        was = slot.state
        # spine-level damage at startup/probe goes straight to quarantine
        # (a corrupt manifest will not heal by immediate retry); load or
        # mid-compare failures get one suspect retry first
        if during == "spine" or was in (PARTITION_SUSPECT, PARTITION_QUARANTINED):
            slot.state = PARTITION_QUARANTINED
            slot.backoff_s = min(
                self.probe_max_s,
                max(self.probe_backoff_s, slot.backoff_s * 2.0),
            )
            slot.next_probe_mono = now + slot.backoff_s
            if was != PARTITION_QUARANTINED:
                counters.add_fault("partition_quarantined")
            telemetry.event(
                "partition_quarantine", pid=slot.pid, during=during,
                reason=msg, heal_hint=partition_heal_hint(slot.pid),
                backoff_s=round(slot.backoff_s, 3),
            )
        else:
            slot.state = PARTITION_SUSPECT
        get_logger().warning(
            "federated serve: partition %d %s after a %s failure: %s",
            slot.pid, slot.state, during, msg,
        )

    def _mark_recovered(self, slot: _PartitionSlot) -> None:
        from drep_tpu.utils import telemetry

        slot.state = PARTITION_HEALTHY
        slot.failures = 0
        slot.backoff_s = 0.0
        slot.reason = None
        self.stats["recoveries"] += 1
        telemetry.event("partition_recovered", pid=slot.pid, loads=slot.loads)
        get_logger().info(
            "federated serve: partition %d recovered (probe load succeeded) "
            "— full coverage restored for its range", slot.pid,
        )

    def _drop_residency(self, slot: _PartitionSlot) -> None:
        if not slot.resident:
            return
        for u in slot.u_of_local if slot.u_of_local is not None else ():
            self.union.bottom[int(u)] = None
            self.union.scaled[int(u)] = None
        self._resident_total -= slot.resident_bytes
        slot.resident = False
        slot.resident_bytes = 0

    def _evict(self, slot: _PartitionSlot) -> None:
        from drep_tpu.utils import telemetry

        nbytes = slot.resident_bytes
        self._drop_residency(slot)
        self.stats["evictions"] += 1
        telemetry.event("partition_evict", pid=slot.pid, bytes=nbytes)

    def _evict_to_budget(self, pin: set[int]) -> None:
        from drep_tpu.utils.profiling import counters

        resident = [s for s in self._slots.values() if s.resident]
        self.stats["peak_resident_partitions"] = max(
            self.stats["peak_resident_partitions"], len(resident)
        )
        if self.budget_bytes:
            evictable = sorted(
                (s for s in resident if s.pid not in pin),
                key=lambda s: s.last_used,
            )
            while self._resident_total > self.budget_bytes and evictable:
                self._evict(evictable.pop(0))
        counters.set_gauge(
            "serve_partitions_resident",
            float(sum(1 for s in self._slots.values() if s.resident)),
        )
        counters.set_gauge("serve_resident_bytes", float(self._resident_total))

    def ensure_resident(self, pid: int, pin: frozenset | set = frozenset()) -> bool:
        """Make partition `pid`'s sketch payload resident (lazily loading
        it on first consult, re-probing a quarantined partition once its
        backoff elapsed). Returns False — the caller's PARTIAL verdict —
        when the partition is (or just became) unavailable."""
        from drep_tpu.utils import faults, telemetry
        from drep_tpu.utils.profiling import counters

        slot = self._slots[pid]
        if slot.n <= 0:
            return True
        if slot.resident:
            self._tick += 1
            slot.last_used = self._tick
            return True
        now = time.monotonic()
        if slot.state == PARTITION_QUARANTINED and now < slot.next_probe_mono:
            return False
        probing = slot.state != PARTITION_HEALTHY
        try:
            with counters.span("partition_load", pid=pid, probe=probing):
                faults.fire("partition_load")
                if slot.u_of_local is None:
                    self._load_spine(slot, self.union.names, self.union.locations)
                    self.union.gdb = pd.DataFrame(
                        {"genome": list(self.union.names), **self._stats_arrays}
                    )
                self._load_sketches(slot)
        except Exception as err:  # noqa: BLE001 — containment: book and degrade
            self._book_failure(slot, err, during="load")
            return False
        slot.resident = True
        slot.loads += 1
        self._tick += 1
        slot.last_used = self._tick
        slot.last_probe_mono = now
        self._resident_total += slot.resident_bytes
        self.stats["loads"] += 1
        if probing:
            self._mark_recovered(slot)
        self._evict_to_budget(set(pin) | {pid})
        return True

    # ---- routing + per-partition compare --------------------------------
    def route_candidates(self, q_bottoms: list[np.ndarray]) -> list[set[int]]:
        """Per-query candidate partitions: the partitions whose genomes
        can share a band code with the query (coarse-summary intersect —
        recall 1.0, see rangepart.ROUTE_SUMMARY_BITS). Without a usable
        routing summary every non-empty partition is a candidate."""
        from drep_tpu.ops import rangepart

        active = [pid for pid, s in self._slots.items() if s.n > 0]
        if self._route_bitmaps is None:
            return [set(active) for _ in q_bottoms]
        out: list[set[int]] = []
        for b in q_bottoms:
            codes = rangepart.coarse_codes(b, self._route_bits)
            out.append(
                {
                    pid for pid in active
                    if pid < len(self._route_bitmaps)
                    and rangepart.bitmap_contains_any(
                        self._route_bitmaps[pid], codes
                    )
                }
            )
        return out

    def classify_partition(
        self, pid: int, q_names: list[str], q_bottoms: list[np.ndarray],
        prune_cfg: dict | None,
    ):
        """One routed batch vs one resident partition: an ordinary rect
        compare over [partition | queries] with ``min_col = n_p`` —
        distances are pack-independent, so the retained (indexed, query)
        edges are bit-identical to the union compare's slice for this
        partition. Returns (union_i, query_idx, dist) or None after
        booking a mid-compare failure (suspect/quarantine)."""
        from drep_tpu.utils import faults
        from drep_tpu.utils.profiling import counters

        slot = self._slots[pid]
        try:
            with counters.span("partition_classify", pid=pid, k=len(q_names)):
                faults.fire("partition_classify")
                return self._rect_compare(slot, q_names, q_bottoms, prune_cfg)
        except Exception as err:  # noqa: BLE001 — mid-classify containment
            self._book_failure(slot, err, during="classify")
            return None

    def _rect_compare(
        self, slot: _PartitionSlot, q_names: list[str],
        q_bottoms: list[np.ndarray], prune_cfg: dict | None,
    ):
        from drep_tpu.ops.minhash import pack_sketches
        from drep_tpu.parallel.streaming import streaming_mash_edges

        p = self.params
        _, keep = _retention(p)
        n_p = slot.n
        part_names = [self.union.names[int(u)] for u in slot.u_of_local]
        part_bottoms = [self.union.bottom[int(u)] for u in slot.u_of_local]
        packed = pack_sketches(
            part_bottoms + list(q_bottoms), part_names + list(q_names),
            int(p["sketch_size"]),
        )
        prune = None
        if prune_cfg and prune_cfg.get("primary_prune", "off") == "lsh":
            from drep_tpu.ops.lsh import build_candidates

            prune = build_candidates(
                packed, keep=keep, k=int(p["kmer_size"]),
                bands=int(prune_cfg.get("prune_bands", 0)),
                min_shared=int(prune_cfg.get("prune_min_shared", 0)),
                min_col=n_p,
                join_chunk=int(prune_cfg.get("prune_join_chunk", 0)),
            )
        ii, jj, dd, _pairs = streaming_mash_edges(
            packed, int(p["kmer_size"]), keep,
            block=int(p["streaming_block"]), min_col=n_p, prune=prune,
        )
        sel = (jj >= n_p) & (ii < n_p)  # (indexed, query) pairs only
        return slot.u_of_local[ii[sel]], jj[sel] - n_p, dd[sel]

    # ---- union edge view -------------------------------------------------
    def _spineless(self) -> set[int]:
        return {
            pid for pid, s in self._slots.items()
            if s.n > 0 and s.u_of_local is None
        }

    def edges_excluding(self, excluded: set[int]):
        """The union retained-edge graph with every edge incident to an
        excluded (or spine-less) partition's genomes removed, in the
        canonical global (ii, jj) lexsort order — the degraded graph a
        PARTIAL verdict reclusters over (full graph when nothing is
        excluded)."""
        eff = frozenset(set(excluded) | self._spineless())
        hit = self._edge_cache.get(eff)
        if hit is not None:
            return hit
        parts_ii: list[np.ndarray] = []
        parts_jj: list[np.ndarray] = []
        parts_dd: list[np.ndarray] = []
        for pid in sorted(self._slots):
            slot = self._slots[pid]
            if pid in eff or slot.intra is None or not len(slot.intra[0]):
                continue
            parts_ii.append(slot.intra[0])
            parts_jj.append(slot.intra[1])
            parts_dd.append(slot.intra[2])
        ci, cj, cd = self._cross
        if len(ci):
            if eff:
                bad = np.asarray(sorted(eff), np.int64)
                mask = ~np.isin(self._cross_pi, bad) & ~np.isin(self._cross_pj, bad)
                ci, cj, cd = ci[mask], cj[mask], cd[mask]
            parts_ii.append(ci)
            parts_jj.append(cj)
            parts_dd.append(cd)
        if parts_ii:
            ii = np.concatenate(parts_ii)
            jj = np.concatenate(parts_jj)
            dd = np.concatenate(parts_dd)
            order = np.lexsort((jj, ii))
            out = (ii[order], jj[order], dd[order])
        else:
            out = _EMPTY_EDGES()
        self._edge_cache[eff] = out
        return out

    def scratch_excluding(self, excluded: set[int]) -> LoadedIndex:
        """A classify-scratch union copy (fresh containers, shared
        immutable payloads — the _scratch_index contract); the caller
        installs its own per-query edge view.

        Excluded partitions' genomes keep their OLD primary labels —
        the clean-cluster structure (and with it the from-scratch
        renumbering) is untouched, which is what keeps unaffected
        partitions' verdicts byte-identical to the oracle under a
        quarantine — but are marked FROZEN (``frozen_rows``):
        ``recluster`` carries their old suffix/score verbatim and never
        routes them into a secondary recompute, because their sketch
        payloads are exactly what is unavailable. A split cluster's
        AVAILABLE remainder still re-clusters (the honest degraded
        answer a PARTIAL verdict reports), which is why the component
        closure makes remainders resident too."""
        u = self.union
        sq = LoadedIndex(
            location=u.location, params=u.params, generation=u.generation,
            names=list(u.names), locations=list(u.locations),
            gdb=u.gdb, admitted=u.admitted,
            bottom=list(u.bottom), scaled=list(u.scaled),
            edges=u.edges, primary=u.primary, suffix=u.suffix,
            score=u.score, winners=u.winners,
        )
        eff = set(excluded) | self._spineless()
        if eff:
            bad = np.isin(self.part_of, np.asarray(sorted(eff), np.int64))
            sq.frozen_rows = np.nonzero(bad)[0]  # type: ignore[attr-defined]
        return sq

    # ---- health surface ---------------------------------------------------
    def retry_hint_s(self) -> float:
        """The strict-mode refusal's retry_after hint: the soonest any
        quarantined partition will be probed again."""
        now = time.monotonic()
        waits = [
            max(0.0, s.next_probe_mono - now)
            for s in self._slots.values()
            if s.state == PARTITION_QUARANTINED
        ]
        return round(max(0.05, min(waits) if waits else self.probe_backoff_s), 4)

    def health_map(self) -> dict:
        """The partition health map `/healthz` and `pod_status --serve`
        render: per-partition state / residency / probe schedule, plus
        the replica-level residency accounting."""
        now = time.monotonic()
        parts: dict[str, dict] = {}
        for pid in sorted(self._slots):
            s = self._slots[pid]
            entry: dict = {
                "state": s.state if s.n > 0 else "empty",
                "resident": bool(s.resident),
                "resident_bytes": int(s.resident_bytes),
                "n_genomes": int(s.n),
                "generation": int(s.meta_generation),
                "loads": int(s.loads),
                "last_probe_ago_s": (
                    round(now - s.last_probe_mono, 3)
                    if s.last_probe_mono is not None else None
                ),
            }
            if s.state == PARTITION_QUARANTINED:
                entry["next_probe_in_s"] = round(
                    max(0.0, s.next_probe_mono - now), 3
                )
                entry["heal_hint"] = partition_heal_hint(pid)
            if s.reason:
                entry["reason"] = s.reason
            parts[str(pid)] = entry
        return {
            "generation": self.generation,
            "n_partitions": len(self._slots),
            "resident_partitions": sum(
                1 for s in self._slots.values() if s.resident
            ),
            "resident_bytes": int(self._resident_total),
            "budget_bytes": int(self.budget_bytes),
            "peak_resident_partitions": self.stats["peak_resident_partitions"],
            "loads": self.stats["loads"],
            "evictions": self.stats["evictions"],
            "recoveries": self.stats["recoveries"],
            "quarantined": sorted(
                p for p, s in self._slots.items()
                if s.state == PARTITION_QUARANTINED
            ),
            "suspect": sorted(
                p for p, s in self._slots.items()
                if s.state == PARTITION_SUSPECT
            ),
            "partitions": parts,
        }


# ---------------------------------------------------------------------------
# streaming classify over a FederatedResident
# ---------------------------------------------------------------------------


def _query_query_edges(fed: FederatedResident, q_names: list[str], q_bottoms: list):
    """Retained query-query edges for the JOINT mode, from a K-only pack
    (pair distances are pack-independent: identical to the union rect
    compare's query-query slice). Returns pack-local (ti, tj, dd)."""
    from drep_tpu.ops.minhash import pack_sketches
    from drep_tpu.parallel.streaming import streaming_mash_edges

    if len(q_names) < 2:
        return (np.empty(0, np.int64), np.empty(0, np.int64),
                np.empty(0, np.float32))
    p = fed.params
    _, keep = _retention(p)
    packed = pack_sketches(list(q_bottoms), list(q_names), int(p["sketch_size"]))
    ii, jj, dd, _ = streaming_mash_edges(
        packed, int(p["kmer_size"]), keep, block=int(p["streaming_block"])
    )
    return ii, jj, dd


def _component_closure(
    fed: FederatedResident,
    q_edges: list[tuple[np.ndarray, np.ndarray]],  # per query: (union_i, dd)
    unavailable: set[int],
):
    """Grow the consulted set until every member of every query's dirty
    component is sketch-resident (the per-query recluster's secondary
    stage needs co-member sketches), excluding — and stamping — the
    partitions that cannot be loaded. Returns (base edge view, per-query
    filtered direct edges, consulted-by-closure, unavailable)."""
    from scipy.sparse import coo_matrix
    from scipy.sparse.csgraph import connected_components as _cc

    n_old = fed.n
    k = len(q_edges)
    excluded = set(unavailable)
    closure_consulted: set[int] = set()
    for _ in range(len(fed._slots) + 1):
        base = fed.edges_excluding(excluded)
        eff = excluded | fed._spineless()
        filt: list[tuple[np.ndarray, np.ndarray]] = []
        for ui, dd in q_edges:
            if len(ui) and eff:
                bad = np.asarray(sorted(eff), np.int64)
                m = ~np.isin(fed.part_of[ui], bad)
                ui, dd = ui[m], dd[m]
            filt.append((ui, dd))
        n_tot = n_old + k
        ii = np.concatenate([base[0]] + [f[0] for f in filt])
        jj = np.concatenate(
            [base[1]]
            + [np.full(len(f[0]), n_old + t, np.int64) for t, f in enumerate(filt)]
        )
        graph = coo_matrix(
            (np.ones(len(ii), np.int8), (ii, jj)), shape=(n_tot, n_tot)
        )
        _, comp = _cc(graph, directed=False)
        q_comps = {comp[n_old + t] for t in range(k)}
        members = np.nonzero(np.isin(comp[:n_old], sorted(q_comps)))[0]
        need = {int(p) for p in np.unique(fed.part_of[members])} if len(members) else set()
        # a cluster SPLIT by the exclusion re-clusters its available
        # remainder (the degraded answer) — multi-member remainders run
        # the secondary stage, so their sketches must be resident too
        if eff:
            bad = np.isin(fed.part_of, np.asarray(sorted(eff), np.int64))
            for lab in np.unique(fed.union.primary[bad]) if bad.any() else ():
                rem = np.nonzero((fed.union.primary == lab) & ~bad)[0]
                if len(rem) >= 2:
                    need |= {int(p) for p in np.unique(fed.part_of[rem])}
        need -= excluded
        missing = set()
        for pid in sorted(need - excluded):
            if not fed.ensure_resident(pid, pin=need):
                missing.add(pid)
        closure_consulted |= need - missing - excluded
        if not missing:
            return base, filt, closure_consulted, excluded
        excluded |= missing
    return base, filt, closure_consulted, excluded  # pragma: no cover — bounded


def _affected_by_exclusion(
    fed: FederatedResident,
    q_edges: list[tuple[np.ndarray, np.ndarray]],
    eff: set[int],
) -> list[set[int]]:
    """Per query: the excluded partitions whose genomes are connected to
    its UNFILTERED component — the transitive coverage holes the
    filtered graph can no longer see. A quarantined partition's genome
    can co-cluster with the query purely through dropped edges (an
    a--b cross edge where the query only reaches `a`), in which case the
    degraded answer differs from the oracle even though the partition
    was never routed to or needed by the filtered closure — the verdict
    must still stamp it unavailable, or a strict client would silently
    accept the degraded answer. Built from every spine-loaded
    partition's intra edges (a spine-less partition contributes only its
    cross edges — its internal chains are unknowable, which can only
    under-extend a component WITHIN that already-stamped partition)."""
    from scipy.sparse import coo_matrix
    from scipy.sparse.csgraph import connected_components as _cc

    if not eff:
        return [set() for _ in q_edges]
    n_old = fed.n
    k = len(q_edges)
    parts_ii = [fed._cross[0]]
    parts_jj = [fed._cross[1]]
    for pid in sorted(fed._slots):
        slot = fed._slots[pid]
        if slot.intra is not None and len(slot.intra[0]):
            parts_ii.append(slot.intra[0])
            parts_jj.append(slot.intra[1])
    ii = np.concatenate(parts_ii + [e[0] for e in q_edges])
    jj = np.concatenate(
        parts_jj
        + [np.full(len(e[0]), n_old + t, np.int64) for t, e in enumerate(q_edges)]
    )
    n_tot = n_old + k
    graph = coo_matrix((np.ones(len(ii), np.int8), (ii, jj)), shape=(n_tot, n_tot))
    _, comp = _cc(graph, directed=False)
    out: list[set[int]] = []
    for t in range(k):
        members = np.nonzero(comp[:n_old] == comp[n_old + t])[0]
        pids = {int(p) for p in np.unique(fed.part_of[members])} if len(members) else set()
        out.append(pids & eff)
    return out


def _stamp(verdict: dict, consulted: set[int], unavailable: set[int]) -> dict:
    verdict["partitions_consulted"] = sorted(consulted)
    verdict["partitions_unavailable"] = sorted(unavailable)
    if unavailable:
        verdict["partial"] = True
    return verdict


def classify_batch_federated(
    fed: FederatedResident,
    queries,
    processes: int = 1,
    prune_cfg: dict | None = None,
    joint: bool = True,
    partition_compare=None,
    consult_check=None,
) -> list[dict]:
    """Streaming per-partition classify (ISSUE 14 tentpole): route, run
    one rect compare per (consulted partition x batch), merge the
    per-partition edges, and assemble per-query verdicts through the
    exact recluster machinery the union path runs — verdicts IDENTICAL
    to union-assembled ``classify_batch`` (oracle-pinned in tests) when
    every consulted partition is healthy, honest PARTIAL verdicts
    (stamped ``partitions_consulted`` / ``partitions_unavailable``)
    when one is not. No K-pad shape bucketing here: device shapes vary
    with the consulted partition sizes anyway, and each per-partition
    pack is already block-padded by the streaming executor.

    ``partition_compare(pid, names, bottoms) -> (ui, qi, dd) | None``
    (optional) substitutes the per-partition rect compare — the fleet
    router (serve/router.py) injects pre-gathered REMOTE leg results
    here, so a scatter/gathered verdict runs the very same merge +
    recluster below and stays byte-identical to the local path. ``None``
    books the partition unavailable, exactly like a local residency
    failure.

    ``consult_check() -> bool`` (optional) gates each partition consult
    up front: False books the partition unavailable WITHOUT running its
    compare. The fleet router passes its batch's remaining deadline
    budget here (ISSUE 19), so a gather whose clients have already
    walked away degrades to an immediate honest PARTIAL instead of
    burning device time per partition on an answer nobody reads."""
    from drep_tpu.index.classify import _assemble_verdicts

    if not queries.n:
        return []
    gen = int(fed.generation)
    n_old = fed.n
    q_names = list(queries.admitted["genome"])
    q_bottoms = [
        np.asarray(queries.results[g]["bottom"], np.uint64) for g in q_names
    ]
    k = len(q_names)
    cand = fed.route_candidates(q_bottoms)
    consulted: set[int] = set()
    unavailable: set[int] = set()
    q_edges: list[tuple[np.ndarray, np.ndarray]] = [
        (np.empty(0, np.int64), np.empty(0, np.float32)) for _ in range(k)
    ]
    for pid in sorted(set().union(*cand) if cand else ()):
        if consult_check is not None and not consult_check():
            # the batch's deadline budget expired mid-merge: every
            # remaining partition books unavailable — the verdict goes
            # out PARTIAL (stamped, honest) and the batch thread frees
            # for work someone is still waiting on
            unavailable.add(pid)
            continue
        cols = [t for t in range(k) if pid in cand[t]]
        if partition_compare is not None:
            res = partition_compare(
                pid, [q_names[t] for t in cols], [q_bottoms[t] for t in cols]
            )
        else:
            if not fed.ensure_resident(pid, pin={pid}):
                unavailable.add(pid)
                continue
            res = fed.classify_partition(
                pid, [q_names[t] for t in cols], [q_bottoms[t] for t in cols],
                prune_cfg,
            )
        if res is None:
            unavailable.add(pid)
            continue
        consulted.add(pid)
        ui, qt, dd = res
        for j, t in enumerate(cols):
            s = qt == j
            if s.any():
                old_ui, old_dd = q_edges[t]
                q_edges[t] = (
                    np.concatenate([old_ui, ui[s]]),
                    np.concatenate([old_dd, dd[s].astype(np.float32)]),
                )

    routed_unavailable = set(unavailable)
    base, filt, closure_consulted, excluded = _component_closure(
        fed, q_edges, unavailable
    )
    closure_missing = excluded - routed_unavailable
    unavailable = excluded  # closure started from the routed failures
    # a partition can be consulted for the compare and THEN fail its
    # closure reload (evicted + rot landed in between): its edges were
    # re-filtered out, so "consulted" must not keep claiming it — the
    # two stamps are one-or-the-other by contract
    consulted = (consulted | closure_consulted) - unavailable
    closure_consulted -= unavailable
    # transitive coverage holes: excluded partitions reachable from a
    # query's component only through DROPPED edges still degrade its
    # answer and must be stamped (see _affected_by_exclusion)
    affected = _affected_by_exclusion(
        fed, q_edges, unavailable | fed._spineless()
    )

    if joint:
        sq = fed.scratch_excluding(excluded)
        _admit_batch(sq, queries.admitted, queries.results, gen + 1)
        ti, tj, td = _query_query_edges(fed, q_names, q_bottoms)
        new_ii = np.concatenate([f[0] for f in filt] + [n_old + ti])
        new_jj = np.concatenate(
            [np.full(len(f[0]), n_old + t, np.int64) for t, f in enumerate(filt)]
            + [n_old + tj]
        )
        new_dd = np.concatenate([f[1] for f in filt] + [td])
        order = np.lexsort((new_jj, new_ii))
        new_ii, new_jj, new_dd = new_ii[order], new_jj[order], new_dd[order]
        sq.edges = (
            np.concatenate([base[0], new_ii]),
            np.concatenate([base[1], new_jj]),
            np.concatenate([base[2], new_dd]),
        )
        recluster(sq, n_old, processes=processes)
        out = _assemble_verdicts(sq, n_old, new_ii, new_jj, new_dd, gen)
        fed._evict_to_budget(set())  # settle under the budget between batches
        joint_unavail = unavailable | set().union(*affected)
        return [_stamp(v, consulted - joint_unavail, joint_unavail) for v in out]

    out: list[dict] = []
    for t in range(k):
        sq = fed.scratch_excluding(excluded)
        _admit_batch(sq, queries.admitted.iloc[[t]], queries.results, gen + 1)
        ui, dd = filt[t]
        order = np.argsort(ui, kind="stable")
        qii, qdd = ui[order], dd[order]
        qjj = np.full(len(qii), n_old, np.int64)
        sq.edges = (
            np.concatenate([base[0], qii]),
            np.concatenate([base[1], qjj]),
            np.concatenate([base[2], qdd]),
        )
        recluster(sq, n_old, processes=processes)
        v = _assemble_verdicts(sq, n_old, qii, qjj, qdd, gen)[0]
        # this query's coverage: its routed candidates plus whatever the
        # component closure pulled in (closure needs are graph-global —
        # attributed to every query, honestly erring toward "consulted")
        unavail_t = (routed_unavailable & cand[t]) | closure_missing | affected[t]
        consulted_t = ((consulted & cand[t]) | closure_consulted) - unavail_t
        out.append(_stamp(v, consulted_t, unavail_t))
    # one batch's working set (every query component's sketches) is
    # legitimately pinned above the budget while in flight; settle back
    # under it before the next batch — residency is an inter-batch
    # contract, the peak gauge records the in-flight truth
    fed._evict_to_budget(set())
    return out


# ---------------------------------------------------------------------------
# federated build + update
# ---------------------------------------------------------------------------


def build_federated(
    location: str, genome_paths: list[str], partitions: int,
    processes: int = 1, fed_pods: int | None = None, **kwargs,
) -> dict:
    """`index build --partitions N`: create a federated index and admit
    the whole input set as federation generation 0. The build is an
    empty-skeleton meta publish followed by one ordinary federated
    update, so a killed build resumes through the exact update machinery
    (`index update <root> -g <same paths>`) and converges.

    Under ``fed_pods`` even partition MATERIALIZATION (each partition's
    generation 0) parallelizes: the router's sketches and the meta's
    pinned params ride a ``--params_file`` handoff into each pod
    (:func:`write_params_handoff` — the ISSUE 14 fix for the old
    pods-can't-ride-the-CLI limitation)."""
    store = FederationStore(location)
    if store.exists() or IndexStore(location).exists():
        raise UserInputError(
            f"{location} already holds an index; `index update` grows it — "
            f"build refuses to overwrite"
        )
    from drep_tpu.index.build import resolve_params

    params = resolve_params(**kwargs)
    bounds = fedmeta.partition_bounds(partitions)
    skeleton = {
        "format": fedmeta.FED_FORMAT,
        "generation": -1,
        "n_genomes": 0,
        "n_partitions": int(partitions),
        "params": params,
        "partitions": [
            {
                "pid": p,
                "dir": fedmeta.partition_dir_name(p),
                "range": [int(lo), int(hi)],
                "generation": -1,
                "n_genomes": 0,
                "manifest_crc": None,
            }
            for p, (lo, hi) in enumerate(bounds)
        ],
        "cross_shards": [],
        "state": None,
    }
    store.ensure_dirs()
    store.publish_meta(skeleton)
    summary = fed_update(
        location, genome_paths, processes=processes, fed_pods=fed_pods
    )
    get_logger().info(
        "index build: federated %d genomes over %d partitions -> %s "
        "(federation generation 0)",
        summary.get("n_genomes", 0), partitions, location,
    )
    return summary


def write_params_handoff(
    path: str, params: dict, batch: pd.DataFrame, results: dict[str, dict]
) -> None:
    """The router -> partition-pod handoff (ISSUE 14 satellite): the
    routed batch's ALREADY-COMPUTED sketches plus the federation's
    PINNED params, serialized as one durable payload — so a ``--fed_pods``
    pod neither re-sketches its batch nor needs the CLI bootstrap to
    express the meta's params (which it cannot: generation-0
    materialization now parallelizes as pods too). The in-process path
    passes the same (batch, results) directly (``presketched``). Written by
    the index store's own writer (``store.write_payload``): the head at
    `path`, a member over ``workdir.ARRAY_PART_BYTES`` in parts beside it,
    uncompressed as a sketch shard is and for its reason."""
    import json

    from drep_tpu.index.store import write_payload
    from drep_tpu.ingest import pack_ragged

    names = list(batch["genome"])
    payload: dict[str, np.ndarray] = {
        "names": np.array(names, dtype=str),
        "locations": np.array(list(batch["location"]), dtype=str),
        "params_json": np.array(json.dumps(params, sort_keys=True)),
    }
    for c in _STAT_COLS:
        payload[c] = np.array([results[g][c] for g in names], np.int64)
    for key in ("bottom", "scaled"):
        payload[key], payload[f"{key}_offsets"] = pack_ragged(
            [results[g][key] for g in names]
        )
    write_payload(path, compressed=False, **payload)


def read_params_handoff(path: str, workers: int = 1) -> dict:
    """Read a :func:`write_params_handoff` file back into
    {"params", "batch", "results"} — the exact shapes ``sketch_batch``
    produces, so the consuming update is bit-identical to an in-process
    one (sketches were computed once, by the router)."""
    import json

    from drep_tpu.ingest import unpack_ragged

    z = read_payload(path, "params handoff", workers)
    names = [str(x) for x in z["names"]]
    bottom = unpack_ragged(z["bottom"], z["bottom_offsets"], len(names))
    scaled = unpack_ragged(z["scaled"], z["scaled_offsets"], len(names))
    results = {
        g: {
            "bottom": bottom[i], "scaled": scaled[i],
            **{c: int(z[c][i]) for c in _STAT_COLS},
        }
        for i, g in enumerate(names)
    }
    batch = pd.DataFrame(
        {"genome": names, "location": [str(x) for x in z["locations"]]}
    )
    return {
        "params": json.loads(str(z["params_json"])),
        "batch": batch,
        "results": results,
    }


def _build_partition(
    part_dir: str, params: dict, batch: pd.DataFrame, results: dict,
    processes: int,
) -> None:
    """Materialize an empty partition's generation 0 with the
    federation's PINNED params and the router's sketches (never
    re-sketched — the shared ``materialize_generation0`` core the
    ``--params_file`` pod path runs too)."""
    from drep_tpu.index.update import materialize_generation0

    materialize_generation0(
        IndexStore(part_dir), params, batch, results, processes=processes
    )


def _partition_generation(part_dir: str) -> int:
    """The partition's current manifest generation, -1 when the store
    does not exist yet — the ONLY read the happy path (partition exactly
    at the meta's generation) pays per update."""
    store = IndexStore(part_dir)
    if not store.exists():
        return -1
    return int(store.read_manifest()["generation"])


def _partition_names(part_dir: str, lo: int = 0) -> list[str]:
    """Genome names at index >= `lo`, read from only the sketch shards
    whose range reaches there — the resume skip-detection's tail probe.
    Deliberately NOT a full partition load: only the rare resume
    branches pay it, and only for the tail shards they compare."""
    store = IndexStore(part_dir)
    names: list[str] = []
    for e in store.read_manifest()["sketch_shards"]:
        if int(e["hi"]) <= lo:
            continue
        z = read_payload(store.abspath(e["file"]), "sketch shard")
        names.extend(
            str(x) for i, x in enumerate(z["names"], start=int(e["lo"])) if i >= lo
        )
    return names


def _run_pods(
    jobs: list[tuple[int, str, str, dict]], pods: int, processes: int
) -> dict[int, object]:
    """Run partition-update jobs as detached `index update` CLI pods, up
    to `pods` concurrently. Each pod is the ordinary single-store update
    — crash-resumable on its own pending checkpoint, publishing its own
    manifest atomically — consuming the router's sketches + pinned
    params through a ``--params_file`` handoff (never re-sketching, and
    MATERIALIZING an empty partition's generation 0 when the store does
    not exist yet — the ISSUE 14 pods-can't-ride-the-CLI fix). Pod
    output goes to a temp file per pod (a PIPE left undrained until exit
    would deadlock a chatty pod against the OS pipe buffer). The
    ``partition_update`` fault site fires immediately before EACH pod
    launch (the registered skip=N semantics); a raise there books that
    partition failed, like the in-process path. Returns
    {pid: returncode or failure-message}."""
    import tempfile

    from drep_tpu.utils import faults

    logger = get_logger()
    repo = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
    env = dict(os.environ)
    env["PYTHONPATH"] = repo + os.pathsep + env.get("PYTHONPATH", "")
    queue = list(jobs)
    running: dict[int, tuple[subprocess.Popen, object]] = {}
    results: dict[int, object] = {}
    while queue or running:
        while queue and len(running) < max(1, pods):
            pid, part_dir, handoff, prune_flags = queue.pop(0)
            try:
                faults.fire("partition_update")
            except Exception as e:  # noqa: BLE001 — same partition-level
                # failure tolerance as the in-process path
                results[pid] = f"{type(e).__name__}: {e}"
                logger.error(
                    "federated update: partition %d pod launch failed: %s", pid, e
                )
                continue
            cmd = [sys.executable, "-m", "drep_tpu", "index", "update", part_dir,
                   "--params_file", handoff, "-p", str(processes)]
            for flag, val in prune_flags.items():
                if val:
                    cmd += [f"--{flag}", str(val)]
            logger.info("federated update: launching pod for partition %d "
                        "(sketches ride the params handoff %s)",
                        pid, os.path.basename(handoff))
            log = tempfile.TemporaryFile(mode="w+")
            proc = subprocess.Popen(cmd, env=env, stdout=log, stderr=log, text=True)
            running[pid] = (proc, log)
        for pid, (proc, log) in list(running.items()):
            rc = proc.poll()
            if rc is None:
                continue
            log.seek(0)
            out = log.read()
            log.close()
            results[pid] = rc
            del running[pid]
            if rc != 0:
                logger.error(
                    "federated update: partition %d pod failed (rc=%d):\n%s",
                    pid, rc, out[-2000:],
                )
        if running:
            time.sleep(0.05)
    return results


def _routed_batches(
    batch: pd.DataFrame, results: dict[str, dict], bounds: list
) -> dict[int, pd.DataFrame]:
    """Route the sketched batch to partitions by range code, preserving
    batch order within each partition (the deterministic admission order
    a resume must reproduce)."""
    pids = [
        fedmeta.route_partition(
            fedmeta.route_code(results[g]["bottom"]), bounds
        )
        for g in batch["genome"]
    ]
    out: dict[int, pd.DataFrame] = {}
    for pid in sorted(set(pids)):
        sel = [p == pid for p in pids]
        out[pid] = batch[sel].reset_index(drop=True)
    return out


def _publish_unavailable_meta(
    store: FederationStore, m: dict, pid: int, reason: str,
    genome_paths: list[str] | None, logger,
) -> dict:
    """The degraded-but-honest PARTIAL meta: same generation, the
    unreadable partition stamped ``partial.partitions_unavailable`` (its
    recorded generation/count untouched), this batch's genomes recorded
    unadmitted. Idempotent — a repeat update against the still-broken
    partition merges into the existing stamp."""
    from drep_tpu.utils import telemetry

    partial = dict(m.get("partial") or {})
    unavailable = sorted(set(partial.get("partitions_unavailable", ())) | {pid})
    partial["partitions_unavailable"] = unavailable
    partial["reason"] = reason
    if genome_paths:
        partial["unadmitted"] = sorted(
            set(partial.get("unadmitted", ()))
            | {os.path.basename(p) for p in genome_paths}
        )
    m2 = dict(m)
    m2["partial"] = partial
    store.publish_meta(m2)
    telemetry.event(
        "federation_partial_meta", partitions_unavailable=unavailable,
        unadmitted=len(partial.get("unadmitted", ())),
    )
    logger.error(
        "federated update: partition %d is unreadable — publishing a "
        "DEGRADED meta at generation %d (partitions_unavailable=%s, %d "
        "genome(s) unadmitted; serve answers PARTIAL beside it). Heal the "
        "partition and re-run `index update` — a clean heal pass clears "
        "the stamp. %s",
        pid, int(m.get("generation", -1)), unavailable,
        len(partial.get("unadmitted", ())), reason,
    )
    return {
        "admitted": 0,
        "generation": int(m.get("generation", -1)),
        "n_partitions": int(m.get("n_partitions", 0)),
        "partitions_unavailable": unavailable,
        "unadmitted": list(partial.get("unadmitted", ())),
        "partial": partial,
    }


def fed_update(
    location: str, genome_paths: list[str] | None, processes: int = 1,
    fed_pods: int | None = None, primary_prune: str = "off",
    prune_bands: int = 0, prune_min_shared: int = 0, prune_join_chunk: int = 0,
) -> dict:
    """`index update` on a federated root: sketch + route the batch, run
    one INDEPENDENT update per dirty partition (in-process, or as
    `--fed_pods` concurrent subprocess pods), join the boundary buckets
    across partitions, re-cluster the union's dirty components, and
    publish the next federation generation through the meta-manifest.

    Partition-level failure is tolerated honestly: the failed partition
    stays at its old generation, its routed genomes are NOT admitted,
    and the published meta carries a ``partial`` note naming them (the
    summary lists them too — re-submit those genomes to finish). With no
    genomes this is a pure HEAL pass over every partition plus the
    federation families; the generation stays put."""
    from drep_tpu.utils import faults, telemetry
    from drep_tpu.utils import envknobs

    logger = get_logger()
    store = FederationStore(location)
    # converge any interrupted split/merge/compaction FIRST: an update
    # must never land on a half-committed range map (lazy import — the
    # maintenance module builds on this one)
    from drep_tpu.index import maintenance as fedmaint

    fedmaint.roll_forward(location)
    m = store.read_meta()
    params = m["params"]
    gen = int(m["generation"])
    gen_new = gen + 1
    if fed_pods is None:
        fed_pods = envknobs.env_int("DREP_TPU_FED_PODS")
    try:
        union = load_federated(location, heal=True)
    except UserInputError as err:
        bad_pid = getattr(err, "fed_partition", None)
        if bad_pid is None:
            raise  # not a partition-scoped fault: refuse as before
        # PARTIAL update contract (ROADMAP federated follow-on (e),
        # ISSUE 15 satellite): one quarantined/unreadable partition no
        # longer refuses the whole operation — the update DEGRADES
        # honestly instead. Nothing can be admitted (the union's cross
        # edges need the broken partition's sketches), so the meta is
        # republished at the SAME generation with the partition stamped
        # ``partitions_unavailable`` and the batch recorded unadmitted:
        # the serving tier keeps answering PARTIAL beside it (the
        # streaming resident quarantines the partition on its own
        # probes), pod_status renders the degradation, and the next
        # heal pass that finds the partition readable again clears the
        # stamp. Old generation retained, nothing laundered.
        return _publish_unavailable_meta(
            store, m, int(bad_pid), str(err), genome_paths, logger
        )
    stale_unavail = (m.get("partial") or {}).get("partitions_unavailable")
    if stale_unavail:
        # every meta-recorded partition just loaded (healed where
        # needed): the degradation is over — clear the stamp so serve's
        # meta view and pod_status stop reporting a recovered partition
        # as unavailable. Genomes unadmitted under the degraded window
        # stay listed until a batch/heal republish supersedes them only
        # if a real failed_partitions note needs them; here the window
        # closed, so the operator's cue is this log line + the summary.
        partial = dict(m["partial"])
        partial.pop("partitions_unavailable", None)
        partial.pop("reason", None)
        if not partial.get("failed_partitions"):
            partial.pop("unadmitted", None)
        m2 = dict(m)
        if partial:
            m2["partial"] = partial
        else:
            m2.pop("partial", None)
        store.publish_meta(m2)
        m = m2
        telemetry.event(
            "federation_partial_cleared", partitions_recovered=stale_unavail
        )
        logger.warning(
            "federated index: previously unavailable partition(s) %s are "
            "readable again — PARTIAL stamp cleared at generation %d "
            "(genomes unadmitted during the window must be re-submitted)",
            stale_unavail, int(m.get("generation", -1)),
        )
    part_of = np.asarray(union.fed_part_of, np.int64)  # type: ignore[attr-defined]
    local_of = np.asarray(union.fed_local_of, np.int64)  # type: ignore[attr-defined]

    batch = results = None
    if genome_paths:
        batch, results = sketch_batch(union, genome_paths, processes=processes)
    if batch is None or not len(batch):
        summary = {
            "admitted": 0, "generation": gen, "healed": union.healed,
            "n_partitions": int(m["n_partitions"]),
        }
        if union.state_missing and union.n:
            summary.update(recluster(union, union.n, processes=processes))
            store.write_fedstate(
                store.fedstate_name(gen), union, part_of, local_of
            )
            logger.warning("federated index: union state healed via full recompute")
        # routing-summary heal/upgrade: the streaming serve router needs
        # the per-partition coarse-code bitmaps (ISSUE 14); a rotted file
        # recomputes deterministically from the resident union, and a
        # pre-routing federation gains one on its first heal pass (the
        # meta republishes at the SAME generation with the family added)
        if union.n and gen >= 0:
            rt_rel = m.get("routing") or store.routing_name(gen)
            rt_ok = False
            if m.get("routing"):
                from drep_tpu.utils import durableio

                try:
                    durableio.load_npz_checked(
                        store.abspath(rt_rel), what="routing summary"
                    )
                    rt_ok = True
                except Exception:  # noqa: BLE001 — missing/corrupt -> rewrite
                    rt_ok = False
            if not rt_ok:
                store.ensure_dirs()
                store.write_routing_summary(
                    rt_rel, union.bottom, part_of, int(m["n_partitions"])
                )
                summary["healed"] = list(summary["healed"]) + [rt_rel]
                if m.get("routing") != rt_rel:
                    m2 = dict(m)
                    m2["routing"] = rt_rel
                    store.publish_meta(m2)
                logger.info(
                    "federated heal pass: routing summary rewritten (%s)", rt_rel
                )
        if union.healed:
            logger.info("federated heal pass: repaired %s", union.healed)
        return summary

    bounds = [tuple(e["range"]) for e in m["partitions"]]
    meta_gen = {int(e["pid"]): int(e["generation"]) for e in m["partitions"]}
    meta_n = {int(e["pid"]): int(e["n_genomes"]) for e in m["partitions"]}
    # pid -> store dir from the meta (post-split/merge renumbering
    # decouples the dense pid from the part_### name)
    meta_dir = {int(e["pid"]): store.abspath(e["dir"]) for e in m["partitions"]}
    routed = _routed_batches(batch, results, bounds)
    prune_flags = {
        "primary_prune": primary_prune if primary_prune != "off" else "",
        "prune_bands": prune_bands, "prune_min_shared": prune_min_shared,
        "prune_join_chunk": prune_join_chunk,
    }

    # -- per-partition resume/skip classification -------------------------
    # a partition AHEAD of the meta that this batch does NOT route to is
    # a killed PREVIOUS update mid-resume (this covers meta-empty
    # partitions a crashed attempt materialized, too): admitting a
    # different batch now would strand its already-admitted tail outside
    # the union forever — refuse with the resume instruction instead
    for e in m["partitions"]:
        pid = int(e["pid"])
        if pid in routed:
            continue
        if _partition_generation(meta_dir[pid]) > int(e["generation"]):
            raise UserInputError(
                f"federated index: partition {pid} is ahead of the "
                f"meta-manifest from an interrupted earlier update, and "
                f"this batch routes nothing to it — re-run the "
                f"interrupted update with ITS batch first (its admitted "
                f"tail must reach the union before a new batch lands)"
            )
    dirty: list[tuple[int, str, str]] = []  # (pid, part_dir, build|update)
    done: set[int] = set()
    for pid in sorted(routed):
        pdir = meta_dir.get(pid, store.partition_dir(pid))
        want = list(routed[pid]["genome"])
        actual_gen = _partition_generation(pdir)
        base_n = meta_n[pid]
        if meta_gen[pid] < 0:
            if actual_gen < 0:
                dirty.append((pid, pdir, "build"))
            elif actual_gen == 0 and sorted(_partition_names(pdir)) == sorted(want):
                done.add(pid)  # a killed prior attempt already materialized it
            else:
                raise UserInputError(
                    f"federated index: empty partition {pid} holds an "
                    f"unexpected store (generation {actual_gen}) — it was "
                    f"written out of band, or a DIFFERENT interrupted batch "
                    f"materialized it; re-run that batch first, or remove "
                    f"{pdir} / restore the federation backup"
                )
        elif actual_gen == meta_gen[pid]:
            dirty.append((pid, pdir, "update"))
        elif actual_gen == meta_gen[pid] + 1 and sorted(
            _partition_names(pdir, lo=base_n)
        ) == sorted(want):
            done.add(pid)  # a killed prior attempt already admitted the batch
        else:
            raise UserInputError(
                f"federated index: partition {pid} is at generation "
                f"{actual_gen} (meta records {meta_gen[pid]}) with a tail "
                f"that does not match this batch — it was updated out of "
                f"band, or a different batch is being resumed"
            )

    # -- run the dirty partitions as independent units --------------------
    # The router already sketched the whole batch — partitions consume
    # those sketches (never re-sketching): in-process via `presketched`,
    # pods via a `--params_file` handoff that also carries the pinned
    # params, so BUILDS (generation-0 materialization) parallelize as
    # pods too (the ROADMAP federated follow-on (b) fix).
    failed: dict[int, str] = {}
    if fed_pods > 0 and dirty:
        store.ensure_dirs()
        jobs: list[tuple[int, str, str, dict]] = []
        handoffs: list[str] = []
        for pid, pdir, _kind in dirty:
            handoff = store.abspath(
                os.path.join("log", f"handoff_p{pid:03d}_g{gen_new:06d}.npz")
            )
            write_params_handoff(handoff, params, routed[pid], results)
            handoffs.append(handoff)
            jobs.append((pid, pdir, handoff, prune_flags))
        try:
            rcs = _run_pods(jobs, fed_pods, processes)
        finally:
            import contextlib

            for handoff in handoffs:
                with contextlib.suppress(OSError):
                    os.remove(handoff)
        for pid, rc in rcs.items():
            if rc != 0:
                failed[pid] = (
                    f"pod exited rc={rc}" if isinstance(rc, int) else str(rc)
                )
            else:
                telemetry.event(
                    "federation_partition", pid=pid, op="pod",
                    n=len(routed[pid]),
                )
    else:
        for pid, pdir, kind in dirty:
            try:
                faults.fire("partition_update")
                if kind == "build":
                    _build_partition(
                        pdir, params, routed[pid], results, processes
                    )
                else:
                    index_update(
                        pdir, None, processes=processes,
                        primary_prune=primary_prune, prune_bands=prune_bands,
                        prune_min_shared=prune_min_shared,
                        prune_join_chunk=prune_join_chunk,
                        presketched=(routed[pid], results),
                    )
                telemetry.event("federation_partition", pid=pid, op=kind,
                                n=len(routed[pid]))
            except Exception as e:  # noqa: BLE001 — partition-level failure
                # is tolerated: the partition stays at its old generation
                # (or absent), the publish is PARTIAL
                failed[pid] = f"{type(e).__name__}: {e}"
                logger.error(
                    "federated update: partition %d %s failed: %s", pid, kind, e
                )

    succeeded = sorted((set(routed) - set(failed)) | done)
    if not succeeded:
        raise UserInputError(
            f"federated update: every dirty partition failed "
            f"({sorted(failed)}) — nothing to publish. Per-partition "
            f"errors: {failed}"
        )

    # -- append the admitted tails to the union ---------------------------
    n_old = union.n
    part_of_l = list(part_of)
    local_of_l = list(local_of)
    new_intra: list[tuple[np.ndarray, np.ndarray, np.ndarray]] = []
    unadmitted: list[str] = []
    for pid in sorted(routed):
        if pid in failed:
            unadmitted.extend(routed[pid]["genome"])
            continue
        pdir = meta_dir[pid]
        pidx = load_index(pdir)
        base_n = meta_n[pid]
        tail = list(range(base_n, pidx.n))
        want = sorted(routed[pid]["genome"])
        if sorted(pidx.names[base_n:]) != want:
            raise UserInputError(
                f"federated update: partition {pid} admitted "
                f"{pidx.names[base_n:]} but this batch routed {want} — "
                f"concurrent out-of-band update detected"
            )
        # the union admission order is (pid, local) over this batch —
        # deterministic, so a killed run's rerun reproduces it exactly
        l2u = np.full(pidx.n, -1, np.int64)
        sel = np.nonzero(part_of == pid)[0]
        l2u[local_of[sel]] = sel
        for loc in tail:
            l2u[loc] = len(part_of_l)
            part_of_l.append(pid)
            local_of_l.append(loc)
            union.names.append(pidx.names[loc])
            union.locations.append(pidx.locations[loc])
            union.bottom.append(pidx.bottom[loc])
            union.scaled.append(pidx.scaled[loc])
        rows = pidx.gdb.iloc[tail][["genome", *_STAT_COLS]]
        union.gdb = pd.concat([union.gdb, rows], ignore_index=True)
        union.admitted = np.concatenate(
            [union.admitted, np.full(len(tail), gen_new, np.int64)]
        )
        ii, jj, dd = pidx.edges
        sel_new = jj >= base_n
        new_intra.append((l2u[ii[sel_new]], l2u[jj[sel_new]], dd[sel_new]))
    part_of = np.asarray(part_of_l, np.int64)
    local_of = np.asarray(local_of_l, np.int64)
    admitted_k = union.n - n_old

    # -- boundary-bucket cross join over the grown union ------------------
    ci, cj = cross_candidates(union.bottom, part_of, min_col=n_old)
    xi, xj, xd, cross_pairs = cross_edges(union, part_of, ci, cj, min_col=n_old)
    ii = np.concatenate([union.edges[0], *(e[0] for e in new_intra), xi])
    jj = np.concatenate([union.edges[1], *(e[1] for e in new_intra), xj])
    dd = np.concatenate([union.edges[2], *(e[2] for e in new_intra), xd])
    order = np.lexsort((jj, ii))
    union.edges = (ii[order], jj[order], dd[order])

    summary = recluster(union, n_old, processes=processes)

    # -- publish: cross shard + union state first, the meta LAST ----------
    store.ensure_dirs()
    cr_rel = store.cross_shard_name(gen_new)
    st_rel = store.fedstate_name(gen_new)
    rt_rel = store.routing_name(gen_new)
    store.write_cross_shard(
        cr_rel, xi, xj, xd, part_of[n_old:], local_of[n_old:]
    )
    union.generation = gen_new
    store.write_fedstate(st_rel, union, part_of, local_of)
    store.write_routing_summary(
        rt_rel, union.bottom, part_of, int(m["n_partitions"])
    )
    new_n = {pid: meta_n[pid] for pid in meta_n}
    new_gen = dict(meta_gen)
    for pid in sorted(routed):
        if pid in failed:
            continue
        new_gen[pid] = max(meta_gen[pid] + 1, 0)
        new_n[pid] = meta_n[pid] + len(routed[pid])
    meta_new = {
        "format": fedmeta.FED_FORMAT,
        "generation": gen_new,
        "n_genomes": union.n,
        "n_partitions": int(m["n_partitions"]),
        "params": params,
        "partitions": [
            {
                "pid": int(e["pid"]),
                "dir": e["dir"],
                "range": [int(e["range"][0]), int(e["range"][1])],
                "generation": new_gen[int(e["pid"])],
                "n_genomes": new_n[int(e["pid"])],
                "manifest_crc": (
                    fedmeta.manifest_crc(store.abspath(e["dir"]))
                    if new_n[int(e["pid"])] > 0
                    else None
                ),
            }
            for e in m["partitions"]
        ],
        "cross_shards": list(m.get("cross_shards", ()))
        + [{"file": cr_rel, "lo": n_old, "hi": union.n, "generation": gen_new}],
        "state": st_rel,
        "routing": rt_rel,
    }
    if failed:
        meta_new["partial"] = {
            "failed_partitions": sorted(failed),
            "unadmitted": sorted(unadmitted),
        }
    store.publish_meta(meta_new)
    store.gc_states(st_rel, rt_rel)

    summary.update(
        {
            "admitted": admitted_k,
            "n_genomes": union.n,
            "generation": gen_new,
            "n_partitions": int(m["n_partitions"]),
            "partitions_updated": succeeded,
            "partitions_failed": sorted(failed),
            "unadmitted": sorted(unadmitted),
            "cross_edges": int(len(xi)),
            "cross_pairs_compared": cross_pairs,
            "healed": union.healed,
        }
    )
    logger.info(
        "federated update: +%d genomes over %d partition(s) -> federation "
        "generation %d (%d genomes, %d cross edge(s)%s)",
        admitted_k, len(succeeded), gen_new, union.n, len(xi),
        f"; PARTIAL — {len(unadmitted)} genome(s) unadmitted in "
        f"partition(s) {sorted(failed)}" if failed else "",
    )
    return summary
