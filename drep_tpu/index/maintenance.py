"""Transactional index lifecycle: partition split/merge + generation
compaction (ISSUE 18).

The federated store (index/federation.py) pins its partition ranges at
creation and appends one sketch/edge/state shard triple per admitted
generation forever — the two growth limits the ROADMAP names for
continuous admission at 10M+ genomes. This module makes the index a
system that can run for months:

SPLIT / MERGE — meta-manifest transactions over the range map
    ``fed_split`` bisects one partition's range at the sketch-code
    median into two child partition stores; ``fed_merge`` folds two
    adjacent partitions into one. Neither recomputes a single distance:
    the loaded union edge graph already holds every retained edge
    (partition intra edges in union coordinates + the recall-1.0 cross
    shards), so child stores are derived by re-partitioning that graph
    and re-clustering each child locally. The transaction is staged:

    1. STAGE    ``pending/maint.json`` (checked JSON — the transaction
                record) + child stores materialized under ``pending/``,
                beside the parent. Old meta fully live.
    2. INSTALL  children renamed to their final ``part_###`` dirs; the
                cross/fedstate/routing families rewritten at the new
                federation generation for the new range map (partition
                ids renumbered DENSE by range order — the routing
                bitmaps are pid-indexed). Still invisible: the old meta
                references none of it.
    3. COMMIT   one atomic ``federation.json`` publish. This is an
                ordinary generation bump to every reader — serve
                replicas and the fleet router adopt it through the same
                hot-swap path an `index update` publish rides.
    4. GC       parent stores and superseded family files removed,
                strictly after the commit (``DREP_TPU_SPLIT_GC_GRACE_S``
                delays this so live replicas on the old meta hot-swap
                before the parent disappears; a straggler that consults
                a gc'd parent is contained by the ordinary partition
                quarantine -> stamped-PARTIAL machinery).

    A SIGKILL at any phase either leaves the old meta fully live
    (pre-commit: ``roll_forward`` discards the staging and the rerun
    converges byte-identically — everything above is deterministic) or
    is rolled forward by the next maintenance pass (post-commit:
    ``roll_forward`` completes the gc idempotently). The deterministic
    kill points fire the ``partition_split`` fault site at each phase
    boundary (skip=0 staged, skip=1 pre-commit, skip=2 pre-gc).

COMPACTION — LSM-style merge-and-supersede over generation families
    ``fed_compact`` (and ``compact_store`` for a plain index) folds a
    store's N sketch/edge/state generations into ONE freshly-written
    generation at ``g+1`` — same genomes, same per-genome admitted
    generations, same edge set — publishes the manifest, bumps the
    federation meta (new partition ``(generation, manifest_crc)``; the
    union families are untouched because membership did not move), and
    gc's the superseded shards. The pinned incremental==from-scratch
    oracle is the compaction oracle: a compacted store classifies and
    updates byte-identical to its uncompacted twin. Kill points fire
    the ``compaction`` site with the same skip discipline. A kill
    between a partition's manifest publish and the meta publish leaves
    the partition ahead-by-one WITH UNCHANGED genome count — an
    unambiguous compaction interrupt (updates always grow n), which
    ``roll_forward`` adopts by republishing the meta even when the
    transaction record itself was lost.

``roll_forward(location)`` is the convergence point: every maintenance
verb AND ``fed_update`` call it first, so an interrupted transaction is
finished (or discarded) before any new work lands.
"""

from __future__ import annotations

import contextlib
import os
import shutil
import time

import numpy as np
import pandas as pd

from drep_tpu.errors import UserInputError
from drep_tpu.index import meta as fedmeta
from drep_tpu.index.federation import (
    FederationStore,
    _partition_generation,
    load_federated,
)
from drep_tpu.index.store import IndexStore, LoadedIndex, build_manifest, load_index, unreferenced
from drep_tpu.utils.logger import get_logger

_STAT_COLS = ("length", "N50", "contigs", "n_kmers")

MAINT_NAME = os.path.join("pending", "maint.json")


# ---------------------------------------------------------------------------
# transaction record
# ---------------------------------------------------------------------------


def maint_path(location: str) -> str:
    return os.path.join(os.path.abspath(location), MAINT_NAME)


def read_staging(location: str) -> dict | None:
    """The in-flight transaction record, or None. A torn/corrupt record
    reads as None PLUS a planted tombstone removal: a record that cannot
    name its children cannot be rolled forward, and the staged artifacts
    it would have named are exactly what the scrubber classifies as
    orphaned staging."""
    from drep_tpu.utils.durableio import CorruptPayloadError, read_json_checked

    path = maint_path(location)
    if not os.path.exists(path):
        return None
    try:
        doc = read_json_checked(path, what="maintenance transaction record")
    except CorruptPayloadError:
        get_logger().warning(
            "index maintenance: transaction record %s is corrupt — "
            "discarding it (staged artifacts become scrub-able orphans; "
            "the next maintenance pass restages from the live meta)", path,
        )
        with contextlib.suppress(OSError):
            os.remove(path)
        return None
    return doc if isinstance(doc, dict) else None


def _write_staging(location: str, doc: dict) -> None:
    from drep_tpu.utils.durableio import atomic_write_json

    os.makedirs(os.path.dirname(maint_path(location)), exist_ok=True)
    atomic_write_json(maint_path(location), doc)


def _remove_staging(location: str) -> None:
    with contextlib.suppress(OSError):
        os.remove(maint_path(location))
    # the shared pending/ staging area goes when it is empty (partition
    # stores keep their own pending/ rect checkpoints — different dirs)
    with contextlib.suppress(OSError):
        os.rmdir(os.path.join(os.path.abspath(location), "pending"))


# ---------------------------------------------------------------------------
# roll-forward / roll-back
# ---------------------------------------------------------------------------


def roll_forward(location: str) -> dict | None:
    """Converge an interrupted maintenance transaction before any new
    work: a COMMITTED transaction (meta already at ``gen_new``) finishes
    its gc idempotently; an uncommitted split/merge is discarded (old
    meta fully live — the rerun restages deterministically); an
    uncommitted compaction is completed (its per-partition manifest
    publishes may already be durable and cannot be unwound — but the
    fold is deterministic, so finishing it IS the convergent rerun).
    Also adopts record-less compaction interrupts: a partition ahead of
    the meta by exactly one generation with an UNCHANGED genome count.
    Returns a small summary of what it did, or None."""
    store = FederationStore(location)
    if not store.exists():
        return None
    logger = get_logger()
    doc = read_staging(location)
    out: dict | None = None
    if doc is not None:
        m = store.read_meta()
        gen_new = int(doc.get("gen_new", -1))
        op = str(doc.get("op", "?"))
        if int(m["generation"]) >= gen_new:
            _gc_after_commit(store, doc)
            logger.info(
                "index maintenance: rolled %s transaction forward "
                "(generation %d committed; gc completed)", op, gen_new,
            )
            out = {"op": op, "rolled": "forward", "generation": gen_new,
                   "parents": [int(p["pid"]) for p in doc.get("parents", ())]}
        elif op == "compact":
            out = _resume_compact(store, doc)
        else:
            _discard_staging(store, doc)
            logger.info(
                "index maintenance: discarded uncommitted %s staging — "
                "old meta (generation %d) fully live; rerun restages "
                "deterministically", op, int(m["generation"]),
            )
            out = {"op": op, "rolled": "back",
                   "generation": int(m["generation"])}
    adopted = _adopt_ahead_partitions(store)
    return out or adopted


def _discard_staging(store: FederationStore, doc: dict) -> None:
    """Undo an uncommitted split/merge: remove staged children (under
    pending/ AND any already renamed to final dirs — never a dir the
    live meta references), the pre-written family files at the aborted
    generation, and the record itself."""
    m = store.read_meta()
    live_dirs = {e["dir"] for e in m.get("partitions", ())}
    for child in doc.get("children", ()):
        d = str(child["dir"])
        if d in live_dirs:
            continue  # paranoia: never touch a meta-referenced store
        shutil.rmtree(os.path.join(store.location, "pending", d),
                      ignore_errors=True)
        shutil.rmtree(store.abspath(d), ignore_errors=True)
    gen_new = int(doc.get("gen_new", -1))
    if gen_new > int(m["generation"]):
        for rel in (store.cross_shard_name(gen_new),
                    store.fedstate_name(gen_new), store.routing_name(gen_new)):
            with contextlib.suppress(OSError):
                os.remove(store.abspath(rel))
    _remove_staging(store.location)


def _adopt_ahead_partitions(store: FederationStore) -> dict | None:
    """Record-less compaction interrupt: a partition manifest published
    at meta+1 with an unchanged genome count (an interrupted update
    always GROWS n, so this state is unambiguous). Republish the meta
    acknowledging the new (generation, crc) — completing the commit —
    then gc the superseded shards."""
    m = store.read_meta()
    gen = int(m["generation"])
    if gen < 0:
        return None
    adopted: list[int] = []
    entries = [dict(e) for e in m["partitions"]]
    for e in entries:
        if int(e["n_genomes"]) <= 0:
            continue
        pdir = store.abspath(e["dir"])
        if _partition_generation(pdir) != int(e["generation"]) + 1:
            continue
        try:
            pm = IndexStore(pdir).read_manifest()
        except UserInputError:
            continue
        if int(pm.get("n_genomes", -1)) != int(e["n_genomes"]):
            continue  # grown tail: an interrupted UPDATE — not ours
        e["generation"] = int(e["generation"]) + 1
        e["manifest_crc"] = fedmeta.manifest_crc(pdir)
        adopted.append(int(e["pid"]))
    if not adopted:
        return None
    m_new = dict(m)
    m_new["partitions"] = entries
    m_new["generation"] = gen + 1
    store.publish_meta(m_new)
    for e in entries:
        if int(e["pid"]) in adopted:
            _gc_unreferenced(store.abspath(e["dir"]))
    get_logger().warning(
        "index maintenance: adopted interrupted compaction of partition(s) "
        "%s (ahead-by-one, unchanged genome count) -> federation "
        "generation %d", adopted, gen + 1,
    )
    return {"op": "compact", "rolled": "forward", "generation": gen + 1,
            "parents": adopted}


# ---------------------------------------------------------------------------
# gc
# ---------------------------------------------------------------------------


def _gc_after_commit(store: FederationStore, doc: dict) -> None:
    """Phase 4: strictly after the meta publish. Grace-delayed so live
    replicas still on the old meta hot-swap before the parents vanish;
    idempotent — a kill anywhere in here reruns harmlessly."""
    from drep_tpu.utils import envknobs

    knob = ("DREP_TPU_COMPACT_GC_GRACE_S" if doc.get("op") == "compact"
            else "DREP_TPU_SPLIT_GC_GRACE_S")
    grace = envknobs.env_float(knob)
    if grace > 0:
        time.sleep(grace)
    m = store.read_meta()
    live_dirs = {e["dir"] for e in m.get("partitions", ())}
    if doc.get("op") == "compact":
        for p in doc.get("parents", ()):
            if p["dir"] in live_dirs:
                _gc_unreferenced(store.abspath(p["dir"]))
    else:
        for p in doc.get("parents", ()):
            if p["dir"] not in live_dirs:
                shutil.rmtree(store.abspath(p["dir"]), ignore_errors=True)
        for child in doc.get("children", ()):
            shutil.rmtree(
                os.path.join(store.location, "pending", str(child["dir"])),
                ignore_errors=True,
            )
        _gc_superseded_families(store, m)
    _remove_staging(store.location)


def _gc_superseded_families(store: FederationStore, m: dict) -> None:
    """Remove federation-level family files the CURRENT meta no longer
    references (a split/merge folds every cross shard into one)."""
    referenced = {os.path.basename(e["file"]) for e in m.get("cross_shards", ())}
    cross_dir = os.path.join(store.location, "cross")
    if os.path.isdir(cross_dir):
        for f in os.listdir(cross_dir):
            if (f.startswith("cross_g") and f.endswith(".npz")
                    and f not in referenced):
                with contextlib.suppress(OSError):
                    os.remove(os.path.join(cross_dir, f))
    if m.get("state"):
        store.gc_states(m["state"], m.get("routing"))


def _gc_unreferenced(part_dir: str) -> None:
    """Partition-store gc: remove generation-family files the CURRENT
    manifest does not reference (compaction's superseded shards) plus
    the pending rect-checkpoint dir. Idempotent by construction."""
    try:
        pm = IndexStore(part_dir).read_manifest()
    except UserInputError:
        return
    referenced = {e["file"] for e in pm.get("sketch_shards", ())}
    referenced |= {e["file"] for e in pm.get("edge_shards", ())}
    if pm.get("state"):
        referenced.add(pm["state"])
    referenced = {os.path.basename(r) for r in referenced}
    for sub, prefix in (("sketches", "sketch_g"), ("edges", "edges_g"),
                        ("state", "state_g")):
        fam = os.path.join(part_dir, sub)
        if not os.path.isdir(fam):
            continue
        for loc in unreferenced(fam, prefix, referenced):  # a shard's parts go with its head
            with contextlib.suppress(OSError):
                os.remove(loc)
    shutil.rmtree(os.path.join(part_dir, "pending"), ignore_errors=True)


# ---------------------------------------------------------------------------
# split / merge
# ---------------------------------------------------------------------------


def _refuse_if_degraded(m: dict, location: str, verb: str) -> None:
    partial = m.get("partial") or {}
    if partial.get("failed_partitions") or partial.get("partitions_unavailable"):
        raise UserInputError(
            f"federated index at {location} carries a PARTIAL stamp "
            f"({partial}) — `index {verb}` rewrites the range map and "
            f"refuses to bake a degraded union in; finish/heal the "
            f"pending work first (`drep-tpu index update {location}`)"
        )


def _allocate_dirs(m: dict, count: int) -> list[str]:
    """Fresh partition dir names: the smallest part_### numbers no meta
    entry uses. Deterministic from the meta alone, so an interrupted
    transaction's rerun allocates the same names."""
    used = {str(e["dir"]) for e in m.get("partitions", ())}
    out: list[str] = []
    i = 0
    while len(out) < count:
        name = fedmeta.partition_dir_name(i)
        if name not in used:
            out.append(name)
        i += 1
        if i > fedmeta.MAX_PARTITIONS:
            raise UserInputError(
                f"federation at {m.get('n_partitions')} partitions has no "
                f"free part_### names (MAX_PARTITIONS={fedmeta.MAX_PARTITIONS})"
            )
    return out


def _member_rows(union: LoadedIndex, pid: int) -> np.ndarray:
    part_of = np.asarray(union.fed_part_of, np.int64)  # type: ignore[attr-defined]
    local_of = np.asarray(union.fed_local_of, np.int64)  # type: ignore[attr-defined]
    rows = np.nonzero(part_of == pid)[0]
    return rows[np.argsort(local_of[rows], kind="stable")]


def _build_child_store(
    union: LoadedIndex, dst: str, rows: np.ndarray, processes: int = 1
) -> None:
    """Materialize one child partition store from the union: the child's
    genomes in parent-local order, its retained edge graph RESTRICTED
    from the union graph (distances are pack-independent — a from-
    scratch build of the same member set retains exactly these pairs),
    and a local from-scratch recluster for its derived state. One
    generation-0 shard per family; per-genome admitted generations are
    preserved (the compacted-shard discipline)."""
    from drep_tpu.index.update import recluster

    rows = np.asarray(rows, np.int64)
    n_c = len(rows)
    if n_c == 0:
        return
    u2c = np.full(union.n, -1, np.int64)
    u2c[rows] = np.arange(n_c, dtype=np.int64)
    ii, jj, dd = union.edges
    sel = (u2c[ii] >= 0) & (u2c[jj] >= 0)
    ci, cj, cd = u2c[ii[sel]], u2c[jj[sel]], dd[sel]
    # the union's ii<jj canon can invert under a merge's member
    # reordering (parent-b rows land after parent-a rows)
    swap = ci > cj
    ci[swap], cj[swap] = cj[swap], ci[swap].copy()
    child = LoadedIndex(
        location=os.path.abspath(dst), params=union.params, generation=0,
        names=[union.names[u] for u in rows],
        locations=[union.locations[u] for u in rows],
        gdb=pd.DataFrame({
            "genome": [union.names[u] for u in rows],
            **{c: union.gdb[c].to_numpy()[rows].astype(np.int64)
               for c in _STAT_COLS},
        }),
        admitted=np.asarray(union.admitted, np.int64)[rows],
        bottom=[union.bottom[u] for u in rows],
        scaled=[union.scaled[u] for u in rows],
        edges=(ci, cj, cd),
        primary=np.zeros(n_c, np.int64), suffix=np.zeros(n_c, np.int64),
        score=np.zeros(n_c, np.float64),
        winners=pd.DataFrame({"cluster": [], "genome": [], "score": []}),
    )
    recluster(child, 0, processes=processes)
    st = IndexStore(dst)
    st.ensure_dirs()
    sk_rel, ed_rel = st.sketch_shard_name(0), st.edge_shard_name(0)
    state_rel = st.state_name(0)
    st.write_sketch_shard(
        sk_rel, child.names, child.locations, child.gdb,
        child.bottom, child.scaled, child.admitted,
    )
    st.write_edge_shard(ed_rel, ci, cj, cd)
    st.write_state(state_rel, child)
    child.sketch_shards = [{"file": sk_rel, "lo": 0, "hi": n_c, "generation": 0}]
    child.edge_shards = [{"file": ed_rel, "lo": 0, "hi": n_c, "generation": 0}]
    st.publish_manifest(build_manifest(child, state_rel))


def _run_range_txn(
    store: FederationStore, m: dict, union: LoadedIndex, txn: dict,
    members_by_dir: dict[str, np.ndarray], processes: int,
) -> dict:
    """The shared split/merge transaction body: stage, install, commit,
    gc — with the ``partition_split`` fault site fired at each phase
    boundary (skip=0 staged, skip=1 pre-commit, skip=2 pre-gc)."""
    from drep_tpu.utils import faults, telemetry

    logger = get_logger()
    location = store.location
    gen_new = int(txn["gen_new"])
    op = str(txn["op"])
    parent_pids = {int(p["pid"]) for p in txn["parents"]}
    parent_dirs = {str(p["dir"]) for p in txn["parents"]}

    # -- phase 1: STAGE ---------------------------------------------------
    _write_staging(location, txn)
    staged_root = os.path.join(location, "pending")
    for child in txn["children"]:
        rows = members_by_dir[str(child["dir"])]
        if not len(rows):
            continue
        dst = os.path.join(staged_root, str(child["dir"]))
        shutil.rmtree(dst, ignore_errors=True)
        _build_child_store(union, dst, rows, processes=processes)
    faults.fire("partition_split")  # kill point: STAGED

    # -- phase 2: INSTALL -------------------------------------------------
    # children to final dirs; pids renumbered DENSE by range-lo order
    # (routing bitmaps are pid-indexed arrays); families rewritten for
    # the new range map. Old meta references none of this yet.
    for child in txn["children"]:
        if not int(child["n_genomes"]):
            continue
        src = os.path.join(staged_root, str(child["dir"]))
        dst = store.abspath(str(child["dir"]))
        if os.path.isdir(dst):
            shutil.rmtree(dst)
        # drep-lint: allow[durable-funnel] — whole-DIRECTORY install: every file inside was durably written (atomic_savez/json) when staged under pending/; this rename is the publish half, and the store stays invisible until the federation.json commit regardless
        os.replace(src, dst)
    kept = [e for e in m["partitions"] if int(e["pid"]) not in parent_pids]
    entries = [dict(e) for e in kept]
    for child in txn["children"]:
        entries.append({
            "pid": -1, "dir": str(child["dir"]),
            "range": [int(child["range"][0]), int(child["range"][1])],
            "generation": 0 if int(child["n_genomes"]) else -1,
            "n_genomes": int(child["n_genomes"]),
            "manifest_crc": (
                fedmeta.manifest_crc(store.abspath(str(child["dir"])))
                if int(child["n_genomes"]) else None
            ),
        })
    entries.sort(key=lambda e: int(e["range"][0]))
    dir_to_pid = {}
    for new_pid, e in enumerate(entries):
        e["pid"] = new_pid
        dir_to_pid[str(e["dir"])] = new_pid

    part_of = np.asarray(union.fed_part_of, np.int64)  # type: ignore[attr-defined]
    local_of = np.asarray(union.fed_local_of, np.int64)  # type: ignore[attr-defined]
    old_dir = {int(e["pid"]): str(e["dir"]) for e in m["partitions"]}
    new_part_of = np.empty(union.n, np.int64)
    new_local_of = np.empty(union.n, np.int64)
    keep_sel = ~np.isin(part_of, list(parent_pids))
    for u in np.nonzero(keep_sel)[0]:
        new_part_of[u] = dir_to_pid[old_dir[int(part_of[u])]]
        new_local_of[u] = local_of[u]
    for child in txn["children"]:
        pid = dir_to_pid[str(child["dir"])]
        rows = members_by_dir[str(child["dir"])]
        new_part_of[rows] = pid
        new_local_of[rows] = np.arange(len(rows), dtype=np.int64)

    store.ensure_dirs()
    cr_rel = store.cross_shard_name(gen_new)
    st_rel = store.fedstate_name(gen_new)
    rt_rel = store.routing_name(gen_new)
    ii, jj, dd = union.edges
    xsel = new_part_of[ii] != new_part_of[jj]
    store.write_cross_shard(
        cr_rel, ii[xsel], jj[xsel], dd[xsel], new_part_of, new_local_of
    )
    union.generation = gen_new
    store.write_fedstate(st_rel, union, new_part_of, new_local_of)
    store.write_routing_summary(rt_rel, union.bottom, new_part_of, len(entries))
    meta_new = {
        "format": fedmeta.FED_FORMAT,
        "generation": gen_new,
        "n_genomes": union.n,
        "n_partitions": len(entries),
        "params": m["params"],
        "partitions": entries,
        # the fold: ONE cross shard covering the whole union, its
        # redundant (map_pid, map_local) copy matching the NEW range map
        "cross_shards": [
            {"file": cr_rel, "lo": 0, "hi": union.n, "generation": gen_new}
        ],
        "state": st_rel,
        "routing": rt_rel,
    }
    faults.fire("partition_split")  # kill point: PRE-COMMIT

    # -- phase 3: COMMIT --------------------------------------------------
    store.publish_meta(meta_new)
    telemetry.event(
        "index_maintenance", op=op, generation=gen_new,
        parents=sorted(parent_pids), n_partitions=len(entries),
    )
    faults.fire("partition_split")  # kill point: PRE-GC

    # -- phase 4: GC ------------------------------------------------------
    _gc_after_commit(store, txn)
    logger.info(
        "index %s: partition(s) %s (%s) -> %s at federation generation %d "
        "(%d partitions, %d cross edge(s))",
        op, sorted(parent_pids), sorted(parent_dirs),
        [c["dir"] for c in txn["children"]], gen_new, len(entries),
        int(np.count_nonzero(xsel)),
    )
    return {
        "op": op,
        "generation": gen_new,
        "n_partitions": len(entries),
        "n_genomes": union.n,
        "parents": sorted(parent_pids),
        "children": [
            {"pid": dir_to_pid[str(c["dir"])], "dir": str(c["dir"]),
             "range": [int(c["range"][0]), int(c["range"][1])],
             "n_genomes": int(c["n_genomes"])}
            for c in txn["children"]
        ],
        "cross_edges": int(np.count_nonzero(xsel)),
    }


def fed_split(location: str, pid: int, processes: int = 1) -> dict:
    """`index split`: bisect partition `pid`'s range at its sketch-code
    median into two child partition stores, as one staged meta-manifest
    transaction (module docstring). Rerunning after a kill converges:
    pre-commit the staging is discarded and restaged byte-identically;
    post-commit the transaction is rolled forward (and a rerun naming
    the same parent returns its committed summary instead of splitting
    the renumbered pid that now wears the number)."""
    rf = roll_forward(location)
    if (rf and rf.get("rolled") == "forward" and rf.get("op") == "split"
            and int(pid) in rf.get("parents", ())):
        return {"op": "split", "generation": int(rf["generation"]),
                "already_committed": True, "parents": [int(pid)]}
    store = FederationStore(location)
    m = store.read_meta()
    _refuse_if_degraded(m, location, "split")
    gen = int(m["generation"])
    if gen < 0:
        raise UserInputError(
            f"federated index at {location} is an empty skeleton — there "
            f"is nothing to split yet"
        )
    entry = next(
        (e for e in m["partitions"] if int(e["pid"]) == int(pid)), None
    )
    if entry is None:
        raise UserInputError(
            f"federated index at {location} has no partition {pid} "
            f"(pids 0..{int(m['n_partitions']) - 1})"
        )
    if int(entry["n_genomes"]) < 2:
        raise UserInputError(
            f"partition {pid} holds {entry['n_genomes']} genome(s) — a "
            f"split needs at least 2"
        )
    union = load_federated(location, heal=False)
    rows = _member_rows(union, int(pid))
    codes = np.array(
        [fedmeta.route_code(union.bottom[int(u)]) for u in rows], np.uint64
    )
    uniq = np.unique(codes)
    if len(uniq) < 2:
        raise UserInputError(
            f"partition {pid}: all {len(rows)} genomes share one sketch "
            f"range code — the range cannot be bisected (they would all "
            f"land in one child). Merge-and-resplit a neighboring range "
            f"instead."
        )
    mid = int(uniq[len(uniq) // 2])
    lo, hi = int(entry["range"][0]), int(entry["range"][1])
    left = rows[codes < np.uint64(mid)]
    right = rows[codes >= np.uint64(mid)]
    dirs = _allocate_dirs(m, 2)
    txn = {
        "op": "split",
        "gen_new": gen + 1,
        "parents": [{"pid": int(pid), "dir": str(entry["dir"])}],
        "children": [
            {"dir": dirs[0], "range": [lo, mid], "n_genomes": int(len(left))},
            {"dir": dirs[1], "range": [mid, hi], "n_genomes": int(len(right))},
        ],
        "mid": mid,
    }
    return _run_range_txn(
        store, m, union, txn, {dirs[0]: left, dirs[1]: right}, processes
    )


def fed_merge(location: str, pid_a: int, pid_b: int, processes: int = 1) -> dict:
    """`index merge`: fold two ADJACENT partitions into one child whose
    range is their union — the split's inverse, through the same staged
    transaction (and the same ``partition_split`` fault site: one
    machinery, one chaos story)."""
    pids = sorted({int(pid_a), int(pid_b)})
    if len(pids) != 2:
        raise UserInputError("`index merge` needs two DISTINCT partition ids")
    rf = roll_forward(location)
    if (rf and rf.get("rolled") == "forward" and rf.get("op") == "merge"
            and set(pids) <= set(rf.get("parents", ()))):
        return {"op": "merge", "generation": int(rf["generation"]),
                "already_committed": True, "parents": pids}
    store = FederationStore(location)
    m = store.read_meta()
    _refuse_if_degraded(m, location, "merge")
    gen = int(m["generation"])
    if gen < 0:
        raise UserInputError(
            f"federated index at {location} is an empty skeleton — there "
            f"is nothing to merge yet"
        )
    if int(m["n_partitions"]) <= 2:
        raise UserInputError(
            "a federation keeps at least 2 partitions (a 1-partition "
            "federation is just a plain index) — merge refused"
        )
    by_pid = {int(e["pid"]): e for e in m["partitions"]}
    try:
        ea, eb = by_pid[pids[0]], by_pid[pids[1]]
    except KeyError as e:
        raise UserInputError(
            f"federated index at {location} has no partition {e} "
            f"(pids 0..{int(m['n_partitions']) - 1})"
        ) from e
    if int(ea["range"][1]) != int(eb["range"][0]):
        raise UserInputError(
            f"partitions {pids[0]} and {pids[1]} are not adjacent "
            f"(ranges {ea['range']} and {eb['range']}) — merge folds one "
            f"contiguous range"
        )
    union = load_federated(location, heal=False)
    rows_a = _member_rows(union, pids[0])
    rows_b = _member_rows(union, pids[1])
    rows = np.concatenate([rows_a, rows_b])
    (child_dir,) = _allocate_dirs(m, 1)
    txn = {
        "op": "merge",
        "gen_new": gen + 1,
        "parents": [
            {"pid": pids[0], "dir": str(ea["dir"])},
            {"pid": pids[1], "dir": str(eb["dir"])},
        ],
        "children": [
            {"dir": child_dir,
             "range": [int(ea["range"][0]), int(eb["range"][1])],
             "n_genomes": int(len(rows))}
        ],
    }
    return _run_range_txn(store, m, union, txn, {child_dir: rows}, processes)


# ---------------------------------------------------------------------------
# compaction
# ---------------------------------------------------------------------------


def _family_generations(pm: dict) -> int:
    return max(len(pm.get("sketch_shards", ())), len(pm.get("edge_shards", ())))


def _stage_compact(part_dir: str, processes: int = 1) -> tuple[dict, int]:
    """Write one partition's folded generation (shards only — the
    manifest publish is the per-store commit, deferred to the caller).
    Returns (manifest_doc, healed_count). Deterministic: a rerun
    rewrites the same names with the same bytes."""
    st = IndexStore(part_dir)
    idx = load_index(part_dir, heal=True)
    gen_new = idx.generation + 1
    sk_rel, ed_rel = st.sketch_shard_name(gen_new), st.edge_shard_name(gen_new)
    state_rel = st.state_name(gen_new)
    st.write_sketch_shard(
        sk_rel, idx.names, idx.locations, idx.gdb,
        idx.bottom, idx.scaled, idx.admitted,
    )
    st.write_edge_shard(ed_rel, *idx.edges)
    idx.generation = gen_new
    st.write_state(state_rel, idx)
    idx.sketch_shards = [{"file": sk_rel, "lo": 0, "hi": idx.n,
                          "generation": gen_new}]
    idx.edge_shards = [{"file": ed_rel, "lo": 0, "hi": idx.n,
                        "generation": gen_new}]
    return build_manifest(idx, state_rel), len(idx.healed)


def compact_store(location: str, processes: int = 1) -> dict:
    """Compact a PLAIN index store: fold its N shard generations into
    one at ``g+1``, publish, gc the superseded shards. The same folded
    payload discipline the federated path uses — per-genome admitted
    generations preserved, the edge set unchanged, classify/update
    byte-identical to the uncompacted twin (the oracle). Idempotent:
    an already-compact store just sweeps unreferenced leftovers."""
    from drep_tpu.utils import faults, telemetry

    st = IndexStore(location)
    pm = st.read_manifest()
    if _family_generations(pm) < 2:
        _gc_unreferenced(st.location)
        return {"op": "compact", "generation": int(pm["generation"]),
                "compacted": [], "skipped": ["single-generation store"]}
    manifest, healed = _stage_compact(st.location, processes=processes)
    faults.fire("compaction")  # kill point: STAGED
    faults.fire("compaction")  # kill point: PRE-COMMIT
    st.publish_manifest(manifest)
    telemetry.event(
        "index_maintenance", op="compact", generation=int(manifest["generation"]),
        n_genomes=int(manifest["n_genomes"]),
    )
    faults.fire("compaction")  # kill point: PRE-GC
    _gc_unreferenced(st.location)
    return {"op": "compact", "generation": int(manifest["generation"]),
            "compacted": [os.path.basename(st.location)],
            "healed": healed, "skipped": []}


def fed_compact(
    location: str, pid: int | None = None, processes: int = 1,
    min_generations: int = 2,
) -> dict:
    """`index compact` on a federated root: fold every target
    partition's shard families into one fresh generation, commit through
    partition-manifest publishes followed by ONE meta publish (new
    ``(generation, manifest_crc)`` per compacted partition — the union
    families are untouched because membership did not move), then gc.
    ``pid=None`` compacts every partition holding at least
    ``min_generations`` generations. The ``compaction`` fault site fires
    at each phase boundary (skip=0 staged, skip=1 pre-commit, skip=2
    pre-gc)."""
    from drep_tpu.utils import faults, telemetry

    if not fedmeta.is_federated(location):
        return compact_store(location, processes=processes)
    rf = roll_forward(location)
    store = FederationStore(location)
    m = store.read_meta()
    gen = int(m["generation"])
    if gen < 0:
        raise UserInputError(
            f"federated index at {location} is an empty skeleton — there "
            f"is nothing to compact yet"
        )
    targets: list[dict] = []
    skipped: list[str] = []
    for e in m["partitions"]:
        if pid is not None and int(e["pid"]) != int(pid):
            continue
        if int(e["n_genomes"]) <= 0:
            if pid is not None:
                raise UserInputError(
                    f"partition {pid} is empty — nothing to compact"
                )
            continue
        pdir = store.abspath(e["dir"])
        pm = IndexStore(pdir).read_manifest()
        need = 2 if pid is not None else max(2, int(min_generations))
        if _family_generations(pm) < need:
            skipped.append(str(e["dir"]))
            continue
        targets.append(dict(e))
    if pid is not None and not targets and not skipped:
        raise UserInputError(
            f"federated index at {location} has no partition {pid} "
            f"(pids 0..{int(m['n_partitions']) - 1})"
        )
    if not targets:
        return {"op": "compact", "generation": gen, "compacted": [],
                "skipped": skipped,
                "already_committed": bool(rf and rf.get("op") == "compact")}

    txn = {
        "op": "compact",
        "gen_new": gen + 1,
        "parents": [
            {"pid": int(e["pid"]), "dir": str(e["dir"]),
             "generation": int(e["generation"])}
            for e in targets
        ],
        "children": [],
    }
    _write_staging(location, txn)
    manifests: dict[str, dict] = {}
    healed = 0
    for e in targets:
        doc, h = _stage_compact(store.abspath(e["dir"]), processes=processes)
        manifests[str(e["dir"])] = doc
        healed += h
    faults.fire("compaction")  # kill point: STAGED
    # per-partition commits (each its own atomic manifest publish) —
    # a kill between any of them and the meta publish is the adoptable
    # ahead-by-one-unchanged-n state roll_forward converges
    for e in targets:
        IndexStore(store.abspath(e["dir"])).publish_manifest(
            manifests[str(e["dir"])]
        )
    entries = [dict(e) for e in m["partitions"]]
    target_pids = {int(e["pid"]) for e in targets}
    for e in entries:
        if int(e["pid"]) in target_pids:
            e["generation"] = int(e["generation"]) + 1
            e["manifest_crc"] = fedmeta.manifest_crc(store.abspath(e["dir"]))
    meta_new = dict(m)
    meta_new["partitions"] = entries
    meta_new["generation"] = gen + 1
    faults.fire("compaction")  # kill point: PRE-COMMIT
    store.publish_meta(meta_new)
    telemetry.event(
        "index_maintenance", op="compact", generation=gen + 1,
        parents=sorted(target_pids),
    )
    faults.fire("compaction")  # kill point: PRE-GC
    _gc_after_commit(store, txn)
    get_logger().info(
        "index compact: folded %d partition(s) %s -> federation "
        "generation %d (%d skipped already-compact)",
        len(targets), sorted(target_pids), gen + 1, len(skipped),
    )
    return {"op": "compact", "generation": gen + 1,
            "compacted": sorted(str(e["dir"]) for e in targets),
            "skipped": skipped, "healed": healed,
            "parents": sorted(target_pids)}


def _resume_compact(store: FederationStore, doc: dict) -> dict:
    """Roll an uncommitted compaction FORWARD: its per-partition
    manifest publishes may already be durable (they cannot be unwound —
    the superseded shard lists died with the old manifests), but the
    fold is deterministic, so finishing the transaction IS the
    convergent rerun. Partitions still at their old generation are
    re-staged and published; then the meta commit and gc complete."""
    gen_new = int(doc["gen_new"])
    m = store.read_meta()
    for p in doc.get("parents", ()):
        pdir = store.abspath(str(p["dir"]))
        if _partition_generation(pdir) <= int(p["generation"]):
            manifest, _healed = _stage_compact(pdir)
            IndexStore(pdir).publish_manifest(manifest)
    entries = [dict(e) for e in m["partitions"]]
    by_dir = {str(p["dir"]): p for p in doc.get("parents", ())}
    for e in entries:
        p = by_dir.get(str(e["dir"]))
        if p is not None:
            e["generation"] = int(p["generation"]) + 1
            e["manifest_crc"] = fedmeta.manifest_crc(store.abspath(e["dir"]))
    meta_new = dict(m)
    meta_new["partitions"] = entries
    meta_new["generation"] = gen_new
    store.publish_meta(meta_new)
    _gc_after_commit(store, doc)
    get_logger().info(
        "index maintenance: resumed interrupted compaction -> federation "
        "generation %d", gen_new,
    )
    return {"op": "compact", "rolled": "forward", "generation": gen_new,
            "parents": [int(p["pid"]) for p in doc.get("parents", ())]}


# ---------------------------------------------------------------------------
# maintenance scheduler inputs (the pure policy lives in autoscale/policy.py)
# ---------------------------------------------------------------------------


def maintenance_snapshot(location: str) -> dict:
    """Read-only scheduler input for ``autoscale.policy.maintenance_
    decide``: per-partition genome counts and shard-family generation
    counts, stamped with the monotonic clock (the same clock family the
    autoscale controller's history uses). Never writes."""
    out: dict = {"observed_at": time.monotonic(), "location": location}
    if not fedmeta.is_federated(location):
        out["error"] = "not a federated index"
        return out
    try:
        m = fedmeta.read_meta(location)
    except UserInputError as e:
        out["error"] = str(e)
        return out
    store = FederationStore(location)
    parts = []
    for e in m["partitions"]:
        entry = {"pid": int(e["pid"]), "n_genomes": int(e["n_genomes"]),
                 "generations": 0}
        if int(e["n_genomes"]) > 0:
            try:
                pm = IndexStore(store.abspath(e["dir"])).read_manifest()
                entry["generations"] = _family_generations(pm)
            except UserInputError:
                entry["generations"] = -1  # unreadable: scheduler holds
        parts.append(entry)
    out.update({
        "generation": int(m["generation"]),
        "n_partitions": int(m["n_partitions"]),
        "maintenance_pending": os.path.exists(maint_path(location)),
        "partitions": parts,
    })
    return out


def maintenance_targets_from_env():
    """The operator's maintenance envelope, resolved ONCE from the knob
    registry (the pure policy never reads env): compaction proposed past
    ``DREP_TPU_COMPACT_MIN_SHARDS`` generations, split past
    ``DREP_TPU_SPLIT_MAX_GENOMES`` genomes (0 = never)."""
    from drep_tpu.autoscale.policy import MaintenanceTargets
    from drep_tpu.utils import envknobs

    return MaintenanceTargets(
        compact_min_shards=envknobs.env_int("DREP_TPU_COMPACT_MIN_SHARDS"),
        split_max_genomes=envknobs.env_int("DREP_TPU_SPLIT_MAX_GENOMES"),
    )
