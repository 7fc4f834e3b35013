#!/usr/bin/env python
"""Checkpoint-store scrubber: walk a store, verify every payload, report
(and optionally delete) damage. Exit status 1 when anything is damaged.

The shard stores ARE the durable contract between pipeline stages (the
rebuild's Mdb/Ndb/Cdb-equivalent), and they live on shared filesystems
where bytes rot after the atomic rename. Every payload carries an in-band
checksum (utils/durableio.py: a ``__crc__`` npz member, a ``"crc"`` JSON
key); this tool is the offline verifier — run it against a workdir (or any
single store) before trusting a resume, or from cron against a long-lived
checkpoint tree::

    python tools/scrub_store.py <wd>/data                 # report damage
    python tools/scrub_store.py <wd>/data --delete        # + remove bad shards
    python tools/scrub_store.py ckpt_dir another_dir ...  # multiple roots

Verified payload families (everything else is left alone):

- ``*.npz`` shards — streaming row stripes (``row_*.npz``), dense-ring
  blocks (``blk_*.npz``), secondary per-cluster results (``pc_*.npz``),
  ingest sketch shards, workdir arrays, and every genome-index family
  (``sketch_g*.npz``, ``edges_g*.npz``, ``state_g*.npz`` — sketches,
  edge graph, labels/winner table; drep_tpu/index/store.py — a shard's
  heads and its ``sketch_g*.<member>.NNNN.npz`` part files alike, each a
  checked payload of its own). Zero-byte,
  truncated, unparseable, or checksum-mismatched shards are DAMAGE.
- ``meta.json``, the genome-index ``manifest.json``, the FEDERATED
  index's ``federation.json`` meta-manifest (drep_tpu/index/meta.py),
  and the pod protocol's JSON notes (``.pod-done.*``, ``.pod-dead.*``,
  and the elastic membership family ``.pod-drain.*`` / ``.pod-join.*`` /
  ``.pod-admit.*``) — unparseable or checksum-mismatched is DAMAGE,
  never an orphan.
- a federated index root recurses into its ``part_NNN/`` partition
  stores (each an ordinary index store) plus the federation families
  (``cross_g*.npz`` cross-partition edges, ``fedstate_g*.npz`` union
  state); damage under a partition is reported WITH the partition id,
  so an `index update` heal pass can be pointed at the right store.
- index-maintenance lifecycle leftovers (drep_tpu/index/maintenance.py)
  report as their own NON-damage classes, like torn tails: ``STAGED``
  (a federated root's ``pending/`` transaction record + child stores —
  an in-flight or interrupted split/merge/compact) and ``SUPERSEDED``
  (payloads a committed transaction no longer references but has not
  yet gc'd: old parent partition stores, unreferenced cross/fedstate/
  routing files, a compacted store's pre-fold shard generations).
  ``--delete`` removes them, pre-empting the convergence the next
  maintenance pass would perform anyway.
- ``events.p*.jsonl`` telemetry logs (utils/telemetry.py) — every
  complete line must parse as JSON (mid-file rot is DAMAGE); a torn
  FINAL line is a killed writer's expected crash evidence, reported as
  its own non-damage class (like orphaned ``.tmp-``). ``metrics.prom``
  (the Prometheus textfile flush) and ``events.runid`` are known
  plain-text families, deliberately skipped.

For a genome index, a damaged shard removed by ``--delete`` is healed by
the next ``drep-tpu index update`` (sketch shards re-sketch from the
recorded FASTA locations, edge shards recompute their column range,
state recomputes wholesale); only ``manifest.json`` is unhealable.

Payloads written before checksums existed verify structurally (a full
decode catches truncation) and are counted ``legacy`` — readable, but
carrying no checksum to prove rot hasn't touched them.

``--delete`` removes each damaged payload so the NEXT resume treats it as
missing and recomputes it — the self-heal path the stores already
implement (parallel/streaming.py, parallel/allpairs.py,
cluster/secondary_ckpt.py); deleting a damaged ``meta.json`` invalidates
the store wholesale (open clears + recomputes). CPU-only, no JAX backend
required.
"""

from __future__ import annotations

import argparse
import json
import os
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

from drep_tpu.utils import durableio  # noqa: E402
from drep_tpu.workdir import head_of  # noqa: E402

import re  # noqa: E402

# the telemetry log family (ISSUE 10, utils/telemetry.py): line-wise JSON,
# crash-safe by construction — a torn FINAL line is expected SIGKILL
# evidence (its own non-damage class, like orphaned .tmp-), a torn
# MID-FILE line is damage
_EVENTS_RE = re.compile(r"^events\.p\d+\.jsonl$")

# federated index partition dirs (drep_tpu/index/federation.py): damage
# under one is reported with the partition id so heal passes target the
# right store
_PARTITION_RE = re.compile(r"(?:^|[\\/])(part_\d{3})[\\/]")

_PART_DIR_RE = re.compile(r"^part_\d{3}$")


def _maintenance_map(root: str) -> dict[str, str]:
    """Classify index-maintenance leftovers (ISSUE 18) under `root`:
    path -> "staged" (artifacts of an in-flight/interrupted split/merge/
    compact transaction under a federated root's ``pending/``) or
    "superseded" (payloads a COMMITTED maintenance transaction no longer
    references but has not yet gc'd: old parent partition stores,
    unreferenced cross/fedstate/routing family files, and a compacted
    store's pre-fold shard generations). Both are expected lifecycle
    states, NOT damage — the next maintenance pass (`index split|merge|
    compact`, or any federated `index update`) converges them; --delete
    just gets there first. Reads metas UNVERIFIED (a rotted meta still
    reports as damage through the ordinary walk — this pre-pass only
    decides which intact files are maintenance leftovers)."""
    out: dict[str, str] = {}

    def _tag_tree(top: str, cls: str) -> None:
        for dp, _dd, ff in os.walk(top):
            for f in ff:
                out[os.path.join(dp, f)] = cls

    for dirpath, dirs, files in os.walk(root):
        if "federation.json" in files:
            try:
                with open(os.path.join(dirpath, "federation.json"), "rb") as f:
                    meta = json.load(f)
                entries = list(meta.get("partitions", ()))
            except (OSError, ValueError):
                continue
            _tag_tree(os.path.join(dirpath, "pending"), "staged")
            live_dirs = {str(e.get("dir")) for e in entries}
            for d in dirs:
                if _PART_DIR_RE.match(d) and d not in live_dirs:
                    _tag_tree(os.path.join(dirpath, d), "superseded")
            keep = {
                os.path.basename(str(e.get("file")))
                for e in meta.get("cross_shards", ())
            }
            for sub, prefix, keep_set in (
                ("cross", "cross_g", keep),
                ("state", "fedstate_g",
                 {os.path.basename(str(meta.get("state") or ""))}),
                ("routing", "summary_g",
                 {os.path.basename(str(meta.get("routing") or ""))}),
            ):
                fam = os.path.join(dirpath, sub)
                if not os.path.isdir(fam):
                    continue
                for f in os.listdir(fam):
                    if (f.startswith(prefix) and f.endswith(".npz")
                            and f not in keep_set):
                        out[os.path.join(fam, f)] = "superseded"
        elif "manifest.json" in files:
            # an index store (plain, or one federated partition): shard
            # generations the CURRENT manifest no longer references are
            # a compaction's not-yet-gc'd leftovers
            try:
                with open(os.path.join(dirpath, "manifest.json"), "rb") as f:
                    pm = json.load(f)
            except (OSError, ValueError):
                continue
            keep = {
                os.path.basename(str(e.get("file")))
                for fam in ("sketch_shards", "edge_shards")
                for e in pm.get(fam, ())
            }
            keep.add(os.path.basename(str(pm.get("state") or "")))
            for sub, prefix in (
                ("sketches", "sketch_g"), ("edges", "edges_g"),
                ("state", "state_g"),
            ):
                fam_dir = os.path.join(dirpath, sub)
                if not os.path.isdir(fam_dir):
                    continue
                # a shard's part files (index/store.py) are kept or
                # superseded with their head
                for f in os.listdir(fam_dir):
                    if (f.startswith(prefix) and f.endswith(".npz")
                            and head_of(f) not in keep):
                        out[os.path.join(fam_dir, f)] = "superseded"
    return out


_FLEET_GEN_RE = re.compile(r"^fleet\.g(\d+)\.json$")


def _is_json_note(name: str) -> bool:
    # every checked-JSON family the pipeline publishes: store meta, the
    # pod protocol's membership notes (done/death verdicts, plus the
    # ISSUE-9 drain departures and join request/admit pairs), workdir
    # argument snapshots, ingest poison markers, the genome-index
    # manifest (drep_tpu/index/store.py), and the fleet supervisor's
    # membership manifest + generation snapshots (serve/supervisor.py)
    # — all carry the in-band "crc"
    return (
        name in ("meta.json", "manifest.json", "federation.json",
                 "fleet.json")
        or _FLEET_GEN_RE.match(name) is not None
        or name.startswith(
            (
                ".pod-done.", ".pod-dead.", ".pod-drain.", ".pod-join.",
                ".pod-admit.", "ingest_error_",
            )
        )
        or name.endswith("_arguments.json")
    )


def _membership_map(root: str) -> tuple[dict[str, str], dict[str, list[str]]]:
    """Classify fleet-supervisor leftovers (ISSUE 20) under `root`:
    returns ``(stale_paths, compactions)`` where `stale_paths` maps a
    ``fleet.gNNNNNN.json`` generation snapshot the supervisor's own gc
    would have removed — one OLDER than the KEEP_GENERATIONS newest the
    supervisor deliberately retains — to ``"stale_gen"`` (a crashed
    supervisor's not-yet-gc'd history), and `compactions` maps a
    ``fleet.json`` path to the slot ids whose recorded pid is DEAD
    while the recorded supervisor is dead too (nobody owns the slot; a
    successor supervisor would reap it at recovery — --delete compacts
    it first). Expected lifecycle states, NOT damage. QUARANTINED
    slots are never listed: their durable reason is the contract. A
    live supervisor's fleet_dir is left entirely alone — both the
    manifest and its retained snapshots have an owner racing us."""
    stale: dict[str, str] = {}
    compact: dict[str, list[str]] = {}
    from drep_tpu.serve.supervisor import KEEP_GENERATIONS, pid_alive

    for dirpath, _dirs, files in os.walk(root):
        if "fleet.json" not in files:
            continue
        man_path = os.path.join(dirpath, "fleet.json")
        try:
            doc = durableio.read_json_checked(man_path, what="fleet manifest")
        except (OSError, durableio.CorruptPayloadError):
            continue  # the ordinary walk classifies the rot
        if not isinstance(doc, dict):
            continue
        if pid_alive(doc.get("supervisor_pid")):
            continue
        # gens >= cur - (KEEP_GENERATIONS - 1) are the retained window
        # the supervisor's gc itself keeps — never stale
        cutoff = int(doc.get("generation") or 0) - (KEEP_GENERATIONS - 1)
        for name in files:
            m = _FLEET_GEN_RE.match(name)
            if m and int(m.group(1)) < cutoff:
                stale[os.path.join(dirpath, name)] = "stale_gen"
        dead_slots = [
            sid for sid, slot in (doc.get("slots") or {}).items()
            if isinstance(slot, dict)
            and slot.get("state") in ("healthy", "starting", "draining")
            and not pid_alive(slot.get("pid"))
        ]
        if dead_slots:
            compact[man_path] = sorted(dead_slots)
    return stale, compact


def scrub(roots: list[str], delete: bool = False, out=sys.stdout) -> dict:
    """Walk `roots`; returns {"verified": n, "legacy": n, "damaged": [...]}.
    With `delete`, damaged payloads are removed (the next resume recomputes
    them). Checksum verification is forced ON for the walk even when the
    hot-path escape hatch (DREP_TPU_IO_CRC=0) is exported — a scrub that
    silently skipped the compare while printing "checksum-verified" would
    be worse than no scrub — and the caller's setting is restored after
    (scrub() runs in-process from tools/chaos_matrix.py and tests)."""
    saved_crc = os.environ.get(durableio.CRC_ENV)
    os.environ[durableio.CRC_ENV] = "1"
    try:
        return _scrub(roots, delete=delete, out=out)
    finally:
        if saved_crc is None:
            os.environ.pop(durableio.CRC_ENV, None)
        else:
            os.environ[durableio.CRC_ENV] = saved_crc


def _scrub(roots: list[str], delete: bool, out) -> dict:
    verified = legacy = 0
    damaged: list[tuple[str, str]] = []
    artifacts: list[str] = []
    torn_tails: list[str] = []
    staged: list[str] = []
    superseded: list[str] = []
    stale_membership: list[str] = []
    maint_map: dict[str, str] = {}
    member_map: dict[str, str] = {}
    compactions: dict[str, list[str]] = {}
    for root in roots:
        if os.path.isdir(root):
            maint_map.update(_maintenance_map(root))
            m_stale, m_compact = _membership_map(root)
            member_map.update(m_stale)
            compactions.update(m_compact)

    def check_events(path: str) -> None:
        """Line-wise validation of a telemetry event log: every COMPLETE
        line must parse as JSON (mid-file rot is damage); a torn final
        line — no trailing newline — is the expected crash evidence a
        SIGKILLed writer leaves, counted in its own class."""
        nonlocal verified
        with open(path, "rb") as f:
            raw = f.read()
        body, _, tail = raw.rpartition(b"\n")
        for i, line in enumerate(body.split(b"\n") if body else []):
            if not line.strip():
                continue
            try:
                json.loads(line.decode())
            except (ValueError, UnicodeDecodeError):
                raise durableio.CorruptPayloadError(
                    f"unparseable event line {i + 1}"
                ) from None
        if tail.strip():
            torn_tails.append(path)
        verified += 1

    def check(path: str, name: str) -> None:
        nonlocal verified, legacy
        cls = maint_map.get(path)
        if cls is not None:
            # maintenance lifecycle leftovers (ISSUE 18): staged txn
            # artifacts / committed-but-not-yet-gc'd payloads — expected
            # states the next maintenance pass converges, NOT damage
            (staged if cls == "staged" else superseded).append(path)
            return
        if path in member_map:
            # fleet-supervisor lifecycle leftovers (ISSUE 20): a
            # generation snapshot an interrupted publish never gc'd —
            # expected crash history, NOT damage
            stale_membership.append(path)
            return
        if ".tmp-" in name:
            # an orphaned atomic-write tmp (SIGKILL mid-publish — the
            # cleanup `finally` never ran): garbage no reader ever
            # consults, NOT store damage. Reported separately and never
            # affecting exit status — a crash artifact crying "DAMAGED"
            # forever would train operators to ignore the scrubber.
            artifacts.append(path)
            return
        if name == "metrics.prom" or name == "events.runid":
            # Prometheus textfile (atomic publish, plain text — no
            # checksum contract) and the run-id marker: known families,
            # deliberately skipped
            return
        try:
            if _EVENTS_RE.match(name):
                check_events(path)
                return
            if name.endswith(".npz"):
                if os.path.getsize(path) == 0:
                    raise durableio.CorruptPayloadError("zero-byte shard")
                # one read: the unverified decode still carries __crc__
                # (classifies legacy payloads), then verify in place
                loaded = durableio.read_npz_unverified(path, what="shard")
                has_crc = durableio.CRC_KEY in loaded
                durableio.verify_npz_payload(loaded, path, "shard")  # raises on damage
            elif _is_json_note(name):
                body = durableio.read_json_unverified(path, what="note")
                has_crc = isinstance(body, dict) and durableio.JSON_CRC_KEY in body
                durableio.verify_json_payload(body, path, "note")  # raises on damage
            else:
                return
        except durableio.CorruptPayloadError as e:
            damaged.append((path, str(e)))
            return
        except OSError as e:
            damaged.append((path, f"unreadable: {e}"))
            return
        if has_crc:
            verified += 1
        else:
            legacy += 1

    for root in roots:
        if os.path.isfile(root):
            check(root, os.path.basename(root))
            continue
        for dirpath, _dirs, files in os.walk(root):
            for name in sorted(files):
                check(os.path.join(dirpath, name), name)

    by_partition: dict[str, int] = {}
    for path, reason in damaged:
        action = ""
        if delete:
            try:
                # drep-lint: allow[reader-purity] — --delete repair mode: operator-requested removal of VERIFIED-damaged payloads; the default scan never reaches here
                os.remove(path)
                action = " [deleted — next resume recomputes it]"
            except OSError as e:
                action = f" [delete failed: {e}]"
        # federated stores: name the partition so `index update` heal
        # passes (and operators) target the right store
        m = _PARTITION_RE.search(path)
        part = f" [partition {m.group(1)}]" if m else ""
        if m:
            by_partition[m.group(1)] = by_partition.get(m.group(1), 0) + 1
        print(f"DAMAGED {part} {path}: {reason}{action}" if part
              else f"DAMAGED  {path}: {reason}{action}", file=out)
    for path in artifacts:
        action = ""
        if delete:
            try:
                # drep-lint: allow[reader-purity] — --delete repair mode: crash-orphaned tmp artifacts, same operator gate as above
                os.remove(path)
                action = " [deleted]"
            except OSError as e:
                action = f" [delete failed: {e}]"
        print(f"ARTIFACT {path}: orphaned atomic-write tmp (crash leftover, "
              f"never read by resume){action}", file=out)
    for path in torn_tails:
        print(f"TORN-TAIL {path}: event log ends mid-line (expected crash "
              f"evidence from a killed writer, not damage)", file=out)
    for path in staged:
        action = ""
        if delete:
            try:
                # drep-lint: allow[reader-purity] — --delete repair mode: staged maintenance-transaction artifacts; removing them just pre-empts the rollback/roll-forward the next maintenance pass performs
                os.remove(path)
                action = " [deleted — next maintenance pass restages]"
            except OSError as e:
                action = f" [delete failed: {e}]"
        print(f"STAGED {path}: in-flight index-maintenance staging "
              f"(pending split/merge/compact transaction — converged or "
              f"discarded by the next maintenance pass, not damage)"
              f"{action}", file=out)
    for path in superseded:
        action = ""
        if delete:
            try:
                # drep-lint: allow[reader-purity] — --delete repair mode: payloads a COMMITTED maintenance transaction superseded; the next maintenance pass gc's them identically
                os.remove(path)
                action = " [deleted — completes the interrupted gc]"
            except OSError as e:
                action = f" [delete failed: {e}]"
        print(f"SUPERSEDED {path}: superseded by a committed index-"
              f"maintenance transaction, gc pending (the next maintenance "
              f"pass removes it, not damage){action}", file=out)
    for path in stale_membership:
        action = ""
        if delete:
            try:
                # drep-lint: allow[reader-purity] — --delete repair mode: stale fleet-manifest generation snapshots the supervisor's own gc would remove identically
                os.remove(path)
                action = " [deleted — completes the supervisor's gc]"
            except OSError as e:
                action = f" [delete failed: {e}]"
        print(f"STALE-MEMBERSHIP {path}: superseded fleet-manifest "
              f"generation (crash leftover of an interrupted supervisor "
              f"publish, not damage){action}", file=out)
    for man_path, dead_slots in sorted(compactions.items()):
        action = ""
        if delete:
            try:
                doc = durableio.read_json_checked(
                    man_path, what="fleet manifest"
                )
                for sid in dead_slots:
                    doc.get("slots", {}).pop(sid, None)
                # drep-lint: allow[reader-purity] — --delete repair mode: compacting dead-pid slots out of an UNOWNED manifest (recorded supervisor dead); a successor supervisor would reap them identically at recovery
                durableio.atomic_write_json(man_path, doc)
                action = " [compacted out]"
            except (OSError, durableio.CorruptPayloadError) as e:
                action = f" [compaction failed: {e}]"
        print(f"STALE-MEMBERSHIP {man_path}: dead-pid slot(s) "
              f"{','.join(dead_slots)} with no live supervisor (a "
              f"successor would reap them at recovery, not damage)"
              f"{action}", file=out)
        stale_membership.append(man_path)
    if by_partition:
        print(
            "scrub: federated damage by partition: "
            + ", ".join(f"{p}={c}" for p, c in sorted(by_partition.items())),
            file=out,
        )
    print(
        f"scrub: {verified} payload(s) checksum-verified, {legacy} legacy "
        f"(readable, no in-band checksum), {len(damaged)} damaged"
        + (" (deleted)" if delete and damaged else "")
        + (f", {len(artifacts)} crash artifact(s)" if artifacts else "")
        + (f", {len(torn_tails)} torn event-log tail(s)" if torn_tails else "")
        + (f", {len(staged)} staged maintenance artifact(s)" if staged else "")
        + (f", {len(superseded)} superseded (gc-pending) payload(s)"
           if superseded else "")
        + (f", {len(stale_membership)} stale membership entr(ies)"
           if stale_membership else ""),
        file=out,
    )
    return {"verified": verified, "legacy": legacy, "damaged": damaged,
            "artifacts": artifacts, "torn_tails": torn_tails,
            "staged": staged, "superseded": superseded,
            "stale_membership": stale_membership,
            "by_partition": by_partition}


# severity-ordered damage classes for a partition-scoped scrub: the
# manifest is unhealable, state/sketch/edges heal through the store's
# own matrix (state recluster, re-sketch, range recompute)
_DAMAGE_CLASSES = (
    ("manifest", lambda n: n == "manifest.json"),
    ("state", lambda n: n.startswith("state_g")),
    ("sketch", lambda n: n.startswith("sketch_g")),
    ("edges", lambda n: n.startswith("edges_g")),
    ("other", lambda n: True),
)


def damage_class(damaged: list[tuple[str, str]]) -> str:
    """The worst damage family among the damaged paths — "clean" when
    empty. The one-word verdict a serve daemon's heal hint (or an
    orchestrator) consumes from the partition-scoped probe."""
    names = {os.path.basename(p) for p, _ in damaged}
    for cls, match in _DAMAGE_CLASSES:
        if any(match(n) for n in names):
            return cls
    return "clean"


def scrub_partition(root: str, pid: int, delete: bool = False, out=sys.stdout) -> dict:
    """`--partition <pid>` (ISSUE 14 satellite): scope a federated scrub
    to ONE partition store — the cheap, targeted probe a serve daemon's
    quarantine heal hint shells to. The report gains ``damage_class``
    (manifest > state > sketch > edges > other severity order; "clean"
    when undamaged) so callers branch on one word."""
    if not os.path.exists(os.path.join(root, "federation.json")):
        print(f"scrub: {root} is not a federated index root (no "
              f"federation.json) — --partition needs one", file=out)
        return {"error": "not federated", "damaged": [], "damage_class": "clean"}
    # resolve the partition's RECORDED dir from the meta (the same field
    # the unscoped federated walk honors); a rotted meta falls back to
    # the default naming so the scoped scrub still reaches the store
    part_dirname = f"part_{pid:03d}"
    try:
        meta = durableio.read_json_checked(
            os.path.join(root, "federation.json"), what="federation meta"
        )
        entry = next(
            (e for e in meta.get("partitions", ())
             if int(e.get("pid", -1)) == pid),
            None,
        )
        if entry is not None and entry.get("dir"):
            part_dirname = str(entry["dir"])
    except (OSError, ValueError, durableio.CorruptPayloadError):
        pass
    pdir = os.path.join(root, part_dirname)
    if not os.path.isdir(pdir):
        print(f"scrub: no partition {pid} under {root} ({pdir} missing)", file=out)
        return {"error": "no such partition",
                "damaged": [(pdir, "partition directory missing")],
                "damage_class": "other"}
    report = scrub([pdir], delete=delete, out=out)
    report["damage_class"] = damage_class(report["damaged"])
    print(f"scrub: partition part_{pid:03d} damage class: "
          f"{report['damage_class']}", file=out)
    return report


def main(argv: list[str] | None = None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("roots", nargs="+", help="store directories (or files) to scrub")
    ap.add_argument(
        "--delete", action="store_true",
        help="remove damaged payloads so the next resume recomputes them",
    )
    ap.add_argument(
        "--partition", type=int, default=None, metavar="PID",
        help="scope a FEDERATED-index scrub to one partition store "
             "(part_PID under the single given root) and report its "
             "damage class — the serve daemon's quarantine heal hint "
             "names this probe",
    )
    args = ap.parse_args(argv)
    if args.partition is not None:
        if len(args.roots) != 1:
            ap.error("--partition takes exactly one federated root")
        report = scrub_partition(
            args.roots[0], args.partition, delete=args.delete
        )
        # a probe that could not even run (wrong root, no such partition)
        # must NOT exit 0 — automation branching on the exit code would
        # read "clean" and skip the heal the quarantine is waiting for
        return 1 if (report["damaged"] or report.get("error")) else 0
    report = scrub(args.roots, delete=args.delete)
    return 1 if report["damaged"] else 0


if __name__ == "__main__":
    sys.exit(main())
