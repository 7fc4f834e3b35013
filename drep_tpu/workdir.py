"""Work-directory persistence: the checkpoint/resume substrate.

Reference parity: drep/WorkDirectory.py (SURVEY.md §2, L1; reference mount
empty — contract reconstructed from upstream layout). The work directory IS
the checkpoint system: every pipeline stage persists its DataFrame to
``data_tables/*.csv`` immediately, stage arguments are snapshotted to
``log/*_arguments.json``, and a rerun with matching arguments loads the
stored tables instead of recomputing (SURVEY.md §5.4, §3.5).

TPU-native addition: ``store_array``/``get_array`` persist packed sketch
tensors (``.npz``) under ``data/arrays/`` so the expensive host-ingest stage
(FASTA -> k-mer hashes -> sketches) is resumable independently of the device
compute, and sharded tile results can be checkpointed per-shard.
"""

from __future__ import annotations

import contextlib
import glob
import json
import os
import re
from typing import Any

import numpy as np
import pandas as pd

from drep_tpu.utils.logger import get_logger

_SUBDIRS = ["data", "data_tables", "figures", "log", "dereplicated_genomes", os.path.join("data", "arrays")]

# snapshot keys added after the first release, with the value every older
# workdir implicitly used. A stored snapshot missing one of these keys must
# compare EQUAL to the key's historical default — otherwise upgrading the
# tool would invalidate every existing cache/resume for no numeric reason.
LEGACY_SNAPSHOT_DEFAULTS: dict[str, Any] = {
    "hash": "splitmix64",
}


# No single file of the array store grows past this. Machines cap file size
# (RLIMIT_FSIZE, an object store's part limit), and the sketch cache is the
# one GB-scale payload: ~200 KB per genome at production width.
ARRAY_PART_BYTES = 16 << 20
_PARTS_PREFIX = "__parts__"


def _atomic_write(loc: str, write_fn) -> None:
    """Whole-file-or-nothing table/array/args writes: (a) a kill mid-write
    must not leave a torn table that a later RESUME trusts (the workdir IS
    the checkpoint system); (b) on a shared-filesystem workdir every
    process of a multi-host run stores the same replicated tables —
    concurrent identical writes must coexist. One shared primitive
    (utils/ckptmeta.py::atomic_write); keep_suffix=True because
    np.savez_compressed derives its output name from the ``.npz`` suffix,
    and nothing globs the workdir's table/array suffixes."""
    from drep_tpu.utils.ckptmeta import atomic_write

    atomic_write(loc, write_fn, keep_suffix=True)


def part_loc(head_loc: str, key: str, i: int) -> str:
    """Part `i` of the member `key` of the payload whose head is `head_loc`
    (``<name>.npz`` -> ``<name>.<key>.NNNN.npz``, beside it)."""
    return f"{head_loc[: -len('.npz')]}.{key}.{i:04d}.npz"


def part_locs(head_loc: str) -> list[str]:
    """Every part file on disk beside `head_loc`, whichever save wrote it."""
    return glob.glob(f"{glob.escape(head_loc[: -len('.npz')])}.*.{'[0-9]' * 4}.npz")


_PART_NAME = re.compile(r"^(?P<head>.+)\.[A-Za-z_]\w*\.\d{4}\.npz$")


def head_of(filename: str) -> str:
    """The head file a part file belongs to; any other name is its own head.
    What lists a store's payloads by name (gc, the scrubber's superseded
    class) keeps or drops a part with its head."""
    m = _PART_NAME.match(filename)
    return m.group("head") + ".npz" if m else filename


def store_parted(head_loc: str, arrays: dict[str, np.ndarray], publish, part_bytes: int) -> dict[str, int]:
    """Publish `arrays` as the payload `head_loc` with no file past
    `part_bytes`: an array larger than that, or that would take the head past
    it, is cut along its first axis into
    parts (``part_loc``; each ``publish(loc, {"part": rows})``, a checked
    payload of its own) and the head records the parts' lengths under
    ``__parts__<key>``. The head is the commit point, removed first and
    published last, so a kill mid-save leaves an absent payload, never a head
    over another save's parts. Returns what was written: `files`, `parts`,
    `bytes` (on disk)."""
    for loc in (head_loc, *part_locs(head_loc)):  # the head first
        with contextlib.suppress(FileNotFoundError):
            os.remove(loc)
    head: dict[str, np.ndarray] = {}
    wrote = {"files": 0, "parts": 0, "bytes": 0}

    def put(loc: str, payload: dict) -> None:
        publish(loc, payload)
        wrote["files"] += 1
        wrote["bytes"] += os.path.getsize(loc)

    in_head = 0
    for key, arr in arrays.items():
        arr = np.asarray(arr)
        # the head holds what fits it TOGETHER: a member that would take the
        # head past the bound goes to parts like one that is past it alone
        if arr.ndim == 0 or len(arr) < 2 or in_head + arr.nbytes <= part_bytes:
            head[key] = arr
            in_head += arr.nbytes
            continue
        rows = max(1, part_bytes // (arr.nbytes // len(arr)))
        lengths = []
        for i, lo in enumerate(range(0, len(arr), rows)):
            part = arr[lo : lo + rows]
            put(part_loc(head_loc, key, i), {"part": part})
            lengths.append(len(part))
        wrote["parts"] += len(lengths)
        head[_PARTS_PREFIX + key] = np.asarray(lengths, dtype=np.int64)
    put(head_loc, head)
    return wrote


def fill_parted(head_loc: str, out: dict[str, np.ndarray], what: str, remedy: str,
                workers: int = 1) -> dict[str, int]:
    """Replace every ``__parts__<key>`` entry of the decoded head `out`
    (``load_npz_checked(head_loc)``) by its member, read part by part into
    ONE array allocated for the whole of it. A head with no such entry (a
    one-file payload) is left as it is. Returns the account of the read:
    `members` read from parts, their `parts`, `direct_parts`,
    `fallback_parts`, `bytes`, the `threads` of the widest member. A part
    that is missing, torn or of another shape than its head says raises
    `CorruptPayloadError` naming the part, `what` and the `remedy`."""
    read = {"members": 0, "parts": 0, "direct_parts": 0, "fallback_parts": 0, "bytes": 0, "threads": 0}
    for pkey in [k for k in out if k.startswith(_PARTS_PREFIX)]:
        lengths = out.pop(pkey).tolist()
        key = pkey[len(_PARTS_PREFIX) :]
        out[key], direct, threads = _read_parts(head_loc, key, lengths, what, remedy, workers)
        read["members"] += 1
        read["parts"] += len(lengths)
        read["direct_parts"] += direct
        read["fallback_parts"] += len(lengths) - direct
        read["bytes"] += out[key].nbytes
        read["threads"] = max(read["threads"], threads)
    return read


def _read_parts(head_loc: str, key: str, lengths: list[int], what: str, remedy: str,
                workers: int) -> tuple[np.ndarray, int, int]:
    """The member `key` out of its parts: (the array, the parts read in
    place, the threads that read). The first part's header says what to
    allocate; every part is then read into its own rows."""
    from concurrent.futures import ThreadPoolExecutor

    from drep_tpu.utils.durableio import (
        CorruptPayloadError, PayloadShapeError, load_npz_checked, load_npz_member_into, npz_member_header,
    )
    from drep_tpu.utils.hosttools import usable_cores

    part_what = f"{what} part"

    def missing(loc: str, e: Exception) -> CorruptPayloadError:
        return CorruptPayloadError(f"{what}: part {loc} is missing ({e!r}) — {remedy}")

    first = part_loc(head_loc, key, 0)
    header = npz_member_header(first, "part")
    if header is None:  # unreadable so: the checked reader says why, or reads it
        try:
            part = load_npz_checked(first, what=part_what)["part"]
        except (FileNotFoundError, KeyError) as e:
            raise missing(first, e) from e
        header = part.dtype, part.shape
    dtype, shape = header
    starts = np.concatenate([[0], np.cumsum(lengths, dtype=np.int64)]).tolist()
    whole = np.empty((starts[-1], *shape[1:]), dtype=dtype)  # one allocation, filled part by part

    def read(i: int) -> bool:
        loc = part_loc(head_loc, key, i)
        try:
            return load_npz_member_into(loc, "part", whole[starts[i] : starts[i + 1]], what=part_what)
        except (FileNotFoundError, KeyError) as e:
            raise missing(loc, e) from e
        except PayloadShapeError as e:
            if e.shape[:1] != (lengths[i],):
                raise CorruptPayloadError(
                    f"{what}: part {loc} holds {e.shape[0] if e.shape else 0} rows, "
                    f"its head says {lengths[i]} — {remedy}"
                ) from e
            raise CorruptPayloadError(
                f"{what}: part {loc} holds {e.dtype}{list(e.shape[1:])} rows, "
                f"the first part {dtype}{list(shape[1:])} — {remedy}"
            ) from e

    threads = max(1, min(int(workers), usable_cores(), len(lengths)))
    if threads == 1:
        direct = sum(read(i) for i in range(len(lengths)))
    else:
        with ThreadPoolExecutor(max_workers=threads) as pool:
            # the first error is raised here; `map` cancels what has not begun
            direct = sum(pool.map(read, range(len(lengths))))
    return whole, direct, threads


def _json_default(o: Any):
    if isinstance(o, (np.integer,)):
        return int(o)
    if isinstance(o, (np.floating,)):
        return float(o)
    if isinstance(o, np.ndarray):
        return o.tolist()
    raise TypeError(f"not JSON serializable: {type(o)}")


class WorkDirectory:
    """Filesystem-backed store for pipeline tables, arrays, and arguments."""

    def __init__(self, location: str):
        self.location = os.path.abspath(location)
        for sub in _SUBDIRS:
            os.makedirs(os.path.join(self.location, sub), exist_ok=True)
        # what :meth:`hold` keeps of tables this process stored, by table:
        # (columns, the file they were stored as)
        self._held: dict[str, tuple[Any, tuple]] = {}

    # ---- directories -----------------------------------------------------
    def get_dir(self, name: str) -> str:
        path = os.path.join(self.location, name)
        os.makedirs(path, exist_ok=True)
        return path

    # ---- DataFrame tables ------------------------------------------------
    def _table_loc(self, name: str) -> str:
        return os.path.join(self.location, "data_tables", f"{name}.csv")

    def store_db(self, df: pd.DataFrame, name: str) -> int:
        """Publish `df` as ``data_tables/<name>.csv``: the bytes of
        ``df.to_csv(index=False)``, written column by column
        (drep_tpu/tablewriter.py). What the write took is booked in the
        record's ``tables_write``; returns the file's bytes."""
        from drep_tpu.tablewriter import write_csv
        from drep_tpu.utils.profiling import counters

        loc = self._table_loc(name)
        done: dict = {}  # of the attempt that was published: a transient I/O error re-runs the write
        _atomic_write(loc, lambda tmp: done.update(write_csv(df, tmp)))
        counters.add_table_write(name, **done)
        get_logger().debug("stored table %s (%d rows) -> %s", name, len(df), loc)
        return done["bytes"]

    def get_db(self, name: str) -> pd.DataFrame:
        loc = self._table_loc(name)
        if not os.path.exists(loc):
            raise FileNotFoundError(f"table {name} not present in workdir {self.location}")
        return pd.read_csv(loc)

    def hasDb(self, name: str) -> bool:  # noqa: N802 — reference-compatible name
        return os.path.exists(self._table_loc(name))

    # ---- a stored table's columns, for a later stage of the same job -----
    def _table_file(self, name: str) -> tuple:
        st = os.stat(self._table_loc(name))
        return st.st_ino, st.st_size, st.st_mtime_ns

    def hold(self, name: str, columns: Any) -> None:
        """Keep `columns` of the table `name` this process has just stored,
        so that a later stage of the same job need not read the file back
        (ISSUE 35: `stage:evaluate` and the pair tables of `stage:cluster`)."""
        self._held[name] = (columns, self._table_file(name))

    def take_held(self, name: str) -> Any:
        """What :meth:`hold` kept of `name`, handed over once: None where
        this process holds nothing of it (a resumed work directory), or the
        file is no longer the one the columns were stored as."""
        columns, stored_as = self._held.pop(name, (None, None))
        if columns is not None and self.hasDb(name) and stored_as == self._table_file(name):
            return columns
        return None

    # ---- packed arrays (TPU-native extension) ----------------------------
    def _array_loc(self, name: str) -> str:
        return os.path.join(self.location, "data", "arrays", f"{name}.npz")

    def store_arrays(self, name: str, compressed: bool = True, **arrays: np.ndarray) -> None:
        """`compressed=False` for high-entropy payloads (the MinHash sketch
        cache: uniform 64-bit hashes are incompressible, and zlib over the
        ~GB-scale cache was pure CPU on both the save AND the timed-resume
        load path — cf. ckptmeta.atomic_savez's same knob). Payloads carry
        the in-band ``__crc__`` (utils/durableio.py) so a bit-rotted cache
        is detected at load, never silently trusted; the write streams to
        the tmp file directly (no in-memory serialize — the sketch cache
        is ~GB at 100k genomes).

        No file grows past ARRAY_PART_BYTES: an array larger than that is
        cut along its first axis into ``<name>.<key>.NNNN.npz`` parts (each
        a checked payload of its own) and the head ``<name>.npz`` records
        the parts' lengths under ``__parts__<key>``. The head is the commit
        point — removed first, published last — so a kill mid-save leaves
        an absent cache, never a head over another save's parts.

        A part written with `compressed=False` is what :meth:`get_arrays`
        reads in place: a zip of two STORED members, `part` and
        ``__crc__``. The format is numpy's own and has not changed since
        the parts came (a cache either side of ISSUE 43 loads on the
        other)."""
        from drep_tpu.utils.durableio import with_checksum

        writer = np.savez_compressed if compressed else np.savez

        def publish(loc: str, payload: dict) -> None:
            payload = with_checksum(payload)
            _atomic_write(loc, lambda tmp: writer(tmp, **payload))

        store_parted(self._array_loc(name), arrays, publish, ARRAY_PART_BYTES)

    def get_arrays(self, name: str, workers: int = 1) -> dict[str, np.ndarray]:
        """What :meth:`store_arrays` stored as `name`: :meth:`read_arrays`'
        arrays, without its account of the read."""
        return self.read_arrays(name, workers)[0]

    def read_arrays(self, name: str, workers: int = 1) -> tuple[dict[str, np.ndarray], dict[str, Any]]:
        """What :meth:`store_arrays` stored as `name`, and what reading it
        did. The head goes through `load_npz_checked`; a member the head
        lists in parts is read part by part into ONE array allocated for the
        whole of it, each part by the reader its file asks for
        (`durableio.load_npz_member_into`): a plain stored part with its
        ``__crc__``, which is what `compressed=False` writes, straight into
        its rows and checksummed there; any other (a compressed part, one
        written or read with checksums off) decoded, verified and copied.
        Either way no byte is returned that the part's checksum has not
        covered. A member's parts are read on up to `workers` threads (the
        job's `-p`), no more than the usable cores or the parts; an error on
        any of them is raised here, as itself, and no array is returned.
        The account: the `members` read from parts, their `parts`, how many
        went `direct_parts` and how many `fallback_parts`, their `bytes`,
        the `threads` of the widest member, the call's `seconds`."""
        import time

        from drep_tpu.utils.durableio import load_npz_checked

        t0 = time.perf_counter()
        head_loc = self._array_loc(name)
        out = load_npz_checked(head_loc, what=f"workdir array {name}")
        read: dict[str, Any] = fill_parted(
            head_loc, out, f"workdir array {name}", f"delete {head_loc} to recompute the cache", workers
        )
        read["seconds"] = time.perf_counter() - t0
        return out, read

    def has_arrays(self, name: str) -> bool:
        return os.path.exists(self._array_loc(name))

    # ---- argument snapshots (the resume compatibility check) -------------
    def _args_loc(self, stage: str) -> str:
        return os.path.join(self.location, "log", f"{stage}_arguments.json")

    def store_arguments(self, stage: str, kwargs: dict[str, Any]) -> None:
        # checked JSON (utils/durableio.py): the snapshot carries an
        # in-band "crc" so a bit-rotted snapshot is DETECTED at read and
        # classified as absent (stage recomputes) instead of either
        # crashing the resume or silently mis-matching
        from drep_tpu.utils.durableio import atomic_write_json

        atomic_write_json(self._args_loc(stage), kwargs, default=_json_default)

    def get_arguments(self, stage: str) -> dict[str, Any] | None:
        loc = self._args_loc(stage)
        if not os.path.exists(loc):
            return None
        from drep_tpu.utils.durableio import CorruptPayloadError, read_json_checked

        try:
            out = read_json_checked(loc, what=f"{stage} argument snapshot")
        except CorruptPayloadError:
            get_logger().warning(
                "corrupt argument snapshot %s — treating as absent (the "
                "stage recomputes and rewrites it)", loc,
            )
            return None
        return out if isinstance(out, dict) else None

    def arguments_match(self, stage: str, kwargs: dict[str, Any], keys: list[str] | None = None) -> bool:
        """True iff a stored snapshot exists and agrees with `kwargs`.

        `keys` restricts the comparison to resume-relevant flags (the
        reference compares the clustering-relevant subset, not e.g. -p).
        Stored snapshots from older releases may lack recently-added keys;
        those fill in from LEGACY_SNAPSHOT_DEFAULTS so an upgrade does not
        invalidate byte-identical caches.
        """
        stored = self.get_arguments(stage)
        if stored is None:
            return False
        stored = {**LEGACY_SNAPSHOT_DEFAULTS, **stored}
        current = json.loads(json.dumps(kwargs, default=_json_default, sort_keys=True))
        current = {**LEGACY_SNAPSHOT_DEFAULTS, **current}  # both sides, symmetric
        if keys is None:
            keys = sorted(set(stored) | set(current))
        return all(stored.get(k) == current.get(k) for k in keys)

    # ---- misc ------------------------------------------------------------
    def get_loc(self, name: str) -> str:
        """Named well-known locations, reference-compatible accessor."""
        known = {
            "log": os.path.join(self.location, "log", "logger.log"),
            "warnings": os.path.join(self.location, "log", "warnings.txt"),
            "dereplicated_genomes": os.path.join(self.location, "dereplicated_genomes"),
            "figures": os.path.join(self.location, "figures"),
        }
        if name not in known:
            raise KeyError(f"unknown location {name!r}; known: {sorted(known)}")
        return known[name]
