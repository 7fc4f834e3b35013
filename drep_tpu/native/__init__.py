"""Native (C++) host-side bindings — ctypes, no pybind11.

Three sources, built lazily with g++ into ONE content-addressed shared
library cached next to them: ingest.cc (FASTA -> canonical k-mers ->
sketches; SURVEY.md §7 step 2 / hard part (f)), linkage.cc (sparse UPGMA
over the streaming primary's retained edges) and rank.cc (the dense ranks
of the primary pack's hashes, bucketed and threaded). Without a compiler
everything degrades to the numpy / python path (ops/kmers.py,
ops/linkage.py, ops/minhash.py), so the framework never *requires* the
native path — but never silently: a failed build is logged with the
compiler's output, and every run records which path served
(perf_counters.json note ``ingest_path``, ``primary_pack.native_calls``;
the numpy ingest is more than an order of magnitude slower).

DREP_TPU_NO_NATIVE=1 disables the native path entirely (used by the
equivalence tests to pin the numpy oracle).
"""

from __future__ import annotations

import ctypes
import hashlib
import os
import subprocess
import threading

import numpy as np

from drep_tpu.utils.logger import get_logger

_HERE = os.path.dirname(os.path.abspath(__file__))
_SOURCES = [os.path.join(_HERE, name) for name in ("ingest.cc", "linkage.cc", "rank.cc")]

_lock = threading.Lock()
_lib: ctypes.CDLL | None = None
_lib_failed = False


class _DrepSketch(ctypes.Structure):
    _fields_ = [
        ("length", ctypes.c_int64),
        ("n50", ctypes.c_int64),
        ("n_contigs", ctypes.c_int32),
        ("n_kmers", ctypes.c_int64),
        ("bottom_len", ctypes.c_int64),
        ("scaled_len", ctypes.c_int64),
        ("bottom", ctypes.POINTER(ctypes.c_uint64)),
        ("scaled", ctypes.POINTER(ctypes.c_uint64)),
        ("n_valid", ctypes.c_int64),
    ]


def _build_library() -> str | None:
    """Compile the sources -> cached .so keyed on their hash; None on failure.

    EVERYTHING here may fail — including makedirs when the package sits in
    a read-only site-packages — and then degrades to the numpy path, never
    aborting ingest (the module contract); the reason is logged."""
    tmp = None
    try:
        h = hashlib.sha256()
        for src in _SOURCES:
            with open(src, "rb") as f:
                h.update(f.read())
        digest = h.hexdigest()[:16]
        build_dir = os.path.join(_HERE, "_build")
        so_path = os.path.join(build_dir, f"libdrep_native_{digest}.so")
        if os.path.exists(so_path):
            return so_path
        os.makedirs(build_dir, exist_ok=True)
        tmp = so_path + f".tmp{os.getpid()}"
        cmd = ["g++", "-O3", "-std=c++17", "-shared", "-fPIC", "-pthread", *_SOURCES, "-o", tmp, "-lz"]
        res = subprocess.run(cmd, capture_output=True, text=True, timeout=300)
        if res.returncode != 0:
            get_logger().warning("native build failed: %s", res.stderr[-1000:])
            return None
        # drep-lint: allow[durable-funnel] — local build artifact: g++ wrote the tmp; the rename IS the atomic publish (no shared-FS payload, no crc story)
        os.replace(tmp, so_path)  # atomic: concurrent builders race safely
        return so_path
    except Exception as e:  # noqa: BLE001 — no compiler, read-only package dir, ...
        get_logger().warning("native build unavailable: %s", e)
        return None
    finally:
        if tmp is not None and os.path.exists(tmp):
            os.unlink(tmp)


def get_library() -> ctypes.CDLL | None:
    """The loaded native library, building it on first use; None if
    unavailable (missing compiler, failed build, or DREP_TPU_NO_NATIVE)."""
    global _lib, _lib_failed
    from drep_tpu.utils import envknobs

    if envknobs.env_bool("DREP_TPU_NO_NATIVE"):
        return None
    if _lib is not None or _lib_failed:
        return _lib
    with _lock:
        if _lib is not None or _lib_failed:
            return _lib
        # drep-lint: allow[reader-purity] — lazy one-time g++ build into the package's own build dir, never a checkpoint/index store
        so_path = _build_library()
        if so_path is None:
            _lib_failed = True
            get_logger().warning("native ingest unavailable — using the numpy path")
            return None
        lib = ctypes.CDLL(so_path)
        lib.drep_sketch_fasta.restype = ctypes.c_int
        lib.drep_sketch_fasta.argtypes = [
            ctypes.c_char_p,
            ctypes.c_int,
            ctypes.c_int64,
            ctypes.c_uint64,
            ctypes.c_int,  # hash_id: 0 splitmix64, 1 murmur3
            ctypes.POINTER(_DrepSketch),
        ]
        lib.drep_sketch_free.restype = None
        lib.drep_sketch_free.argtypes = [ctypes.POINTER(_DrepSketch)]
        lib.drep_sparse_upgma.restype = ctypes.c_int
        lib.drep_sparse_upgma.argtypes = [
            ctypes.c_int64,
            ctypes.c_int64,
            ctypes.POINTER(ctypes.c_int64),
            ctypes.POINTER(ctypes.c_int64),
            ctypes.POINTER(ctypes.c_double),
            ctypes.c_double,
            ctypes.c_double,
            ctypes.POINTER(ctypes.c_int64),
            ctypes.POINTER(ctypes.c_int64),
        ]
        lib.drep_rank_rows.restype = ctypes.c_int
        lib.drep_rank_rows.argtypes = [
            ctypes.POINTER(ctypes.c_void_p),
            ctypes.POINTER(ctypes.c_int64),
            ctypes.c_int64,
            ctypes.c_int64,
            ctypes.POINTER(ctypes.c_int32),
            ctypes.c_int32,
            ctypes.c_int,
            ctypes.c_int64,
            ctypes.POINTER(ctypes.c_int64),
        ]
        _lib = lib
    return _lib


def scaled_max_hash(scale: int) -> int:
    """FracMinHash threshold — the shared definition in ops/kmers.py."""
    from drep_tpu.ops.kmers import max_scaled_hash

    return max_scaled_hash(scale)


_HASH_IDS = {"splitmix64": 0, "murmur3": 1}


def _read_fasta(path: str, k: int, sketch_size: int, scaled_max: int, hash_id: int) -> dict | None:
    """One call of the native kernel (k == 0: stats only, nothing hashed).
    None when the library is unavailable; raises on file errors, matching
    the numpy path. The sketch arrays are copies — safe after the native
    buffers are freed."""
    lib = get_library()
    if lib is None:
        return None
    out = _DrepSketch()
    rc = lib.drep_sketch_fasta(path.encode(), k, sketch_size, scaled_max, hash_id, ctypes.byref(out))
    if rc == -1:
        if not os.path.exists(path):
            raise FileNotFoundError(f"cannot read FASTA {path!r}")
        raise RuntimeError(f"corrupt or truncated FASTA {path!r}")
    if rc != 0:
        raise RuntimeError(f"native ingest failed on {path!r} (rc={rc})")
    try:
        bottom = np.ctypeslib.as_array(out.bottom, shape=(out.bottom_len,)).copy()
        scaled = np.ctypeslib.as_array(out.scaled, shape=(out.scaled_len,)).copy()
    finally:
        lib.drep_sketch_free(ctypes.byref(out))
    return {
        "length": int(out.length),
        "N50": int(out.n50),
        "contigs": int(out.n_contigs),
        "n_kmers": int(out.n_kmers),
        "valid_kmers": int(out.n_valid),  # windows hashed, duplicates counted
        "bottom": bottom.astype(np.uint64),
        "scaled": scaled.astype(np.uint64),
    }


def sketch_fasta_native(
    path: str, k: int, sketch_size: int, scale: int, hash_name: str = "splitmix64"
) -> dict | None:
    """Full per-genome ingest in one native call.

    Returns {length, N50, contigs, n_kmers, valid_kmers, bottom, scaled} with uint64
    sketch arrays, or None when the native library is unavailable.
    """
    res = _read_fasta(path, k, sketch_size, scaled_max_hash(scale), _HASH_IDS[hash_name])
    # n_kmers == -1 marks the FracMinHash fast path: the native side never
    # built the full distinct set, so report the standard cardinality
    # estimate |scaled| * scale (ops/kmers.py::sketches_from_raw rule)
    if res is not None and res["n_kmers"] < 0:
        res["n_kmers"] = len(res["scaled"]) * scale
    return res


def fasta_stats_native(path: str) -> dict | None:
    """The kernel's stats-only mode: {length, N50, contigs} from the same
    parse, no k-mer hashed. None when the native library is unavailable."""
    res = _read_fasta(path, 0, 0, 0, 0)
    return None if res is None else {key: res[key] for key in ("length", "N50", "contigs")}


def sparse_upgma_native(
    n: int,
    ii: np.ndarray,
    jj: np.ndarray,
    dd: np.ndarray,
    cutoff: float,
    keep: float,
) -> tuple[np.ndarray, int] | None:
    """Native sparse UPGMA (linkage.cc) — a bit-exact replica of
    ops/linkage.py::sparse_average_linkage's partition (equality-tested).
    Returns (raw labels, approx_merges) — the CALLER renumbers labels by
    first appearance, same as the Python path — or None when the native
    library is unavailable."""
    lib = get_library()
    if lib is None:
        return None
    ii = np.ascontiguousarray(ii, dtype=np.int64)
    jj = np.ascontiguousarray(jj, dtype=np.int64)
    dd = np.ascontiguousarray(dd, dtype=np.float64)
    labels = np.zeros(n, dtype=np.int64)
    approx = ctypes.c_int64(0)
    p = lambda a, t: a.ctypes.data_as(ctypes.POINTER(t))  # noqa: E731
    rc = lib.drep_sparse_upgma(
        n, len(ii), p(ii, ctypes.c_int64), p(jj, ctypes.c_int64),
        p(dd, ctypes.c_double), float(cutoff), float(keep),
        p(labels, ctypes.c_int64), ctypes.byref(approx),
    )
    if rc == -2:
        # caller bug (edge index out of range): loud on BOTH paths — the
        # python reference would KeyError — never a silent wrong partition
        raise ValueError(f"sparse UPGMA: edge index out of range for n={n}")
    if rc != 0:
        # any other native failure degrades to the python reference path
        # (the module contract: native is an accelerator, never a gate)
        get_logger().warning("native sparse UPGMA failed (rc=%d) — python fallback", rc)
        return None
    return labels, int(approx.value)


def rank_rows_native(
    rows: list[np.ndarray], out: np.ndarray, pad: int, threads: int, limit: int
) -> int | None:
    """Dense ranks of the hashes of `rows` (rank.cc: the number of distinct
    hashes smaller, over all rows), row r's written to `out[r, :len(rows[r])]`
    and `pad` past them, on `threads` threads. Returns the number of
    distinct hashes — where it reaches `limit` the CALLER refuses, as the
    NumPy path does, and `out` holds no rank — or None when the native
    library is unavailable. `rows` are 1-D uint64, none longer than `out` is
    wide; `out` is C-contiguous int32 and need not be initialised."""
    lib = get_library()
    if lib is None:
        return None
    rows = [np.ascontiguousarray(r) for r in rows]  # (itself where it is: a sketch's slice)
    offsets = np.zeros(len(rows) + 1, dtype=np.int64)
    np.cumsum([len(r) for r in rows], out=offsets[1:])
    at = np.fromiter((r.ctypes.data for r in rows), dtype=np.uintp, count=len(rows))
    distinct = ctypes.c_int64(0)
    p = lambda a, t: a.ctypes.data_as(ctypes.POINTER(t))  # noqa: E731
    rc = lib.drep_rank_rows(
        p(at, ctypes.c_void_p), p(offsets, ctypes.c_int64), len(rows), out.shape[1],
        p(out, ctypes.c_int32), int(pad), int(threads), int(limit), ctypes.byref(distinct),
    )
    if rc == 2:
        raise MemoryError(f"native rank: no memory for {offsets[-1]} (hash, place) pairs")
    return int(distinct.value)
