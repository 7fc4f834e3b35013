"""Durable shared-filesystem I/O: checksums, atomic publishes, retry/backoff.

Every elastic protocol in this repo — heartbeat notes, sentinel-note
barriers, epoch-stamped row/block shard stores, checkpoint meta — rides on
a shared filesystem that production runs mount as NFS or a FUSE-fronted
object store: transient ``EIO``/``ESTALE``/``ETIMEDOUT`` errors, stale
reads, quota exhaustion, and post-write corruption are operating reality,
not edge cases. dRep itself treats its work-directory tables as the
durable contract between pipeline stages (Mdb/Ndb/Cdb); our shard stores
play that role, so their integrity gets the same first-class treatment the
compute path's fault tolerance (parallel/faulttol.py) gave live device
failures. This module is THE funnel all shared-filesystem traffic goes
through (utils/ckptmeta.py re-exports the write primitives so no call
site drifts off it):

- **Atomic publishes** (:func:`atomic_write` / :func:`atomic_write_bytes`
  / :func:`atomic_savez`): uuid-tmp + rename, whole-file-or-nothing, with
  optional fsync of the tmp file AND its directory (``DREP_TPU_FSYNC=1``)
  so a host power loss cannot revert a rename the run already trusted.
- **In-band checksums**: every npz payload carries a ``__crc__`` member
  (crc32 over member names, dtypes, shapes, and bytes), every JSON note
  a ``"crc"`` key — verified on read (:func:`load_npz_checked`,
  :func:`read_json_checked`). A mismatch raises
  :class:`CorruptPayloadError`, which shard-store readers treat exactly
  like a MISSING shard: the existing recompute paths (streaming row
  stripes, ring blocks, secondary per-cluster results) fire and the store
  self-heals instead of crashing with ``BadZipFile``. Payloads written
  before checksums existed (no ``__crc__``/``"crc"``) stay readable —
  legacy-accepted, flagged by the scrubber (tools/scrub_store.py) but
  never invalidated. One payload has a second reader
  (:func:`load_npz_member_into`, the workdir array store's parts): where
  the FILE says it is a plain stored payload (zip method STORED, one
  member of a fixed-size C-ordered dtype, ``__crc__`` beside it) the
  member's bytes are ``readinto`` their destination and ``__crc__`` is
  computed over them there; the zip's own CRC-32 is not read on that
  path, because ``__crc__`` covers the same bytes and the name, dtype and
  shape besides. Every other file, and every file when checksums are off,
  is decoded there as :func:`load_npz_checked` decodes it (zipfile checks
  its CRC) and verified as there.
- **Transient-error retries**: ``EIO``/``ESTALE``/``ETIMEDOUT`` on read
  or write retry with bounded exponential backoff
  (``DREP_TPU_IO_RETRIES``, default 3; first delay
  ``DREP_TPU_IO_BACKOFF_S``), counted honestly (``io_retries``; an op
  that fails past the budget books ``io_unrecoverable`` and raises — the
  shard READ paths still degrade to recompute, the honest counters say
  how the run really went). ``ENOSPC`` never retries: it degrades into an
  actionable :class:`StoreFullError` naming the store and the bytes the
  write needed.
- **Chaos injection**: the ``io`` fault site (utils/faults.py) fires
  inside the retried regions — ``io_error`` (EIO on read+write),
  ``stale_read`` (ESTALE on read), ``enospc`` (ENOSPC on write), and
  ``corrupt`` (bit-flip the published npz AFTER the atomic rename — the
  post-write corruption a checksum exists to catch) — so the whole layer
  is testable on CPU, including multi-process pod runs.

Zero overhead when nothing fails: the fault check is one falsy lookup,
retries only spin on an actual OSError, and the crc32 cost is pinned at
<= 5% of a warm streaming pass by tests/test_perf_guards.py
(``DREP_TPU_IO_CRC=0`` disables checksum embed+verify as the escape
hatch / guard baseline).

This module must stay importable without a JAX backend (the scrubber runs
standalone); jax is never imported here.
"""

from __future__ import annotations

import contextlib
import errno
import io
import json
import math
import os
import time
import uuid
import zlib
from typing import Any, Callable

import numpy as np

from drep_tpu.utils import envknobs

IO_RETRIES_ENV = "DREP_TPU_IO_RETRIES"
IO_BACKOFF_ENV = "DREP_TPU_IO_BACKOFF_S"
# single source: the envknobs registry owns the defaults; the names stay
# for importers (docs, tests) that quote them
DEFAULT_IO_RETRIES = int(envknobs.knob(IO_RETRIES_ENV).default)
DEFAULT_IO_BACKOFF_S = float(envknobs.knob(IO_BACKOFF_ENV).default)
FSYNC_ENV = "DREP_TPU_FSYNC"
CRC_ENV = "DREP_TPU_IO_CRC"

# in-band checksum carriers: an npz member / a JSON key, stored INSIDE the
# payload so no side-car file can go missing independently
CRC_KEY = "__crc__"
JSON_CRC_KEY = "crc"

# errno classes retried as transient (NFS / FUSE object stores): EIO
# (flaky backend), ESTALE (handle invalidated by a server-side rename
# window), ETIMEDOUT (slow metadata server). Everything else — ENOENT,
# EACCES, EROFS — is a real answer and surfaces immediately.
TRANSIENT_ERRNOS = frozenset({errno.EIO, errno.ESTALE, errno.ETIMEDOUT})

# process-wide overrides installed by the CLI (cluster/controller.py);
# None = fall through to the env var / default
_CONFIG: dict[str, Any] = {"retries": None, "fsync": None}


def configure(retries: int | None = None, fsync: bool | None = None) -> None:
    """Install run-wide I/O knobs (the CLI's --io_retries / --fsync).
    Replaces the whole config: an omitted argument resets that knob to
    env/default resolution — same contract as allpairs.configure_ring."""
    _CONFIG["retries"] = retries
    _CONFIG["fsync"] = fsync


def io_retries() -> int:
    if _CONFIG["retries"] is not None:
        return max(0, int(_CONFIG["retries"]))
    return max(0, envknobs.env_int(IO_RETRIES_ENV))


def io_backoff_s() -> float:
    return envknobs.env_float(IO_BACKOFF_ENV)


def fsync_enabled() -> bool:
    if _CONFIG["fsync"] is not None:
        return bool(_CONFIG["fsync"])
    return envknobs.env_bool(FSYNC_ENV)


def crc_enabled() -> bool:
    return envknobs.env_bool(CRC_ENV)


class StoreFullError(OSError):
    """ENOSPC, degraded into an actionable error naming the store and the
    bytes the write needed — quota exhaustion on a shared checkpoint store
    must tell the operator WHAT to grow, not print a bare errno."""


class CorruptPayloadError(Exception):
    """A payload read back corrupt: truncated/zero-byte/unparseable, or an
    in-band checksum mismatch. Shard-store readers treat this exactly like
    a missing shard (recompute + heal); it is deliberately NOT an OSError
    so the transient-retry loop never spins on it."""


def _count(kind: str, n: int = 1) -> None:
    # lazy: profiling must stay importable without this module and vice
    # versa, and the scrubber imports durableio with no pipeline around
    from drep_tpu.utils.profiling import counters

    counters.add_fault(kind, n)


def retry_io(
    fn: Callable[[], Any],
    what: str,
    path: str,
    bytes_needed: int | None = None,
):
    """Run `fn`, retrying transient OSErrors (TRANSIENT_ERRNOS) with
    bounded exponential backoff. ENOSPC raises StoreFullError immediately
    (retrying a full filesystem burns the backoff for nothing); past the
    retry budget the op books ``io_unrecoverable`` and the last error
    surfaces."""
    retries = io_retries()
    last: OSError | None = None
    for attempt in range(retries + 1):
        if attempt:
            time.sleep(io_backoff_s() * (2 ** (attempt - 1)))
            _count("io_retries")
        try:
            return fn()
        except OSError as e:
            if e.errno == errno.ENOSPC:
                need = (
                    f"~{bytes_needed} bytes"
                    if bytes_needed is not None
                    else "an unknown payload size"
                )
                raise StoreFullError(
                    errno.ENOSPC,
                    f"{what}: filesystem full (ENOSPC) publishing {path} — "
                    f"the store at {os.path.dirname(os.path.abspath(path))} "
                    f"needs {need} free. Grow the quota / free space and "
                    f"rerun; finished shards resume.",
                ) from e
            if e.errno not in TRANSIENT_ERRNOS:
                raise
            last = e
            from drep_tpu.utils.logger import get_logger

            get_logger().warning(
                "%s: transient I/O error (%s) on %s — attempt %d/%d",
                what, errno.errorcode.get(e.errno, e.errno), path,
                attempt + 1, retries + 1,
            )
    _count("io_unrecoverable")
    # timeline detail the bare counter cannot carry: WHICH payload ran
    # out of retry budget (the generic fault instant rides add_fault)
    from drep_tpu.utils import telemetry

    telemetry.event("io_unrecoverable", what=what, path=path)
    raise last  # type: ignore[misc]  # loop ran >= once with a transient error


def _fsync_path(path: str) -> None:
    fd = os.open(path, os.O_RDONLY)
    try:
        os.fsync(fd)
    finally:
        os.close(fd)


def atomic_write(
    path: str,
    write_fn: Callable[[str], None],
    keep_suffix: bool = False,
    bytes_needed: int | None = None,
) -> None:
    """THE whole-file-or-nothing write primitive (kills mid-write must not
    leave torn files a later resume trusts; replicated multi-host writers
    of the same target must never interleave — uuid tmp names because pids
    collide ACROSS hosts/containers of a pod). `write_fn(tmp)` produces
    the content; a raising write_fn leaves no orphan tmp behind. Transient
    I/O errors retry the WHOLE attempt (write_fn is re-run — every caller
    produces deterministic content, so a retry is idempotent); with
    ``DREP_TPU_FSYNC=1`` the tmp file is fsynced before the rename and the
    directory after it, so a host power loss cannot revert a publish.

    `keep_suffix` picks the tmp-name shape, and the two shapes serve
    CONFLICTING invariants — choose deliberately:

    - False (default): ``<path>.tmp-<uuid>`` — the tmp shares no suffix
      with the target, so shard-store resume globs (``*.npz``) can never
      pick up a crash artifact as a corrupt-looking shard (the ingest
      shard store depends on this).
    - True: ``<base>.tmp-<uuid><suffix>`` — required when write_fn derives
      the real output name from the suffix (``np.savez_compressed``
      appends ``.npz`` to names without it, which would orphan the
      suffixless tmp). Only safe where nothing globs the target's suffix
      (the workdir array store).
    """
    from drep_tpu.utils import faults

    def attempt() -> None:
        base, suffix = os.path.splitext(path)
        tmp = (
            f"{base}.tmp-{uuid.uuid4().hex}{suffix}"
            if keep_suffix
            else f"{path}.tmp-{uuid.uuid4().hex}"
        )
        try:
            faults.fire_io("write", path=path)
            write_fn(tmp)
            if fsync_enabled():
                _fsync_path(tmp)
            os.replace(tmp, path)
            if fsync_enabled():
                with contextlib.suppress(OSError):  # dirs may refuse fsync
                    _fsync_path(os.path.dirname(os.path.abspath(path)) or ".")
        finally:
            if os.path.exists(tmp):
                with contextlib.suppress(OSError):
                    os.remove(tmp)

    retry_io(attempt, what="atomic write", path=path, bytes_needed=bytes_needed)


def atomic_write_bytes(path: str, data) -> None:
    def write(tmp: str) -> None:
        with open(tmp, "wb") as f:
            f.write(data)

    atomic_write(path, write, bytes_needed=len(data))


# -- in-band checksums ------------------------------------------------------


def _checksum_header(name: str, dtype: np.dtype, shape: tuple, crc: int) -> int:
    """`crc` run on over what the in-band checksum covers of an array
    before its bytes: its member name, dtype and shape."""
    crc = zlib.crc32(str(name).encode(), crc)
    crc = zlib.crc32(str(dtype).encode(), crc)
    return zlib.crc32(str(shape).encode(), crc)


def checksum_arrays(arrays: dict[str, np.ndarray]) -> int:
    """crc32 over member names, dtypes, shapes, and raw bytes (sorted by
    name, CRC_KEY excluded) — pinned to the decoded arrays, not the zip
    container, so the same content checks equal whether it was stored
    compressed or raw."""
    crc = 0
    for name in sorted(arrays):
        if name == CRC_KEY:
            continue
        a = np.ascontiguousarray(arrays[name])
        crc = _checksum_header(name, a.dtype, a.shape, crc)
        try:
            # hash the buffer in place: a.tobytes() would transiently copy
            # the payload, doubling peak memory on the GB-scale sketch cache
            buf = memoryview(a).cast("B")
        except (TypeError, ValueError):
            buf = a.tobytes()  # exotic dtypes without a flat buffer view
        crc = zlib.crc32(buf, crc)
    return crc & 0xFFFFFFFF


def with_checksum(arrays: dict[str, np.ndarray]) -> dict[str, np.ndarray]:
    """The arrays plus their in-band ``__crc__`` member (a no-op pass-
    through when checksums are disabled). A payload that already carries
    the reserved member raises — same loud contract as
    :func:`dump_json_checked`'s ``"crc"`` key: silently replacing the
    caller's array would lose data AND strip it again on every read."""
    if CRC_KEY in arrays:
        raise ValueError(
            f"npz payload already carries the reserved in-band checksum "
            f"member {CRC_KEY!r} — rename that array (utils/durableio.py "
            f"owns the member in every checked payload)"
        )
    if not crc_enabled():
        return arrays
    out = dict(arrays)
    out[CRC_KEY] = np.array([checksum_arrays(arrays)], dtype=np.uint32)
    return out


def _stored_checksum(crc_member, path: str, what: str) -> int:
    """The value of a payload's ``__crc__`` member."""
    try:
        return int(np.asarray(crc_member).ravel()[0])
    except (IndexError, TypeError, ValueError) as e:
        # a rotted/empty __crc__ member is itself corruption — it must
        # classify, never crash (the corruption-never-crashes contract)
        raise CorruptPayloadError(
            f"{what} {path}: unreadable in-band checksum ({e!r})"
        ) from e


def _checksum_mismatch(path: str, what: str) -> CorruptPayloadError:
    return CorruptPayloadError(
        f"{what} {path}: in-band checksum mismatch — the payload was "
        f"corrupted after it was written"
    )


def verify_npz_payload(loaded: dict[str, np.ndarray], path: str, what: str) -> dict:
    """Strip + verify the in-band checksum of an already-decoded payload.
    Payloads with no ``__crc__`` are legacy-accepted (pre-checksum stores
    must stay resumable); a present-but-wrong crc raises."""
    if CRC_KEY in loaded:
        stored = _stored_checksum(loaded.pop(CRC_KEY), path, what)
        if crc_enabled() and checksum_arrays(loaded) != stored:
            raise _checksum_mismatch(path, what)
    return loaded


def _member_data_offset(f, info) -> int | None:
    """Where the data of the zip member `info` begins in the open file `f`
    (its local file header holds the name and extra lengths at 26/28); None
    where no local header stands at the member's offset."""
    f.seek(info.header_offset)
    local = f.read(30)
    if len(local) != 30 or local[:4] != b"PK\x03\x04":
        return None
    return (info.header_offset + 30 + int.from_bytes(local[26:28], "little")
            + int.from_bytes(local[28:30], "little"))


def _flip_bit(path: str) -> None:
    """Chaos helper for the ``io:corrupt`` mode: flip one bit of the
    PUBLISHED file — the post-atomic-rename corruption (disk rot, a
    misbehaving object-store cache) a checksum exists to catch. The
    atomic path is untouched; only the durable bytes rot. For zip/npz
    payloads the flipped bit lands INSIDE a member's data region
    (mid-file on a tiny payload can hit a structure field zipfile
    ignores, which would make the injection a silent no-op)."""
    size = os.path.getsize(path)
    if size == 0:
        return
    off = None
    try:
        import zipfile

        with zipfile.ZipFile(path) as zf:
            info = max(zf.infolist(), key=lambda i: i.compress_size)
        if info.compress_size > 0:
            with open(path, "rb") as f:
                off = _member_data_offset(f, info) + info.compress_size // 2
    except Exception:  # noqa: BLE001 — not a zip: rot the middle byte
        off = None
    if off is None or off >= size:
        off = size // 2
    with open(path, "r+b") as f:
        f.seek(off)
        b = f.read(1)
        f.seek(off)
        f.write(bytes([b[0] ^ 0x01]))


def atomic_savez(
    path: str, compressed: bool = True, fault_site: str = "shard_write", **arrays
) -> None:
    """Serialize arrays (plus their in-band ``__crc__``) to `.npz` IN
    MEMORY and publish through atomic_write: uuid tmp (two writers of one
    target on a shared pod filesystem must never interleave) whose name
    does NOT end in .npz — crash artifacts must stay outside the shard
    namespace that resume globs and ``clear_suffixes`` scan. One helper
    for every shard store (streaming row blocks, ring block tiles,
    per-cluster secondary results, ingest sketch shards) so the
    atomicity+checksum recipe cannot drift between them.
    `compressed=False` where zlib is a measured hot spot: a store of
    thousands of tiny files, or a payload of uniform 64-bit hashes, which
    deflate shrinks by a few percent for several times the seconds (the
    ingest sketch shards; the sketch cache and the index store write
    theirs stored too)."""
    from drep_tpu.utils import faults

    buf = io.BytesIO()
    (np.savez_compressed if compressed else np.savez)(buf, **with_checksum(arrays))
    if faults.torn_write(fault_site, path=path):
        # chaos injection: publish a truncated file AT the target path,
        # bypassing the atomic tmp+rename — the on-disk state a mid-write
        # kill on a non-atomic filesystem would leave. Resume must detect
        # it as corrupt and recompute (the path this injection tests).
        data = bytes(buf.getbuffer())
        with open(path, "wb") as f:
            f.write(data[: max(1, len(data) // 2)])
        return
    atomic_write_bytes(path, buf.getbuffer())
    if faults.corrupt_write(path=path):
        # chaos injection: the atomic publish SUCCEEDED, then the durable
        # bytes rotted — exactly what the in-band checksum defends against
        _flip_bit(path)


def _read_npz(path: str, what: str, decode: Callable[[Any], Any], buffering: int = -1) -> Any:
    """THE retried read of an npz: the ``io`` fault site, then
    ``decode(the open file)``, once an attempt; transient OSErrors retried,
    anything but an OSError that `decode` raises classified corrupt."""
    from drep_tpu.utils import faults

    def read():
        faults.fire_io("read", path=path)
        with open(path, "rb", buffering=buffering) as f:
            return decode(f)

    try:
        return retry_io(read, what=f"read {what}", path=path)
    except (OSError, CorruptPayloadError):
        raise
    except Exception as e:  # noqa: BLE001 — BadZipFile / EOF / pickle guard
        raise CorruptPayloadError(f"{what} {path}: unreadable ({e!r})") from e


def _decode_npz(f) -> dict[str, np.ndarray]:
    with np.load(f, allow_pickle=False) as z:
        return {k: z[k] for k in z.files}


def read_npz_unverified(path: str, what: str = "payload") -> dict[str, np.ndarray]:
    """Retried read + full decode with corrupt classification, but NO
    checksum verification — the returned dict still carries its
    ``__crc__`` member. The scrubber reads through this so it can
    classify legacy (crc-less) payloads without a second open; everything
    else wants :func:`load_npz_checked`."""
    return _read_npz(path, what, _decode_npz)


def load_npz_checked(path: str, what: str = "payload") -> dict[str, np.ndarray]:
    """Read an npz payload with transient-error retries and in-band
    checksum verification. Raises :class:`CorruptPayloadError` for
    anything the WRITER's atomicity cannot explain — zero-byte, truncated,
    unparseable, or checksum-mismatched bytes — which shard-store callers
    treat exactly like a missing shard (recompute + heal). OSErrors that
    survive the retry budget surface as themselves (missing file, real
    permission trouble — answers, not corruption)."""
    return verify_npz_payload(read_npz_unverified(path, what), path, what)


# The in-place reader takes a member in pieces of this size and runs the
# checksum on over each while the cache still holds it. On the chip host
# (PERF.md section 6, PR 43) 1.04 GB in 16 MiB parts took 0.89 s a part at
# a time and 0.68 s in pieces of 256 KiB on six threads, 0.81 s in pieces
# of 1 MiB; one thread loses what the read calls cost (1.26 against 1.56 s)
READ_PIECE_BYTES = 256 << 10


def _npy_header(f):
    """``(shape, fortran_order, dtype)`` from the `.npy` header at `f`'s
    position, `f` left at the array's first byte; None for a header version
    numpy has no public reader of."""
    from numpy.lib import format as npy

    read = {(1, 0): npy.read_array_header_1_0, (2, 0): npy.read_array_header_2_0}.get(npy.read_magic(f))
    return None if read is None else read(f)


def npz_member_header(path: str, member: str) -> tuple[np.dtype, tuple] | None:
    """Dtype and shape of the array `member` of the npz at `path`, from its
    `.npy` header alone: no data read, no fault site fired, nothing raised.
    None where they cannot be had so; :func:`load_npz_checked` says why."""
    import zipfile

    try:
        with zipfile.ZipFile(path) as zf, zf.open(member + ".npy") as m:
            shape, _, dtype = _npy_header(m)
        return dtype, shape
    except Exception:  # noqa: BLE001 — missing, torn, no such member, an odd header
        return None


def _stored_member(f, member: str):
    """Where the one array of a PLAIN STORED payload lies in the open npz
    `f`: ``(dtype, shape, offset of its bytes, the __crc__ member)``, or
    None where the file does not say, beyond doubt, that it is one: a zip
    holding `member` and ``__crc__`` and nothing else, both stored
    uncompressed, `member` a C-ordered array of a fixed-size dtype (numbers,
    strings, dates, records: anything but objects) whose bytes run to the
    member's end.
    Only OSErrors leave here: a file that cannot be read this way is the
    decoding reader's to judge."""
    import zipfile
    from numpy.lib import format as npy

    try:
        with zipfile.ZipFile(f) as zf:
            infos = {i.filename: i for i in zf.infolist()}
            if sorted(infos) != sorted((member + ".npy", CRC_KEY + ".npy")) or any(
                i.compress_type != zipfile.ZIP_STORED or i.flag_bits & 0x1 for i in infos.values()
            ):
                return None
            with zf.open(CRC_KEY + ".npy") as m:
                crc_member = npy.read_array(m, allow_pickle=False)
        info = infos[member + ".npy"]
        data = _member_data_offset(f, info)
        if data is None:
            return None
        f.seek(data)
        shape, fortran_order, dtype = _npy_header(f)
        offset = f.tell()
        nbytes = math.prod(shape) * dtype.itemsize
        if (
            fortran_order or dtype.hasobject or nbytes <= 0
            or offset + nbytes != data + info.file_size
            or offset + nbytes > os.fstat(f.fileno()).st_size
        ):
            return None
        return dtype, shape, offset, crc_member
    except OSError:
        raise
    except Exception:  # noqa: BLE001 — BadZipFile, a torn header: not this reader's to name
        return None


class PayloadShapeError(CorruptPayloadError):
    """A sound payload whose array has another `dtype` or `shape` than the
    array its reader was to fill."""

    def __init__(self, message: str, dtype: np.dtype, shape: tuple):
        super().__init__(message)
        self.dtype, self.shape = dtype, shape


def load_npz_member_into(path: str, member: str, dest: np.ndarray, what: str = "payload") -> bool:
    """Read the array `member` of the checked payload at `path` into the
    C-contiguous array `dest` (:class:`PayloadShapeError` where the file
    holds another dtype or shape), verified before this returns. Read,
    verify, place, by the one of two readers the FILE asks for; True where
    it was the first:

    - **in place**, where the file is a plain stored payload
      (:func:`_stored_member`): the member's bytes are ``readinto`` `dest`
      straight from their offset in the file, a piece of `READ_PIECE_BYTES`
      at a time, and the in-band checksum (:func:`checksum_arrays`' value
      for ``{member: array}``) is run on over each piece as it arrives. One
      read, one CRC, no copy, no second array, and nothing that holds the
      GIL, so a store's parts can be read on several threads. The zip's own
      CRC-32 of the member is not read: ``__crc__`` covers the same bytes,
      and the name, dtype and shape besides.
    - **decoded** from the same open file as :func:`load_npz_checked`
      decodes it, verified as there, then copied: a compressed payload, one
      without ``__crc__`` or read with checksums off (the zip's CRC is then
      the only check there is, and zipfile's reader makes it), an object
      dtype, anything unparseable; the errors are that reader's.

    Either way the file is opened once an attempt, and the transient-error
    retries, the ``io`` fault site and :class:`CorruptPayloadError` are
    :func:`load_npz_checked`'s, per call and on the calling thread."""

    def must_fit(dtype: np.dtype, shape: tuple) -> None:
        if (dtype, shape) != (dest.dtype, dest.shape):
            raise PayloadShapeError(
                f"{what} {path}: holds {dtype}{list(shape)}, its reader expects "
                f"{dest.dtype}{list(dest.shape)}", dtype, shape)

    def decode(f) -> dict[str, np.ndarray] | None:
        found = _stored_member(f, member) if crc_enabled() else None
        if found is None:
            f.seek(0)
            return _decode_npz(f)
        dtype, shape, offset, crc_member = found
        stored = _stored_checksum(crc_member, path, what)
        must_fit(dtype, shape)
        # as bytes through a uint8 view: a buffer of `<U..` or `M8` cannot be cast
        buf = memoryview(dest.view(np.uint8)).cast("B")
        crc = _checksum_header(member, dtype, shape, 0)
        f.seek(offset)
        for lo in range(0, len(buf), READ_PIECE_BYTES):
            piece = buf[lo : lo + READ_PIECE_BYTES]
            got = 0
            while got < len(piece):
                n = f.readinto(piece[got:])
                if not n:
                    raise CorruptPayloadError(f"{what} {path}: unreadable (truncated under the read)")
                got += n
            crc = zlib.crc32(piece, crc)
        if crc & 0xFFFFFFFF != stored:
            raise _checksum_mismatch(path, what)
        return None

    decoded = _read_npz(path, what, decode, buffering=0)
    if decoded is None:
        return True
    payload = verify_npz_payload(decoded, path, what)[member]
    must_fit(payload.dtype, payload.shape)
    dest[...] = payload
    return False


def load_npz_or_none(path: str, what: str, convert: Callable[[dict], Any], warn: str) -> Any:
    """THE corrupt-vs-missing classifier every shard-store reader shares
    (streaming row shards, ring blocks, secondary per-cluster results —
    one implementation so the heal-accounting contract cannot drift):
    `convert(payload)` builds the caller's result (member indexing inside
    it counts as corruption — a shard missing its members IS rot);
    a missing file returns None UNCOUNTED (a peer may have healed it
    first — booking it would report phantom heals across survivors);
    anything else warns with `warn` (%s = path), books one
    ``corrupt_shards_healed``, best-effort removes the payload, and
    returns None so the caller recomputes."""
    try:
        return convert(load_npz_checked(path, what=what))
    except FileNotFoundError:
        return None
    except OSError:
        # transient retry budget exhausted (io_unrecoverable already
        # booked by retry_io) or real FS trouble: the shard ITSELF may be
        # perfectly intact — recompute without deleting it and without
        # booking a heal. Deleting here would let an NFS brownout destroy
        # a fully-computed store the moment a resume walks it. Its own
        # message, NOT the caller's corrupt-shard one: telling an operator
        # an intact shard is "corrupt" invites a --delete that destroys it.
        from drep_tpu.utils.logger import get_logger

        get_logger().warning(
            "%s %s: unreadable after transient I/O retries — recomputing, "
            "shard left in place", what, path,
        )
        return None
    except Exception:  # noqa: BLE001 — any unreadable shard degrades to recompute
        from drep_tpu.utils.logger import get_logger

        get_logger().warning(warn, path)
        quarantine_corrupt(path)
        return None


def quarantine_corrupt(path: str) -> None:
    """Book one corrupt-shard heal (the caller is about to recompute) and
    best-effort remove the bad payload — the remove itself may fail on
    EACCES/flaky NFS; the recompute's atomic rewrite replaces it either
    way (the idempotent self-heal invariant)."""
    _count("corrupt_shards_healed")
    from drep_tpu.utils import telemetry

    telemetry.event("io_heal", path=path)
    with contextlib.suppress(OSError):
        os.remove(path)


# -- checked JSON notes -----------------------------------------------------


def dump_json_checked(obj: dict[str, Any], default=str) -> bytes:
    """Canonical JSON bytes with an in-band ``"crc"`` key — crc32 of the
    canonical dump WITHOUT it. The verify side recomputes the crc from
    the PARSED body, so any `default` serializer is consistent (canonical
    json round-trips: dump(parse(dump(x))) == dump(x)). A payload that
    already carries a ``"crc"`` key raises: silently replacing the
    caller's value would lose data AND make every later read classify
    the note as rotted — the key is reserved, loudly."""
    if JSON_CRC_KEY in obj:
        raise ValueError(
            f"JSON payload already carries the reserved in-band checksum "
            f"key {JSON_CRC_KEY!r} — rename that field (utils/durableio.py "
            f"owns the key on every checked note)"
        )
    body = dict(obj)
    if crc_enabled():
        canon = json.dumps(body, sort_keys=True, default=default).encode()
        body[JSON_CRC_KEY] = zlib.crc32(json.dumps(json.loads(canon), sort_keys=True).encode()) & 0xFFFFFFFF
    return json.dumps(body, sort_keys=True, default=default).encode()


def atomic_write_json(path: str, obj: dict[str, Any], default=str) -> None:
    atomic_write_bytes(path, dump_json_checked(obj, default=default))


def read_json_unverified(path: str, what: str = "note"):
    """Retried read + parse with corrupt classification, but NO checksum
    verification — a present ``"crc"`` key stays in the returned document.
    The scrubber reads through this so it can classify legacy (crc-less)
    notes without a second parse; everything else wants
    :func:`read_json_checked`."""
    from drep_tpu.utils import faults

    def read() -> bytes:
        # binary read: a note bit-rotted into invalid UTF-8 must classify
        # as corrupt below, not blow up as UnicodeDecodeError mid-read
        faults.fire_io("read", path=path)
        with open(path, "rb") as f:
            return f.read()

    raw = retry_io(read, what=f"read {what}", path=path)
    try:
        return json.loads(raw.decode())
    except ValueError as e:  # includes UnicodeDecodeError
        raise CorruptPayloadError(f"{what} {path}: unparseable JSON ({e})") from e


def verify_json_payload(body, path: str, what: str = "note"):
    """Strip + verify the in-band ``"crc"`` of an already-parsed JSON
    document (consumers compare payload keys — meta matching must never
    see the checksum as a pinned parameter). Documents with no crc key
    are legacy-accepted, and non-dict documents pass through untouched
    (callers validate shape). Raises CorruptPayloadError on a mismatch."""
    if not isinstance(body, dict) or JSON_CRC_KEY not in body:
        return body
    stored = body.pop(JSON_CRC_KEY)
    if crc_enabled():
        try:
            want = int(stored)
        except (TypeError, ValueError) as e:
            # the crc value itself rotted (null, string garbage): that IS
            # corruption and must classify, never crash the reader
            raise CorruptPayloadError(
                f"{what} {path}: unreadable in-band checksum ({stored!r})"
            ) from e
        canon = json.dumps(body, sort_keys=True, default=str).encode()
        if (zlib.crc32(canon) & 0xFFFFFFFF) != want:
            raise CorruptPayloadError(f"{what} {path}: in-band checksum mismatch")
    return body


def read_json_checked(path: str, what: str = "note"):
    """Read + verify a checked JSON note; the ``"crc"`` key is stripped
    from the returned dict. Notes written before checksums existed (no
    crc key) are legacy-accepted. Raises CorruptPayloadError on
    unparseable bytes or a crc mismatch."""
    return verify_json_payload(read_json_unverified(path, what), path, what)
