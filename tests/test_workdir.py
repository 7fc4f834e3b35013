"""WorkDirectory: the persistence/checkpoint substrate (SURVEY.md §5.4)."""

import glob
import os

import numpy as np
import pandas as pd
import pytest

from drep_tpu.workdir import WorkDirectory


def test_store_get_roundtrip(tmp_path):
    wd = WorkDirectory(str(tmp_path / "wd"))
    df = pd.DataFrame({"genome": ["a", "b"], "score": [1.5, 2.5]})
    wd.store_db(df, "Sdb")
    assert wd.hasDb("Sdb")
    out = wd.get_db("Sdb")
    pd.testing.assert_frame_equal(df, out)


def test_missing_table_raises(tmp_path):
    wd = WorkDirectory(str(tmp_path / "wd"))
    assert not wd.hasDb("Cdb")
    with pytest.raises(FileNotFoundError):
        wd.get_db("Cdb")


def test_arrays_roundtrip(tmp_path):
    wd = WorkDirectory(str(tmp_path / "wd"))
    a = np.arange(10, dtype=np.uint64)
    b = np.ones((3, 4), dtype=np.int32)
    wd.store_arrays("sketches", a=a, b=b)
    out = wd.get_arrays("sketches")
    assert np.array_equal(out["a"], a)
    assert np.array_equal(out["b"], b)


def test_large_arrays_split_into_bounded_parts(tmp_path, monkeypatch):
    """No file of the array store grows past ARRAY_PART_BYTES (a machine may
    cap file size — the chip-check machine did, and the one-file sketch
    cache died there with EFBIG): a large array round-trips through parts,
    small ones stay in the head, a shorter re-save drops the longer save's
    tail, and a lost part is corruption, not a short array."""
    import glob
    import os

    from drep_tpu import workdir
    from drep_tpu.utils.durableio import CorruptPayloadError

    monkeypatch.setattr(workdir, "ARRAY_PART_BYTES", 4096)
    wd = WorkDirectory(str(tmp_path / "wd"))
    rng = np.random.default_rng(0)
    big = rng.integers(0, 2**63, size=5000, dtype=np.uint64)  # 40 KB -> 10 parts
    mat = rng.integers(0, 255, size=(100, 300), dtype=np.uint8)  # rows kept whole
    small = np.arange(7, dtype=np.int64)
    names = np.array(["a", "b"])
    wd.store_arrays("sketches", compressed=False, big=big, mat=mat, small=small, names=names)
    files = glob.glob(os.path.join(wd.location, "data", "arrays", "*"))
    assert len(files) > 10
    # zip + npy framing is a few hundred bytes on top of the payload
    assert max(os.path.getsize(f) for f in files) < 4096 + 1024
    out = wd.get_arrays("sketches")
    assert sorted(out) == ["big", "mat", "names", "small"]
    for key, want in (("big", big), ("mat", mat), ("small", small), ("names", names)):
        assert out[key].dtype == want.dtype and np.array_equal(out[key], want)

    wd.store_arrays("sketches", compressed=False, big=big[:1500])
    assert np.array_equal(wd.get_arrays("sketches")["big"], big[:1500])
    parts = sorted(glob.glob(os.path.join(wd.location, "data", "arrays", "sketches.big.*")))
    assert len(parts) == 3
    assert not glob.glob(os.path.join(wd.location, "data", "arrays", "sketches.mat.*"))

    os.remove(parts[1])
    with pytest.raises(CorruptPayloadError, match="missing"):
        wd.get_arrays("sketches")


def test_arguments_match(tmp_path):
    wd = WorkDirectory(str(tmp_path / "wd"))
    args = {"P_ani": 0.9, "S_ani": 0.95, "genomes": ["a", "b"]}
    assert not wd.arguments_match("cluster", args)
    wd.store_arguments("cluster", args)
    assert wd.arguments_match("cluster", args)
    assert not wd.arguments_match("cluster", {**args, "S_ani": 0.99})
    # restricting keys ignores non-resume-relevant changes
    assert wd.arguments_match("cluster", {**args, "S_ani": 0.99}, keys=["P_ani", "genomes"])


def test_arguments_match_legacy_snapshot_missing_hash(tmp_path):
    """A snapshot written before the --hash flag existed must still match a
    current run with the default hash — upgrading the tool must not throw
    away byte-identical sketch caches."""
    wd = WorkDirectory(str(tmp_path / "wd"))
    legacy = {"k": 21, "sketch_size": 1000, "scale": 200, "genomes": ["a"]}
    wd.store_arguments("sketch", legacy)
    assert wd.arguments_match("sketch", {**legacy, "hash": "splitmix64"})
    assert not wd.arguments_match("sketch", {**legacy, "hash": "murmur3"})


def test_numpy_types_serializable(tmp_path):
    wd = WorkDirectory(str(tmp_path / "wd"))
    wd.store_arguments("x", {"a": np.int64(3), "b": np.float32(0.5), "c": np.array([1, 2])})
    stored = wd.get_arguments("x")
    assert stored == {"a": 3, "b": 0.5, "c": [1, 2]}


def test_subdirs_created(tmp_path):
    wd = WorkDirectory(str(tmp_path / "wd"))
    import os

    for sub in ("data", "data_tables", "figures", "log", "dereplicated_genomes"):
        assert os.path.isdir(os.path.join(wd.location, sub))


# --- the sketch cache read back in place and on threads (ISSUE 43) ---------

_FIXTURE = os.path.join(os.path.dirname(os.path.abspath(__file__)), "array_store_pr42")


def _member(kind: str) -> np.ndarray:
    rng = np.random.default_rng(43)
    return {
        "uint64": lambda: rng.integers(0, 2**63, size=5000, dtype=np.uint64),  # 10 parts of 4 KiB
        "int64": lambda: np.arange(-2000, 2500, dtype=np.int64),  # 9 parts
        "matrix": lambda: rng.integers(0, 255, size=(100, 300), dtype=np.uint8),  # rows kept whole: 8 parts
    }[kind]()


def _store(tmp_path, monkeypatch, compressed=False, **arrays) -> WorkDirectory:
    from drep_tpu import workdir

    monkeypatch.setattr(workdir, "ARRAY_PART_BYTES", 4096)
    wd = WorkDirectory(str(tmp_path / "wd"))
    wd.store_arrays("sketches", compressed=compressed, names=np.array(["a", "b"]), **arrays)
    return wd


def _grant_threads(monkeypatch, cores: int = 8) -> None:
    """The reader asks the host for its cores: grant it some, whatever this one has."""
    from drep_tpu.utils import hosttools

    monkeypatch.setattr(hosttools, "usable_cores", lambda: cores)


def _through_the_fallback(monkeypatch) -> None:
    """No file is a plain stored payload: every part takes `load_npz_checked`."""
    from drep_tpu.utils import durableio

    monkeypatch.setattr(durableio, "_stored_member", lambda f, member: None)


def _parts(wd: WorkDirectory, key: str) -> list[str]:
    return sorted(glob.glob(os.path.join(wd.location, "data", "arrays", f"sketches.{key}.*")))


@pytest.mark.parametrize("workers", [1, 6])
@pytest.mark.parametrize("kind", ["uint64", "int64", "matrix"])
def test_the_direct_reader_returns_what_todays_path_returns(tmp_path, monkeypatch, kind, workers):
    from drep_tpu.utils import durableio

    want = _member(kind)
    wd = _store(tmp_path, monkeypatch, big=want)
    _grant_threads(monkeypatch)
    monkeypatch.setattr(durableio, "READ_PIECE_BYTES", 1000)  # several pieces a part, the last one short
    arrs, did = wd.read_arrays("sketches", workers=workers)
    got = arrs["big"]
    parts = len(_parts(wd, "big"))
    assert parts >= 8
    assert {k: did[k] for k in ("members", "parts", "direct_parts", "fallback_parts", "bytes", "threads")} == {
        "members": 1, "parts": parts, "direct_parts": parts, "fallback_parts": 0,
        "bytes": want.nbytes, "threads": workers}
    assert did["seconds"] > 0
    _through_the_fallback(monkeypatch)
    arrs, did = wd.read_arrays("sketches", workers=workers)
    todays = arrs["big"]
    assert (did["direct_parts"], did["fallback_parts"], did["threads"]) == (0, parts, workers)
    for arr in (got, todays):
        assert arr.dtype == want.dtype and arr.shape == want.shape and np.array_equal(arr, want)
        assert arr.flags.c_contiguous and arr.flags.owndata  # one allocation, no view of a file
    assert sorted(wd.get_arrays("sketches")) == ["big", "names"]  # the old call still reads


@pytest.mark.parametrize("workers,cores,want", [(6, 4, 4), (2, 8, 2), (32, 64, 10), (0, 8, 1)],
                         ids=["the_cores", "the_grant", "the_parts", "none_granted"])
def test_reader_threads_follow_the_grant_the_cores_and_the_parts(tmp_path, monkeypatch, workers, cores, want):
    """No cap by the bytes beside these: a part is `ARRAY_PART_BYTES`, so the
    parts are the bytes (a 40 MB member has three)."""
    wd = _store(tmp_path, monkeypatch, big=_member("uint64"), small=np.arange(1000))  # 10 parts and 2
    _grant_threads(monkeypatch, cores)
    arrs, did = wd.read_arrays("sketches", workers=workers)
    assert did["threads"] == want and (did["members"], did["parts"], did["direct_parts"]) == (2, 12, 12)
    assert np.array_equal(arrs["small"], np.arange(1000))


def _other_member(kind: str) -> np.ndarray:
    """A member that is no plain number: each a fixed-size dtype whose buffer
    `memoryview.cast` refuses (``41w``, ``5s``, ``Zf``, a date, a record)."""
    rng = np.random.default_rng(44)
    return {
        "str": lambda: np.array([f"genome_{i:05d}_{'x' * (i % 30)}.fasta" for i in range(600)]),  # <U41
        "bytes": lambda: np.array([b"%05d" % i for i in range(3000)]),
        "datetime": lambda: np.arange(3000).astype("M8[s]"),
        "complex": lambda: (rng.random(1500) + 1j * rng.random(1500)).astype(np.complex64),
        "bool": lambda: rng.random(30000) < 0.5,
        "record": lambda: np.array([(i, i / 3) for i in range(1500)], dtype=[("a", "<i4"), ("b", "<f8")]),
    }[kind]()


@pytest.mark.parametrize("workers", [1, 6])
@pytest.mark.parametrize("kind", ["str", "bytes", "datetime", "complex", "bool", "record"])
def test_a_member_that_is_no_plain_number_loads_from_parts_on_both_readers(tmp_path, monkeypatch, kind, workers):
    """`ingest._save` stores `names` as `<U..` beside the hashes, and past
    16 MiB (some 100,000 genomes) `store_arrays` cuts it into parts like any
    other member: its bytes are placed through a uint8 view, and what the
    checked reader returned before is what is returned now."""
    want = _other_member(kind)
    wd = _store(tmp_path, monkeypatch, big=want)
    _grant_threads(monkeypatch)
    parts = len(_parts(wd, "big"))
    assert parts >= 3
    arrs, did = wd.read_arrays("sketches", workers=workers)
    assert (did["parts"], did["direct_parts"], did["fallback_parts"]) == (parts, parts, 0)
    _through_the_fallback(monkeypatch)
    todays, did = wd.read_arrays("sketches", workers=workers)
    assert (did["parts"], did["direct_parts"], did["fallback_parts"]) == (parts, 0, parts)
    for got in (arrs["big"], todays["big"]):
        assert got.dtype == want.dtype and got.shape == want.shape and np.array_equal(got, want)


def test_an_object_member_in_parts_is_refused_at_the_store_as_before(tmp_path, monkeypatch):
    """Nothing but fixed-size dtypes reaches a part: `np.savez` of an object
    array needs pickle, and the checked reader loads with `allow_pickle=False`."""
    from drep_tpu.utils.durableio import CorruptPayloadError

    wd = _store(tmp_path, monkeypatch, big=np.array([{"a": i} for i in range(3000)], dtype=object))
    with pytest.raises(CorruptPayloadError):
        wd.get_arrays("sketches", workers=6)


def _flip(path: str, member: str) -> None:
    """Flip one bit of `member` in the zip at `path`: of its last byte where
    it is stored (the checksum's own value), mid-stream where it is deflated."""
    import zipfile

    with zipfile.ZipFile(path) as zf:
        info = zf.getinfo(member)
    with open(path, "r+b") as f:
        f.seek(info.header_offset + 26)
        lens = f.read(4)
        data = info.header_offset + 30 + int.from_bytes(lens[:2], "little") + int.from_bytes(lens[2:], "little")
        stored = info.compress_type == zipfile.ZIP_STORED
        at = data + (info.compress_size - 1 if stored else info.compress_size // 2)
        f.seek(at)
        b = f.read(1)
        f.seek(at)
        f.write(bytes([b[0] ^ 0x10]))


def _damage(how: str, part: str, compressed: bool) -> None:
    from drep_tpu.utils.durableio import with_checksum

    if how == "data_byte":
        _flip(part, "part.npy")
    elif how == "crc_member":
        _flip(part, "__crc__.npy")
    elif how == "truncated":
        os.truncate(part, os.path.getsize(part) // 2)
    elif how == "zero_byte":
        os.truncate(part, 0)
    elif how == "missing":
        os.remove(part)
    elif how == "row_count":  # a sound payload of its own, one row short of what the head says
        from drep_tpu.utils.durableio import load_npz_checked

        rows = load_npz_checked(part)["part"][:-1]
        (np.savez_compressed if compressed else np.savez)(part, **with_checksum({"part": rows}))


@pytest.mark.parametrize("workers", [1, 6])
@pytest.mark.parametrize("path", ["direct", "fallback"])
@pytest.mark.parametrize("how,words", [
    ("data_byte", "checksum mismatch|unreadable"), ("crc_member", "unreadable|checksum"),
    ("truncated", "unreadable"), ("zero_byte", "unreadable"), ("missing", "is missing"),
    ("row_count", "rows, its head says"),
])
def test_a_damaged_part_is_corruption_on_both_paths(tmp_path, monkeypatch, how, words, path, workers):
    from drep_tpu.utils.durableio import CorruptPayloadError

    compressed = path == "fallback"
    wd = _store(tmp_path, monkeypatch, compressed=compressed, big=_member("uint64"),
                mat=_member("matrix"))
    _grant_threads(monkeypatch)
    did = wd.read_arrays("sketches", workers=workers)[1]
    assert did["direct_parts"] == (0 if compressed else did["parts"])
    part = _parts(wd, "mat")[5]
    _damage(how, part, compressed)
    with pytest.raises(CorruptPayloadError, match=words) as raised:
        wd.get_arrays("sketches", workers=workers)
    assert part in str(raised.value)  # the error names the part
    if (how, path) == ("data_byte", "direct"):  # the bytes were placed, then failed their checksum
        assert "in-band checksum mismatch" in str(raised.value)
    if how in ("missing", "row_count"):  # and, as before, the head to delete
        assert os.path.join("arrays", "sketches.npz") in str(raised.value)


def test_a_part_of_another_dtype_than_the_first_is_corruption(tmp_path, monkeypatch):
    from drep_tpu.utils.durableio import CorruptPayloadError, with_checksum

    wd = _store(tmp_path, monkeypatch, big=_member("uint64"))
    part = _parts(wd, "big")[2]
    np.savez(part, **with_checksum({"part": np.zeros(512, np.float64)}))
    with pytest.raises(CorruptPayloadError, match="float64"):
        wd.get_arrays("sketches")


@pytest.mark.parametrize("store", ["compressed", "no_crc", "one_compressed_part", "crc_off_at_read"])
def test_a_part_that_is_no_plain_stored_payload_loads_through_the_fallback(tmp_path, monkeypatch, store):
    """What the FILE says decides: a compressed part, and a part without
    `__crc__` (the zip's own CRC is then the only check, and zipfile's
    reader makes it), go through `load_npz_checked`; so does every part
    when this process reads with checksums off."""
    want = _member("uint64")
    if store == "no_crc":
        monkeypatch.setenv("DREP_TPU_IO_CRC", "0")
    wd = _store(tmp_path, monkeypatch, compressed=store == "compressed", big=want)
    monkeypatch.delenv("DREP_TPU_IO_CRC", raising=False)
    parts = _parts(wd, "big")
    fallback = len(parts)
    if store == "one_compressed_part":
        from drep_tpu.utils.durableio import load_npz_checked, with_checksum

        np.savez_compressed(parts[3], **with_checksum(load_npz_checked(parts[3])))
        fallback = 1
    if store == "crc_off_at_read":
        monkeypatch.setenv("DREP_TPU_IO_CRC", "0")
    _grant_threads(monkeypatch)
    arrs, did = wd.read_arrays("sketches", workers=6)
    got = arrs["big"]
    assert np.array_equal(got, want) and got.dtype == want.dtype
    assert (did["parts"], did["fallback_parts"]) == (len(parts), fallback)
    assert did["direct_parts"] + did["fallback_parts"] == did["parts"]


def test_a_one_file_cache_from_before_the_parts_loads_through_the_head(tmp_path):
    """A cache written before PR 21 is one npz with every array in it: the
    head is `load_npz_checked`'s, and no part is read by either reader."""
    from drep_tpu.utils.durableio import with_checksum

    wd = WorkDirectory(str(tmp_path / "wd"))
    want = {"scaled": _member("uint64"), "names": np.array(["a", "b"])}
    np.savez(os.path.join(wd.location, "data", "arrays", "sketches.npz"), **with_checksum(want))
    got, did = wd.read_arrays("sketches", workers=6)
    assert sorted(got) == sorted(want) and all(np.array_equal(got[k], want[k]) for k in want)
    assert {k: v for k, v in did.items() if k != "seconds"} == {
        "members": 0, "parts": 0, "direct_parts": 0, "fallback_parts": 0, "bytes": 0, "threads": 0}


@pytest.mark.parametrize("workers", [1, 6])
@pytest.mark.parametrize("path", ["direct", "fallback"])
def test_an_injected_read_fault_on_one_part_is_retried_and_past_the_budget_surfaces(
        tmp_path, monkeypatch, path, workers):
    import errno

    from drep_tpu.utils import faults
    from drep_tpu.utils.profiling import counters

    want = _member("uint64")
    wd = _store(tmp_path, monkeypatch, compressed=path == "fallback", big=want)
    _grant_threads(monkeypatch)
    monkeypatch.setenv("DREP_TPU_IO_BACKOFF_S", "0.001")
    target = os.path.basename(_parts(wd, "big")[4])
    counters.reset()
    try:
        faults.configure(f"io:io_error:1.0:max=2:path={target}")
        assert np.array_equal(wd.get_arrays("sketches", workers=workers)["big"], want)
        assert counters.faults.get("injected_io_io_error") == 2
        assert counters.faults.get("io_retries") == 2 and "io_unrecoverable" not in counters.faults
        counters.reset()
        faults.configure(f"io:stale_read:1.0:path={target}")
        with pytest.raises(OSError) as raised:
            wd.get_arrays("sketches", workers=workers)
        assert raised.value.errno == errno.ESTALE  # as itself, on the caller's thread
        assert counters.faults.get("io_unrecoverable") == 1
    finally:
        faults.configure(None)
        counters.reset()


@pytest.mark.parametrize("workers", [1, 6])
@pytest.mark.parametrize("store", ["stored", "compressed", "no_crc"])
def test_every_part_fires_the_io_site_once_a_read_whichever_reader_takes_it(tmp_path, monkeypatch, store, workers):
    """The per-part budget is the parent's: one fault site and one retried
    region a part, also where the file turns out to be the decoding reader's."""
    import collections

    from drep_tpu.utils import faults

    if store == "no_crc":
        monkeypatch.setenv("DREP_TPU_IO_CRC", "0")
    wd = _store(tmp_path, monkeypatch, compressed=store == "compressed", big=_member("uint64"))
    monkeypatch.delenv("DREP_TPU_IO_CRC", raising=False)
    _grant_threads(monkeypatch)
    fired: collections.Counter = collections.Counter()
    monkeypatch.setattr(faults, "fire_io", lambda op, path=None: fired.update([(op, path)]))
    did = wd.read_arrays("sketches", workers=workers)[1]
    assert did["fallback_parts"] == (0 if store == "stored" else did["parts"])
    head = os.path.join(wd.location, "data", "arrays", "sketches.npz")
    assert fired == {("read", p): 1 for p in [head, *_parts(wd, "big")]}


def test_a_cache_written_by_the_parents_tree_loads_through_the_direct_reader(tmp_path):
    """The format is unchanged: `tests/array_store_pr42/` holds the bytes
    PR 42's `store_arrays` wrote (`compressed=False`, parts of 1 KiB)."""
    import shutil

    wd = WorkDirectory(str(tmp_path / "wd"))
    for f in glob.glob(os.path.join(_FIXTURE, "*.npz")):
        shutil.copy(f, os.path.join(wd.location, "data", "arrays"))
    rng = np.random.default_rng(42)
    want = {"scaled": rng.integers(0, 2**63, size=400, dtype=np.uint64),
            "offsets": np.arange(0, 4000, 13, dtype=np.int64),
            "matrix": rng.integers(0, 255, size=(40, 96), dtype=np.uint8),
            "names": np.array(["a.fasta", "b.fasta"])}
    got, did = wd.read_arrays("sketches", workers=2)
    assert sorted(got) == sorted(want)
    for key, arr in want.items():
        assert got[key].dtype == arr.dtype and np.array_equal(got[key], arr), key
    assert (did["members"], did["parts"], did["direct_parts"], did["fallback_parts"]) == (3, 11, 11, 0)
    assert did["bytes"] == sum(want[k].nbytes for k in ("scaled", "offsets", "matrix"))


def test_more_reader_threads_than_cores_fill_every_row_once(tmp_path, monkeypatch):
    """The threads share one array and nothing else: each part has its own
    rows. Many small parts, more threads than cores, a short switch interval."""
    import sys

    from drep_tpu import workdir
    from drep_tpu.utils import hosttools

    rng = np.random.default_rng(7)
    want = rng.integers(0, 2**63, size=(6000, 8), dtype=np.uint64)
    monkeypatch.setattr(workdir, "ARRAY_PART_BYTES", 1024)  # 16 rows a part: 375 parts
    wd = WorkDirectory(str(tmp_path / "wd"))
    wd.store_arrays("sketches", compressed=False, big=want)
    monkeypatch.setattr(hosttools, "usable_cores", lambda: 64)
    interval = sys.getswitchinterval()
    sys.setswitchinterval(1e-6)
    try:
        for _ in range(3):
            arrs, did = wd.read_arrays("sketches", workers=32)
            got = arrs["big"]
            assert np.array_equal(got, want)
            assert (did["parts"], did["direct_parts"], did["threads"]) == (375, 375, 32)
    finally:
        sys.setswitchinterval(interval)
