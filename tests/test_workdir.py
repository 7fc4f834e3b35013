"""WorkDirectory: the persistence/checkpoint substrate (SURVEY.md §5.4)."""

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
