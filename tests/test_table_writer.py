"""The columnar CSV writer behind `WorkDirectory.store_db` (ISSUE 30): the
bytes of the installed pandas' `to_csv(index=False)` for every table the
pipeline stores, pandas itself for what the writer does not render, the
atomic publish kept, and the record's `tables_write`."""

import glob
import os

import numpy as np
import pandas as pd
import pytest

from drep_tpu import schemas, tablewriter
from drep_tpu.cluster import pairs
from drep_tpu.cluster.controller import _mdb_from_dist, _streaming_mdb
from drep_tpu.utils.profiling import counters
from drep_tpu.workdir import WorkDirectory

NAMES = [f"synth_{i}.fasta" for i in range(64)]


def _dist(m: int, seed: int = 0) -> np.ndarray:
    """A Mash-shaped float32 matrix: few distinct values, zero diagonal."""
    rng = np.random.default_rng(seed)
    shared = rng.integers(300, 1001, size=(m, m))
    shared = np.minimum(shared, shared.T)
    j = shared / (2000.0 - shared)
    d = (-np.log(2 * j / (1 + j)) / 21).astype(np.float32)
    np.fill_diagonal(d, 0.0)
    return d


def _dense_mdb():
    return _mdb_from_dist(_dist(64), NAMES, 10**6, 0.9, 0.25)


def _thresholded_mdb():
    return _mdb_from_dist(_dist(64), NAMES, 10, 0.9, 0.03)


def _sparse_mdb():
    d = _dist(64)
    ii, jj = np.nonzero(np.triu(d <= 0.03, 1))
    return _streaming_mdb((ii, jj, d[ii, jj]), NAMES)


def _ndb():
    rng = np.random.default_rng(1)
    parts = []
    for cluster, m in ((1, 24), (2, 9)):
        inter = rng.integers(100, 4000, size=(m, m))
        cov = (np.minimum(inter, inter.T) / rng.integers(4000, 4400, size=(m, 1))).astype(np.float32)
        ani = (np.maximum(cov, cov.T) ** (1 / 21)).astype(np.float32)
        parts.append(pairs.directional_ndb(NAMES[cluster * 24 :][:m], ani, cov, cluster))
    return pd.concat(parts, ignore_index=True)


def _cdb():
    primary = np.repeat(np.arange(1, 9), 8)
    return pd.DataFrame(
        {
            "genome": NAMES,
            "secondary_cluster": [f"{p}_{i % 3}" for i, p in enumerate(primary)],
            "threshold": 1.0 - 0.95,
            "cluster_method": "average",
            "comparison_algorithm": "jax_ani",
            "primary_cluster": primary,
        }
    )


def _bdb():
    return pd.DataFrame({"genome": NAMES, "location": [f"/data/génomes/run 7/{n}" for n in NAMES]})


def _wdb():
    return pd.DataFrame({"genome": NAMES[:5], "cluster": ["1_1", "1_2", "2_0", "3_1", "10_0"],
                         "score": [101.5, 97.25, 3.0, -12.75, 1e-05]})


def _sdb():
    rng = np.random.default_rng(2)
    return pd.DataFrame({"genome": NAMES, "score": rng.normal(90, 5, 64)})


def _genome_information():
    rng = np.random.default_rng(3)
    return pd.DataFrame({"genome": NAMES, "length": rng.integers(2_000_000, 6_000_000, 64),
                         "N50": rng.integers(10_000, 900_000, 64),
                         "contigs": rng.integers(1, 400, 64).astype(np.int32)})


def _float_edges(dtype):
    info = np.finfo(dtype)
    bits = np.random.default_rng(4).integers(0, 2 ** (8 * info.bits // 8), 10_000, dtype=f"u{info.bits // 8}")
    noise = bits.view(dtype)
    edges = np.array([0.0, -0.0, 1.0, -1.0, 1e-05, 1e16, 1e15, 0.0001, 123456789.125, np.inf, -np.inf,
                      info.max, info.min, info.tiny, info.smallest_subnormal, -info.smallest_subnormal,
                      info.eps, 1 / 3, 2 / 3, 0.1], dtype=dtype)
    values = np.concatenate([edges, noise[~np.isnan(noise)]])
    return pd.DataFrame({"x": values, "y": values[::-1], "n": np.arange(len(values))})


def _int_edges():
    return pd.DataFrame({"a": np.array([0, -1, 1, np.iinfo(np.int64).min, np.iinfo(np.int64).max]),
                         "b": np.array([0, 1, 2, 3, np.iinfo(np.uint64).max], dtype=np.uint64),
                         "c": np.array([-128, 127, 0, 5, 5], dtype=np.int8),
                         "name": pd.Series(["ä", "b c", "'q'", "x;y", "tab\there"], dtype=object)})


def _long():
    rng = np.random.default_rng(5)
    n = 50_000
    return pd.DataFrame({"g": np.array(NAMES)[rng.integers(0, 64, n)],
                         "d": rng.integers(0, 1000, n).astype(np.float32) / np.float32(1000),
                         "w": rng.random(n), "k": rng.integers(0, 5, n)})


TABLES = {
    "dense_mdb": _dense_mdb,
    "thresholded_mdb": _thresholded_mdb,
    "streaming_mdb": _sparse_mdb,
    "ndb_two_clusters": _ndb,
    "cdb": _cdb,
    "bdb": _bdb,
    "wdb": _wdb,
    "sdb": _sdb,
    "genome_information": _genome_information,
    "empty_ndb": lambda: schemas.empty("Ndb"),
    "empty_typed": lambda: _ndb().iloc[:0],
    "one_row": lambda: _cdb().iloc[:1],
    "one_column": lambda: _sdb()[["score"]],
    "duplicate_labels": lambda: pd.concat([_sdb(), _sdb()], axis=1),
    "more_rows_than_a_block": _long,
    "float64_edges": lambda: _float_edges(np.float64),
    "float32_edges": lambda: _float_edges(np.float32),
    "int_edges": _int_edges,
}


@pytest.mark.parametrize("case", sorted(TABLES))
def test_bytes_are_pandas_bytes(case, tmp_path, monkeypatch):
    df = TABLES[case]()
    if case == "more_rows_than_a_block":  # several chunks of several blocks, the last of each short
        monkeypatch.setattr(tablewriter, "CHUNK_ROWS", 17_001)
        monkeypatch.setattr(tablewriter, "BLOCK_BYTES", 1 << 16)
    path = str(tmp_path / "t.csv")
    done = tablewriter.write_csv(df, path)
    with open(path, "rb") as f:
        got = f.read()
    assert got == df.to_csv(index=False).encode()
    assert done["fallback"] is None
    assert (done["rows"], done["bytes"], done["values"]) == (len(df), len(got), df.size)
    assert (done["distinct"] > 0) == (len(df) > 0)
    if case in ("dense_mdb", "ndb_two_clusters"):
        # names and a float column's repeats are rendered once, not once a row
        assert done["distinct"] < done["values"] / 3
    if case == "ndb_two_clusters":
        # alignment_coverage, querry_coverage and the transposed ref_coverage hold one set of values
        cov_values = len(set(df["ani"]) | set(df["alignment_coverage"]))
        assert done["distinct"] == cov_values + 2 * df["querry"].nunique() + df["primary_cluster"].nunique()


def _with(df: pd.DataFrame, **cols) -> pd.DataFrame:
    return df.assign(**cols)


FALLBACKS = {
    "nan": (lambda: _with(_sdb(), score=[np.nan] + [1.5] * 63), "missing value"),
    "nan_float32": (lambda: _with(_sdb(), score=np.array([np.nan] + [1.5] * 63, np.float32)), "missing value"),
    "none": (lambda: _with(_sdb(), genome=[None] + NAMES[1:]), "missing value"),
    "none_in_objects": (lambda: _with(_sdb(), genome=pd.Series([None] + NAMES[1:], dtype=object)), "missing value"),
    "comma": (lambda: _with(_sdb(), genome=["a,b"] + NAMES[1:]), "string that needs quoting"),
    "quote": (lambda: _with(_sdb(), genome=['a"b'] + NAMES[1:]), "string that needs quoting"),
    "newline": (lambda: _with(_sdb(), genome=["a\nb"] + NAMES[1:]), "string that needs quoting"),
    "carriage_return": (lambda: _with(_sdb(), genome=["a\rb"] + NAMES[1:]), "string that needs quoting"),
    "empty_string": (lambda: _sdb()[["genome"]].replace(NAMES[0], ""), "string that needs quoting"),
    "bool": (lambda: _with(_sdb(), quality_informed=False), "dtype bool"),
    "categorical": (lambda: _with(_sdb(), genome=pd.Categorical(NAMES)), "dtype category"),
    "datetime": (lambda: _with(_sdb(), at=pd.Timestamp("2026-09-28")), "dtype datetime64[us]"),
    "nullable_int": (lambda: _with(_sdb(), k=pd.array([1] * 64, dtype="Int64")), "dtype Int64"),
    "mixed_objects": (lambda: _with(_sdb(), genome=pd.Series([7] + NAMES[1:], dtype=object)), "mixed object column"),
    "label_not_a_string": (lambda: _sdb().rename(columns={"score": 0}), "column labels that are not plain strings"),
    "label_with_comma": (lambda: _sdb().rename(columns={"score": "a,b"}), "column labels that are not plain strings"),
    "ragged_strings": (lambda: _with(_long(), g=["x" * 5000] + ["y"] * 49_999), "ragged strings"),
}


@pytest.mark.parametrize("case", sorted(FALLBACKS))
def test_what_the_writer_cannot_render_is_pandas_own(case, tmp_path):
    make, reason = FALLBACKS[case]
    df = make()
    wd = WorkDirectory(str(tmp_path / "wd"))
    counters.reset()
    try:
        wd.store_db(df, "Sdb")
        booked = counters.report(device=False)["tables_write"]["Sdb"]
    finally:
        counters.reset()
    want = df.to_csv(index=False).encode()
    with open(wd._table_loc("Sdb"), "rb") as f:
        assert f.read() == want
    assert booked == {"calls": 1, "rows": len(df), "bytes": len(want), "values": df.size,
                      "distinct": 0, "fallback": 1, "fallback_reasons": {reason: 1}}


@pytest.mark.parametrize("case", ["dense_mdb", "ndb_two_clusters", "cdb", "bdb", "wdb", "genome_information",
                                  "float64_edges", "nan", "none", "comma", "quote", "newline", "bool"])
def test_store_then_get_is_the_frame(case, tmp_path):
    df = (TABLES.get(case) or FALLBACKS[case][0])()
    wd = WorkDirectory(str(tmp_path / "wd"))
    wd.store_db(df, "T")
    # float32 columns come back as float64 of their shortest text
    pd.testing.assert_frame_equal(wd.get_db("T"), df, check_dtype=False, rtol=1e-7)


def test_a_kill_inside_the_block_loop_leaves_no_table_and_no_temp(tmp_path, monkeypatch):
    df = _long()
    wd = WorkDirectory(str(tmp_path / "wd"))
    monkeypatch.setattr(tablewriter, "BLOCK_BYTES", 1 << 16)
    writes = []

    class Dies:
        def __init__(self, f):
            self.f = f

        def __enter__(self):
            self.f.__enter__()
            return self

        def __exit__(self, *exc):
            return self.f.__exit__(*exc)

        def write(self, data):
            if len(writes) == 3:  # the header and two blocks are on disk
                raise KeyboardInterrupt
            writes.append(self.f.write(data))

    monkeypatch.setattr(tablewriter, "open", lambda path, mode: Dies(open(path, mode)), raising=False)
    counters.reset()
    with pytest.raises(KeyboardInterrupt):
        wd.store_db(df, "Mdb")
    assert len(writes) == 3 and sum(writes) > 1 << 16
    assert not wd.hasDb("Mdb")
    assert glob.glob(os.path.join(wd.location, "data_tables", "*")) == []
    assert "tables_write" not in counters.report(device=False)  # nothing published, nothing booked
    monkeypatch.undo()
    wd.store_db(df, "Mdb")
    with open(wd._table_loc("Mdb"), "rb") as f:
        assert f.read() == df.to_csv(index=False).encode()
    counters.reset()


def test_a_compare_job_books_its_tables_and_its_spans_cover_the_writes(tmp_path, monkeypatch):
    """The CPU rehearsal of `ecoli_1k` (96 genomes in one primary cluster):
    the record's `tables_write`, the event log's `rows=` / `bytes=`, and
    every write inside a span that `tables_s` reads, so the containers of
    `host_unattributed_s` gained nothing."""
    import json

    from benchmark import cells
    from benchmark.batch_jobs import run_job
    from drep_tpu.utils import telemetry

    cell = cells.load_cell("ecoli_1k.secondary_deep")
    cfg = cell["config"]
    prepared = cell["generator"].prepare({**cfg, "data": {**cfg["data"], **cfg["rehearse"]}}, 9, str(tmp_path))
    written_under = {}
    write_csv = tablewriter.write_csv

    def watched(df, path):
        table = os.path.basename(path).split(".")[0]
        written_under[table] = [span.name for span in counters._stack()]
        return write_csv(df, path)

    monkeypatch.setattr(tablewriter, "write_csv", watched)
    wd = str(tmp_path / "job")
    try:
        job = run_job(cell["traffic"]["argv"] + ["--events", "on"], prepared["workdir"], wd)
    finally:
        telemetry.configure()
    assert job["error"] is None, job["error"]
    with open(os.path.join(wd, "log", "perf_counters.json")) as f:
        record = json.load(f)

    n = len(prepared["data"].names)
    booked = record["tables_write"]
    assert {"Mdb", "Ndb", "Cdb", "genomeInformation"} <= set(booked)
    for table, rows, columns in (("Mdb", n * n, 4), ("Ndb", n * (n - 1), 7), ("Cdb", n, 6)):
        ent = booked[table]
        assert (ent["calls"], ent["rows"], ent["values"], ent["fallback"]) == (1, rows, rows * columns, 0)
        assert ent["bytes"] == os.path.getsize(os.path.join(wd, "data_tables", table + ".csv"))
        assert 0 < ent["distinct"] < ent["values"]
    assert booked["Mdb"]["distinct"] < 2 * n + 2 * 1001  # two name columns, two functions of one count
    assert booked["Ndb"]["distinct"] < 2 * n + 2 * n * (n - 1) + 1  # ani, one coverage matrix, names
    assert not any("fallback_reasons" in ent for ent in booked.values())

    for table, spans in written_under.items():
        assert spans[-1] in ("tables_io", "stage:assembly_io"), (table, spans)
    assert {"Mdb", "Ndb", "Cdb"} <= set(written_under)
    phases = record["phases"]
    main = sum(p["self_seconds"] for p in phases.values() if p["thread"] == "main")
    assert main == pytest.approx(phases["job"]["seconds"], rel=0.01)

    with open(glob.glob(os.path.join(wd, "log", "events.*.jsonl"))[0]) as f:
        ends = [json.loads(line) for line in f if '"tables_io"' in line and '"E"' in line]
    noted = {e["args"]["rows"]: e["args"]["bytes"] for e in ends if "rows" in e.get("args", {})}
    assert noted[n * n] == booked["Mdb"]["bytes"] and noted[n] == booked["genomeInformation"]["bytes"]
