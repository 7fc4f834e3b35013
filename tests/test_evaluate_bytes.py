"""`log/warnings.txt` byte for byte (ISSUE 35): the stage reads the pair
tables as columns and assembles the lines as bytes; the spelling it replaced
is kept here as the oracle (`parent_warnings`: masks over string frames, an
f-string a surviving row), run over the tables as `store_db` writes them and
`get_db` reads them back. Every case is held to it three ways: the stage on
a work directory that holds nothing (`disk`), the stage after the hand-over
`d_cluster_wrapper` makes (`job`, or `disk` again where a name would not
read back as the string it is), and `evaluate_warnings` over the frames.
"""

import io
import json
import os

import numpy as np
import pandas as pd
import pytest

from drep_tpu import evaluate, schemas
from drep_tpu.cluster import controller, pairs
from drep_tpu.utils.profiling import counters
from drep_tpu.workdir import WorkDirectory


def parent_warnings(mdb, ndb, cdb, wdb, **kwargs) -> bytes:
    """`evaluate_warnings` as it was before ISSUE 35, and the bytes
    `d_evaluate_wrapper` wrote of it."""
    kw = dict(evaluate.EVALUATE_DEFAULTS)
    kw.update({k: v for k, v in kwargs.items() if v is not None and k in evaluate.EVALUATE_DEFAULTS})
    warnings: list[str] = []
    winners = set(wdb["genome"])
    cluster_of = cdb.set_index("genome")["secondary_cluster"]
    if mdb is not None and len(mdb):
        close = mdb[
            (mdb["genome1"] < mdb["genome2"])
            & mdb["genome1"].isin(winners)
            & mdb["genome2"].isin(winners)
            & (mdb["dist"] <= kw["warn_dist"])
        ]
        warnings += [
            f"Primary: winners {g1} and {g2} have Mash "
            f"distance {d:.4f} (<= warn_dist {kw['warn_dist']})"
            for g1, g2, d in zip(close["genome1"], close["genome2"], close["dist"])
        ]
    if ndb is not None and len(ndb):
        sub = ndb[
            (ndb["querry"] < ndb["reference"])
            & ndb["querry"].isin(winners)
            & ndb["reference"].isin(winners)
            & (ndb["ani"] >= kw["warn_sim"])
        ]
        split = sub["querry"].map(cluster_of).to_numpy() != sub["reference"].map(cluster_of).to_numpy()
        sub = sub[split]
        warnings += [
            f"Secondary: winners {a} and {b} are in different secondary "
            f"clusters but have ANI {ani:.4f} (>= warn_sim {kw['warn_sim']})"
            for a, b, ani in zip(sub["querry"], sub["reference"], sub["ani"])
        ]
        low = ndb[
            (ndb["querry"] < ndb["reference"])
            & (ndb["alignment_coverage"] > 0)
            & (ndb["alignment_coverage"] <= kw["warn_aln"])
        ]
        warnings += [
            f"Coverage: {q} vs {r} aligned only "
            f"{c:.3f} (<= warn_aln {kw['warn_aln']})"
            for q, r, c in zip(low["querry"], low["reference"], low["alignment_coverage"])
        ]
    return "".join(w + "\n" for w in warnings).encode()


def parent_stage(wd: WorkDirectory, **kwargs) -> bytes:
    """What the stage read before ISSUE 35: every table back from disk."""
    mdb = wd.get_db("Mdb") if wd.hasDb("Mdb") else None
    ndb = wd.get_db("Ndb") if wd.hasDb("Ndb") else None
    cdb = wd.get_db("Cdb")
    wdb = wd.get_db("Wdb") if wd.hasDb("Wdb") else pd.DataFrame({"genome": cdb["genome"]})
    return parent_warnings(mdb, ndb, cdb, wdb, **kwargs)


# --- the tables of a case, built the way the cluster stage builds them -----


def _names(n: int) -> list[str]:
    return [f"genome_{i:03d}.fasta" for i in range(n)]


def _dist(rng, n: int) -> np.ndarray:
    """A symmetric float32 distance matrix, zero diagonal, about a third of it under 0.25."""
    d = rng.uniform(0.0, 0.75, (n, n)).astype(np.float32)
    d = np.maximum(d, d.T)
    np.fill_diagonal(d, 0.0)
    return d


def _ndb(rng, names, groups) -> pd.DataFrame:
    """Ndb rows of every ordered pair inside each group of names, as
    `directional_ndb` lays them out; ANI 0.93-1, coverage 0-1."""
    parts = []
    for pc, members in enumerate(groups, start=1):
        m = len(members)
        ani = rng.uniform(0.93, 1.0, (m, m)).astype(np.float32)
        cov = rng.uniform(0.0, 1.0, (m, m)).astype(np.float32)
        parts.append(pairs.directional_ndb([names[i] for i in members], ani, cov, pc))
    return pd.concat(parts, ignore_index=True) if parts else schemas.empty("Ndb")


def _cdb(names, secondary, primary=None) -> pd.DataFrame:
    return pd.DataFrame({
        "genome": names, "secondary_cluster": secondary, "threshold": 0.05,
        "cluster_method": "average", "comparison_algorithm": "jax_ani",
        "primary_cluster": primary if primary is not None else 1,
    })


def _usual(rng, names, mdb=None) -> dict:
    """A dense Mdb and two primary clusters' Ndb over `names`, secondary
    clusters of three: some high-ANI pairs fall across them."""
    n = len(names)
    half = n // 2
    secondary = [f"{1 + (i >= half)}_{1 + i % 3}" for i in range(n)]
    return {
        "Mdb": controller._mdb_from_dist(_dist(rng, n), names, 10_000, 0.9, 0.25) if mdb is None else mdb,
        "Ndb": _ndb(rng, names, [range(half), range(half, n)]),
        "Cdb": _cdb(names, secondary, [1 + (i >= half) for i in range(n)]),
    }


def _ties() -> np.ndarray:
    """float32 distances at `.4f` ties: values whose shortest text, parsed
    (what `read_csv` hands the stage), and whose widened float64 round to
    different fourth decimals."""
    at = (np.arange(1, 2400, dtype=np.float64) * 1e-4 + 5e-5).astype(np.float32)
    parsed = np.array([float(str(v)) for v in at])
    differ = np.array([f"{p:.4f}" != f"{float(v):.4f}" for p, v in zip(parsed, at)])
    assert differ.sum() >= 100
    return at[differ]


def case_dense(rng):
    return _usual(rng, _names(40))


def case_thresholded(rng):
    names = _names(40)  # over dense_limit: pairs up to warn_dist, both directions, and the diagonal
    return _usual(rng, names, controller._mdb_from_dist(_dist(rng, 40), names, 10, 0.9, 0.25))


def case_streaming(rng):
    names = _names(40)
    ii, jj = np.triu_indices(40, k=1)
    keep = rng.random(len(ii)) < 0.3
    edges = ii[keep], jj[keep], rng.uniform(0.0, 0.3, keep.sum()).astype(np.float32)
    return _usual(rng, names, controller._streaming_mdb(edges, names))


def case_dereplicate(rng):
    names = _names(40)
    tables = _usual(rng, names)
    winners = tables["Cdb"].iloc[::2]  # more winners than clusters: pairs of them inside and across clusters
    tables["Wdb"] = pd.DataFrame({"genome": winners["genome"], "cluster": winners["secondary_cluster"],
                                  "score": rng.uniform(80, 100, len(winners))})
    tables["genomeInformation"] = pd.DataFrame({"genome": names, "length": 5_000_000, "N50": 40_000,
                                                "contigs": 100})
    return tables


def case_skip_secondary(rng):
    names = _names(30)  # --SkipSecondary: an Ndb of no rows, secondary clusters <primary>_0
    return {"Mdb": controller._mdb_from_dist(_dist(rng, 30), names, 10_000, 0.9, 0.25),
            "Ndb": schemas.empty("Ndb"), "Cdb": _cdb(names, [f"{1 + i % 4}_0" for i in range(30)])}


def case_no_pair_tables(rng):
    names = _names(6)  # multiround primary: no Mdb at all
    return {"Ndb": schemas.empty("Ndb"), "Cdb": _cdb(names, [f"{i + 1}_0" for i in range(6)])}


def case_empty_tables(rng):
    return {"Mdb": schemas.empty("Mdb"), "Ndb": schemas.empty("Ndb"),
            "Cdb": pd.DataFrame({c: [] for c in schemas.CDB_COLUMNS})}


def case_one_genome(rng):
    names = ["only.fasta"]
    return {"Mdb": controller._mdb_from_dist(np.zeros((1, 1), np.float32), names, 10_000, 0.9, 0.25),
            "Ndb": schemas.empty("Ndb"), "Cdb": _cdb(names, ["1_0"])}


def case_non_ascii_ragged_names(rng):
    names = ["é.fa", "Escherichia_coli_str._K-12_substr._MG1655_" + "x" * 90 + ".fasta", "ß-lactam.fna", "a",
             "大腸菌.fasta", "Ω", "zz top.fa", "naïve_bin.7.fa", "b" * 31, "ａ.fa", "é.fa", "~.fa"]
    return _usual(rng, names)


def case_names_out_of_input_order(rng):
    names = ["b.fa", "a.fa", "C.fa", "10.fa", "9.fa", "_x", "Z", "aa.fa", "a-.fa", "B.fa", "a.fb", "9x"]
    return _usual(rng, names)


def case_kwargs_thresholds(rng):
    tables = _usual(rng, _names(30))
    tables["kwargs"] = {"warn_dist": 0.1, "warn_sim": 0.95, "warn_aln": None, "S_ani": 0.9, "processes": 4}
    return tables


def case_kwargs_numpy_and_int_thresholds(rng):
    tables = _usual(rng, _names(30))
    tables["kwargs"] = {"warn_dist": 1, "warn_sim": np.float64(0.5), "warn_aln": np.float32(0.5)}
    return tables


def case_coverage_edges(rng):
    names = _names(12)
    tables = _usual(rng, names)
    cov = tables["Ndb"]["alignment_coverage"].to_numpy().copy()
    cov[::4] = 0.0  # exactly 0: no warning
    cov[1::4] = 0.25  # exactly warn_aln: a warning
    cov[2::4] = np.nextafter(0.25, 1.0)
    tables["Ndb"] = tables["Ndb"].assign(alignment_coverage=cov)
    return tables


def case_dist_ties(rng):
    ties = _ties()
    n = 24
    d = np.zeros((n, n), np.float32)
    ii, jj = np.triu_indices(n, k=1)
    d[ii, jj] = d[jj, ii] = ties[: len(ii)]
    names = _names(n)
    return _usual(rng, names, controller._mdb_from_dist(d, names, 10_000, 0.9, 0.25))


def case_random_float_bits(rng):
    """10^4 float32 bit patterns as distances (every exponent, NaN, the
    infinities, both zeros) and as many float64 ones as ANI and coverage,
    under thresholds that let most of them through."""
    n = 101  # 10,201 ordered pairs
    names = _names(n)
    ii, jj = np.divmod(np.arange(n * n), n)
    arr = np.array(names)
    d = rng.integers(0, 2**32, n * n, dtype=np.uint64).astype(np.uint32).view(np.float32)
    d[:4] = [0.0, -0.0, np.inf, -np.inf]
    with np.errstate(invalid="ignore"):
        mdb = pd.DataFrame({"genome1": arr[ii], "genome2": arr[jj], "dist": d, "similarity": 1.0 - d})
    off = ii != jj
    bits = rng.integers(0, 2**63, (2, int(off.sum())), dtype=np.uint64) * 2 + rng.integers(0, 2, 1, dtype=np.uint64)
    ani, cov = bits.view(np.float64)
    cov = np.where(rng.random(len(cov)) < 0.5, rng.uniform(0, 1, len(cov)), cov)
    ndb = pd.DataFrame({"reference": arr[jj[off]], "querry": arr[ii[off]], "ani": ani, "alignment_coverage": cov,
                        "ref_coverage": cov, "querry_coverage": cov, "primary_cluster": 1})
    return {"Mdb": mdb, "Ndb": ndb, "Cdb": _cdb(names, [f"1_{1 + i % 7}" for i in range(n)]),
            "kwargs": {"warn_dist": 3e38, "warn_sim": -3e300, "warn_aln": 1e300}}


def case_names_read_as_numbers(rng):
    tables = _usual(rng, ["1", "2", "03", "10", "1e3", "7.50", "-4", "+5", ".5", "inf", "0", "100"])
    tables["source"] = "disk"  # read back they are numbers, and are compared and printed as numbers
    return tables


def case_one_name_reads_as_missing(rng):
    tables = _usual(rng, ["a.fa", "NA", "b.fa", "i.fa", "c.fa", "d.fa", "j.fa", "e.fa", "k.fa", "f.fa", "g", "h"])
    tables["source"] = "disk"
    return tables


def case_names_to_quote(rng):
    tables = _usual(rng, ["E. coli, K-12.fa", 'the "type" strain.fa', "plain.fa", " lead.fa", "trail.fa ", "x.fa",
                          "True", "y.fa", "z.fa", "w.fa", "v.fa", "u.fa"])
    tables["source"] = "disk"
    return tables


def case_winners_the_tables_lack(rng):
    tables = _usual(rng, _names(20))
    tables["Cdb"] = tables["Cdb"].iloc[3:]  # three names of the pair tables in no cluster: NaN != NaN
    tables["Wdb"] = pd.DataFrame({"genome": _names(24)[2:], "cluster": "x", "score": 1.0})
    return tables


CASES = [case_dense, case_thresholded, case_streaming, case_dereplicate, case_skip_secondary,
         case_no_pair_tables, case_empty_tables, case_one_genome, case_non_ascii_ragged_names,
         case_names_out_of_input_order, case_kwargs_thresholds, case_kwargs_numpy_and_int_thresholds,
         case_coverage_edges, case_dist_ties, case_random_float_bits, case_names_read_as_numbers,
         case_one_name_reads_as_missing, case_names_to_quote, case_winners_the_tables_lack]


def _stage(wd: WorkDirectory, **kwargs) -> tuple[bytes, dict]:
    counters.reset()
    lines = evaluate.d_evaluate_wrapper(wd, **kwargs)
    with open(wd.get_loc("warnings"), "rb") as f:
        data = f.read()
    booked = counters.report(device=False)["evaluate"]
    assert lines == sum(booked["warnings"].values()) and booked["bytes"] == len(data)
    return data, booked


@pytest.mark.parametrize("case", CASES, ids=lambda c: c.__name__[5:])
def test_warnings_txt_is_byte_for_byte_the_parents(case, rng, tmp_path):
    tables = case(rng)
    kwargs = tables.pop("kwargs", {})
    source = tables.pop("source", "job")
    wd = WorkDirectory(str(tmp_path / "wd"))
    for name, df in tables.items():
        wd.store_db(df, name)
    want = parent_stage(wd, **kwargs)
    widb = None
    if "Wdb" in tables:  # Widb as the parent wrote it: the same frames through the same `make_widb`
        stats = wd.get_db("genomeInformation") if wd.hasDb("genomeInformation") else None
        widb = evaluate.make_widb(wd.get_db("Wdb"), wd.get_db("Cdb"), stats, None).to_csv(index=False).encode()

    # a work directory that holds nothing (a resumed job, `evaluate` alone): the tables read back
    got, booked = _stage(wd, **kwargs)
    assert got == want
    pair_tables = [t for t in ("Mdb", "Ndb") if t in tables]
    assert [booked[t.lower()] for t in pair_tables] == [{"source": "disk", "rows": len(tables[t])} for t in pair_tables]
    assert booked["bytes"] == len(want) and sum(booked["warnings"].values()) == want.count(b"\n")
    for kind in ("Primary", "Secondary", "Coverage"):
        assert booked["warnings"][kind.lower()] == sum(l.startswith(kind.encode()) for l in want.splitlines())

    # the job that computed them: the hand-over `d_cluster_wrapper` makes, and no read of a pair table
    for t in pair_tables:
        controller._hold_for_evaluate(wd, tables[t], t)
    os.remove(wd.get_loc("warnings"))
    read = []
    real = WorkDirectory.get_db
    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(WorkDirectory, "get_db", lambda self, name: (read.append(name), real(self, name))[1])
        got, booked = _stage(wd, **kwargs)
    assert got == want
    assert [booked[t.lower()]["source"] for t in pair_tables] == [source] * len(pair_tables)
    assert (not set(read) & {"Mdb", "Ndb"}) == (source == "job" or not pair_tables)
    assert wd._held == {}
    if widb is not None:
        with open(os.path.join(wd.location, "data_tables", "Widb.csv"), "rb") as f:
            assert f.read() == widb

    # the frames, as callers and tests pass them
    frames = {t: wd.get_db(t) if t in tables else None for t in ("Mdb", "Ndb")}
    cdb = wd.get_db("Cdb")
    wdb = wd.get_db("Wdb") if "Wdb" in tables else pd.DataFrame({"genome": cdb["genome"]})
    lines = evaluate.evaluate_warnings(frames["Mdb"], frames["Ndb"], cdb, wdb, **kwargs)
    assert "".join(l + "\n" for l in lines).encode() == want
    if case in (case_dense, case_thresholded, case_streaming, case_dereplicate, case_dist_ties,
                case_random_float_bits, case_coverage_edges):
        assert want.count(b"Primary") >= 5 and want.count(b"Coverage") >= 3, "the case exercises nothing"


def test_the_held_float_is_the_parsed_shortest_text_not_the_float32_widened(rng):
    """The trap of ISSUE 35: at a `.4f` tie the held float32, widened,
    rounds the other way than what `read_csv` makes of its text."""
    ties = _ties()
    back = evaluate.read_back(ties)
    assert back.dtype == np.float64 and np.array_equal(back, [float(str(v)) for v in ties])
    assert not np.array_equal(back, ties.astype(np.float64))
    names = np.array(_names(2))
    n = len(ties)
    codes = np.zeros(n, np.int32), np.ones(n, np.int32)
    cdb = _cdb(list(names), ["1_1", "1_1"])
    wdb = pd.DataFrame({"genome": names})
    held = evaluate.PairColumns(pd.Index(names), *codes, {"dist": ties}, held=True)
    assert held.reads_back()
    from_text = b"".join(evaluate.warning_blocks(held, None, cdb, wdb)[0])
    widened = b"".join(evaluate.warning_blocks(evaluate.PairColumns(pd.Index(names), *codes, {"dist": ties}),
                                               None, cdb, wdb)[0])
    assert from_text.count(b"\n") == widened.count(b"\n") == n
    assert all(a != b for a, b in zip(from_text.splitlines(), widened.splitlines()))
    # and a float64 comes back an ulp off about one time in three: the held one is not trusted either
    wide = rng.uniform(0.9, 1.0, 10_000)
    assert 0.1 < (evaluate.read_back(wide) != wide).mean() < 0.6


def test_a_held_value_a_rounding_from_its_threshold_is_judged_as_read_back(rng):
    """The thresholds keep the rows the read-back values keep: held values
    are only sifted loosely, then read back and compared exactly."""
    thr = np.float32(0.25)
    near = np.array([np.nextafter(thr, 0, dtype=np.float32), thr, np.nextafter(thr, 1, dtype=np.float32)] * 2)
    n = len(near)
    names = pd.Index(_names(2))
    codes = np.zeros(n, np.int32), np.ones(n, np.int32)
    cdb = _cdb(list(names), ["1_1", "1_2"])
    wdb = pd.DataFrame({"genome": names})
    for bound in (0.25, float(near[0]), float(near[2]), 0.2500000001, 0.2499999999, float(str(near[0])),
                  float(str(near[2]))):
        for table, value, kw in (("Mdb", "dist", "warn_dist"), ("Ndb", "ani", "warn_sim"),
                                 ("Ndb", "alignment_coverage", "warn_aln")):
            values = {v: near if v == value else np.full(n, 0.5, np.float32) for v in evaluate.PAIR_TABLES[table][2]}
            held = evaluate.PairColumns(names, *codes, values, held=True)
            read = evaluate.PairColumns(names, *codes, {v: evaluate.read_back(x) for v, x in values.items()})
            args = (held, None) if table == "Mdb" else (None, held)
            args_read = (read, None) if table == "Mdb" else (None, read)
            got = b"".join(evaluate.warning_blocks(*args, cdb, wdb, **{kw: bound})[0])
            assert got == b"".join(evaluate.warning_blocks(*args_read, cdb, wdb, **{kw: bound})[0]), (bound, value)


def test_every_default_missing_value_of_read_csv_is_known():
    from pandas._libs.parsers import STR_NA_VALUES

    assert set(STR_NA_VALUES) <= evaluate._READS_AS_MISSING
    for name in ["1", "1.5", "-2", "1e5", "E", "inf", "-Infinity", "NaN", "TRUE", "false", "", " a", "a ", "a,b",
                 'a"b', "a\nb", 5, None]:
        assert not evaluate.reads_back_as_text(name), name
    for name in ["genome_A.fasta", "1_1", "e1x", "GCF_000005845.2", "é", "1 2", "0x10", "1_000", "a b"]:
        assert evaluate.reads_back_as_text(name), name
        back = pd.read_csv(io.StringIO(f"n\n{name}\n"))["n"]
        assert back.tolist() == [name]


def test_a_table_rewritten_since_the_hand_over_is_read_from_disk(rng, tmp_path):
    tables = case_dense(rng)
    wd = WorkDirectory(str(tmp_path / "wd"))
    for name, df in tables.items():
        wd.store_db(df, name)
    controller._hold_for_evaluate(wd, tables["Mdb"], "Mdb")
    controller._hold_for_evaluate(wd, tables["Ndb"], "Ndb")
    wd.store_db(tables["Mdb"].iloc[: len(tables["Mdb"]) // 2], "Mdb")  # another file than the columns were stored as
    got, booked = _stage(wd)
    assert got == parent_stage(wd)
    assert booked["mdb"] == {"source": "disk", "rows": len(tables["Mdb"]) // 2} and booked["ndb"]["source"] == "job"
    record = json.loads(json.dumps(counters.report(device=False)))  # the record's shape, as the file holds it
    assert set(record["evaluate"]) == {"mdb", "ndb", "warnings", "bytes", "distinct"}
    assert set(record["evaluate"]["warnings"]) == {"primary", "secondary", "coverage"}
