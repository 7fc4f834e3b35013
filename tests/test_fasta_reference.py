"""`dereplicate` from FASTA against the plain reference (ISSUE 31): on a small
seeded planted collection (benchmark/generators/planted_fasta.py: files of
20-80 kb with runs of N, IUPAC codes, lower case, a short bin, incomplete and
contaminated members) the program's sketches equal
``benchmark.reference_fasta``'s hash for hash, its tables agree with the
reference's answers, the native and the NumPy ingest path give the same
sketches, the new spans partition their stages and the `ingest` and `filter`
counters say what the sketches and the tables hold. Every FASTA is opened once,
by the pool's `sketch_one` (ISSUE 32): the filter reads none, a genome the
quality table drops is read for its stats alone, a rerun reads nothing."""

import json
import os

import numpy as np
import pandas as pd
import pytest

from benchmark import cells, fasta_jobs
from benchmark import reference_fasta as rf

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = os.path.join(REPO, "benchmark")
SEED = 3000000031


@pytest.fixture(scope="module")
def cfg():
    cfg = cells.read_json(os.path.join(BENCH, "configs", "mag_fasta_384.json"))
    cfg["data"].update(cfg["rehearse"])
    # 16 files, every kind of file among them
    cfg["data"].update({"n": 16, "total_bases": 900_000, "short_share": 1 / 16,
                        "short_length": [20000, 30000], "n_run_share": 0.3, "lowercase_share": 0.3})
    return cfg


@pytest.fixture(scope="module")
def planted(cfg, tmp_path_factory):
    gen = cells.load_module(os.path.join(BENCH, "generators", "planted_fasta.py"))
    return gen.prepare(cfg, SEED, str(tmp_path_factory.mktemp("planted_fasta")))["data"]


@pytest.fixture(scope="module")
def reference(cfg, planted):
    sketches, quality, want = fasta_jobs.reference_answers(planted, cfg["params"])
    return {"sketches": dict(zip(planted.names, sketches)), "quality": quality, "want": want}


@pytest.fixture(scope="module")
def job(cfg, planted, tmp_path_factory):
    """One `dereplicate` through the CLI's own function, on an empty workdir."""
    from drep_tpu.utils import telemetry

    mix = cells.read_json(os.path.join(BENCH, "traffic", "dereplicate.json"))
    wd = str(tmp_path_factory.mktemp("fasta_job") / "wd")
    out = fasta_jobs.run_job(mix["argv"], planted, wd)
    telemetry.configure()
    assert out["error"] is None, out["error"]
    fasta_jobs._read_record(out)
    return {**out, "mix": mix}


def test_the_planted_files_hold_every_kind_of_file(planted, reference):
    texts = [open(p, "rb").read() for p in planted.paths]
    bodies = [b"".join(line for line in t.split(b"\n") if not line.startswith(b">")) for t in texts]
    assert sum(b"NNNNNNNNNN" in b for b in bodies) >= 1
    assert sum(any(c in b for c in b"RYKMSW") for b in bodies) >= 1
    assert sum(any(c in b for c in b"acgt") for b in bodies) >= 1
    assert planted.short.sum() == 1 and (planted.completeness < 75).sum() >= 1
    assert (planted.contamination > 1).sum() >= 1
    assert all(len(line) <= 80 for line in texts[0].split(b"\n") if not line.startswith(b">"))
    # the invalid characters cost k-mers: fewer valid windows than a clean file of its size has
    lost = [s["length"] - 20 * s["contigs"] - s["valid_kmers"] for s in reference["sketches"].values()]
    assert max(lost) > 100 and min(lost) == 0
    kept = reference["want"]["kept"]
    assert 4 <= len(kept) < 16 and len(set(reference["want"]["secondary"].values())) < len(kept)


@pytest.mark.parametrize("what", ["bottom", "scaled"])
def test_the_programs_sketches_are_the_references_hash_for_hash(job, reference, what):
    cache = fasta_jobs.read_sketches(job["workdir"])
    kept = reference["want"]["kept"]
    assert sorted(cache) == sorted(kept)
    slot = {"bottom": 0, "scaled": 1}[what]
    for g in kept:
        assert cache[g][slot].dtype == np.uint64
        assert np.array_equal(cache[g][slot], reference["sketches"][g][what]), g
    # at this size a scaled sketch is under 1,000 hashes: the bottom sketch came from the full set
    assert all(len(reference["sketches"][g]["scaled"]) < 1000 == len(cache[g][0]) for g in kept)


def test_native_and_numpy_ingest_give_the_same_sketches(planted, reference, monkeypatch):
    from drep_tpu import native
    from drep_tpu.ingest import make_bdb, sketch_genomes

    if native.get_library() is None:
        pytest.skip("native library unavailable (no g++?)")
    bdb = make_bdb(planted.paths)
    via_native = sketch_genomes(bdb, processes=1)
    monkeypatch.setenv("DREP_TPU_NO_NATIVE", "1")
    via_numpy = sketch_genomes(bdb, processes=1)
    assert via_native.gdb.equals(via_numpy.gdb)
    for i, g in enumerate(via_native.names):
        for mine, theirs in ((via_native.bottom[i], via_numpy.bottom[i]),
                             (via_native.scaled[i], via_numpy.scaled[i])):
            assert np.array_equal(mine, theirs), g
        assert np.array_equal(via_numpy.scaled[i], reference["sketches"][g]["scaled"])
        assert tuple(via_numpy.gdb.iloc[i][["length", "N50", "contigs"]]) == tuple(
            reference["sketches"][g][k] for k in ("length", "N50", "contigs"))


@pytest.mark.parametrize("table", ["genomeInformation", "Bdb", "Cdb", "Wdb", "Sdb"])
def test_the_tables_agree_with_the_reference(job, reference, cfg, table):
    want = reference["want"]
    df = pd.read_csv(os.path.join(job["workdir"], "data_tables", table + ".csv"))
    if table == "genomeInformation":
        assert {g: (a, b, c) for g, a, b, c in zip(df["genome"], df["length"], df["N50"], df["contigs"])} \
            == {g: (s["length"], s["N50"], s["contigs"]) for g, s in reference["sketches"].items()}
    elif table == "Bdb":
        assert sorted(df["genome"]) == sorted(want["kept"])
    elif table == "Cdb":
        for level in ("primary", "secondary"):
            got = dict(zip(df["genome"], df[level + "_cluster"]))
            assert rf.partition_mismatch(got, want[level]) == 0
    elif table == "Wdb":
        assert set(df["genome"]) == set(want["winners"].values())
    else:
        assert sorted(df["genome"]) == sorted(want["kept"]) and df["quality_informed"].all()
        err = max(abs(s - want["score"][g]) for g, s in zip(df["genome"], df["score"]))
        assert err < job["mix"]["limits"]["score"]


def test_the_cells_own_comparison_passes_and_prints_every_number(job, reference, planted, cfg, capsys):
    from benchmark import check

    want = reference["want"]
    got = fasta_jobs.read_answers(job["workdir"], want["kept"])
    out = fasta_jobs.compare_sketches(fasta_jobs.read_sketches(job["workdir"]),
                                      list(reference["sketches"].values()), planted.names,
                                      want["kept"], got["stats"])
    out += fasta_jobs.compare_answers(got, want, cfg["params"], job["mix"]["limits"])
    assert check.report(out) and len(out) == 12
    assert sum(c["limit"] == 0 for c in out) == 8 and all(np.isfinite(c["value"]) for c in out)
    assert "WRONG" not in capsys.readouterr().out
    # a sketch off by one hash, a dropped genome and a moved score are each caught
    cache = fasta_jobs.read_sketches(job["workdir"])
    first = want["kept"][0]
    cache[first] = (cache[first][0], cache[first][1][:-1])
    bad = fasta_jobs.compare_sketches(cache, list(reference["sketches"].values()), planted.names,
                                      want["kept"], got["stats"])
    assert [c["value"] for c in bad] == [0, 0, 1]
    fewer = {**got, "kept": got["kept"][1:]}
    assert [c["ok"] for c in fasta_jobs.compare_answers(fewer, want, cfg["params"], job["mix"]["limits"])] == [False]
    moved = {**got, "score": {**got["score"], first: got["score"][first] + 1e-3}}
    assert not all(c["ok"] for c in fasta_jobs.compare_answers(moved, want, cfg["params"], job["mix"]["limits"]))


@pytest.mark.parametrize("stage,inside,holds_only", [
    ("stage:filter", {"filter/quality", "tables_io"}, True),  # both halves: no FASTA is read in it
    ("stage:ingest_or_cache", {"ingest/sketch", "ingest/cache_save"}, True),
    ("ingest/sketch", {"ingest/pool_start", "ingest/shard_flush"}, False),  # its self time is the wait
    ("stage:choose", {"choose/tables", "choose/score", "choose/copy"}, True),
    ("choose/score", {"choose/centrality"}, False),
    ("stage:evaluate", {"evaluate/tables", "evaluate/warnings"}, True),
])
def test_the_new_spans_partition_their_stage(job, stage, inside, holds_only):
    ph = job["record"]["phases"]
    assert inside <= set(ph) and all(ph[n]["thread"] == "main" for n in inside | {stage})
    if holds_only:  # what the spans inside leave of the stage is a sliver of it
        assert ph[stage]["self_seconds"] <= 0.2 * ph[stage]["seconds"] + 0.02, ph[stage]
    # tables_io is also booked outside stage:filter: held by the stage's self seconds above
    assert all(ph[n]["seconds"] <= ph[stage]["seconds"] + 1e-3 for n in inside - {"tables_io"})
    mains = sum(p["self_seconds"] for p in ph.values() if p["thread"] == "main")
    assert mains == pytest.approx(ph["job"]["seconds"], rel=0.01)


@pytest.fixture(scope="module")
def serial_job(planted, tmp_path_factory):
    """The same job with `-p 1`, so that every `sketch_one` call is in this
    process and can be counted; then the same command again on its work
    directory. Every open of a planted file from Python is recorded too, by
    the module that opened it."""
    import builtins
    import sys

    from drep_tpu import ingest
    from drep_tpu.utils import telemetry

    mix = cells.read_json(os.path.join(BENCH, "traffic", "dereplicate.json"))
    wd = str(tmp_path_factory.mktemp("fasta_serial") / "wd")
    argv = fasta_jobs.job_argv(mix["argv"] + ["-p", "1"], wd, planted)
    calls: list[tuple] = []
    opened: list[str] = []
    real_sketch, real_open = ingest._sketch_one, builtins.open

    def counted(job):
        calls.append(job)
        return real_sketch(job)

    def recording(file, *a, **k):
        if file in planted.paths:
            opened.append(sys._getframe(1).f_globals["__name__"])
        return real_open(file, *a, **k)

    from drep_tpu import controller

    runs = []
    mp = pytest.MonkeyPatch()
    mp.setattr(ingest, "_sketch_one", counted)
    mp.setattr(builtins, "open", recording)
    try:
        for _ in range(2):
            calls.clear()
            controller.main(argv)
            with real_open(os.path.join(wd, "log", "perf_counters.json")) as f:
                runs.append({"calls": list(calls), "record": json.load(f)})
    finally:
        mp.undo()
        telemetry.configure()
    return {"workdir": wd, "first": runs[0], "again": runs[1], "opened": opened}


def test_every_fasta_is_opened_once_by_the_pool_and_by_nothing_else(serial_job, job, planted, reference, cfg):
    p = cfg["params"]
    first = serial_job["first"]
    # one sketch_one call a path of the input Bdb; beside the kernel's, no open of a FASTA from Python
    # but the copies of the winners
    assert sorted(c[1] for c in first["calls"]) == sorted(planted.paths)
    assert set(serial_job["opened"]) == {"shutil"}
    by_table = {g for g, c, x in zip(planted.names, planted.completeness, planted.contamination)
                if c < p["completeness"] or x > p["contamination"]}
    assert {c[0] for c in first["calls"] if len(c) == 2} == by_table and by_table
    ph, ingest, booked = first["record"]["phases"], first["record"]["ingest"], first["record"]["filter"]
    assert "filter/fasta_stats" not in ph and ph["stage:filter"]["calls"] == 2
    assert ph["ingest/sketch"]["calls"] == 1 and ph["stage:ingest_or_cache"]["calls"] == 2
    kept = reference["want"]["kept"]
    short = [g for g in planted.names if reference["sketches"][g]["length"] < p["length"] and g not in by_table]
    assert ingest["stats_only_genomes"] == len(by_table) and ingest["sketched_then_dropped"] == len(short) >= 1
    assert ingest["stats_only_genomes"] + ingest["sketched_then_dropped"] + ingest["genomes"] == booked["genomes"] == 16
    assert ingest["stats_only_bases"] == sum(reference["sketches"][g]["length"] for g in by_table)
    assert ingest["sketched_then_dropped_bases"] == sum(reference["sketches"][g]["length"] for g in short)
    assert ingest["stats_only_seconds"] > 0 and ingest["sketched_then_dropped_seconds"] > 0
    assert ingest["genomes"] == len(kept) and ingest["bases"] == sum(reference["sketches"][g]["length"] for g in kept)
    # the cache holds the kept genomes and no other, in either job; the pooled job books the same counts
    bdb = pd.read_csv(os.path.join(serial_job["workdir"], "data_tables", "Bdb.csv"))
    assert sorted(fasta_jobs.read_sketches(serial_job["workdir"])) == sorted(bdb["genome"]) == sorted(kept)
    pooled = job["record"]["ingest"]
    assert {k: v for k, v in pooled.items() if not k.endswith("seconds") and k != "workers"} \
        == {k: v for k, v in ingest.items() if not k.endswith("seconds") and k != "workers"}
    for table in ("genomeInformation", "Bdb", "Gdb", "genomeInfo", "Cdb", "Sdb", "Wdb"):
        with open(os.path.join(serial_job["workdir"], "data_tables", table + ".csv"), "rb") as a, \
                open(os.path.join(job["workdir"], "data_tables", table + ".csv"), "rb") as b:
            assert a.read() == b.read(), table


def test_a_second_dereplicate_on_the_work_directory_reads_no_fasta(serial_job):
    again = serial_job["again"]
    assert again["calls"] == [] and set(serial_job["opened"]) == {"shutil"}
    assert not {"ingest/sketch", "ingest/cache_save", "filter/fasta_stats"} & set(again["record"]["phases"])
    assert "ingest" not in again["record"] and again["record"]["filter"] == serial_job["first"]["record"]["filter"]


def test_a_candidate_with_no_sequence_is_dropped_by_length_and_raises_nothing(planted, serial_job, tmp_path):
    """As before this PR: length 0, dropped by -l, never the "no valid k-mers"
    error, which is for genomes the filter keeps."""
    from drep_tpu.errors import UserInputError
    from drep_tpu.utils import telemetry
    from drep_tpu.workflows import dereplicate_wrapper

    hollow = tmp_path / "hollow.fa"
    hollow.write_text(">only a header\n")
    quality = pd.read_csv(planted.genome_info)
    quality.loc[len(quality)] = {"genome": "hollow.fa", "completeness": 99.0, "contamination": 0.0}
    wd = str(tmp_path / "wd")
    dereplicate_wrapper(wd, planted.paths + [str(hollow)], genomeInfo=quality, skip_plots=True, processes=1)
    info = pd.read_csv(os.path.join(wd, "data_tables", "genomeInformation.csv")).set_index("genome")
    assert tuple(info.loc["hollow.fa"]) == (0, 0, 0) and len(info) == 17
    assert "hollow.fa" not in fasta_jobs.read_sketches(wd)
    with open(os.path.join(wd, "log", "perf_counters.json")) as f:
        ingest = json.load(f)["ingest"]
    without = serial_job["first"]["record"]["ingest"]
    assert ingest["sketched_then_dropped"] == without["sketched_then_dropped"] + 1
    assert ingest["stats_only_genomes"] == without["stats_only_genomes"] and ingest["genomes"] == without["genomes"]
    # kept, it is the error it always was
    with pytest.raises(UserInputError, match="no FASTA records with valid nucleotide"):
        dereplicate_wrapper(str(tmp_path / "wd2"), planted.paths + [str(hollow)], genomeInfo=quality,
                            skip_plots=True, processes=1, length=0)
    telemetry.configure()


def test_without_a_quality_table_every_genome_is_sketched(planted, reference, cfg, tmp_path):
    from drep_tpu.utils import telemetry
    from drep_tpu.workflows import dereplicate_wrapper

    wd = str(tmp_path / "wd")
    dereplicate_wrapper(wd, planted.paths, ignoreGenomeQuality=True, skip_plots=True, processes=2)
    telemetry.configure()
    with open(os.path.join(wd, "log", "perf_counters.json")) as f:
        rec = json.load(f)
    long_enough = [g for g in planted.names if reference["sketches"][g]["length"] >= cfg["params"]["length"]]
    assert rec["ingest"]["stats_only_genomes"] == 0 and rec["ingest"]["genomes"] == len(long_enough)
    assert rec["ingest"]["sketched_then_dropped"] == 16 - len(long_enough) == rec["filter"]["dropped_length"]
    tables = os.path.join(wd, "data_tables")
    info = pd.read_csv(os.path.join(tables, "genomeInformation.csv"))
    assert {g: (a, b, c) for g, a, b, c in zip(info["genome"], info["length"], info["N50"], info["contigs"])} \
        == {g: (s["length"], s["N50"], s["contigs"]) for g, s in reference["sketches"].items()}
    assert list(pd.read_csv(os.path.join(tables, "Bdb.csv"))["genome"]) == long_enough
    assert sorted(fasta_jobs.read_sketches(wd)) == sorted(long_enough)
    assert not os.path.exists(os.path.join(tables, "genomeInfo.csv"))
    cache = fasta_jobs.read_sketches(wd)
    assert all(np.array_equal(cache[g][1], reference["sketches"][g]["scaled"]) for g in long_enough)


def test_the_ingest_counter_says_what_the_sketches_hold(job, reference, planted):
    ingest = job["record"]["ingest"]
    kept = reference["want"]["kept"]
    sk = [reference["sketches"][g] for g in kept]
    size = {os.path.basename(p): os.path.getsize(p) for p in planted.paths}
    assert ingest["genomes"] == len(kept) == job["kept"]
    assert ingest["bases"] == sum(s["length"] for s in sk)
    assert ingest["valid_kmers"] == sum(s["valid_kmers"] for s in sk)
    assert ingest["bottom_hashes"] == sum(len(s["bottom"]) for s in sk)
    assert ingest["scaled_hashes"] == sum(len(s["scaled"]) for s in sk)
    assert ingest["file_bytes"] == sum(size[g] for g in kept)
    assert ingest["path"] == job["record"]["notes"]["ingest_path"] == "native"
    assert ingest["workers"] == 6 and 0 < ingest["busy_seconds"]
    # the workers' own seconds fit inside workers x the span that waited for them
    span = job["record"]["phases"]["ingest/sketch"]["seconds"]
    assert ingest["busy_seconds"] <= ingest["workers"] * span


def test_the_filter_counter_says_what_was_dropped_and_why(job, reference, planted, cfg):
    p = cfg["params"]
    booked = job["record"]["filter"]
    long_enough = np.array([reference["sketches"][g]["length"] >= p["length"] for g in planted.names])
    assert booked["genomes"] == 16 and booked["dropped_length"] == int((~long_enough).sum()) >= 1
    assert booked["dropped_completeness"] == int((long_enough & (planted.completeness < p["completeness"])).sum())
    assert booked["dropped_contamination"] == int((long_enough & (planted.contamination > p["contamination"])).sum())
    assert 16 - len(reference["want"]["kept"]) <= sum(booked[k] for k in booked if k != "genomes")


def test_sketch_one_carries_its_seconds_on_either_path(planted, monkeypatch):
    from drep_tpu.sketch_worker import sketch_one

    job = ("g", planted.paths[0], 21, 1000, 200, "splitmix64")
    _, native = sketch_one(job)
    monkeypatch.setenv("DREP_TPU_NO_NATIVE", "1")
    _, plain = sketch_one(job)
    for res in (native, plain):
        assert res["seconds"] > 0 and res["file_bytes"] == os.path.getsize(planted.paths[0])
    assert native["valid_kmers"] == plain["valid_kmers"] > 0


def test_a_compare_job_books_neither_counter_and_none_of_the_new_stage_spans(tmp_path, genome_paths):
    """The cells that start at a planted cache never reach ingest/sketch,
    stage:filter or stage:choose: their records carry no `ingest` on a cache hit."""
    from drep_tpu.utils import telemetry
    from drep_tpu.workflows import compare_wrapper

    wd = str(tmp_path / "wd")
    compare_wrapper(wd, genome_paths, skip_plots=True)
    with open(os.path.join(wd, "log", "perf_counters.json")) as f:
        first = json.load(f)
    assert first["ingest"]["genomes"] == 5 and "filter" not in first
    # the single read's engagement keys are `dereplicate`'s alone
    assert not [k for k in first["ingest"] if k.startswith(("stats_only", "sketched_then_dropped"))]
    os.remove(os.path.join(wd, "data_tables", "Cdb.csv"))  # recompute from the sketch cache
    compare_wrapper(wd, skip_plots=True)
    telemetry.configure()
    with open(os.path.join(wd, "log", "perf_counters.json")) as f:
        again = json.load(f)
    assert "ingest" not in again and "filter" not in again
    assert not {"ingest/sketch", "stage:filter", "stage:choose"} & set(again["phases"])
    assert {"evaluate/tables", "evaluate/warnings"} <= set(again["phases"])
