"""ISSUE 27: the deployment `ecoli_1k` (one deep primary cluster that holds
several planted species groups) at toy size on the CPU: its generator, its
reference, the vocabulary-chunked matmul it drives on the chip, the spans and
the counter that call books, and the comparison that decides `correct`."""

import json
import os

import numpy as np
import pytest

from benchmark import cells, check, species_jobs
from benchmark import reference as ref
from benchmark import reference_species as refs

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = os.path.join(REPO, "benchmark")
CELL = "ecoli_1k.secondary_deep"


@pytest.fixture(scope="module")
def cell():
    loaded = cells.load_cell(CELL)
    cfg = loaded["config"]
    loaded["config"] = {**cfg, "data": {**cfg["data"], **cfg["rehearse"]}}
    return loaded


def _toy(cell, seed):
    return cell["generator"].generate(cell["config"]["data"], seed)


def _secondary(cell, data, module=refs):
    p = cell["config"]["params"]
    return module.secondary_of_cluster(data.scaled, int(p["kmer_size"]), p["S_ani"], p["cov_thresh"])


# ---- (a) the generator ---------------------------------------------------------


@pytest.mark.parametrize("seed", [0, 1, 2**31 + 11])
def test_planted_species_is_a_pure_function_of_the_seed_and_plants_what_it_says(cell, seed):
    data, again = _toy(cell, seed), _toy(cell, seed)
    assert data.names == again.names
    for a, b in zip(data.scaled + data.bottom, again.scaled + again.bottom):
        assert a.dtype == np.uint64 and np.array_equal(a, b)
    assert np.array_equal(data.labels, again.labels)
    other = _toy(cell, seed + 1)
    assert not np.array_equal(data.scaled[0], other.scaled[0])
    p, d = cell["config"]["params"], cell["config"]["data"]
    n = len(data.names)
    assert n == d["n"] and len(set(data.labels.tolist())) == d["groups"]
    assert np.bincount(data.labels).max() >= d["largest_share"][0] * n - 1
    assert all(np.array_equal(b, s[: d["s_bottom"]]) for b, s in zip(data.bottom, data.scaled))
    assert all(s.max() < 2**64 // d["scale"] for s in data.scaled)
    # one primary cluster, and the planted groups under the reference
    dist = refs.mash_matrix(data.bottom, int(p["sketch_size"]), int(p["kmer_size"]))
    assert len(set(refs.primary_labels(dist, 1.0 - p["P_ani"]).tolist())) == 1
    ani, cov, labels = _secondary(cell, data)
    assert ref.partition_of(labels) == ref.partition_of(data.labels)
    same = data.labels[:, None] == data.labels[None, :]
    inside = ani[same & ~np.eye(n, dtype=bool)].min() - p["S_ani"]
    across = p["S_ani"] - ani[~same].max()
    print(f"seed {seed}: least ANI margin to {p['S_ani']}: {inside:.5f} inside a group, "
          f"{across:.5f} across groups; largest Mash distance {dist.max():.5f}")
    assert inside > 0.005 and across > 0.002
    # structure inside a group: a lineage's pairs are closer than the group's other pairs
    lineage = data.lineages[:, None] == data.lineages[None, :]
    assert ani[lineage & ~np.eye(n, dtype=bool)].min() > ani[same & ~lineage].max()
    assert np.abs(cov - cov.T).max() > 0.005  # coverage is directional


# ---- (b) the reference against the double loop ---------------------------------


@pytest.mark.parametrize("seed", [3, 4, 5])
def test_reference_species_equals_the_pairwise_reference_value_for_value(cell, seed):
    data = _toy(cell, seed)
    ani, cov, labels = _secondary(cell, data)
    ani0, cov0, labels0 = _secondary(cell, data, module=ref)
    assert np.array_equal(ani, ani0) and np.array_equal(cov, cov0)
    assert np.array_equal(labels, labels0)
    p = cell["config"]["params"]
    s, k = int(p["sketch_size"]), int(p["kmer_size"])
    # Mash too, with a few sketches cut short so that s differs by pair
    bottom = [b[: 400 + 7 * i] if i % 5 == 0 else b for i, b in enumerate(data.bottom)]
    dist = refs.mash_matrix(bottom, s, k)
    edges = ref.mash_edges(bottom, s, k)
    assert len(edges) == len(bottom) * (len(bottom) - 1) // 2
    assert all(dist[i, j] == d == dist[j, i] for (i, j), d in edges.items())


# ---- (c) the chunked call against the reference --------------------------------


def _chunked(sketches, budget, monkeypatch):
    """(ani, cov, the record) of one chunked call under a patched budget:
    the seam tests/test_rangepart.py uses."""
    import drep_tpu.ops.containment as cont
    from drep_tpu.utils.profiling import counters

    packed = cont.pack_scaled_sketches(sketches, [f"g{i}" for i in range(len(sketches))])
    monkeypatch.setattr(cont, "MATMUL_BUDGET_ELEMS", budget)
    counters.reset()
    with counters.span("job"):
        ani, cov = cont.all_vs_all_containment_matmul_chunked(packed, k=21)
        record = counters.report(device=False)
    counters.reset()
    return ani, cov, record


def _sparse_cluster(seed):
    """1,000 sketches of 100 hashes that share nothing: ids are dense ranks,
    so only rows this sparse leave the uint16 plan no cheaper than the int32
    one (both at the lane-width floor). No real cluster looks like this."""
    rng = np.random.default_rng(seed)
    return [np.unique(rng.integers(0, 1 << 60, size=100).astype(np.uint64)) for _ in range(1000)]


@pytest.mark.parametrize("plan", ["uint16", "int32"])
def test_chunked_matmul_equals_the_reference_over_several_chunks(cell, plan, monkeypatch):
    if plan == "uint16":
        sketches, budget = _toy(cell, 6).scaled, 128 * 8193  # v_chunk 8192
    else:
        sketches, budget = _sparse_cluster(6), 1024 * 65537  # v_chunk 65536
        sketches[1] = np.union1d(sketches[0][::2], sketches[1])  # one pair that shares
    ani, cov, record = _chunked(sketches, budget, monkeypatch)
    (call,) = record["secondary_chunked_calls"]
    assert call["id_dtype"] == plan and call["chunks"] >= 2, call
    want_ani, want_cov, _ = refs.secondary_of_cluster(sketches, 21, 0.95, 0.1)
    limits = cell["traffic"]["limits"]
    assert np.abs(cov - want_cov).max() <= limits["coverage"]
    assert np.abs(ani - want_ani).max() <= limits["ani"]
    # the counts themselves are exact
    lens = np.array([len(s) for s in sketches])
    assert np.array_equal(np.rint(cov.astype(np.float64) * lens[:, None]).astype(np.int64),
                          refs.intersection_counts(sketches))


# ---- (e) what the chunked call books --------------------------------------------


def test_chunked_call_books_its_spans_and_its_counter(cell, monkeypatch):
    sketches = _toy(cell, 7).scaled
    _, _, record = _chunked(sketches, 128 * 8193, monkeypatch)
    ph = record["phases"]
    assert {"secondary/chunks", "secondary/wait", "secondary/post"} <= set(ph)
    assert all(ph[n]["calls"] == 1 and ph[n]["thread"] == "main" for n in
               ("secondary/chunks", "secondary/wait", "secondary/post"))
    # nothing of the call is left to its caller's self time
    assert ph["job"]["self_seconds"] < 0.1 * ph["job"]["seconds"] + 0.05
    (call,) = record["secondary_chunked_calls"]
    hashes = sum(len(s) for s in sketches)
    vocabulary = len(np.unique(np.concatenate(sketches)))
    assert call == {
        "rows_pad": 128, "v_chunk": 8192, "chunks": -(-vocabulary // 8192), "width": call["width"],
        "id_dtype": "uint16", "calls": 1, "rows": len(sketches), "extent": vocabulary,
        "hashes": hashes, "id_slots": call["chunks"] * 128 * call["width"],
        "bytes_shipped": 2 * call["id_slots"]}
    assert call["width"] >= 128 and call["width"] & (call["width"] - 1) == 0
    assert call["id_slots"] >= hashes


def test_chunked_calls_group_by_shape_and_stay_bounded():
    from drep_tpu.utils.profiling import SECONDARY_SHAPES_MAX, Counters

    c = Counters()
    shape = dict(rows_pad=128, v_chunk=8192, chunks=6, width=1024, id_dtype="uint16")
    for extent in (40_000, 44_000):
        c.add_chunked_call(rows=96, extent=extent, hashes=400_000, id_slots=786_432,
                           bytes_shipped=1_572_864, **shape)
    for i in range(SECONDARY_SHAPES_MAX + 5):
        c.add_chunked_call(rows=70, rows_pad=128, v_chunk=8192, chunks=7 + i, width=512,
                           id_dtype="uint16", extent=50_000, hashes=1, id_slots=2, bytes_shipped=4)
    calls = c.report(device=False)["secondary_chunked_calls"]
    assert len(calls) == SECONDARY_SHAPES_MAX + 1
    first = next(x for x in calls if x["chunks"] == 6)
    assert (first["calls"], first["rows"], first["extent"], first["hashes"]) == (2, 192, 84_000, 800_000)
    rest = next(x for x in calls if x["v_chunk"] == 0)
    assert rest["rows_pad"] == 128 and rest["calls"] == 6 and rest["id_dtype"] == ""
    c.reset()
    assert "secondary_chunked_calls" not in c.report(device=False)


# ---- (d), (e), (f) whole toy jobs -------------------------------------------------


def _job(cell, pristine, wd, extra=()):
    from benchmark.batch_jobs import run_job

    job = run_job(cell["traffic"]["argv"] + list(extra), pristine, wd)
    assert job["error"] is None, job["error"]
    with open(os.path.join(wd, "log", "perf_counters.json")) as f:
        return json.load(f)


def _tables(cell, wd, data):
    return species_jobs.read_pair_tables(wd, data.names, cell["traffic"]["compare"])


def _compare(cell, tables, data):
    return species_jobs.check_species(tables, data, cell["config"]["params"],
                                      cell["traffic"]["compare"], cell["traffic"]["limits"])


@pytest.fixture(scope="module")
def toy_jobs(cell, tmp_path_factory):
    """One planted toy workdir and three `compare` jobs on copies of it: as
    the CPU dispatches it (one-shot), then twice with the per-cluster engine
    steered, in this test, to the chunked call the chip takes (event log on,
    then off)."""
    import drep_tpu.cluster.engines as engines
    import drep_tpu.ops.containment as cont
    from drep_tpu.utils import telemetry

    root = tmp_path_factory.mktemp("deep")
    prepared = cell["generator"].prepare(cell["config"], 8, str(root))
    data, pristine = prepared["data"], prepared["workdir"]
    out = {"data": data, "plain": str(root / "plain"), "on": str(root / "on"),
           "off": str(root / "off")}
    out["plain_record"] = _job(cell, pristine, out["plain"])

    def chunked_only(packed, k, **_):
        engines._count_path("matmul_chunked")
        return cont.all_vs_all_containment_matmul_chunked(packed, k=k)

    mp = pytest.MonkeyPatch()
    mp.setattr(engines, "containment_matrices", chunked_only)
    mp.setattr(cont, "MATMUL_BUDGET_ELEMS", 128 * 8193)
    try:
        out["on_record"] = _job(cell, pristine, out["on"], extra=("--events", "on"))
        telemetry.configure()
        out["off_record"] = _job(cell, pristine, out["off"])
    finally:
        mp.undo()
    return out


def test_whole_toy_compare_gives_the_references_tables_within_the_cells_limits(cell, toy_jobs):
    assert toy_jobs["plain_record"]["secondary_paths"] == {"one_shot": 1}
    comparisons = _compare(cell, _tables(cell, toy_jobs["plain"], toy_jobs["data"]), toy_jobs["data"])
    assert len(comparisons) == 8 and check.report(comparisons), comparisons
    n = len(toy_jobs["data"].names)
    assert f"over {n * (n - 1)} ordered pairs" in comparisons[-1]["what"]
    assert f"over {n * (n - 1) // 2} pairs" in comparisons[3]["what"]


def test_job_through_the_chunked_call_names_it_and_writes_the_same_tables(cell, toy_jobs):
    rec = toy_jobs["on_record"]
    assert rec["secondary_paths"] == {"matmul_chunked": 1}
    assert {"secondary/chunks", "secondary/wait", "secondary/post", "secondary/pack"} <= set(rec["phases"])
    (call,) = rec["secondary_chunked_calls"]
    assert call["rows"] == len(toy_jobs["data"].names) and call["chunks"] >= 2
    # the record's self seconds still add up to the job
    ph = rec["phases"]
    total = sum(p["self_seconds"] for p in ph.values() if p["thread"] == "main")
    assert total == pytest.approx(ph["job"]["seconds"], rel=0.01)
    assert check.report(_compare(cell, _tables(cell, toy_jobs["on"], toy_jobs["data"]),
                                 toy_jobs["data"]))
    # tracing off: the same tables, byte for byte, and the same counter
    assert toy_jobs["off_record"]["secondary_chunked_calls"] == rec["secondary_chunked_calls"]
    for table in ("Cdb.csv", "Mdb.csv", "Ndb.csv"):
        with open(os.path.join(toy_jobs["on"], "data_tables", table), "rb") as a, \
                open(os.path.join(toy_jobs["off"], "data_tables", table), "rb") as b:
            assert a.read() == b.read(), table
    # and the one-shot job's values are the chunked job's: the counts are the same integers
    plain = _tables(cell, toy_jobs["plain"], toy_jobs["data"])
    chunked = _tables(cell, toy_jobs["off"], toy_jobs["data"])
    assert all(np.array_equal(plain[t], chunked[t], equal_nan=True) for t in ("mdb", "ani", "cov"))
    assert plain["secondary"] == chunked["secondary"]


def test_the_pack_span_and_the_record_say_how_the_cluster_was_ranked(toy_jobs):
    """ISSUE 44: the per-cluster engine's `secondary/pack` span carries
    `hashes=`, `path=` and `workers=` (the route `rank_route` names for the
    job's `-p`), and the record's `secondary_pack` books the call beside
    `primary_pack`: native wherever the library is there."""
    from drep_tpu import native
    from drep_tpu.ops.minhash import rank_route
    from tools import trace_report

    data = toy_jobs["data"]
    hashes = sum(len(s) for s in data.scaled)
    path, threads = rank_route(hashes, 6)  # the cell's argv leaves `-p` at the CLI's 6
    is_native = native.get_library() is not None
    assert path == ("native" if is_native else "numpy")
    want = {"calls": 1, "native_calls": int(is_native), "rows": len(data.names), "hashes": hashes,
            "threads": threads}
    for job in ("plain_record", "on_record", "off_record"):
        assert toy_jobs[job]["secondary_pack"] == want, job
        assert toy_jobs[job]["secondary_pack"]["native_calls"] == toy_jobs[job]["primary_pack"]["native_calls"]
    spans, _ = trace_report.pair_spans(trace_report.load_events(os.path.join(toy_jobs["on"], "log"))["events"])
    ranked = [sp["args"] for sp in spans if sp["ev"] == "secondary/pack" and "hashes" in sp["args"]]
    assert ranked == [{"hashes": hashes, "path": path, "workers": threads}]


@pytest.mark.parametrize("fault", ["groups_merged", "one_hash_count", "pair_missing"])
def test_comparison_prints_wrong_for_a_wrong_answer(cell, toy_jobs, fault, capsys):
    data = toy_jobs["data"]
    tables = _tables(cell, toy_jobs["plain"], data)
    if fault == "groups_merged":
        a, b = sorted(set(tables["secondary"].values()))[:2]
        tables["secondary"] = {g: (a if s == b else s) for g, s in tables["secondary"].items()}
        broken = "genomes in a secondary cluster the reference does not have"
    elif fault == "one_hash_count":
        i, j = 0, int(np.flatnonzero(data.labels != data.labels[0])[0])
        shorter = min(len(data.scaled[i]), len(data.scaled[j]))
        shared = len(np.intersect1d(data.scaled[i], data.scaled[j]))
        tables["ani"][i, j] = ((shared + 1) / shorter) ** (1.0 / 21)
        broken = "largest ANI error"
    else:
        tables["ani"][3, 5] = tables["cov"][3, 5] = np.nan
        broken = "largest ANI error"
    comparisons = _compare(cell, tables, data)
    assert not check.report(comparisons)
    printed = capsys.readouterr().out
    wrong = [line for line in printed.splitlines() if line.endswith("WRONG")]
    assert wrong and any(broken in line for line in wrong), printed
    assert all(c["ok"] for c in comparisons if "primary" in c["what"] or "Mash" in c["what"])


def test_rehearsal_tells_the_device_paths_expectation_from_a_fault(cell, toy_jobs):
    rec, expect = toy_jobs["plain_record"], cell["traffic"]["expect"]
    device = {"platform": rec["platform"], "kind": rec["device_kind"], "count": rec["n_devices"]}
    faults = species_jobs.job_faults(rec, device, expect, "sort")
    assert any("matmul_chunked" in f for f in faults) and any("one_shot" in f for f in faults)
    # off a TPU both only say which path served: a rehearsal prints them and fails nothing
    assert species_jobs.device_path_only(rec, device, "sort", faults) == faults
    # the chunked job meets the expectation as it stands
    assert species_jobs.job_faults(toy_jobs["on_record"], device, expect, "sort") == []
    # a fall-back, or another device, fails in a rehearsal too
    hidden = {**rec, "fault_tolerance": {"cpu_fallback_tiles": 3}}
    faults = species_jobs.job_faults(hidden, {**device, "count": 4}, expect, "sort")
    soft = species_jobs.device_path_only(hidden, {**device, "count": 4}, "sort", faults)
    assert len(faults) - len(soft) == 2 and all("cpu_fallback" not in f for f in soft)
