"""The cell `ecoli_1k.secondary_deep`: its files are found by name, its three
readers read a record kept from the chip (fixtures/ecoli_1k_record.json),
the roofline's counts equal a hand count at one shape, and its margin sweep
and its control run at toy size."""

import json
import os

import numpy as np
import pytest

from benchmark import cells, check, control_species, margin_sweep_species, roofline_chunked
from benchmark import species_jobs

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
REPO = os.path.dirname(BENCH)
CELL = "ecoli_1k.secondary_deep"
V5E = {"int8_ops_per_s": 393e12, "hbm_bytes_per_s": 819e9}


def _reader(name):
    return cells.load_module(os.path.join(BENCH, "layer_metrics", name + ".py"))


@pytest.fixture(scope="module")
def record():
    with open(os.path.join(BENCH, "fixtures", "ecoli_1k_record.json")) as f:
        return json.load(f)


def test_the_cell_is_found_by_name_and_declared_where_it_reports():
    loaded = cells.load_cell(CELL)
    assert loaded["cell"] == {**loaded["cell"], "config": "ecoli_1k", "traffic": "secondary_deep",
                              "chips": 1}
    assert loaded["traffic"]["kind"] == "species_jobs" and hasattr(species_jobs, "run")
    assert hasattr(loaded["generator"], "prepare") and hasattr(loaded["generator"], "generate")
    cfg = loaded["config"]
    assert cfg["data"]["n"] == 1024 and cfg["reduced"] == [] and cfg["rehearse"]["n"] < 128
    # the guarantees are those of mags_5k, word for word; no key forces the path
    assert cfg["guarantees"] == cells.read_json(os.path.join(BENCH, "configs", "mags_5k.json"))["guarantees"]
    assert cfg["params"] == cells.read_json(os.path.join(BENCH, "configs", "mags_5k.json"))["params"]
    assert not any("chunk" in k or "budget" in k or "path" in k for k in cfg["data"])
    assert loaded["traffic"]["argv"] == ["compare", "{workdir}", "--skip_plots"]
    spec = loaded["spec"]
    assert [m["name"] for m in cells.metrics_of(spec, CELL, "end_to_end")] == ["setup_s", "job_wall_s"]
    mine = {m["name"] for m in cells.metrics_of(spec, CELL, "per_layer")}
    assert {"secondary_chunk_layout_s", "secondary_chunk_pad_share", "secondary_chunked_roofline",
            "secondary_device_wait_s", "host_unattributed_s", "idle_attributed"} <= mine
    assert "secondary_useful_pair_share" not in mine and "ring_collective_exposed" not in mine
    for m in spec["per_layer"][-3:]:
        assert m["workloads"] == [CELL] and m["moves"] == "job_wall_s"
        assert m["layer"] == "secondary compare"
    # appended, never put first: the cells that were there keep their places
    for m in spec["per_layer"] + spec["end_to_end"]:
        if CELL in m.get("workloads", []):
            assert m["workloads"][-1] == CELL
    assert len(spec["workloads"]) == 4 and sum(w["chips"] == 4 for w in spec["workloads"]) == 1


def test_the_three_readers_on_a_record_kept_from_the_chip(record):
    run = {"jobs": [{"wall_s": record["phases"]["job"]["seconds"], "record": record}]}
    (call,) = record["secondary_chunked_calls"]
    assert call["rows"] == call["rows_pad"] == 1024 and call["calls"] == 1
    assert call["id_slots"] == call["chunks"] * 1024 * call["width"]
    assert call["bytes_shipped"] == call["id_slots"] * {"uint16": 2, "int32": 4}[call["id_dtype"]]
    assert call["extent"] > 262_144  # or the configuration is wrong
    assert record["secondary_paths"] == {"matmul_chunked": 1} and "secondary_calls" not in record
    layout = _reader("secondary_chunk_layout_s").read(run)
    assert layout == record["phases"]["secondary/chunks"]["self_seconds"] > 0
    pad = _reader("secondary_chunk_pad_share").read(run)
    assert pad == pytest.approx(100.0 * (1 - call["hashes"] / call["id_slots"])) and 0 < pad < 100
    # two jobs: the layout's median, the padding over both
    two = {"jobs": run["jobs"] * 2}
    assert _reader("secondary_chunk_layout_s").read(two) == layout
    assert _reader("secondary_chunk_pad_share").read(two) == pytest.approx(pad)
    # the roofline: the device operations inside the call's wait span, and no others
    lo = 1e9
    host = [("drep:job", 0.0, 20e9), ("drep:secondary/wait", lo, 1e9), ("drep:secondary/post", 3e9, 1e9)]
    device = [("fusion.1", lo + 1e8, 3e8), ("sort", lo + 5e8, 1e8), ("mash", 1e8, 5e8), ("copy", 2.5e9, 1e8)]
    traced = {**run, "peaks": V5E,
              "trace": {"events": {"host": host, "devices": {"/device:TPU:0": device}}}}
    least, bound = roofline_chunked.chunked_least_seconds([call], V5E)
    share = _reader("secondary_chunked_roofline").read(traced)
    assert share == pytest.approx(100.0 * least / 0.4) and bound in ("int8", "hbm")
    # the same shapes can never be faster than the peaks allow
    assert least > 0 and share < 100.0


@pytest.mark.parametrize("name", ["secondary_chunk_layout_s", "secondary_chunk_pad_share",
                                  "secondary_chunked_roofline"])
def test_a_program_without_the_spans_and_the_counter_gives_the_readers_nothing(name, record):
    parent = {k: v for k, v in record.items() if k != "secondary_chunked_calls"}
    parent["phases"] = {k: v for k, v in record["phases"].items() if k != "secondary/chunks"}
    run = {"jobs": [{"wall_s": 20.0, "record": parent}], "peaks": V5E,
           "trace": {"events": {"host": [("drep:secondary/wait", 0.0, 1e9)],
                                "devices": {"/device:TPU:0": [("fusion", 1.0, 5.0)]}}}}
    assert _reader(name).read(run) is None
    assert _reader(name).read({"jobs": [{"wall_s": 1.0, "record": {"stages": {}}}]}) is None
    assert _reader(name).read({"jobs": []}) is None and _reader(name).read({}) is None


def test_roofline_is_not_read_where_one_shot_calls_share_the_span_or_no_trace_is(record):
    reader = _reader("secondary_chunked_roofline")
    job = {"wall_s": 20.0, "record": record}
    events = {"host": [("drep:secondary/wait", 0.0, 1e9)],
              "devices": {"/device:TPU:0": [("fusion", 1.0, 5.0)]}}
    assert reader.read({"jobs": [job], "peaks": V5E, "trace": {"events": events}}) > 0
    mixed = {**record, "secondary_calls": [{"rows_pad": 512, "calls": 3}]}
    assert reader.read({"jobs": [{"wall_s": 20.0, "record": mixed}], "peaks": V5E,
                        "trace": {"events": events}}) is None
    assert reader.read({"jobs": [job], "peaks": V5E, "trace": None}) is None
    assert reader.read({"jobs": [job], "peaks": None, "trace": {"events": events}}) is None  # a rehearsal
    nothing_ran = {"host": events["host"], "devices": {"/device:TPU:0": [("fusion", 2e9, 5.0)]}}
    assert reader.read({"jobs": [job], "peaks": V5E, "trace": {"events": nothing_ran}}) is None


def test_roofline_counts_against_a_hand_count_at_one_shape():
    # 1,024 rows: 8 row blocks of 128, 36 canonical blocks of 128 x 128 pairs
    assert roofline_chunked.canonical_pairs(1024) == 36 * 128 * 128 == 589_824
    assert roofline_chunked.canonical_pairs(64) == 64 * 64  # one block at the smallest bucket
    call = {"rows_pad": 1024, "v_chunk": 32768, "chunks": 49, "width": 1024, "id_dtype": "uint16",
            "calls": 2, "rows": 2048, "extent": 3_200_000, "hashes": 51_200_000,
            "id_slots": 102_760_448, "bytes_shipped": 205_520_896}
    # two calls over 1.6M ids each: the entry holds their sum
    assert roofline_chunked.chunked_macs(call) == 589_824 * 3_200_000
    # indicator written and read once a call, canonical int32 counts once a call, each id once
    by_hand = 2 * 1024 * 3_200_000 + 2 * 4 * 589_824 + 2 * 51_200_000
    assert roofline_chunked.chunked_bytes(call) == by_hand == 6_660_718_592
    seconds, bound = roofline_chunked.chunked_least_seconds([call], V5E)
    assert bound == "int8" and seconds == pytest.approx(2 * 589_824 * 3.2e6 / 393e12)
    assert by_hand / 819e9 < seconds
    # a narrow vocabulary is bound by memory, and the least never counts the padding
    thin = {**call, "rows_pad": 64, "extent": 300_000, "hashes": 1_000_000}
    assert roofline_chunked.chunked_least_seconds([thin], V5E)[1] == "hbm"
    assert roofline_chunked.chunked_bytes({**call, "id_slots": 10**12}) == by_hand


def test_reference_species_equals_the_pairwise_reference_at_toy_size():
    from benchmark import reference as ref
    from benchmark import reference_species as refs

    cfg = cells.read_json(os.path.join(BENCH, "configs", "ecoli_1k.json"))
    cfg["data"].update(cfg["rehearse"])
    gen = cells.load_module(os.path.join(BENCH, "generators", cfg["generator"] + ".py"))
    data = gen.generate(cfg["data"], 2**31 + 5)
    got = refs.secondary_of_cluster(data.scaled, 21, 0.95, 0.1)
    want = ref.secondary_of_cluster(data.scaled, 21, 0.95, 0.1)
    assert all(np.array_equal(g, w) for g, w in zip(got, want))
    dist = refs.mash_matrix(data.bottom, 1000, 21)
    edges = ref.mash_edges(data.bottom, 1000, 21)
    assert all(dist[i, j] == d == dist[j, i] for (i, j), d in edges.items())
    low = refs.mash_matrix(data.bottom, 1000, 21, lower_precision=True)
    assert low[0, 1] == ref.to_bfloat16(dist[0, 1]) != dist[0, 1]


def test_margin_sweep_and_control_at_toy_size(capsys):
    cfg = cells.read_json(os.path.join(BENCH, "configs", "ecoli_1k.json"))
    cfg["data"].update(cfg["rehearse"])
    gen = cells.load_module(os.path.join(BENCH, "generators", cfg["generator"] + ".py"))
    row = margin_sweep_species.sweep(cfg, gen, 12)
    assert row["primary_wrong"] == row["secondary_wrong"] == 0
    assert min(row[k] for k in row if "gap" in k) > 0
    assert row["largest_group_share"] >= 0.39 and row["vocabulary"] > cfg["data"]["s_scaled"]
    # the control fails the ANI and the Mash limit and leaves the partitions alone
    assert control_species.main(["--workload", CELL, "--seeds", "12", "--rehearse"]) == 0
    printed = capsys.readouterr().out
    wrong = [line for line in printed.splitlines() if line.endswith("WRONG")]
    assert len(wrong) == 2 and "correct = False" in printed
    data = gen.generate(cfg["data"], 12)
    mix = cells.load_cell(CELL)["traffic"]
    out = species_jobs.check_species({}, data, cfg["params"], mix["compare"], mix["limits"],
                                     lower_precision=True)
    assert not check.report(out) and all(c["ok"] for c in out if c["limit"] == 0)
    assert all(np.isfinite(c["value"]) for c in out)
