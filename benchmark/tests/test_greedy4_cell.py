"""The cell `gtdb_release_host4_6k.compare_greedy4`: its files are found by
name wherever later cells put theirs, its deployment is `gtdb_release_6k`'s but
for the table and the layout's seed, its table adds up, keeps what ISSUE 42
asks of the engine route and gives every seed the same work, its control fails
every value limit, its three readers read a made-up record and trace and give
nothing where the program has no such span or counter, a job that the host's
chips did not serve counts as failed (the streaming walk's reach and each
engine cluster's `mesh_devices`), the kind forces the matmul route in a
rehearsal and only there, and a rehearsal of the whole cell on four virtual
devices prints a well-formed line."""

import json
import os
import subprocess
import sys

import pytest

from benchmark import cells, check, control_greedy, greedy4_jobs, greedy_jobs, margin_sweep_release
from benchmark.tests.test_greedy_cell import (  # the one-chip cell's made-up record, a job's form
    _as_a_job_writes_it, _call, _record, _swap_a_representative)

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
REPO = os.path.dirname(BENCH)
CELL = "gtdb_release_host4_6k.compare_greedy4"
CONFIG = "gtdb_release_host4_6k"
ONE_CHIP = "gtdb_release_6k"
NEW = ["secondary_greedy_put_s", "secondary_greedy_mesh_occupancy", "secondary_greedy_reship_share"]
DEVICE = {"platform": "tpu", "kind": "TPU v5 lite", "count": 4}
MESH_BLOCK = 4 * margin_sweep_release.BLOCK  # the engine's block on four chips


def _reader(name):
    return cells.load_module(os.path.join(BENCH, "layer_metrics", name + ".py"))


def _mesh_call(**over):
    """An engine cluster's entry as four chips book it: 576 genomes in two
    blocks of 512, one trailing tile a block, the block shipped once for the
    tile, once more row-sharded and once a device for the self comparison."""
    block = 4 * 512 * 24576
    call = _call(blocks=2, block_rows=512, rep_rows_shipped=1024, rep_rows_real=5, device_calls=12,
                 mesh_devices=4, rep_tiles_replicated=0, partial_tile_ships=2,
                 block_bytes=2 * 6 * block, rep_bytes=2 * 4 * block)
    call.update(over)
    return call


def _mesh_record(calls=None, **over):
    rec = _record(n_devices=4, gauges={"streaming_devices_used": 4.0},
                  secondary_greedy_calls=calls if calls is not None else [_mesh_call()])
    rec["phases"]["secondary/greedy_put"] = {"seconds": 0.4, "self_seconds": 0.4, "calls": 14,
                                             "thread": "main"}
    rec.update(over)
    return rec


@pytest.fixture(scope="module")
def toy():
    loaded = cells.load_cell(CELL)
    cfg = loaded["config"]
    cfg = {**cfg, "data": {**cfg["data"], **cfg["rehearse"]}}
    return {"cfg": cfg, "mix": loaded["traffic"], "gen": loaded["generator"]}


# ---- found by name -----------------------------------------------------------------------


def test_the_cell_is_found_by_name_wherever_later_cells_are_appended():
    loaded = cells.load_cell(CELL)
    assert loaded["cell"] == {**loaded["cell"], "config": CONFIG, "traffic": "compare_greedy4", "chips": 4}
    assert len(loaded["cell"]["why"]) <= 200 and "6,144" in loaded["cell"]["why"]
    assert loaded["traffic"]["kind"] == "greedy4_jobs" and hasattr(greedy4_jobs, "run")
    cfg, mix, spec = loaded["config"], loaded["traffic"], loaded["spec"]
    one = cells.read_json(os.path.join(BENCH, "configs", ONE_CHIP + ".json"))
    # the one-chip deployment's shapes, thresholds and guarantees, word for word: only the table
    # of sizes and the layout's seed are this configuration's own
    assert cfg["generator"] == one["generator"] == "planted_release"
    assert cfg["params"] == one["params"] and cfg["guarantees"] == one["guarantees"]
    own = ("clusters", "layout_seed")
    assert {k: v for k, v in cfg["data"].items() if k not in own} == \
        {k: v for k, v in one["data"].items() if k not in own}
    assert cfg["data"]["layout_seed"] != one["data"]["layout_seed"]
    assert cfg["reduced"] == ["n"] == list(cfg["reduced_why"]) and cfg["data"]["n"] == 6144
    assert {"clusters", "left out", "layout_seed", "accessory_max"} <= set(cfg["assumed"])
    assert "E. coli" in cfg["assumed"]["left out"]
    greedy = cells.read_json(os.path.join(BENCH, "traffic", "compare_greedy.json"))
    assert mix["argv"] == greedy["argv"] and mix["compare"] == greedy["compare"]
    assert mix["limits"] == greedy["limits"] and set(mix["limits_why"]) == set(mix["limits"])
    assert mix["expect"] == {k: v for k, v in greedy["expect"].items() if k != "secondary_path"}
    entry = next(c for c in spec["configs"] if c["name"] == CONFIG)
    assert entry["source"] == cfg["source"] and entry["reduced"] == cfg["reduced"]
    assert "config 5" in entry["source"] and "--greedy_secondary_clustering" in entry["source"]
    assert len(entry["source"]) <= 200 and len(entry["why"]) <= 200
    assert entry["file"] == f"benchmark/configs/{CONFIG}.json"
    assert [w["name"] for w in spec["workloads"] if w["config"] == CONFIG] == [CELL]
    assert [m["name"] for m in cells.metrics_of(spec, CELL, "end_to_end")] == ["setup_s", "job_wall_s"]
    mine = {m["name"] for m in cells.metrics_of(spec, CELL, "per_layer")}
    # what the one-chip greedy cell reports, what the four-chip streaming cell adds, and its own
    assert {m["name"] for m in cells.metrics_of(spec, ONE_CHIP + ".compare_greedy", "per_layer")} <= mine
    assert {"stream_turn_pad_share", "stream_slot_balance", "stream_chip_occupancy"} | set(NEW) <= mine
    assert not {m for m in mine if m.startswith(("ingest_", "ring_", "secondary_chunk"))}
    by_name = {m["name"]: m for m in spec["per_layer"]}
    for name in NEW:  # wherever they stand in the list
        assert by_name[name]["moves"] == "job_wall_s" and by_name[name]["layer"] == "secondary compare"
        assert os.path.exists(os.path.join(BENCH, "layer_metrics", name + ".py"))
    assert by_name["secondary_greedy_put_s"]["workloads"][:2] == [ONE_CHIP + ".compare_greedy", CELL]
    assert by_name["secondary_greedy_put_s"]["source"] == "program_span"
    assert by_name["secondary_greedy_mesh_occupancy"]["source"] == "device_trace"
    assert by_name["secondary_greedy_reship_share"]["source"] == "program_counter"
    assert by_name["secondary_greedy_reship_share"]["better"] == "lower"
    # at most half of the cells, rounded down, ask for four chips
    four = [w for w in spec["workloads"] if w["chips"] == 4]
    assert len(four) <= len(spec["workloads"]) // 2
    # appended where it was added: the cells that were there before it keep their places
    before = [w["name"] for w in spec["workloads"]]
    before = before[:before.index(CELL)]
    assert len(before) == 7
    for m in spec["per_layer"] + spec["end_to_end"]:
        listed = m.get("workloads", [])
        if CELL in listed:
            assert [w for w in listed if w in before] == listed[:listed.index(CELL)]


@pytest.mark.parametrize("size", ["data", "rehearse"])
def test_the_size_table_adds_up_and_keeps_what_the_issue_asks_of_the_engine(size):
    cfg = cells.read_json(os.path.join(BENCH, "configs", CONFIG + ".json"))
    data = {**cfg["data"], **(cfg[size] if size == "rehearse" else {})}
    table = data["clusters"]
    sizes = [c["size"] for c in table for _ in range(c["count"])]
    assert sum(sizes) == data["n"] and all(sum(c["groups"]) == c["size"] for c in table)
    engine = [s for s in sizes if s > margin_sweep_release.ENGINE_OVER]
    # no two genomes of a cluster may tie in size: the accessory hashes have to allow it
    assert max(sizes) <= int(data["accessory_max"] * data["s_scaled"]) + 1
    # every engine cluster is one a mesh takes, by the program's own rule
    from drep_tpu.cluster.engines import MESH_MIN_GENOMES

    assert min(engine) >= greedy4_jobs.MESH_MIN_ROWS == MESH_MIN_GENOMES
    if size == "rehearse":
        assert max(engine) >= 144 and data["n"] > 3 * 256  # four CPU tiles in a stripe: four slots
        return
    assert data["n"] == 6144 and len(engine) == 4
    assert sum(engine) >= 1536 and sum(-(-s // MESH_BLOCK) for s in engine) >= 2
    # more than four representatives in a cluster, for the first time; groups in every engine cluster
    assert max(len(c["groups"]) for c in table) == 5
    assert all(len(c["groups"]) >= 3 for c in table if c["size"] > margin_sweep_release.ENGINE_OVER)
    # the batched route's rows are the one-chip deployment's, unchanged
    one = cells.read_json(os.path.join(BENCH, "configs", ONE_CHIP + ".json"))["data"]["clusters"]
    small = (lambda t: [c for c in t if 2 <= c["size"] <= margin_sweep_release.ENGINE_OVER])
    assert small(table) == small(one) and sum(c["size"] * c["count"] for c in small(table)) == 1536


def test_four_seeds_give_the_same_work_and_the_control_fails_every_value_limit(toy, capsys):
    found = [margin_sweep_release.sweep(toy["cfg"], toy["gen"], seed)
             for seed in (1, 42, 2**31 + 42, 3_000_004_201)]
    assert all(f["work"] == found[0]["work"] for f in found)
    assert found[0]["moves"] != found[1]["moves"]  # the values are the seed's
    work = found[0]["work"]
    assert sorted(e["rows"] for e in work["engine"]) == [72, 144]
    assert sorted(e["reps"] for e in work["engine"]) == [3, 5]
    assert work["ndb_rows"] == sum(e["compared_pairs"] for e in work["engine"]) + \
        work["batched"]["compared_pairs"]
    for f in found:
        assert f["margins"]["primary_wrong"] == f["margins"]["secondary_wrong"] == 0
        assert min(v for k, v in f["margins"].items() if "wrong" not in k) > 0
    capsys.readouterr()
    assert control_greedy.main(["--workload", CELL, "--seeds", "42", "--rehearse"]) == 0
    printed = capsys.readouterr().out
    wrong = [line for line in printed.splitlines() if line.endswith("WRONG")]
    assert len(wrong) == 3 and "every value limit failed = True" in printed
    assert all(any(w in line for w in ("Mash distance", "ANI error", "coverage error")) for line in wrong)


# ---- the readers -----------------------------------------------------------------------------


def test_the_readers_on_a_record_and_a_trace_of_the_cell():
    rec = _mesh_record()
    run = {"jobs": [{"wall_s": 17.0, "record": rec}, {"wall_s": 17.4, "record": rec}], "device": DEVICE}
    assert _reader("secondary_greedy_put_s").read(run) == 0.4
    assert _reader("secondary_greedy_wait_s").read(run) == 4.2  # the accepted reader, beside it
    # a block crossed six times where once would do: five crossings of six are reshipments
    assert _reader("secondary_greedy_reship_share").read(run) == pytest.approx(100 * 5 / 6)
    one_chip = _record(secondary_greedy_calls=[_call(mesh_devices=1, block_bytes=4 * 5 * 128 * 24576,
                                                     rep_bytes=4 * 3 * 24576)])
    assert _reader("secondary_greedy_reship_share").read({"jobs": [{"record": one_chip}]}) == 0.0
    # occupancy: a wait of 2 s with a put of 0.5 s inside it and a put of 1 s inside a layout:
    # 3 s covered; chip 0 busy 1.5 s inside (and 1 s outside, which does not count), chip 1 busy
    # 0.5 s across the wait's end (0.25 inside), two chips idle
    host = [("drep:job", 0.0, 20e9), ("drep:secondary/greedy_layout", 0.5e9, 1.5e9),
            ("drep:secondary/greedy_put", 1e9, 1e9), ("drep:secondary/greedy_wait", 3e9, 2e9),
            ("drep:secondary/greedy_put", 3e9, 0.5e9), ("drep:secondary/wait", 8e9, 1e9)]
    devices = {"/device:TPU:0": [("copy", 1.25e9, 0.5e9), ("fusion.1", 3.5e9, 1e9), ("fusion", 8e9, 1e9)],
               "/device:TPU:1": [("fusion.1", 4.75e9, 0.5e9)], "/device:TPU:2": []}
    traced = {**run, "trace": {"events": {"host": host, "devices": devices}}}
    assert _reader("secondary_greedy_mesh_occupancy").read(traced) == pytest.approx(100 * 1.75 / (4 * 3.0))
    full = {"/device:TPU:%d" % d: [("fusion.1", 1e9, 1e9), ("fusion.1", 3e9, 2e9)] for d in range(4)}
    traced["trace"]["events"]["devices"] = full
    assert _reader("secondary_greedy_mesh_occupancy").read(traced) == pytest.approx(100.0)


@pytest.mark.parametrize("name", NEW)
def test_a_program_without_the_span_or_the_counters_gives_the_readers_nothing(name):
    parent = _record(n_devices=4)  # the parent's record: no greedy_put, no mesh_devices, no block_bytes
    events = {"host": [("drep:job", 0.0, 1e9), ("drep:secondary/greedy_wait", 0.0, 1e9)],
              "devices": {"/device:TPU:0": [("fusion", 1.0, 5.0)]}}
    run = {"jobs": [{"wall_s": 14.0, "record": parent}], "device": DEVICE, "trace": {"events": events}}
    assert _reader(name).read(run) is None
    assert _reader(name).read({"jobs": [{"wall_s": 1.0, "record": {"stages": {}}}], "device": DEVICE}) is None
    assert _reader(name).read({"jobs": []}) is None and _reader(name).read({}) is None
    if name == "secondary_greedy_mesh_occupancy":  # an untraced run, and a job that put nothing
        assert _reader(name).read({"jobs": [{"record": _mesh_record()}], "device": DEVICE, "trace": None}) is None
        quiet = {"host": [("drep:job", 0.0, 1e9)], "devices": events["devices"]}
        assert _reader(name).read({"jobs": [{"record": _mesh_record()}], "device": DEVICE,
                                   "trace": {"events": quiet}}) is None


# ---- a job's own record ------------------------------------------------------------------------


@pytest.mark.parametrize("record,fault", [
    (_mesh_record(), None),
    (_mesh_record([_mesh_call(), _mesh_call(rows=48, mesh_devices=1)]), None),  # under 64: never a mesh's
    (_mesh_record([_mesh_call(), _mesh_call(rows=128, mesh_devices=1)]),
     "a cluster of 128 was served by 1 device(s), the cell asks for 4"),
    (_mesh_record([_mesh_call(rows=64, mesh_devices=2)]), "a cluster of 64 was served by 2 device(s)"),
    (_mesh_record([_call()]), "the entry of a cluster of 576 holds no mesh_devices"),
    (_mesh_record(gauges={"streaming_devices_used": 1.0}), "reached 1 device(s), the cell asks for 4"),
    (_mesh_record(gauges={}), "holds no gauge streaming_devices_used"),
    (_mesh_record(secondary_paths={"greedy_gather": 4, "one_shot_clusterlocal": 4}), "['greedy_gather'], outside"),
    (_mesh_record(secondary_paths={"greedy_matmul": 4, "one_shot_clusterlocal": 4, "mesh_ring": 1}),
     "['mesh_ring'], outside"),
    (_mesh_record(secondary_greedy_batched={}), "holds no secondary_greedy_batched"),
    (_mesh_record(n_devices=1), "record says n_devices=1"),
])
def test_a_job_the_host_s_chips_did_not_serve_counts_as_failed(record, fault):
    from benchmark import batch_jobs, stream4_jobs

    expect = cells.load_cell(CELL)["traffic"]["expect"]
    faults = (batch_jobs.record_faults(record, DEVICE, expect, "streaming_sort")
              + greedy_jobs.route_faults(record, expect) + greedy_jobs.counter_faults(record, expect)
              + stream4_jobs.slot_faults(record, 4) + greedy4_jobs.mesh_faults(record, 4))
    assert (faults == []) if fault is None else any(fault in f for f in faults), faults


def test_the_runner_counts_a_job_on_fewer_devices_as_failed_and_keeps_the_sound_ones(monkeypatch):
    """``run`` on a window handed to it: ``greedy_jobs.run`` is replaced, the
    record's reach is the kind's own; the knob is a rehearsal's alone."""
    records = [_mesh_record(), _mesh_record([_mesh_call(mesh_devices=1)]),
               _mesh_record(gauges={"streaming_devices_used": 2.0}), _mesh_record()]
    seen = []

    def window(ctx):
        seen.append(os.environ.get("DREP_TPU_GREEDY_MATMUL"))
        jobs = [{"wall_s": 17.0 + i, "workdir": f"job{i}", "error": None, "record": rec}
                for i, rec in enumerate(records)]
        return {"correct": True, "attempted": 5, "failed": 1,
                "end_to_end": {"setup_s": 50.0, "job_wall_s": 18.5},
                "run": {"jobs": jobs, "trace": None, "window_s": 45.0}}

    monkeypatch.setattr(greedy_jobs, "run", window)
    monkeypatch.delenv("DREP_TPU_GREEDY_MATMUL", raising=False)
    ctx = {"cell": {"chips": 4}, "rehearse": False}
    out = greedy4_jobs.run(ctx)
    assert out["correct"] is True and (out["attempted"], out["failed"]) == (5, 3)
    assert [j["workdir"] for j in out["run"]["jobs"]] == ["job0", "job3"]
    assert out["end_to_end"] == {"setup_s": 50.0, "job_wall_s": 18.5}  # the median of 17 and 20
    greedy4_jobs.run({**ctx, "rehearse": True})
    assert seen == [None, "1"] and "DREP_TPU_GREEDY_MATMUL" not in os.environ
    records[:] = [_mesh_record([_mesh_call(mesh_devices=1)])]
    with pytest.raises(SystemExit, match="no job of the window ran soundly"):
        greedy4_jobs.run(ctx)
    # a program whose record cannot say who served is refused before any set-up
    monkeypatch.setattr(greedy_jobs, "counters_unknown", lambda expect: expect["counters"])
    with pytest.raises(SystemExit, match="mesh_devices"):
        greedy4_jobs.run(ctx)
    assert len(seen) == 3


def test_the_digest_names_who_served_and_what_crossed():
    digest = greedy4_jobs.mesh_digest(_mesh_record())
    assert digest["devices_used"] == 4.0 and digest["mesh_devices"] == [4] and digest["block_rows"] == [512]
    assert digest["partial_tile_ships"] == [2] and digest["block_bytes"] == [2 * 6 * 4 * 512 * 24576]
    assert greedy4_jobs.mesh_digest({})["mesh_devices"] == []


def test_the_check_is_the_greedy_cell_s_own_on_this_deployment_s_data(toy):
    """Nothing of the comparison is this kind's: the planted answer passes it
    and a swapped representative fails the partition, at the rehearsal size of
    THIS table (five groups in a cluster)."""
    from benchmark import reference_greedy as rg

    data = toy["gen"].generate(toy["cfg"]["data"], 2**31 + 42)
    want = rg.compare_greedy(data.bottom, data.scaled, data.n_kmers, toy["cfg"]["params"])
    both = {"data": data, "cfg": toy["cfg"], "mix": toy["mix"], "want": want}
    args = (data, toy["cfg"]["params"], toy["mix"]["compare"], toy["mix"]["limits"])
    got = _as_a_job_writes_it(want)
    out = greedy_jobs.check_greedy(got, *args, expected=want)
    assert len(out) == 9 and check.report(out)
    _swap_a_representative(got, both)
    failed = [c["what"] for c in greedy_jobs.check_greedy(got, *args, expected=want) if not c["ok"]]
    assert failed == ["genomes in a secondary cluster the reference does not have"]


# ---- the whole cell, rehearsed -------------------------------------------------------------------


def test_a_rehearsal_on_four_virtual_devices_takes_the_mesh_route_and_prints_a_well_formed_line():
    seed = 2**31 + 42
    argv = [sys.executable, os.path.join(BENCH, "run.py"), "--workload", CELL, "--seed", str(seed),
            "--seconds", "1", "--trace", "1", "--rehearse"]
    env = {**os.environ, "JAX_PLATFORMS": "cpu",
           "XLA_FLAGS": "--xla_force_host_platform_device_count=4"}
    proc = subprocess.run(argv, capture_output=True, text=True, env=env, timeout=900, cwd=REPO)
    assert proc.returncode == 0, proc.stderr[-3000:]
    line, out = json.loads(proc.stdout.strip().splitlines()[-1]), proc.stdout
    assert line["correct"] is True and line["failed"] == 0 and line["attempted"] == 1
    assert line["rehearsal"] is True and line["device"] == {**line["device"], "platform": "cpu", "count": 4}
    listed = {m["name"] for m in cells.metrics_of(cells.load_cell(CELL)["spec"], CELL, "per_layer")}
    # every metric the cell lists reads a number, but the two that need a TPU's kernel and peaks
    assert listed - set(line["metrics"]) == {"mash_kernel_ns_per_pair", "secondary_greedy_roofline"}
    assert line["metrics"]["compiles_in_window.batch"]["value"] == 0
    assert line["metrics"]["secondary_greedy_put_s"]["value"] > 0
    assert 0 < line["metrics"]["secondary_greedy_mesh_occupancy"]["value"] <= 100
    # one block, one trailing tile a cluster: the block crosses 1 + 1 + 4 times
    assert line["metrics"]["secondary_greedy_reship_share"]["value"] == pytest.approx(100 * 5 / 6)
    # 800 genomes in CPU tiles of 256: stripes of 4, 3, 2 and 1 tiles, a turn each on four slots
    assert line["metrics"]["stream_turn_pad_share"]["value"] == pytest.approx(37.5)
    # batch_jobs.run's own comparison (the Cdb of every job) and the nine of the greedy rule
    assert out.count("compare: ") == 10 and "WRONG" not in out and "job failed" not in out
    assert "rehearsal: expected of the device path" not in out  # the knob: the matmul route served
    assert "mesh: {'devices_used': 4.0, 'mesh_devices': [4, 4], 'block_rows': [512, 512]" in out
    assert not os.path.exists(os.path.join(BENCH, ".work", f"{CELL}-{seed}"))
