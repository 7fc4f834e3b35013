"""The streaming primary over several local devices (ISSUE 39): a whole
`compare` job whose tiles are dealt over four of the test mesh's devices
equals the plain reference; the record's `primary_stream_slots` adds up; the
spans carry what varies; one device reads one slot; the tile programs are
built for every device the walk can reach before its first dispatch."""

import json
import os

import jax
import numpy as np
import pytest

from benchmark import cells, greedy_jobs, stream4_jobs
from drep_tpu.ops.minhash import PAD_ID, PackedSketches
from drep_tpu.parallel import streaming
from drep_tpu.utils import telemetry
from drep_tpu.utils.profiling import HOST_ARGS, Counters

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
TABLE = [(24, 1), (12, 2), (6, 4), (3, 8), (2, 16), (1, 128)]  # 256 genomes, 4 stripes of 64
ARGV = ["--streaming_primary", "--SkipSecondary", "--skip_plots", "--streaming_block", "64",
        "--events", "on"]


def _only(monkeypatch, n_devices: int) -> None:
    """The walk sees the first `n_devices` of the test mesh's eight."""
    real = jax.local_devices
    monkeypatch.setattr(jax, "local_devices", lambda *a, **k: real(*a, **k)[:n_devices])


def _job(tmp, monkeypatch, n_devices: int) -> dict:
    from drep_tpu import controller

    loaded = cells.load_cell("gtdb_release_25k.primary_stream4")
    cfg = loaded["config"]
    cfg["data"].update({"n": 256, "clusters": [{"size": s, "count": c, "groups": [s]} for s, c in TABLE]})
    planted = loaded["generator"].prepare(cfg, 2**31 + 39, str(tmp))
    _only(monkeypatch, n_devices)
    controller.main(["compare", planted["workdir"], *ARGV])
    telemetry.configure()
    with open(os.path.join(planted["workdir"], "log", "perf_counters.json")) as f:
        record = json.load(f)
    with open(os.path.join(planted["workdir"], "log", "events.p0.jsonl")) as f:
        events = [json.loads(line) for line in f if line.strip()]
    return {"wd": planted["workdir"], "data": planted["data"], "cfg": cfg, "mix": loaded["traffic"],
            "record": record,
            "ends": [e for e in events if e.get("ph") == "E" and e["ev"].startswith("primary/")]}


@pytest.fixture(scope="module")
def job4(tmp_path_factory):
    with pytest.MonkeyPatch.context() as mp:
        yield _job(tmp_path_factory.mktemp("slots4"), mp, 4)


def test_four_devices_give_the_reference_s_partition_pairs_and_distances(job4):
    data, cfg, mix = job4["data"], job4["cfg"], job4["mix"]
    out = greedy_jobs.check_greedy(
        stream4_jobs.read_answers(job4["wd"], data.names), data, cfg["params"], mix["compare"],
        mix["limits"], expected=stream4_jobs.expected_answers(data, cfg["params"]))
    assert len(out) == 4 and all(c["ok"] for c in out), out
    planted_pairs = sum(c * s * (s - 1) // 2 for s, c in TABLE)
    assert f"over {2 * planted_pairs} Mdb rows" in out[3]["what"]  # both directions
    assert stream4_jobs.slot_faults(job4["record"], 4) == []


def test_the_slots_of_four_devices_add_up(job4):
    rec = job4["record"]
    slots, stage = rec["primary_stream_slots"], rec["stages"]["primary_compare"]
    assert (slots["slots"], slots["stripes"], slots["tiles"]) == (4, 4, 10) == (
        len(slots["by_slot"]), 4, stage["tiles_computed"])
    assert [s["tiles"] for s in slots["by_slot"]] == [3, 3, 2, 2]  # round-robin over the stripes
    assert slots["turns"] == sum(-(-t // 4) for t in (4, 3, 2, 1)) == 4
    assert sum(s["pairs"] for s in slots["by_slot"]) == stage["pairs"] == 256 * 255 // 2
    assert all(s["pairs"] > 0 and s["finalize_wait_s"] >= 0 for s in slots["by_slot"])
    # the whole pack on every device: 256 rows of 1000 int32 ids and their counts
    assert {s["put_bytes"] for s in slots["by_slot"]} == {256 * 1000 * 4 + 256 * 4}
    assert rec["gauges"]["streaming_devices_used"] == 4.0
    assert not set(rec.get("fault_tolerance") or {}) & {"retries", "cpu_fallback_tiles", "watchdog_trips"}


def test_the_spans_carry_devices_bytes_and_tiles(job4):
    by_name: dict = {}
    for e in job4["ends"]:
        by_name.setdefault(e["ev"], []).append({k: v for k, v in e["args"].items() if k != "dur" and k not in HOST_ARGS})
    put = by_name["primary/put"]
    assert put == [{"devices": 4, "bytes": 4 * (256 * 1000 * 4 + 256 * 4)}]
    want = [{"bi": bi, "tiles": 4 - bi} for bi in range(4)]
    assert by_name["primary/dispatch"] == want and by_name["primary/wait"] == want


def test_one_device_reads_one_slot(tmp_path, monkeypatch):
    rec = _job(tmp_path, monkeypatch, 1)["record"]
    slots = rec["primary_stream_slots"]
    assert (slots["slots"], slots["stripes"], slots["tiles"], slots["turns"]) == (1, 4, 10, 10)
    assert [s["tiles"] for s in slots["by_slot"]] == [10]
    assert slots["by_slot"][0]["pairs"] == 256 * 255 // 2
    assert rec["gauges"]["streaming_devices_used"] == 1.0
    assert stream4_jobs.slot_faults(rec, 4) == ["the tiles reached 1 device(s), the cell asks for 4"]


def _packed(n: int, s: int = 64) -> PackedSketches:
    rng = np.random.default_rng(n)
    ids = np.sort(rng.choice(2**20, size=(n, s), replace=False).astype(np.int32), axis=1)
    assert PAD_ID not in ids
    return PackedSketches(ids=ids, counts=np.full(n, s, np.int32), names=[f"g{i}" for i in range(n)])


@pytest.mark.parametrize("n,tiles", [(16, 1), (32, 3), (64, 10)])
def test_tile_programs_are_built_for_every_reachable_device_before_the_first_dispatch(
        monkeypatch, n, tiles):
    _only(monkeypatch, 4)
    order: list = []
    real_build, real_compact = streaming._build_tile_programs, streaming._compact_tile

    def build(width, block, k, cutoff, use_pallas, device):
        order.append(("build", device.id))
        return real_build(width, block, k, cutoff, use_pallas, device)

    def compact():
        fn = real_compact()

        def call(*args, **kwargs):
            order.append(("tile", None))
            return fn(*args, **kwargs)

        call.lower = fn.lower  # the build step lowers the same program
        return call

    monkeypatch.setattr(streaming, "_build_tile_programs", build)
    monkeypatch.setattr(streaming, "_compact_tile", compact)
    streaming.streaming_mash_edges(_packed(n), k=21, cutoff=0.1, block=16)
    reach = min(4, tiles)
    assert order[:reach] == [("build", d.id) for d in jax.local_devices()[:reach]]
    assert order[reach:] == [("tile", None)] * tiles


def test_the_counter_sums_slot_by_slot_over_the_walks_of_a_job_and_reset_clears_it():
    c = Counters()
    assert "primary_stream_slots" not in c.report(device=False)
    c.add_stream_slots(2, 3, [2, 1], [100, 50], [8, 8], [0.25, 0.125])
    c.add_stream_slots(1, 1, [1, 1, 1], [10, 10, 10], [4, 4, 4], [0.5, 0.0, 0.00004])
    got = c.report(device=False)["primary_stream_slots"]
    assert got == {"slots": 3, "stripes": 3, "tiles": 6, "turns": 4, "by_slot": [
        {"tiles": 3, "pairs": 110, "put_bytes": 12, "finalize_wait_s": 0.75},
        {"tiles": 2, "pairs": 60, "put_bytes": 12, "finalize_wait_s": 0.125},
        {"tiles": 1, "pairs": 10, "put_bytes": 4, "finalize_wait_s": 0.0}]}
    c.reset()
    assert "primary_stream_slots" not in c.report(device=False)
