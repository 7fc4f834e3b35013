"""The per-layer readers that read the program's own spans and counters
(the `phases` and `secondary_calls` sections of a job's record, and the
`drep:<span>` events of the profiler's host plane), each on a hand-made run."""

import json
import os

import pytest

from benchmark import cells, phases

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
REPO = os.path.dirname(BENCH)


def _reader(name):
    return cells.load_module(os.path.join(BENCH, "layer_metrics", name + ".py"))


def _job(wall, self_seconds: dict, calls=None):
    rec = {"stages": {}, "phases": {
        n: {"seconds": s, "self_seconds": s, "calls": 1, "thread": "main"}
        for n, s in self_seconds.items()}}
    if calls is not None:
        rec["secondary_calls"] = calls
    return {"wall_s": wall, "record": rec}


DENSE = {
    "job": 0.5, "stage:cluster": 0.25, "stage:primary_compare": 0.125, "stage:secondary": 0.0625,
    "stage:secondary_compare": 0.03125, "stage:ingest_or_cache": 2.0, "mdb_build": 0.5,
    "tables_io": 0.25, "stage:assembly_io": 0.125, "primary/pack": 0.5, "primary/dispatch": 0.25,
    "primary/wait": 1.5, "primary/assemble": 1.0, "primary/linkage": 3.0, "secondary/pack": 4.0,
    "secondary/wait": 1.25, "secondary/post": 0.5, "stage:secondary_postprocess": 0.75,
    "secondary/checkpoint": 1.75, "stage:evaluate": 0.5,
    # another thread's span is keyed apart and never read
    "primary/wait@other": 50.0,
}
EXPECT_DENSE = {
    "host_unattributed_s": 0.5 + 0.25 + 0.125 + 0.0625 + 0.03125,
    "load_sketches_s": 2.0, "tables_s": 0.875, "primary_prep_s": 0.75,
    "primary_device_wait_s": 1.5, "primary_post_s": 1.0, "primary_linkage_s": 3.0,
    "secondary_pack_s": 4.0, "secondary_device_wait_s": 1.25, "secondary_post_s": 1.25,
    "secondary_checkpoint_s": 1.75,
}


@pytest.mark.parametrize("name", sorted(EXPECT_DENSE))
def test_span_reader_sums_its_phases_and_takes_the_median_over_jobs(name):
    reader = _reader(name)
    one = {"jobs": [_job(20.0, DENSE)]}
    assert reader.read(one) == pytest.approx(EXPECT_DENSE[name])
    # three jobs: the median of the per-job sums
    three = {"jobs": [_job(20.0, {k: v * f for k, v in DENSE.items()}) for f in (1.0, 3.0, 2.0)]}
    assert reader.read(three) == pytest.approx(2.0 * EXPECT_DENSE[name])
    # the parent of the PR that brought the spans has no `phases`: nothing to read
    assert reader.read({"jobs": [{"wall_s": 20.0, "record": {"stages": {}}}]}) is None
    assert reader.read({"jobs": []}) is None and reader.read({}) is None


@pytest.mark.parametrize("name", ["secondary_pack_s", "secondary_device_wait_s", "secondary_post_s",
                                  "secondary_checkpoint_s", "secondary_useful_pair_share"])
def test_secondary_readers_read_nothing_where_the_secondary_is_off(name):
    primary_only = {k: v for k, v in DENSE.items() if "secondary" not in k}
    assert _reader(name).read({"jobs": [_job(5.0, primary_only)]}) is None


def test_the_named_phases_and_the_unattributed_rest_add_up_to_the_job():
    """Every bare name belongs to exactly one reader or to no metric's
    business (`stage:evaluate`): the readers partition the job."""
    run = {"jobs": [_job(20.0, DENSE)]}
    read = sum(_reader(n).read(run) for n in EXPECT_DENSE)
    main = sum(v for k, v in DENSE.items() if "@" not in k)
    assert read == pytest.approx(main - DENSE["stage:evaluate"])


def test_useful_pair_share_is_useful_pairs_over_the_pairs_the_calls_compute():
    calls = [
        {"rows_pad": 512, "width": 32768, "v_pad": 32768, "calls": 8, "clusters": 900,
         "rows": 4000, "useful_pairs": 9000},
        {"rows_pad": 256, "width": 32768, "v_pad": 32768, "calls": 1, "clusters": 40,
         "rows": 200, "useful_pairs": 640},
    ]
    computed = 8 * 512 * 511 // 2 + 256 * 255 // 2
    run = {"jobs": [_job(20.0, DENSE, calls), _job(20.0, DENSE, calls)]}
    assert _reader("secondary_useful_pair_share").read(run) == pytest.approx(100.0 * 9640 / computed)


def _trace(host, device):
    return {"trace": {"events": {"host": host, "devices": {"/device:TPU:0": device,
                                                            "/device:TPU:1": []}}}}


def test_idle_attributed_on_a_hand_made_event_list():
    reader = _reader("idle_attributed")
    # job [0, 100]; the device is busy in [10, 20] and [60, 70]: idle is 80
    device = [("custom-call", 10.0, 10.0), ("fusion", 60.0, 10.0)]
    host = [
        ("drep:job", 0.0, 100.0),
        ("drep:stage:cluster", 0.0, 95.0),          # a container: names nothing
        ("drep:stage:ingest_or_cache", 0.0, 8.0),   # 8 idle
        ("drep:primary/wait", 15.0, 10.0),          # [15, 25]: 5 of it idle
        ("drep:primary/linkage", 30.0, 25.0),       # [30, 55]: 25 idle
        ("drep:primary/linkage", 40.0, 5.0),        # inside the last: counted once
        ("drep:tables_io", 90.0, 30.0),             # [90, 120]: 10 inside the job
        ("PjitFunction(f)", 70.0, 20.0),            # not the program's span
        ("drep:tables_io", 200.0, 10.0),            # after the job: nothing
    ]
    assert reader.read(_trace(host, device)) == pytest.approx(100.0 * (8 + 5 + 25 + 10) / 80)
    # only containers: the idle time is inside the job and inside no phase
    assert reader.read(_trace(host[:2], device)) == 0.0
    # the parent's trace has no `drep:` event, a run with --trace 0 no trace
    assert reader.read(_trace([("PjitFunction(f)", 0.0, 100.0)], device)) is None
    assert reader.read({"trace": None}) is None and reader.read({}) is None
    # no operation on the first device: nothing to be idle between
    assert reader.read(_trace(host, [])) is None


def test_every_new_metric_is_declared_with_its_reader_and_its_cells():
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        spec = json.load(f)
    declared = {m["name"]: m for m in spec["per_layer"]}
    new = set(EXPECT_DENSE) | {"idle_attributed", "secondary_useful_pair_share"}
    assert new <= set(declared)
    batch = ["mags_5k.compare_dense", "mags_5k.primary_stream", "gtdb_reps_10k.primary_ring4"]
    for name in new:
        m = declared[name]
        assert m["moves"] == "job_wall_s"
        # the secondary's readers find something only where the secondary runs
        assert m["workloads"] == (batch[:1] if name.startswith("secondary_") else batch), name
    # a container is what `host_unattributed_s` reads and what `idle_attributed` leaves out
    assert "job" in phases.CONTAINERS and "primary/wait" not in phases.CONTAINERS
