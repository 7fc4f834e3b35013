"""The reduction from trace events to numbers, on a hand-made trace whose
answers are known, and on a slice recorded on the chip."""

import json
import os

import pytest

from benchmark import tracered

FIXTURES = os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(__file__))), "fixtures")


def load(name):
    with open(os.path.join(FIXTURES, name)) as f:
        raw = json.load(f)
    return {"devices": {p: [tuple(e) for e in ev] for p, ev in raw["devices"].items()},
            "host": [tuple(e) for e in raw["host"]]}


def test_busy_is_the_union_of_intervals_averaged_over_devices():
    trace = load("trace_small.json")
    # device 0: [0,150) + [200,350) = 300 ns; device 1: [0,260) = 260 ns
    assert tracered.busy_seconds(trace) == pytest.approx(280e-9)


def test_kernel_time_sums_events_and_devices():
    assert tracered.op_seconds(load("trace_small.json"), r"mash") == pytest.approx(300e-9)


def test_exposed_collective_is_the_worst_device():
    # device 0: the collective [200,300) is covered by fusion.2 from 250: 50 ns exposed;
    # device 1: 60 ns, nothing beside it
    assert tracered.exposed_collective_seconds(load("trace_small.json")) == pytest.approx(60e-9)


def test_top_ops_and_idle_gaps():
    reduced = tracered.reduce_trace(load("trace_small.json"), 400e-9)
    assert [n for n, _ in reduced["breakdown"]["device_ops"]] == [
        "mash_kernel", "collective-permute.1", "fusion.1", "fusion.2"]
    assert reduced["breakdown"]["device_ops"][0][1] == pytest.approx(300e-9)
    # device 0 idles in [150,200), inside the shorter of the two host events, and from
    # its last operation (350) to the trace's last event (400)
    assert sorted(reduced["breakdown"]["idle_gaps"]) == [
        ["host:PjitFunction(step)", pytest.approx(50e-9)], ["host:outer", pytest.approx(50e-9)]]
    assert reduced["busy_s"] / reduced["window_s"] == pytest.approx(0.7)


def test_merge_intervals():
    assert tracered.merge_intervals([(5, 6), (0, 2), (1, 3), (3, 4)]) == [(0, 4), (5, 6)]


@pytest.mark.parametrize("name", sorted(f for f in os.listdir(FIXTURES) if f.startswith("v5e_") and f.endswith(".json")))
def test_recorded_slice(name):
    """A slice of a real v5e trace: the device planes and the kernel's name are
    found by the same patterns the metrics use."""
    with open(os.path.join(FIXTURES, name)) as f:
        raw = json.load(f)
    trace = load(name)
    assert all(tracered.DEVICE_PLANE.match(p) for p in trace["devices"])
    assert tracered.busy_seconds(trace) == pytest.approx(raw["answers"]["busy_s"])
    assert tracered.op_seconds(trace, raw["answers"]["kernel_pattern"]) == pytest.approx(
        raw["answers"]["kernel_s"])
    assert tracered.op_seconds(trace, raw["answers"]["kernel_pattern"]) > 0


def test_load_a_real_xplane_file():
    """The profiler's own file from a v5e run: the device plane, its ops line
    and the Mash kernel's name are where the loader looks for them."""
    trace = tracered.load_xplane(os.path.join(FIXTURES, "v5e_dense.xplane.pb"))
    assert list(trace["devices"]) == ["/device:TPU:0"]
    names = {name for name, _, _ in trace["devices"]["/device:TPU:0"]}
    assert any(n.startswith("_mash_shared_grid") for n in names)
    assert all(" = " not in n for n in names)
    assert 2.0 < tracered.busy_seconds(trace) < 2.5  # 2.28 s of a 24.5 s job
    assert trace["host"]
