"""The readers of what the host spent inside a span (ISSUE 52): the shared
`benchmark/host.py` and the seven `benchmark/layer_metrics/host_*.py`,
`secondary_checkpoint_sys_s.py` and `load_sketches_fault_gib.py`, each on a
hand-made record, with the fields and without; `resume_jobs.merge_records`
over two attempts' records, which must sum them with no edit to the
benchmark; the three whose source a sandboxed kernel does not keep; and the
declaration of the other four in BENCHMARK.json."""

import os
import resource

import pytest

from benchmark import cells, resume_jobs

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
GIB = float(2**30)
PAGE = resource.getpagesize()


def _phase(seconds, self_seconds, thread="main", **host) -> dict:
    return {"seconds": seconds, "self_seconds": self_seconds, "calls": 1, "thread": thread, **host}


def _record(scale: float = 1.0) -> dict:
    """A job's `phases` as `Counters._phases_report` writes it, every number
    times `scale`: `job` holds two phases and a device wait; a worker
    thread's span carries the thread's fields alone."""
    def host(cpu, sys, faults, thread_cpu, invol, gc_s=0.0, whole=None):
        whole_cpu, whole_sys = whole or (cpu, sys)
        return {"cpu_s": whole_cpu * scale, "self_cpu_s": cpu * scale, "sys_s": whole_sys * scale,
                "self_sys_s": sys * scale, "self_minor_faults": int(faults * scale),
                "self_major_faults": 0, "self_thread_cpu_s": thread_cpu * scale,
                "self_invol_switches": int(invol * scale), "self_vol_switches": 0,
                "gc_s": gc_s * scale, "gc_collections": int(bool(gc_s))}
    return {"phases": {
        "job": _phase(10.0 * scale, 1.0 * scale, **host(0.5, 0.1, 1024, 0.5, 2, whole=(14.0, 3.6))),
        "stage:ingest_or_cache": _phase(2.0 * scale, 2.0 * scale, **host(4.0, 1.5, 262144, 0.5, 4)),
        "secondary/checkpoint": _phase(3.0 * scale, 3.0 * scale, **host(2.5, 2.0, 2048, 2.25, 8, gc_s=0.25)),
        "secondary/wait": _phase(4.0 * scale, 4.0 * scale, **host(7.0, 0.0, 0, 0.5, 1)),
        "secondary/pack@other": _phase(1.0 * scale, 1.0 * scale, thread="other",
                                       self_thread_cpu_s=0.75 * scale, self_invol_switches=int(64 * scale),
                                       self_vol_switches=0, gc_s=0.5 * scale, gc_collections=1),
    }}


def _run(*records) -> dict:
    return {"jobs": [{"record": rec} for rec in records]}


def _read(name: str, run: dict):
    path = os.path.join(REPO, "benchmark", "layer_metrics", name + ".py")
    return cells.load_module(path).read(run)


# what each reader makes of `_record()`: a worker thread's phase is in none of them
WANT = {
    "host_sys_s": 3.6,
    "host_first_touch_gib": (1024 + 262144 + 2048) * PAGE / GIB,
    # the main thread off its CPU outside the device wait: 0.5 + 1.5 + 0.75
    "host_offcpu_s": (1.0 - 0.5) + (2.0 - 0.5) + (3.0 - 2.25),
    "host_gc_s": 0.25,
    "host_preempted": 2 + 4 + 8 + 1,
    "secondary_checkpoint_sys_s": 2.0,
    "load_sketches_fault_gib": 262144 * PAGE / GIB,
}


@pytest.mark.parametrize("name", sorted(WANT))
def test_a_reader_reads_its_field_from_a_hand_made_record(name):
    assert _read(name, _run(_record())) == pytest.approx(WANT[name])
    # the median over the window's jobs: the middle one of three
    three = _run(_record(1.0), _record(3.0), _record(2.0))
    assert _read(name, three) == pytest.approx(2 * WANT[name], rel=1e-3)


@pytest.mark.parametrize("name", sorted(WANT))
def test_a_reader_gives_none_on_a_record_without_the_fields(name):
    """The parent's record: seconds, self seconds, calls and thread alone."""
    bare = {"phases": {k: {f: v for f, v in ph.items() if f in ("seconds", "self_seconds", "calls", "thread")}
                       for k, ph in _record()["phases"].items()}}
    assert _read(name, _run(bare)) is None
    assert _read(name, _run({})) is None and _read(name, {"jobs": []}) is None


def test_merge_records_sums_the_host_fields_over_a_jobs_attempts():
    first, second = _record(1.0), _record(2.0)
    del second["phases"]["secondary/wait"]  # an attempt that never reached the phase
    merged = resume_jobs.merge_records([first, second])
    ckpt = merged["phases"]["secondary/checkpoint"]
    assert ckpt["self_sys_s"] == pytest.approx(6.0) and ckpt["cpu_s"] == pytest.approx(7.5)
    assert ckpt["self_minor_faults"] == 3 * 2048 and ckpt["gc_collections"] == 2
    assert ckpt["thread"] == "main" and ckpt["calls"] == 2
    assert merged["phases"]["secondary/wait"] == first["phases"]["secondary/wait"]
    other = merged["phases"]["secondary/pack@other"]
    assert other["self_thread_cpu_s"] == pytest.approx(2.25) and "cpu_s" not in other
    # the readers take the merged record as one job's
    assert _read("host_sys_s", _run(merged)) == pytest.approx(3 * 3.6)
    assert _read("secondary_checkpoint_sys_s", _run(merged)) == pytest.approx(6.0)
    assert _read("host_preempted", _run(merged)) == 3 * (2 + 4 + 8) + 1


# the readers whose source a sandboxed kernel does not keep
UNCOUNTED = ("host_first_touch_gib", "host_preempted", "load_sketches_fault_gib")
DECLARED = sorted(set(WANT) - set(UNCOUNTED))


@pytest.mark.parametrize("name", UNCOUNTED)
def test_a_kernel_that_counts_nothing_is_no_source_and_not_a_reading_of_zero(name):
    """The chip host's kernel reports no page fault, ever, and a voluntary
    switch in one job of a hundred: a job whose main thread touched no page
    has nothing to read of faults or of switches; one quiet phase in a job
    that counts reads 0."""
    dead = _record()
    for phase in dead["phases"].values():
        phase.update({f: 0 for f in ("self_minor_faults", "self_invol_switches") if f in phase})
    dead["phases"]["secondary/wait"]["self_vol_switches"] = 1  # the one that kernel did count
    assert _read(name, _run(dead)) is None
    assert _read(name, _run(dead, _record())) == pytest.approx(WANT[name])  # the jobs that count
    quiet = _record()
    quiet["phases"]["stage:ingest_or_cache"].update(self_minor_faults=0, self_invol_switches=0)
    assert _read(name, _run(quiet)) is not None
    if name == "load_sketches_fault_gib":
        assert _read(name, _run(quiet)) == 0.0
    # the readers of seconds take the same record as it is
    assert _read("host_sys_s", _run(dead)) == pytest.approx(WANT["host_sys_s"])


def test_the_declared_are_in_benchmark_json_with_their_readers_and_their_cells():
    """By name, wherever they stand: the next PR appends after them."""
    spec = cells.read_json(os.path.join(REPO, "BENCHMARK.json"))
    by_name = {m["name"]: m for m in spec["per_layer"]}
    every = [w["name"] for w in spec["workloads"]]
    for name in WANT:
        assert os.path.exists(os.path.join(REPO, "benchmark", "layer_metrics", name + ".py"))
    for name in DECLARED:
        m = by_name[name]
        assert (m["source"], m["moves"], m["better"]) == ("program_counter", "job_wall_s", "lower")
        assert set(m["workloads"]) <= set(every)
    for name in ("host_sys_s", "host_offcpu_s", "host_gc_s"):
        assert by_name[name]["workloads"] == every and by_name[name]["layer"] == "workflow"
    assert by_name["secondary_checkpoint_sys_s"]["workloads"] == by_name["secondary_checkpoint_s"]["workloads"]
    assert by_name["secondary_checkpoint_sys_s"]["layer"] == by_name["secondary_checkpoint_s"]["layer"]
