"""ISSUE 47: a ONE-process `compare` job that is stopped with notice and run
again with the same command on the same work directory. The stop is honoured
at the next boundary (a streaming stripe's shard published, a primary
cluster's secondary checkpoint published) with exit code 0 and the attempt's
record written; the rerun computes exactly what was not published and ends
with tables byte for byte those of an undisturbed job. The deterministic
route is the fault registry's `drain` mode (`process_death`, and the site
`secondary_checkpoint` this issue added); the same path takes a real SIGTERM.

A planted collection of 100 genomes under `--streaming_block 32`: four
stripes (10 tiles), two engine clusters (40 and 34 genomes), six batched ones
in one call, six singletons."""

import json
import os
import shutil
import signal
import subprocess
import sys
import time

import pytest

from benchmark import cells, resume_jobs

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ARGV = ["--greedy_secondary_clustering", "--streaming_primary", "--skip_plots",
        "--streaming_block", "32"]
STRIPES, CLUSTERS = 4, 8
TILES = STRIPES * (STRIPES + 1) // 2
PRIMARY = "process_death:drain:skip=2"  # two stripes publish, the third's head is the boundary
SECONDARY = "secondary_checkpoint:drain:skip=3"  # both engine clusters and two of the batched six


def _attempt(wd: str, fault: str | None) -> dict:
    """One call of the CLI's own function; the drain flag and the fault
    registry are left as they were found. {"exit", "record"}."""
    from drep_tpu import controller
    from drep_tpu.parallel import faulttol
    from drep_tpu.utils import faults, telemetry

    faults.configure(fault)
    code = None
    try:
        controller.main(["compare", wd, *ARGV])
    except SystemExit as e:
        code = e.code
    finally:
        faulttol.clear_drain()
        faults.configure(None)
        telemetry.configure()
    with open(os.path.join(wd, "log", "perf_counters.json")) as f:
        record = json.load(f)
    os.unlink(os.path.join(wd, "log", "perf_counters.json"))
    return {"exit": code, "record": record}


@pytest.fixture(scope="module")
def planted(tmp_path_factory):
    gen = cells.load_module(os.path.join(REPO, "benchmark", "generators", "planted_release.py"))
    cfg = cells.read_json(os.path.join(REPO, "benchmark", "configs", "gtdb_release_preempt_6k.json"))
    cfg["data"].update({"n": 100, "s_scaled": 1900, "clusters": [
        {"size": 40, "count": 1, "groups": [28, 12]}, {"size": 34, "count": 1, "groups": [22, 12]},
        {"size": 6, "count": 1, "groups": [6]}, {"size": 4, "count": 2, "groups": [4]},
        {"size": 2, "count": 3, "groups": [2]}, {"size": 1, "count": 6, "groups": [1]}]})
    out = str(tmp_path_factory.mktemp("resume_drain"))
    pristine = gen.prepare(cfg, 47, out)["workdir"]
    plain = os.path.join(out, "undisturbed")
    shutil.copytree(pristine, plain)
    done = _attempt(plain, None)
    assert done["exit"] is None and "drain" not in done["record"]
    return {"out": out, "pristine": pristine, "digests": resume_jobs.table_digests(plain),
            "record": done["record"]}


def _copy(planted, name: str) -> str:
    wd = os.path.join(planted["out"], name)
    shutil.copytree(planted["pristine"], wd)
    return wd


def _stores(wd: str) -> tuple[list[str], list[str]]:
    """The file names of the shards and of the checkpoints the stores hold."""
    held = resume_jobs.published(wd)
    return ([f"row_{bi:05d}.npz" for bi in held["stripes"]],
            [f"pc_{pc:06d}.npz" for pc in held["clusters"]])


def test_an_undisturbed_job_computes_everything_and_reads_nothing_back(planted):
    rec = planted["record"]
    assert rec["resume"] == {"tiles_computed": TILES, "clusters_computed": CLUSTERS}
    assert rec["primary_stream_slots"]["stripes"] == STRIPES
    assert not [name for name in rec["phases"] if "resume_load" in name]


@pytest.mark.parametrize("name,stops", [
    ("primary", [PRIMARY]), ("secondary", [SECONDARY]), ("both", [PRIMARY, SECONDARY])])
def test_a_stopped_job_goes_on_from_its_stores_and_ends_with_the_same_bytes(planted, name, stops):
    wd = _copy(planted, "stopped_" + name)
    stripes_held, clusters_held = 0, 0
    for fault in stops:
        got = _attempt(wd, fault)
        rec, drain = got["record"], got["record"]["drain"]
        assert got["exit"] == 0 and not os.path.exists(os.path.join(wd, "data_tables", "Cdb.csv"))
        did = rec["resume"]
        # exactly the unpublished stripes were dispatched, the published ones read back
        assert did.get("stripes_resumed", 0) == stripes_held
        if fault == PRIMARY:
            assert drain == {**drain, "stage": "primary", "stripes_published": 2, "next_stripe": 2}
            assert did["tiles_computed"] == STRIPES + (STRIPES - 1) and "clusters_computed" not in did
            assert "secondary_compare" not in rec["stages"]
            # the stage as far as it came: its pairs and seconds are in the attempt's record
            assert 0 < rec["stages"]["primary_compare"]["pairs"] < 100 * 99 // 2
            assert rec["stages"]["primary_compare"]["tiles_computed"] == did["tiles_computed"]
        else:
            assert drain == {**drain, "stage": "secondary", "clusters_published": 4}
            assert did["tiles_computed"] == TILES - sum(STRIPES - b for b in range(stripes_held))
            # both engine clusters, then the one batched call of six: the call in flight
            assert did["clusters_computed"] == CLUSTERS and "clusters_resumed" not in did
        assert drain["requested_monotonic_s"] <= time.monotonic() and drain["after_s"] > 0
        assert rec["fault_tolerance"] == {f"injected_{fault.split(':')[0]}_drain": 1}
        assert rec["phases"]["job"]["seconds"] > 0 and "stage:evaluate" not in rec["phases"]
        rows, pcs = _stores(wd)
        stripes_held, clusters_held = len(rows), len(pcs)
        assert (stripes_held, clusters_held) == ((2, 0) if fault == PRIMARY else (STRIPES, 4))
    last = _attempt(wd, None)
    assert last["exit"] is None and "drain" not in last["record"]
    did = last["record"]["resume"]
    assert did["stripes_resumed"] == stripes_held and did["shard_bytes"] > 0
    assert did["tiles_resumed"] == sum(STRIPES - b for b in range(stripes_held))
    assert did["tiles_computed"] == TILES - did["tiles_resumed"]
    assert did.get("clusters_resumed", 0) == clusters_held
    assert did["clusters_computed"] == CLUSTERS - clusters_held
    assert (did.get("checkpoint_bytes", 0) > 0) == (clusters_held > 0)
    assert resume_jobs.table_digests(wd) == planted["digests"]
    assert "fault_tolerance" not in last["record"]  # nothing healed, nothing retried


def test_a_flipped_byte_in_a_shard_and_in_a_checkpoint_is_refused_recomputed_and_counted(planted):
    wd = _copy(planted, "flipped")
    assert _attempt(wd, SECONDARY)["exit"] == 0
    rows, pcs = _stores(wd)
    assert len(rows) == STRIPES and len(pcs) == 4
    for path in (os.path.join(wd, "data", "streaming_primary", rows[1]),
                 os.path.join(wd, "data", "secondary_checkpoints", pcs[-1])):
        with open(path, "r+b") as f:
            f.seek(os.path.getsize(path) // 2)
            byte = f.read(1)
            f.seek(-1, os.SEEK_CUR)
            f.write(bytes([byte[0] ^ 0x01]))
    last = _attempt(wd, None)
    did = last["record"]["resume"]
    assert last["record"]["fault_tolerance"] == {"corrupt_shards_healed": 2}
    assert did["stripes_resumed"] == STRIPES - 1 and did["tiles_computed"] == STRIPES - 1  # stripe 1's
    assert did["clusters_resumed"] == 3 and did["clusters_computed"] == CLUSTERS - 3
    assert last["record"]["phases"]["primary/resume_load"]["calls"] == STRIPES  # the refused one too
    assert resume_jobs.table_digests(wd) == planted["digests"]


@pytest.mark.parametrize("stage,pace,store,prefix", [
    ("primary", "process_death:sleep:secs=0.25", "streaming_primary", "row_"),
    ("secondary", "secondary_checkpoint:sleep:secs=0.25", "secondary_checkpoints", "pc_")])
def test_a_real_sigterm_to_a_one_process_compare_exits_0_at_the_next_boundary(planted, stage, pace,
                                                                              store, prefix):
    """The same path as the fault mode, with the signal: the job is paced at
    the stage's boundaries so that the signal lands inside the stage, leaves
    at the next one with exit code 0 and its record written, long before the
    grace thread's thirty seconds, and the rerun completes."""
    wd = _copy(planted, "sigterm_" + stage)
    env = {k: v for k, v in os.environ.items() if k != "XLA_FLAGS"}  # one device
    env.update({"JAX_PLATFORMS": "cpu", "DREP_TPU_FAULTS": pace})
    cmd = [sys.executable, "-m", "drep_tpu", "compare", wd, *ARGV]
    proc = subprocess.Popen(cmd, cwd=REPO, env=env, stdout=subprocess.PIPE, stderr=subprocess.STDOUT,
                            text=True)
    held = os.path.join(wd, "data", store)
    deadline = time.monotonic() + 120
    while time.monotonic() < deadline and proc.poll() is None:
        if os.path.isdir(held) and any(n.startswith(prefix) for n in os.listdir(held)):
            break
        time.sleep(0.02)
    assert proc.poll() is None, proc.stdout.read()[-2000:]
    sent = time.monotonic()
    proc.send_signal(signal.SIGTERM)
    out, _ = proc.communicate(timeout=60)
    took = time.monotonic() - sent
    assert proc.returncode == 0, out[-2000:]
    # the boundary is at most one paced unit away; the rest is the record and the interpreter's exit
    assert took < 5.0, took
    assert "drained cleanly" in out and "drain grace" not in out
    assert not os.path.exists(os.path.join(wd, "data_tables", "Cdb.csv"))
    with open(os.path.join(wd, "log", "perf_counters.json")) as f:
        rec = json.load(f)
    assert rec["drain"]["stage"] == stage and rec["n_devices"] == 1
    # from the request to the boundary, on the program's own clock: inside a second
    assert rec["drain"]["after_s"] > 0 and "fault_tolerance" in rec
    rows, pcs = _stores(wd)
    assert 0 < len(rows if stage == "primary" else pcs) < (STRIPES if stage == "primary" else CLUSTERS)
    env.pop("DREP_TPU_FAULTS")
    again = subprocess.run(cmd, cwd=REPO, env=env, capture_output=True, text=True, timeout=300)
    assert again.returncode == 0, again.stderr[-2000:]
    assert resume_jobs.table_digests(wd) == planted["digests"]
    with open(os.path.join(wd, "log", "perf_counters.json")) as f:
        did = json.load(f)["resume"]
    assert did["stripes_resumed"] == len(rows) and did.get("clusters_resumed", 0) == len(pcs)


def test_the_drain_mode_is_taken_at_the_three_boundary_sites_and_nowhere_else():
    from drep_tpu.utils import faults

    assert faults.DRAIN_SITES == ("process_death", "ring_step", "secondary_checkpoint")
    assert set(faults.DRAIN_SITES) <= set(faults.SITES)
    for site in faults.DRAIN_SITES:
        assert faults._parse(f"{site}:drain:skip=3")[site][0].skip == 3
    for site in ("secondary_batch", "streaming_tile", "shard_write"):
        with pytest.raises(faults.FaultSpecError, match="safe-boundary"):
            faults._parse(f"{site}:drain")


def test_with_nothing_pending_a_boundary_is_one_flag_test_and_books_nothing():
    from drep_tpu.parallel import faulttol
    from drep_tpu.utils.profiling import counters

    counters.reset()
    assert not faulttol.drain_requested()
    assert faulttol.drain_at_boundary("secondary", clusters_published=1) is None
    assert counters.drain == {} and "drain" not in counters.report()
    faulttol.request_drain()
    try:
        with pytest.raises(faulttol.PodDrained, match="secondary: drained at a safe boundary"):
            faulttol.drain_at_boundary("secondary", clusters_published=1)
        assert counters.report()["drain"]["clusters_published"] == 1
    finally:
        faulttol.clear_drain()
        counters.reset()
    assert "drain" not in counters.report()
