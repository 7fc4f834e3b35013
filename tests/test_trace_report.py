"""Pod-wide timeline reconstruction + live status view (ISSUE 10):
tools/trace_report.py and tools/pod_status.py.

Fast tier-1 tests cover the single-process contracts (loadable Chrome
trace, text report sections, membership timeline == epoch_history,
pod_status correctness on a planted store with a byte-for-byte read-only
assertion). The pod cells — a real 3-process jax.distributed CPU pod
traced through a graceful DRAIN and through a SIGKILL death, with the
merged timeline asserted in causal order — are `slow`+`chaos`, run via
``tools/chaos_matrix.py --events``."""

import glob
import hashlib
import importlib.util
import json
import os
import resource
import socket
import subprocess
import sys
import time

import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
WORKER = os.path.join(os.path.dirname(os.path.abspath(__file__)), "_multihost_worker.py")


def _tool(name):
    spec = importlib.util.spec_from_file_location(
        name, os.path.join(REPO, "tools", f"{name}.py")
    )
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@pytest.fixture()
def trace_report():
    return _tool("trace_report")


@pytest.fixture()
def pod_status():
    return _tool("pod_status")


@pytest.fixture(autouse=True)
def _reset_telemetry():
    from drep_tpu.utils import telemetry

    yield
    telemetry.configure()


def _packed(n=64, s=32, seed=0):
    from drep_tpu.ops.minhash import PAD_ID, PackedSketches

    rng = np.random.default_rng(seed)
    ids = np.full((n, s), PAD_ID, np.int32)
    cts = np.full(n, s, np.int32)
    pools = [
        np.sort(rng.choice(2**20, size=s * 2, replace=False).astype(np.int32))
        for _ in range(5)
    ]
    for i in range(n):
        ids[i] = np.sort(rng.choice(pools[i % 5], size=s, replace=False))
    return PackedSketches(ids=ids, counts=cts, names=[f"g{i}" for i in range(n)])


# --- fast tier-1: single-process trace_report contracts -------------------


def test_traced_run_produces_loadable_chrome_trace_and_report(
    tmp_path, trace_report
):
    from drep_tpu.parallel.streaming import streaming_mash_edges
    from drep_tpu.utils import telemetry
    from drep_tpu.utils.profiling import counters

    log = str(tmp_path / "log")
    ckpt = str(tmp_path / "ckpt")
    counters.reset()
    telemetry.configure(log_dir=log, enabled=True, pid=0)
    streaming_mash_edges(_packed(), k=21, cutoff=0.2, block=8, checkpoint_dir=ckpt)
    # a synthetic membership bump so the timeline/history cross-check has
    # content even single-process (the pod cells cover the real protocol)
    counters.note_epoch(1, "drain")
    counters.write(log)
    telemetry.close()

    loaded = trace_report.load_events(log)
    evs = loaded["events"]
    assert not loaded["bad_lines"] and not loaded["torn_tails"]
    names = {e["ev"] for e in evs}
    assert {"stripe", "shard_publish", "epoch"} <= names, names

    # chrome trace: loadable JSON, one named track, X spans with dur
    ct = trace_report.chrome_trace(evs)
    ct = json.loads(json.dumps(ct))  # round-trips
    phs = {e["ph"] for e in ct["traceEvents"]}
    assert {"M", "X", "i"} <= phs
    stripes = [
        e for e in ct["traceEvents"] if e["ph"] == "X" and e["name"] == "stripe"
    ]
    assert len(stripes) == 8  # 64 genomes / block 8 -> 8 stripes
    assert all(e["dur"] >= 0 and e["ts"] >= 0 for e in stripes)

    # text report: latency percentiles + the counters cross-check
    with open(os.path.join(log, "perf_counters.json")) as f:
        cdoc = json.load(f)
    rep = trace_report.text_report(evs, cdoc)
    assert "stripe latency" in rep
    assert "epoch 1: drain" in rep
    assert "MATCH" in rep and "MISMATCH" not in rep
    assert trace_report.timeline_matches_history(evs, cdoc)
    # a forged history must be caught
    forged = dict(cdoc, epoch_history=[{"epoch": 1, "reason": "death"}])
    assert not trace_report.timeline_matches_history(evs, forged)

    # the CLI end-to-end: writes the trace file, exits 0
    rc = trace_report.main([log])
    assert rc == 0
    with open(os.path.join(log, "trace.json")) as f:
        assert json.load(f)["traceEvents"]


@pytest.mark.parametrize("source", ["record", "event_log"])
def test_the_phases_table_says_what_the_host_spent_from_a_record_or_an_event_log(
    tmp_path, trace_report, source, capsys
):
    """ISSUE 52, the operator's use of the new fields: one line a phase,
    largest self seconds first, with cores, kernel seconds, memory first
    touched, off-CPU seconds and collector seconds; from the record beside
    the logs, or rebuilt from the E lines where a job left none."""
    from drep_tpu.utils import telemetry
    from drep_tpu.utils.profiling import Counters

    log = str(tmp_path / "log")
    telemetry.configure(log_dir=log, enabled=True, pid=0)
    c = Counters()
    with c.span("job"):
        with c.span("stage:cluster"):
            with c.span("secondary/pack", workers=2):
                cpu0 = time.thread_time()
                while time.thread_time() - cpu0 < 0.03:  # by its own CPU, whatever the cores lent
                    hashlib.sha1(bytes(1 << 16)).digest()
            time.sleep(0.01)
        if source == "record":
            c.write(log, device=False)
    telemetry.close()
    evs = trace_report.load_events(log)["events"]
    rebuilt = trace_report.phases_from_events(evs)
    assert set(rebuilt) == {"job", "stage:cluster", "secondary/pack"}
    pack, stage = rebuilt["secondary/pack"], rebuilt["stage:cluster"]
    assert pack["calls"] == 1 and pack["cpu_s"] >= 0.02
    assert pack["self_cpu_s"] == pytest.approx(pack["cpu_s"])
    # self values by nesting: the stage's own is what the pack does not cover
    assert stage["self_seconds"] == pytest.approx(stage["seconds"] - pack["seconds"], abs=1e-6)
    assert stage["self_cpu_s"] == pytest.approx(stage["cpu_s"] - pack["cpu_s"], abs=1e-6)
    assert 0.005 <= stage["self_seconds"] < pack["seconds"]
    if source == "record":
        with open(os.path.join(log, "perf_counters.json")) as f:
            cdoc = json.load(f)
        rep = trace_report.text_report(evs, cdoc)
        assert "(perf_counters.json)" in rep
        # the record alone, by its path
        assert trace_report.main([os.path.join(log, "perf_counters.json")]) == 0
        alone = capsys.readouterr().out
        assert "secondary/pack" in alone and "cores" in alone
    else:
        rep = trace_report.text_report(evs)
        assert "(rebuilt from the event log)" in rep
    table = rep[rep.index("phases, largest self seconds first"):].splitlines()
    assert table[1].split() == ["phase", "calls", "seconds", "self", "cores", "sys", "MiB", "new",
                                "off-CPU", "gc"]
    rows = [ln.split() for ln in table[2:] if ln.strip()]
    assert [r[0] for r in rows][0] == "secondary/pack"  # the largest self seconds first
    assert {r[0] for r in rows} == {"job", "stage:cluster", "secondary/pack"}
    cores = float(rows[0][4])
    assert 0.0 < cores < 1.6  # one thread burning: one core at most


def test_the_phases_table_leaves_first_touched_memory_out_where_the_kernel_counts_no_faults(trace_report):
    """The chip host's kernel reports no page fault, ever: zero in every
    phase is no source, and the column says so; one phase that faulted makes
    a quiet one's zero a reading."""
    ph = {"job": {"seconds": 2.0, "self_seconds": 0.5, "calls": 1, "cpu_s": 3.0, "self_sys_s": 0.1,
                  "self_minor_faults": 0, "self_thread_cpu_s": 0.4, "gc_s": 0.0},
          "load": {"seconds": 1.5, "self_seconds": 1.5, "calls": 1, "cpu_s": 2.5, "self_sys_s": 0.9,
                   "self_minor_faults": 0, "self_thread_cpu_s": 0.5, "gc_s": 0.0}}
    rows = [ln.split() for ln in trace_report.phases_table(ph).splitlines()[1:]]
    assert [r[0] for r in rows] == ["load", "job"] and [r[6] for r in rows] == ["-", "-"]
    ph["load"]["self_minor_faults"] = 512
    rows = [ln.split() for ln in trace_report.phases_table(ph).splitlines()[1:]]
    assert [r[6] for r in rows] == [f"{512 * resource.getpagesize() / 2**20:.1f}", "0.0"]


def test_trace_report_surfaces_unclosed_spans_as_crash_evidence(
    tmp_path, trace_report
):
    from drep_tpu.utils import telemetry

    telemetry.configure(log_dir=str(tmp_path), enabled=True, pid=0)
    telemetry._emit("stripe", "B", {"bi": 4})  # B with no E: died in flight
    telemetry.event("fault", kind="watchdog_trips", n=1)
    telemetry.close()
    loaded = trace_report.load_events(str(tmp_path))
    spans, unclosed = trace_report.pair_spans(loaded["events"])
    assert spans == []
    assert len(unclosed) == 1 and unclosed[0]["ev"] == "stripe"
    rep = trace_report.text_report(loaded["events"])
    assert "crash evidence" in rep
    ct = trace_report.chrome_trace(loaded["events"])
    assert any(e["name"] == "UNCLOSED stripe" for e in ct["traceEvents"])


def test_timeline_match_accepts_partial_views(trace_report):
    """Original members must match exactly; a joiner's (or early-drained
    member's) history is a contiguous run of the merged timeline and must
    not read as MISMATCH — anything else is a real disagreement."""
    evs = [
        {"ev": "epoch", "ph": "i", "pid": 0, "wall": 1.0,
         "args": {"epoch": 1, "reason": "death"}},
        {"ev": "epoch", "ph": "i", "pid": 0, "wall": 2.0,
         "args": {"epoch": 2, "reason": "join"}},
    ]

    def doc(*hist):
        return {"epoch_history": [{"epoch": e, "reason": r} for e, r in hist]}

    assert trace_report.timeline_matches_history(evs, doc((1, "death"), (2, "join")))
    assert trace_report.timeline_matches_history(evs, doc((2, "join")))  # joiner
    assert trace_report.timeline_matches_history(evs, doc((1, "death")))  # drained early
    assert not trace_report.timeline_matches_history(evs, doc((1, "drain")))
    assert not trace_report.timeline_matches_history(
        evs, doc((2, "join"), (1, "death"))  # wrong order
    )
    assert not trace_report.timeline_matches_history(evs, doc())


# --- fast tier-1: pod_status on a planted store ---------------------------


def _dir_digest(root):
    """Byte-for-byte fingerprint of a directory tree: relative path,
    size, mtime_ns, and content hash of every file."""
    out = {}
    for dirpath, _dirs, files in os.walk(root):
        for name in files:
            p = os.path.join(dirpath, name)
            st = os.stat(p)
            with open(p, "rb") as f:
                h = hashlib.sha256(f.read()).hexdigest()
            out[os.path.relpath(p, root)] = (st.st_size, st.st_mtime_ns, h)
    return out


def test_pod_status_reads_a_planted_store_and_stays_read_only(
    tmp_path, pod_status, monkeypatch
):
    """A mid-run pod frozen in time: 2 live members, 1 drained, 1 dead,
    a pending join, 5 of 9 stripes published. pod_status must report all
    of it — and the store must be byte-for-byte untouched afterward (the
    `index classify` read-only contract)."""
    from drep_tpu.utils.ckptmeta import atomic_savez
    from drep_tpu.utils.durableio import atomic_write_json

    monkeypatch.setenv("DREP_TPU_HEARTBEAT_S", "5")
    ckpt = tmp_path / "ckpt"
    ckpt.mkdir()
    now = time.time()
    atomic_write_json(
        str(ckpt / "meta.json"), {"n": 72, "block": 8, "n_blocks": 9}
    )
    empty = np.empty(0, np.int64)
    for bi in (0, 1, 2, 3):
        atomic_savez(str(ckpt / f"row_{bi:05d}.npz"), ii=empty, jj=empty,
                     dist=np.empty(0, np.float32))
    # a re-dealt epoch-1 shard of stripe 4 (counts once in progress)
    atomic_savez(str(ckpt / "row_00004.e01.npz"), ii=empty, jj=empty,
                 dist=np.empty(0, np.float32))
    for pid in (0, 2):  # fresh beats
        (ckpt / f".pod-hb.p{pid}").write_bytes(b"1")
    (ckpt / ".pod-hb.p3").write_bytes(b"1")
    os.utime(ckpt / ".pod-hb.p3", (now - 120, now - 120))  # stale beat
    atomic_write_json(str(ckpt / ".pod-drain.p1"),
                      {"seq": 1, "epoch": 1, "pairs": 99, "at": now})
    atomic_write_json(str(ckpt / ".pod-dead.p3"),
                      {"by": 0, "seq": 1, "at": now})
    atomic_write_json(str(ckpt / ".pod-join.p5"), {"token": "t", "at": now})

    before = _dir_digest(str(ckpt))
    st = pod_status.collect(str(ckpt))
    text = pod_status.render(st)
    after = _dir_digest(str(ckpt))
    assert before == after, "pod_status wrote/touched the store"

    assert st["live"] == [0, 2]
    assert st["draining"] == [1]
    assert st["dead"] == [3]
    assert st["pending_joins"] == [5]
    assert st["members"]["1"]["pairs"] == 99  # honest drained partial
    assert st["epoch"] >= 1
    assert st["shards_published"] == 5 and st["shards_total"] == 9
    assert st["progress"] == round(5 / 9, 4)
    assert "p1   draining" in text and "5/9 shards" in text

    # the CLI --json path is read-only too
    rc = pod_status.main([str(ckpt), "--json"])
    assert rc == 0
    assert _dir_digest(str(ckpt)) == before


def test_pod_status_empty_store(tmp_path, pod_status):
    st = pod_status.collect(str(tmp_path))
    assert st["members"] == {} and st["shards_published"] == 0
    assert pod_status.collect(str(tmp_path / "missing")).get("error")


# --- pod cells (slow/chaos): drain + death with events on -----------------

CADENCE_S = 0.25


def _free_port():
    s = socket.socket()
    s.bind(("localhost", 0))
    port = s.getsockname()[1]
    s.close()
    return port


def _launch_pod(outdir, ckpt, nproc, faults, extra_env=None):
    port = _free_port()
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=2"
    env["PYTHONPATH"] = REPO + os.pathsep + env.get("PYTHONPATH", "")
    env["DREP_TPU_HEARTBEAT_S"] = str(CADENCE_S)
    env["DREP_TPU_COLLECTIVE_TIMEOUT_S"] = "90"
    env["DREP_TPU_EVENTS"] = "on"
    env.pop("DREP_TPU_POD_JOIN", None)
    env["DREP_TPU_FAULTS"] = faults
    env.update(extra_env or {})
    os.makedirs(outdir, exist_ok=True)
    return [
        subprocess.Popen(
            [
                sys.executable, WORKER, str(i), str(nproc),
                f"localhost:{port}", str(outdir), "elastic", str(ckpt),
            ],
            env=env, stdout=subprocess.PIPE, stderr=subprocess.STDOUT, cwd=REPO,
        )
        for i in range(nproc)
    ]


def _reap(procs, timeout=300):
    outs = []
    try:
        for p in procs:
            out, _ = p.communicate(timeout=timeout)
            outs.append(out.decode(errors="replace"))
    finally:
        for p in procs:
            if p.poll() is None:
                p.kill()
                p.communicate()
    return outs


def _first(evs, name, pid=None):
    for r in evs:
        if r["ev"] == name and (pid is None or r.get("pid") == pid):
            return r
    return None


@pytest.mark.chaos
@pytest.mark.slow
def test_drain_pod_events_timeline_causal(tmp_path, trace_report, pod_status):
    """The ``--events`` chaos cell (ISSUE 10 satellite): the drain-mid-
    streaming pod re-run with tracing on. The merged timeline must hold
    the drain note, the epoch bump, and the re-deal (plus the epoch-1
    re-dealt stripe spans) in CAUSAL order; the Chrome trace must load;
    the membership timeline must equal the survivors' epoch_history; and
    pod_status must read the live store mid-run."""
    outdir, ckpt = str(tmp_path / "out"), str(tmp_path / "ckpt")
    pod = _launch_pod(
        outdir, ckpt, nproc=3,
        faults=(
            "process_death:drain:1.0:proc=1:skip=1,"
            "process_death:sleep:1.0:secs=0.15"
        ),
        extra_env={"DREP_TPU_TEST_MAX_DEAD": "0"},
    )
    # live status while the pod runs: once the departure note is out,
    # the read-only view must see the draining member and live survivors
    mid = None
    deadline = time.time() + 240
    while time.time() < deadline and any(p.poll() is None for p in pod):
        if os.path.exists(os.path.join(ckpt, ".pod-drain.p1")):
            mid = pod_status.collect(ckpt)
            break
        time.sleep(0.05)
    outs = _reap(pod)
    for i, p in enumerate(pod):
        assert p.returncode == 0, f"worker {i} failed:\n{outs[i]}"
    assert os.path.exists(os.path.join(outdir, "drained_1")), outs[1]
    if mid is not None and any(p in mid["draining"] for p in (1,)):
        # racy by nature (the pod may finish between the note and the
        # poll) — when the snapshot DID land mid-run, it must be right
        assert 1 in mid["draining"], mid
        assert set(mid["live"]) <= {0, 2}, mid

    log = os.path.join(outdir, "log")
    loaded = trace_report.load_events(log)
    evs = loaded["events"]
    assert not loaded["bad_lines"], loaded["bad_lines"]
    assert len(glob.glob(os.path.join(log, "events.p*.jsonl"))) == 3

    # causal order: announce (p1) -> adoption+epoch bump (a survivor) ->
    # re-deal instant -> an epoch-1 stripe span
    announce = _first(evs, "drain_announce", pid=1)
    adopted = _first(evs, "drain_adopted")
    bump = next(
        r for r in evs
        if r["ev"] == "epoch" and (r.get("args") or {}).get("reason") == "drain"
    )
    re_deal = _first(evs, "re_deal")
    assert announce and adopted and re_deal
    assert announce["wall"] <= adopted["wall"] <= re_deal["wall"]
    assert announce["wall"] <= bump["wall"]
    redealt = [
        r for r in evs
        if r["ev"] == "stripe" and r["ph"] == "E"
        and (r.get("args") or {}).get("epoch", 0) >= 1
    ]
    assert redealt, "no re-dealt (epoch>=1) stripe spans in the timeline"
    assert all(bump["wall"] <= r["wall"] for r in redealt)

    # loadable Chrome trace with one track per member
    ct = json.loads(json.dumps(trace_report.chrome_trace(evs)))
    tracks = {e["pid"] for e in ct["traceEvents"] if e["ph"] == "M"}
    assert tracks == {0, 1, 2}

    # membership timeline == every survivor's epoch_history, exactly
    for pid in (0, 2):
        with open(os.path.join(outdir, f"counters_{pid}.json")) as f:
            cdoc = json.load(f)
        assert cdoc["epoch_history"], cdoc
        assert trace_report.timeline_matches_history(evs, cdoc), (
            trace_report.membership_timeline(evs), cdoc["epoch_history"],
        )
    rep = trace_report.text_report(evs, cdoc)
    assert "epoch 1: drain" in rep and "MATCH" in rep

    # post-run status from the store alone: survivors finished, the
    # drained member visible with its honest partial count
    st = pod_status.collect(ckpt)
    assert set(st["finished"]) == {0, 2}, st
    assert st["draining"] == [1]
    assert st["epoch"] >= 1


@pytest.mark.chaos
@pytest.mark.slow
def test_death_pod_events_timeline(tmp_path, trace_report):
    """The death cell with tracing on: a SIGKILLed member's log simply
    STOPS (its in-flight stripe span stays unclosed — the crash
    evidence), the survivors' merged timeline carries the death verdict
    and the epoch bump in order, and the membership timeline equals the
    survivors' epoch_history."""
    outdir, ckpt = str(tmp_path / "out"), str(tmp_path / "ckpt")
    pod = _launch_pod(
        outdir, ckpt, nproc=3,
        faults="process_death:kill:1.0:proc=1:skip=1",
    )
    outs = _reap(pod)
    for i in (0, 2):
        assert pod[i].returncode == 0, f"survivor {i} failed:\n{outs[i]}"
        assert os.path.exists(os.path.join(outdir, f"ok_{i}")), outs[i]

    log = os.path.join(outdir, "log")
    loaded = trace_report.load_events(log)
    evs = loaded["events"]
    assert not loaded["bad_lines"], loaded["bad_lines"]

    verdict = _first(evs, "death_verdict")
    bump = next(
        r for r in evs
        if r["ev"] == "epoch" and (r.get("args") or {}).get("reason") == "death"
    )
    assert verdict and (verdict["args"]["peers"] == [1])
    assert verdict["wall"] <= bump["wall"]
    # the victim's stream ends before the verdict lands (staleness window)
    last_p1 = max(
        (r["wall"] for r in evs if r.get("pid") == 1), default=None
    )
    assert last_p1 is not None and last_p1 < verdict["wall"]
    # its killed stripe is the unclosed span
    _spans, unclosed = trace_report.pair_spans(evs)
    assert any(
        b.get("pid") == 1 and b["ev"] == "stripe" for b in unclosed
    ), unclosed

    for pid in (0, 2):
        with open(os.path.join(outdir, f"counters_{pid}.json")) as f:
            cdoc = json.load(f)
        assert trace_report.timeline_matches_history(evs, cdoc), (
            trace_report.membership_timeline(evs), cdoc["epoch_history"],
        )
