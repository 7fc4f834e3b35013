"""Autoscaling controller chaos cells (ISSUE 15) — real multi-process
CPU pods GOVERNED from outside, `tools/chaos_matrix.py --autoscale`.

Cell 1: a 3-process streaming pod under ``--deadline`` pressure. The
controller (a separate ``tools/pod_autoscale.py`` process that never
touches the workers) watches the checkpoint dir, decides scale_up, and
spawns a joiner with ``DREP_TPU_POD_JOIN=auto``; the joiner is admitted
mid-run and every member finishes with edges BYTE-IDENTICAL to the
fixed-membership oracle, with ``autoscale_decision`` instants in the
merged event trace next to the membership timeline and
``autoscale_churn`` provenance booked by every member.

Cell 2: the ring-phase JOIN upgrade at D=3 (3 processes x 1 forced host
device). A gated joiner is admitted mid-dense-phase; the pod KEEPS its
collective step schedule (pure-join bumps are join-tolerant) while the
joiner consumes whole ring steps from the schedule tail — pinned
bit-identical to the MONOLITHIC fixed-membership reference, with the
joiner's step participation (``ring_join_tail_blocks``) asserted, not
just standalone block recovery.

Marked slow+chaos (pod launches + interpreter startups).
"""

import json
import os
import socket
import subprocess
import sys
import time

import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)
WORKER = os.path.join(os.path.dirname(os.path.abspath(__file__)), "_multihost_worker.py")

CADENCE_S = 0.25

pytestmark = [pytest.mark.chaos, pytest.mark.slow]


def _free_port() -> int:
    s = socket.socket()
    s.bind(("localhost", 0))
    port = s.getsockname()[1]
    s.close()
    return port


def _base_env(faults=None, extra=None, ndev=2):
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={ndev}"
    env["DREP_TPU_TEST_CPU_DEVICES"] = str(ndev)
    env["PYTHONPATH"] = REPO + os.pathsep + env.get("PYTHONPATH", "")
    env["DREP_TPU_HEARTBEAT_S"] = str(CADENCE_S)
    env["DREP_TPU_COLLECTIVE_TIMEOUT_S"] = "120"
    env.pop("DREP_TPU_FAULTS", None)
    env.pop("DREP_TPU_POD_JOIN", None)
    env.pop("DREP_TPU_AUTOSCALE_SPAWNED", None)
    if faults:
        env["DREP_TPU_FAULTS"] = faults
    if extra:
        env.update(extra)
    return env


def _launch_pod(outdir, ckpt, mode, nproc, faults=None, extra_env=None, ndev=2):
    port = _free_port()
    env = _base_env(faults, extra_env, ndev=ndev)
    os.makedirs(outdir, exist_ok=True)
    return [
        subprocess.Popen(
            [
                sys.executable, WORKER, str(i), str(nproc),
                f"localhost:{port}", str(outdir), mode, str(ckpt),
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


def _edges(outdir, who):
    with np.load(os.path.join(str(outdir), f"edges_{who}.npz")) as z:
        return z["ii"].copy(), z["jj"].copy(), z["dd"].copy(), int(z["pairs"])


def _ctr(outdir, who) -> dict:
    with open(os.path.join(str(outdir), f"counters_{who}.json")) as f:
        return json.load(f)


@pytest.fixture(scope="module")
def healthy_edges(tmp_path_factory):
    """The fixed-membership oracle: one healthy 3-process elastic pod
    (the canonical epoch-0 assembly order is a function of
    (n_blocks, pc=3), so the governed pod's bytes must match exactly)."""
    base = tmp_path_factory.mktemp("healthy")
    outdir, ckpt = str(base / "out"), str(base / "ckpt")
    outs = _reap(_launch_pod(outdir, ckpt, "elastic", nproc=3))
    for i in range(3):
        assert os.path.exists(os.path.join(outdir, f"ok_{i}")), (
            f"healthy worker {i}:\n{outs[i]}"
        )
    return _edges(outdir, 0)


def test_controller_spawned_joiner_meets_deadline_bit_identical(
    tmp_path, healthy_edges
):
    """THE acceptance cell: a real pod under --deadline pressure gets a
    CONTROLLER-spawned joiner admitted mid-run and finishes with edges
    byte-identical to the fixed-membership oracle; the scaling decision
    is visible in the decision log AND as autoscale_decision instants in
    the merged event trace; every member books autoscale_churn (so bench
    records of a governed run refuse as measured perf)."""
    outdir, ckpt = str(tmp_path / "out"), str(tmp_path / "ckpt")
    log_dir = os.path.join(outdir, "log")
    decision_log = os.path.join(outdir, "autoscale.jsonl")
    # pace each stripe so the controller's spawn -> joiner startup ->
    # admission pipeline (seconds of interpreter + jax init) lands while
    # stripes remain to re-deal
    pod = _launch_pod(
        outdir, ckpt, "elastic", nproc=3,
        faults="process_death:sleep:1.0:secs=3.0",
        extra_env={
            "DREP_TPU_TEST_MAX_JOINS": "2",
            "DREP_TPU_EVENTS": "on",
        },
    )
    spawn_cmd = (
        f"{sys.executable} {WORKER} 0 1 localhost:0 {outdir} join_streaming {ckpt}"
    )
    controller = subprocess.Popen(
        [
            sys.executable, os.path.join(REPO, "tools", "pod_autoscale.py"),
            ckpt,
            "--deadline", "1",  # already-missed: scale up on first ETA
            "--min_procs", "3", "--max_procs", "4",
            "--interval", "0.2", "--cooldown", "120", "--max_spawn", "1",
            "--spawn", spawn_cmd,
            "--decision_log", decision_log,
            "--log_dir", log_dir,
        ],
        env=_base_env(extra={"DREP_TPU_EVENTS": "on"}),
        stdout=subprocess.PIPE, stderr=subprocess.STDOUT, cwd=REPO,
    )
    outs = _reap(pod)
    for i, p in enumerate(pod):
        assert p.returncode == 0, f"pod worker {i} failed:\n{outs[i]}"
        assert os.path.exists(os.path.join(outdir, f"ok_{i}")), outs[i]
    # the joiner is the controller's child — poll for its verdict file
    deadline = time.time() + 120
    while time.time() < deadline and not os.path.exists(
        os.path.join(outdir, "ok_joiner")
    ):
        time.sleep(0.1)
    try:
        ctl_out, _ = controller.communicate(timeout=60)
    except subprocess.TimeoutExpired:
        controller.terminate()
        ctl_out, _ = controller.communicate()
    assert os.path.exists(os.path.join(outdir, "ok_joiner")), (
        f"controller-spawned joiner never finished.\ncontroller:\n"
        f"{ctl_out.decode(errors='replace')}"
    )

    # byte-identity: membership churn changed WHO computed, never WHAT
    h = healthy_edges
    for who in (0, 1, 2, "joiner"):
        e = _edges(outdir, who)
        assert all(
            a.tobytes() == b.tobytes() for a, b in zip(e[:3], h[:3])
        ), f"member {who}'s edges differ from the fixed-membership oracle"

    # the scaling decision is durable and machine-readable
    with open(decision_log, encoding="utf-8") as f:
        decisions = [json.loads(ln) for ln in f.read().splitlines()]
    ups = [d for d in decisions if d["verdict"] == "scale_up"]
    assert ups, decisions
    assert ups[0]["reason"] in ("deadline-passed", "eta-misses-deadline"), ups[0]
    assert "spawned 1 joiner" in ups[0]["actuation"], ups[0]

    # provenance: the joiner self-identifies as controller-spawned, every
    # member books the churn, the store meta stamps the join
    jc = _ctr(outdir, "joiner")
    assert jc.get("pod_join_accepted") == 1, jc
    assert jc.get("autoscale_churn", 0) >= 1, jc
    for i in range(3):
        ci = _ctr(outdir, i)
        assert ci.get("pod_joins", 0) >= 1, ci
        assert ci.get("autoscale_churn", 0) >= 1, ci
    with open(os.path.join(ckpt, "meta.json")) as f:
        meta = json.load(f)
    assert meta.get("pod_joins", 0) >= 1, meta

    # the scaling timeline rides the SAME merged trace as the membership
    # timeline (trace_report renders them side by side)
    from tools.trace_report import load_events

    events = load_events(log_dir)["events"]
    names = {e.get("ev") for e in events}
    assert "autoscale_decision" in names, sorted(names)
    assert "join_admitted" in names or "join_adopted" in names, sorted(names)
    ups_ev = [e for e in events if e.get("ev") == "autoscale_decision"
              and e.get("args", {}).get("verdict") == "scale_up"]
    assert ups_ev, "scale_up decision instant missing from the merged trace"


def test_ring_phase_join_tail_participation_d3_bit_identical(tmp_path):
    """The ring-phase JOIN upgrade (PR 9 follow-on (c)) at D=3: a gated
    joiner admitted mid-dense-phase no longer demotes anyone to pure
    standalone recovery — the pod keeps its collective step loop
    (join-tolerant waits) while the joiner consumes whole ring steps
    from the schedule TAIL; the assembled matrix on every member is
    byte-identical to the MONOLITHIC fixed-membership reference."""
    from drep_tpu.parallel.allpairs import configure_ring, sharded_mash_allpairs
    from drep_tpu.parallel.mesh import make_mesh

    sys.path.insert(0, os.path.dirname(WORKER))
    import _multihost_worker as w

    configure_ring()  # the monolithic fixed-membership reference, D=3
    oracle = sharded_mash_allpairs(
        w._elastic_packed(), k=21, mesh=make_mesh(3), monolithic=True
    )

    outdir, ckpt = str(tmp_path / "out"), str(tmp_path / "ring")
    pod = _launch_pod(
        outdir, ckpt, "ring", nproc=3, ndev=1,
        # pace the step boundaries so the (gated, pre-started) joiner's
        # tail blocks land while the collective ring works the head
        faults="ring_step:sleep:1.0:secs=1.5",
        extra_env={
            "DREP_TPU_TEST_MAX_JOINS": "1",
            "DREP_TPU_TEST_WAIT_JOIN": "1",
        },
    )
    joiner = subprocess.Popen(
        [
            sys.executable, WORKER, "0", "1", "localhost:0",
            str(outdir), "join_ring", str(ckpt),
        ],
        env=_base_env(extra={"DREP_TPU_POD_JOIN": "3"}, ndev=1),
        stdout=subprocess.PIPE, stderr=subprocess.STDOUT, cwd=REPO,
    )
    outs = _reap(pod + [joiner])
    for i, p in enumerate(pod):
        assert p.returncode == 0, f"pod worker {i} failed:\n{outs[i]}"
    assert joiner.returncode == 0, f"joiner failed:\n{outs[-1]}"

    for who in (0, 1, 2, "joiner"):
        got = np.load(os.path.join(outdir, f"ring_{who}.npy"))
        assert got.tobytes() == oracle.tobytes(), (
            f"member {who}'s ring matrix differs from the monolithic oracle"
        )
    # the joiner PARTICIPATED IN RING STEPS (tail consumption), not only
    # standalone block recovery
    jc = _ctr(outdir, "joiner")
    assert jc.get("pod_join_accepted") == 1, jc
    assert jc.get("ring_join_tail_blocks", 0) >= 1, jc
    # the pod never abandoned its collective schedule for the join
    for i in range(3):
        ci = _ctr(outdir, i)
        assert ci.get("pod_joins", 0) >= 1, ci
        assert "ring_step_failures" not in ci, ci
    blocks = sorted(f for f in os.listdir(ckpt) if f.startswith("blk_"))
    assert len(blocks) == 3 * 4 // 2, blocks  # D*(D+1)/2 half-ring blocks
    assert any(".e" in f for f in blocks), blocks  # post-admission stamps
