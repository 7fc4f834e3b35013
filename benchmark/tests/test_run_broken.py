"""A whole run at toy sizes with the harness's look for a chip skipped
(--rehearse), sound and then with the timed path broken underneath: an answer
altered where it is produced has to come out as `correct: false`. Each run is
a process of its own, as the driver's are."""

import json
import os
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

DRIVER = r"""
import sys
sys.path.insert(0, {repo!r})
{patch}
sys.argv = ["run.py"] + {argv!r}
import runpy
runpy.run_path({repo!r} + "/benchmark/run.py", run_name="__main__")
"""

# the batch path, broken: every job's Mdb holds one distance that is off by 1e-4
BREAK_BATCH = r"""
import drep_tpu.cluster.controller as cc
_real = cc._mdb_from_dist
def _broken(dist, *a, **k):
    dist = dist.copy(); i, j = (dist < 0.2).nonzero(); off = i != j
    dist[i[off][0], j[off][0]] += 1e-4
    return _real(dist, *a, **k)
cc._mdb_from_dist = _broken
"""


def run(workload: str, patch: str, seed: int) -> dict:
    argv = ["--workload", workload, "--seed", str(seed), "--seconds", "6", "--trace", "0",
            "--rehearse"]
    env = {**os.environ, "JAX_PLATFORMS": "cpu"}
    proc = subprocess.run([sys.executable, "-c", DRIVER.format(repo=REPO, patch=patch, argv=argv)],
                          capture_output=True, text=True, env=env, timeout=900, cwd=REPO)
    assert proc.returncode == 0, proc.stderr[-3000:]
    return json.loads(proc.stdout.strip().splitlines()[-1])


@pytest.mark.parametrize("workload,patch", [("mags_5k.compare_dense", BREAK_BATCH)])
def test_sound_is_correct_and_broken_is_not(workload, patch):
    sound = run(workload, "", seed=4242)
    assert sound["correct"] is True and sound["failed"] == 0 and sound["rehearsal"] is True
    assert sound["device"]["platform"] == "cpu"  # never to be read as a chip result
    broken = run(workload, patch, seed=4242)
    assert broken["correct"] is False
    assert broken["failed"] == 0  # a wrong answer is not a failed job


def test_no_accelerator_no_result_line():
    env = {**os.environ, "JAX_PLATFORMS": "cpu"}
    proc = subprocess.run([sys.executable, os.path.join(REPO, "benchmark", "run.py"), "--workload",
                           "mags_5k.compare_dense", "--seed", "1", "--seconds", "5", "--trace", "0"],
                          capture_output=True, text=True, env=env, timeout=300, cwd=REPO)
    assert proc.returncode != 0
    assert not any(line.startswith("{") for line in proc.stdout.splitlines())
