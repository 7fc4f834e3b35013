"""chip_smoke.py's own contract, as far as a machine without a chip can
show it: no accelerator -> fail in seconds, before any data, with nothing
that reads as a result; the parent process never imports jax (one process
per chip); alone in a directory it fails; the rehearsal runs every leg at
toy sizes and still cannot pass as a chip run."""

import json
import os
import shutil
import subprocess
import sys
import time

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SMOKE = os.path.join(REPO, "chip_smoke.py")


def _run(args, cwd=REPO, timeout=120, script=SMOKE):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("XLA_FLAGS", None)
    return subprocess.run(
        [sys.executable, script, *args], capture_output=True, text=True,
        cwd=cwd, env=env, timeout=timeout,
    )


def test_no_accelerator_fails_fast_and_generates_nothing(tmp_path):
    out = tmp_path / "out"
    t0 = time.monotonic()
    r = _run(["--out", str(out)])
    assert r.returncode != 0
    assert time.monotonic() - t0 < 60
    last = r.stdout.strip().splitlines()[-1]
    assert "platform: cpu" in last and "FAILED" in last
    with pytest.raises(json.JSONDecodeError):  # not a result line
        json.loads(last)
    assert not out.exists(), "the smoke generated data without an accelerator"


def test_alone_in_a_directory_it_fails(tmp_path):
    shutil.copy(SMOKE, tmp_path / "chip_smoke.py")
    r = _run([], cwd=str(tmp_path), script=str(tmp_path / "chip_smoke.py"))
    assert r.returncode != 0
    assert "FAILED" in r.stdout.strip().splitlines()[-1]
    assert os.listdir(tmp_path) == ["chip_smoke.py"]


def test_parent_process_never_imports_jax():
    """Everything the parent imports — its own module and the drep_tpu
    helpers it plants data and talks to the daemon with — must leave jax
    out of sys.modules: a parent that has touched JAX holds the chip."""
    code = (
        "import sys\n"
        f"sys.path.insert(0, {REPO!r})\n"
        "import chip_smoke\n"
        "chip_smoke._generator()\n"
        "import drep_tpu.ingest, drep_tpu.workdir, drep_tpu.native\n"
        "import drep_tpu.serve.client, drep_tpu.utils.synth, drep_tpu.utils.durableio\n"
        "assert 'jax' not in sys.modules, sorted(m for m in sys.modules if m.startswith('jax'))\n"
    )
    r = subprocess.run([sys.executable, "-c", code], capture_output=True, text=True,
                       timeout=120)
    assert r.returncode == 0, r.stderr[-2000:]


@pytest.mark.slow
def test_rehearsal_runs_every_leg_and_is_never_a_chip_pass(tmp_path):
    r = _run(["--rehearse", "--out", str(tmp_path / "out")], timeout=1500)
    assert r.returncode == 0, r.stdout[-3000:] + r.stderr[-3000:]
    last = json.loads(r.stdout.strip().splitlines()[-1])
    assert last["ok"] is False and last["rehearsal"] is True
    assert last["rehearsal_ok"] is True and last["device"]["platform"] == "cpu"
    for leg in "ABCD":
        assert f"leg {leg}: OK" in r.stdout


# ---- start-up on a sealed one-host machine ----------------------------------


def test_single_host_start_makes_no_distributed_call(monkeypatch):
    """Multi-host bring-up only when the operator configured it: JAX's
    argument-less auto-detect asks the cloud metadata server on a TPU VM,
    which a sealed machine cannot reach."""
    import jax

    from drep_tpu.parallel.mesh import initialize_distributed

    calls = []
    monkeypatch.setattr(jax.distributed, "initialize", lambda **kw: calls.append(kw))
    monkeypatch.delenv("JAX_COORDINATOR_ADDRESS", raising=False)
    initialize_distributed()
    assert calls == []
    monkeypatch.setenv("JAX_COORDINATOR_ADDRESS", "host0:1234")
    initialize_distributed()
    initialize_distributed("host0:1234", num_processes=2, process_id=1)
    assert [c["coordinator_address"] for c in calls] == [None, "host0:1234"]


def test_compile_cache_is_placed_from_outside_or_fixed_in_the_checkout(monkeypatch):
    import jax

    from drep_tpu.utils import xla_cache

    seen = []
    monkeypatch.setattr(jax.config, "update", lambda k, v: seen.append((k, v)))
    monkeypatch.setattr(xla_cache, "_done", False)
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(REPO) + "/elsewhere")
    xla_cache.enable_persistent_cache()
    assert seen == []  # JAX reads the variable itself; the program sets nothing
    monkeypatch.setattr(xla_cache, "_done", False)
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
    xla_cache.enable_persistent_cache()
    assert seen == [("jax_compilation_cache_dir", os.path.join(REPO, ".jax_cache"))]
