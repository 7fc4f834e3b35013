"""Test configuration: force an 8-device virtual CPU mesh.

The reference has no fake backend (SURVEY.md §4); our multi-device tests run
on CPU with XLA's forced host device count, so sharding/collective code is
exercised without TPU hardware. Must be set before jax initializes.
"""

import os

# hard override: the tests are written against an 8-device virtual CPU mesh,
# whatever accelerator the machine has. jax may already be imported by a
# pytest plugin (jaxtyping), so set the config, not just env.
os.environ["JAX_PLATFORMS"] = "cpu"
flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (flags + " --xla_force_host_platform_device_count=8").strip()

import jax  # noqa: E402

jax.config.update("jax_platforms", "cpu")
jax.config.update("jax_num_cpu_devices", 8)

import numpy as np  # noqa: E402
import pandas as pd  # noqa: E402
import pytest  # noqa: E402

GENOME_DIR = os.path.join(os.path.dirname(__file__), "genomes")
GENOME_NAMES = ["genome_A", "genome_B", "genome_C", "genome_D", "genome_E"]


def pytest_addoption(parser):
    # per-test wall-clock budget for the `chaos` marker (pyproject.toml
    # sets the value): chaos tests exercise watchdogs, dead-peer barriers
    # and kill/recovery protocols — a protocol regression shows up as a
    # HANG, and without a budget one wedged chaos test stalls the whole
    # tier-1 suite until the outer CI timeout kills it with no attribution
    parser.addini(
        "chaos_timeout_s",
        "wall-clock budget in seconds for each `chaos`-marked test "
        "(SIGALRM-enforced; 0 disables; needs no pytest-timeout plugin)",
        default="240",
    )


@pytest.hookimpl(hookwrapper=True)
def pytest_runtest_call(item):
    import signal
    import threading

    budget = 0.0
    if item.get_closest_marker("chaos") is not None:
        try:
            budget = float(item.config.getini("chaos_timeout_s"))
        except (TypeError, ValueError):
            budget = 0.0
    usable = (
        budget > 0
        and hasattr(signal, "SIGALRM")
        and threading.current_thread() is threading.main_thread()
    )
    if not usable:
        yield
        return

    def on_alarm(signum, frame):
        raise TimeoutError(
            f"chaos test exceeded its {budget:.0f}s wall-clock budget "
            f"(chaos_timeout_s in pyproject.toml) — a watchdog or "
            f"dead-peer protocol is likely wedged"
        )

    old = signal.signal(signal.SIGALRM, on_alarm)
    signal.setitimer(signal.ITIMER_REAL, budget)
    try:
        yield
    finally:
        signal.setitimer(signal.ITIMER_REAL, 0.0)
        signal.signal(signal.SIGALRM, old)


@pytest.fixture(scope="session")
def genome_paths() -> list[str]:
    return [os.path.join(GENOME_DIR, f"{g}.fasta") for g in GENOME_NAMES]


@pytest.fixture(scope="session")
def bdb(genome_paths) -> pd.DataFrame:
    from drep_tpu.ingest import make_bdb

    return make_bdb(genome_paths)


@pytest.fixture(scope="session")
def sketches(bdb):
    """Session-cached sketches of the 5 fixture genomes (k=21 defaults)."""
    from drep_tpu.ingest import sketch_genomes

    return sketch_genomes(bdb)


@pytest.fixture()
def rng() -> np.random.Generator:
    return np.random.default_rng(0)
