"""The yardstick's own tests, benchmark/tests/test_run_broken.py, collected by
the run that checks every PR: that code accepts or refuses each of them."""

import os

import pytest

pytest.register_assert_rewrite("benchmark.tests.test_run_broken")

from benchmark.tests.test_run_broken import *  # noqa: E402,F401,F403


@pytest.fixture(autouse=True)
def _one_device_for_the_child_runs(monkeypatch):
    """These tests start `benchmark/run.py` as processes of their own, with
    this process's environment: tests/conftest.py has forced eight virtual
    CPU devices into XLA_FLAGS, and a one-chip cell rehearsed on eight is
    refused. This process's own backend is up already and does not change."""
    flags = os.environ.get("XLA_FLAGS", "").split()
    kept = [f for f in flags if "xla_force_host_platform_device_count" not in f]
    monkeypatch.setenv("XLA_FLAGS", " ".join(kept))
