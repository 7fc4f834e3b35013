"""The yardstick's own tests, benchmark/tests/test_discovery.py, collected by
the run that checks every PR: that code accepts or refuses each of them."""

import pytest

pytest.register_assert_rewrite("benchmark.tests.test_discovery")

from benchmark.tests.test_discovery import *  # noqa: E402,F401,F403
