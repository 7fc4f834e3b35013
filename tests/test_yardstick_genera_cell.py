"""The yardstick's own tests, benchmark/tests/test_genera_cell.py, collected by
the run that checks every PR: that code accepts or refuses each of them."""

import pytest

pytest.register_assert_rewrite("benchmark.tests.test_genera_cell")

from benchmark.tests.test_genera_cell import *  # noqa: E402,F401,F403
