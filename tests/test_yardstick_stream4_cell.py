"""The yardstick's own tests, benchmark/tests/test_stream4_cell.py, collected by
the run that checks every PR: that code accepts or refuses each of them."""

import pytest

pytest.register_assert_rewrite("benchmark.tests.test_stream4_cell")

from benchmark.tests.test_stream4_cell import *  # noqa: E402,F401,F403
from benchmark.tests import test_stream4_cell as _yardstick  # noqa: E402

# strict: tier-1 says so the day a `benchmark` PR mends it
test_the_cell_is_found_by_name_wherever_later_cells_are_appended = pytest.mark.xfail(
    strict=True,
    reason="pins the `workloads` of the three `stream_*` metrics to the stream4 cell alone: fails by "
    "the name gtdb_release_host4_6k.compare_greedy4 appends there, as ISSUE 42 asks (the second "
    "cell whose streaming walk deals its tiles over four chips; PERF.md section 7): a `benchmark` "
    "PR's to relax, since no other PR may edit a file under benchmark/",
)(_yardstick.test_the_cell_is_found_by_name_wherever_later_cells_are_appended)
