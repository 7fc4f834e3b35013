"""The yardstick's own tests, benchmark/tests/test_index_cell.py, collected by
the run that checks every PR: that code accepts or refuses each of them."""

import pytest

pytest.register_assert_rewrite("benchmark.tests.test_index_cell")

from benchmark.tests.test_index_cell import *  # noqa: E402,F401,F403
from benchmark.tests import test_index_cell as _yardstick  # noqa: E402

_as_it_came = _yardstick.test_the_cell_is_found_by_name_and_declared_where_it_reports


def test_the_cell_is_found_by_name_and_declared_where_it_reports(loaded):
    """The yardstick's test pins the index layer's eight metrics to the last
    eight of `per_layer`, and a later PR's entries go last, after them. So it
    runs on the list as it stood when the cell came, every other assertion on
    the file as it is; what came later is held here to what it holds the
    rest to: no metric of the index layer, and this cell last where listed."""
    spec = loaded["spec"]
    cut = [m["name"] for m in spec["per_layer"]].index(_yardstick.NEW[-1]) + 1
    for m in spec["per_layer"][cut:]:
        assert m["layer"] != "index"
        assert _yardstick.CELL not in m["workloads"][:-1]
    _as_it_came({**loaded, "spec": {**spec, "per_layer": spec["per_layer"][:cut]}})
