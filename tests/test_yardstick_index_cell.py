"""The yardstick's own tests, benchmark/tests/test_index_cell.py, collected by
the run that checks every PR: that code accepts or refuses each of them."""

import pytest

pytest.register_assert_rewrite("benchmark.tests.test_index_cell")

from benchmark.tests.test_index_cell import *  # noqa: E402,F401,F403
from benchmark.tests import test_index_cell as _yardstick  # noqa: E402

_as_it_came = _yardstick.test_the_cell_is_found_by_name_and_declared_where_it_reports


def test_the_cell_is_found_by_name_and_declared_where_it_reports(loaded):
    """The yardstick's test pins the index layer's eight metrics to the last
    eight of `per_layer`, this cell and its configuration to the last of their
    lists and the cells to ten, and a later PR's entries go last, after them.
    So it runs on the file as it stood when the cell came (what came later
    cut off, and taken out of the `workloads` lists), and what came later is
    held here to what it holds the rest to: no metric of the index layer, and
    where this cell is listed only cells that came after it stand behind it
    (ISSUE 54 appended a configuration and a cell)."""
    spec = loaded["spec"]
    cut = [m["name"] for m in spec["per_layer"]].index(_yardstick.NEW[-1]) + 1
    cells_in_order = [w["name"] for w in spec["workloads"]]
    here = cells_in_order.index(_yardstick.CELL) + 1
    there, later = cells_in_order[:here], cells_in_order[here:]
    assert _yardstick.CONFIG not in {w["config"] for w in spec["workloads"][here:]}
    for m in spec["per_layer"][cut:]:
        assert m["layer"] != "index"
    for m in spec["per_layer"] + spec["end_to_end"]:
        listed = m.get("workloads", [])
        if _yardstick.CELL in listed:
            behind = listed[listed.index(_yardstick.CELL) + 1:]
            assert behind == [w for w in later if w in behind]

    def as_it_stood(m: dict) -> dict:
        return {**m, "workloads": [w for w in m["workloads"] if w in there]} if "workloads" in m else m

    configs = [c["name"] for c in spec["configs"]]
    _as_it_came({**loaded, "spec": {
        **spec, "workloads": spec["workloads"][:here],
        "configs": spec["configs"][:configs.index(_yardstick.CONFIG) + 1],
        "end_to_end": [as_it_stood(m) for m in spec["end_to_end"]],
        "per_layer": [as_it_stood(m) for m in spec["per_layer"][:cut]]}})
