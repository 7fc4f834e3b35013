"""The yardstick's own tests, benchmark/tests/test_greedy4_cell.py, collected by
the run that checks every PR: that code accepts or refuses each of them."""

import pytest

pytest.register_assert_rewrite("benchmark.tests.test_greedy4_cell")

from benchmark.tests.test_greedy4_cell import *  # noqa: E402,F401,F403
from benchmark.tests import test_greedy4_cell as _yardstick  # noqa: E402

# strict: tier-1 says so the day a `benchmark` PR mends it
test_a_rehearsal_on_four_virtual_devices_takes_the_mesh_route_and_prints_a_well_formed_line = pytest.mark.xfail(
    strict=True,
    reason="pins the fixed representative tile at the rehearsal's size: a cluster of one block "
    "shipped one trailing tile of padding (`secondary_greedy_reship_share` 5/6, a "
    "`secondary_greedy_rep_pad_share` to read); since ISSUE 55 a block that meets no "
    "representative ships no tile (4/5, and no representative row to take a share of; the test "
    "below holds the rest of it; PERF.md section 7): a `benchmark` PR's to mend, since no other "
    "PR may edit a file under benchmark/",
)(_yardstick.test_a_rehearsal_on_four_virtual_devices_takes_the_mesh_route_and_prints_a_well_formed_line)


def test_a_rehearsal_on_four_virtual_devices_ships_no_tile_for_a_cluster_of_one_block():
    """The yardstick's rehearsal test above as ISSUE 55 leaves it: the same
    command, every line of it but the two that pinned the tile of padding."""
    import json
    import os
    import subprocess
    import sys

    from benchmark import cells

    cell, bench, repo = _yardstick.CELL, _yardstick.BENCH, _yardstick.REPO
    seed = 2**31 + 55
    argv = [sys.executable, os.path.join(bench, "run.py"), "--workload", cell, "--seed", str(seed),
            "--seconds", "1", "--trace", "1", "--rehearse"]
    env = {**os.environ, "JAX_PLATFORMS": "cpu",
           "XLA_FLAGS": "--xla_force_host_platform_device_count=4"}
    proc = subprocess.run(argv, capture_output=True, text=True, env=env, timeout=900, cwd=repo)
    assert proc.returncode == 0, proc.stderr[-3000:]
    line, out = json.loads(proc.stdout.strip().splitlines()[-1]), proc.stdout
    assert line["correct"] is True and line["failed"] == 0 and line["attempted"] == 1
    assert line["rehearsal"] is True and line["device"] == {**line["device"], "platform": "cpu", "count": 4}
    listed = {m["name"] for m in cells.metrics_of(cells.load_cell(cell)["spec"], cell, "per_layer")}
    # the two that need a TPU's kernel and peaks, and the share of representative rows that hold
    # none: both toy clusters are one block, which meets no representative and ships no tile
    assert listed - set(line["metrics"]) == {"mash_kernel_ns_per_pair", "secondary_greedy_roofline",
                                             "secondary_greedy_rep_pad_share"}
    assert "'rep_rows_shipped': [0, 0]" in out and "'device_calls': [1, 1]" in out
    assert line["metrics"]["compiles_in_window.batch"]["value"] == 0
    assert line["metrics"]["secondary_greedy_put_s"]["value"] > 0
    assert 0 < line["metrics"]["secondary_greedy_mesh_occupancy"]["value"] <= 100
    # one block, no tile: the block crosses 1 + 4 times, for itself
    assert line["metrics"]["secondary_greedy_reship_share"]["value"] == pytest.approx(100 * 4 / 5)
    assert line["metrics"]["stream_turn_pad_share"]["value"] == pytest.approx(37.5)
    assert out.count("compare: ") == 10 and "WRONG" not in out and "job failed" not in out
    assert "rehearsal: expected of the device path" not in out  # the knob: the matmul route served
    assert "'partial_tile_ships': [0, 0]" in out and "'rep_bytes': [0, 0]" in out
    assert not os.path.exists(os.path.join(bench, ".work", f"{cell}-{seed}"))
