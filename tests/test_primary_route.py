"""ISSUE 45: the Mash primary has one estimator, and one function names the
route a run takes (`controller._primary_route`). The name it returns is the
route that runs, the string the resume snapshot records and the string a
benchmark cell `expect`s; no flag chooses an estimator or a ring program."""

import json
import os
import shutil

import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

N = 72  # over engines.MESH_MIN_GENOMES: a mesh, where there is one, takes the ring
SKETCH = 64


def _plant(root: str, n: int) -> str:
    """A work directory of `n` planted sketch sets (Bdb and sketch cache),
    the resume state `compare <wd>` starts the cluster stage from."""
    from benchmark import cells

    gen = cells.load_module(os.path.join(REPO, "benchmark", "generators", "planted_sketches.py"))
    cfg = cells.read_json(os.path.join(REPO, "benchmark", "configs", "mags_5k.json"))
    cfg["data"].update({"n": n, "s_bottom": SKETCH, "own_bottom": 10, "s_scaled": 200})
    return gen.prepare(cfg, 45, os.path.join(root, f"n{n}"))["workdir"]


@pytest.fixture(scope="module")
def pristine(tmp_path_factory):
    root = str(tmp_path_factory.mktemp("route"))
    return {N: _plant(root, N), 1: _plant(root, 1)}


def _tiles(rec) -> tuple:
    st = rec["stages"]["primary_compare"]
    return st.get("tiles_computed"), st.get("tiles_total")


# case -> (genomes, CLI flags, the same as keyword arguments, the route,
#          what only that route books in the job's record)
ROUTES = {
    "SkipMash": (N, ["--SkipMash"], {"SkipMash": True}, "skipmash",
                 lambda rec: "primary_pack" not in rec and _tiles(rec) == (None, None)),
    "one genome": (1, [], {}, "skipmash",
                   lambda rec: "primary_pack" not in rec and _tiles(rec) == (None, None)),
    # chunks of 30, 30 and 12, then their representatives: four packs, no tree
    "multiround over primary_chunksize": (
        N, ["--multiround_primary_clustering", "--primary_chunksize", "30", "--mesh_shape", "1"],
        {"multiround_primary_clustering": True, "primary_chunksize": 30, "mesh_shape": 1},
        "multiround_sort",
        lambda rec: rec["primary_pack"]["calls"] == 4 and "primary_linkage" not in rec
        and "primary_stream_slots" not in rec),
    "--streaming_primary": (
        N, ["--streaming_primary", "--streaming_block", "32"],
        {"streaming_primary": True, "streaming_block": 32}, "streaming_sort",
        lambda rec: rec["primary_stream_slots"]["tiles"] == 6 and _tiles(rec) == (6, 9)
        and "stripe" in rec["phases"]),
    "N at streaming_threshold": (
        N, ["--streaming_threshold", str(N), "--streaming_block", "32"],
        {"streaming_threshold": N, "streaming_block": 32}, "streaming_sort",
        lambda rec: rec["primary_stream_slots"]["tiles"] == 6 and "stripe" in rec["phases"]),
    "dense on one device": (
        N, ["--mesh_shape", "1"], {"mesh_shape": 1}, "sort",
        lambda rec: _tiles(rec) == (1, 1) and "ring_step" not in rec["phases"]
        and rec["primary_linkage"]["genomes"] == N),
    # the 8 virtual devices of conftest: the half ring's 36 of 64 block tiles
    "dense on the 8 virtual devices": (
        N, [], {}, "ring_sort",
        lambda rec: _tiles(rec) == (36, 64) and rec["phases"]["ring_step"]["calls"] == 5
        and rec["primary_linkage"]["genomes"] == N),
}


@pytest.mark.parametrize("case", list(ROUTES))
def test_the_recorded_route_is_the_route_that_ran(case, pristine, tmp_path):
    """`_primary_route`'s name against what the job booked in its record and
    against the snapshot in `log/cluster_arguments.json`."""
    import jax

    from drep_tpu import controller
    from drep_tpu.cluster.controller import _fill_defaults, _primary_route

    assert len(jax.devices()) == 8
    n, flags, kwargs, route, booked = ROUTES[case]
    assert _primary_route(n, _fill_defaults({"MASH_sketch": SKETCH, **kwargs})) == route
    wd = str(tmp_path / "wd")
    shutil.copytree(pristine[n], wd)
    controller.main(["compare", wd, "--skip_plots", "--SkipSecondary", "-ms", str(SKETCH), *flags])
    with open(os.path.join(wd, "log", "cluster_arguments.json")) as f:
        snapshot = json.load(f)
    assert snapshot["primary_estimator_resolved"] == route
    assert "primary_estimator" not in snapshot and "ring_monolithic" not in snapshot
    with open(os.path.join(wd, "log", "perf_counters.json")) as f:
        rec = json.load(f)
    pairs = rec["stages"]["primary_compare"]["pairs"]
    assert (pairs == 0) == (route == "skipmash")
    assert booked(rec), (route, rec["stages"]["primary_compare"], sorted(rec))


def test_auto_off_a_tpu_is_the_sort_estimator_at_any_n(rng):
    """Off a TPU the dense engine is the jnp sort tiles at any N: bit-equal
    to `all_vs_all_mash`, the tests' reference. (From 512 genomes on it
    used to switch to another estimator family, and the numerics with it.)"""
    from drep_tpu.cluster.engines import mash_distance_matrix
    from drep_tpu.ops.minhash import all_vs_all_mash, pack_sketches

    n, s = 600, 24
    pool = rng.integers(0, 2**63, size=400, dtype=np.uint64)
    sketches = [np.unique(rng.choice(pool, size=s, replace=False)) for _ in range(n)]
    packed = pack_sketches(sketches, [f"g{i}" for i in range(n)], s)
    got = mash_distance_matrix(packed, k=21, mesh_shape=1)
    want, _jac = all_vs_all_mash(packed, k=21)
    assert got.dtype == want.dtype and got.tobytes() == want.tobytes()
    assert 0.0 < got[np.triu_indices(n, 1)].min() < 1.0  # pairs that share hashes, not all-or-nothing


def test_a_snapshot_that_still_names_primary_estimator_resumes(tmp_path, genome_paths):
    """A work directory clustered by an older tree holds
    `"primary_estimator": "auto"` in its snapshot: the resume check compares
    the keys it is asked for, so the run resumes and recomputes nothing."""
    from drep_tpu.workflows import compare_wrapper

    wd = str(tmp_path / "wd")
    compare_wrapper(wd, genome_paths, skip_plots=True)
    cdb_path = os.path.join(wd, "data_tables", "Cdb.csv")
    with open(cdb_path, "rb") as f:
        before = f.read()
    loc = os.path.join(wd, "log", "cluster_arguments.json")
    with open(loc) as f:
        args = json.load(f)
    assert "primary_estimator" not in args
    args["primary_estimator"] = "auto"
    args.pop("crc", None)  # a hand edit: a crc-less snapshot is legacy-accepted
    with open(loc, "w") as f:
        json.dump(args, f)
    stamp = os.stat(cdb_path).st_mtime_ns
    compare_wrapper(wd, genome_paths, skip_plots=True)
    with open(os.path.join(wd, "log", "logger.log")) as f:
        log = f.read()
    assert log.count("skipping recompute") == 1
    with open(cdb_path, "rb") as f:
        assert f.read() == before
    assert os.stat(cdb_path).st_mtime_ns == stamp


@pytest.mark.parametrize("flag", [["--primary_estimator", "matmul"], ["--ring_monolithic"]])
def test_a_retired_switch_is_refused_by_the_parser(flag, capsys):
    """No user switch chooses the estimator or the ring's program: the
    parser does not know the retired flags, on either batch command."""
    from drep_tpu.argparser import build_parser

    for command in ("compare", "dereplicate"):
        with pytest.raises(SystemExit) as e:
            build_parser().parse_args([command, "wd", *flag])
        assert e.value.code == 2
        assert "unrecognized arguments" in capsys.readouterr().err
