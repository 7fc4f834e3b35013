"""A primary cluster's Ndb rows stay columns from `(ani, cov)` to the one
table (ISSUE 49): the table is the one `pd.concat` of a frame a cluster gave,
the callers that read one cluster still get a frame, and the secondary stage
constructs no DataFrame."""

import os
import sys
import types

import numpy as np
import pandas as pd
import pytest

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
import _ndb_parent as parent  # noqa: E402

from drep_tpu import schemas, tablewriter  # noqa: E402
from drep_tpu.cluster import pairs  # noqa: E402

KW = {"S_ani": 0.9, "cov_thresh": 0.3}


def _names(pc: int, m: int) -> list[str]:
    # widths differ inside a cluster and between clusters
    return [f"pc{pc}_{'x' * (i % 5)}genome_{i}.fasta" for i in range(m)]


def _all_pairs(pc: int, m: int):
    ani, cov = parent.planted_matrices(m, seed=pc)
    names = _names(pc, m)
    return pairs.directional_ndb_columns(names, ani, cov, pc), parent.directional_frame(names, ani, cov, pc)


def _greedy(pc: int, m: int):
    from drep_tpu.cluster.greedy import greedy_assign_from_matrices

    ani, cov = parent.planted_matrices(m, seed=pc)
    names = _names(pc, m)
    n_kmers = [int(x) for x in np.random.default_rng(pc).permutation(m) + 500]
    gs = types.SimpleNamespace(names=names, gdb=pd.DataFrame({"n_kmers": n_kmers}))
    cols, labels = greedy_assign_from_matrices(gs, list(range(m)), pc, KW, ani, cov)
    frame, want = parent.greedy_frame(names, n_kmers, pc, KW, ani, cov)
    np.testing.assert_array_equal(labels, want)
    assert 0 < len(frame) < m * (m - 1)  # the scan consumed a subset of the pairs
    return cols, frame


def _empty(pc: int, m: int):
    from drep_tpu.cluster.greedy import _ndb_from_rows

    return _ndb_from_rows([], pc, _names(pc, m)), pd.DataFrame(columns=pairs.NDB_COLUMNS)


def _through_a_checkpoint(tmp_path, pc, cols):
    """`cols` as a resumed job holds them: saved, then loaded."""
    from drep_tpu.cluster.secondary_ckpt import SecondaryCheckpoint

    def store():
        return SecondaryCheckpoint(str(tmp_path / "ck"), {"S_ani": 0.9}, np.array([1, 1]), ["a", "b"])

    store().save(pc, cols, np.array([1, 1]), np.empty((0, 4)))
    return store().load(pc)[0]


CASES = {
    "clusters_of_2_3_and_40": [(_all_pairs, 1, 2), (_all_pairs, 2, 3), (_all_pairs, 3, 40)],
    "one_cluster": [(_all_pairs, 4, 9)],
    "greedy_scans": [(_greedy, 1, 6), (_greedy, 2, 12), (_greedy, 3, 30)],
    "every_cluster_empty": [(_empty, 1, 2), (_empty, 2, 3)],
    "computed_and_resumed": [(_all_pairs, 1, 3), (_greedy, 2, 8), (_all_pairs, 3, 5)],
}


def _csv(tmp_path, df: pd.DataFrame, name: str) -> bytes:
    loc = str(tmp_path / name)
    tablewriter.write_csv(schemas.validate(df, "Ndb"), loc)
    with open(loc, "rb") as f:
        return f.read()


@pytest.mark.parametrize("tertiary", [False, True], ids=["secondary", "tertiary_appended"])
@pytest.mark.parametrize("case", list(CASES))
def test_the_assembled_ndb_is_the_concat_of_a_frame_a_cluster(tmp_path, case, tertiary):
    """Values, dtypes, column order, index and the stored CSV's bytes."""
    built = [make(pc, m) for make, pc, m in CASES[case]]
    parts, frames = [b[0] for b in built], [b[1] for b in built]
    if case == "computed_and_resumed":
        parts[1] = _through_a_checkpoint(tmp_path, 2, parts[1])
        assert parts[1].names is None and parts[0].names is not None
    got = pairs.assemble_ndb(parts)
    want = pd.concat(frames, ignore_index=True)
    if tertiary:
        # as the controller appends them: cross-primary rows, primary cluster 0
        ani, cov = parent.planted_matrices(6, seed=99)
        cross = np.arange(6)[:, None] % 3 != np.arange(6)[None, :] % 3
        rows = pairs.directional_ndb(_names(0, 6), ani, cov, 0, pair_mask=cross)
        pd.testing.assert_frame_equal(rows, parent.directional_frame(_names(0, 6), ani, cov, 0, pair_mask=cross))
        got = pd.concat([got, rows], ignore_index=True)
        want = pd.concat([want, rows], ignore_index=True)
    pd.testing.assert_frame_equal(got, want)  # values, dtypes, column order, index type
    assert got.equals(want) and list(got.columns) == pairs.NDB_COLUMNS
    assert list(got.dtypes) == list(want.dtypes)
    assert _csv(tmp_path, got, "got.csv") == _csv(tmp_path, want, "want.csv") == want.to_csv(index=False).encode()


@pytest.mark.parametrize("m", [2, 3, 40])
@pytest.mark.parametrize("masked", [False, True])
def test_directional_ndb_still_gives_the_frame(m, masked):
    """tertiary.py keeps receiving a frame, the parent's."""
    ani, cov = parent.planted_matrices(m, seed=m)
    mask = (np.arange(m)[:, None] + np.arange(m)[None, :]) % 2 == 1 if masked else None
    got = pairs.directional_ndb(_names(5, m), ani, cov, 5, pair_mask=mask)
    want = parent.directional_frame(_names(5, m), ani, cov, 5, pair_mask=mask)
    pd.testing.assert_frame_equal(got, want)
    assert len(got) == (int(mask.sum()) if masked else m * (m - 1))


def test_secondary_for_cluster_gives_the_frames_columns(sketches, bdb):
    """The stage's and the index's entry point: (columns, labels, linkage),
    their `.frame()` the frame the parent built of the engine's matrices
    (the index scores its changed clusters from the columns of all of them)."""
    from drep_tpu.cluster import controller, dispatch

    kw = controller._fill_defaults({})
    indices = [0, 1, 2]
    ndb, labels, link = controller.secondary_for_cluster(sketches, bdb, indices, 3, kw)
    ani, cov = dispatch.get_secondary(kw["S_algorithm"])(
        sketches, indices, bdb=bdb, processes=kw["processes"], mesh_shape=kw["mesh_shape"])
    assert isinstance(ndb, pairs.NdbColumns) and not hasattr(controller, "_secondary_for_cluster")
    pd.testing.assert_frame_equal(
        ndb.frame(), parent.directional_frame([sketches.names[i] for i in indices], ani, cov, 3))
    assert len(labels) == 3 and link.shape[1] == 4


@pytest.mark.parametrize("greedy", [False, True], ids=["all_pairs", "greedy"])
def test_the_secondary_stage_constructs_no_frame(tmp_path, monkeypatch, greedy):
    """50 multi-member clusters through `compare`: no `pd.DataFrame(...)`
    between `_secondary_stage`'s entry and its return (not a cluster's rows,
    not a checkpoint's), and ONE for the table the columns assemble to."""
    from drep_tpu import controller as cli
    from drep_tpu.cluster import controller
    from drep_tpu.ingest import DEFAULT_SCALE, _save, sketch_args_snapshot
    from drep_tpu.utils.synth import plant_genome_sketches
    from drep_tpu.workdir import WorkDirectory

    gs, labels = plant_genome_sketches(260, np.random.default_rng(49), s_scaled=400)
    wd_loc = str(tmp_path / "wd")
    wd = WorkDirectory(wd_loc)
    wd.store_db(pd.DataFrame({"genome": gs.names, "location": [f"/nonexistent/{g}" for g in gs.names]}), "Bdb")
    _save(wd, gs)
    wd.store_arguments("sketch", sketch_args_snapshot(gs.names, gs.k, gs.sketch_size, DEFAULT_SCALE, "splitmix64"))

    made = {"stage": 0, "assembly": 0}
    where = [None]
    real_init = pd.DataFrame.__init__

    def counting_init(self, *a, **k):
        if where[0] is not None:
            made[where[0]] += 1
        real_init(self, *a, **k)

    def inside(site, fn):
        def run(*a, **k):
            where[0] = site
            try:
                return fn(*a, **k)
            finally:
                where[0] = None
        return run

    monkeypatch.setattr(pd.DataFrame, "__init__", counting_init)
    monkeypatch.setattr(controller, "_secondary_stage", inside("stage", controller._secondary_stage))
    monkeypatch.setattr(pairs, "assemble_ndb", inside("assembly", pairs.assemble_ndb))
    argv = ["compare", wd_loc, "--skip_plots"] + (["--greedy_secondary_clustering"] if greedy else [])
    cli.main(argv)
    monkeypatch.undo()

    clusters = sum(1 for c in np.bincount(labels) if c > 1)
    assert clusters >= 50 and made == {"stage": 0, "assembly": 1}
    saved = [f for f in os.listdir(os.path.join(wd_loc, "data", "secondary_checkpoints")) if f.startswith("pc_")]
    assert len(saved) == clusters
    ndb = pd.read_csv(os.path.join(wd_loc, "data_tables", "Ndb.csv"))
    assert list(ndb.columns) == pairs.NDB_COLUMNS and ndb["primary_cluster"].nunique() == clusters
