"""Per-cluster secondary checkpointing: resume, invalidation, corruption."""

import glob
import io
import os
import sys
import time
import types

import numpy as np
import pandas as pd
import pytest

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
import _ndb_parent as parent  # noqa: E402

from drep_tpu.cluster import pairs  # noqa: E402
from drep_tpu.cluster.secondary_ckpt import SecondaryCheckpoint  # noqa: E402


def _mk(tmp_path, snapshot=None, primary=None, names=None):
    return SecondaryCheckpoint(
        str(tmp_path / "ckpt"),
        snapshot if snapshot is not None else {"S_ani": 0.95},
        primary if primary is not None else np.array([1, 1, 2]),
        names if names is not None else ["a", "b", "c"],
    )


def _payload():
    """What the stage hands `save`: a cluster's Ndb rows as columns."""
    ani, cov = parent.planted_matrices(2, seed=0)
    return pairs.directional_ndb_columns(["a", "b"], ani, cov, 1), np.array([1, 1]), np.empty((0, 4))


def test_save_load_roundtrip(tmp_path):
    ck = _mk(tmp_path)
    ndb, labels, link = _payload()
    ck.save(1, ndb, labels, link)

    ck2 = _mk(tmp_path)
    got = ck2.load(1)
    assert got is not None
    pd.testing.assert_frame_equal(got[0].frame(), ndb.frame())
    assert got[0].names is None  # the names as the payload's arrays, no frame built
    np.testing.assert_array_equal(got[1], labels)
    assert ck2.n_resumed == 1
    assert ck2.load(2) is None


GREEDY_KW = {"S_ani": 0.9, "cov_thresh": 0.3}


def _cluster(shape):
    """(columns as the stage builds them, the frame PR 48's stage built of
    the same rows, labels, linkage) for one primary cluster."""
    from drep_tpu.ops.linkage import cluster_hierarchical
    from drep_tpu.cluster.greedy import _ndb_from_rows, greedy_assign_from_matrices

    if shape == "empty":
        return (_ndb_from_rows([], 7, ["x", "y"]), pd.DataFrame(columns=pairs.NDB_COLUMNS),
                np.array([1, 2]), np.empty((0, 4)))
    # names of three widths; the longest is no representative below
    names = ["g1.fa", "a_much_longer_genome_name.fasta", "mid_name.fna", "g4.fa", "g_five.fa"]
    ani, cov = parent.planted_matrices(len(names), seed=11)
    if shape == "all_pairs":
        frame = parent.directional_frame(names, ani, cov, 7)
        dist = 1.0 - pairs.gated_symmetric_ani(ani, cov, 0.1)
        labels, link = cluster_hierarchical(dist, 0.05, method="average")
        return pairs.directional_ndb_columns(names, ani, cov, 7), frame, labels, link
    n_kmers = [900, 500, 800, 700, 600]
    gs = types.SimpleNamespace(names=names, gdb=pd.DataFrame({"n_kmers": n_kmers}))
    frame, want = parent.greedy_frame(names, n_kmers, 7, GREEDY_KW, ani, cov)
    cols, labels = greedy_assign_from_matrices(gs, list(range(len(names))), 7, GREEDY_KW, ani, cov)
    np.testing.assert_array_equal(labels, want)
    # the scan consumed a subset of the pairs, and the reference column is
    # narrower than the querry column: the store's widths are a column's own
    assert 0 < len(frame) < len(names) * (len(names) - 1) and 1 < labels.max() < len(names)
    assert frame["reference"].str.len().max() < frame["querry"].str.len().max()
    return cols, frame, labels, np.empty((0, 4))


@pytest.mark.parametrize("shape", ["all_pairs", "greedy", "empty"])
def test_a_checkpoint_from_columns_is_the_file_the_frame_gave(tmp_path, monkeypatch, shape):
    """`save` from columns writes, byte for byte, what PR 48's `save` wrote
    from the frame of the same rows (no format bump: either side resumes
    the other's store), and `load` reads it back as columns and counts it."""
    from drep_tpu.utils import durableio

    # a zip member carries its write time at 2 s resolution
    monkeypatch.setattr(time, "localtime", lambda *a: time.struct_time((2026, 10, 2, 0, 0, 0, 4, 275, 0)))
    cols, frame, labels, link = _cluster(shape)
    pd.testing.assert_frame_equal(pairs.assemble_ndb([cols]), frame)
    ck = _mk(tmp_path)
    ck.save(7, cols, labels, link)
    want = io.BytesIO()
    np.savez(want, **durableio.with_checksum(parent.checkpoint_arrays(frame, labels, link)))
    with open(ck._loc(7), "rb") as f:
        assert f.read() == want.getvalue()

    ck2 = _mk(tmp_path)
    ndb, got_labels, got_link = ck2.load(7)
    assert ck2.n_resumed == 1
    assert isinstance(ndb, pairs.NdbColumns) and list(ndb.cols) == pairs.NDB_COLUMNS
    np.testing.assert_array_equal(got_labels, labels)
    np.testing.assert_array_equal(got_link, link)
    # a resumed cluster assembles to the rows a computed one gives
    pd.testing.assert_frame_equal(pairs.assemble_ndb([ndb]), frame)


def test_snapshot_change_invalidates(tmp_path):
    ck = _mk(tmp_path)
    ck.save(1, *_payload())
    ck2 = _mk(tmp_path, snapshot={"S_ani": 0.99})
    assert ck2.load(1) is None  # wholesale invalidation


def test_primary_partition_change_invalidates(tmp_path):
    ck = _mk(tmp_path)
    ck.save(1, *_payload())
    ck2 = _mk(tmp_path, primary=np.array([1, 2, 2]))
    assert ck2.load(1) is None


def test_corrupt_checkpoint_recomputed(tmp_path):
    ck = _mk(tmp_path)
    ck.save(1, *_payload())
    pkl = glob.glob(str(tmp_path / "ckpt" / "pc_*.npz"))[0]
    with open(pkl, "wb") as f:
        f.write(b"garbage")
    ck2 = _mk(tmp_path)
    assert ck2.load(1) is None  # detected, removed, recomputable
    assert not os.path.exists(pkl)


def test_disabled_is_noop():
    ck = SecondaryCheckpoint(None, {}, np.array([1]), ["a"])
    ck.save(1, *_payload())
    assert ck.load(1) is None
    ck.finish(1)


def test_pipeline_resumes_secondary(tmp_path, genome_paths, monkeypatch):
    """Crash after secondary checkpoints are written; rerun must reuse them."""
    from drep_tpu.workflows import compare_wrapper

    wd_loc = str(tmp_path / "wd")
    compare_wrapper(wd_loc, genome_paths, skip_plots=True)
    pkls = glob.glob(os.path.join(wd_loc, "data", "secondary_checkpoints", "pc_*.npz"))
    assert len(pkls) == 2  # two multi-member primary clusters in the fixture

    # simulate a crash after secondary: remove Cdb/Ndb so the stage reruns,
    # and make fresh ANI computation blow up — only checkpoints can succeed
    os.remove(os.path.join(wd_loc, "data_tables", "Cdb.csv"))
    os.remove(os.path.join(wd_loc, "data_tables", "Ndb.csv"))

    def boom(*a, **k):
        raise AssertionError("secondary recomputed despite valid checkpoints")

    import drep_tpu.cluster.controller as ctl
    from drep_tpu.cluster import dispatch

    monkeypatch.setattr(ctl, "secondary_for_cluster", boom)
    # the small-cluster batched path must not recompute either
    monkeypatch.setitem(dispatch.SECONDARY_BATCHED, "jax_ani", boom)
    cdb = compare_wrapper(wd_loc, genome_paths, skip_plots=True)
    assert cdb["secondary_cluster"].nunique() == 3
