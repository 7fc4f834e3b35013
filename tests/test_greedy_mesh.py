"""ISSUE 42: the greedy engine's mesh route (candidate blocks row-sharded over
a device mesh, representative tiles replicated) at a small size on the CPU's
virtual devices: against the one-device route and against the plain reference
of the greedy rule (benchmark/reference_greedy.py) on seeded planted clusters,
and what the cluster's record entry says of who served and of what crossed the
link, by count, for a cluster whose blocks and tiles are known."""

import os

import numpy as np
import pandas as pd
import pytest

from benchmark import cells
from benchmark import reference_greedy as rg
from drep_tpu.cluster.greedy import greedy_secondary_cluster
from drep_tpu.ingest import GenomeSketches
from drep_tpu.utils.profiling import counters
from tests._greedy_testlib import (
    assert_same_answers, fixed_tiles, run_engine, sized_tiles, tile_cluster)

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
KW = {"S_ani": 0.95, "cov_thresh": 0.1}
# a primary cluster each: its genomes and the secondary groups planted inside it
CLUSTERS = {"144_in_3": [88, 40, 16], "300_in_5": [150, 75, 40, 25, 10],
            "600_in_6": [300, 150, 75, 40, 25, 10]}
COLUMNS = ("ani", "alignment_coverage", "ref_coverage", "querry_coverage")


def _sketches(names, scaled, n_kmers) -> GenomeSketches:
    gdb = pd.DataFrame({"genome": names, "n_kmers": n_kmers})
    return GenomeSketches(names=names, gdb=gdb, bottom=[s[:100] for s in scaled], scaled=scaled,
                          k=21, sketch_size=100, scale=200)


@pytest.fixture(scope="module")
def planted():
    """{cluster: its sketches, the reference's labels and rows, the one-device
    route's Ndb and labels}, each made once."""
    gen = cells.load_module(os.path.join(REPO, "benchmark", "generators", "planted_release.py"))
    cfg = cells.read_json(os.path.join(REPO, "benchmark", "configs", "gtdb_release_host4_6k.json"))
    out = {}
    for at, (name, groups) in enumerate(CLUSTERS.items()):
        m = sum(groups)
        # shallow sketches; the accessory share lets 600 genomes differ in size
        params = {**cfg["data"], "n": m, "s_scaled": 2000, "accessory_max": 0.32,
                  "clusters": [{"size": m, "count": 1, "groups": groups}]}
        data = gen.generate(params, 2**31 + 42 + at)
        gs = _sketches(data.names, data.scaled, data.n_kmers)
        labels, rows = rg.greedy_of_cluster(data.scaled, data.n_kmers, 21, KW["S_ani"], KW["cov_thresh"])
        with pytest.MonkeyPatch.context() as mp:
            mp.setenv("DREP_TPU_GREEDY_MATMUL", "1")
            one = greedy_secondary_cluster(gs, None, list(range(m)), pc=1, kw={**KW, "mesh_shape": 1})
        out[name] = {"gs": gs, "data": data, "want_labels": labels, "want_rows": rows, "one": one}
    return out


@pytest.mark.parametrize("cluster", list(CLUSTERS))
@pytest.mark.parametrize("devices", [1, 2, 4])
def test_the_mesh_route_equals_the_one_device_route_and_the_reference(planted, monkeypatch, devices, cluster):
    p = planted[cluster]
    gs, m = p["gs"], len(p["gs"].names)
    monkeypatch.setenv("DREP_TPU_GREEDY_MATMUL", "1")
    counters.reset()
    ndb, labels = greedy_secondary_cluster(gs, None, list(range(m)), pc=1,
                                           kw={**KW, "mesh_shape": devices})
    (call,) = counters.report(device=False)["secondary_greedy_calls"]
    assert (call["mesh_devices"], call["block_rows"]) == (devices, 128 * devices)
    assert call["blocks"] == -(-m // (128 * devices)) and call["reps"] == len(CLUSTERS[cluster])
    # the one-device route: same labels, same rows in the same order, same values
    # the engine hands the stage columns; the rows are read here as a frame
    one_ndb, one_labels = p["one"][0].frame(), p["one"][1]
    ndb = ndb.frame()
    np.testing.assert_array_equal(labels, one_labels)
    assert list(ndb["querry"]) == list(one_ndb["querry"])
    assert list(ndb["reference"]) == list(one_ndb["reference"])
    for col in COLUMNS:
        np.testing.assert_array_equal(ndb[col].to_numpy(), one_ndb[col].to_numpy(), err_msg=col)
    # the reference: the same partition (it is the planted one), the same pair set, its values
    by_label = {}
    for got, want in zip(labels, p["want_labels"]):
        assert by_label.setdefault(int(got), int(want)) == int(want)
    assert len(set(by_label.values())) == len(by_label) == len(CLUSTERS[cluster])
    index = {name: i for i, name in enumerate(gs.names)}
    got = {(index[q], index[r]): (a, cq, cr) for q, r, a, cq, cr in zip(
        ndb["querry"], ndb["reference"], ndb["ani"], ndb["alignment_coverage"], ndb["ref_coverage"])}
    want = {(q, r): (a, cq, cr) for q, r, a, cq, cr in p["want_rows"]}
    assert got.keys() == want.keys() and len(got) == len(ndb) == call["compared_pairs"]
    worst = max(abs(g - w) for key in want for g, w in zip(got[key], want[key]))
    assert 0 < worst < 1e-6  # float32 against float64: compared, and not equal by copy


@pytest.fixture(scope="module")
def strangers():
    """640 genomes that share a fifth of their hashes: one 'cluster' in which
    every genome founds a group of its own, so the representatives fill a
    tile of 512 and the counts below are known without running anything."""
    rng = np.random.default_rng(42)
    core = rng.choice(np.uint64(1) << np.uint64(40), size=60, replace=False).astype(np.uint64)
    scaled = [np.unique(np.concatenate([core, rng.integers(1 << 41, 1 << 62, size=240 + i % 7,
                                                           dtype=np.uint64)])) for i in range(640)]
    return _sketches([f"g{i}" for i in range(640)], scaled, [10_000 - i for i in range(640)])


@pytest.mark.parametrize("devices", [1, 4])
def test_the_record_says_who_served_and_what_crossed_the_link(strangers, monkeypatch, devices):
    monkeypatch.setenv("DREP_TPU_GREEDY_MATMUL", "1")
    counters.reset()
    ndb, labels = greedy_secondary_cluster(strangers, None, list(range(640)), pc=1,
                                           kw={**KW, "mesh_shape": devices})
    assert sorted(labels) == list(range(1, 641))  # each its own representative
    rec = counters.report(device=False)
    (call,) = rec["secondary_greedy_calls"]
    block = 128 * devices
    blocks = -(-640 // block)
    shipped_reps = (blocks - 1) * block  # those founded before the last block
    tile_ids = 512 * call["widths"]  # a representative tile, a block on four devices, in ids
    assert (call["mesh_devices"], call["block_rows"], call["blocks"], call["rep_tile"]) == (
        devices, block, blocks, 512)
    assert call["chunks"] == 1 and call["reps"] == 640
    # the representatives before each block: 0, block, 2 x block ...: no tile for none, then
    # the trailing tile at its bucket (128 -> 128, 256 -> 256, 384 -> 512), a filled one whole
    met = [b * block for b in range(blocks)]
    assert call["rep_rows_shipped"] == {1: 128 + 256 + 512 + 512, 4: 512}[devices]
    assert call["blocks_without_reps"] == 1 and call["rep_rows_real"] == sum(met)
    assert call["device_calls"] == sum(len(sized_tiles(n)) + 1 for n in met)
    assert call["bytes_shipped"] == 4 * (blocks * block + shipped_reps) * call["widths"]
    puts = rec["phases"]["secondary/greedy_put"]["calls"]
    if devices == 1:
        # a block crosses once, a representative once when it is founded; nothing is a mesh's
        assert call["block_bytes"] == 4 * blocks * block * call["widths"]
        assert call["rep_bytes"] == 4 * shipped_reps * call["widths"]  # the last block's stay home
        assert call["block_bytes"] + call["rep_bytes"] == call["bytes_shipped"]
        assert (call["rep_tiles_replicated"], call["partial_tile_ships"]) == (0, 0)
        assert puts == blocks
    else:
        # block 1 meets no representative (no tile, nothing shipped for one), founds 512, which
        # fill tile 0 (replicated once); block 2 meets that tile from the cache. A block crosses
        # once a tile, then once row-sharded and once a device for the self comparison
        assert (call["rep_tiles_replicated"], call["partial_tile_ships"]) == (1, 0)
        assert call["rep_bytes"] == 4 * tile_ids * devices
        assert call["block_bytes"] == 4 * tile_ids * ((1 + devices) + (1 + 1 + devices))
        assert puts == 2 + 1 + 3
    assert rec["phases"]["secondary/greedy_wait"]["calls"] == blocks
    assert len(ndb) == call["compared_pairs"] == 640 * 639 // 2  # every genome against all before it


# the representatives the last MESH block (512 rows on four devices) meets -> each block's founders
MESH_TILE_CASES = {1: [1, 0], 129: [129, 0], 512: [512, 0], 513: [512, 1, 0]}


@pytest.mark.parametrize("meets", list(MESH_TILE_CASES))
def test_on_a_mesh_the_trailing_tile_is_shipped_at_its_bucket_and_a_filled_one_once(monkeypatch, meets):
    """ISSUE 55 on the CPU's four virtual devices: the sized tiles give the
    one-device route's and the fixed 512-row tile's Ndb and labels bit for
    bit; a tile the representatives fill is still replicated once and read
    from the cache, the trailing one crosses at 128, 256 or 512 rows a
    block, and a block that meets no representative ships no tile."""
    import drep_tpu.cluster.greedy as greedy_mod

    founders = MESH_TILE_CASES[meets]
    gs = tile_cluster(founders, block=512)
    met = [sum(founders[:b]) for b in range(len(founders))]
    assert met[-1] == meets
    monkeypatch.setenv("DREP_TPU_GREEDY_MATMUL", "1")
    ndb, labels, rec = run_engine(gs, 4)
    one_ndb, one_labels, _ = run_engine(gs, 1)
    monkeypatch.setattr(greedy_mod, "_rep_tile_rows", fixed_tiles)
    fixed_ndb, fixed_labels, fixed_rec = run_engine(gs, 4)
    assert_same_answers(ndb, labels, one_ndb, one_labels)
    assert_same_answers(ndb, labels, fixed_ndb, fixed_labels)
    (call,), (fixed_call,) = rec["secondary_greedy_calls"], fixed_rec["secondary_greedy_calls"]

    tiles = [sized_tiles(n) for n in met]
    assert (call["mesh_devices"], call["block_rows"], call["rep_tile"]) == (4, 512, 512)
    assert call["rep_rows_shipped"] == sum(map(sum, tiles)) and call["blocks_without_reps"] == 1
    assert call["device_calls"] == sum(len(t) + 1 for t in tiles)
    # filled tiles: replicated once when the 512th representative is founded; every other tile
    # of a block is the trailing one, shipped with that block at its own rows, once a device
    filled = meets // 512
    trailing = [t[-1] for n, t in zip(met, tiles) if n % 512]
    assert (call["rep_tiles_replicated"], call["partial_tile_ships"]) == (filled, len(trailing))
    assert call["rep_bytes"] == 4 * call["widths"] * 4 * (512 * filled + sum(trailing))
    # the rule before shipped a tile of 512 with every block that had no filled tile to meet
    assert fixed_call["rep_rows_shipped"] == sum(max(-(-n // 512), 1) * 512 for n in met)
    assert fixed_call["rep_bytes"] >= call["rep_bytes"] + 4 * call["widths"] * 4 * 512  # block 1's
