"""The index store's part files (ISSUE 50): no file of an index grows past
``workdir.ARRAY_PART_BYTES``; a shard is a head written last and checked
parts beside it; a lost part is its shard torn (refused read-only, healed by
`index update`); gc, compaction and the scrubber treat a part with its head;
a one-file shard of an older tree loads unchanged."""

from __future__ import annotations

import glob
import os
import subprocess
import sys
import textwrap

import numpy as np
import pytest

from tests._index_testlib import write_genome_set

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

# 72 genomes of 125,000 scaled hashes: 72 MB of generation-0 sketches; a batch of 8
_UNDER_THE_LIMIT = textwrap.dedent("""
    import os, resource, signal, sys
    sys.path.insert(0, {repo!r})
    signal.signal(signal.SIGXFSZ, signal.SIG_IGN)  # a write past the limit fails with EFBIG
    resource.setrlimit(resource.RLIMIT_FSIZE, (32 << 20, 32 << 20))
    import numpy as np, pandas as pd
    from drep_tpu import controller
    from drep_tpu.index.build import resolve_params
    from drep_tpu.index.federation import write_params_handoff
    from drep_tpu.index.store import load_index

    out = sys.argv[1]
    rng = np.random.default_rng(7)
    shared = np.sort(rng.integers(0, 2**63, 125_000, dtype=np.uint64))

    def genome(i):
        own = rng.integers(0, 2**63, 125_000, dtype=np.uint64)
        if i % 9 == 0:  # every ninth genome is a near copy of one root: one cluster
            own[:120_000] = shared[:120_000]
        return np.unique(own)

    def handoff(path, lo, hi):
        names = [f"g{{i:03d}}.fasta" for i in range(lo, hi)]
        sk = {{g: genome(i) for g, i in zip(names, range(lo, hi))}}
        results = {{g: {{"bottom": s[:1000], "scaled": s, "length": 25_000_000, "N50": 50_000,
                        "contigs": 10, "n_kmers": 25_000_000}} for g, s in sk.items()}}
        batch = pd.DataFrame({{"genome": names, "location": ["/nonexistent/" + g for g in names]}})
        write_params_handoff(path, resolve_params(), batch, results)
        return sk

    first = handoff(os.path.join(out, "h0", "h0.npz"), 0, 72)
    idx_dir = os.path.join(out, "idx")
    controller.main(["index", "update", idx_dir, "--params_file", os.path.join(out, "h0", "h0.npz")])
    idx = load_index(idx_dir)
    assert idx.n == 72 and idx.generation == 0
    assert all(np.array_equal(idx.scaled[i], first[g]) for i, g in enumerate(idx.names))
    second = handoff(os.path.join(out, "h1", "h1.npz"), 72, 80)
    controller.main(["index", "update", idx_dir, "--params_file", os.path.join(out, "h1", "h1.npz")])
    controller.main(["index", "compact", idx_dir, "--min_generations", "2"])
    idx = load_index(idx_dir)
    assert idx.n == 80 and idx.generation == 2 and len(idx.sketch_shards) == 1
    both = {{**first, **second}}
    assert all(np.array_equal(idx.scaled[i], both[g]) for i, g in enumerate(idx.names))
    assert len(set(idx.primary.tolist())) == 72  # the nine near copies are one primary cluster
    print("OK", flush=True)
""")


def _sizes(root: str) -> dict[str, int]:
    """The bytes of every file under `root` but the logs."""
    return {os.path.join(d, f): os.path.getsize(os.path.join(d, f))
            for d, _, ff in os.walk(root) for f in ff if os.path.basename(d) != "log"}


def test_an_index_over_the_file_size_cap_builds_loads_updates_and_compacts(tmp_path):
    """RLIMIT_FSIZE of 32 MiB, as the check machine has one: generation 0's
    sketches are 72 MB and the folded shard 80 MB, and no file passes
    ARRAY_PART_BYTES plus a header."""
    from drep_tpu import workdir

    script = tmp_path / "job.py"
    script.write_text(_UNDER_THE_LIMIT.format(repo=REPO))
    env = {**os.environ, "JAX_PLATFORMS": "cpu"}
    done = subprocess.run([sys.executable, str(script), str(tmp_path)], env=env, capture_output=True,
                          text=True, timeout=900)
    assert done.returncode == 0 and done.stdout.strip().endswith("OK"), done.stderr[-3000:]
    sizes = _sizes(str(tmp_path))
    assert sum(sizes.values()) > 150e6  # two hand-offs and the folded shard
    assert max(sizes.values()) <= workdir.ARRAY_PART_BYTES + 4096, max(sizes, key=sizes.get)
    # compaction's gc dropped the two superseded shards with their parts
    left = sorted(os.listdir(tmp_path / "idx" / "sketches"))
    assert left[0] == "sketch_g000002.npz" and all(f.startswith("sketch_g000002.") for f in left)
    assert len(left) >= 1 + 5  # the head, and 80 MB of `scaled` in 16 MiB parts


@pytest.fixture
def small_parts(monkeypatch):
    from drep_tpu import workdir

    monkeypatch.setattr(workdir, "ARRAY_PART_BYTES", 4096)


@pytest.fixture
def fasta_index(tmp_path, small_parts):
    """An index of two generations from FASTA, every family cut at 4 KiB."""
    from drep_tpu.index import build_from_paths, index_update

    paths = write_genome_set(str(tmp_path / "genomes"), [3, 2, 1, 1], seed=11)
    idx_dir = str(tmp_path / "idx")
    build_from_paths(idx_dir, paths[:5], length=0)
    index_update(idx_dir, paths[5:])
    return idx_dir, paths


def _arrays(idx) -> dict:
    return {"names": list(idx.names), "bottom": np.concatenate(idx.bottom),
            "scaled": np.concatenate(idx.scaled), "primary": idx.primary.copy(),
            "suffix": idx.suffix.copy(), "edges": np.stack([idx.edges[0], idx.edges[1]])}


def _same(a: dict, b: dict) -> bool:
    return a["names"] == b["names"] and all(np.array_equal(a[k], b[k]) for k in a if k != "names")


def test_a_lost_part_refuses_read_only_and_heals_under_update(fasta_index, tmp_path):
    from drep_tpu.errors import UserInputError
    from drep_tpu.index import index_classify, index_update
    from drep_tpu.index.store import load_index

    idx_dir, paths = fasta_index
    before = _arrays(load_index(idx_dir))
    parts = sorted(glob.glob(os.path.join(idx_dir, "sketches", "sketch_g000000.bottom.*.npz")))
    assert len(parts) >= 3 and max(_sizes(idx_dir).values()) < 4096 + 4096  # a head: 13 members, 250 B of zip and npy framing each
    os.remove(parts[1])
    query = write_genome_set(str(tmp_path / "query"), [1], seed=99, prefix="q")
    with pytest.raises(UserInputError, match="is corrupt.*is missing"):
        index_classify(idx_dir, query)
    assert not os.path.exists(parts[1])  # read-only: nothing written, nothing removed
    summary = index_update(idx_dir, None)
    assert summary["admitted"] == 0 and summary["healed"] == [os.path.join("sketches", "sketch_g000000.npz")]
    assert os.path.exists(parts[1])
    healed = load_index(idx_dir)
    assert not healed.healed and _same(before, _arrays(healed))
    # a torn part heals like a lost one
    os.truncate(parts[0], os.path.getsize(parts[0]) // 2)
    with pytest.raises(UserInputError, match="is corrupt"):
        load_index(idx_dir)
    assert index_update(idx_dir, None)["healed"]
    assert _same(before, _arrays(load_index(idx_dir)))


def test_a_one_file_shard_of_an_older_tree_loads_unchanged(fasta_index):
    """The old writer: every member in ONE `atomic_savez`. Such a shard is a
    head with no part list."""
    from drep_tpu import workdir
    from drep_tpu.index.store import load_index, read_payload
    from drep_tpu.utils.ckptmeta import atomic_savez

    idx_dir, _ = fasta_index
    before = _arrays(load_index(idx_dir))
    for sub, compressed in (("sketches", False), ("edges", True), ("state", True)):
        for head in sorted(glob.glob(os.path.join(idx_dir, sub, "*_g00000[01].npz"))):
            if workdir.head_of(os.path.basename(head)) != os.path.basename(head):
                continue
            whole = read_payload(head, "payload")
            for loc in (head, *workdir.part_locs(head)):
                os.remove(loc)
            atomic_savez(head, compressed=compressed, **whole)  # the fixture: the old writer
    files = [f for d in ("sketches", "edges", "state") for f in os.listdir(os.path.join(idx_dir, d))]
    assert all(workdir.head_of(f) == f for f in files) and len(files) == 5
    assert _same(before, _arrays(load_index(idx_dir)))


def test_the_scrubber_verifies_parts_and_keeps_them_with_their_head(fasta_index):
    sys.path.insert(0, os.path.join(REPO, "tools"))
    import io

    import scrub_store

    from drep_tpu.utils.durableio import _flip_bit

    idx_dir, _ = fasta_index
    report = scrub_store.scrub([idx_dir], out=io.StringIO())
    parts = sorted(glob.glob(os.path.join(idx_dir, "sketches", "sketch_g000000.bottom.*.npz")))
    assert not report["damaged"] and not report["superseded"] and len(parts) >= 3
    assert report["verified"] >= len(os.listdir(os.path.join(idx_dir, "sketches")))
    _flip_bit(parts[2])
    report = scrub_store.scrub([idx_dir], out=io.StringIO())
    assert [p for p, _ in report["damaged"]] == [parts[2]]
    assert scrub_store.damage_class(report["damaged"]) == "sketch"


def test_compaction_and_gc_drop_a_shards_parts_with_it(fasta_index):
    from drep_tpu.index.maintenance import compact_store
    from drep_tpu.index.store import load_index

    idx_dir, _ = fasta_index
    before = _arrays(load_index(idx_dir))
    # an orphan of a killed write: parts of a generation no manifest names
    orphan = os.path.join(idx_dir, "sketches", "sketch_g000007.scaled.0000.npz")
    with open(orphan, "wb") as f:
        f.write(b"x")
    out = compact_store(idx_dir)
    assert out["compacted"] and out["generation"] == 2
    for sub, prefix in (("sketches", "sketch_g000002"), ("edges", "edges_g000002"), ("state", "state_g000002")):
        names = os.listdir(os.path.join(idx_dir, sub))
        assert names and all(f.startswith(prefix + ".") for f in names), names
    assert _same(before, _arrays(load_index(idx_dir)))


def test_the_handoff_goes_through_the_same_writer(tmp_path, small_parts):
    import pandas as pd

    from drep_tpu.index.build import resolve_params
    from drep_tpu.index.federation import read_params_handoff, write_params_handoff

    rng = np.random.default_rng(3)
    names = [f"h{i}.fasta" for i in range(4)]
    results = {g: {"bottom": np.sort(rng.integers(0, 2**63, 300, dtype=np.uint64)),
                   "scaled": np.sort(rng.integers(0, 2**63, 900, dtype=np.uint64)),
                   "length": 1000 + i, "N50": 10, "contigs": 1, "n_kmers": 980 + i}
               for i, g in enumerate(names)}
    batch = pd.DataFrame({"genome": names, "location": ["/x/" + g for g in names]})
    path = str(tmp_path / "pod" / "handoff.npz")
    write_params_handoff(path, resolve_params(), batch, results)
    assert len(glob.glob(str(tmp_path / "pod" / "handoff.scaled.*.npz"))) >= 7
    assert max(_sizes(str(tmp_path / "pod")).values()) < 4096 + 4096
    back = read_params_handoff(path, workers=3)
    assert back["params"] == resolve_params() and list(back["batch"]["genome"]) == names
    assert all(np.array_equal(back["results"][g][k], results[g][k]) for g in names for k in ("bottom", "scaled"))
    assert all(back["results"][g]["n_kmers"] == results[g]["n_kmers"] for g in names)
