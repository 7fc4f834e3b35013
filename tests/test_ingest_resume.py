"""Mid-ingest kill/resume via sketch shard checkpoints.

A killed 100k-genome ingest (hours of host sketching) must resume from
the genomes already sketched, not restart: finished genomes flush to
shard files every INGEST_SHARD completions, and a rerun loads them and
sketches only the remainder.
"""

import os
import zipfile

import numpy as np
import pandas as pd
import pytest

from drep_tpu.errors import UserInputError
import drep_tpu.ingest as ingest_mod
from drep_tpu.ingest import make_bdb, sketch_genomes
from drep_tpu.workdir import WorkDirectory


@pytest.fixture()
def counting_sketch(monkeypatch):
    """Wrap the worker with a call counter and an optional kill switch."""
    calls = {"n": 0, "die_after": None}
    real = ingest_mod._sketch_one

    def wrapped(job):
        if calls["die_after"] is not None and calls["n"] >= calls["die_after"]:
            raise RuntimeError("simulated kill")
        calls["n"] += 1
        return real(job)

    monkeypatch.setattr(ingest_mod, "_sketch_one", wrapped)
    return calls


def _assert_same_sketches(got, want):
    assert got.names == want.names
    for a, b in zip(got.bottom + got.scaled, want.bottom + want.scaled):
        np.testing.assert_array_equal(a, b)
    pd.testing.assert_frame_equal(got.gdb, want.gdb)


def test_killed_ingest_resumes_from_shards(tmp_path, genome_paths, counting_sketch, monkeypatch):
    monkeypatch.setattr(ingest_mod, "INGEST_SHARD", 2)  # flush every 2 genomes
    wd = WorkDirectory(str(tmp_path / "wd"))
    bdb = make_bdb(genome_paths)  # 5 genomes

    counting_sketch["die_after"] = 4
    with pytest.raises(RuntimeError, match="simulated kill"):
        sketch_genomes(bdb, wd=wd)
    assert counting_sketch["n"] == 4  # 4 sketched, 2 shards (2+2) flushed

    counting_sketch["die_after"] = None
    counting_sketch["n"] = 0
    gs = sketch_genomes(bdb, wd=wd)
    assert counting_sketch["n"] == 1  # only the 5th genome was recomputed
    assert gs.names == list(bdb["genome"])

    # results identical to a fresh, uninterrupted run
    wd2 = WorkDirectory(str(tmp_path / "wd2"))
    _assert_same_sketches(gs, sketch_genomes(bdb, wd=wd2))

    # the assembled cache supersedes the shards (disk footprint)
    import glob

    assert not glob.glob(os.path.join(str(tmp_path / "wd"), "data", "sketch_shards", "*.npz"))


def test_changed_args_invalidate_sketch_shards(tmp_path, genome_paths, counting_sketch, monkeypatch):
    monkeypatch.setattr(ingest_mod, "INGEST_SHARD", 2)
    wd = WorkDirectory(str(tmp_path / "wd"))
    bdb = make_bdb(genome_paths)

    counting_sketch["die_after"] = 4
    with pytest.raises(RuntimeError):
        sketch_genomes(bdb, wd=wd)

    # different sketching arguments: stale shards must NOT be resumed
    counting_sketch["die_after"] = None
    counting_sketch["n"] = 0
    sketch_genomes(bdb, wd=wd, scale=100)
    assert counting_sketch["n"] == len(bdb)


def test_pooled_ingest_matches_serial(genome_paths):
    """The process-pool path (spawn context — fork after JAX backend init
    can deadlock on inherited locks) returns results identical to the
    serial path."""
    bdb = make_bdb(genome_paths)
    serial = sketch_genomes(bdb)
    pooled = sketch_genomes(bdb, processes=2)
    _assert_same_sketches(pooled, serial)


def test_missing_genome_file_fails_fast():
    """A bad path must die as one clean error before any sketching."""
    with pytest.raises(UserInputError, match="do not exist"):
        make_bdb(["/nonexistent/g1.fasta", "/nonexistent/g2.fasta"])


def test_non_fasta_input_is_an_error(tmp_path):
    """A file with no FASTA records must not become a silent zero-length
    genome that clusters happily (observed: 'not a fasta' text produced a
    1-genome Cdb)."""
    p = tmp_path / "bad.txt"
    p.write_text("not a fasta\n")
    with pytest.raises(UserInputError, match="no FASTA records with valid nucleotide"):
        sketch_genomes(make_bdb([str(p)]))


def test_cli_reports_clean_error_for_bad_input(tmp_path):
    """CLI: user-input errors end as one `!!!` line + exit 1, no traceback."""
    import subprocess
    import sys
    from pathlib import Path

    p = tmp_path / "bad.txt"
    p.write_text("junk\n")
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    repo_root = Path(__file__).resolve().parents[1]
    r = subprocess.run(
        [sys.executable, "-m", "drep_tpu", "compare", str(tmp_path / "wd"), "-g", str(p)],
        capture_output=True, text=True, env=env, cwd=str(repo_root),
    )
    assert r.returncode == 1
    combined = r.stdout + r.stderr
    assert "!!!" in combined
    assert "Traceback" not in combined


def test_sketch_cache_will_hit_sees_shard_complete_store(
    tmp_path, genome_paths, counting_sketch
):
    """The controller's warmup pre-check (sketch_cache_will_hit) must
    treat a shard store that already covers every genome as a hit: a run
    killed after the last shard flush but before whole-run cache assembly
    rebuilds from shards with zero sketching work, so there is no ingest
    to hide the streaming compile behind (and the warmup's throwaway
    execution would just race the first real tiles)."""
    from drep_tpu.ingest import (
        DEFAULT_SCALE,
        DEFAULT_SKETCH_SIZE,
        sketch_args_snapshot,
        sketch_cache_will_hit,
    )
    from drep_tpu.ops.kmers import DEFAULT_K
    from drep_tpu.utils.ckptmeta import open_checkpoint_dir

    wd = WorkDirectory(str(tmp_path / "wd"))
    bdb = make_bdb(genome_paths)
    key = (bdb["genome"], DEFAULT_K, DEFAULT_SKETCH_SIZE, DEFAULT_SCALE, "splitmix64")

    assert not sketch_cache_will_hit(None, *key)
    assert not sketch_cache_will_hit(wd, *key)  # empty workdir

    # real sketches computed without a workdir, then planted as shards —
    # the on-disk state of a run killed between last flush and assembly
    gs = sketch_genomes(bdb)
    batch = {
        g: {
            **{k: int(gs.gdb.iloc[i][k]) for k in ("length", "N50", "contigs", "n_kmers")},
            "bottom": gs.bottom[i],
            "scaled": gs.scaled[i],
        }
        for i, g in enumerate(gs.names)
    }
    shard_dir = wd.get_dir(ingest_mod._SKETCH_SHARD_SUBDIR)
    snapshot = sketch_args_snapshot(*key)
    open_checkpoint_dir(
        shard_dir, ingest_mod._sketch_shard_meta(snapshot), clear_suffixes=(".npz",)
    )

    # partial coverage: not a hit (real sketching remains -> warmup pays)
    ingest_mod._save_sketch_shard(
        os.path.join(shard_dir, "shard_a.npz"), {g: batch[g] for g in gs.names[:3]}
    )
    assert not sketch_cache_will_hit(wd, *key)

    # complete coverage with NO whole-run cache: must be a hit
    ingest_mod._save_sketch_shard(
        os.path.join(shard_dir, "shard_b.npz"), {g: batch[g] for g in gs.names[3:]}
    )
    assert not wd.has_arrays("sketches")
    assert sketch_cache_will_hit(wd, *key)
    # different args against the same store: meta mismatch, no hit —
    # and read-only: the probe must not clear the store's shards
    assert not sketch_cache_will_hit(wd, bdb["genome"], DEFAULT_K,
                                     DEFAULT_SKETCH_SIZE, 100, "splitmix64")
    assert len(os.listdir(shard_dir)) == 3  # meta + two shards survive

    # and the pre-check told the truth: the resumed run sketches nothing
    counting_sketch["n"] = 0
    gs2 = sketch_genomes(bdb, wd=wd)
    assert counting_sketch["n"] == 0
    assert gs2.names == gs.names
    # after assembly the whole-run cache carries the hit
    assert sketch_cache_will_hit(wd, *key)


def test_sketch_cache_will_hit_rejects_zero_kmer_stale_cache(tmp_path, genome_paths):
    """A whole-run cache carrying a zero-kmer genome is dropped and fully
    re-sketched by sketch_genomes (legacy pre-validation caches); the
    warmup pre-check must mirror that rule and NOT claim a hit, or the
    re-sketch runs without the compile overlap it exists for."""
    from drep_tpu.ingest import (
        DEFAULT_SCALE,
        DEFAULT_SKETCH_SIZE,
        sketch_cache_will_hit,
    )
    from drep_tpu.ops.kmers import DEFAULT_K

    wd = WorkDirectory(str(tmp_path / "wd"))
    bdb = make_bdb(genome_paths)
    key = (bdb["genome"], DEFAULT_K, DEFAULT_SKETCH_SIZE, DEFAULT_SCALE, "splitmix64")

    sketch_genomes(bdb, wd=wd)
    assert sketch_cache_will_hit(wd, *key)  # healthy cache: hit

    # forge the legacy state: same cache arrays/args, but Gdb says one
    # genome sketched to zero k-mers (written before validation existed)
    gdb = wd.get_db("Gdb")
    gdb.loc[0, "n_kmers"] = 0
    wd.store_db(gdb, "Gdb")
    assert not sketch_cache_will_hit(wd, *key)


# ---- the shard's format: stored since ISSUE 53, deflated before it --------


def _open_store_and_sketch(wd_path, bdb, indices, k=21, sketch_size=1000, scale=200):
    """The shard store of `wd_path` opened under the matching meta, and the
    genomes `indices` sketched as the batch a flush would hold."""
    from drep_tpu.ingest import _SKETCH_SHARD_SUBDIR, _sketch_shard_meta, sketch_args_snapshot
    from drep_tpu.utils.ckptmeta import open_checkpoint_dir

    wd = WorkDirectory(wd_path)
    shard_dir = wd.get_dir(_SKETCH_SHARD_SUBDIR)
    snap = sketch_args_snapshot(bdb["genome"], k, sketch_size, scale, "splitmix64")
    open_checkpoint_dir(shard_dir, _sketch_shard_meta(snap), clear_suffixes=(".npz",))
    batch = {}
    for i in indices:
        row = bdb.iloc[i]
        name, res = ingest_mod._sketch_one(
            (row.genome, row.location, k, sketch_size, scale, "splitmix64")
        )
        batch[name] = res
    return shard_dir, batch


def _save_deflated(path, batch, monkeypatch):
    """The batch as a tree before ISSUE 53 wrote it: the same payload
    through `atomic_savez(compressed=True)`."""
    from drep_tpu.utils import ckptmeta

    real = ckptmeta.atomic_savez
    with monkeypatch.context() as m:
        m.setattr(ckptmeta, "atomic_savez",
                  lambda path, compressed=True, **arrays: real(path, compressed=True, **arrays))
        ingest_mod._save_sketch_shard(path, batch)


def _compress_types(path):
    with zipfile.ZipFile(path) as zf:
        return {info.filename: info.compress_type for info in zf.infolist()}


@pytest.mark.parametrize("fmt", ["stored", "deflated"])
def test_a_shard_of_either_format_loads_bit_equal_and_is_not_sketched_again(
    fmt, tmp_path, genome_paths, counting_sketch, monkeypatch
):
    """`_load_sketch_shard` reads stored and deflated members alike, so a
    work directory whose shards an older tree wrote compressed resumes on
    this one: no format version, no migration."""
    bdb = make_bdb(genome_paths)
    shard_dir, batch = _open_store_and_sketch(str(tmp_path / "wd"), bdb, [0, 1, 3])
    path = os.path.join(shard_dir, "shard_planted.npz")
    if fmt == "stored":
        ingest_mod._save_sketch_shard(path, batch)
    else:
        _save_deflated(path, batch, monkeypatch)
    kinds = set(_compress_types(path).values())
    assert kinds == {zipfile.ZIP_STORED if fmt == "stored" else zipfile.ZIP_DEFLATED}

    loaded = ingest_mod._load_sketch_shard(path)
    assert list(loaded) == list(batch)
    for g, res in batch.items():
        # what a shard keeps of a result: the run's own seconds and byte
        # counts are not resumed
        assert set(loaded[g]) == {*ingest_mod._SHARD_SCALARS, "bottom", "scaled"}
        for key in ingest_mod._SHARD_SCALARS:
            assert loaded[g][key] == res[key]
        for key in ("bottom", "scaled"):
            assert loaded[g][key].dtype == res[key].dtype == np.uint64
            np.testing.assert_array_equal(loaded[g][key], res[key])

    counting_sketch["n"] = 0
    gs = sketch_genomes(bdb, wd=WorkDirectory(str(tmp_path / "wd")))
    assert counting_sketch["n"] == 2  # the two genomes no shard held
    _assert_same_sketches(gs, sketch_genomes(bdb))


def test_the_writer_leaves_stored_members_and_the_checksum(tmp_path, genome_paths):
    """Uniform 64-bit hashes do not deflate: every member of the shard the
    writer publishes is ZIP_STORED, the in-band `__crc__` among them, and
    no tmp is left beside it."""
    from drep_tpu.utils.durableio import CRC_KEY

    shard_dir, batch = _open_store_and_sketch(str(tmp_path / "wd"), make_bdb(genome_paths), range(5))
    path = os.path.join(shard_dir, "shard_planted.npz")
    ingest_mod._save_sketch_shard(path, batch)
    kinds = _compress_types(path)
    assert set(kinds) == {f"{key}.npy" for key in (
        "names", *ingest_mod._SHARD_SCALARS, "bottom", "bottom_offsets",
        "scaled", "scaled_offsets", CRC_KEY)}
    assert set(kinds.values()) == {zipfile.ZIP_STORED}
    hashes = sum(len(r["bottom"]) + len(r["scaled"]) for r in batch.values())
    assert 8 * hashes < os.path.getsize(path) < 8 * hashes + 8192  # the arrays and their headers
    assert sorted(os.listdir(shard_dir)) == ["meta.json", "shard_planted.npz"]


def test_a_flipped_byte_in_a_stored_shard_is_quarantined_and_its_genomes_sketched_again(
    tmp_path, genome_paths, counting_sketch
):
    """A stored member has no deflate stream to break: the zip's and the
    payload's checksums alone say that the bytes rotted. The shard is
    healed (counted, removed), its genomes sketched again, the healthy
    shard beside it resumed."""
    from drep_tpu.utils.durableio import _flip_bit
    from drep_tpu.utils.profiling import counters

    bdb = make_bdb(genome_paths)
    shard_dir, batch = _open_store_and_sketch(str(tmp_path / "wd"), bdb, range(5))
    names = list(batch)
    rotted, healthy = (os.path.join(shard_dir, f"shard_{x}.npz") for x in "ab")
    ingest_mod._save_sketch_shard(rotted, {g: batch[g] for g in names[:3]})
    ingest_mod._save_sketch_shard(healthy, {g: batch[g] for g in names[3:]})
    _flip_bit(rotted)

    counters.reset()
    counting_sketch["n"] = 0
    gs = sketch_genomes(bdb, wd=WorkDirectory(str(tmp_path / "wd")))
    assert counting_sketch["n"] == 3  # the rotted shard's genomes, no others
    assert counters.faults.get("corrupt_shards_healed") == 1, counters.faults
    _assert_same_sketches(gs, sketch_genomes(bdb))


# ---- per-process sharded ingest (faked 2-process pod, single process) ----


@pytest.fixture()
def fake_pod_pid1(monkeypatch):
    """Make sketch_genomes believe it is process 1 of a 2-process pod
    without real jax.distributed: process count/index faked, the
    checkpoint-dir open barrier no-op'd (single OS process)."""
    import jax
    from jax.experimental import multihost_utils

    monkeypatch.setenv("DREP_TPU_INGEST_BARRIER_S", "5")
    monkeypatch.setattr(jax, "process_count", lambda: 2)
    monkeypatch.setattr(jax, "process_index", lambda: 1)
    monkeypatch.setattr(multihost_utils, "sync_global_devices", lambda *_a, **_k: None)


def _plant_peer_shards(wd_path, bdb, indices):
    """Simulate the pid-0 peer: sketch `indices` and write them as shards
    with the matching meta (real single-process calls, before any fakes)."""
    shard_dir, batch = _open_store_and_sketch(wd_path, bdb, indices)
    ingest_mod._save_sketch_shard(os.path.join(shard_dir, "shard_peer.npz"), batch)
    return shard_dir


def test_sharded_ingest_assembles_peer_stripes(tmp_path, genome_paths, counting_sketch, fake_pod_pid1):
    """pid 1 of a faked 2-process pod must sketch ONLY its global-index
    stripe (odd indices), assemble the even indices from the peer's
    shards, and signal assembly with its marker instead of writing the
    cache (that is pid 0's job)."""
    bdb = make_bdb(genome_paths)  # 5 genomes: pid1 owns indices 1, 3
    shard_dir = _plant_peer_shards(str(tmp_path / "wd"), bdb, [0, 2, 4])
    counting_sketch["n"] = 0  # planting went through the counted wrapper

    gs = sketch_genomes(bdb, wd=WorkDirectory(str(tmp_path / "wd")))
    assert counting_sketch["n"] == 2  # stripe only: indices 1 and 3
    assert gs.names == list(bdb["genome"])  # full assembly
    assert all(len(s) > 0 for s in gs.scaled)
    assert os.path.exists(os.path.join(shard_dir, "assembled_1.done"))
    # cache write + shard reclamation belong to pid 0
    assert not WorkDirectory(str(tmp_path / "wd")).has_arrays("sketches")


def test_sharded_ingest_poison_marker_fails_fast(tmp_path, genome_paths, fake_pod_pid1):
    """A peer's unparseable-input poison marker must surface as the real
    UserInputError in every process's barrier, not a timeout."""
    import json
    import time

    bdb = make_bdb(genome_paths)
    shard_dir = _plant_peer_shards(str(tmp_path / "wd"), bdb, [])  # peer wrote nothing
    with open(os.path.join(shard_dir, "ingest_error_0.json"), "w") as f:
        json.dump({"pid": 0, "genomes": ["genome_A.fasta"], "n": 1}, f)

    t0 = time.monotonic()
    with pytest.raises(UserInputError, match="peer process 0"):
        sketch_genomes(bdb, wd=WorkDirectory(str(tmp_path / "wd")))
    assert time.monotonic() - t0 < 4  # fail fast, not the barrier timeout
