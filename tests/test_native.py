"""Native C++ ingest: byte-for-byte equivalence with the numpy oracle.

The C++ path (drep_tpu/native/ingest.cc) must produce EXACTLY the same
stats and sketch hash sets as ops/kmers.py + utils/fasta.py — same
canonical packing, same splitmix64, same N50 convention — on the fixture
genomes and on adversarial synthetic FASTAs (lowercase, Ns, multi-line,
empty headers, gzip).
"""

import gzip
import os

import numpy as np
import pytest

from drep_tpu.native import get_library, sketch_fasta_native
from drep_tpu.ops import kmers
from drep_tpu.utils.fasta import fasta_stats, n50, read_fasta_contigs

def test_build_succeeds_when_compiler_present():
    # deliberately NOT behind needs_native: if g++ exists, a failed build is
    # a BUG in ingest.cc, and skipping the whole module would mask it
    import shutil

    if shutil.which("g++") is None:
        pytest.skip("no g++ on this machine")
    assert get_library() is not None, "g++ present but native build failed"


needs_native = pytest.mark.skipif(
    get_library() is None, reason="native library unavailable (no g++?)"
)

K, SKETCH, SCALE = 21, 1000, 200


def _oracle(path):
    contigs = read_fasta_contigs(path)
    lengths = np.array([len(c) for c in contigs], dtype=np.int64)
    raw = np.concatenate(
        [kmers.splitmix64(kmers.packed_kmers(c, K)) for c in contigs]
        or [np.empty(0, np.uint64)]
    )
    bottom, scaled, n_kmers = kmers.sketches_from_raw(raw, SKETCH, SCALE)
    return {
        "length": int(lengths.sum()) if len(lengths) else 0,
        "N50": n50(lengths),
        "contigs": len(contigs),
        "n_kmers": n_kmers,
        "bottom": bottom,
        "scaled": scaled,
    }


def _assert_equal(native, oracle):
    assert native["length"] == oracle["length"]
    assert native["N50"] == oracle["N50"]
    assert native["contigs"] == oracle["contigs"]
    assert native["n_kmers"] == oracle["n_kmers"]
    np.testing.assert_array_equal(native["bottom"], oracle["bottom"])
    np.testing.assert_array_equal(native["scaled"], oracle["scaled"])


@needs_native
def test_native_matches_oracle_on_fixtures(genome_paths):
    for path in genome_paths:
        native = sketch_fasta_native(path, K, SKETCH, SCALE)
        _assert_equal(native, _oracle(path))


@needs_native
def test_native_adversarial_fasta(tmp_path):
    content = (
        ">c1 description words\n"
        "acgtACGTacgtACGTacgtACGTNNNNacgtacgtacgtacgtacgtacgt\n"
        "ACGTACGTACGTACGTACGTACGT\n"
        ">empty_contig\n"
        ">c2\n"
        "TTTTTTTTTTTTTTTTTTTTTTTTGGGGGGGGCCCCCCCCAAAAAAAAACGT\n"
        ">c3_internal_whitespace\n"
        "  ACGTACGTACGTACGTACGTACGTA CGTACGTACGTACGTACGTACGTACGT\t\r\n"
    )
    p = tmp_path / "adv.fasta"
    p.write_text(content)
    native = sketch_fasta_native(str(p), K, SKETCH, SCALE)
    _assert_equal(native, _oracle(str(p)))
    assert native["contigs"] == 3  # the empty header makes no contig


@needs_native
def test_native_truncated_gzip_raises(tmp_path, genome_paths):
    gz = tmp_path / "trunc.fasta.gz"
    with open(genome_paths[0], "rb") as fin, gzip.open(gz, "wb") as fout:
        fout.write(fin.read())
    data = gz.read_bytes()
    gz.write_bytes(data[: len(data) // 2])  # chop the stream mid-way
    with pytest.raises(RuntimeError, match="truncated"):
        sketch_fasta_native(str(gz), K, SKETCH, SCALE)


@needs_native
def test_native_gzip(tmp_path, genome_paths):
    gz = tmp_path / "g.fasta.gz"
    with open(genome_paths[0], "rb") as fin, gzip.open(gz, "wb") as fout:
        fout.write(fin.read())
    native = sketch_fasta_native(str(gz), K, SKETCH, SCALE)
    _assert_equal(native, _oracle(genome_paths[0]))


@needs_native
def test_native_missing_file_raises(tmp_path):
    with pytest.raises(FileNotFoundError):
        sketch_fasta_native(str(tmp_path / "nope.fasta"), K, SKETCH, SCALE)


@needs_native
def test_native_stats_match_fasta_stats(genome_paths):
    for path in genome_paths:
        native = sketch_fasta_native(path, K, SKETCH, SCALE)
        st = fasta_stats(path)
        assert (native["length"], native["N50"], native["contigs"]) == (
            st.length,
            st.N50,
            st.contigs,
        )


_SEQ = "ACGTTGCAAGCTTAGCCGATATCGGCTAAGCTTGCAACGTACGGATCCGTA"  # 50 bases

# what the filter's numbers have to survive, now that they are the kernel's:
# name -> the file's text (gzip and the empty file are made from it below)
_STATS_CASES = {
    "n_runs": f">a\n{_SEQ}{'N' * 40}{_SEQ}\n>b\n{'N' * 30}\n{_SEQ}\n",
    "iupac": f">a\n{_SEQ}RYKMSWBDHVN{_SEQ}\n>b\n{_SEQ}\n",
    "soft_masked": f">a\n{_SEQ.lower()}\n{_SEQ}\n>b\n{_SEQ.lower()}{_SEQ}\n",
    "crlf": f">a\r\n{_SEQ}\r\n{_SEQ}\r\n>b\r\n{_SEQ}\r\n",
    "blank_lines": f">a\n{_SEQ}\n\n   \n\t\n{_SEQ}\n\n>b\n \n{_SEQ}\n \n",
    "header_without_sequence": f">a\n>b\n{_SEQ}\n>c\n\n>d\n{_SEQ}{_SEQ}\n>e\n",
    "no_trailing_newline": f">a\n{_SEQ}\n>b\n{_SEQ}{_SEQ}",
    "empty_file": "",
    "gzip": f">a\n{_SEQ}\n{_SEQ}\n>b\n{_SEQ}\n",
    "shorter_than_k": ">a\nACGTACGT\n>b\nACG\n",
}


@needs_native
@pytest.mark.parametrize("mode", ["sketch", "stats_only"])
@pytest.mark.parametrize("path_kind", ["native", "numpy"])
@pytest.mark.parametrize("case", sorted(_STATS_CASES))
def test_stats_of_either_kernel_in_either_mode_match_fasta_stats(
    tmp_path, monkeypatch, case, path_kind, mode
):
    """`sketch_one`'s length, N50 and contigs — the native kernel's and the
    NumPy fall-back's, sketching and read for the stats alone — are
    `fasta_stats`'s; the stats-only result hashed nothing and carries no sketch."""
    from drep_tpu.sketch_worker import sketch_one

    path = tmp_path / (case + ".fa")
    data = _STATS_CASES[case].encode()
    path.write_bytes(gzip.compress(data) if case == "gzip" else data)
    want = fasta_stats(str(path))
    if case != "empty_file":
        assert want.contigs >= 2 and want.length > 0
    if path_kind == "numpy":
        monkeypatch.setenv("DREP_TPU_NO_NATIVE", "1")
    job = ("g", str(path)) + ((K, SKETCH, SCALE, "splitmix64") if mode == "sketch" else ())
    name, got = sketch_one(job)
    assert name == "g"
    assert (got["length"], got["N50"], got["contigs"]) == (want.length, want.N50, want.contigs)
    assert got["file_bytes"] == os.path.getsize(path) and got["seconds"] > 0
    if mode == "stats_only":
        assert "bottom" not in got and "scaled" not in got
        assert got["valid_kmers"] == 0 and got["n_kmers"] == 0
    else:
        oracle = _oracle(str(path))
        np.testing.assert_array_equal(got["bottom"], oracle["bottom"])
        np.testing.assert_array_equal(got["scaled"], oracle["scaled"])
        assert (got["valid_kmers"] == 0) == (case in ("empty_file", "shorter_than_k"))


@needs_native
def test_env_kill_switch(monkeypatch, genome_paths):
    monkeypatch.setenv("DREP_TPU_NO_NATIVE", "1")
    assert sketch_fasta_native(genome_paths[0], K, SKETCH, SCALE) is None


@needs_native
def test_pipeline_uses_native_transparently(bdb):
    # ingest through the public API must give identical sketches either way
    from drep_tpu.ingest import _sketch_one

    row = next(bdb.itertuples())
    _, via_native = _sketch_one((row.genome, row.location, K, SKETCH, SCALE, "splitmix64"))
    os.environ["DREP_TPU_NO_NATIVE"] = "1"
    try:
        _, via_numpy = _sketch_one((row.genome, row.location, K, SKETCH, SCALE, "splitmix64"))
    finally:
        del os.environ["DREP_TPU_NO_NATIVE"]
    _assert_equal(via_native, via_numpy)


@needs_native
def test_native_murmur3_matches_numpy(genome_paths):
    """The Mash-compatible murmur3 hash must be byte-equal across the C++
    and numpy ingest paths (both sketches AND the FracMinHash fast-path
    rule are hash-dependent)."""
    path = genome_paths[0]
    native = sketch_fasta_native(path, K, SKETCH, SCALE, hash_name="murmur3")
    contigs = read_fasta_contigs(path)
    raw = np.concatenate(
        [kmers.hash_kmers(kmers.packed_kmers(c, K), K, "murmur3") for c in contigs]
    )
    bottom, scaled, n_kmers = kmers.sketches_from_raw(raw, SKETCH, SCALE)
    np.testing.assert_array_equal(native["bottom"], bottom)
    np.testing.assert_array_equal(native["scaled"], scaled)
    assert native["n_kmers"] == n_kmers
    # and it is genuinely a different hash from the default
    default = sketch_fasta_native(path, K, SKETCH, SCALE)
    assert not np.array_equal(native["bottom"], default["bottom"])


@needs_native
def test_native_fast_path_matches_oracle(tmp_path):
    """A genome big enough that the scaled set holds >= sketch_size hashes
    takes the FracMinHash fast path (skips the full dedup) — both paths
    must take it identically: same bottom/scaled sketches, same estimated
    n_kmers."""
    import sys

    sys.path.insert(0, os.path.join(os.path.dirname(__file__), "genomes"))
    from generate import random_genome, write_fasta

    rng = np.random.default_rng(7)
    path = str(tmp_path / "big.fasta")
    write_fasta(path, random_genome(rng, 1_500_000), n_contigs=10, name="big")

    native = sketch_fasta_native(path, K, SKETCH, SCALE)
    oracle = _oracle(path)
    assert len(oracle["scaled"]) >= SKETCH, "fixture too small for the fast path"
    assert oracle["n_kmers"] == len(oracle["scaled"]) * SCALE  # estimated
    _assert_equal(native, oracle)


@needs_native
def test_without_the_library_the_primary_pack_is_numpys_and_the_tables_are_the_same(
    tmp_path, genome_paths, monkeypatch
):
    """ISSUE 40: `DREP_TPU_NO_NATIVE=1` sends the primary's pack through
    NumPy's lines, the record says so (`native_calls` 0, one thread), and
    the job's tables are the bytes the native kernel's job leaves."""
    import json

    from drep_tpu.workflows import compare_wrapper

    def job(name):
        wd = str(tmp_path / name)
        compare_wrapper(wd, genome_paths, skip_plots=True, processes=2)
        with open(os.path.join(wd, "log", "perf_counters.json")) as f:
            pack = json.load(f)["primary_pack"]
        tables = {}
        for table in ("Cdb", "Mdb", "Ndb"):
            with open(os.path.join(wd, "data_tables", table + ".csv"), "rb") as f:
                tables[table] = f.read()
        return pack, tables

    pack, tables = job("native")
    assert (pack["calls"], pack["native_calls"]) == (1, 1) and 1 <= pack["threads"] <= 2
    monkeypatch.setenv("DREP_TPU_NO_NATIVE", "1")
    pack_np, tables_np = job("numpy")
    assert (pack_np["calls"], pack_np["native_calls"], pack_np["threads"]) == (1, 0, 1)
    for how in ("native_calls", "threads"):  # what was ranked is the same
        del pack[how], pack_np[how]
    assert pack_np == pack
    assert tables_np == tables and all(tables.values())
