"""Genome ingest: FASTA files -> per-genome stats + MinHash/scaled sketches.

This is the host side of the sketching pipeline (SURVEY.md §7 step 2). It
plays the role of the reference's `mash sketch` fan-out plus
d_filter.calc_fasta_stats (reference mount empty; upstream layout), but
produces device-ready packed arrays instead of .msh files. Results are
cached in the work directory (``data/arrays/sketches.npz`` plus its
size-bounded ``sketches.<key>.NNNN.npz`` parts, workdir.py) keyed on the
sketching arguments, giving sub-stage resume like the reference's cached
sketch files under ``<wd>/data/``.

Parallelism: a process pool over genomes (numpy releases little GIL during
the pack matmul, so processes, not threads). The optional C++ ingest
(drep_tpu.native) replaces the per-genome numpy kernel transparently.

A job reads each FASTA once. `compare` sketches its whole Bdb
(:func:`sketch_genomes`). `dereplicate`'s filter needs every genome's
length, N50 and contigs before it knows what to keep, so it takes the pass
in two steps: :func:`read_genomes` over the whole input Bdb (a genome the
quality table already drops is read for its stats alone), then
:meth:`IngestPass.keep` of the genomes that passed — the cache, Gdb and the
`sketch` arguments hold those and no other (filter.py).
"""

from __future__ import annotations

import contextlib
import multiprocessing
import os
import time
from concurrent.futures import ProcessPoolExecutor
from dataclasses import dataclass

import numpy as np
import pandas as pd

from drep_tpu.errors import UserInputError
from drep_tpu.utils import envknobs
from drep_tpu.ops import kmers
from drep_tpu.sketch_worker import sketch_one as _sketch_one
from drep_tpu.utils.fasta import fasta_stats
from drep_tpu.utils.logger import get_logger
from drep_tpu.workdir import WorkDirectory

DEFAULT_SKETCH_SIZE = 1000  # reference: --MASH_sketch default 1000
DEFAULT_SCALE = 200  # FracMinHash scale for the jax_ani secondary


@dataclass
class GenomeSketches:
    names: list[str]
    # genome, length, N50, contigs, n_kmers. NB: n_kmers is the EXACT distinct
    # count for small genomes but the FracMinHash estimate |scaled|*scale on
    # the fast path — consumers (rep-ordering heuristics) tolerate the mix
    gdb: pd.DataFrame
    bottom: list[np.ndarray]  # uint64 bottom-k sketches (sorted)
    scaled: list[np.ndarray]  # uint64 scaled sketches (sorted, ragged)
    k: int
    sketch_size: int
    scale: int


def sketch_args_snapshot(
    genomes, k: int, sketch_size: int, scale: int, hash_name: str
) -> dict:
    """THE sketch-cache compatibility key. Anything that pre-populates a
    workdir sketch cache (benchmark/'s generators, tests) must build the
    snapshot through this helper so it can never drift from the check in
    :func:`sketch_genomes`."""
    return {
        "k": k, "sketch_size": sketch_size, "scale": scale,
        "hash": hash_name, "genomes": sorted(genomes),
    }


# genomes per ingest checkpoint shard: a mid-ingest kill at the 100k scale
# (hours of host sketching) must not restart from zero — finished genomes
# flush to shard files as they accumulate and a rerun resumes from them
INGEST_SHARD = 512


def _sketch_shard_meta(args_snapshot: dict) -> dict:
    """The shard-store meta for a given args snapshot — one constructor
    shared by sketch_genomes (which opens the store against it) and
    sketch_cache_will_hit (which probes it read-only), so the two can
    never drift."""
    from drep_tpu.utils.ckptmeta import content_fingerprint

    return {
        "kind": "sketch_shards",
        "k": args_snapshot["k"], "sketch_size": args_snapshot["sketch_size"],
        "scale": args_snapshot["scale"], "hash": args_snapshot["hash"],
        "genomes": content_fingerprint(args_snapshot["genomes"]),
    }


_SKETCH_SHARD_SUBDIR = os.path.join("data", "sketch_shards")


def _sketch_shard_dir(wd: WorkDirectory) -> str:
    """Shard-store path WITHOUT creating it (read-only probes); the
    writer side goes through wd.get_dir on the same subdir."""
    return os.path.join(wd.location, _SKETCH_SHARD_SUBDIR)


def sketch_cache_will_hit(
    wd: WorkDirectory | None,
    genomes,
    k: int,
    sketch_size: int,
    scale: int,
    hash_name: str,
) -> bool:
    """Will :func:`sketch_genomes` return without sketching any genome?

    True when the whole-run cache matches, OR when a valid shard store
    already covers every genome — a run killed after the last shard flush
    but before the whole-run cache was assembled rebuilds from shards in
    IO-bound seconds with zero sketching work. Read-only (never creates
    the shard dir or rewrites its meta). The cluster controller uses this
    to decide whether hiding the streaming compile behind ingest buys
    anything; sketch_genomes re-validates everything itself, so a wrong
    answer here costs only a skipped (or useless) warmup overlap, never
    correctness."""
    import glob

    from drep_tpu.utils.ckptmeta import checkpoint_meta_matches

    if wd is None:
        return False
    snapshot = sketch_args_snapshot(genomes, k, sketch_size, scale, hash_name)
    if wd.has_arrays("sketches") and wd.arguments_match("sketch", snapshot):
        # mirror sketch_genomes' staleness rule: a cache carrying a
        # zero-kmer genome (written before validation existed) gets
        # dropped and re-sketched — that run wants the warmup, so fall
        # through to the shard probe instead of claiming a hit
        try:
            if not (wd.get_db("Gdb")["n_kmers"] == 0).any():
                return True
        except Exception:
            pass  # unreadable Gdb: let the shard probe decide
    shard_dir = _sketch_shard_dir(wd)
    try:
        if not checkpoint_meta_matches(shard_dir, _sketch_shard_meta(snapshot)):
            return False
    except OSError:
        # transient budget exhausted reading the meta: this probe is
        # advisory (a wrong answer only costs the warmup overlap) — the
        # brownout error belongs to sketch_genomes' own open, not here
        return False
    covered: set[str] = set()
    for f in glob.glob(os.path.join(shard_dir, "*.npz")):
        try:
            # np.load on an npz reads only the members touched — names +
            # n_kmers, not the sketch arrays — so this stays cheap at 100k
            with np.load(f, allow_pickle=False) as z:
                names = [str(x) for x in z["names"]]
                n_kmers = z["n_kmers"]
        except Exception:
            return False  # corrupt shard: its genomes re-sketch -> warmup pays
        # zero-kmer entries are dropped on resume (see sketch_genomes);
        # a shard that only covers a genome with n_kmers==0 does not cover it
        covered.update(g for g, n in zip(names, n_kmers) if int(n) > 0)
    return covered >= set(snapshot["genomes"])

_SHARD_SCALARS = ("length", "N50", "contigs", "n_kmers")


def _pack_ragged(arrs: list[np.ndarray]) -> tuple[np.ndarray, np.ndarray]:
    """Ragged uint64 arrays -> (flat concat, int64 offsets) — the ONE
    serialization layout shared by the whole-run sketch cache and the
    mid-run shard store (so the two can never drift)."""
    flat = np.concatenate(arrs) if arrs else np.empty(0, np.uint64)
    return flat, np.cumsum([0] + [len(a) for a in arrs]).astype(np.int64)


def _unpack_ragged(flat: np.ndarray, offs: np.ndarray, n: int) -> list[np.ndarray]:
    return [flat[offs[i] : offs[i + 1]] for i in range(n)]


# the genome-index store (drep_tpu/index/store.py) serializes its sketch
# shards in THE SAME ragged layout as the workdir cache and the ingest
# shard store — public aliases so it cannot drift off the recipe
pack_ragged = _pack_ragged
unpack_ragged = _unpack_ragged


def _note_ingest_path() -> str:
    """Record which per-genome kernel sketched this run's genomes — the
    native C++ library or the numpy path (native/__init__.py) — in the
    run record (perf_counters.json note ``ingest_path``), and return it.
    The pool workers load the very library this process loads, so asking
    here answers for them."""
    from drep_tpu import native
    from drep_tpu.utils.profiling import counters

    path = "native" if native.get_library() is not None else "numpy"
    counters.set_note("ingest_path", path)
    return path


def sketch_paths(
    bdb: pd.DataFrame,
    k: int,
    sketch_size: int,
    scale: int,
    hash_name: str,
    processes: int = 1,
) -> dict[str, dict]:
    """Sketch a Bdb's genomes with NO workdir/cache/shard machinery —
    the incremental index's ingest path (drep_tpu/index/update.py), where
    durability lives in the index store itself, not in a workdir. Returns
    {name: {length, N50, contigs, n_kmers, bottom, scaled}} using the
    exact per-genome kernel (sketch_worker.sketch_one) the pipeline runs,
    so an index update's sketches are bit-identical to what a from-scratch
    rerun would ingest. Raises UserInputError on unparseable inputs."""
    jobs = [
        (row.genome, row.location, k, sketch_size, scale, hash_name)
        for row in bdb.itertuples()
    ]
    results: dict[str, dict] = {}
    if processes > 1 and len(jobs) > 1:
        ctx = multiprocessing.get_context("spawn")  # same rationale as sketch_genomes
        with ProcessPoolExecutor(max_workers=processes, mp_context=ctx) as pool:
            for name, res in pool.map(_sketch_one, jobs):
                results[name] = res
    else:
        for job in jobs:
            name, res = _sketch_one(job)
            results[name] = res
    _note_ingest_path()
    bad = sorted(g for g, r in results.items() if r["n_kmers"] == 0)
    if bad:
        shown = ", ".join(bad[:10]) + (" ..." if len(bad) > 10 else "")
        raise UserInputError(
            f"no FASTA records with valid nucleotide {k}-mers in {len(bad)} "
            f"input file(s) (not FASTA, empty, or shorter than k): {shown}"
        )
    return results


def _save_sketch_shard(path: str, batch: dict[str, dict]) -> None:
    from drep_tpu.utils.ckptmeta import atomic_savez

    names = list(batch)
    payload: dict[str, np.ndarray] = {
        "names": np.array(names, dtype=object).astype(str)
    }
    for key in _SHARD_SCALARS:
        payload[key] = np.array([batch[g][key] for g in names], dtype=np.int64)
    for key in ("bottom", "scaled"):
        payload[key], payload[f"{key}_offsets"] = _pack_ragged(
            [batch[g][key] for g in names]
        )
    # the durable savez: in-memory serialize, in-band __crc__, atomic tmp
    # whose suffix does NOT end in .npz (a crash artifact can never be
    # picked up by the resume glob as a corrupt-looking shard), transient
    # I/O retries — one recipe with every other shard store. Stored, not
    # deflated: the payload is uniform 64-bit hashes, as in `_save`, and
    # zlib took six times the seconds for 7% of the bytes
    atomic_savez(path, compressed=False, **payload)


def _load_sketch_shard(path: str) -> dict[str, dict]:
    from drep_tpu.utils.durableio import load_npz_checked

    out: dict[str, dict] = {}
    z = load_npz_checked(path, what="sketch shard")
    names = [str(x) for x in z["names"]]
    scalars = {key: z[key] for key in _SHARD_SCALARS}
    bottom = _unpack_ragged(z["bottom"], z["bottom_offsets"], len(names))
    scaled = _unpack_ragged(z["scaled"], z["scaled_offsets"], len(names))
    for i, g in enumerate(names):
        out[g] = {
            **{key: int(scalars[key][i]) for key in _SHARD_SCALARS},
            "bottom": bottom[i].copy(),
            "scaled": scaled[i].copy(),
        }
    return out


_INGEST_BARRIER_ENV = "DREP_TPU_INGEST_BARRIER_S"
_INGEST_BARRIER_POLL_S = 0.2


def _barrier_deadline() -> float:
    """Monotonic deadline for the sharded-ingest coordination waits (one
    env knob, one default, shared by the assembly barrier and the
    marker wait so the two cannot drift)."""
    return time.monotonic() + envknobs.env_float(_INGEST_BARRIER_ENV)


def sketch_genomes(
    bdb: pd.DataFrame,
    k: int = kmers.DEFAULT_K,
    sketch_size: int = DEFAULT_SKETCH_SIZE,
    scale: int = DEFAULT_SCALE,
    processes: int = 1,
    wd: WorkDirectory | None = None,
    hash_name: str = "splitmix64",
) -> GenomeSketches:
    """Sketch every genome in Bdb; cache/restore via the work directory
    (whole-run cache, plus mid-run shard checkpoints every INGEST_SHARD
    genomes so a killed ingest resumes where it stopped)."""
    logger = get_logger()
    args_snapshot = sketch_args_snapshot(bdb["genome"], k, sketch_size, scale, hash_name)

    if wd is not None and wd.has_arrays("sketches") and wd.arguments_match("sketch", args_snapshot):
        cached = _load(wd, k, sketch_size, scale, processes)
        if not (cached.gdb["n_kmers"] == 0).any():
            logger.info("loading cached sketches from workdir")
            return cached
        # a cache written before zero-kmer validation existed can carry an
        # unparseable genome; the args snapshot keys on NAMES, so a fixed
        # file would never be re-read — drop the cache and re-sketch
        logger.warning(
            "ingest: cached sketches contain zero-kmer genomes (stale cache "
            "from an unvalidated run?) — recomputing"
        )
    return read_genomes(bdb, k, sketch_size, scale, processes, wd, hash_name).keep(bdb["genome"])


def _results_of(gs: GenomeSketches) -> dict[str, dict]:
    """A whole-run cache as the per-genome results it was assembled from."""
    scalars = {key: gs.gdb[key].to_numpy() for key in _SHARD_SCALARS}
    return {
        g: {**{key: int(scalars[key][i]) for key in _SHARD_SCALARS},
            "bottom": gs.bottom[i], "scaled": gs.scaled[i]}
        for i, g in enumerate(gs.names)
    }


def _scalars_frame(names: list[str], results: dict[str, dict], keys) -> pd.DataFrame:
    """genome and the per-genome integers `keys`, a row a name."""
    return pd.DataFrame(
        {"genome": names, **{key: [results[g][key] for g in names] for key in keys}}
    )


def _unparseable(names: list[str], k: int) -> UserInputError:
    shown = ", ".join(names[:10]) + (" ..." if len(names) > 10 else "")
    return UserInputError(
        f"no FASTA records with valid nucleotide {k}-mers in {len(names)} "
        f"input file(s) (not FASTA, empty, or shorter than k): {shown}"
    )


def read_genomes(
    bdb: pd.DataFrame,
    k: int,
    sketch_size: int,
    scale: int,
    processes: int = 1,
    wd: WorkDirectory | None = None,
    hash_name: str = "splitmix64",
    stats_only=None,
) -> IngestPass:
    """One pass of the pool over every genome of `bdb`: each file opened and
    parsed once, by `sketch_one`. The caller then says which genomes it keeps
    (:meth:`IngestPass.keep`), having seen every genome's length, N50 and
    contigs (:attr:`IngestPass.stats`).

    `stats_only` is the filter's (filter.py): the names it already knows it
    will drop, read for their stats alone — no k-mer hashed, no sketch, never
    a shard entry. None from every other caller, which keeps all it reads.
    The shard store is keyed on the whole list given here; a rerun resumes
    the sketched genomes from it and reads the stats-only ones again. In a
    multi-process pod every genome is sketched: the shard store is the only
    channel between the processes and holds sketched genomes alone."""
    import glob
    import uuid

    logger = get_logger()
    args_snapshot = sketch_args_snapshot(bdb["genome"], k, sketch_size, scale, hash_name)
    results: dict[str, dict] = {}
    shard_dir = None
    resume_loaded: set[str] = set()  # shard paths the resume glob consumed
    if wd is not None:
        from drep_tpu.utils.ckptmeta import open_checkpoint_dir

        shard_dir = wd.get_dir(_SKETCH_SHARD_SUBDIR)
        if open_checkpoint_dir(
            shard_dir, _sketch_shard_meta(args_snapshot), clear_suffixes=(".npz",)
        ):
            for f in sorted(glob.glob(os.path.join(shard_dir, "*.npz"))):
                try:
                    shard = _load_sketch_shard(f)
                    resume_loaded.add(f)
                except FileNotFoundError:
                    # a peer healed (removed) it between our glob and the
                    # read — merely missing, NOT corruption: counting it
                    # would book phantom heals across ingest peers
                    continue
                except OSError:
                    # transient retry budget exhausted: the shard may be
                    # intact — re-sketch its genomes WITHOUT deleting it
                    # or booking a heal (durableio.load_npz_or_none's
                    # brownout invariant; the re-sketch rewrites in place)
                    logger.warning("ingest: unreadable sketch shard %s — recomputing its genomes", f)
                    continue
                except Exception:
                    from drep_tpu.utils.durableio import quarantine_corrupt

                    logger.warning("ingest: corrupt sketch shard %s — recomputing its genomes", f)
                    quarantine_corrupt(f)  # counted heal; re-sketch rewrites
                    continue
                # drop zero-kmer entries written before validation existed:
                # resuming one by name would re-raise the input error even
                # after the user fixed the file (shard meta keys on names)
                results.update(
                    {g: r for g, r in shard.items() if r["n_kmers"] > 0}
                )
            if results:
                logger.info(
                    "ingest: resumed %d/%d sketched genomes from shards",
                    len(results), len(bdb),
                )

    # per-process sharded ingest (SURVEY.md §7 hard part (f)): under an
    # initialized jax.distributed runtime each process sketches only its
    # stripe of the work into the shared shard dir (writes are atomic —
    # tmp suffix + os.replace), then assembles the full set by polling
    # the dir until every genome is covered. The barrier is DATA
    # COMPLETENESS, not marker files: stale state from a killed run can
    # delay it only until the owning process re-sketches, never fake it.
    # jax.process_count() is safe here: open_checkpoint_dir above already
    # initialized the backend on every wd path.
    nproc, pid = 1, 0
    if shard_dir is not None:
        import jax

        nproc, pid = jax.process_count(), jax.process_index()
    # the shard dir is the only channel between a pod's processes and holds
    # sketched genomes alone: there every genome is sketched
    read_for_stats = frozenset(stats_only or ()) if nproc == 1 else frozenset()
    jobs = [
        (row.genome, row.location) if row.genome in read_for_stats
        else (row.genome, row.location, k, sketch_size, scale, hash_name)
        for row in bdb.itertuples()
    ]
    if nproc > 1:
        # stripe ownership keys on the GLOBAL job index, never on the
        # locally-observed resume state: two processes whose resume globs
        # saw different shard sets would otherwise interleave DIFFERENT
        # todo lists, leaving some genome in nobody's stripe and every
        # process stuck in the barrier below
        todo = [
            j for i, j in enumerate(jobs)
            if i % nproc == pid and j[0] not in results
        ]
        # best-effort hygiene (pid 0, right after the synchronized
        # checkpoint-dir open): a previous killed run's assembly/poison
        # markers must not satisfy this run's marker wait or fail its
        # barrier instantly — the cache-first ordering and tolerant
        # marker writes below keep any residual race benign, this just
        # removes the common case
        if pid == 0:
            for pat in ("assembled_*.done", "ingest_error_*.json"):
                for f in glob.glob(os.path.join(shard_dir, pat)):
                    with contextlib.suppress(OSError):
                        os.remove(f)
    else:
        todo = [j for j in jobs if j[0] not in results]
    my_shard_files: set[str] = set()  # shards THIS process wrote (skip re-reading)
    pending: dict[str, dict] = {}
    read: dict[str, dict] = {}  # what THIS run read: the record's `ingest` counter
    from drep_tpu.utils.profiling import counters

    def flush(force: bool = False) -> None:
        if shard_dir is not None and pending and (force or len(pending) >= INGEST_SHARD):
            path = os.path.join(shard_dir, f"shard_{uuid.uuid4().hex}.npz")
            with counters.span("ingest/shard_flush", genomes=len(pending)) as span:
                _save_sketch_shard(path, pending)
                span.note(bytes=os.path.getsize(path))
            my_shard_files.add(path)  # already in `results`: barrier skips it
            pending.clear()

    def collect(name: str, res: dict) -> None:
        results[name] = read[name] = res
        # never checkpoint an unparseable result: a persisted zero-kmer
        # shard would be resumed by name on the next run and keep raising
        # the validation error even after the user fixes the file. A genome
        # read for its stats alone hashed nothing and is such a result
        if res["n_kmers"] > 0:
            pending[name] = res
            flush()

    # `ingest/sketch` is the main thread's wall from the pool's spawn to its
    # last result and the last shard flush; the workers open no spans, each
    # result carries its own seconds (the record's `ingest` counter)
    workers = min(processes, len(todo)) if processes > 1 and len(todo) > 1 else 1
    with counters.span("ingest/sketch", genomes=len(todo), workers=workers):
        if workers > 1:
            # spawn, not fork: by the time ingest runs inside a pipeline the
            # JAX backend is usually initialized and multithreaded, and a
            # forked child can deadlock on locks held at fork time (CPython
            # itself warns on fork-after-threads). The worker module chain is
            # deliberately jax-free and lean (sketch_worker.py), so spawn
            # startup stays ~0.7 s/worker.
            ctx = multiprocessing.get_context("spawn")
            with ProcessPoolExecutor(max_workers=workers, mp_context=ctx) as pool:
                with counters.span("ingest/pool_start"):  # spawn to the first result
                    arriving = pool.map(_sketch_one, todo)
                    first = next(arriving)
                collect(*first)
                for name, res in arriving:
                    collect(name, res)
        else:
            for job in todo:
                collect(*_sketch_one(job))
        flush(force=True)
    path = _note_ingest_path() if todo else None

    if nproc > 1:
        # unparseable inputs in THIS stripe fail the whole pod fast: a
        # poison marker carries the real error to every peer's barrier
        # (zero-kmer results are never checkpointed, so without it peers
        # would stall their full timeout on a genome that never arrives)
        bad = sorted(g for g, r in results.items() if r["n_kmers"] == 0)
        if bad:
            from drep_tpu.utils.durableio import atomic_write_json

            with contextlib.suppress(OSError):
                atomic_write_json(
                    os.path.join(shard_dir, f"ingest_error_{pid}.json"),
                    {"pid": pid, "genomes": bad[:10], "n": len(bad)},
                )
            raise _unparseable(bad, k)

        # assemble peers' stripes: re-glob until all genomes are covered,
        # or until the whole-run cache appears (a peer that finished
        # assembly first may have written it and reclaimed the shards).
        # Own + resume-loaded shard files are pre-seen: their genomes are
        # already in `results`, and reading them again would duplicate
        # this process's share of the pod-wide shard I/O for nothing.
        # The timeout is PROGRESS-based: any new shard resets it — stripe
        # skew (one process owning slower genomes) is normal at scale and
        # must never read as a dead peer while shards keep appearing.
        deadline = _barrier_deadline()
        seen_files: set[str] = set(my_shard_files) | resume_loaded
        need = {j[0] for j in jobs}
        while need - set(results):
            progressed = False
            for f in sorted(glob.glob(os.path.join(shard_dir, "*.npz"))):
                if f in seen_files:
                    continue
                try:
                    shard = _load_sketch_shard(f)
                except Exception:
                    continue  # peer mid-write artifact: retry next pass
                seen_files.add(f)
                progressed = True
                results.update({g: r for g, r in shard.items() if r["n_kmers"] > 0})
            if progressed:
                deadline = _barrier_deadline()
            if not (need - set(results)):
                break
            for f in glob.glob(os.path.join(shard_dir, "ingest_error_*.json")):
                from drep_tpu.utils.durableio import read_json_checked

                try:
                    info = read_json_checked(f, what="ingest poison marker")
                except Exception:
                    continue  # torn/rotted marker: the data barrier decides
                shown = ", ".join(info.get("genomes", []))
                raise UserInputError(
                    f"ingest peer process {info.get('pid')} reported "
                    f"{info.get('n')} unparseable input file(s) "
                    f"(not FASTA, empty, or shorter than k): {shown}"
                )
            if wd.has_arrays("sketches") and wd.arguments_match("sketch", args_snapshot):
                cached = _load(wd, k, sketch_size, scale, processes)
                if not (cached.gdb["n_kmers"] == 0).any():
                    logger.info(
                        "ingest: peer assembled the whole-run cache first — using it"
                    )
                    # :meth:`IngestPass.keep` still signals process 0: its
                    # marker wait may be pending, and an unsignaled exit
                    # would leak the superseded shard store forever (no
                    # later run reopens it past the whole-run cache hit)
                    results.update(_results_of(cached))
                    break
            if time.monotonic() > deadline:
                missing = sorted(need - set(results))[:10]
                raise RuntimeError(
                    f"sharded ingest barrier timed out: {len(need - set(results))} "
                    f"genomes never appeared in {shard_dir} for "
                    f"{envknobs.env_float(_INGEST_BARRIER_ENV):.0f}s with no new "
                    f"shards (first missing: {missing}). A peer process likely "
                    "died; raise the window via DREP_TPU_INGEST_BARRIER_S if its "
                    "per-shard gaps are legitimately longer."
                )
            time.sleep(_INGEST_BARRIER_POLL_S)

    return IngestPass(
        names=list(bdb["genome"]), results=results, read=read, workers=workers, path=path,
        for_filter=stats_only is not None, k=k, sketch_size=sketch_size, scale=scale,
        hash_name=hash_name, wd=wd, shard_dir=shard_dir, nproc=nproc, pid=pid,
    )


@dataclass
class IngestPass:
    """What :func:`read_genomes` read, until the caller says what it keeps."""

    names: list[str]  # every genome of the Bdb the pool was given, in its order
    results: dict[str, dict]  # per genome: `sketch_one`'s result, or a shard's entry
    read: dict[str, dict]  # the results THIS run read (the rest came from shards)
    workers: int
    path: str | None  # the kernel that served, None where nothing was read
    for_filter: bool  # the filter's pass: the `ingest` counter says what it did beside the kept
    k: int
    sketch_size: int
    scale: int
    hash_name: str
    wd: WorkDirectory | None
    shard_dir: str | None
    nproc: int
    pid: int

    @property
    def stats(self) -> pd.DataFrame:
        """genome, length, N50, contigs of every genome read: the filter's
        `genomeInformation`."""
        return _scalars_frame(self.names, self.results, ("length", "N50", "contigs"))

    def keep(self, genomes) -> GenomeSketches:
        """The sketches of `genomes` (all of them sketched, in this order),
        written as the workdir's sketch cache, which holds these genomes and
        no other; what was read beside them is thrown away. Raises
        UserInputError for a kept genome with no valid k-mer, and books the
        record's `ingest` counter."""
        import shutil

        from drep_tpu.utils.profiling import counters

        names = list(genomes)
        results, wd, shard_dir = self.results, self.wd, self.shard_dir
        unparsed = [g for g in names if results[g]["n_kmers"] == 0]
        if unparsed:
            raise _unparseable(unparsed, self.k)
        if self.read:
            counters.add_ingest(self.read, set(names), self.workers, self.path, self.for_filter)
        out = GenomeSketches(
            names=names,
            gdb=_scalars_frame(names, results, _SHARD_SCALARS),
            bottom=[results[g]["bottom"] for g in names],
            scaled=[results[g]["scaled"] for g in names],
            k=self.k,
            sketch_size=self.sketch_size,
            scale=self.scale,
        )
        # the caller may hold this pass to the job's end: what was not kept goes now
        self.results = self.read = {}
        if wd is None:
            return out
        if self.nproc > 1 and self.pid != 0:
            # signal assembly-complete and leave the cache write + shard
            # reclamation to process 0: concurrent identical cache writes
            # are not atomic, and reclaiming shards a peer still reads
            # would strand its barrier (it recovers via the cache, but
            # only after process 0 wrote it — ordering below). Tolerant
            # write: if a stale-marker race let process 0 reclaim the dir
            # already, the cache necessarily exists (written BEFORE the
            # rmtree) and this process's result is complete — the signal
            # is moot, not an error.
            from drep_tpu.utils.ckptmeta import atomic_write_bytes

            with contextlib.suppress(OSError):
                atomic_write_bytes(
                    os.path.join(shard_dir, f"assembled_{self.pid}.done"), b""
                )
            return out
        peers_done = True
        if self.nproc > 1:
            # wait (bounded) for peers to finish assembling; cache-first
            # ordering below makes a timeout or stale marker harmless —
            # a peer still polling finds the cache on its next pass
            deadline = _barrier_deadline()
            peers = [
                os.path.join(shard_dir, f"assembled_{p}.done")
                for p in range(1, self.nproc)
            ]
            peers_done = all(os.path.exists(f) for f in peers)
            while not peers_done and time.monotonic() < deadline:
                time.sleep(_INGEST_BARRIER_POLL_S)
                peers_done = all(os.path.exists(f) for f in peers)
        with counters.span("ingest/cache_save", genomes=len(names)):
            _save(wd, out)
            wd.store_arguments(
                "sketch",
                sketch_args_snapshot(names, self.k, self.sketch_size, self.scale, self.hash_name),
            )
            # the assembled cache supersedes the shards — drop them rather
            # than double the on-disk footprint (~16 GB at 100k genomes)
            if peers_done:
                shutil.rmtree(shard_dir, ignore_errors=True)
        return out


def _save(wd: WorkDirectory, gs: GenomeSketches) -> None:
    bottom, bottom_offsets = _pack_ragged(gs.bottom)
    scaled, scaled_offsets = _pack_ragged(gs.scaled)
    wd.store_arrays(
        "sketches",
        # uniform 64-bit hashes are incompressible: zlib here was pure CPU
        # on the save AND on the cache-hit load inside every timed resume
        compressed=False,
        bottom=bottom,
        bottom_offsets=bottom_offsets,
        scaled=scaled,
        scaled_offsets=scaled_offsets,
        names=np.array(gs.names, dtype=object).astype(str),
    )
    wd.store_db(gs.gdb, "Gdb")


def _load(wd: WorkDirectory, k: int, sketch_size: int, scale: int, processes: int = 1) -> GenomeSketches:
    """The whole-run cache :func:`_save` wrote, its parts read on up to
    `processes` threads; the record's `sketch_cache_read` says how."""
    from drep_tpu.utils.profiling import counters

    arrs, read = wd.read_arrays("sketches", workers=processes)
    counters.add_sketch_cache_read(**read)
    names = [str(x) for x in arrs["names"]]
    bottom = _unpack_ragged(arrs["bottom"], arrs["bottom_offsets"], len(names))
    scaled = _unpack_ragged(arrs["scaled"], arrs["scaled_offsets"], len(names))
    return GenomeSketches(
        names=names,
        gdb=wd.get_db("Gdb"),
        bottom=bottom,
        scaled=scaled,
        k=k,
        sketch_size=sketch_size,
        scale=scale,
    )


def make_bdb(genome_paths: list[str]) -> pd.DataFrame:
    """Genome list -> Bdb (genome name = basename, reference convention).

    Fails fast on unreadable paths: a missing file must surface as one
    clean error naming it, before hours of sketching — not as a raw
    traceback from whichever worker hits it first."""
    names = [os.path.basename(p) for p in genome_paths]
    if len(set(names)) != len(names):
        raise UserInputError("duplicate genome basenames in input list")
    missing = [p for p in genome_paths if not os.path.isfile(p)]
    if missing:
        shown = ", ".join(missing[:10]) + (" ..." if len(missing) > 10 else "")
        raise UserInputError(
            f"{len(missing)} genome file(s) do not exist or are not files: {shown}"
        )
    return pd.DataFrame({"genome": names, "location": [os.path.abspath(p) for p in genome_paths]})


def genome_info_from_stats(paths: list[str]) -> pd.DataFrame:
    """Convenience: length/N50 stats table for a list of FASTAs (no quality)."""
    return pd.DataFrame([fasta_stats(p).__dict__ for p in paths])
