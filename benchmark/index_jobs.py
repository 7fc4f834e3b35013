"""Traffic of kind `index_jobs`: whole `index update` jobs of the program's
CLI, back to back, each growing a fresh copy of one pristine index by the same
batch (generators/planted_index.py).

Set-up plants the index's genomes as a work directory, runs the first
`compare` and `index build --work_directory` through ``controller.main``
(`setup_argv`), writes the batch as the program's sketch hand-off under the
parameters the built index pins, takes the sha256 of generation 0's payloads,
and runs one untimed warm-up update. The window is ``batch_jobs.run``'s,
mirrored (that one asks for a ``Cdb.csv``): one job at a time, closed loop,
the first job traced under ``--trace 1``, another job only if it fits. A job
is one call of ``controller.main(["index", "update", <copy>, "--params_file",
<batch>, "-p", "6"])`` from the call to its return, on a copy whose shards are
hard links (an update appends files and replaces the manifest by a rename:
the pristine bytes are never written) and whose `log/` is a real copy.

A job whose own record (``<index>/log/perf_counters.json``) shows a hiding
counter (``batch_jobs.record_faults``), lacks a counter or a route the cell
names, or whose `index` section reads another `generation` than 1 or another
`admitted` than the batch counts as failed.

`correct` comes from ``reference_index`` (the union clustered from scratch, at
full size, no sample) against the LAST job's directory read back from disk,
never from the program's memory: ``load_index(heal=False)`` has to read every
shard and part back, checked (guarantee b); both partitions exact; every
winner the best of its reference cluster; the new edges exactly the
reference's pairs that reach the batch and their distances under the limit;
every genome's score under the limit; the record's recomputed clusters,
reclustered components and recomputed members exactly the reference's count
of union clusters whose member set changed; generation 0's payloads byte for
byte what they were (guarantee c); every job's state equal to the last's.
"""

from __future__ import annotations

import hashlib
import json
import os
import shutil
import statistics
import time

import numpy as np

from benchmark import batch_jobs, check, tracered
from benchmark import reference as ref
from benchmark import reference_index as ri
from benchmark.greedy_jobs import counter_faults, counters_unknown, route_faults
from benchmark.species_jobs import _worst

N50 = 50_000  # every planted genome's (planted_release.write_workdir)


# ---- a job -------------------------------------------------------------------------------


def _main(argv: list[str]) -> str | None:
    """One call of the CLI's own function; why it failed, or None."""
    from drep_tpu import controller

    try:
        controller.main(argv)
    except SystemExit as e:  # the CLI's way of refusing
        if e.code not in (0, None):
            return f"exit code {e.code}"
    except Exception as e:  # noqa: BLE001 — a failed job is counted, not fatal
        return f"{type(e).__name__}: {e}"
    return None


def copy_index(pristine: str, job_dir: str) -> None:
    """A fresh copy of the pristine index: hard links for the payloads, real
    copies of what a job appends to (`log/`)."""
    shutil.rmtree(job_dir, ignore_errors=True)
    shutil.copytree(pristine, job_dir, copy_function=os.link,
                    ignore=lambda d, names: ["log"] if d == pristine else [])
    shutil.copytree(os.path.join(pristine, "log"), os.path.join(job_dir, "log"))


def run_job(argv_template: list[str], pristine: str, job_dir: str, batch: str) -> dict:
    """One job. Returns {"wall_s", "index", "error"}; its record and its
    store are read by the caller, outside the timed span."""
    copy_index(pristine, job_dir)
    argv = [a.replace("{index}", job_dir).replace("{batch}", batch) for a in argv_template]
    t0 = time.monotonic()
    error = _main(argv)
    return {"wall_s": time.monotonic() - t0, "index": job_dir, "error": error}


def _read_record(job: dict) -> None:
    with open(os.path.join(job["index"], "log", "perf_counters.json")) as f:
        job["record"] = json.load(f)


def index_faults(rec: dict, k_batch: int) -> list[str]:
    """Why the record's `index` section says this was not the cell's job."""
    did = rec.get("index") or {}
    bad = []
    if did.get("generation") != 1:
        bad.append(f"the job published generation {did.get('generation')!r}, the cell means 1")
    if did.get("admitted") != k_batch:
        bad.append(f"the job admitted {did.get('admitted')!r} genomes, the batch holds {k_batch}")
    return bad


# ---- the store, read back ----------------------------------------------------------------


def payload_digests(index_dir: str, generation: int = 0) -> dict[str, str]:
    """sha256 of every sketch and edge file of `generation`: heads and parts."""
    out = {}
    tag = f"_g{generation:06d}."
    for sub in ("sketches", "edges"):
        for f in sorted(os.listdir(os.path.join(index_dir, sub))):
            if tag in f:
                h = hashlib.sha256()
                with open(os.path.join(index_dir, sub, f), "rb") as fh:
                    for piece in iter(lambda: fh.read(1 << 22), b""):
                        h.update(piece)
                out[os.path.join(sub, f)] = h.hexdigest()
    return out


def read_answers(index_dir: str, names: list[str]) -> dict:
    """What an update left on disk, over the planted genome numbers: a fresh
    ``load_index(heal=False)`` of the directory (every shard and part read
    back, checked; it raises on a missing or torn one)."""
    from drep_tpu.index.store import load_index

    idx = load_index(index_dir, heal=False)
    number = {g: i for i, g in enumerate(names)}
    rows = np.array([number[g] for g in idx.names], np.int64)
    back = np.empty(len(names), np.int64)
    back[rows] = np.arange(len(rows))  # planted number -> the store's row
    primary = np.asarray(idx.primary)[back]
    secondary = np.unique(np.stack([primary, np.asarray(idx.suffix)[back]]), axis=1,
                          return_inverse=True)[1].reshape(-1)
    ii, jj, dd = idx.edges
    return {"n": idx.n, "generation": idx.generation, "row": back,
            "primary": primary, "secondary": secondary, "score": np.asarray(idx.score)[back],
            "winners": np.array(sorted(number[g] for g in idx.winners["genome"]), np.int64),
            "winner_clusters": len(idx.winners),
            "edges": {"i": rows[ii], "j": rows[jj], "dist": np.asarray(dd, np.float64)},
            "admitted": np.asarray(idx.admitted)[back]}


def state_digest(index_dir: str) -> str:
    """The derived state a job published, whatever its clusters are numbered:
    the secondary partition and the winners, from the state payload alone."""
    from drep_tpu.index.store import IndexStore, read_payload

    store = IndexStore(index_dir)
    z = read_payload(store.abspath(store.read_manifest()["state"]), "state")
    names = [str(x) for x in z["names"]]
    groups: dict = {}
    for g, p, s in zip(names, z["primary"], z["suffix"]):
        groups.setdefault((int(p), int(s)), []).append(g)
    canon = sorted(",".join(sorted(c)) for c in groups.values())
    winners = sorted(str(x) for x in z["winner_genome"])
    return hashlib.sha1(("\n".join(canon) + "\n#\n" + "\n".join(winners)).encode()).hexdigest()[:16]


# ---- the comparison ----------------------------------------------------------------------


def reference_of(data, params: dict, lower_precision: bool = False) -> dict:
    """The union from scratch, the index alone from scratch (its primary
    partition only), and the work a sound update does between the two."""
    u = data.union
    n50 = np.full(len(u.names), N50, np.int64)
    want = ri.from_scratch(u.bottom, u.scaled, u.names, u.length, n50, params, lower_precision)
    old_primary = ri.rg.primary(u.bottom[:data.n_old], int(params["sketch_size"]),
                                int(params["kmer_size"]), 1.0 - params["P_ani"], lower_precision)[0]
    want["work"] = ri.expected_work(want["primary"], old_primary)
    return want


def check_index(got: dict, record_index: dict, data, params: dict, want_of: list[str], limits: dict,
                expected: dict) -> list[dict]:
    """One job's answers (``read_answers``) and its record's `index` section
    against the reference (``reference_of``)."""
    u, n, n_old = data.union, len(data.union.names), data.n_old
    out = []
    if "primary" in want_of:
        out.append(check.comparison(
            "genomes in a primary cluster the reference does not have",
            ref.partition_mismatch(ref.partition_of(got["primary"]),
                                   ref.partition_of(expected["primary"])), 0))
        out.append(check.comparison(
            "genomes whose reference primary cluster is not the planted one",
            ref.partition_mismatch(ref.partition_of(expected["primary"]),
                                   ref.partition_of(u.primary_labels)), 0))
    if "secondary" in want_of:
        out.append(check.comparison(
            "genomes in a secondary cluster the reference does not have",
            ref.partition_mismatch(ref.partition_of(got["secondary"]),
                                   ref.partition_of(expected["secondary"])), 0))
        out.append(check.comparison(
            "genomes whose reference secondary cluster is not the planted group",
            ref.partition_mismatch(ref.partition_of(expected["secondary"]),
                                   ref.partition_of(u.labels)), 0))
    if "winners" in want_of:
        # a winner is wrong when the reference scores it under the best of its
        # reference cluster by more than the score limit; each cluster has one
        best = np.full(int(expected["secondary"].max()) + 1, -np.inf)
        np.maximum.at(best, expected["secondary"], expected["score"])
        w = got["winners"]
        short = expected["score"][w] < best[expected["secondary"][w]] - limits["score"]
        clusters = len(np.unique(expected["secondary"]))
        unserved = clusters - len(np.unique(expected["secondary"][w[~short]]))
        exact = len(np.setxor1d(w, expected["winners"])) // 2
        out.append(check.comparison(
            f"secondary clusters of {clusters} whose winner is not their best genome, or that have "
            f"none ({exact} winner(s) are not the reference's own pick among equals)",
            int(short.sum()) + unserved + abs(got["winner_clusters"] - clusters), 0))
    if "edges" in want_of:
        e, m = got["edges"], expected["mash"]
        lo, hi = np.minimum(e["i"], e["j"]), np.maximum(e["i"], e["j"])
        # the store's edge (ii < jj by its own rows) reaches the batch iff its later row does
        new = np.maximum(got["row"][e["i"]], got["row"][e["j"]]) >= n_old
        keys, known = (lo * n + hi)[new], m["i"] * n + m["j"]
        by_key = np.argsort(known)
        known, known_dist = known[by_key], m["dist"][by_key]
        reach = (np.maximum(m["i"], m["j"])[by_key] >= n_old)
        must = known[reach & (known_dist <= params["retention_dist"])]
        out.append(check.comparison(
            f"new edges missing, or present and not in the reference (it has {len(must)} inside "
            f"the retention bound)",
            len(np.setdiff1d(must, keys)) + len(np.setdiff1d(keys, known[reach])), 0))
        both = np.isin(keys, known)
        out.append(check.comparison(
            f"largest Mash distance error over {int(both.sum())} new edges",
            _worst(e["dist"][new][both], known_dist[np.searchsorted(known, keys[both])]),
            limits["mash_dist"]))
        old_keys = np.sort((lo * n + hi)[~new])
        old_known = known[~reach & (known_dist <= params["retention_dist"])]
        out.append(check.comparison(
            "generation 0's edges missing from the store read back",
            len(np.setdiff1d(old_known, old_keys)), 0))
    if "score" in want_of:
        out.append(check.comparison(
            f"largest score error over {n} genomes", _worst(got["score"], expected["score"]),
            limits["score"]))
    if "work" in want_of:
        work = expected["work"]
        for key in ("clusters_recomputed", "members_recomputed", "secondary_calls",
                    "singletons_scored", "clusters_reused"):
            out.append(check.comparison(
                f"record's index.{key} = {record_index.get(key)} against the reference's count of "
                f"union clusters whose member set changed ({work[key]}): difference",
                abs(int(record_index.get(key, -1)) - work[key]), 0))
        out.append(check.comparison(
            f"record's index.components_reclustered = {record_index.get('components_reclustered')} "
            f"against the reference's changed clusters ({work['clusters_recomputed']}): difference",
            abs(int(record_index.get("components_reclustered", -1)) - work["clusters_recomputed"]), 0))
    return out


def check_guarantees(got: dict | None, read_error: str | None, before: dict, after: dict,
                     n: int, n_old: int) -> list[dict]:
    """Guarantees (b) and (c) of the configuration."""
    out = [check.comparison(
        "read-back: shards or parts a fresh load_index(heal=False) could not read, checked"
        + (f" ({read_error})" if read_error else ""), int(read_error is not None), 0)]
    if got is not None:
        out.append(check.comparison(
            f"read-back: n = {got['n']}, generation = {got['generation']}, "
            f"{int((got['admitted'][n_old:] == 1).sum())} genomes admitted at generation 1: off",
            int(got["n"] != n) + int(got["generation"] != 1)
            + int((got["admitted"][n_old:] != 1).sum()) + int((got["admitted"][:n_old] != 0).sum()), 0))
    moved = [f for f in before if after.get(f) != before[f]]
    out.append(check.comparison(
        f"generation 0's {len(before)} sketch and edge files whose sha256 moved, or that are gone",
        len(moved), 0))
    return out


# ---- the runner --------------------------------------------------------------------------


def set_up(ctx: dict) -> dict:
    """Plant, first run, build, hand-off. Returns {"pristine", "batch",
    "data", "digests"}."""
    cfg, mix = ctx["config"], ctx["traffic"]
    prepared = ctx["generator"].prepare(cfg, ctx["seed"], ctx["work_dir"])
    data = prepared["data"]
    print(f"setup: planted {data.n_old} + {len(data.names) - data.n_old} sketch sets at "
          f"{ctx['setup_clock']():.1f}s", flush=True)
    pristine = os.path.join(ctx["work_dir"], "pristine_index")
    for argv in mix["setup_argv"]:
        t0 = time.monotonic()
        error = _main([a.replace("{workdir}", prepared["workdir"]).replace("{index}", pristine)
                       for a in argv])
        if error:
            raise SystemExit(f"set-up: `{' '.join(argv[:2])}` failed: {error}")
        print(f"setup: `{' '.join(argv[:2])}` took {time.monotonic() - t0:.1f}s", flush=True)
    shutil.rmtree(prepared["workdir"], ignore_errors=True)  # the index holds its own copy
    with open(os.path.join(pristine, "manifest.json")) as f:
        pinned = json.load(f)["params"]
    batch = os.path.join(ctx["work_dir"], "batch", "batch.npz")
    ctx["generator"].write_batch(data, batch, pinned)
    sizes = [os.path.getsize(os.path.join(d, f)) for top in (pristine, os.path.dirname(batch))
             for d, _, ff in os.walk(top) for f in ff]
    print(f"setup: index and hand-off: {sum(sizes) / 1e6:.0f} MB in {len(sizes)} files, the largest "
          f"{max(sizes) / 2**20:.2f} MiB", flush=True)
    return {"pristine": pristine, "batch": batch, "data": data,
            "digests": payload_digests(pristine)}


def run(ctx: dict) -> dict:
    cfg, mix = ctx["config"], ctx["traffic"]
    expect = mix.get("expect", {})
    unknown = counters_unknown(expect)
    if unknown:
        raise SystemExit(f"this program's record has no {unknown}: every job of the cell would count "
                         f"as failed, nothing to measure")
    made = set_up(ctx)
    pristine, batch, data = made["pristine"], made["batch"], made["data"]
    k_batch = len(data.names) - data.n_old
    warm = run_job(mix["argv"], pristine, os.path.join(ctx["work_dir"], "warm"), batch)
    if warm["error"]:
        raise SystemExit(f"the warm-up job failed: {warm['error']}")
    shutil.rmtree(warm["index"], ignore_errors=True)
    print(f"setup: warm-up job took {warm['wall_s']:.1f}s", flush=True)
    setup_s = ctx["setup_clock"]()

    # ---- the window (batch_jobs.run's, mirrored) ----
    ctx["compiles"].clear()
    jobs: list[dict] = []
    trace = None
    t0 = time.monotonic()
    while True:
        job_dir = os.path.join(ctx["work_dir"], f"job{len(jobs)}")
        tracing = ctx["trace"] and not jobs
        if tracing:
            trace_dir = os.path.join(ctx["work_dir"], "trace")
            ctx["start_trace"](trace_dir)
        job = run_job(mix["argv"], pristine, job_dir, batch)
        if tracing:
            ctx["stop_trace"]()
            xplane = tracered.find_xplane(trace_dir)
            if xplane is None:
                raise SystemExit("the profiler wrote no trace")
            events = tracered.load_xplane(xplane, ctx["rehearse"])
            trace = {**tracered.reduce_trace(events, job["wall_s"]), "events": events}
        jobs.append(job)
        # another job only if it fits; the first always runs to its end
        if time.monotonic() - t0 + job["wall_s"] > ctx["seconds"]:
            break
    window_s = time.monotonic() - t0
    compiles_in_window = len(ctx["compiles"])

    # ---- after the window: records, then the reference ----
    failed = 0
    not_held: list[str] = []
    for job in jobs:
        if job["error"] is None:
            _read_record(job)
            rec = job["record"]
            faults = batch_jobs.record_faults(rec, ctx["device"], {}, None)
            faults += counter_faults(rec, expect) + index_faults(rec, k_batch)
            routes = route_faults(rec, expect)
            if ctx["rehearse"]:  # off a TPU the deep cluster takes another route
                not_held += [f for f in routes + [f for f in faults if "holds no secondary_chunked" in f]
                             if f not in not_held]
                faults = [f for f in faults if "holds no secondary_chunked" not in f]
            else:
                faults += routes
            job["error"] = "; ".join(faults) or None
        if job["error"]:
            failed += 1
            print(f"job failed: {job['error']}", flush=True)
    for f in not_held:
        print(f"rehearsal: expected of the device path, not held here (not failed): {f}", flush=True)
    sound = [j for j in jobs if not j["error"]]
    if not sound:
        raise SystemExit("no job of the window ran soundly: nothing to report")
    last = sound[-1]
    print(f"index: {json.dumps(last['record']['index'], sort_keys=True)}", flush=True)
    t_ref = time.monotonic()
    got, read_error = None, None
    try:
        got = read_answers(last["index"], data.names)
    except Exception as e:  # noqa: BLE001 — a store that does not read back is a wrong answer
        read_error = f"{type(e).__name__}: {e}"
    comparisons = []
    if got is not None:
        comparisons += check_index(got, last["record"]["index"], data, cfg["params"], mix["compare"],
                                   mix["limits"], reference_of(data, cfg["params"]))
    if "guarantees" in mix["compare"]:
        comparisons += check_guarantees(got, read_error, made["digests"], payload_digests(last["index"]),
                                        len(data.names), data.n_old)
    digest = state_digest(last["index"]) if got is not None else None
    comparisons.append(check.comparison(
        f"jobs of {len(sound)} whose published state differs from the last job's",
        sum(state_digest(j["index"]) != digest for j in sound[:-1]) if digest else len(sound), 0))
    print(f"reference: {time.monotonic() - t_ref:.1f}s after the window ({len(sound)} sound job(s) of "
          f"{len(jobs)} in {window_s:.1f}s)", flush=True)
    correct = check.report(comparisons)
    return {
        "correct": correct, "attempted": len(jobs), "failed": failed,
        "end_to_end": {"setup_s": setup_s,
                       "job_wall_s": statistics.median(j["wall_s"] for j in sound)},
        "run": {"jobs": sound, "trace": trace, "compiles_in_window": compiles_in_window,
                "window_s": window_s, "config": cfg, "traffic": mix, "device": ctx["device"],
                "peaks": ctx["peaks"], "planted": data},
    }
