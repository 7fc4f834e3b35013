#!/usr/bin/env python
"""CLI client + loadgen for the `index serve` daemon (ISSUE 11).

Client modes (against a RUNNING daemon)::

    python tools/serve_client.py <addr> -g query.fasta [more.fasta ...]
    python tools/serve_client.py <addr> --status
    python tools/serve_client.py <addr> --ping

``<addr>`` is the daemon's ready-line address — ``host:port`` or a unix
socket path. Classify prints one JSON verdict line per query (the same
contract as one-shot `index classify`).

Bench mode (``--bench``) is the serving tier's PERF GUARD: it spawns its
own daemons over its own synthetic index (or ``--index``/-g yours) and
pins the two claims the tentpole makes —

- **dynamic batching pays**: closed-loop loadgen at ``--clients``
  concurrency against ``--max_batch`` 1 (unbatched FIFO reference) vs
  16 vs 256; the guard requires batched (16) >= ``--speedup`` x
  unbatched throughput at 16 concurrent clients.
- **residency amortizes startup**: the first query (pays sketch-kernel
  compile) vs the steady-state median on one daemon; the ratio is
  recorded and must exceed ``--amortization``.

The record (``--out``, default SERVE_BENCH.json) is stamped
``proxy_metrics: true`` + the backend the DAEMON reports in its ready
line: closed-loop loadgen numbers characterize the batching/admission
layers and are never hardware claims. The parent never opens a JAX
backend (one process per chip): the daemons run on whatever
``JAX_PLATFORMS`` says — run the guard as ``JAX_PLATFORMS=cpu python
tools/serve_client.py --bench``; ``--fleet`` starts two replicas, which
cannot share one chip. Guards exit 1 on miss (``--no_guard`` records
without judging).
``--deadline_ms`` stamps every loadgen request with an end-to-end
budget (ISSUE 19); the record then carries the honest deadline-miss
rate, the clients' wire-damage tallies, and the daemon's own
``deadline_shed``/``cancels`` counters.

Fleet mode (``--bench --fleet``, ISSUE 17) is the ROUTER's perf guard:
it builds a synthetic FEDERATED index, then measures the same
closed-loop loadgen at ``--clients`` (default 64) concurrency against
(a) ONE serve daemon and (b) TWO unscoped replicas behind an
`index route` front door. The guard requires fleet qps >=
``--fleet_speedup`` (default 2.0) x the single daemon — the claim that
the router turns replica processes into throughput instead of just a
hop. The record (FLEET_BENCH.json) carries the router's own stats
(forwarded/scattered/hedges/reroutes) and the same
``proxy_metrics: true`` honesty stamp.
"""

from __future__ import annotations

import argparse
import json
import os
import signal
import subprocess
import sys
import tempfile
import threading
import time

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

from drep_tpu.serve.client import ServeClient, ServeError  # noqa: E402
from drep_tpu.utils.durableio import atomic_write_bytes  # noqa: E402


# ---- client modes ---------------------------------------------------------


def run_classify(
    address: str, genomes: list[str], retries: int, strict: bool = False
) -> int:
    """Serial classify (one per turn) so `--retries` can honor each
    refusal's retry_after_s hint; the pipelined path is the loadgen's.
    ``strict`` (federated serving, ISSUE 14) refuses PARTIAL partition
    coverage: the daemon answers ``reason=partial_coverage`` with a
    retry_after_s hint (honored by the same retry loop) instead of a
    degraded verdict."""
    rc = 0
    with ServeClient(address) as c:
        for g in genomes:
            try:
                resp = c.classify(os.path.abspath(g), retries=retries,
                                  strict=strict)
                print(json.dumps(resp["verdict"]))
            except ServeError as e:
                rc = 1
                print(json.dumps({"ok": False, "genome": g, "error": str(e),
                                  "reason": e.reason,
                                  "retry_after_s": e.retry_after_s}),
                      file=sys.stderr)
    return rc


# ---- bench mode -----------------------------------------------------------


def _plant_genomes(out_dir: str, n: int, length: int = 4000, seed: int = 0) -> list[str]:
    """Small deterministic FASTA set: a few mutation families (so the
    index has real cluster structure) + per-genome noise. Self-contained
    — the tool must run without the tests tree installed."""
    import numpy as np

    rng = np.random.default_rng(seed)
    bases = np.frombuffer(b"ACGT", dtype=np.uint8)
    os.makedirs(out_dir, exist_ok=True)
    fams = max(2, n // 4)
    family_seqs = [rng.integers(0, 4, size=length) for _ in range(fams)]
    paths = []
    for i in range(n):
        seq = family_seqs[i % fams].copy()
        pos = rng.random(length) < 0.01
        seq[pos] = (seq[pos] + rng.integers(1, 4, size=int(pos.sum()))) % 4
        s = bases[seq].tobytes().decode()
        p = os.path.join(out_dir, f"bench{i:03d}.fasta")
        body = f">bench{i}\n" + "\n".join(
            s[o : o + 80] for o in range(0, len(s), 80)
        ) + "\n"
        atomic_write_bytes(p, body.encode())
        paths.append(p)
    return paths


def _child_env() -> dict:
    """The children's environment: the caller's, untouched (a daemon runs
    on whatever JAX_PLATFORMS says — set JAX_PLATFORMS=cpu for the CPU
    proxy guard), plus the repo on PYTHONPATH."""
    env = dict(os.environ)
    env["PYTHONPATH"] = REPO + os.pathsep + env.get("PYTHONPATH", "")
    return env


def _build_index(index_loc: str, genomes: list[str], partitions: int = 0) -> None:
    """Bootstrap the bench index in a CHILD: this parent never opens a JAX
    backend — on a chip machine the chip must be free for the daemons it
    spawns (one process per chip), and the record's backend comes from
    the daemon that served, never from this process's view."""
    argv = [sys.executable, "-m", "drep_tpu", "index", "build", index_loc,
            "--length", "0", "-g", *genomes]
    if partitions:
        argv += ["--partitions", str(partitions)]
    subprocess.run(argv, check=True, cwd=REPO, env=_child_env(),
                   stdout=subprocess.DEVNULL, stderr=subprocess.DEVNULL)


def _spawn_daemon(index_loc: str, max_batch: int, extra: list[str] | None = None):
    """Start one `index serve` child; returns (proc, address, ready line)."""
    env = _child_env()
    proc = subprocess.Popen(
        [sys.executable, "-m", "drep_tpu", "index", "serve", index_loc,
         "--max_batch", str(max_batch), "--batch_window_ms", "10",
         *(extra or [])],
        stdout=subprocess.PIPE, stderr=subprocess.DEVNULL, text=True,
        cwd=REPO, env=env,
    )
    line = proc.stdout.readline()
    if not line:
        proc.kill()
        raise RuntimeError("daemon died before its ready line")
    ready = json.loads(line)
    return proc, ready["serving"], ready


def _loadgen(
    address: str, genomes: list[str], clients: int, requests_per_client: int,
    pipeline: int, warmup: bool = True, deadline_ms: float | None = None,
) -> dict:
    """Closed-loop concurrent loadgen: `clients` threads, each sending
    `requests_per_client` classifies (pipelined `pipeline` at a time —
    how the daemon's batch window actually fills). Returns qps +
    latency stats + the daemon-observed batch sizes.

    `warmup` first runs one unmeasured full-concurrency turn so the
    measured window sees the daemon's steady state — the same
    compile-warmup exclusion every bench stage in this repo applies
    (the rect compare compiles one kernel per batch-size bucket; a
    daemon pays that once per process, not per request).

    ``deadline_ms`` (ISSUE 19) stamps every request with that budget;
    ``deadline_exceeded`` refusals are counted as MISSES (distinct from
    errors — a shed is the deadline contract working) and the record
    carries the honest miss rate plus the clients' wire-damage tallies
    (corrupt frames, dup replies, wire retries)."""
    if warmup:
        _loadgen(address, genomes, clients, max(1, pipeline), pipeline,
                 warmup=False)
    lat_ms: list[float] = []
    batch_sizes: list[int] = []
    errors = [0]
    misses = [0]
    wire = {"corrupt": 0, "dup": 0, "wire_retries": 0}
    lock = threading.Lock()
    barrier = threading.Barrier(clients + 1)

    def worker(ci: int) -> None:
        with ServeClient(address, timeout_s=600) as c:
            my = [genomes[(ci + k) % len(genomes)] for k in range(requests_per_client)]
            barrier.wait()
            for off in range(0, len(my), max(1, pipeline)):
                chunk = my[off : off + max(1, pipeline)]
                # same-basename chunks cannot pipeline into one batch;
                # the client dedups nothing — the daemon's batcher defers
                t0 = time.perf_counter()
                resps = c.classify_many(chunk, deadline_ms=deadline_ms)
                dt_ms = (time.perf_counter() - t0) * 1000.0 / len(chunk)
                with lock:
                    for r in resps:
                        if r.get("ok"):
                            lat_ms.append(dt_ms)
                            batch_sizes.append(int(r.get("batch_size", 1)))
                        elif r.get("reason") == "deadline_exceeded":
                            misses[0] += 1
                        else:
                            errors[0] += 1
            with lock:
                for k in wire:
                    wire[k] += c.wire_stats[k]

    threads = [
        threading.Thread(target=worker, args=(ci,), daemon=True)
        for ci in range(clients)
    ]
    for t in threads:
        t.start()
    barrier.wait()
    t0 = time.perf_counter()
    for t in threads:
        t.join()
    wall = time.perf_counter() - t0
    done = len(lat_ms)
    lat_ms.sort()

    def pct(q: float) -> float:
        if not lat_ms:
            return 0.0
        return lat_ms[min(done - 1, max(0, round(q * (done - 1))))]

    total = done + misses[0] + errors[0]
    return {
        "clients": clients,
        "requests": done,
        "errors": errors[0],
        "wall_s": round(wall, 3),
        "qps": round(done / wall, 2) if wall > 0 else 0.0,
        "latency_ms": {"p50": round(pct(0.5), 2), "p99": round(pct(0.99), 2)},
        "mean_batch_size": round(sum(batch_sizes) / max(1, len(batch_sizes)), 2),
        "max_batch_size": max(batch_sizes, default=0),
        "deadline_ms": deadline_ms,
        "deadline_misses": misses[0],
        "deadline_miss_rate": round(misses[0] / max(1, total), 4),
        "wire": wire,
    }


def _spawn_router(index_loc: str, replicas: list[str], max_batch: int):
    env = _child_env()
    argv = [sys.executable, "-m", "drep_tpu", "index", "route", index_loc,
            "--max_batch", str(max_batch), "--batch_window_ms", "10",
            "--probe_interval_s", "0.5"]
    for addr in replicas:
        argv += ["--replica", addr]
    proc = subprocess.Popen(
        argv, stdout=subprocess.PIPE, stderr=subprocess.DEVNULL, text=True,
        cwd=REPO, env=env,
    )
    line = proc.stdout.readline()
    if not line:
        proc.kill()
        raise RuntimeError("router died before its ready line")
    return proc, json.loads(line)["serving"]


def run_fleet_bench(args) -> int:
    """The router perf guard: one daemon vs two replicas behind the
    front door, same federated index, same loadgen."""
    tmp = tempfile.mkdtemp(prefix="drep_fleet_bench_")
    print(f"fleet bench: planting {args.n_genomes} synthetic genomes...",
          file=sys.stderr)
    planted = _plant_genomes(os.path.join(tmp, "g"), args.n_genomes)
    index_loc = os.path.join(tmp, "idx")
    _build_index(index_loc, planted, partitions=args.partitions)
    # a WIDE disjoint hot set: the single daemon's identical-request
    # coalescing must not trivialize the workload, or the ratio would
    # measure framing overhead instead of compute parallelism
    genomes = _plant_genomes(os.path.join(tmp, "q"), args.n_queries, seed=1)

    record: dict = {
        "kind": "fleet_bench",
        "proxy_metrics": True,  # loadgen numbers are NEVER hardware claims
        "n_indexed": len(planted),
        "n_partitions": args.partitions,
        "n_query_hot_set": len(genomes),
        "n_replicas": 2,
        "configs": {},
    }

    procs: list = []
    try:
        # -- single daemon reference --------------------------------------
        proc, addr, ready = _spawn_daemon(index_loc, args.max_batch)
        procs.append(proc)
        # what served, as the daemon itself reports it
        record["backend"] = ready["platform"]
        record["device_kind"] = ready["device_kind"]
        single = _loadgen(
            addr, genomes, clients=args.clients,
            requests_per_client=args.requests_per_client,
            pipeline=args.pipeline, deadline_ms=args.deadline_ms or None,
        )
        with ServeClient(addr, timeout_s=60) as c:
            st = c.status()
            single["deadline_shed"] = st.get("deadline_shed", 0)
            single["cancels"] = st.get("cancels", 0)
        record["configs"]["single"] = single
        print(f"fleet bench: single daemon: {single['qps']} qps "
              f"(p50 {single['latency_ms']['p50']}ms)", file=sys.stderr)
        proc.send_signal(signal.SIGTERM)
        proc.wait(60)

        # -- two replicas behind the router -------------------------------
        r1, a1, _ = _spawn_daemon(index_loc, args.max_batch)
        r2, a2, _ = _spawn_daemon(index_loc, args.max_batch)
        procs += [r1, r2]
        router, raddr = _spawn_router(index_loc, [a1, a2], args.max_batch)
        procs.append(router)
        fleet = _loadgen(
            raddr, genomes, clients=args.clients,
            requests_per_client=args.requests_per_client,
            pipeline=args.pipeline, deadline_ms=args.deadline_ms or None,
        )
        with ServeClient(raddr, timeout_s=60) as c:
            st = c.status()
            fleet["router"] = st.get("router")
            fleet["deadline_shed"] = st.get("deadline_shed", 0)
            fleet["cancels"] = st.get("cancels", 0)
            fleet["replica_states"] = {
                a: e.get("state")
                for a, e in (st.get("replicas") or {}).get("replicas", {}).items()
            }
            fleet["replica_breakers"] = {
                a: e.get("breaker")
                for a, e in (st.get("replicas") or {}).get("replicas", {}).items()
            }
        record["configs"]["fleet"] = fleet
        print(f"fleet bench: 2-replica fleet: {fleet['qps']} qps "
              f"(p50 {fleet['latency_ms']['p50']}ms; "
              f"router {fleet.get('router')})", file=sys.stderr)
        for p in (router, r1, r2):
            p.send_signal(signal.SIGTERM)
        for p in (router, r1, r2):
            p.wait(60)
    finally:
        for p in procs:
            if p.poll() is None:
                p.kill()

    qps_single = record["configs"]["single"]["qps"]
    qps_fleet = record["configs"]["fleet"]["qps"]
    record["fleet_speedup_x"] = round(qps_fleet / max(qps_single, 1e-9), 2)
    record["guards"] = {
        "fleet_speedup_min": args.fleet_speedup,
        "fleet_speedup_ok": record["fleet_speedup_x"] >= args.fleet_speedup,
        "fleet_errors_ok": record["configs"]["fleet"]["errors"] == 0,
    }
    out = args.out if args.out != "SERVE_BENCH.json" else "FLEET_BENCH.json"
    atomic_write_bytes(out, json.dumps(record, indent=1, sort_keys=True).encode())
    print(json.dumps({k: record[k] for k in
                      ("fleet_speedup_x", "guards", "backend", "proxy_metrics")}))
    print(f"fleet bench: record -> {out}", file=sys.stderr)
    if args.no_guard:
        return 0
    ok = all(v for k, v in record["guards"].items() if k.endswith("_ok"))
    if not ok:
        print(f"fleet bench: GUARD FAILED: {record['guards']}", file=sys.stderr)
    return 0 if ok else 1


def run_bench(args) -> int:
    tmp = tempfile.mkdtemp(prefix="drep_serve_bench_")
    if args.index:
        index_loc = args.index
        genomes = [os.path.abspath(g) for g in (args.genomes or [])]
        if len(genomes) < 2:
            # the startup-amortization probe needs a first AND a warm
            # query; failing here beats an IndexError mid-run with
            # daemons already spawned
            print("--bench with --index needs -g with >= 2 query genomes",
                  file=sys.stderr)
            return 2
    else:
        print(f"bench: planting {args.n_genomes} synthetic genomes...", file=sys.stderr)
        planted = _plant_genomes(os.path.join(tmp, "g"), args.n_genomes)
        index_loc = os.path.join(tmp, "idx")
        _build_index(index_loc, planted)
        # queries: a disjoint synthetic HOT SET (novel + near-family mix).
        # Small on purpose — the serving scenario is many concurrent
        # users asking about a working set of genomes, which is exactly
        # where coalescing (shared sketch+rect, identical-request
        # fan-out) pays; the set size is recorded in the artifact.
        genomes = _plant_genomes(os.path.join(tmp, "q"), args.n_queries, seed=1)

    record: dict = {
        "kind": "serve_bench",
        "proxy_metrics": True,  # loadgen numbers are NEVER hardware claims
        "n_indexed": None,
        "n_query_hot_set": len(genomes),
        "configs": {},
    }

    rpc = args.requests_per_client
    daemons: list = []
    try:
        for max_batch in (1, 16, 256):
            proc, addr, ready = _spawn_daemon(index_loc, max_batch)
            daemons.append(proc)
            # what served, as the daemon itself reports it
            record["backend"] = ready["platform"]
            record["device_kind"] = ready["device_kind"]
            with ServeClient(addr, timeout_s=600) as c:
                st = c.status()
                record["n_indexed"] = st["n_genomes"]
                # startup amortization: first query pays the sketch/compare
                # compile; steady state is the residency win
                t0 = time.perf_counter()
                c.classify(genomes[0])
                first_ms = (time.perf_counter() - t0) * 1000.0
                warm = []
                for g in genomes[1:4]:
                    t0 = time.perf_counter()
                    c.classify(g)
                    warm.append((time.perf_counter() - t0) * 1000.0)
            warm_ms = sorted(warm)[len(warm) // 2]
            cfg = _loadgen(
                addr, genomes, clients=args.clients, requests_per_client=rpc,
                pipeline=max(1, min(max_batch, args.pipeline)),
                deadline_ms=args.deadline_ms or None,
            )
            with ServeClient(addr, timeout_s=60) as c:
                st = c.status()
                cfg["deadline_shed"] = st.get("deadline_shed", 0)
                cfg["cancels"] = st.get("cancels", 0)
            cfg["first_query_ms"] = round(first_ms, 1)
            cfg["warm_query_ms"] = round(warm_ms, 1)
            cfg["startup_amortization_x"] = round(first_ms / max(warm_ms, 1e-3), 1)
            record["configs"][f"max_batch_{max_batch}"] = cfg
            print(
                f"bench: max_batch={max_batch}: {cfg['qps']} qps, "
                f"p50 {cfg['latency_ms']['p50']}ms, mean batch "
                f"{cfg['mean_batch_size']}, first/warm "
                f"{cfg['first_query_ms']}/{cfg['warm_query_ms']}ms",
                file=sys.stderr,
            )
            proc.send_signal(signal.SIGTERM)
            proc.wait(60)
    finally:
        for p in daemons:
            if p.poll() is None:
                p.kill()

    unbatched = record["configs"]["max_batch_1"]["qps"]
    batched = record["configs"]["max_batch_16"]["qps"]
    record["batched_speedup_x"] = round(batched / max(unbatched, 1e-9), 2)
    amort = record["configs"]["max_batch_16"]["startup_amortization_x"]
    record["guards"] = {
        "batched_speedup_min": args.speedup,
        "batched_speedup_ok": record["batched_speedup_x"] >= args.speedup,
        "startup_amortization_min": args.amortization,
        "startup_amortization_ok": amort >= args.amortization,
    }
    out = args.out
    atomic_write_bytes(out, json.dumps(record, indent=1, sort_keys=True).encode())
    print(json.dumps({k: record[k] for k in
                      ("batched_speedup_x", "guards", "backend", "proxy_metrics")}))
    print(f"bench: record -> {out}", file=sys.stderr)
    if args.no_guard:
        return 0
    ok = all(v for k, v in record["guards"].items() if k.endswith("_ok"))
    if not ok:
        print(f"bench: GUARD FAILED: {record['guards']}", file=sys.stderr)
    return 0 if ok else 1


def main(argv: list[str] | None = None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("address", nargs="?", default=None,
                    help="a running daemon's address (host:port or socket "
                         "path) — omit with --bench (it spawns its own)")
    ap.add_argument("-g", "--genomes", nargs="*", default=None)
    ap.add_argument("--status", action="store_true")
    ap.add_argument("--ping", action="store_true")
    ap.add_argument("--retries", type=int, default=3,
                    help="backpressure retries per classify (sleeps the "
                         "daemon's retry_after_s hint)")
    ap.add_argument("--strict", action="store_true",
                    help="FEDERATED serving: refuse PARTIAL partition "
                         "coverage — a verdict that would be stamped with "
                         "partitions_unavailable (a quarantined partition) "
                         "comes back as a partial_coverage refusal with a "
                         "retry_after_s hint (the next reload probe) "
                         "instead of a degraded answer")
    ap.add_argument("--bench", action="store_true",
                    help="spawn daemons + loadgen: the serving perf guard")
    ap.add_argument("--fleet", action="store_true",
                    help="with --bench: the ROUTER perf guard — 2 replicas "
                         "behind `index route` vs 1 daemon over the same "
                         "federated index (FLEET_BENCH.json)")
    ap.add_argument("--partitions", type=int, default=2,
                    help="federated partition count for --fleet (default 2)")
    ap.add_argument("--max_batch", type=int, default=64,
                    help="daemon/router max_batch for --fleet (default 64)")
    ap.add_argument("--fleet_speedup", type=float, default=2.0,
                    help="guard: fleet / single-daemon qps floor at "
                         "--clients concurrency (default 2.0)")
    ap.add_argument("--index", default=None,
                    help="bench against this existing index (default: "
                         "build a synthetic one)")
    ap.add_argument("--n_genomes", type=int, default=12,
                    help="synthetic index size for --bench (default 12)")
    ap.add_argument("--n_queries", type=int, default=4,
                    help="size of the synthetic query hot set the clients "
                         "cycle over (default 4 — concurrent traffic over "
                         "a working set is the coalescing scenario)")
    ap.add_argument("--clients", type=int, default=16,
                    help="concurrent loadgen clients (default 16)")
    ap.add_argument("--requests_per_client", type=int, default=8)
    ap.add_argument("--pipeline", type=int, default=4,
                    help="requests each client pipelines per turn (fills "
                         "the batch window; capped at the daemon's "
                         "max_batch per config)")
    ap.add_argument("--speedup", type=float, default=3.0,
                    help="guard: batched(16) / unbatched qps floor")
    ap.add_argument("--amortization", type=float, default=3.0,
                    help="guard: first-query / warm-query latency floor")
    ap.add_argument("--deadline_ms", type=float, default=0.0,
                    help="stamp every loadgen request with this end-to-end "
                         "deadline budget (ISSUE 19); deadline_exceeded "
                         "refusals are recorded as an honest miss rate "
                         "alongside the daemon's shed/cancel counters "
                         "(0 = unbudgeted, the default)")
    ap.add_argument("--no_guard", action="store_true",
                    help="record without judging (exploration runs)")
    ap.add_argument("--out", default="SERVE_BENCH.json")
    args = ap.parse_args(argv)

    if args.bench and args.fleet:
        if args.clients == 16:
            args.clients = 64  # the fleet claim is pinned at 64 concurrent
        if args.n_queries == 4:
            args.n_queries = 32  # wide hot set: no identical-request
            # coalescing shortcut — the ratio must measure parallel compute
        return run_fleet_bench(args)
    if args.bench:
        return run_bench(args)
    if not args.address:
        ap.error("need a daemon address (or --bench)")
    try:
        if args.status:
            with ServeClient(args.address) as c:
                print(json.dumps(c.status(), indent=1, sort_keys=True))
            return 0
        if args.ping:
            with ServeClient(args.address) as c:
                print(json.dumps(c.ping()))
            return 0
        if args.genomes:
            return run_classify(args.address, args.genomes, args.retries,
                                strict=args.strict)
    except ServeError as e:
        print(f"serve error: {e} (reason={e.reason})", file=sys.stderr)
        return 1
    ap.error("nothing to do: -g <genomes>, --status, --ping, or --bench")
    return 2


if __name__ == "__main__":
    sys.exit(main())
