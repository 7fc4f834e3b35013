"""Top-level workflows composing the pipeline stages.

Reference parity: drep/d_workflows.py (SURVEY.md §2/§3; reference mount
empty): dereplicate = filter -> cluster -> choose -> evaluate -> analyze;
compare = cluster -> evaluate -> analyze (no filter/choose).
"""

from __future__ import annotations

import contextlib

import pandas as pd

from drep_tpu.choose import d_choose_wrapper
from drep_tpu.cluster.controller import d_cluster_wrapper
from drep_tpu.evaluate import d_evaluate_wrapper
from drep_tpu.filter import d_filter_wrapper
from drep_tpu.ingest import make_bdb
from drep_tpu.utils.logger import get_logger, setup_logger
from drep_tpu.workdir import WorkDirectory
from drep_tpu.errors import UserInputError


def _bring_up(wd_loc: str, verb: str, events: str | bool | None = None) -> WorkDirectory:
    """What has to be in place before the `job` span and the profiler
    open: the distributed runtime (it must come before ANY backend use, and
    ``jax.profiler.start_trace`` initialises the backend), the workdir, the
    logger, the event log and fresh per-run state. `job` opens right after,
    so its `B` line lands in THIS job's event log. No span covers this: the
    process ledger holds its wall as the job's `bring_up_s`."""
    from drep_tpu.utils.profiling import counters, listen_for_compiles

    counters.process.begin(verb)
    # multi-host bring-up must precede any backend use (no-op single-host)
    from drep_tpu.parallel.mesh import initialize_distributed
    from drep_tpu.utils.xla_cache import enable_persistent_cache

    enable_persistent_cache()
    listen_for_compiles()
    initialize_distributed()
    wd = WorkDirectory(wd_loc)
    setup_logger(wd.get_dir("log"))
    # structured event tracing (ISSUE 10): per-process append-only JSONL
    # under <wd>/log, gated by --events / DREP_TPU_EVENTS (default off —
    # zero files, zero overhead); plus the optional periodic Prometheus
    # textfile flush (DREP_TPU_METRICS_FLUSH_S, default off)
    import jax

    from drep_tpu.utils import telemetry
    from drep_tpu.utils.profiling import start_metrics_flush

    telemetry.configure(
        log_dir=wd.get_dir("log"), enabled=events, pid=jax.process_index()
    )
    start_metrics_flush(wd.get_dir("log"))
    # fresh per-run state (library users may call several workflows per process)
    from drep_tpu.cluster.anim import reset_run_state

    counters.reset()
    reset_run_state()
    counters.process.brought_up()
    return wd


def _load_bdb(wd: WorkDirectory, genomes: list[str]) -> pd.DataFrame:
    """The first work inside `job`: name the device, store or reload Bdb."""
    from drep_tpu.utils.profiling import counters, device_record

    get_logger().info("device: %s", device_record())
    with counters.span("tables_io") as io:
        if genomes:
            bdb = make_bdb(genomes)
            io.note(rows=len(bdb), bytes=wd.store_db(bdb, "Bdb"))
        elif wd.hasDb("Bdb"):
            bdb = wd.get_db("Bdb")  # resume from an existing workdir
        else:
            raise UserInputError("no genomes given and workdir has no stored Bdb")
    return bdb


def _trace_dir(wd_loc: str, profile) -> str | None:
    if not profile:
        return None
    if isinstance(profile, str) and profile != "auto":
        return profile
    import os

    return os.path.join(wd_loc, "log", "jax_trace")


def _finish_counters(wd: WorkDirectory) -> None:
    """Write the job's record, then enter the job in the process ledger.
    Called inside the `job` span, which is counted as far as it has come;
    the wrappers close the event log once the span has written its end."""
    from drep_tpu.utils.profiling import counters, stop_metrics_flush

    stop_metrics_flush(final=True)
    rep = counters.report()
    path = counters.write(wd.get_dir("log"))
    total = rep["total"]
    get_logger().info(
        "perf: %d pairs in %.2fs = %s pairs/sec/chip (%d chip(s)) -> %s",
        total["pairs"], total["seconds"], total["pairs_per_sec_per_chip"],
        rep["n_chips"], path,
    )
    counters.finish_job()


@contextlib.contextmanager
def _record_a_drain(wd: WorkDirectory):
    """A job that leaves at a safe boundary (`PodDrained`) writes its record
    like one that ends: its spans and counters up to there, and `drain`, the
    boundary (profiling.Counters.note_drain). Inside the `job` span."""
    from drep_tpu.parallel.faulttol import PodDrained
    from drep_tpu.utils.profiling import counters

    try:
        yield
    except PodDrained as drained:
        if not counters.drain:  # a pod member's boundary: its loop told the peers
            counters.note_drain("pod", message=str(drained))
        _finish_counters(wd)
        raise


def compare_wrapper(wd_loc: str, genomes: list[str] | None = None, **kwargs) -> pd.DataFrame:
    """`compare`: cluster + evaluate + analyze. Returns Cdb."""
    from drep_tpu.utils import telemetry
    from drep_tpu.utils.profiling import counters, trace

    # `job` is the root span, from the bring-up to the record's write: its
    # self time is the job's unattributed host time. --profile wraps it whole.
    wd = _bring_up(wd_loc, "compare", events=kwargs.pop("events", None))
    with trace(_trace_dir(wd_loc, kwargs.pop("profile", None))), counters.span("job"):
        bdb = _load_bdb(wd, genomes or [])
        with _record_a_drain(wd), counters.span("stage:cluster"):
            cdb = d_cluster_wrapper(wd, bdb, **kwargs)
        # per-genome stats for downstream stages come from the ingest pass's Gdb
        # (one FASTA read per genome, not a second parse)
        with counters.span("tables_io") as io:
            stats = wd.get_db("Gdb")[["genome", "length", "N50", "contigs"]]
            io.note(rows=len(stats), bytes=wd.store_db(stats, "genomeInformation"))
        with counters.span("stage:evaluate"):
            d_evaluate_wrapper(wd, **kwargs)
        if not kwargs.get("skip_plots", False):
            from drep_tpu.analyze import plot_all

            plot_all(wd)
        _finish_counters(wd)
    telemetry.close()
    get_logger().info("compare finished: %d genomes, %d secondary clusters",
                      len(cdb), cdb["secondary_cluster"].nunique())
    return cdb


def _init_index(index_loc: str, verb: str, write_logs: bool = True) -> str | None:
    """Service-mode session setup: logging under the index's own log dir,
    persistent compile cache, fresh counters — the index equivalents of
    `_bring_up`, minus workdir/Bdb machinery (the store IS the state).
    `write_logs=False` (classify) keeps logging console-only: classify is
    read-only by contract, and even a log line under the index dir would
    violate the nothing-written assertion its tests pin. Returns the log
    dir (None when console-only) for :func:`_finish_index`."""
    import os

    from drep_tpu.utils.xla_cache import enable_persistent_cache
    from drep_tpu.utils.profiling import counters, listen_for_compiles

    counters.process.begin(verb)
    enable_persistent_cache()
    listen_for_compiles()
    log_dir = None
    if write_logs:
        log_dir = os.path.join(os.path.abspath(index_loc), "log")
        os.makedirs(log_dir, exist_ok=True)
    setup_logger(log_dir)
    # event tracing + metrics flush ride the index log dir; classify
    # (write_logs=False) keeps BOTH off — its read-only byte-for-byte
    # contract forbids even an event line under the index tree
    from drep_tpu.utils import telemetry
    from drep_tpu.utils.profiling import start_metrics_flush, stop_metrics_flush

    telemetry.configure(log_dir=log_dir)
    if log_dir is not None:
        start_metrics_flush(log_dir)
    else:
        stop_metrics_flush()
    counters.reset()
    counters.process.brought_up()
    return log_dir


def _finish_index(log_dir: str | None) -> None:
    """The index verbs' run record (the `_finish_counters` twin): which
    device the verb ran on, its fault-tolerance counters and kernel
    paths — ``<index>/log/perf_counters.json``, or ONE console log line
    for the read-only classify, which may write nothing. Then the verb is
    entered in the process ledger."""
    import json

    from drep_tpu.utils.profiling import counters

    if log_dir is not None:
        counters.write(log_dir)
    else:
        get_logger().info(
            "perf_counters: %s", json.dumps(counters.report(), sort_keys=True)
        )
    counters.finish_job()


def index_build_wrapper(
    index_loc: str, genomes: list[str] | None = None,
    work_directory: str | None = None, **kwargs,
) -> dict:
    """`index build`: generation 0 from a completed workdir snapshot
    (--work_directory) or bootstrapped from FASTAs (-g). With
    ``--partitions N`` the bootstrap creates a FEDERATED index
    (index/federation.py): N range-partitioned stores under one
    meta-manifest, the whole input admitted as federation generation 0."""
    from drep_tpu.index import build_federated, build_from_paths, build_from_workdir
    from drep_tpu.utils.profiling import counters

    log_dir = _init_index(index_loc, "index build")
    with counters.span("job"):
        if work_directory and genomes:
            raise UserInputError(
                "index build takes --work_directory OR -g genomes, not both"
            )
        partitions = int(kwargs.pop("partitions", 0) or 0)
        if work_directory:
            if partitions:
                raise UserInputError(
                    "index build --partitions is a bootstrap (-g) mode: a "
                    "workdir snapshot has no per-genome routing pass — build "
                    "federated from the FASTAs instead"
                )
            summary = build_from_workdir(index_loc, work_directory)
        elif genomes and partitions:
            summary = build_federated(
                index_loc, genomes, partitions,
                processes=kwargs.pop("processes", 1) or 1, **kwargs,
            )
        elif genomes:
            summary = build_from_paths(
                index_loc, genomes,
                processes=kwargs.pop("processes", 1) or 1, **kwargs,
            )
        else:
            raise UserInputError(
                "index build needs a source: --work_directory <completed run> or "
                "-g <genome FASTAs>"
            )
        _finish_index(log_dir)
    return summary


def index_update_wrapper(
    index_loc: str, genomes: list[str] | None = None, **kwargs
) -> dict:
    """`index update`: admit a batch (or heal, with no genomes). A
    federated root routes by range code and updates partitions as
    independent units (``--fed_pods`` for concurrent subprocess pods)."""
    from drep_tpu.index import index_update
    from drep_tpu.utils.profiling import counters

    log_dir = _init_index(index_loc, "index update")
    with counters.span("job"):
        summary = index_update(
            index_loc, genomes, processes=kwargs.get("processes", 1) or 1,
            primary_prune=kwargs.get("primary_prune", "off") or "off",
            prune_bands=kwargs.get("prune_bands", 0) or 0,
            prune_min_shared=kwargs.get("prune_min_shared", 0) or 0,
            prune_join_chunk=kwargs.get("prune_join_chunk", 0) or 0,
            fed_pods=kwargs.get("fed_pods"),
            params_file=kwargs.get("params_file"),
        )
        _finish_index(log_dir)
    return summary


def index_maintenance_wrapper(index_loc: str, *, op: str, **kwargs) -> dict:
    """`index split|merge|compact`: the transactional index lifecycle
    (index/maintenance.py). Each verb first converges any interrupted
    earlier transaction (roll_forward), then runs its own staged
    transaction — crash-safe at every phase by construction."""
    from drep_tpu.index import fed_compact, fed_merge, fed_split
    from drep_tpu.utils import envknobs

    log_dir = _init_index(index_loc, "index " + op)
    processes = kwargs.get("processes", 1) or 1
    if op == "split":
        summary = fed_split(index_loc, int(kwargs["pid"]), processes=processes)
    elif op == "merge":
        pid_a, pid_b = kwargs["pids"]
        summary = fed_merge(
            index_loc, int(pid_a), int(pid_b), processes=processes
        )
    else:
        min_gens = kwargs.get("min_generations")
        if min_gens is None:
            min_gens = envknobs.env_int("DREP_TPU_COMPACT_MIN_SHARDS")
        summary = fed_compact(
            index_loc, pid=kwargs.get("pid"), processes=processes,
            min_generations=int(min_gens),
        )
    get_logger().info("index %s summary: %s", op, summary)
    _finish_index(log_dir)
    return summary


def index_classify_wrapper(
    index_loc: str, genomes: list[str] | None = None, **kwargs
) -> list[dict]:
    """`index classify`: read-only membership verdicts (optionally via
    the LSH candidate set — verdicts identical, see index/classify.py)."""
    from drep_tpu.index import index_classify

    if not genomes:
        raise UserInputError("index classify needs -g <genome FASTAs>")
    log_dir = _init_index(index_loc, "index classify", write_logs=False)
    verdicts = index_classify(
        index_loc, genomes, processes=kwargs.get("processes", 1) or 1,
        primary_prune=kwargs.get("primary_prune", "off") or "off",
        prune_bands=kwargs.get("prune_bands", 0) or 0,
        prune_min_shared=kwargs.get("prune_min_shared", 0) or 0,
        prune_join_chunk=kwargs.get("prune_join_chunk", 0) or 0,
    )
    _finish_index(log_dir)
    return verdicts


def index_serve_wrapper(index_loc: str, genomes: list[str] | None = None, **kwargs) -> int:
    """`index serve`: the resident serving tier (drep_tpu/serve/) —
    load once, batch dynamically, hot-swap generations, drain on
    SIGTERM. Blocks until drained; returns the (0) exit status.

    Observability setup mirrors `_init_index` with one inversion: the
    daemon is a pure READER of the index, so its logs/metrics/events
    live under ``--log_dir`` (or nowhere) — never the index tree the
    byte-for-byte contract protects."""
    import os

    from drep_tpu.serve import IndexServer, ServeConfig, install_signal_handlers
    from drep_tpu.utils import telemetry
    from drep_tpu.utils.profiling import counters, start_metrics_flush, stop_metrics_flush
    from drep_tpu.utils.xla_cache import enable_persistent_cache

    enable_persistent_cache()
    log_dir = kwargs.get("log_dir") or None
    if telemetry.resolve_enabled(kwargs.get("events")) and not log_dir:
        raise UserInputError(
            "--events on needs --log_dir (the daemon never writes under "
            "the index directory, so traces have nowhere to go)"
        )
    if log_dir:
        log_dir = os.path.abspath(log_dir)
        idx_abs = os.path.abspath(index_loc)
        if log_dir == idx_abs or log_dir.startswith(idx_abs + os.sep):
            raise UserInputError(
                f"--log_dir {log_dir} is inside the index directory — the "
                f"daemon is read-only by contract; point it elsewhere"
            )
        os.makedirs(log_dir, exist_ok=True)
    # keep the console verbosity the controller already set for -d:
    # setup_logger replaces handlers, and clobbering a long-lived
    # daemon's debug logging back to INFO would make the flag a no-op
    import logging

    console_lvl = next(
        (h.level for h in get_logger().handlers
         if isinstance(h, logging.StreamHandler)),
        logging.INFO,
    )
    setup_logger(log_dir, verbosity=console_lvl or logging.INFO)
    telemetry.configure(log_dir=log_dir, enabled=kwargs.get("events"))
    if log_dir:
        start_metrics_flush(log_dir)
    else:
        stop_metrics_flush()
    counters.reset()
    cfg = ServeConfig(
        index_loc=index_loc,
        host=kwargs.get("host", "127.0.0.1") or "127.0.0.1",
        port=int(kwargs.get("port", 0) or 0),
        socket_path=kwargs.get("socket") or None,
        max_queue=int(kwargs.get("max_queue", 256) or 256),
        max_batch=int(kwargs.get("max_batch", 64) or 64),
        batch_window_ms=float(kwargs.get("batch_window_ms", 5.0) or 0.0),
        poll_generation_s=float(kwargs.get("poll_generation_s", 2.0) or 2.0),
        processes=int(kwargs.get("processes", 1) or 1),
        prune_cfg={
            "primary_prune": kwargs.get("primary_prune", "off") or "off",
            "prune_bands": int(kwargs.get("prune_bands", 0) or 0),
            "prune_min_shared": int(kwargs.get("prune_min_shared", 0) or 0),
            "prune_join_chunk": int(kwargs.get("prune_join_chunk", 0) or 0),
        },
        log_dir=log_dir,
        resident_mb=kwargs.get("resident_mb"),
    )
    server = IndexServer(cfg)
    install_signal_handlers(server)
    try:
        return server.run()
    finally:
        stop_metrics_flush(final=bool(log_dir))
        if log_dir:
            counters.write(log_dir)
        telemetry.close()


def index_route_wrapper(index_loc: str, genomes: list[str] | None = None, **kwargs) -> int:
    """`index route`: the fleet front door (drep_tpu/serve/router.py) —
    a stateless scatter/gather router over N `index serve` replicas.
    Blocks until drained; returns the (0) exit status.

    Same reader-purity inversion as `index serve`: the router never
    writes under the index tree — logs/metrics/events go to --log_dir
    or nowhere. An empty --replica list is legal (replicas may join
    later via the ``fleet`` op); queries before any join are refused
    with reason ``no_replicas``."""
    import os

    from drep_tpu.serve import RouterConfig, RouterServer, install_signal_handlers
    from drep_tpu.utils import telemetry
    from drep_tpu.utils.profiling import counters, start_metrics_flush, stop_metrics_flush
    from drep_tpu.utils.xla_cache import enable_persistent_cache

    enable_persistent_cache()
    log_dir = kwargs.get("log_dir") or None
    if telemetry.resolve_enabled(kwargs.get("events")) and not log_dir:
        raise UserInputError(
            "--events on needs --log_dir (the router never writes under "
            "the index directory, so traces have nowhere to go)"
        )
    if log_dir:
        log_dir = os.path.abspath(log_dir)
        idx_abs = os.path.abspath(index_loc)
        if log_dir == idx_abs or log_dir.startswith(idx_abs + os.sep):
            raise UserInputError(
                f"--log_dir {log_dir} is inside the index directory — the "
                f"router is read-only by contract; point it elsewhere"
            )
        os.makedirs(log_dir, exist_ok=True)
    import logging

    console_lvl = next(
        (h.level for h in get_logger().handlers
         if isinstance(h, logging.StreamHandler)),
        logging.INFO,
    )
    setup_logger(log_dir, verbosity=console_lvl or logging.INFO)
    telemetry.configure(log_dir=log_dir, enabled=kwargs.get("events"))
    if log_dir:
        start_metrics_flush(log_dir)
    else:
        stop_metrics_flush()
    counters.reset()
    replicas = list(kwargs.get("replica") or [])
    if not replicas:
        get_logger().warning(
            "index route starting with an empty replica table — queries "
            "will be refused (no_replicas) until a `fleet` join arrives"
        )
    cfg = RouterConfig(
        index_loc=index_loc,
        host=kwargs.get("host", "127.0.0.1") or "127.0.0.1",
        port=int(kwargs.get("port", 0) or 0),
        socket_path=kwargs.get("socket") or None,
        max_batch=int(kwargs.get("max_batch", 64) or 64),
        batch_window_ms=float(kwargs.get("batch_window_ms", 5.0) or 0.0),
        poll_generation_s=float(kwargs.get("poll_generation_s", 2.0) or 2.0),
        processes=int(kwargs.get("processes", 1) or 1),
        prune_cfg={
            "primary_prune": kwargs.get("primary_prune", "off") or "off",
            "prune_bands": int(kwargs.get("prune_bands", 0) or 0),
            "prune_min_shared": int(kwargs.get("prune_min_shared", 0) or 0),
            "prune_join_chunk": int(kwargs.get("prune_join_chunk", 0) or 0),
        },
        log_dir=log_dir,
        resident_mb=kwargs.get("resident_mb"),
        replicas=replicas,
        max_inflight=kwargs.get("max_inflight"),
        leg_timeout_s=kwargs.get("leg_timeout_s"),
        hedge_delay_s=kwargs.get("hedge_delay_s"),
        probe_interval_s=float(kwargs.get("probe_interval_s", 1.0) or 1.0),
        probe_backoff_s=kwargs.get("probe_backoff_s"),
        fleet_manifest=kwargs.get("fleet_manifest"),
    )
    server = RouterServer(cfg)
    install_signal_handlers(server)
    try:
        return server.run()
    finally:
        stop_metrics_flush(final=bool(log_dir))
        if log_dir:
            counters.write(log_dir, device=False)  # control plane: never opens a backend
        telemetry.close()


def index_supervise_wrapper(index_loc: str, **kwargs) -> int:
    """`index supervise`: the fleet supervisor
    (drep_tpu/serve/supervisor.py) — replica process lifecycle against
    the durable ``fleet.json`` manifest. Adoption first (a restarted
    supervisor re-attaches every still-live replica it finds in the
    manifest, never double-spawns), then the requested initial
    placement for ranges the manifest doesn't already cover, then the
    heartbeat loop: liveness + /healthz per slot, decorrelated-backoff
    restarts, crash-loop quarantine, drain escalation.

    Prints one JSON ready line (``{"supervising": ..., "pid": ...}``)
    once recovery + initial placement are published — the same
    stdout contract every daemon in the serve tier honors. Exit is
    harmless by design: replicas outlive their supervisor, and the
    manifest makes the successor whole. The supervisor needs no JAX —
    it is pure control plane."""
    import json as _json
    import os
    import time as _time

    from drep_tpu.serve.router import parse_replica_spec
    from drep_tpu.serve.supervisor import FleetSupervisor, manifest_path
    from drep_tpu.utils import telemetry
    from drep_tpu.utils.profiling import counters, start_metrics_flush, stop_metrics_flush

    log_dir = kwargs.get("log_dir") or None
    if telemetry.resolve_enabled(kwargs.get("events")) and not log_dir:
        raise UserInputError(
            "--events on needs --log_dir (the supervisor writes only the "
            "fleet manifest under the index tree; traces go elsewhere)"
        )
    if log_dir:
        log_dir = os.path.abspath(log_dir)
        idx_abs = os.path.abspath(index_loc)
        if log_dir == idx_abs or log_dir.startswith(idx_abs + os.sep):
            raise UserInputError(
                f"--log_dir {log_dir} is inside the index directory — "
                f"the supervisor's one sanctioned write there is the "
                f"fleet manifest; point logs elsewhere"
            )
        os.makedirs(log_dir, exist_ok=True)
    import logging

    console_lvl = next(
        (h.level for h in get_logger().handlers
         if isinstance(h, logging.StreamHandler)),
        logging.INFO,
    )
    setup_logger(log_dir, verbosity=console_lvl or logging.INFO)
    telemetry.configure(log_dir=log_dir, enabled=kwargs.get("events"))
    if log_dir:
        start_metrics_flush(log_dir)
    else:
        stop_metrics_flush()
    counters.reset()
    fleet_dir = kwargs.get("fleet_dir") or os.path.join(index_loc, "fleet")
    # initial placement specs: "N" (unscoped) or "N=0-2,5" (scoped)
    wanted: list[tuple[int, frozenset | None]] = []
    for spec in kwargs.get("replica") or []:
        count_s, _, pids_s = str(spec).partition("=")
        try:
            count = int(count_s)
        except ValueError:
            raise UserInputError(
                f"bad --replica spec {spec!r}: want N or N=PIDS "
                f"(e.g. 2 or 1=0-2,5)"
            ) from None
        assigned = parse_replica_spec(f"x={pids_s}")[1] if pids_s else None
        wanted.append((count, assigned))
    sup = FleetSupervisor(
        fleet_dir,
        spawn_cmd=kwargs.get("spawn"),
        router_address=kwargs.get("router"),
        heartbeat_s=kwargs.get("heartbeat_s"),
        backoff_max_s=kwargs.get("backoff_max_s"),
        crashloop_k=kwargs.get("crashloop_k"),
        crashloop_window_s=kwargs.get("crashloop_window_s"),
        drain_deadline_s=kwargs.get("drain_deadline_s"),
        startup_deadline_s=kwargs.get("startup_deadline_s"),
    )
    try:
        recovered = sup.recover()
        from drep_tpu.serve.supervisor import slot_range_key

        for count, assigned in wanted:
            key = ("all" if assigned is None
                   else ",".join(str(p) for p in sorted(assigned)))
            have = sum(
                1 for s in sup.doc["slots"].values()
                if slot_range_key(s) == key
                and s.get("state") not in ("draining",)
            )
            need = count - have
            if need > 0:
                sup.place(partitions=(
                    sorted(assigned) if assigned is not None else None
                ), count=need)
        print(_json.dumps({
            "supervising": fleet_dir,
            "manifest": manifest_path(fleet_dir),
            "pid": os.getpid(),
            "slots": len(sup.doc["slots"]),
            "adopted": len(recovered["adopted"]),
        }), flush=True)
        ticks = int(kwargs.get("ticks", 0) or 0)
        n = 0
        try:
            while True:
                sup.tick()
                n += 1
                if ticks and n >= ticks:
                    break
                _time.sleep(max(0.05, sup.heartbeat_s))
        except KeyboardInterrupt:
            pass
        return 0
    finally:
        stop_metrics_flush(final=bool(log_dir))
        if log_dir:
            counters.write(log_dir, device=False)  # control plane: never opens a backend
        telemetry.close()


def dereplicate_wrapper(wd_loc: str, genomes: list[str] | None = None, **kwargs) -> pd.DataFrame:
    """`dereplicate`: filter + cluster + choose + evaluate + analyze.
    Returns Wdb (the winners)."""
    from drep_tpu.utils import telemetry
    from drep_tpu.utils.profiling import counters, trace

    wd = _bring_up(wd_loc, "dereplicate", events=kwargs.pop("events", None))
    with trace(_trace_dir(wd_loc, kwargs.pop("profile", None))), counters.span("job"):
        bdb = _load_bdb(wd, genomes or [])
        if kwargs.get("run_tax"):
            from drep_tpu.bonus import validate_bonus_args

            validate_bonus_args(kwargs)  # fail fast, before hours of clustering
        # `stage:filter` in two halves, round the one read of every FASTA
        # (a `stage:ingest_or_cache` of its own): filter.py
        filtered, sketches = d_filter_wrapper(
            wd, bdb, genomeInfo=kwargs.pop("genomeInfo", None), **kwargs
        )
        with _record_a_drain(wd), counters.span("stage:cluster"):
            d_cluster_wrapper(wd, filtered, sketches=sketches, **kwargs)
        with counters.span("stage:choose"):
            wdb = d_choose_wrapper(wd, filtered, **kwargs)
        if kwargs.get("run_tax"):
            from drep_tpu.bonus import d_bonus_wrapper

            d_bonus_wrapper(
                wd, filtered,
                cent_index=kwargs.get("cent_index"),
                processes=kwargs.get("processes", 1),
            )
        with counters.span("stage:evaluate"):
            d_evaluate_wrapper(wd, **kwargs)
        if not kwargs.get("skip_plots", False):
            from drep_tpu.analyze import plot_all

            plot_all(wd)
        _finish_counters(wd)
    telemetry.close()
    get_logger().info("dereplicate finished: %d winners", len(wdb))
    return wdb
