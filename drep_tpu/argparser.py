"""CLI argument tree.

Reference parity: drep/argumentParser.py (SURVEY.md §2; reference mount
empty) — subcommands `compare`, `dereplicate`, `check_dependencies`, with
the reference's flag groups and names (FILTERING, GENOME COMPARISON,
CLUSTERING, SCORING, WARNINGS) plus the TPU-native additions
(`--primary_algorithm jax_mash`, `--S_algorithm jax_ani` are the defaults
here; the reference's subprocess algorithms remain selectable).
"""

from __future__ import annotations

import argparse

from drep_tpu import __version__


def build_parser() -> argparse.ArgumentParser:
    parser = argparse.ArgumentParser(
        prog="drep-tpu",
        description="TPU-native genome dereplication and comparison (dRep-compatible pipeline)",
    )
    parser.add_argument("--version", action="version", version=f"drep-tpu {__version__}")
    sub = parser.add_subparsers(dest="operation", required=True)

    def add_common(p: argparse.ArgumentParser, with_filter: bool, with_scoring: bool):
        p.add_argument("work_directory", help="directory for tables, figures, logs (the resume checkpoint)")
        p.add_argument("-g", "--genomes", nargs="*", default=None, help="genome FASTA files")
        p.add_argument("-p", "--processes", type=int, default=6)
        p.add_argument("-d", "--debug", action="store_true")

        comp = p.add_argument_group("GENOME COMPARISON")
        comp.add_argument("--primary_algorithm", default="jax_mash",
                          help="primary (coarse) comparison engine [jax_mash|mash]")
        comp.add_argument("--S_algorithm", default="jax_ani",
                          help="secondary (ANI) comparison engine "
                               "[jax_ani|fastANI|ANImf|ANIn|gANI|goANI]")
        comp.add_argument("-ms", "--MASH_sketch", type=int, default=1000)
        comp.add_argument("--scale", type=int, default=200,
                          help="FracMinHash scale for jax_ani (smaller = more precise)")
        comp.add_argument("-k", "--kmer_size", type=int, default=21)
        comp.add_argument("--hash", default="splitmix64",
                          choices=["splitmix64", "murmur3"],
                          help="k-mer hash: splitmix64 (fastest) or murmur3 "
                               "(Mash-compatible for k>16 — sketches comparable "
                               "to `mash info` output)")
        comp.add_argument("--SkipMash", action="store_true")
        comp.add_argument("--SkipSecondary", action="store_true")
        comp.add_argument("-nc", "--cov_thresh", type=float, default=0.1)

        clus = p.add_argument_group("CLUSTERING")
        clus.add_argument("-pa", "--P_ani", type=float, default=0.9)
        clus.add_argument("-sa", "--S_ani", type=float, default=0.95)
        clus.add_argument("--clusterAlg", default="average",
                          choices=["average", "single", "complete", "weighted", "ward"])
        clus.add_argument("--multiround_primary_clustering", action="store_true")
        clus.add_argument("--primary_chunksize", type=int, default=5000)
        clus.add_argument("--greedy_secondary_clustering", action="store_true")
        clus.add_argument("--run_tertiary_clustering", action="store_true",
                          help="re-compare secondary-cluster representatives across "
                               "primary-cluster boundaries and merge co-clustering groups")
        clus.add_argument("--streaming_primary", action="store_true",
                          help="out-of-core primary clustering: thresholded edge stream "
                               "with per-block checkpoints, clustered per --clusterAlg "
                               "(average via sparse UPGMA on the retained edge graph, or "
                               "single via connected components); auto-enabled beyond "
                               "--streaming_threshold")
        clus.add_argument("--streaming_block", type=int, default=1024)
        clus.add_argument("--streaming_threshold", type=int, default=30_000,
                          help="genome count beyond which the primary stage streams "
                               "instead of materializing the N^2 matrix")
        clus.add_argument("--primary_prune", default="off",
                          choices=["off", "lsh"],
                          help="sub-quadratic streaming primary: 'lsh' bands the "
                               "MinHash sketches into LSH buckets and dispatches "
                               "only tiles containing a candidate pair (recall "
                               "1.0 at the retention bound by construction — "
                               "retained edges are bit-identical to the dense "
                               "schedule; see README 'Candidate pruning'). "
                               "Default off")
        clus.add_argument("--prune_bands", type=int, default=0,
                          help="LSH band count: 0 (default) buckets on individual "
                               "sketch ids (tightest candidates; the derived "
                               "shared-count threshold applies); B>0 splits the "
                               "id space into B ranges (smaller join, coarser "
                               "candidates, threshold pinned to 1)")
        clus.add_argument("--prune_min_shared", type=int, default=0,
                          help="conservative floor on the candidate threshold: "
                               "0 (default) auto-derives the minimum shared-hash "
                               "count from the retention bound; an explicit "
                               "value lowers it (1 = most conservative). Values "
                               "above the derivation are clamped down — they "
                               "would break the recall-1.0 contract")
        clus.add_argument("--prune_join_chunk", type=int, default=0,
                          help="memory bound (in candidate codes) for the LSH "
                               "bucket join's host expansion: 0 (default) joins "
                               "everything in one pass; >0 chunks the expansion "
                               "and folds counts incrementally — identical "
                               "candidate set, bounded host RSS (for >1M-genome "
                               "runs on thin hosts)")

        warn = p.add_argument_group("WARNINGS")
        warn.add_argument("--warn_dist", type=float, default=0.25)
        warn.add_argument("--warn_sim", type=float, default=0.98)
        warn.add_argument("--warn_aln", type=float, default=0.25)

        tpu = p.add_argument_group("TPU EXECUTION")
        tpu.add_argument("--mesh_shape", type=int, default=None,
                         help="shard all-pairs tiles over this many devices (default: all)")
        tpu.add_argument("--skip_plots", action="store_true")
        tpu.add_argument("--no_overlap_ingest", dest="overlap_ingest",
                         action="store_false", default=True,
                         help="disable overlapping the streaming kernel's XLA "
                              "compile with host ingest (results are identical "
                              "either way; this exists for debugging)")
        tpu.add_argument("--fault_retries", type=int, default=2,
                         help="re-dispatch attempts after a failed/wedged device "
                              "call before quarantining the device or falling "
                              "back to CPU recompute (parallel/faulttol.py)")
        tpu.add_argument("--dispatch_timeout", type=float, default=0.0,
                         help="per-dispatch watchdog in seconds: a device call "
                              "exceeding it counts as failed and is retried on "
                              "another device. 0 (default) auto-derives the "
                              "deadline from the run's own tile latencies "
                              "(20x rolling median, floor 30s, warmup excluded; "
                              "a generous 300s bound covers the compile warmup; "
                              "reported as derived_dispatch_timeout_s in "
                              "perf_counters.json); explicit positive values "
                              "are authoritative; negative disables")
        tpu.add_argument("--max_dead_processes", type=int, default=1,
                         help="pod-member deaths the elastic protocol tolerates "
                              "per run (heartbeat detection + ownership-epoch "
                              "re-assignment across the survivors — streaming "
                              "stripes AND dense-ring blocks) before aborting; "
                              "heartbeat cadence via DREP_TPU_HEARTBEAT_S "
                              "(0 disables)")
        tpu.add_argument("--max_joins", type=int, default=0,
                         help="mid-run JOIN admissions the elastic pod accepts "
                              "per stage (scale-UP elasticity): a new process "
                              "started against the same checkpoint dir with "
                              "DREP_TPU_POD_JOIN=auto (or an explicit id) "
                              "publishes a join-request note, the lowest-live "
                              "leader admits it at a stripe/ring-step boundary "
                              "via an epoch bump, and unfinished work re-deals "
                              "over the GROWN live set — final edges/matrices "
                              "stay bit-identical to a fixed-membership run. "
                              "0 (default) refuses joins")
        tpu.add_argument("--drain_grace_s", type=float, default=30.0,
                         help="graceful-preemption window: SIGTERM flags the "
                              "process for a planned departure, honored at the "
                              "next stripe/ring-step boundary (departure note "
                              "published, exit 0, peers re-deal immediately — "
                              "no heartbeat-staleness wait); if nothing "
                              "consumes the flag within this many seconds the "
                              "process publishes the note best-effort and "
                              "exits 0 anyway (preemption grants no extension)")
        tpu.add_argument("--io_retries", type=int, default=None,
                         help="transient shared-filesystem I/O errors "
                              "(EIO/ESTALE/ETIMEDOUT) retried per durable "
                              "read/write with exponential backoff before "
                              "giving up (utils/durableio.py; default from "
                              "DREP_TPU_IO_RETRIES, 3). Retries are counted "
                              "honestly (io_retries in perf_counters.json); "
                              "ENOSPC never retries — it raises an actionable "
                              "error naming the store and bytes needed")
        tpu.add_argument("--fsync", action="store_true",
                         help="fsync every durable publish (tmp file before "
                              "the rename, directory after) so a host power "
                              "loss cannot revert a checkpoint the run "
                              "already trusted — some IOPS cost on shared "
                              "filesystems; DREP_TPU_FSYNC=1 is equivalent")
        tpu.add_argument("--events", default=None, choices=["off", "on"],
                         help="structured event tracing (utils/telemetry.py): "
                              "'on' writes durable append-only per-process "
                              "event logs <wd>/log/events.p<N>.jsonl — spans "
                              "for stages/stripes/ring-steps, instants for "
                              "faults and elastic membership verdicts — read "
                              "by tools/trace_report.py (merged Chrome trace "
                              "+ text forensics) and scrub-safe (a torn "
                              "final line is crash evidence, not damage). "
                              "Default off: zero overhead, zero files. "
                              "DREP_TPU_EVENTS=on is equivalent; an explicit "
                              "flag wins over the env")
        tpu.add_argument("--profile", nargs="?", const="auto", default=None,
                         help="record a jax.profiler trace of the whole job "
                              "(optionally to the given directory; default "
                              "<wd>/log/jax_trace): device operations, and the "
                              "program's own spans as drep:<span> events on the "
                              "host plane, on one clock. Python frames are off. "
                              "The benchmark (benchmark/tracered.py) reduces "
                              "the same trace. perf_counters.json is always written")

        if with_filter:
            tax = p.add_argument_group("TAXONOMY")
            tax.add_argument("--run_tax", action="store_true",
                             help="assign per-genome taxonomy with centrifuge (Tdb)")
            tax.add_argument("--cent_index", default=None,
                             help="centrifuge index prefix (required with --run_tax)")

            filt = p.add_argument_group("FILTERING")
            filt.add_argument("-l", "--length", type=int, default=50_000)
            filt.add_argument("-comp", "--completeness", type=float, default=75.0)
            filt.add_argument("-con", "--contamination", type=float, default=25.0)
            filt.add_argument("--ignoreGenomeQuality", action="store_true")
            filt.add_argument("--genomeInfo", default=None,
                              help="CSV with genome,completeness,contamination")
            filt.add_argument("--checkM_method", default="lineage_wf",
                              choices=["lineage_wf", "taxonomy_wf"],
                              help="CheckM workflow when quality comes from "
                                   "checkm (reference d_filter option)")

        if with_scoring:
            sc = p.add_argument_group("SCORING")
            sc.add_argument("-comW", "--completeness_weight", type=float, default=1.0)
            sc.add_argument("-conW", "--contamination_weight", type=float, default=5.0)
            sc.add_argument("-strW", "--strain_heterogeneity_weight", type=float, default=1.0)
            sc.add_argument("-N50W", "--N50_weight", type=float, default=0.5)
            sc.add_argument("-sizeW", "--size_weight", type=float, default=0.0)
            sc.add_argument("-centW", "--centrality_weight", type=float, default=1.0)
            sc.add_argument("--extra_weight_table", default=None)

    def add_index_io(p: argparse.ArgumentParser):
        p.add_argument("index_directory", help="the long-lived genome index")
        p.add_argument("-g", "--genomes", nargs="*", default=None, help="genome FASTA files")
        p.add_argument("-p", "--processes", type=int, default=6)
        p.add_argument("-d", "--debug", action="store_true")
        p.add_argument("--io_retries", type=int, default=None,
                       help="transient shared-filesystem I/O retry budget "
                            "(utils/durableio.py; same knob as the pipeline)")
        p.add_argument("--fsync", action="store_true",
                       help="fsync every durable publish (DREP_TPU_FSYNC=1 equivalent)")

    idx_p = sub.add_parser(
        "index",
        help="incremental service mode: a long-lived genome index with "
             "build/update/classify entrypoints",
    )
    isub = idx_p.add_subparsers(dest="index_op", required=True)

    b = isub.add_parser(
        "build",
        help="create generation 0: snapshot a completed run's workdir "
             "(--work_directory) or bootstrap from FASTAs (-g)",
    )
    add_index_io(b)
    b.add_argument("--work_directory", default=None,
                   help="completed compare/dereplicate workdir to snapshot "
                        "(sketches, edge graph, labels, winners); omit to "
                        "bootstrap from -g FASTAs instead")
    b.add_argument("--partitions", type=int, default=0,
                   help="create a FEDERATED index: split the genome space "
                        "into this many range partitions (each a full index "
                        "store) under one atomically-published meta-manifest "
                        "(index/federation.py). Bootstrap (-g) builds only; "
                        "routing is by sketch-derived range code, pinned at "
                        "creation. 0/absent = ordinary single-store index")
    b.add_argument("--fed_pods", type=int, default=None,
                   help="with --partitions: run per-partition work as up to "
                        "this many concurrent subprocess pods — including "
                        "generation-0 materialization (sketches + pinned "
                        "params ride a --params_file handoff into each pod)")
    bp = b.add_argument_group("INDEX PARAMETERS (bootstrap build only; "
                              "workdir builds pin the source run's)")
    bp.add_argument("-pa", "--P_ani", type=float, default=None)
    bp.add_argument("-sa", "--S_ani", type=float, default=None)
    bp.add_argument("-nc", "--cov_thresh", type=float, default=None)
    bp.add_argument("--clusterAlg", default=None, choices=["average", "single"])
    bp.add_argument("-ms", "--MASH_sketch", type=int, default=None)
    bp.add_argument("--scale", type=int, default=None)
    bp.add_argument("-k", "--kmer_size", type=int, default=None)
    bp.add_argument("--hash", default=None, choices=["splitmix64", "murmur3"])
    bp.add_argument("--warn_dist", type=float, default=None)
    bp.add_argument("-l", "--length", type=int, default=None,
                    help="minimum genome length admitted (the filter stage's rule)")
    bp.add_argument("--streaming_block", type=int, default=None)

    u = isub.add_parser(
        "update",
        help="admit K new genomes: sketch K, compare K x N through the "
             "streaming tile executor, re-cluster only touched clusters, "
             "publish the next generation (crash-resumable; with no -g "
             "this is a pure heal pass)",
    )
    add_index_io(u)
    u.add_argument("--primary_prune", default="off", choices=["off", "lsh"],
                   help="LSH-banded candidate pruning for the K x N rect "
                        "compare: only column blocks containing a candidate "
                        "pair are dispatched (K x N -> K x bucket_occupancy; "
                        "recall 1.0 at the index's retention bound, results "
                        "identical). Per-invocation knob — never pinned in "
                        "the manifest")
    u.add_argument("--prune_bands", type=int, default=0,
                   help="LSH band count (0 = per-id buckets; same semantics "
                        "as the pipeline flag)")
    u.add_argument("--prune_min_shared", type=int, default=0,
                   help="conservative candidate-threshold floor (0 = "
                        "auto-derive; same semantics as the pipeline flag)")
    u.add_argument("--prune_join_chunk", type=int, default=0,
                   help="memory bound for the bucket join's host expansion "
                        "(0 = one-pass; same semantics as the pipeline flag)")
    u.add_argument("--fed_pods", type=int, default=None,
                   help="FEDERATED index only: run per-partition updates as "
                        "up to this many CONCURRENT subprocess pods (each the "
                        "ordinary `index update` on one partition store, "
                        "crash-resumable on its own). Default: "
                        "DREP_TPU_FED_PODS (0 = in-process, one at a time)")
    u.add_argument("--params_file", default=None, metavar="NPZ",
                   help="sketches+params handoff from a federated router "
                        "(index/federation.py write_params_handoff): the "
                        "routed batch's sketches and the federation's PINNED "
                        "params ride this file, so a partition pod never "
                        "re-sketches its batch and an EMPTY partition can "
                        "materialize generation 0 in a pod (params that the "
                        "CLI bootstrap cannot express). With it, -g is "
                        "ignored — the handoff IS the batch")

    c = isub.add_parser(
        "classify",
        help="membership query: the cluster/winner each FASTA would join, "
             "answered from the index alone (read-only, no re-sketching "
             "of indexed genomes)",
    )
    add_index_io(c)
    c.add_argument("--primary_prune", default="off", choices=["off", "lsh"],
                   help="LSH-banded candidate pruning for the query-vs-index "
                        "rect compare: a query-vs-index bucket join restricts "
                        "the K x N compare to candidate-occupied columns "
                        "(recall 1.0 at the index's retention bound — "
                        "verdicts identical to the dense classify). "
                        "Execution knob only; the index is untouched either "
                        "way (classify stays read-only)")
    c.add_argument("--prune_bands", type=int, default=0,
                   help="LSH band count (0 = per-id buckets; same semantics "
                        "as the pipeline flag)")
    c.add_argument("--prune_min_shared", type=int, default=0,
                   help="conservative candidate-threshold floor (0 = "
                        "auto-derive; same semantics as the pipeline flag)")
    c.add_argument("--prune_join_chunk", type=int, default=0,
                   help="memory bound for the bucket join's host expansion "
                        "(0 = one-pass; same semantics as the pipeline flag)")

    def add_maint_io(p: argparse.ArgumentParser):
        p.add_argument("index_directory", help="the long-lived genome index")
        p.add_argument("-p", "--processes", type=int, default=6)
        p.add_argument("-d", "--debug", action="store_true")
        p.add_argument("--io_retries", type=int, default=None,
                       help="transient shared-filesystem I/O retry budget "
                            "(utils/durableio.py; same knob as the pipeline)")
        p.add_argument("--fsync", action="store_true",
                       help="fsync every durable publish (DREP_TPU_FSYNC=1 "
                            "equivalent)")

    sp = isub.add_parser(
        "split",
        help="index lifecycle: bisect a FEDERATED partition's range at "
             "its sketch-code median into two child partition stores — a "
             "staged meta-manifest transaction (children materialize "
             "under pending/, commit is one atomic federation.json "
             "publish, the parent is gc'd only after); crash-safe at "
             "every phase, and an ordinary hot-swap to live readers",
    )
    add_maint_io(sp)
    sp.add_argument("--pid", type=int, required=True,
                    help="the partition id to split (pids are renumbered "
                         "densely by range order at commit)")

    mg = isub.add_parser(
        "merge",
        help="index lifecycle: fold two ADJACENT federated partitions "
             "into one (the split's inverse — same staged transaction, "
             "same crash-safety contract)",
    )
    add_maint_io(mg)
    mg.add_argument("--pids", type=int, nargs=2, required=True,
                    metavar=("PID_A", "PID_B"),
                    help="the two adjacent partition ids to fold")

    cp = isub.add_parser(
        "compact",
        help="index lifecycle: LSM-style generation compaction — fold a "
             "store's N sketch/edge/state shard generations into ONE "
             "freshly-written generation and gc the superseded shards "
             "(federated roots compact per partition and commit through "
             "the meta-manifest; verdicts/updates are byte-identical to "
             "the uncompacted store — the pinned oracle)",
    )
    add_maint_io(cp)
    cp.add_argument("--pid", type=int, default=None,
                    help="compact only this federated partition (default: "
                         "every partition past --min_generations)")
    cp.add_argument("--min_generations", type=int, default=None,
                    help="without --pid: compact partitions holding at "
                         "least this many shard generations (default: "
                         "DREP_TPU_COMPACT_MIN_SHARDS)")

    s = isub.add_parser(
        "serve",
        help="resident serving tier: a long-lived daemon that loads the "
             "index once, dynamically batches concurrent classify "
             "queries over a local socket into one K x N rect compare, "
             "hot-swaps to newly published generations, and drains "
             "gracefully on SIGTERM (verdicts identical to one-shot "
             "classify; the index stays byte-for-byte untouched)",
    )
    s.add_argument("index_directory", help="the long-lived genome index")
    s.add_argument("-p", "--processes", type=int, default=1,
                   help="sketching processes per batch (queries are small; "
                        "1 keeps the daemon single-sketcher)")
    s.add_argument("-d", "--debug", action="store_true")
    s.add_argument("--io_retries", type=int, default=None,
                   help="transient shared-filesystem I/O retry budget "
                        "(utils/durableio.py; same knob as the pipeline)")
    s.add_argument("--fsync", action="store_true",
                   help="fsync every durable publish (DREP_TPU_FSYNC=1 "
                        "equivalent; the daemon itself never writes the "
                        "index — this covers its log/metrics dir)")
    s.add_argument("--socket", default=None, metavar="PATH",
                   help="serve on a unix-domain socket at PATH instead of TCP")
    s.add_argument("--host", default="127.0.0.1",
                   help="TCP bind host (default 127.0.0.1 — the daemon is "
                        "a LOCAL front door; put a real ingress in front "
                        "for anything wider)")
    s.add_argument("--port", type=int, default=0,
                   help="TCP bind port (default 0 = OS-assigned; the bound "
                        "address is printed as the JSON ready line)")
    s.add_argument("--max_queue", type=int, default=256,
                   help="admission-queue bound: a request arriving at a "
                        "full queue is refused IMMEDIATELY with a "
                        "retry_after_s hint (backpressure beats unbounded "
                        "buffering). Default 256")
    s.add_argument("--max_batch", type=int, default=64,
                   help="most queries coalesced into one rectangular "
                        "compare (1 = unbatched FIFO, the loadgen's "
                        "reference mode). Default 64")
    s.add_argument("--batch_window_ms", type=float, default=5.0,
                   help="how long the first waiting query holds the batch "
                        "open for late arrivals (the latency cost of "
                        "coalescing when idle). Default 5ms")
    s.add_argument("--poll_generation_s", type=float, default=2.0,
                   help="manifest re-read cadence for generation hot-swap: "
                        "a published generation G+1 is adopted between "
                        "batches within this many seconds. Default 2s")
    s.add_argument("--resident_mb", type=int, default=None,
                   help="FEDERATED index only: byte budget (MiB) for "
                        "resident partition sketch payloads — the streaming "
                        "per-partition classify path keeps only hot "
                        "partitions loaded (LRU eviction past the budget). "
                        "Default: DREP_TPU_SERVE_RESIDENT_MB (0 = unlimited)")
    s.add_argument("--log_dir", default=None,
                   help="home for the daemon's logs, Prometheus textfile "
                        "flush (DREP_TPU_METRICS_FLUSH_S), and event "
                        "traces. NEVER the index directory — default is "
                        "console-only logging, no files anywhere")
    s.add_argument("--events", default=None, choices=["off", "on"],
                   help="structured event tracing of the serve timeline "
                        "(serve_batch spans, generation_swap instants) "
                        "into --log_dir; tools/trace_report.py renders "
                        "the server timeline. Needs --log_dir")
    s.add_argument("--primary_prune", default="off", choices=["off", "lsh"],
                   help="LSH-banded candidate pruning applied PER BATCH to "
                        "the query-vs-index rect compare (same candidate "
                        "set `index update` consumes; verdicts identical)")
    s.add_argument("--prune_bands", type=int, default=0,
                   help="LSH band count (0 = per-id buckets; same semantics "
                        "as the pipeline flag)")
    s.add_argument("--prune_min_shared", type=int, default=0,
                   help="conservative candidate-threshold floor (0 = "
                        "auto-derive; same semantics as the pipeline flag)")
    s.add_argument("--prune_join_chunk", type=int, default=0,
                   help="memory bound for the bucket join's host expansion "
                        "(0 = one-pass; same semantics as the pipeline flag)")

    r = isub.add_parser(
        "route",
        help="fleet front door (stateless router): speaks the serve "
             "protocol in front of N `index serve` replicas, routes each "
             "query by its coarse code summary to replicas with cache "
             "affinity, scatter/gathers multi-partition queries through "
             "the exact federated merge (verdicts byte-identical to one "
             "daemon), generation-fences the fan-out, hedges stragglers, "
             "and degrades to stamped PARTIAL verdicts — never a crash — "
             "under replica loss or overload",
    )
    r.add_argument("index_directory",
                   help="the FEDERATED root the fleet serves (the router "
                        "loads only its spine + routing bitmaps)")
    r.add_argument("--replica", action="append", default=[], metavar="ADDR[=PIDS]",
                   help="one serve replica: host:port or socket path, "
                        "optionally '=' a partition assignment as ids/"
                        "inclusive ranges (0-2,5). No assignment = serves "
                        "every partition. Repeatable; replicas can also "
                        "join/leave a running router via the fleet op")
    r.add_argument("-p", "--processes", type=int, default=1,
                   help="sketching processes per batch (queries are small; "
                        "1 keeps the router single-sketcher)")
    r.add_argument("-d", "--debug", action="store_true")
    r.add_argument("--io_retries", type=int, default=None,
                   help="transient shared-filesystem I/O retry budget "
                        "(utils/durableio.py; same knob as the pipeline)")
    r.add_argument("--socket", default=None, metavar="PATH",
                   help="serve on a unix-domain socket at PATH instead of TCP")
    r.add_argument("--host", default="127.0.0.1",
                   help="TCP bind host (default 127.0.0.1)")
    r.add_argument("--port", type=int, default=0,
                   help="TCP bind port (default 0 = OS-assigned; printed "
                        "as the JSON ready line)")
    r.add_argument("--max_inflight", type=int, default=None,
                   help="bounded admission: max queued classify requests "
                        "before the router sheds load with a backpressure "
                        "refusal. Default DREP_TPU_ROUTER_MAX_INFLIGHT")
    r.add_argument("--max_batch", type=int, default=64,
                   help="most queries routed as one scatter/forward round "
                        "(the inherited dynamic batch window). Default 64")
    r.add_argument("--batch_window_ms", type=float, default=5.0,
                   help="batch-formation window (default 5ms)")
    r.add_argument("--poll_generation_s", type=float, default=2.0,
                   help="meta-manifest re-read cadence for the router's own "
                        "generation hot-swap (a fenced gather reloads "
                        "sooner when the fleet is ahead). Default 2s")
    r.add_argument("--leg_timeout_s", type=float, default=None,
                   help="per-leg socket deadline for one scatter/forward "
                        "dispatch. Default DREP_TPU_ROUTER_LEG_TIMEOUT_S")
    r.add_argument("--hedge_delay_s", type=float, default=None,
                   help="straggler hedge: duplicate an unanswered leg to a "
                        "second capable replica after this long (first "
                        "answer wins). Default DREP_TPU_ROUTER_HEDGE_DELAY_S")
    r.add_argument("--probe_interval_s", type=float, default=1.0,
                   help="replica /healthz poll cadence feeding the "
                        "healthy->suspect->ejected table. Default 1s")
    r.add_argument("--probe_backoff_s", type=float, default=None,
                   help="first reprobe delay after an ejection (doubles to "
                        "DREP_TPU_SERVE_PROBE_MAX_S). Default "
                        "DREP_TPU_ROUTER_PROBE_BACKOFF_S")
    r.add_argument("--fleet_manifest", default=None, metavar="PATH",
                   help="the fleet supervisor's durable fleet.json (or its "
                        "directory): the router REBUILDS its replica table "
                        "from it at startup — membership survives a router "
                        "restart with zero `fleet join` replays — and "
                        "reports the supervision tree in /healthz. "
                        "Read-only; only `index supervise` writes it")
    r.add_argument("--resident_mb", type=int, default=None,
                   help="byte budget (MiB) for the router's OWN lazily "
                        "loaded component sketches (the merge's secondary "
                        "recluster stage; the heavy rect compares run on "
                        "the replicas). Default DREP_TPU_SERVE_RESIDENT_MB")
    r.add_argument("--log_dir", default=None,
                   help="home for the router's logs/metrics/events — "
                        "NEVER the index directory (read-only contract)")
    r.add_argument("--events", default=None, choices=["off", "on"],
                   help="structured event tracing (replica_suspect/"
                        "ejected/recovered, fleet_join/leave, fenced "
                        "generation_swap instants) into --log_dir")
    r.add_argument("--primary_prune", default="off", choices=["off", "lsh"],
                   help="LSH candidate pruning, forwarded to every scatter "
                        "leg so the whole fleet prunes identically")
    r.add_argument("--prune_bands", type=int, default=0,
                   help="LSH band count (same semantics as the pipeline flag)")
    r.add_argument("--prune_min_shared", type=int, default=0,
                   help="candidate-threshold floor (same semantics as the "
                        "pipeline flag)")
    r.add_argument("--prune_join_chunk", type=int, default=0,
                   help="bucket-join memory bound (same semantics as the "
                        "pipeline flag)")

    v = isub.add_parser(
        "supervise",
        help="fleet supervisor: owns replica process lifecycle against a "
             "durable fleet.json manifest — spawn with a startup probe "
             "deadline, heartbeat liveness over /healthz, restart on "
             "death with decorrelated backoff, crash-loop QUARANTINE "
             "after K deaths in a window, graceful drain with SIGKILL "
             "escalation. Crash-recovers by ADOPTING still-live orphans "
             "from the manifest (never double-spawns); a restarted "
             "router rebuilds membership from the same file",
    )
    v.add_argument("index_directory",
                   help="the FEDERATED root the supervised fleet serves "
                        "(the manifest lives under <root>/fleet unless "
                        "--fleet_dir says otherwise)")
    v.add_argument("--fleet_dir", default=None, metavar="DIR",
                   help="home for fleet.json + its generation snapshots. "
                        "Default <index_directory>/fleet — the one "
                        "control-plane subtree tools/scrub_store.py "
                        "classifies (stale generations and dead-pid "
                        "slots are stale_membership, never damage)")
    v.add_argument("--spawn", default=None, metavar="CMD",
                   help="full `index serve` command line for ONE replica "
                        "('{partitions}' substituted with a slot's comma "
                        "list, removed for unscoped slots). Required to "
                        "actually spawn; without it the supervisor only "
                        "adopts/retires what the manifest records")
    v.add_argument("--replica", action="append", default=[],
                   metavar="N[=PIDS]",
                   help="initial placement: spawn N unscoped replicas, or "
                        "'N=0-2,5' to scope each to a partition set. "
                        "Repeatable; applied once at startup for slots "
                        "the manifest doesn't already record")
    v.add_argument("--router", default=None, metavar="ADDR",
                   help="a running `index route` front door to announce "
                        "fleet join/leave to (advisory: a dead router "
                        "rebuilds from the manifest when it returns)")
    v.add_argument("--heartbeat_s", type=float, default=None,
                   help="liveness tick cadence (pid poll + /healthz). "
                        "Default DREP_TPU_SUP_HEARTBEAT_S")
    v.add_argument("--backoff_max_s", type=float, default=None,
                   help="decorrelated restart backoff cap. Default "
                        "DREP_TPU_SUP_BACKOFF_MAX_S")
    v.add_argument("--crashloop_k", type=int, default=None,
                   help="deaths inside the window that QUARANTINE a slot "
                        "(0 disables). Default DREP_TPU_SUP_CRASHLOOP_K")
    v.add_argument("--crashloop_window_s", type=float, default=None,
                   help="crash-loop detection window. Default "
                        "DREP_TPU_SUP_CRASHLOOP_WINDOW_S")
    v.add_argument("--drain_deadline_s", type=float, default=None,
                   help="seconds after SIGTERM before a draining replica "
                        "is SIGKILLed (escalations counted). Default "
                        "DREP_TPU_SUP_DRAIN_DEADLINE_S")
    v.add_argument("--startup_deadline_s", type=float, default=None,
                   help="seconds a fresh spawn gets to print its ready "
                        "line before it books a death. Default "
                        "DREP_TPU_SUP_STARTUP_DEADLINE_S")
    v.add_argument("--ticks", type=int, default=0,
                   help="exit after this many supervision ticks (0 = run "
                        "until interrupted; the test harness uses this)")
    v.add_argument("-d", "--debug", action="store_true")
    v.add_argument("--io_retries", type=int, default=None,
                   help="transient shared-filesystem I/O retry budget "
                        "(utils/durableio.py; same knob as the pipeline)")
    v.add_argument("--log_dir", default=None,
                   help="home for the supervisor's logs and event traces "
                        "— NEVER the index directory")
    v.add_argument("--events", default=None, choices=["off", "on"],
                   help="structured event tracing (supervisor_spawn/"
                        "death/quarantine/escalation instants) into "
                        "--log_dir")

    cmp_p = sub.add_parser("compare", help="cluster genomes without dereplicating")
    add_common(cmp_p, with_filter=False, with_scoring=False)

    der_p = sub.add_parser("dereplicate", help="filter, cluster, and pick winner genomes")
    add_common(der_p, with_filter=True, with_scoring=True)

    sub.add_parser("check_dependencies", help="probe TPU topology and optional external binaries")

    return parser


def parse_args(argv: list[str] | None = None) -> argparse.Namespace:
    return build_parser().parse_args(argv)
