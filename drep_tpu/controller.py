"""Top-level controller: parsed args -> workflow.

Reference parity: drep/controller.py::Controller (SURVEY.md §2; reference
mount empty) — maps subcommands to workflows, sets up logging, and hosts
check_dependencies (which here probes the TPU topology first, then the
optional external binaries for the subprocess fallback paths).
"""

from __future__ import annotations

import argparse
import logging

from drep_tpu.argparser import parse_args
from drep_tpu.utils.logger import get_logger, setup_logger
from drep_tpu.workflows import compare_wrapper, dereplicate_wrapper


class Controller:
    def parseArguments(self, args: argparse.Namespace) -> None:  # noqa: N802 — reference name
        op = args.operation
        if op == "check_dependencies":
            self.check_dependencies_operation()
            return
        kwargs = {k: v for k, v in vars(args).items() if k not in ("operation",)}
        if kwargs.pop("debug", False):
            setup_logger(None, verbosity=logging.DEBUG)
        # install the run's durable-I/O policy (--io_retries / --fsync)
        # BEFORE any stage runs: ingest's sketch shards and the workdir
        # sketch cache publish through utils/durableio.py long before the
        # cluster stage re-installs the same knobs in _ft_config
        from drep_tpu.utils import durableio

        durableio.configure(
            retries=kwargs.get("io_retries"),
            fsync=bool(kwargs.get("fsync")) or None,
        )
        if op == "index":
            self.index_operation(**kwargs)
            return
        wd_loc = kwargs.pop("work_directory")
        genomes = kwargs.pop("genomes", None)
        if op == "compare":
            self.compare_operation(wd_loc, genomes, **kwargs)
        elif op == "dereplicate":
            self.dereplicate_operation(wd_loc, genomes, **kwargs)
        else:
            raise ValueError(f"unknown operation {op!r}")

    def compare_operation(self, wd_loc, genomes, **kwargs):
        return compare_wrapper(wd_loc, genomes, **kwargs)

    def dereplicate_operation(self, wd_loc, genomes, **kwargs):
        return dereplicate_wrapper(wd_loc, genomes, **kwargs)

    def index_operation(self, **kwargs):
        """`index build|update|classify` — the incremental service mode
        (drep_tpu/index). classify prints one JSON verdict line per query
        to stdout (the machine-readable contract a service front-end
        consumes); build/update log their summaries."""
        from drep_tpu.workflows import (
            index_build_wrapper,
            index_classify_wrapper,
            index_maintenance_wrapper,
            index_route_wrapper,
            index_serve_wrapper,
            index_supervise_wrapper,
            index_update_wrapper,
        )

        sub = kwargs.pop("index_op")
        index_loc = kwargs.pop("index_directory")
        genomes = kwargs.pop("genomes", None)
        if sub == "build":
            return index_build_wrapper(index_loc, genomes, **kwargs)
        if sub == "update":
            return index_update_wrapper(index_loc, genomes, **kwargs)
        if sub == "serve":
            # blocks until drained (SIGTERM/SIGINT); exit 0 is the drain
            # contract, same as the elastic pod's graceful preemption
            return index_serve_wrapper(index_loc, genomes, **kwargs)
        if sub == "route":
            # the fleet front door: same drain contract as serve
            return index_route_wrapper(index_loc, genomes, **kwargs)
        if sub == "supervise":
            # the fleet supervisor: replica lifecycle against the
            # durable fleet.json manifest (serve/supervisor.py)
            return index_supervise_wrapper(index_loc, **kwargs)
        if sub in ("split", "merge", "compact"):
            # the transactional index lifecycle (index/maintenance.py):
            # crash-safe at every phase, resumable by any later pass
            return index_maintenance_wrapper(index_loc, op=sub, **kwargs)
        if sub == "classify":
            import json
            import sys

            verdicts = index_classify_wrapper(index_loc, genomes, **kwargs)
            for v in verdicts:
                print(json.dumps(v), file=sys.stdout, flush=True)
            return verdicts
        raise ValueError(f"unknown index operation {sub!r}")

    def check_dependencies_operation(self) -> None:
        setup_logger(None)
        logger = get_logger()
        import jax

        devices = jax.devices()
        logger.info("JAX backend: %s; %d device(s)", jax.default_backend(), len(devices))
        for d in devices:
            logger.info("  device: %s", d)
        from drep_tpu.cluster.external import EXTERNAL_SUITE, find_program

        for name in sorted(EXTERNAL_SUITE):
            path, version = find_program(name)
            if path is None:
                status = "NOT FOUND (subprocess fallback unavailable; TPU engines unaffected)"
            else:
                status = f"{path}  ({version})" if version else path
            logger.info("  external %-14s %s", name, status)


def main(argv: list[str] | None = None) -> None:
    from drep_tpu.errors import UserInputError
    from drep_tpu.parallel.faulttol import PodDrained

    try:
        Controller().parseArguments(parse_args(argv))
    except PodDrained as e:
        # graceful preemption (ISSUE 9): this member published its
        # planned-departure note at a safe boundary and the pod re-deals
        # its unfinished work immediately — exit 0 is the drain contract
        # (the orchestrator must see a clean exit, not a failure to
        # restart-loop on; shard-level checkpoints keep the finished work)
        import sys

        get_logger().warning("drained cleanly: %s", e)
        sys.exit(0)
    except UserInputError as e:
        # user-input errors (bad paths, non-FASTA input, conflicting
        # flags) end as one `!!!` line, not a traceback — the reference's
        # user-facing-warning convention (SURVEY.md §5.5). Only the
        # dedicated type is caught: an internal ValueError deep in
        # clustering must keep its traceback.
        import sys

        get_logger().error("!!! %s", e)
        sys.exit(1)


if __name__ == "__main__":
    main()
