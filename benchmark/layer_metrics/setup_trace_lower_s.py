"""Seconds the warm-up job spent tracing its programs and lowering them to
MLIR (`process.first_job.compile.trace_s + lower_s`): paid by every process,
whether the persistent compile cache is warm or cold."""

from benchmark import setup_ledger


def read(run: dict):
    return setup_ledger.compiled(run, "trace_s", "lower_s")
