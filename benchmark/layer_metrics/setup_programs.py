"""Executables the warm-up job built or loaded
(`process.first_job.compile.programs`: jax's backend-compile events). The
window's jobs build none: `compiles_in_window.batch` reads 0."""

from benchmark import setup_ledger


def read(run: dict):
    return setup_ledger.compiled(run, "programs")
