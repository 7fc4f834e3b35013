"""Seconds the warm-up job spent in the backend's compiler, XLA and Mosaic
(`process.first_job.compile.backend_compile_s`: jax's backend-compile events
less the cache retrievals inside them). On a warm persistent cache what is
left is the programs too quick for jax to store, and the cache's own key."""

from benchmark import setup_ledger


def read(run: dict):
    return setup_ledger.compiled(run, "backend_compile_s")
