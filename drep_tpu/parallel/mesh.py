"""Device-mesh construction and multi-host initialization.

The reference has NO distributed runtime at all (SURVEY.md §2c — its only
parallelism is a local multiprocessing.Pool); this module is the greenfield
TPU equivalent: a 1-D mesh over all chips (ICI within a slice, DCN across
hosts once `jax.distributed.initialize` has run), over which the all-pairs
tile grid is sharded (parallel/allpairs.py).
"""

from __future__ import annotations

import os

import jax
from jax.sharding import Mesh

AXIS = "x"


def make_mesh(n_devices: int | None = None) -> Mesh:
    """1-D mesh over the first `n_devices` devices (default: all)."""
    devices = jax.devices()
    if n_devices is not None:
        if n_devices > len(devices):
            raise ValueError(f"requested {n_devices} devices, only {len(devices)} present")
        devices = devices[:n_devices]
    return jax.make_mesh((len(devices),), (AXIS,), devices=devices)


def make_local_mesh() -> Mesh:
    """1-D mesh over THIS process's devices only — no cross-process
    collectives can arise from it.

    Two regimes run on it (cluster/engines.py::_mesh_or_none):

    - degraded pods — after the elastic protocol declared a member dead,
      a global mesh would dispatch collectives that wait on the corpse
      forever, so survivors run replicated-local instead;
    - the SECONDARY engines on ANY multi-process pod (the `local_only`
      contract, ISSUE 4) — a process-local dispatch is independently
      retryable (parallel/faulttol.py retrying_call `local_only`), so a
      mid-batch failure retries on this process instead of desyncing the
      pod. The step-wise dense ring keeps the global mesh (it has its
      own per-block redoable unit — parallel/allpairs.py).

    A shard_map program over this mesh sees axis size = local device
    count, so its block decomposition matches any OTHER live process
    running the same program — replicated results are bit-identical
    across the pod."""
    devices = jax.local_devices()
    return jax.make_mesh((len(devices),), (AXIS,), devices=devices)


def initialize_distributed(coordinator: str | None = None, num_processes: int | None = None, process_id: int | None = None) -> None:
    """Multi-host bring-up (v5e-64-style pods; SURVEY.md §5.8), only when
    the operator configured one: explicit arguments, or
    ``JAX_COORDINATOR_ADDRESS`` in the environment (JAX's cluster
    detection then fills in whatever the arguments leave out).

    Anything else is a single-host run, and it returns at once: no network
    call, no coordination service. JAX's own argument-less auto-detect is
    deliberately NOT the default — on a TPU VM it queries the cloud
    metadata server, which a sealed machine cannot reach.
    """
    if (
        coordinator is None
        and num_processes is None
        and not os.environ.get("JAX_COORDINATOR_ADDRESS")
    ):
        return
    # must run BEFORE any backend use (jax.devices()/process_count() would
    # initialize the local backend and make distributed init impossible)
    try:
        jax.distributed.initialize(
            coordinator_address=coordinator,
            num_processes=num_processes,
            process_id=process_id,
        )
    except RuntimeError as e:
        # tolerable: (a) distributed already initialized (idempotent
        # re-entry), (b) local backend already up in this process (library
        # use after other JAX work — distributed init is impossible now and
        # the run is single-process by construction). Anything else must
        # surface — silently continuing single-host on a pod would compute
        # wrong results.
        msg = str(e).lower()
        if "already initialized" not in msg and "must be called before" not in msg:
            raise
