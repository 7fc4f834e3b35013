"""Per-primary-cluster checkpointing of the secondary (ANI) stage.

The reference's resume is stage-granular: a crash mid-secondary loses every
finished cluster because Ndb/Cdb are only written at the end
(drep/d_cluster — SURVEY.md §5.4; reference mount empty). Here each primary
cluster's secondary result (Ndb rows, labels, linkage) is persisted the
moment it finishes, keyed by a fingerprint of the clustering arguments AND
the primary partition — so a preempted 100k-MAG run resumes exactly where
it stopped, and any change to flags or upstream clustering invalidates the
cache wholesale.
"""

from __future__ import annotations

import json
import os
from typing import Any

import numpy as np

from drep_tpu.cluster.pairs import NdbColumns
from drep_tpu.utils.ckptmeta import content_fingerprint, open_checkpoint_dir
from drep_tpu.utils.logger import get_logger


class SecondaryCheckpoint:
    """Cluster-granular checkpoint store under
    ``<wd>/data/secondary_checkpoints/``. Disabled (no-op) when dir is None."""

    def __init__(self, ckpt_dir: str | None, snapshot: dict[str, Any], primary: np.ndarray, names: list[str]):
        self.dir = ckpt_dir
        self.n_resumed = 0
        if ckpt_dir is None:
            return
        meta = {
            # format 2 = npz payloads (format 1 was pickle — loading pickles
            # from a shared/NFS workdir is arbitrary code execution, so the
            # bump clears any v1 .pkl shards wholesale)
            "format": 2,
            "snapshot": json.loads(json.dumps(snapshot, sort_keys=True, default=str)),
            "fingerprint": content_fingerprint(names, np.asarray(primary, dtype=np.int64)),
        }
        open_checkpoint_dir(ckpt_dir, meta, clear_suffixes=(".npz", ".pkl"))

    def _loc(self, pc: int) -> str:
        return os.path.join(self.dir, f"pc_{pc:06d}.npz")

    def load(self, pc: int):
        """(Ndb columns, labels, link) for a finished cluster, or None."""
        if self.dir is None:
            return None
        loc = self._loc(pc)
        if not os.path.exists(loc):
            return None
        from drep_tpu.utils import durableio
        from drep_tpu.utils.profiling import counters

        def convert(z):
            # the names come back as the payload's unicode arrays: the
            # columns form without a name list
            cols = [str(c) for c in z["ndb_columns"]]
            return NdbColumns({c: z[f"ndb_col_{c}"] for c in cols}), z["labels"], z["link"]

        # a stopped job's checkpoint, read back: a span of its own inside the
        # caller's `secondary/checkpoint`, which a fresh job never opens
        size = os.path.getsize(loc)
        with counters.span("secondary/resume_load", pc=pc, clusters=1, bytes=size):
            result = durableio.load_npz_or_none(
                loc, what="secondary checkpoint", convert=convert,
                warn="secondary checkpoint: unreadable %s — recomputing",
            )
        if result is not None:
            self.n_resumed += 1  # only after the payload fully validates
            counters.add_resume(checkpoint_bytes=size)
        return result

    def save(self, pc: int, ndb: NdbColumns, labels: np.ndarray, link: np.ndarray) -> None:
        if self.dir is None:
            return
        loc = self._loc(pc)
        arrays: dict[str, np.ndarray] = {
            "labels": np.asarray(labels),
            "link": np.asarray(link),
            "ndb_columns": np.array(list(ndb.cols), dtype=str),
        }
        for c in ndb.cols:
            arrays[f"ndb_col_{c}"] = ndb.stored(c)
        from drep_tpu.utils.ckptmeta import atomic_savez

        # uncompressed: thousands of small per-cluster files per run made
        # zlib a measured hot spot; the payloads are tiny either way
        atomic_savez(loc, compressed=False, **arrays)

    def finish(self, n_total: int) -> None:
        if self.dir is None:
            return
        if self.n_resumed:
            get_logger().info(
                "secondary: resumed %d/%d primary clusters from checkpoints",
                self.n_resumed, n_total,
            )
