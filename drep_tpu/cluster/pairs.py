"""Shared pair-table construction and coverage gating.

One implementation of the Ndb row layout (directional, fastANI-style
query->reference rows — reference drep/d_cluster Ndb contract, SURVEY.md §2)
and of the two-sided coverage gate + symmetrization used before secondary/
tertiary hierarchical clustering, so the stages cannot drift apart.
"""

from __future__ import annotations

import numpy as np
import pandas as pd

NDB_COLUMNS = [
    "reference",
    "querry",
    "ani",
    "alignment_coverage",
    "ref_coverage",
    "querry_coverage",
    "primary_cluster",
]


# the two columns that hold genome names, as opposed to numbers
NAME_COLUMNS = ("reference", "querry")


class NdbColumns:
    """One primary cluster's Ndb rows as column arrays, in `NDB_COLUMNS`
    order: what the secondary stage holds and checkpoints for a cluster, so
    that the stage builds ONE frame (:func:`assemble_ndb`), not one a cluster.

    With `names` (the cluster's own name list) the two name columns are row
    indices into it, which is how the rows are cut out of `(ani, cov)`; without,
    they are the names themselves, as a checkpoint read back gives them."""

    __slots__ = ("cols", "names")

    def __init__(self, cols: dict[str, np.ndarray], names: list[str] | np.ndarray | None = None):
        self.cols = cols
        self.names = None if names is None else np.asarray(names)

    def __len__(self) -> int:
        return len(next(iter(self.cols.values())))

    def column(self, c: str) -> np.ndarray:
        """Column `c`'s values, names looked up."""
        col = self.cols[c]
        return col if self.names is None or c not in NAME_COLUMNS else self.names[col]

    def stored(self, c: str) -> np.ndarray:
        """Column `c` as a checkpoint stores it: names as unicode at the width
        of the longest one IN the column (what a frame's column gave back
        through `astype(str)`, which the store's bytes have always been)."""
        col = self.column(c)
        if self.names is not None and c in NAME_COLUMNS:
            width = np.char.str_len(self.names)[self.cols[c]].max(initial=0)
            col = col.astype(f"<U{width}", copy=False)  # "<U0" reads "<U1", as for no rows
        return col

    def frame(self) -> pd.DataFrame:
        return pd.DataFrame({c: self.column(c) for c in self.cols})


def directional_ndb_columns(
    names: list[str],
    ani: np.ndarray,
    cov: np.ndarray,
    primary_cluster: int,
    pair_mask: np.ndarray | None = None,
) -> NdbColumns:
    """All ordered off-diagonal pairs as Ndb rows (row i = query i vs ref j).

    `pair_mask` [m, m] optionally restricts which ordered pairs are emitted
    (tertiary uses it to keep only cross-primary comparisons).
    """
    m = len(names)
    ii, jj = np.meshgrid(np.arange(m), np.arange(m), indexing="ij")
    keep = ii != jj
    if pair_mask is not None:
        keep &= pair_mask
    ii, jj = ii[keep], jj[keep]
    return NdbColumns(
        {
            "reference": jj,
            "querry": ii,
            "ani": ani[ii, jj].astype(np.float64),
            "alignment_coverage": cov[ii, jj].astype(np.float64),
            "ref_coverage": cov[jj, ii].astype(np.float64),
            "querry_coverage": cov[ii, jj].astype(np.float64),
            "primary_cluster": np.full(len(ii), primary_cluster, dtype=np.int64),
        },
        names,
    )


def directional_ndb(
    names: list[str],
    ani: np.ndarray,
    cov: np.ndarray,
    primary_cluster: int,
    pair_mask: np.ndarray | None = None,
) -> pd.DataFrame:
    """:func:`directional_ndb_columns` as a frame, for a caller with one
    cluster (tertiary, the index's update)."""
    return directional_ndb_columns(names, ani, cov, primary_cluster, pair_mask).frame()


def empty_ndb() -> pd.DataFrame:
    return pd.DataFrame(columns=NDB_COLUMNS)


def empty_ndb_columns() -> NdbColumns:
    """No rows, as the checkpoint store has always held them (an empty
    frame's object columns through `astype(str)`): computed or read back,
    seven empty unicode columns."""
    return NdbColumns({c: np.empty(0, dtype="<U1") for c in NDB_COLUMNS})


def assemble_ndb(parts: list[NdbColumns]) -> pd.DataFrame:
    """The clusters' rows, in the order given, as the one Ndb frame: what
    `pd.concat(ignore_index=True)` of a frame a cluster gave. A part of no
    rows adds nothing; parts of no rows at all give :func:`empty_ndb`."""
    parts = [p for p in parts if len(p)]
    if not parts:
        return empty_ndb()
    if len(parts) == 1:
        return parts[0].frame()
    return pd.DataFrame(
        {c: np.concatenate([p.column(c) for p in parts]) for c in NDB_COLUMNS}
    )


def gated_symmetric_ani(
    ani: np.ndarray,
    cov: np.ndarray,
    cov_thresh: float,
    allow_mask: np.ndarray | None = None,
) -> np.ndarray:
    """Symmetrized ANI with the reference's two-sided coverage gate applied
    (cov < cov_thresh in either direction -> similarity zeroed), diagonal 1.

    `allow_mask` [m, m] optionally zeroes additional pairs (tertiary uses it
    to forbid same-primary merges).
    """
    sym = (ani + ani.T) / 2.0
    gate = (cov >= cov_thresh) & (cov.T >= cov_thresh)
    if allow_mask is not None:
        gate &= allow_mask
    sym = np.where(gate, sym, 0.0)
    np.fill_diagonal(sym, 1.0)
    return sym
