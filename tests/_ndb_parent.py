"""The Ndb rows of one primary cluster as the stage built them BEFORE it kept
columns (ISSUE 49): a DataFrame a cluster, taken apart again for its
checkpoint. Kept here, as it was, so the tests can hold the columns form to
the same frames, the same table and the same checkpoint bytes."""

import numpy as np
import pandas as pd

from drep_tpu.cluster.pairs import NDB_COLUMNS


def directional_frame(names, ani, cov, primary_cluster, pair_mask=None) -> pd.DataFrame:
    """`pairs.directional_ndb` as PR 48 had it."""
    m = len(names)
    ii, jj = np.meshgrid(np.arange(m), np.arange(m), indexing="ij")
    keep = ii != jj
    if pair_mask is not None:
        keep &= pair_mask
    ii, jj = ii[keep], jj[keep]
    arr = np.array(names)
    return pd.DataFrame(
        {
            "reference": arr[jj],
            "querry": arr[ii],
            "ani": ani[ii, jj].astype(np.float64),
            "alignment_coverage": cov[ii, jj].astype(np.float64),
            "ref_coverage": cov[jj, ii].astype(np.float64),
            "querry_coverage": cov[ii, jj].astype(np.float64),
            "primary_cluster": primary_cluster,
        }
    )


def greedy_frame(names, n_kmers, pc, kw, ani, cov) -> tuple[pd.DataFrame, np.ndarray]:
    """`greedy._assign_from_matrices` + `_ndb_from_rows` as PR 48 had them:
    each genome, largest first, against the representatives it met."""
    m = len(names)
    order = sorted(range(m), key=lambda t: -n_kmers[t])
    labels = np.zeros(m, dtype=np.int64)
    reps: list[int] = []
    rows: list[dict] = []
    for t in order:
        if reps:
            r = np.asarray(reps)
            cov_row = cov[t, r].astype(np.float64)
            cov_rev = cov[r, t].astype(np.float64)
            ani_row = ani[t, r].astype(np.float64)
            rows.append(
                {
                    "reference": np.array([names[x] for x in reps]),
                    "querry": np.repeat(names[t], len(reps)),
                    "ani": ani_row,
                    "alignment_coverage": cov_row,
                    "ref_coverage": cov_rev,
                    "querry_coverage": cov_row,
                }
            )
            ok = (ani_row >= kw["S_ani"]) & (cov_row >= kw["cov_thresh"]) & (cov_rev >= kw["cov_thresh"])
            if ok.any():
                labels[t] = int(np.argmax(np.where(ok, ani_row, -1.0))) + 1
                continue
        reps.append(t)
        labels[t] = len(reps)
    if not rows:
        return pd.DataFrame(columns=NDB_COLUMNS), labels
    ndb = pd.DataFrame({key: np.concatenate([r[key] for r in rows]) for key in rows[0]})
    ndb["primary_cluster"] = pc
    return ndb, labels


def checkpoint_arrays(ndb: pd.DataFrame, labels, link) -> dict[str, np.ndarray]:
    """What `SecondaryCheckpoint.save` handed `atomic_savez` for a frame."""
    arrays = {
        "labels": np.asarray(labels),
        "link": np.asarray(link),
        "ndb_columns": np.array(list(ndb.columns), dtype=str),
    }
    for c in ndb.columns:
        col = ndb[c].to_numpy()
        if col.dtype == object:
            col = col.astype(str)
        arrays[f"ndb_col_{c}"] = col
    return arrays


def planted_matrices(m: int, seed: int) -> tuple[np.ndarray, np.ndarray]:
    """(ani, cov) float32 [m, m] of the shape the secondary engines return:
    coverage asymmetric, ANI symmetric, diagonal 1."""
    rng = np.random.default_rng(seed)
    inter = rng.integers(100, 4000, size=(m, m))
    cov = (np.minimum(inter, inter.T) / rng.integers(4000, 4400, size=(m, 1))).astype(np.float32)
    ani = (np.maximum(cov, cov.T) ** (1 / 21)).astype(np.float32)
    np.fill_diagonal(ani, 1.0)
    np.fill_diagonal(cov, 1.0)
    return ani, cov
