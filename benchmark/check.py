"""The comparison that decides `correct`: the program's answers against
``reference.py``'s, each number beside its limit.

A comparison is {"what", "value", "limit", "ok"}; `correct` is the AND of
every `ok`. A failed job or a job that did not run where it was meant to is
counted in `failed` by the runner and never reaches here: only a wrong answer
makes `correct` false.

The limits (PERF.md section 2 gives the readings each was set from):

- partitions, and the set of retained pairs: exact, limit 0;
- a Mash distance, an ANI, a coverage: the traffic mix's file states each
  cell's limit (`limits`), set between the largest error sound runs of that
  cell showed and the smallest the control showed. All derive from integer
  hash counts, so a sound value differs from the float64 reference only by
  float32 rounding (host arithmetic: under 2e-7; the device's: about 2e-6),
  while one count more or less moves a distance by 3e-5 and an ANI by 3e-6,
  a bfloat16 value by 3e-5 and more, and a narrower sketch by 1e-3.
"""

from __future__ import annotations

import os

import numpy as np

from benchmark import reference as ref

SAMPLE_CLUSTERS = 200


def comparison(what: str, value, limit) -> dict:
    return {"what": what, "value": value, "limit": limit, "ok": bool(value <= limit)}


def report(comparisons: list[dict]) -> bool:
    for c in comparisons:
        print(f"compare: {c['what']} = {c['value']:.6g} (limit {c['limit']:.6g}) "
              f"{'ok' if c['ok'] else 'WRONG'}", flush=True)
    return all(c["ok"] for c in comparisons)


# ---- a batch job's tables ----------------------------------------------------


def read_tables(wd: str, want: list[str]) -> dict:
    """The tables a job wrote, as plain arrays and dicts."""
    import pandas as pd

    tables = os.path.join(wd, "data_tables")
    cdb = pd.read_csv(os.path.join(tables, "Cdb.csv"))
    out = {"primary": dict(zip(cdb["genome"], cdb["primary_cluster"])),
           "secondary": dict(zip(cdb["genome"], cdb["secondary_cluster"]))}
    if "mdb" in want:
        mdb = pd.read_csv(os.path.join(tables, "Mdb.csv"))
        mdb = mdb[mdb["genome1"] != mdb["genome2"]]
        out["mdb"] = (mdb["genome1"].to_numpy(), mdb["genome2"].to_numpy(),
                      mdb["dist"].to_numpy(np.float64))
    if "ndb" in want:
        ndb = pd.read_csv(os.path.join(tables, "Ndb.csv"))
        out["ndb"] = {(q, r): (a, c) for q, r, a, c in zip(
            ndb["querry"], ndb["reference"], ndb["ani"], ndb["alignment_coverage"])}
    return out


def cdb_digest(tables: dict) -> str:
    import hashlib

    canon = sorted(",".join(sorted(c)) for c in ref.partition_of(tables["secondary"]))
    return hashlib.sha1("\n".join(canon).encode()).hexdigest()[:16]


def check_batch(tables: dict, data, params: dict, want: list[str], limits: dict, seed: int,
                lower_precision: bool = False) -> list[dict]:
    """Compare one job's tables with the reference computed from the planted
    sketches. `lower_precision` puts the control in the program's place:
    `tables` is then ignored and the reference's own low-precision values are
    compared with the full-precision ones."""
    names = data.names
    idx = {g: i for i, g in enumerate(names)}
    k, s = int(params["kmer_size"]), int(params["sketch_size"])
    edges = ref.mash_edges(data.bottom, s, k)
    ref_primary = set(ref.primary_partition(len(names), edges, 1.0 - params["P_ani"]))
    out = []
    if lower_precision:
        low = ref.mash_edges(data.bottom, s, k, lower_precision=True)
        got_primary = set(ref.primary_partition(len(names), low, 1.0 - params["P_ani"]))
        got_pairs = dict(low)
    else:
        got_primary = {frozenset(idx[g] for g in c) for c in ref.partition_of(tables["primary"])}
        g1, g2, dd = tables.get("mdb", ((), (), ()))
        got_pairs = {}
        for a, b, d in zip(g1, g2, dd):
            i, j = idx[a], idx[b]
            key = (min(i, j), max(i, j))
            # both directions are in the table: keep the one farther from the reference
            if key not in got_pairs or abs(d - edges.get(key, 1.0)) > abs(got_pairs[key] - edges.get(key, 1.0)):
                got_pairs[key] = float(d)
    if "primary" in want:
        out.append(comparison("genomes in a primary cluster the reference does not have",
                              ref.partition_mismatch(got_primary, ref_primary), 0))
        out.append(comparison("genomes whose reference primary cluster is not the planted one",
                              ref.partition_mismatch(ref_primary, ref.partition_of(data.labels)), 0))
    if "mdb" in want:
        # every reference pair inside the retention bound must be there, and every
        # pair the table holds under distance 1 must be the reference's (a small
        # collection's table is dense and lists unrelated pairs at distance 1)
        got_pairs = {p: d for p, d in got_pairs.items() if d < 1.0}
        must = {p for p, d in edges.items() if d <= params["retention_dist"]}
        out.append(comparison("Mdb pairs missing, or present and not in the reference",
                              len(must - set(got_pairs)) + len(set(got_pairs) - set(edges)), 0))
        both = set(got_pairs) & set(edges)
        err = max((abs(got_pairs[p] - edges[p]) for p in both), default=0.0)
        out.append(comparison(f"largest Mash distance error over {len(both)} pairs", err,
                              limits["mash_dist"]))
    if "secondary" in want or "ndb" in want:
        out += _check_secondary(tables, data, params, ref_primary, want, limits, seed,
                                lower_precision)
    return out


def _check_secondary(tables, data, params, ref_primary, want, limits, seed,
                     lower_precision) -> list[dict]:
    names = data.names
    k = int(params["kmer_size"])
    multi = sorted((sorted(c) for c in ref_primary if len(c) > 1), key=lambda c: (-len(c), c))
    # a sample drawn from the seed, the largest cluster always in it
    rest = np.random.default_rng(seed).permutation(np.arange(1, len(multi)))
    pick = ([0] if multi else []) + sorted(rest[: SAMPLE_CLUSTERS - 1].tolist())
    wrong = pairs = 0
    ani_err = cov_err = 0.0
    for ci in pick:
        group = multi[ci]  # the largest cluster is always in the sample
        scaled = [data.scaled[g] for g in group]
        ani, cov, labels = ref.secondary_of_cluster(scaled, k, params["S_ani"], params["cov_thresh"])
        if lower_precision:
            got_ani, got_cov, got_labels = ref.secondary_of_cluster(
                scaled, k, params["S_ani"], params["cov_thresh"], lower_precision=True)
            got_part = ref.partition_of(dict(zip(group, got_labels)))
        else:
            got_part = ref.partition_of({g: tables["secondary"][names[g]] for g in group})
        wrong += ref.partition_mismatch(got_part, ref.partition_of(dict(zip(group, labels))))
        if "ndb" not in want:
            continue
        for x, gx in enumerate(group):
            for y, gy in enumerate(group):
                if x == y:
                    continue
                if lower_precision:
                    a, c = got_ani[x, y], got_cov[x, y]
                else:
                    a, c = tables["ndb"].get((names[gx], names[gy]), (np.inf, np.inf))
                ani_err = max(ani_err, abs(a - ani[x, y]))
                cov_err = max(cov_err, abs(c - cov[x, y]))
                pairs += 1
    out = []
    if "secondary" in want:
        out.append(comparison(
            f"genomes of {len(pick)} sampled primary clusters in a secondary cluster the "
            f"reference does not have", wrong, 0))
        if not lower_precision:
            got = {frozenset(c) for c in ref.partition_of(tables["secondary"])}
            planted = {frozenset(names[g] for g in c) for c in ref.partition_of(data.labels)}
            out.append(comparison("genomes whose secondary cluster is not the planted one",
                                  ref.partition_mismatch(got, planted), 0))
    if "ndb" in want:
        out.append(comparison(f"largest ANI error over {pairs} ordered pairs", ani_err,
                              limits["ani"]))
        out.append(comparison(f"largest coverage error over {pairs} ordered pairs", cov_err,
                              limits["coverage"]))
    return out
