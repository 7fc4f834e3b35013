"""Benchmark: genome-pairs/sec/chip across the pipeline's compute stages.

Prints ONE JSON line:
  {"metric": "genome-pairs/sec/chip", "value": N, "unit": "pairs/s",
   "vs_baseline": N, "stages": {...}}

Headline metric (BASELINE.json "genome-pairs/sec/chip on dRep compare"):
unique genome pairs (N*(N-1)/2) / wall-clock of the all-vs-all Mash-distance
computation on one chip, at N=2048 genomes, sketch 1024 (reference default
sketch is 1000, padded to a lane-friendly 1024).

`stages` covers the BASELINE measurement plan:
- primary:              jax_mash all-vs-all (the headline number)
- secondary_matmul:     jax_ani MXU indicator-matmul containment path
- secondary_pallas:     the Pallas bitonic-merge kernel COMPILED on TPU, with
                        an exact-equality check against the matmul path at
                        the same shape (skipped off-TPU: interpret mode
                        measures nothing)
- secondary_production: PRODUCTION shape — m=512 genomes at ~20k-wide scaled
                        sketches (4 Mb at default scale=200 -> width 32768
                        packed) over a multi-million-id vocabulary. Runs the
                        range-partitioned paths (vocab-chunked MXU matmul AND
                        range-bucketed Pallas merge), cross-checks them for
                        exact equality plus a sampled searchsorted oracle,
                        and reports which one the engine dispatch picks.
- e2e_10k / e2e_50k:    wall-clock to Cdb for synthetic compares through the
                        streaming primary + batched secondary path (sketches
                        pre-planted in a workdir cache — FASTA ingest for
                        50k * 4 Mb of sequence is a host-IO benchmark, not a
                        chip benchmark). e2e_50k also records peak host RSS
                        and the retained sparse-edge count — the 100k
                        north-star claim extrapolates from THIS measurement,
                        not from the 10k one.

Roofline counters (SURVEY.md §5.1 rebuild note): matmul stages report
`tflops` and `mfu` against the v5e bf16 peak; merge/sort stages report HBM
traffic (`hbm_gbps`, `membw_frac`) AND compare-exchange element throughput
(`vpu_eops_per_sec`, `vpu_frac`) against a documented VPU estimate — the
merge kernel's working set lives in VMEM, so HBM fractions are tiny by
design and VPU utilization is the binding roofline.

`vs_baseline`: BASELINE.json `published` is empty (no published reference
number exists — SURVEY.md §6), so the honest denominator everywhere is the
north-star requirement: 100k MAGs in <30 min on v5e-16 =>
100k*(100k-1)/2 pairs / 1800 s / 16 chips ~= 1.736e5 pairs/s/chip.
vs_baseline > 1 means the stage clears the north-star rate.

Triangle-only accounting (ISSUE 1): every stage reports `unique_pairs`
(N*(N-1)/2 — the engines compute each unordered pair once and mirror),
and the primary stage reports `tiles_computed`/`tiles_total`/
`tile_fraction` diffed from the engine's schedule counters, proving the
triangular schedule engaged (~0.5-0.56) rather than the full grid (1.0).
The emitted `value` falls back to the first completed stage
(`value_source`) when the headline stage itself never measured — partial
results beat `value: null` (BENCH_r05 post-mortem), and a failed stage is
recorded as `{"error": ...}` inside its stage dict.
"""

from __future__ import annotations

import argparse
import json
import os
import resource
import shutil
import tempfile
import time

import numpy as np

N_GENOMES = 2048
SKETCH_SIZE = 1024
K = 21
TILE = 512
NORTH_STAR_PAIRS_PER_SEC_PER_CHIP = (100_000 * 99_999 / 2) / 1800.0 / 16.0

# secondary-stage shape: one large primary cluster (budget-friendly width)
SEC_M = 512
SEC_WIDTH = 2048
SEC_VOCAB = 120_000

# production secondary shape: 4 Mb genomes at default scale=200 give ~20k
# scaled hashes -> packed width 32768; 8 related subclusters with mostly
# private hash space push the vocabulary to multi-million ids
PROD_M = 512
PROD_SHARED = 10_000  # hashes shared within a subcluster (~95% kept/member)
PROD_OWN = 10_000  # private hashes per genome
PROD_SUBCLUSTERS = 8

# v5e single-chip peaks for the roofline fields. int8 matmul (the indicator
# kernels run int8 0/1 inputs with int32 accumulation) and HBM BW are the
# published chip numbers (cf. jax-ml scaling-book hardware table); the VPU
# figure is an ESTIMATE (8x128 lanes x 4 ALUs x ~940 MHz ~= 3.9e12
# elementwise ops/s) used only to normalize merge-kernel throughput.
V5E_INT8_OPS = 394e12
V5E_HBM_BYTES_PER_S = 819e9
V5E_VPU_EOPS = 3.9e12


def _best_of(fn, reps: int = 3) -> float:
    """Best wall-clock of `reps` runs of the same fixed work. (ROADMAP S0
    replaces this with a median over many readings and its spread.)"""
    dt = float("inf")
    for _ in range(reps):
        t0 = time.perf_counter()
        fn()
        dt = min(dt, time.perf_counter() - t0)
    return dt


def _rate_fields(pairs: float, dt: float) -> dict:
    """Per-stage throughput over UNIQUE genome pairs (N*(N-1)/2): the
    triangular schedules compute each unordered pair once and mirror the
    transpose, so unique pairs are the honest numerator — counting both
    (i,j) and (j,i) would double-report the same work."""
    value = pairs / dt
    return {
        "unique_pairs": int(pairs),
        "seconds": round(dt, 4),
        "pairs_per_sec_per_chip": round(value, 1),
        "vs_baseline": round(value / NORTH_STAR_PAIRS_PER_SEC_PER_CHIP, 3),
    }


def _matmul_roofline(flops: float, dt: float) -> dict:
    return {
        "tflops": round(flops / dt / 1e12, 2),
        "mfu": round(flops / dt / V5E_INT8_OPS, 4),
    }


def _tri_matmul_flops(m_pad: int, v_cols: float) -> float:
    """MACs*2 the TRIANGULAR intersection matmul actually issues: the
    canonical (bi <= bj) block rows sum to m_pad^2 * (B+1)/(2B) output
    elements (B block rows), each contracting v_cols — the honest mfu
    numerator now that the engines skip the mirrored half."""
    from drep_tpu.ops.containment import tri_row_block

    b = m_pad // tri_row_block(m_pad)
    return 2.0 * m_pad * m_pad * ((b + 1) / (2 * b)) * v_cols


def _merge_roofline(pairs: float, s2: int, hbm_bytes: float, dt: float) -> dict:
    """Merge-kernel roofline: compare-exchange element ops (merged width x
    log2 stages x ~4 vector ops per stage: two rolls, compare, select) plus
    the actual HBM tile traffic."""
    stages = (2 * s2).bit_length() - 1
    eops = pairs * 2 * s2 * stages * 4
    return {
        "vpu_eops_per_sec": round(eops / dt / 1e9, 1),  # Geops/s
        "vpu_frac": round(eops / dt / V5E_VPU_EOPS, 4),
        "hbm_gbps": round(hbm_bytes / dt / 1e9, 2),
        "membw_frac": round(hbm_bytes / dt / V5E_HBM_BYTES_PER_S, 5),
    }


def bench_primary(publish=None) -> dict:
    """`publish(out)` is called the moment the HEADLINE number exists and
    `out` is mutated in place afterwards: attempt 2 wedged somewhere in
    this stage after 8 other stages succeeded, and because the stage only
    published its dict on return, whatever it had already measured was
    lost with it. Publishing early means the watchdog's bail snapshot
    carries the headline even when a later variant compile wedges — and
    the sub-stage progress markers on stderr make the wedge point
    attributable from the attempt log."""
    import sys

    from drep_tpu.cluster.engines import mash_distance_matrix
    from drep_tpu.ops.merge import next_pow2
    from drep_tpu.ops.minhash import PackedSketches

    rng = np.random.default_rng(0)
    ids = np.sort(
        rng.integers(0, 2**30, size=(N_GENOMES, SKETCH_SIZE), dtype=np.int32), axis=1
    )
    counts = np.full((N_GENOMES,), SKETCH_SIZE, dtype=np.int32)
    packed = PackedSketches(
        ids=ids, counts=counts, names=[f"g{i}" for i in range(N_GENOMES)]
    )

    import os

    import jax

    # pin the kernel-variant knob to its shipped default for the HEADLINE:
    # a leftover operator export must not silently change what the
    # recorded number measures (variants are reported separately below)
    prev_r = os.environ.get("DREP_TPU_MASH_ROWS_PER_ITER")  # drep-lint: allow[env-knob] — raw save/restore around the sweep's env override, not a typed read
    # try/finally opens IMMEDIATELY after saving prev_r: if the headline
    # measurement itself raises (the stage watchdog swallows it and moves
    # on), the operator's env value must not stay pinned to "1" for every
    # later stage in the process
    try:
        os.environ["DREP_TPU_MASH_ROWS_PER_ITER"] = "1"
        from drep_tpu.utils.profiling import counters as _counters

        mash_distance_matrix(packed, k=K, tile=TILE)  # compile warmup at full shape
        _tiles0 = _counters.stages.get("primary_compare")
        _tc0, _tt0 = (
            (_tiles0.tiles_computed, _tiles0.tiles_total) if _tiles0 else (0, 0)
        )
        dt = _best_of(lambda: mash_distance_matrix(packed, k=K, tile=TILE))
        pairs = N_GENOMES * (N_GENOMES - 1) / 2
        s2 = max(128, next_pow2(SKETCH_SIZE))
        # HBM per 128x128 pair tile: two [128, s2] s32 reads + [128, 128]
        # write, over the wrapped symmetric grid (~half the full tile count)
        t = N_GENOMES // 128
        n_tiles = t * (t // 2 + 1)
        hbm = n_tiles * (2 * 128 * s2 * 4 + 128 * 128 * 4)
        out = {
            "n_genomes": N_GENOMES,
            "sketch": SKETCH_SIZE,
            **_rate_fields(pairs, dt),
            **_merge_roofline(pairs, s2, hbm, dt),
        }
        # triangular-schedule proof: the engine records its pair-tile
        # schedule into the process counters — diffed around the measured
        # calls, the ratio shows the triangle-only path actually engaged
        # (~0.5-0.56) instead of the full grid (1.0)
        _tiles1 = _counters.stages.get("primary_compare")
        if _tiles1 is not None and _tiles1.tiles_total > _tt0:
            tc, tt = _tiles1.tiles_computed - _tc0, _tiles1.tiles_total - _tt0
            out["tiles_computed"] = tc
            out["tiles_total"] = tt
            out["tile_fraction"] = round(tc / tt, 4)
        if publish is not None:
            publish(out)
        print(
            f"bench: primary headline done "
            f"({out['pairs_per_sec_per_chip']:.0f} pairs/s/chip)",
            file=sys.stderr, flush=True,
        )

        # kernel-variant diagnostics: measure the row-batched mash kernel
        # (DREP_TPU_MASH_ROWS_PER_ITER — correctness equality-tested in
        # tests/test_pallas_mash.py) on the same workload. The headline
        # above is the shipped default (r=1, pinned); these rates exist so
        # the default can be flipped on evidence, not on a guess. Single
        # TPU chip only: the multi-device mesh path never reads the knob
        # (measuring it there would report meaningless ~1.0 speedups), and
        # interpret mode measures nothing.
        if jax.devices()[0].platform == "tpu" and len(jax.local_devices()) == 1:
            for r in (2, 4):
                os.environ["DREP_TPU_MASH_ROWS_PER_ITER"] = str(r)
                print(
                    f"bench: primary variant rows_per_iter={r} compiling",
                    file=sys.stderr, flush=True,
                )
                try:
                    mash_distance_matrix(packed, k=K, tile=TILE)  # variant compile
                    dt_r = _best_of(lambda: mash_distance_matrix(packed, k=K, tile=TILE))
                    out[f"rows_per_iter_{r}"] = {
                        "pairs_per_sec_per_chip": round(pairs / dt_r, 1),
                        "speedup_vs_default": round(dt / dt_r, 3),
                    }
                except Exception as e:  # a failed DIAGNOSTIC must not cost the headline
                    out[f"rows_per_iter_{r}"] = {"error": repr(e)}
            # decision evidence, machine-readable: the default flips only
            # when a variant clears a 10% margin (link noise brackets
            # smaller gaps even with _best_of)
            speedups = {
                r: out[f"rows_per_iter_{r}"].get("speedup_vs_default", 0.0)
                for r in (2, 4)
                if f"rows_per_iter_{r}" in out
            }
            if speedups:
                best_r, best_s = max(speedups.items(), key=lambda kv: kv[1])
                out["variant_recommendation"] = (
                    f"set DREP_TPU_MASH_ROWS_PER_ITER={best_r} ({best_s:.2f}x)"
                    if best_s > 1.1
                    else "keep default rows_per_iter=1"
                )
    finally:
        if prev_r is None:
            os.environ.pop("DREP_TPU_MASH_ROWS_PER_ITER", None)
        else:
            os.environ["DREP_TPU_MASH_ROWS_PER_ITER"] = prev_r
    return out


def _secondary_pack():
    from drep_tpu.ops.minhash import PackedSketches

    rng = np.random.default_rng(1)
    ids = np.stack(
        [
            np.sort(rng.choice(SEC_VOCAB, size=SEC_WIDTH, replace=False)).astype(np.int32)
            for _ in range(SEC_M)
        ]
    )
    counts = np.full((SEC_M,), SEC_WIDTH, dtype=np.int32)
    return PackedSketches(ids=ids, counts=counts, names=[f"g{i}" for i in range(SEC_M)])


def bench_secondary_matmul(packed) -> dict:
    from drep_tpu.ops.containment import (
        all_vs_all_containment_matmul,
        matmul_rows_pad,
        matmul_vocab_pad,
    )

    all_vs_all_containment_matmul(packed, k=K)  # warmup
    dt = _best_of(lambda: all_vs_all_containment_matmul(packed, k=K))
    pairs = SEC_M * (SEC_M - 1) / 2
    flops = _tri_matmul_flops(matmul_rows_pad(SEC_M), matmul_vocab_pad(packed))
    return {
        "n_genomes": SEC_M,
        "sketch": SEC_WIDTH,
        **_rate_fields(pairs, dt),
        **_matmul_roofline(flops, dt),
    }


def bench_secondary_pallas(packed) -> dict:
    """Compiled Pallas kernel rate + exact equality vs the MXU matmul path."""
    import jax

    if jax.devices()[0].platform != "tpu":
        return {"skipped": "not on tpu (interpret mode measures nothing)"}

    import jax.numpy as jnp

    from drep_tpu.ops.containment import _intersect_matmul, matmul_vocab_pad
    from drep_tpu.ops.merge import next_pow2
    from drep_tpu.ops.pallas_merge import intersect_counts_pallas_self

    inter_p = intersect_counts_pallas_self(packed.ids)  # warmup + result
    dt = _best_of(lambda: intersect_counts_pallas_self(packed.ids))
    v_pad = matmul_vocab_pad(packed)
    inter_m = np.asarray(_intersect_matmul(jnp.asarray(packed.ids), v_pad=v_pad))
    equal = bool(np.array_equal(inter_p, np.asarray(inter_m)))
    pairs = SEC_M * (SEC_M - 1) / 2
    s2 = max(128, next_pow2(SEC_WIDTH))
    t = -(-SEC_M // 128)
    hbm = t * (t // 2 + 1) * (2 * 128 * s2 * 4 + 128 * 128 * 4)
    return {
        "n_genomes": SEC_M,
        "sketch": SEC_WIDTH,
        "equal_to_matmul": equal,
        **_rate_fields(pairs, dt),
        **_merge_roofline(pairs, s2, hbm, dt),
    }


def _production_pack(adversarial: bool = True):
    """m=512 scaled sketches at production width (~20k ids/row -> packed
    32768). `adversarial`: 8 subclusters with mostly-private hash space ->
    ~5.2M-id vocabulary (the chunked/range regime — a worst case: real
    primary clusters are Mash-similar, so their sketches overlap).
    Otherwise the REALISTIC high-overlap cluster: every member keeps ~95%
    of one shared ~20k pool plus ~700 private hashes -> vocab ~370k, the
    one-shot indicator regime."""
    from drep_tpu.ops.containment import pack_scaled_sketches

    rng = np.random.default_rng(7)
    sketches = []
    if adversarial:
        per = PROD_M // PROD_SUBCLUSTERS
        for _c in range(PROD_SUBCLUSTERS):
            pool = np.unique(
                rng.integers(0, 2**62, size=int(PROD_SHARED * 1.05), dtype=np.uint64)
            )
            for _g in range(per):
                keep = rng.random(len(pool)) < 0.95
                own = np.unique(rng.integers(0, 2**62, size=PROD_OWN, dtype=np.uint64))
                sketches.append(np.unique(np.concatenate([pool[keep], own])))
    else:
        pool = np.unique(
            rng.integers(0, 2**62, size=2 * PROD_SHARED, dtype=np.uint64)
        )
        for _g in range(PROD_M):
            keep = rng.random(len(pool)) < 0.95
            own = np.unique(rng.integers(0, 2**62, size=PROD_OWN // 14, dtype=np.uint64))
            sketches.append(np.unique(np.concatenate([pool[keep], own])))
    return pack_scaled_sketches(sketches, [f"g{i}" for i in range(len(sketches))])


def bench_secondary_production(publish=None) -> dict:
    """The production-width secondary regime (VERDICT r2 next-round #1):
    both range-partitioned paths at m=512 / width 32768 / multi-M vocab,
    exact cross-equality + sampled searchsorted oracle, no OOM.

    Early-publish contract (see bench_primary): `out` reaches the record
    via `publish` before the first compile and is mutated in place, so a
    wedge during any sub-measurement keeps everything already measured —
    one observed wedge struck exactly at this stage's first big compile."""
    import jax

    from drep_tpu.cluster.engines import beyond_budget_secondary_path
    from drep_tpu.ops.containment import (
        all_vs_all_containment_matmul_chunked,
        matmul_rows_pad,
        matmul_vocab_chunk,
        matmul_vocab_pad,
        one_shot_fits,
    )
    from drep_tpu.ops.merge import next_pow2
    from drep_tpu.ops.minhash import PAD_ID
    from drep_tpu.ops.rangepart import vocab_extent

    packed = _production_pack()
    m = packed.n
    width = packed.sketch_size
    v_pad = matmul_vocab_pad(packed)
    pairs = m * (m - 1) / 2
    out: dict = {
        "n_genomes": m,
        "sketch": width,
        "v_pad": v_pad,
        "one_shot_fits": bool(one_shot_fits(m, v_pad)),
        # cleared when the first real rate lands: a wedge before then
        # leaves a number-free record that must not read as a completed
        # stage (ADVICE r4 medium — missing_stages keys on this)
        "measurement_pending": True,
    }
    if publish is not None:
        publish(out)

    ani_c, cov_c = all_vs_all_containment_matmul_chunked(packed, k=K)  # warmup
    dt_m = _best_of(lambda: all_vs_all_containment_matmul_chunked(packed, k=K), reps=2)
    v_chunk = matmul_vocab_chunk(matmul_rows_pad(m))
    n_chunks = -(-vocab_extent(packed.ids) // v_chunk)
    flops = _tri_matmul_flops(matmul_rows_pad(m), n_chunks * v_chunk)
    out["matmul_chunked"] = {**_rate_fields(pairs, dt_m), **_matmul_roofline(flops, dt_m)}
    out.pop("measurement_pending", None)  # first real rate is in the record

    if jax.devices()[0].platform == "tpu":
        from drep_tpu.ops.containment import ani_cov_from_intersections
        from drep_tpu.ops.pallas_merge import intersect_counts_pallas_self

        inter_p = intersect_counts_pallas_self(packed.ids)  # warmup + result
        dt_p = _best_of(lambda: intersect_counts_pallas_self(packed.ids), reps=2)
        s2 = max(128, next_pow2(width))
        # range partitioning re-reads each bucket tile: model HBM as the
        # full-width traffic (buckets sum to the original row content)
        t = -(-m // 128)
        hbm = t * (t // 2 + 1) * (2 * 128 * s2 * 4 + 128 * 128 * 4)
        out["pallas_range"] = {**_rate_fields(pairs, dt_p), **_merge_roofline(pairs, s2, hbm, dt_p)}
        ani_p, _cov_p = ani_cov_from_intersections(inter_p, packed.counts, K)
        out["paths_equal"] = bool(np.array_equal(ani_p, ani_c))

    # sampled searchsorted oracle: 6 query rows against a column stride
    rng = np.random.default_rng(11)
    rows = rng.choice(m, size=6, replace=False)
    ok = True
    for i in rows:
        ai = packed.ids[i][packed.ids[i] != PAD_ID]
        for j in range(0, m, 37):
            bj = packed.ids[j][packed.ids[j] != PAD_ID]
            want = len(np.intersect1d(ai, bj)) / max(len(ai), 1)
            ok &= abs(cov_c[i, j] - want) < 1e-6
    out["oracle_ok"] = bool(ok)

    out["dispatch_picks"] = beyond_budget_secondary_path(width, v_pad)

    # the REALISTIC production cluster: same m/width, high-overlap vocab
    # (Mash-similar genomes share most scaled hashes), one-shot regime —
    # what the engine dispatch actually runs per typical primary cluster
    from drep_tpu.cluster.engines import containment_matrices

    packed_r = _production_pack(adversarial=False)
    v_pad_r = matmul_vocab_pad(packed_r)
    containment_matrices(packed_r, K)  # warmup
    dt_r = _best_of(lambda: containment_matrices(packed_r, K), reps=2)
    flops_r = _tri_matmul_flops(matmul_rows_pad(packed_r.n), v_pad_r)
    out["realistic_highoverlap"] = {
        "v_pad": v_pad_r,
        "one_shot_fits": bool(one_shot_fits(packed_r.n, v_pad_r)),
        **_rate_fields(packed_r.n * (packed_r.n - 1) / 2, dt_r),
        **_matmul_roofline(flops_r, dt_r),
    }

    return out


def _crossover_pack(m: int, width: int, fill: int, v_extent: int, rng):
    """PackedSketches with EXACTLY `v_extent` distinct ids (dense, like
    pack_scaled_sketches output) dealt round-robin so every id appears:
    the honest construction — extent can never exceed m*fill, which is the
    same invariant the engine's dense id remap enforces on real clusters."""
    from drep_tpu.ops.minhash import PAD_ID, PackedSketches

    assert m * fill >= v_extent, "unreachable extent for this (m, fill)"
    # fill > v_extent would deal the same id twice into one row: the
    # indicator scatter dedupes, the merge kernel counts multiplicity —
    # the two kernels would silently compute different quantities
    assert fill <= v_extent, "duplicate ids within a row"
    perm = rng.permutation(v_extent).astype(np.int32)
    flat = perm[np.arange(m * fill) % v_extent]
    ids = np.full((m, width), PAD_ID, dtype=np.int32)
    ids[:, :fill] = np.sort(flat.reshape(m, fill), axis=1)
    counts = np.full((m,), fill, dtype=np.int32)
    return PackedSketches(ids=ids, counts=counts, names=[f"g{i}" for i in range(m)])


def bench_dispatch_crossover(publish=None) -> dict:
    """Bracket the beyond-budget dispatch (VERDICT r3 weak #2): measure
    BOTH kernels — vocab-chunked MXU matmul and range-bucketed Pallas
    merge — at vocab/merge-unit ratios spanning ~8x to ~100x, and fit the
    per-element cost ratio the dispatch constant
    (engines.MERGE_VS_MATMUL_ELEM_COST) encodes. Shapes are all honestly
    reachable (extent <= m*fill, the dense-remap invariant) and all
    beyond the one-shot budget, so each point is a real dispatch site."""
    import jax

    if jax.devices()[0].platform != "tpu":
        return {"skipped": "not on tpu (the pallas side measures nothing off-chip)"}
    from drep_tpu.cluster.engines import MERGE_VS_MATMUL_ELEM_COST
    from drep_tpu.ops.containment import (
        all_vs_all_containment_matmul_chunked,
        matmul_rows_pad,
        matmul_vocab_chunk,
    )
    from drep_tpu.ops.merge import next_pow2
    from drep_tpu.ops.pallas_merge import all_vs_all_containment_pallas

    rng = np.random.default_rng(17)
    points = [
        # (m, width, fill, target ratio) — ratio = v_extent / merge_units
        (512, 32768, 20_000, 8),
        (1024, 2048, 1843, 20),
        (2048, 2048, 1843, 40),
        (4096, 512, 460, 100),
    ]
    table = []
    ratios_fit = []
    # early-publish: 8 fresh kernel shapes compile in this loop; a wedge
    # at point 3 must not cost points 1-2 (the list is shared, the dict
    # is completed in place on return)
    out: dict = {"table": table, "points_measured": 0, "measurement_pending": True}
    if publish is not None:
        publish(out)
    for m, width, fill, ratio in points:
        s2 = max(128, next_pow2(width))
        mu = 2 * s2 * ((2 * s2).bit_length() - 1)
        v_extent = ratio * mu
        packed = _crossover_pack(m, width, fill, v_extent, rng)
        pairs = m * (m - 1) / 2

        ani_c, _ = all_vs_all_containment_matmul_chunked(packed, k=K)  # warmup
        dt_c = _best_of(lambda: all_vs_all_containment_matmul_chunked(packed, k=K), reps=2)
        ani_p, _ = all_vs_all_containment_pallas(packed, k=K)  # warmup
        dt_p = _best_of(lambda: all_vs_all_containment_pallas(packed, k=K), reps=2)

        v_chunk = matmul_vocab_chunk(matmul_rows_pad(m))
        v_cols = -(-v_extent // v_chunk) * v_chunk
        c_col = dt_c / (pairs * v_cols)  # chunked cost per pair-vocab-column
        c_mu = dt_p / (pairs * mu)  # merge cost per pair-merge-unit
        ratios_fit.append(c_mu / c_col)
        table.append(
            {
                "m": m,
                "width": width,
                "v_extent": v_extent,
                "ratio": ratio,
                "chunked_s": round(dt_c, 3),
                "pallas_s": round(dt_p, 3),
                "equal": bool(np.array_equal(ani_c, ani_p)),
                "winner": "pallas_range" if dt_p < dt_c else "matmul_chunked",
                "elem_cost_ratio": round(c_mu / c_col, 2),
            }
        )
        out["points_measured"] = len(table)
        out.pop("measurement_pending", None)  # >=1 real point in the record
    fitted = float(np.median(ratios_fit))
    out.pop("points_measured", None)  # complete: the table speaks for itself
    # the dispatch picks pallas_range when elem_cost * merge_units <
    # v_pad, so `fitted` IS the constant the measurements support
    out["fitted_elem_cost"] = round(fitted, 2)
    out["shipped_elem_cost"] = MERGE_VS_MATMUL_ELEM_COST
    out["shipped_matches_measured"] = bool(
        0.5 <= fitted / MERGE_VS_MATMUL_ELEM_COST <= 2.0
    )
    return out


INGEST_N = 96  # enough that process-pool startup amortizes
INGEST_N_NUMPY = 8  # the numpy path is ~25x slower; sample it
INGEST_MB = 4  # 4 Mb genomes — the production MAG size


def bench_ingest() -> dict:
    """Host ingest wall (SURVEY.md §7 hard part (f)): FASTA -> sketches,
    native C++ vs numpy, serial vs process pool — the numbers the 100k
    ingest extrapolation cites. Written fresh to tmp so the page cache is
    the same warm state a real run sees after its first pass."""
    import os

    from drep_tpu.ingest import make_bdb, sketch_genomes

    rng = np.random.default_rng(5)
    bases = np.frombuffer(b"ACGT", dtype=np.uint8)
    with tempfile.TemporaryDirectory() as td:
        paths = []
        for i in range(INGEST_N):
            seq = bases[rng.integers(0, 4, size=INGEST_MB * 1_000_000)]
            p = os.path.join(td, f"g{i:03d}.fasta")
            # drep-lint: allow[durable-funnel] — synthetic ingest corpus streamed into this process's own TemporaryDirectory; nothing resumes from it
            with open(p, "w") as f:
                f.write(f">g{i}\n")
                s = seq.tobytes().decode()
                for o in range(0, len(s), 80):
                    f.write(s[o : o + 80] + "\n")
            paths.append(p)

        out: dict = {
            "n_genomes": INGEST_N,
            "genome_mb": INGEST_MB,
            # pool scaling is meaningless on a 1-core container (this
            # image); the per-core rate is the portable number
            "host_cores": os.cpu_count(),
        }
        import drep_tpu.native as native_mod

        have_native = native_mod.sketch_fasta_native(paths[0], K, 64, 200, "splitmix64") is not None
        modes = [("native_p1", 1, False), ("native_p8", 8, False)] if have_native else []
        modes.append(("numpy_p1", 1, True))
        for label, procs, force_numpy in modes:
            subset = paths[: INGEST_N_NUMPY if force_numpy else INGEST_N]
            bdb = make_bdb(subset)
            if force_numpy:
                orig = native_mod.sketch_fasta_native
                native_mod.sketch_fasta_native = lambda *a, **k: None
            try:
                t0 = time.perf_counter()
                sketch_genomes(bdb, processes=procs)
                dt = time.perf_counter() - t0
            finally:
                if force_numpy:
                    native_mod.sketch_fasta_native = orig
            out[label] = {
                "n": len(subset),
                "seconds": round(dt, 3),
                "genomes_per_sec": round(len(subset) / dt, 2),
                "mb_per_sec": round(len(subset) * INGEST_MB / dt, 1),
            }
        best = max(
            (v["genomes_per_sec"] for k, v in out.items() if isinstance(v, dict) and k.startswith("native")),
            default=None,
        )
        if best:
            out["extrapolated_100k_minutes_per_core"] = round(100_000 / best / 60, 1)
        return out


GREEDY_M = 1024  # one large primary cluster through the greedy engine
GREEDY_SUBCLUSTERS = 16


def bench_greedy() -> dict:
    """The greedy-incremental secondary engine (BASELINE config 5's path)
    at production sketch width: m=1024 genomes in 16 planted subclusters,
    ~20k-wide scaled sketches. Measures genomes/s through the full
    assignment loop (device comparisons + host sequential logic) and
    checks the recovered representative structure."""
    import pandas as pd

    from drep_tpu.cluster.greedy import greedy_secondary_cluster
    from drep_tpu.ingest import DEFAULT_SCALE, GenomeSketches

    rng = np.random.default_rng(13)
    per = GREEDY_M // GREEDY_SUBCLUSTERS
    sketches = []
    for _c in range(GREEDY_SUBCLUSTERS):
        pool = np.unique(
            rng.integers(0, 2**62, size=int(2 * PROD_SHARED * 1.05), dtype=np.uint64)
        )
        for _g in range(per):
            keep = pool[rng.random(len(pool)) < 0.95]
            own = np.unique(rng.integers(0, 2**62, size=PROD_OWN // 14, dtype=np.uint64))
            sketches.append(np.unique(np.concatenate([keep, own])))
    gdb = pd.DataFrame(
        {
            "genome": [f"g{i}" for i in range(GREEDY_M)],
            "length": 4_000_000,
            "N50": 50_000,
            "contigs": 100,
            "n_kmers": [len(s) * DEFAULT_SCALE for s in sketches],
        }
    )
    gs = GenomeSketches(
        names=list(gdb["genome"]), gdb=gdb, bottom=[], scaled=sketches,
        k=K, sketch_size=1000, scale=DEFAULT_SCALE,
    )
    bdb = pd.DataFrame({"genome": gs.names, "location": gs.names})
    kw = {"S_ani": 0.95, "cov_thresh": 0.1}
    indices = list(range(GREEDY_M))

    from drep_tpu.cluster.greedy import GREEDY_TIMINGS

    greedy_secondary_cluster(gs, bdb, indices, 1, kw)  # warmup/compiles
    before = dict(GREEDY_TIMINGS)
    t0 = time.perf_counter()
    ndb, labels = greedy_secondary_cluster(gs, bdb, indices, 1, kw)
    dt = time.perf_counter() - t0
    # per-phase attribution (VERDICT r4 weak #3: the 45 genomes/s number
    # was unexplained) — diffed module counters, same idiom as
    # SECONDARY_PATH_COUNTS
    phases = {
        k: round(v - before.get(k, 0.0), 3)
        for k, v in GREEDY_TIMINGS.items()
        if v - before.get(k, 0.0) > 0 and k != "device_calls"
    }
    device_calls = int(
        GREEDY_TIMINGS.get("device_calls", 0) - before.get("device_calls", 0)
    )
    return {
        "n_genomes": GREEDY_M,
        "sketch_width": int(max(len(s) for s in sketches)),
        "n_reps": int(labels.max()),
        "comparisons": int(len(ndb)),
        "seconds": round(dt, 3),
        "phase_seconds": phases,
        "device_calls": device_calls,
        "genomes_per_sec": round(GREEDY_M / dt, 1),
        "subclusters_recovered": bool(labels.max() <= 2 * GREEDY_SUBCLUSTERS),
    }


def _plant_sketches(n: int, rng: np.random.Generator, s_scaled: int = 1200):
    from drep_tpu.utils.synth import plant_genome_sketches

    return plant_genome_sketches(n, rng, s_scaled=s_scaled)[0]


def bench_e2e(n: int, s_scaled: int = 1200, publish=None, workdir: str | None = None) -> dict:
    """Wall-clock to Cdb: streaming primary + batched secondary on planted
    sketches. The sketch cache is pre-stored in the workdir (the supported
    resume path), so the measurement starts at the cluster stage — the
    BASELINE "wall-clock to Cdb" clause — not at host FASTA IO. Records
    peak host RSS (process lifetime max) and the retained sparse-edge
    count so the large-n memory behavior is observed, not extrapolated.

    At s_scaled=20_000 (the e2e_prod stage) the batched secondary rides
    the beyond-budget chunked/range kernels — `secondary_paths` in the
    result records which engine paths actually served the run (diffed
    from the engine's path counter, not inferred).

    `publish(out)` fires as soon as the FRESH measurement exists (the
    dict is then mutated in place with the resume-leg fields): the 50k
    fresh run is ~20 min, and a failure during the resume leg must not
    cost it — same early-publish contract as bench_primary.

    `workdir` (scale-class stages): a PERSISTENT directory instead of the
    default throwaway tempdir. The pipeline checkpoints streaming
    row-block shards as it goes, so a run that wedges at minute 19 of 20
    leaves its progress on disk and the next attempt completes from it
    instead of starting over. Honesty
    marker: `warm_start_shards` counts the shard files found before the
    run; a warm-started wall-clock is NOT a cold-run number, and the
    merge tool prefers cold records regardless of rate. The directory is
    deleted after a fully-successful measurement (wedges keep it)."""
    import pandas as pd

    import jax
    from drep_tpu.cluster.controller import d_cluster_wrapper
    from drep_tpu.ingest import DEFAULT_SCALE, _save, sketch_args_snapshot
    from drep_tpu.workdir import WorkDirectory

    rng = np.random.default_rng(2)
    gs = _plant_sketches(n, rng, s_scaled=s_scaled)

    # per-stage attribution via the pipeline's own Counters — diffed
    # around the fresh run because the instance is process-global and
    # earlier bench stages (e2e_10k before e2e_prod) already fed it.
    # Answers where an e2e second went (primary tile loop vs secondary
    # kernels vs everything else: linkage, IO, compile not inside a
    # counted stage) so a below-parity e2e number is diagnosable from
    # the record instead of re-running with a profiler.
    from drep_tpu.utils.profiling import counters

    def _snap() -> dict:
        return {k: (v.pairs, v.seconds) for k, v in counters.stages.items()}

    ctr_before = _snap()
    paths_before = dict(counters.paths)
    faults_before = dict(counters.faults)
    import contextlib
    import glob as _glob

    if workdir is not None:
        os.makedirs(workdir, exist_ok=True)
        td_ctx = contextlib.nullcontext(workdir)
    else:
        td_ctx = tempfile.TemporaryDirectory()
    with td_ctx as td:
        warm_start_shards = len(
            _glob.glob(os.path.join(td, "data", "streaming_primary", "*.npz"))
        )
        wd = WorkDirectory(td)
        bdb = pd.DataFrame(
            {"genome": gs.names, "location": [f"/nonexistent/{g}" for g in gs.names]}
        )
        # the planted cache is deterministic (seeded rng), so re-planting
        # over a kept workdir writes identical content and the streaming
        # shard meta (fingerprint over names+sketches) still matches —
        # a previous wedged attempt's shards resume, not recompute
        _save(wd, gs)
        wd.store_arguments(
            "sketch",
            sketch_args_snapshot(bdb["genome"], K, gs.sketch_size, DEFAULT_SCALE, "splitmix64"),
        )
        # a wedged previous attempt may have died between Cdb assembly and
        # its resume leg; measuring "fresh" with a complete Cdb present
        # would time the early-return path. Drop assembled tables, keep
        # shard-level state — exactly the supported mid-run kill state.
        for tbl in ("Cdb", "Ndb", "Mdb"):
            p = os.path.join(td, "data_tables", f"{tbl}.csv")
            if os.path.exists(p):
                os.remove(p)
        t0 = time.perf_counter()
        cdb = d_cluster_wrapper(wd, bdb, streaming_primary=True)
        dt = time.perf_counter() - t0
        ctr_after = _snap()
        stage_seconds = {
            k: round(s - ctr_before.get(k, (0, 0.0))[1], 2)
            for k, (_, s) in ctr_after.items()
            if s - ctr_before.get(k, (0, 0.0))[1] > 0.005
        }
        stage_seconds["other"] = round(dt - sum(stage_seconds.values()), 2)
        retained_edges = int(len(wd.get_db("Mdb"))) if wd.hasDb("Mdb") else -1
        secondary_paths = {
            p: c - paths_before.get(p, 0)
            for p, c in counters.paths.items()
            if c - paths_before.get(p, 0)
        }
        pairs = n * (n - 1) / 2
        n_chips = len(jax.local_devices())
        value = pairs / dt / n_chips
        out = {
            "n_genomes": n,
            "s_scaled": s_scaled,
            "scaled_width_max": int(max(len(s) for s in gs.scaled)),
            "secondary_paths": secondary_paths,
            "seconds": round(dt, 2),
            "stage_seconds": stage_seconds,
            "primary_clusters": int(cdb["primary_cluster"].max()),
            "secondary_clusters": int(cdb["secondary_cluster"].nunique()),
            "retained_edges": retained_edges,
            "peak_host_rss_gb": round(
                resource.getrusage(resource.RUSAGE_SELF).ru_maxrss / 1e6, 2
            ),
            "pairs_per_sec_per_chip": round(value, 1),
            "vs_baseline": round(value / NORTH_STAR_PAIRS_PER_SEC_PER_CHIP, 3),
            "warm_start_shards": warm_start_shards,
            "resume_pending": True,  # removed when the resume leg lands
        }
        # honesty: a run that survived on retries / a quarantined chip /
        # CPU-fallback tiles is NOT the same measurement as a clean one —
        # the fault-tolerance counters (diffed, same idiom as stage_seconds)
        # ride in the record so the merge tooling can tell them apart
        ft_events = {
            k: c - faults_before.get(k, 0)
            for k, c in counters.faults.items()
            if c - faults_before.get(k, 0)
        }
        if ft_events:
            out["fault_tolerance"] = ft_events
        # degraded-pod honesty (same contract as fault-stamped records): a
        # run that lost a pod member and completed via an ownership-epoch
        # bump produced CORRECT results on FEWER chips — its wall-clock is
        # not a clean throughput measurement, and tools/missing_stages.py
        # refuses these stamps as measured perf
        if ft_events.get("pod_epoch_bumps") or ft_events.get("dead_processes"):
            out["pod_epochs"] = 1 + int(ft_events.get("pod_epoch_bumps", 0))
            out["dead_processes"] = int(ft_events.get("dead_processes", 0))
        # membership-churn honesty (ISSUE 9): a run that admitted mid-run
        # joiners or drained members gracefully ran parts of the stage on
        # a DIFFERENT chip count than the record claims — correct results,
        # never measured perf (tools/missing_stages.py refuses the stamp)
        if ft_events.get("pod_joins") or ft_events.get("planned_departures"):
            out["pod_joins"] = int(ft_events.get("pod_joins", 0))
            out["planned_departures"] = int(ft_events.get("planned_departures", 0))
        # autoscale honesty (ISSUE 15): churn DECIDED by the autoscaling
        # controller (join/drain notes carry its stamp) means the chip
        # count was policy-elastic mid-stage — correct results, never a
        # steady-state measurement. The value counts autoscale-driven
        # churn EVENTS this process adopted (a truthy refusal marker),
        # not the controller's decision tally — that lives in its
        # autoscale.jsonl.
        if ft_events.get("autoscale_churn"):
            out["autoscale_decisions"] = int(ft_events["autoscale_churn"])
        if publish is not None:
            publish(out)

        # mid-run kill/resume at scale: drop the assembled tables but keep
        # the shard-level state (streaming row shards + per-cluster
        # secondary checkpoints + sketch cache) — the exact disk state
        # after a kill between secondary compute and Cdb assembly — and
        # re-run; the resume machinery must rebuild Cdb from shards
        # without recomputing pairs
        for tbl in ("Cdb", "Ndb", "Mdb"):
            p = os.path.join(td, "data_tables", f"{tbl}.csv")
            # fail loudly if the workdir layout ever moves: silently
            # deleting nothing would leave Cdb in place and "measure" the
            # early-return path as a perfect resume
            assert os.path.exists(p), f"workdir layout changed? missing {p}"
            os.remove(p)
        t0 = time.perf_counter()
        cdb2 = d_cluster_wrapper(wd, bdb, streaming_primary=True)
        resume_dt = time.perf_counter() - t0
        key = ["genome", "primary_cluster", "secondary_cluster"]
        resume_ok = bool(
            cdb2.sort_values("genome")[key]
            .reset_index(drop=True)
            .equals(cdb.sort_values("genome")[key].reset_index(drop=True))
        )
    out.pop("resume_pending", None)
    out["resume_seconds"] = round(resume_dt, 2)
    out["resume_clusters_match"] = resume_ok
    # RSS may have peaked during the resume leg; refresh the published value
    out["peak_host_rss_gb"] = round(
        resource.getrusage(resource.RUSAGE_SELF).ru_maxrss / 1e6, 2
    )
    # both legs measured: the persistent dir's wedge-resume purpose is
    # served — reclaim the disk (a 100k workdir is multiple GB). Wedges
    # never reach this line, so their shards survive for the next window.
    if workdir is not None:
        shutil.rmtree(workdir, ignore_errors=True)
    return out


PROXY_N, PROXY_S, PROXY_GROUPS = 256, 64, 16


def bench_proxy() -> dict:
    """CPU-measurable PROXIES for when no accelerator is reachable
    (ROADMAP bench self-resilience, slice 3): the quantities the
    perf-guard suite already computes — schedule tile fraction, the LSH
    pruning skip fraction (+ its dense-oracle equality), per-tile
    dispatch overhead, and durable-I/O checksum overhead — measured on
    the 528-tile warm streaming pass. They characterize the SCHEDULING
    and STORAGE layers, which are host-side and hardware-independent;
    they are NOT throughput and carry no pairs/sec fields, and the whole
    record rides under a `proxy_metrics` key that
    tools/missing_stages.py refuses as a speedup claim."""
    import tempfile as _tempfile

    import jax

    from drep_tpu.ops.lsh import build_candidates
    from drep_tpu.parallel.streaming import streaming_mash_edges
    from drep_tpu.utils.profiling import counters
    from drep_tpu.utils.synth import planted_group_sketches

    # group-CONTIGUOUS clusterable layout — the shared planting recipe
    # (utils/synth.py), same data family as the perf guards measure
    n = PROXY_N
    packed = planted_group_sketches(
        n=PROXY_N, s=PROXY_S, groups=PROXY_GROUPS, seed=3
    )

    streaming_mash_edges(packed, k=K, cutoff=0.2, block=8)  # warm the jits
    counters.reset()
    t0 = time.perf_counter()
    want = streaming_mash_edges(packed, k=K, cutoff=0.2, block=8)
    dt_dense = time.perf_counter() - t0
    st = counters.report()["stages"]["primary_compare"]
    proxy: dict = {
        "tile_fraction": st["tile_fraction"],
        "tiles_computed": st["tiles_computed"],
        "dispatch_overhead_us_per_tile": round(dt_dense / st["tiles_computed"] * 1e6, 1),
    }

    # pruning proxies: skip fraction on clusterable data + the
    # equivalence evidence (pruned edges bit-equal to the dense pass)
    cand = build_candidates(packed, keep=0.2, k=K)
    counters.reset()
    got = streaming_mash_edges(packed, k=K, cutoff=0.2, block=8, prune=cand)
    st_p = counters.report()["stages"]["primary_compare"]
    proxy["skip_fraction"] = st_p.get("skip_fraction", 0.0)
    proxy["tiles_skipped_pruned"] = st_p.get("tiles_skipped_pruned", 0)
    proxy["pruned_edges_equal_dense"] = bool(
        all(np.asarray(a).tobytes() == np.asarray(b).tobytes()
            for a, b in zip(got[:3], want[:3]))
    )

    # checksum overhead: checkpointed pass, CRC on vs off, best-of-2
    def best_of_ckpt(root: str, reps: int = 2) -> float:
        best = float("inf")
        for r in range(reps):
            ck = os.path.join(root, f"ck{r}")
            t0 = time.perf_counter()
            streaming_mash_edges(packed, k=K, cutoff=0.2, block=8, checkpoint_dir=ck)
            best = min(best, time.perf_counter() - t0)
        return best

    prev_crc = os.environ.get("DREP_TPU_IO_CRC")  # drep-lint: allow[env-knob] — raw save/restore around the guard's two-leg env override, not a typed read
    with _tempfile.TemporaryDirectory() as td:
        try:
            # BOTH legs pinned explicitly: an operator export of
            # DREP_TPU_IO_CRC=0 (the escape hatch) must not turn this
            # into an off-vs-off "zero overhead" non-measurement
            os.environ["DREP_TPU_IO_CRC"] = "0"
            dt_off = best_of_ckpt(os.path.join(td, "nocrc"))
            os.environ["DREP_TPU_IO_CRC"] = "1"
            dt_on = best_of_ckpt(os.path.join(td, "crc"))
        finally:
            if prev_crc is None:
                os.environ.pop("DREP_TPU_IO_CRC", None)
            else:
                os.environ["DREP_TPU_IO_CRC"] = prev_crc
    proxy["checksum_overhead_frac"] = round(max(0.0, dt_on / dt_off - 1.0), 4)

    return {
        "platform": jax.default_backend(),
        "n_genomes": n,
        "proxy_metrics": proxy,
        "note": (
            "CPU proxy measurements (no accelerator reachable) — "
            "scheduling/storage-layer quantities only, NOT a hardware "
            "speedup claim; tools/missing_stages.py refuses these records "
            "as measured perf"
        ),
    }


def _require_devices(timeout_s: float = 240.0) -> None:
    """Fail loudly (one JSON error line) when the backend is unusable: a
    backend can enumerate its devices and still hang on the first
    execution, so the probe runs a tiny op end to end, not just
    jax.devices()."""
    import threading

    import jax
    import jax.numpy as jnp

    got: list = []
    failed: list = []

    def probe():
        try:
            jax.devices()
            x = jnp.ones((128, 128), jnp.float32)
            jax.block_until_ready(x @ x)
            got.append(True)
        except Exception as e:  # a raising backend must not read as a timeout
            failed.append(repr(e))

    t = threading.Thread(target=probe, daemon=True)
    t.start()
    t.join(timeout_s)
    if not got:
        import os

        err = (
            f"jax backend probe raised: {failed[0]}"
            if failed
            else f"jax backend init/execution probe did not complete within "
            f"{timeout_s:.0f}s — no measurements taken"
        )
        try:
            from drep_tpu import __version__ as version
        except Exception:
            version = None
        print(
            json.dumps(
                {
                    "metric": "genome-pairs/sec/chip",
                    "value": None,
                    "unit": "pairs/s",
                    "vs_baseline": None,
                    "drep_tpu_version": version,
                    "error": err,
                    # structured stage record even on init failure, so the
                    # driver's stage-level tooling sees WHERE it died
                    # instead of an empty document (BENCH_r05 emitted
                    # value:null with no stage data)
                    "stages": {"backend_probe": {"error": err}},
                }
            ),
            flush=True,
        )
        os._exit(2)


RING_ROWS_PER_DEV, RING_SKETCH_S = 128, 256
# production-size block: per-device rows whose [n, n] f32 tile alone
# busts the pre-grid 12 MB VMEM cap — the sizes fused_block_fits used
# to refuse outright; the gridded ring streams them (ISSUE 16)
RING_PROD_ROWS_PER_DEV = 2048


_FUSED_RING_OFF = (
    "the fused pallas_dma ring step does not compile on the supported "
    "toolchain and is off the default dispatch (ops/pallas_ring.py)"
)


def bench_ring_scaling(publish=None) -> dict:
    """Weak-scaling of the HOST-STEPPED dense ring, PER COMM BACKEND
    (ISSUE 8): fixed per-device work (128 rows/device, sketch 256), D
    swept over powers of two up to the mesh, one row per (D, ring_comm).
    On TPU the comms are the shard_map ppermute reference and — when the
    on-device self-check admits it — the fused pallas DMA ring
    (ops/pallas_ring.py), whose rotation hides behind the tile compute;
    MULTICHIP_r05 measured ppermute efficiency 0.806 at D=8 and the
    fused ring targets >= 0.95. Efficiency is tile-normalized:
    ideal T_D = T_1 * tiles(D) / D (the half-ring schedule's
    D*(D+1)/2 block tiles spread over D chips), so the number isolates
    dispatch gaps + non-overlapped rotation, not schedule growth.

    Off-TPU there is NOTHING to claim: the record carries only CPU
    proxies under `proxy_metrics` — the per-step host dispatch gap
    (step-wise wall minus the monolithic single-program wall, per step)
    and interpret-mode step parity (fused pallas ring bytes == ppermute
    ring bytes at D=3/8) — which tools/missing_stages.py refuses as a
    speedup claim, exactly like every other proxy record."""
    import os as _os

    # the CPU proxy needs a multi-device virtual mesh; must land before
    # this process's first backend use (harmless on TPU — the flag only
    # shapes the HOST platform). If jax initialized earlier in this
    # process with 1 device, the proxy degrades gracefully below.
    flags = _os.environ.get("XLA_FLAGS", "")
    if "xla_force_host_platform_device_count" not in flags:
        _os.environ["XLA_FLAGS"] = (
            flags + " --xla_force_host_platform_device_count=8"
        ).strip()
    import jax

    from drep_tpu.ops.minhash import PackedSketches
    from drep_tpu.parallel.allpairs import (
        configure_ring,
        half_ring_steps,
        ring_allpairs,
        ring_tiles_computed,
    )
    from drep_tpu.parallel.mesh import make_mesh

    configure_ring()  # memory-only rings: no store base, no comm pin
    platform = jax.default_backend()
    n_devices = len(jax.devices())
    out: dict = {"backend": platform, "n_devices": n_devices}
    if publish is not None:
        publish(dict(out, measurement_pending=True))
    if n_devices < 2:
        out["error"] = (
            f"ring scaling needs >= 2 devices, backend {platform!r} has "
            f"{n_devices} (CPU proxy wants XLA_FLAGS device-count forcing "
            f"before jax init)"
        )
        return out

    rng = np.random.default_rng(1)

    def _packed(n: int) -> PackedSketches:
        ids = np.sort(
            rng.integers(0, 2**30, size=(n, RING_SKETCH_S), dtype=np.int32),
            axis=1,
        )
        return PackedSketches(
            ids=ids,
            counts=np.full(n, RING_SKETCH_S, np.int32),
            names=[f"g{i}" for i in range(n)],
        )

    def _time_ring(packed, mesh, comm: str) -> float:
        ring_allpairs(packed, "mash", K, mesh=mesh, ring_comm=comm)  # warm
        return _best_of(
            lambda: ring_allpairs(packed, "mash", K, mesh=mesh, ring_comm=comm)
        )

    if platform == "tpu":
        # the fused pallas_dma step does not compile on the supported
        # toolchain and is off the default dispatch (ops/pallas_ring.py)
        comms = ["ppermute"]
        out["pallas_dma_unavailable"] = True
        out["pallas_ring_unavailable_reason"] = _FUSED_RING_OFF
        sizes = sorted(
            {d for d in (1, 2, 4, 8, 16) if d <= n_devices} | {n_devices}
        )
        # D=1 has no rotation to overlap — ONE baseline row, shared by
        # every comm's ideal (the per-tile compute term is comm-free)
        t1 = _time_ring(_packed(RING_ROWS_PER_DEV), make_mesh(1), "ppermute")
        rows = [
            {
                "D": 1, "ring_comm": "ppermute", "seconds": round(t1, 4),
                "steps": 1, "tiles": 1, "efficiency": 1.0,
            }
        ]
        for comm in comms:
            for d in (s for s in sizes if s > 1):
                mesh = make_mesh(d)
                packed = _packed(RING_ROWS_PER_DEV * d)
                dt = _time_ring(packed, mesh, comm)
                tiles = ring_tiles_computed(d, half=True)
                rows.append(
                    {
                        "D": d,
                        "ring_comm": comm,
                        "seconds": round(dt, 4),
                        "steps": half_ring_steps(d),
                        "tiles": tiles,
                        "efficiency": round(t1 * tiles / d / dt, 3),
                    }
                )
        # production-size blocks — the rows the pre-grid
        # `fused_block_fits` gate refused outright (working set past its
        # 12 MB cap). The gridded kernel streams them; no efficiency
        # normalization (no matching T_1 baseline at this block size),
        # the wall-clock and the per-comm ratio ARE the claim.
        from drep_tpu.ops.pallas_ring import fused_ring_tile

        d_max = max(sizes)
        mesh_prod = make_mesh(d_max)
        packed_prod = _packed(RING_PROD_ROWS_PER_DEV * d_max)
        for comm in comms:
            dt = _time_ring(packed_prod, mesh_prod, comm)
            rows.append(
                {
                    "D": d_max,
                    "ring_comm": comm,
                    "rows_per_device": RING_PROD_ROWS_PER_DEV,
                    "seconds": round(dt, 4),
                    "steps": half_ring_steps(d_max),
                    "tiles": ring_tiles_computed(d_max, half=True),
                    "block": "production (past the pre-grid 12 MB cap)",
                    "grid_tile_rows": fused_ring_tile(
                        RING_PROD_ROWS_PER_DEV, RING_SKETCH_S
                    ),
                }
            )
        out["rows"] = rows
        out["efficiency_at_max_D"] = {
            comm: max(
                (r["efficiency"] for r in rows
                 if r["ring_comm"] == comm and r["D"] == max(sizes)),
                default=None,
            )
            for comm in comms
        }
        return out

    # -- CPU proxies (no hardware claim; refused by missing_stages) ------
    proxy: dict = {}
    d = min(8, n_devices)
    mesh = make_mesh(d)
    packed = _packed(RING_ROWS_PER_DEV * d)
    t_step = _time_ring(packed, mesh, "ppermute")
    ring_allpairs(packed, "mash", K, mesh=mesh, monolithic=True)  # warm
    t_mono = _best_of(
        lambda: ring_allpairs(packed, "mash", K, mesh=mesh, monolithic=True)
    )
    n_steps = half_ring_steps(d)
    proxy["rows"] = [
        {"D": d, "ring_comm": "ppermute", "seconds": round(t_step, 4)},
        {"D": d, "ring_comm": "monolithic_reference", "seconds": round(t_mono, 4)},
    ]
    # what host-stepping costs per step over the single fused program —
    # the dispatch gap the fused DMA ring removes ON HARDWARE (on CPU the
    # "devices" share the host, so this is a scheduling-layer number only)
    proxy["dispatch_gap_ms_per_step"] = round(
        max(0.0, t_step - t_mono) / n_steps * 1e3, 3
    )
    # interpret-mode step parity: the fused pallas kernel must reproduce
    # the ppermute ring bit-for-bit (the tier-1 equality pin, re-proven
    # here on the bench data shape at odd and even D)
    parity = {}
    for dp in sorted({3, d} & set(range(2, n_devices + 1))):
        mesh_p = make_mesh(dp)
        packed_p = _packed(RING_ROWS_PER_DEV * dp)
        want = ring_allpairs(packed_p, "mash", K, mesh=mesh_p, ring_comm="ppermute")
        got = ring_allpairs(
            packed_p, "mash", K, mesh=mesh_p, ring_comm="pallas_interpret"
        )
        parity[f"D{dp}"] = bool(
            all(a.tobytes() == b.tobytes() for a, b in zip(got, want))
        )
    proxy["interpret_step_parity"] = parity
    # GRIDDED interpret parity at a production-size block (the [n, n] f32
    # tile alone busts the pre-grid 12 MB cap, so the kernel MUST grid) —
    # the CPU pin that arbitrary block sizes stream bit-identically.
    # Narrow sketch keeps the merge compute CPU-affordable; the grid
    # pressure comes from the n^2 output tile, which is width-free.
    from drep_tpu.ops.pallas_ring import fused_ring_tile

    ng, sg, dg = 1792, 8, 3
    if dg <= n_devices:
        tile_rows = fused_ring_tile(ng, sg)
        mesh_g = make_mesh(dg)
        ids_g = np.sort(
            rng.integers(0, 2**30, size=(ng * dg, sg), dtype=np.int32), axis=1
        )
        packed_g = PackedSketches(
            ids=ids_g,
            counts=np.full(ng * dg, sg, np.int32),
            names=[f"g{i}" for i in range(ng * dg)],
        )
        want = ring_allpairs(packed_g, "mash", K, mesh=mesh_g, ring_comm="ppermute")
        got = ring_allpairs(
            packed_g, "mash", K, mesh=mesh_g, ring_comm="pallas_interpret"
        )
        proxy["gridded_interpret_step_parity"] = {
            "rows_per_device": ng,
            "sketch": sg,
            "D": dg,
            "grid_tile_rows": tile_rows,
            "gridded": tile_rows < ng,
            "bit_identical": bool(
                all(a.tobytes() == b.tobytes() for a, b in zip(got, want))
            ),
        }
    # why the fused path is not a hardware claim here (the same reason
    # resolve_ring_comm stamps beside the ring_comm_pallas gauge)
    proxy["pallas_ring_unavailable_reason"] = _FUSED_RING_OFF
    out["proxy_metrics"] = proxy
    out["note"] = (
        "CPU proxy measurements (no accelerator reachable) — "
        "scheduling-layer quantities + interpret-mode parity only, NOT a "
        "hardware speedup claim"
    )
    return out


def link_health() -> dict:
    """Host-link context for interpreting every stage number: round-trip
    dispatch latency (median of 10 tiny ops) and host<->device transfer
    bandwidth on a 16 MB block — without these fields a degraded link is
    indistinguishable from a kernel regression in the record."""
    import statistics

    import jax
    import jax.numpy as jnp

    x = jnp.ones((8, 128), jnp.float32)
    jax.block_until_ready(x + 1.0)  # compile outside the timing
    lats = []
    for _ in range(10):
        t0 = time.perf_counter()
        jax.block_until_ready(x + 1.0)
        lats.append(time.perf_counter() - t0)
    big = np.ones((2048, 2048), np.float32)  # 16 MiB
    t0 = time.perf_counter()
    dev = jax.block_until_ready(jax.device_put(big))
    h2d = big.nbytes / (time.perf_counter() - t0)
    t0 = time.perf_counter()
    np.asarray(dev)
    d2h = big.nbytes / (time.perf_counter() - t0)
    out = {
        "dispatch_ms_median": round(statistics.median(lats) * 1e3, 2),
        "h2d_gbps": round(h2d / 1e9, 3),
        "d2h_gbps": round(d2h / 1e9, 3),
    }
    return out


def _emit(stages: dict) -> None:
    """The one JSON line the driver records. Callable from the watchdog,
    so a mid-run hang still reports every stage measured so far.

    `value` prefers the primary headline but FALLS BACK to the first stage
    that measured a rate (value_source names it): a run where the headline
    stage wedged but others completed must not read as `value: null` —
    partial results beat null (BENCH_r05 post-mortem)."""
    try:
        from drep_tpu import __version__ as version
    except Exception:  # provenance must never block the record
        version = None
    try:
        from drep_tpu.utils import envknobs

        fault_spec = envknobs.env_str("DREP_TPU_FAULTS")
    except Exception:  # same contract: a broken install still gets a record
        fault_spec = os.environ.get("DREP_TPU_FAULTS")  # drep-lint: allow[env-knob] — import-failure fallback; provenance must never block the record
    if fault_spec:
        # chaos-mode provenance, stamped INTO each stage record so it
        # survives the partial-merge tooling: an injected-fault bench run
        # must never be mistaken for a clean measurement
        # (tools/missing_stages.py treats stamped records as not-done)
        for st in stages.values():
            if isinstance(st, dict):
                st["faults_injected"] = fault_spec
    # degraded-pod provenance, stamped into EVERY stage record (ISSUE 4 —
    # previously only the streaming e2e stage stamped it): a DENSE ring or
    # SECONDARY stage that survived a pod-member death via the elastic
    # protocol produced correct numbers on fewer chips, and
    # tools/missing_stages.py must refuse every such record as measured
    # perf, not just the streaming one. DELIBERATELY CONSERVATIVE: the
    # process-global pod state cannot attribute the death to a stage, so
    # once the pod is degraded at emission time every un-stamped stage in
    # the run is marked for re-measure — stages that happened to finish
    # before the death are sacrificed rather than risk laundering a
    # degraded number as clean (bench_e2e's own per-stage ft_events diff
    # already stamped the precise stage, and "pod_epochs" not in st keeps
    # that finer stamp authoritative).
    try:
        from drep_tpu.parallel.faulttol import pod_dead, pod_epoch, pod_live

        if pod_live() is not None:
            for st in stages.values():
                if isinstance(st, dict) and "pod_epochs" not in st:
                    st["pod_epochs"] = pod_epoch() + 1
                    st["dead_processes"] = len(pod_dead())
    except Exception:  # provenance must never block the record
        pass
    # membership-churn provenance (ISSUE 9), stamped into EVERY stage
    # record with the same conservatism: a mid-run JOIN admitted capacity
    # partway (wall-clock spans two chip counts), a planned DRAIN shed it
    # — both are counters because a pure-join run deliberately leaves the
    # downstream pod state healthy. tools/missing_stages.py refuses any
    # membership-churned record as measured perf.
    try:
        from drep_tpu.utils.profiling import counters as _pod_counters

        joins = int(_pod_counters.faults.get("pod_joins", 0))
        departs = int(_pod_counters.faults.get("planned_departures", 0))
        if joins or departs:
            for st in stages.values():
                if isinstance(st, dict) and "pod_joins" not in st:
                    st["pod_joins"] = joins
                    st["planned_departures"] = departs
        # autoscale-churn provenance (ISSUE 15), same conservatism: the
        # join/drain notes an autoscaling controller's spawned capacity
        # publishes are stamped, every member books autoscale_churn, and
        # a governed run's wall-clock describes a POLICY-elastic chip
        # count — tools/missing_stages.py refuses it as measured perf
        # (the PR 9 membership-churn rule, attributed to its decider)
        churn = int(_pod_counters.faults.get("autoscale_churn", 0))
        if churn:
            for st in stages.values():
                if isinstance(st, dict) and "autoscale_decisions" not in st:
                    st["autoscale_decisions"] = churn
    except Exception:  # provenance must never block the record
        pass
    # storage-side I/O provenance (ISSUE 5), stamped into EVERY stage
    # record: a run that healed corrupt shards RECOMPUTED work the record
    # does not time-attribute (healing == recompute, the same refusal
    # contract as pod degradation — tools/missing_stages.py), and a run
    # that burned transient-I/O retries ran against a degraded filesystem.
    # Conservative like the pod stamp: the process-global counters cannot
    # attribute a heal to one stage, so every record in the run carries it.
    try:
        from drep_tpu.utils.profiling import counters as _io_counters

        io_retries = int(_io_counters.faults.get("io_retries", 0))
        healed = int(_io_counters.faults.get("corrupt_shards_healed", 0))
        unrecoverable = int(_io_counters.faults.get("io_unrecoverable", 0))
        if io_retries or healed or unrecoverable:
            for st in stages.values():
                if isinstance(st, dict) and "corrupt_shards_healed" not in st:
                    st["io_retries"] = io_retries
                    st["corrupt_shards_healed"] = healed
                    st["io_unrecoverable"] = unrecoverable
    except Exception:  # provenance must never block the record
        pass
    head = stages.get("primary", {})
    value = head.get("pairs_per_sec_per_chip") if isinstance(head, dict) else None
    vs = head.get("vs_baseline") if isinstance(head, dict) else None
    source = "primary"
    if value is None:
        for name, st in stages.items():
            if not isinstance(st, dict):
                continue
            if st.get("pairs_per_sec_per_chip") is not None:
                value, vs, source = st["pairs_per_sec_per_chip"], st.get("vs_baseline"), name
                break
            # secondary_production / dispatch_crossover nest their rate
            # fields one level down (per-kernel sub-records) — a run where
            # only those completed must still report a value
            for sub_name, sub in st.items():
                if isinstance(sub, dict) and sub.get("pairs_per_sec_per_chip") is not None:
                    value = sub["pairs_per_sec_per_chip"]
                    vs = sub.get("vs_baseline")
                    source = f"{name}.{sub_name}"
                    break
            if value is not None:
                break
    doc = {
        "metric": "genome-pairs/sec/chip",
        "value": value,
        "unit": "pairs/s",
        "vs_baseline": vs,
        "drep_tpu_version": version,
        "stages": stages,
    }
    if value is not None and source != "primary":
        doc["value_source"] = source
    print(json.dumps(doc), flush=True)


def _stage_budget(label: str, args) -> float:
    """THE per-stage watchdog budget in seconds — ONE table consumed by
    both the child's in-process stage watchdog and the parent's
    subprocess timeout (parent adds startup slack on top), so the two
    can never drift: a parent deadline below the child's own budget
    would kill healthy children mid-stage. Budgets are ~4x the longest
    wall ever measured for the stage; the scale
    budget grows quadratically with scale_n (device pair count does),
    capped at 2h — beyond that a wedge is indistinguishable from slow."""
    if label == "scale":
        return min(7200.0, 3000.0 * max(1.0, (args.scale_n / 50_000.0) ** 2))
    return {
        "link": 120.0, "primary": 600.0, "secondary": 600.0, "e2e": 1200.0,
        "prod": 2400.0, "ingest": 1200.0, "greedy": 1200.0,
        "production": 1500.0, "crossover": 1500.0, "proxy": 900.0,
        "ring": 900.0,
    }[label]


def _stamp_backend(stages: dict) -> None:
    """Stamp a ``backend`` marker into every stage record when the run
    executed on anything other than a real TPU: a CPU run (an operator
    forcing JAX_PLATFORMS=cpu, a machine with no chip) can legitimately RUN the
    hardware stages, but their rates are not chip measurements and must
    never merge into the round as such — tools/missing_stages.py refuses
    non-tpu-stamped records. TPU runs stay unstamped (the historical
    record shape). Best-effort: provenance must never block a record."""
    try:
        import jax

        backend = jax.default_backend()
    except Exception:
        return
    if backend == "tpu":
        return
    for st in stages.values():
        if isinstance(st, dict) and "backend" not in st:
            st["backend"] = backend


def _record_stage_error(stages: dict, label: str, msg: str) -> None:
    """Record a stage failure as `{"error": ...}` INSIDE the stage's dict
    (merging with any early-published partial measurements) rather than a
    side-channel key: partial numbers + a structured error beat both a
    bare error string and a silently absent stage."""
    entry = stages.get(label)
    if isinstance(entry, dict):
        entry = dict(entry)  # the worker thread may still hold a reference
        entry["error"] = msg
        stages[label] = entry
    else:
        stages[label] = {"error": msg}


def _stall_site() -> dict | None:
    """Wedge diagnosis (ISSUE 11 satellite): when the wedged stage was
    TRACED (`--events on` / DREP_TPU_EVENTS=on routed its telemetry into
    a workdir log dir), read its own event logs through
    tools/trace_report.py's stall_diagnosis and name the in-flight span
    — the durable stage record then says WHERE the run stalled (which
    stripe/ring-step/stage was open when the stream went quiet), not
    just that the watchdog fired. Best-effort: diagnosis must never
    block the bail that makes the record durable."""
    try:
        import importlib.util

        from drep_tpu.utils import telemetry

        log_dir = telemetry.configured_log_dir()
        if not log_dir or not os.path.isdir(log_dir):
            return None
        spec = importlib.util.spec_from_file_location(
            "_bench_trace_report",
            os.path.join(os.path.dirname(os.path.abspath(__file__)),
                         "tools", "trace_report.py"),
        )
        tr = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(tr)
        return tr.stall_diagnosis(log_dir)
    except Exception:  # noqa: BLE001 — forensics, not a dependency
        return None


def _clear_partial() -> None:
    import os

    try:
        os.remove("BENCH_PARTIAL.json")
    except OSError:
        pass


# --- durable per-stage records (ROADMAP bench self-resilience, slice 1) ----
# BENCH r03-r05 lost entire rounds to a single wedged stage because the only
# record was the end-of-run JSON line. Now every stage record ALSO lands in
# its own durable (atomic + checksummed, utils/durableio.py) file the moment
# the stage completes, and the partial-merge runs automatically at exit —
# a wedged stage costs one cell, not the round, and the merged artifact
# never has to be hand-made again (BENCH_r04_merged.json was).

STAGE_DIR = ".bench_stages"


def _version() -> str | None:
    try:
        from drep_tpu import __version__

        return __version__
    except Exception:
        return None


_MERGE_TOOL = None


def _merge_tool():
    """tools/merge_bench_partials.py, loaded by path once (tools/ is not
    a package) — its prefer_new() is THE record-preference rule, shared
    so the per-stage store and the attempt-partial merge cannot drift."""
    global _MERGE_TOOL
    if _MERGE_TOOL is None:
        import importlib.util

        loc = os.path.join(
            os.path.dirname(os.path.abspath(__file__)), "tools", "merge_bench_partials.py"
        )
        spec = importlib.util.spec_from_file_location("merge_bench_partials", loc)
        mod = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(mod)
        _MERGE_TOOL = mod
    return _MERGE_TOOL


def _persist_stages(stages: dict) -> None:
    """Write each stage's current record to .bench_stages/<key>.json —
    durable (atomic publish, in-band checksum) so an external SIGKILL
    between stages can never take completed measurements with it. Records
    from an OLDER code version are replaced unconditionally (new code =
    new measurements); within a version the shared prefer_new rule keeps
    the better record. Best-effort: persistence must never break a run."""
    try:
        from drep_tpu.utils.durableio import atomic_write_json, read_json_checked

        os.makedirs(STAGE_DIR, exist_ok=True)
        mbp = _merge_tool()
        version = _version()
        for key, rec in dict(stages).items():
            loc = os.path.join(STAGE_DIR, f"{key}.json")
            if os.path.exists(loc):
                try:
                    old = read_json_checked(loc, what="bench stage record")
                    if old.get("version") == version:
                        old_rec = old.get("record")
                        if old_rec == rec:
                            continue  # unchanged: no rewrite churn
                        new_err = isinstance(rec, dict) and "error" in rec
                        old_err = isinstance(old_rec, dict) and "error" in old_rec
                        if new_err and not old_err:
                            continue  # a failure never shadows a success
                        if not mbp.prefer_new(old_rec, rec):
                            continue
                except Exception:
                    pass  # unreadable old record: replace it
            atomic_write_json(loc, {"stage": key, "version": version, "record": rec})
    except Exception:
        pass


def _auto_merge() -> None:
    """Union the durable per-stage records into BENCH_merged.json — run
    at EVERY exit (normal completion AND the wedge bail), so the merged
    artifact always reflects everything any attempt of this code version
    measured. Best-effort."""
    import glob as _glob

    try:
        from drep_tpu.utils.durableio import atomic_write_bytes, read_json_checked

        stages: dict = {}
        for f in sorted(_glob.glob(os.path.join(STAGE_DIR, "*.json"))):
            try:
                doc = read_json_checked(f, what="bench stage record")
            except Exception:
                continue  # rotted stage record: its stage re-measures
            if doc.get("version") != _version():
                continue  # stale round / older code: never merged forward
            if doc.get("stage"):
                stages[doc["stage"]] = doc.get("record")
        if not stages:
            return
        merged = _merge_tool().merge([(1, {"drep_tpu_version": _version(), "stages": stages})])
        merged["merged_from"] = ["durable stage records (.bench_stages/)"]

        atomic_write_bytes(
            "BENCH_merged.json", (json.dumps(merged, indent=1) + "\n").encode()
        )
    except Exception:
        pass


def _build_cli() -> argparse.ArgumentParser:
    ap = argparse.ArgumentParser()
    ap.add_argument(
        "--stages",
        default="all",
        help="comma list: primary,secondary,ring,production,crossover,ingest,greedy,e2e,prod,scale,proxy",
    )
    ap.add_argument("--e2e_n", type=int, default=10_000)
    # n=10k: large enough that compile/fixed costs amortize (VERDICT r4
    # missing #1 — the 5k composite could not distinguish fixed cost from
    # secondary throughput), small enough for the 2400 s stage watchdog
    ap.add_argument("--prod_n", type=int, default=10_000)
    ap.add_argument("--scale_n", type=int, default=50_000)
    ap.add_argument(
        "--reverse",
        action="store_true",
        help="run the stage plan in reverse order (the wedge-retry loop "
        "alternates this so a repeatedly-wedging stage cannot starve the "
        "stages behind it; avoids duplicating the stage list out of repo)",
    )
    # internal: the per-stage ISOLATION children (ROADMAP bench
    # self-resilience slice 2). --probe_child runs the backend probe alone;
    # --child runs the given stage plan in-process (the parent already
    # probed, owns the legacy partial file, and enforces its own timeout
    # around this whole process — a wedge here costs only this child).
    ap.add_argument("--probe_child", action="store_true", help=argparse.SUPPRESS)
    ap.add_argument("--child", action="store_true", help=argparse.SUPPRESS)
    return ap


def main() -> None:
    import os
    import sys

    from drep_tpu.utils.xla_cache import enable_persistent_cache

    enable_persistent_cache()
    args = _build_cli().parse_args()
    if args.probe_child:
        # isolated backend probe: _require_devices emits the error doc and
        # exits 2 on a broken backend; the PARENT captures this process's
        # stdout either way, so nothing here can violate the one-line
        # contract. A hung backend hangs THIS process only.
        _require_devices()
        import jax

        print(
            json.dumps(
                {"platform": jax.default_backend(),
                 "n_devices": len(jax.local_devices())}
            ),
            flush=True,
        )
        return
    # ORDERED: the default order is by measurement value (see below), but
    # an explicit --stages list runs in the order given — a hang at the
    # same stage every attempt would otherwise starve every stage queued
    # behind it across retries.
    # Validated HERE, before the partial-clear and the device probe: a
    # usage error is in the same class as --help — it must neither
    # destroy a previous run's recovery record nor burn the probe budget
    default_order = [
        "primary", "secondary", "ring", "e2e", "prod", "scale",
        "ingest", "greedy", "production", "crossover",
    ]
    if args.stages == "all":
        want = default_order
    elif args.stages == "none":  # contract probe: emit the line, run nothing
        want = []
    else:
        want = [s for s in args.stages.split(",") if s]
    # "link" is accepted explicitly (not in the default plan order — it is
    # auto-prepended): `--stages link` is the cheapest real-stage run, used
    # by the durable-stage-record contract test. "proxy" likewise: it is
    # auto-SUBSTITUTED for the default plan when no accelerator answers.
    unknown = set(want) - set(default_order) - {"link", "proxy"}
    if unknown:
        print(f"bench: unknown stages {sorted(unknown)}", file=sys.stderr)
        sys.exit(2)
    # dedup preserving first occurrence (the old set-based parsing ran
    # each stage once; an accidental `scale,prod,scale` must not double
    # the longest stage's wall time and wedge exposure)
    want = list(dict.fromkeys(want))
    if args.reverse:
        want = want[::-1]
    if args.child:
        _child_main(want, args)
        return
    _parent_main(want, args)


def _child_main(want: list, args) -> None:
    """One isolation child: run the given stage plan IN-PROCESS — the
    pre-isolation main loop (per-stage watchdog threads, early-publish
    persistence, the wedge bail) minus the probe (the parent ran it in
    its own subprocess) and minus the legacy BENCH_PARTIAL bookkeeping
    (the parent owns it). A wedge here takes only this process: the bail
    persists everything measured, refreshes the merged artifact, and
    exits 3 — the parent records the verdict and moves to the NEXT
    stage's child."""
    import os
    import sys
    import threading

    # (label, budget_seconds, thunk). Budgets are ~4x the longest wall
    # ever measured for the stage: a device call that never returns
    # leaves the CPU idle, and without a deadline the whole measurement
    # window produces zero output.
    #
    # Stage ORDER is by measurement value, not pipeline order: one
    # observed wedge struck during the production stage's first big
    # compile (v_pad 2^19 indicator matmul — the widest new shape of the
    # run), killing every stage queued behind it. The headline and the
    # end-to-end numbers therefore run before the compile-heavy
    # production/greedy shapes, and ingest (host-only, no device calls)
    # slots in between.
    stages: dict = {}

    def _secondary():
        packed = _secondary_pack()
        stages["secondary_matmul"] = bench_secondary_matmul(packed)
        stages["secondary_pallas"] = bench_secondary_pallas(packed)

    # prod: round-3 flagship COMPOSED — streaming primary + beyond-budget
    # chunked/range secondary + sparse UPGMA as one measured pipeline at
    # production sketch depth (VERDICT r3 weak #5). crossover: its own
    # watchdogged stage — 8 fresh kernel shapes compile there, and a wedge
    # during them must not cost the production stage's already-measured
    # results.
    # budgets come from _stage_budget — the ONE table shared with the
    # parent's subprocess timeouts, so the two deadlines cannot drift
    registry: dict[str, object] = {
        # publish= places the headline in `stages` the moment it exists,
        # so a wedge during the later variant compiles still bails with
        # the headline in the snapshot (attempt 2 lost it exactly there)
        "primary": lambda: stages.__setitem__(
            "primary",
            bench_primary(publish=lambda o: stages.__setitem__("primary", o)),
        ),
        "secondary": _secondary,
        "e2e": lambda: stages.__setitem__(
            f"e2e_{args.e2e_n // 1000}k",
            bench_e2e(args.e2e_n, publish=lambda o: stages.__setitem__(
                f"e2e_{args.e2e_n // 1000}k", o))),
        "prod": lambda: stages.__setitem__(
            "e2e_prod",
            bench_e2e(args.prod_n, s_scaled=20_000,
                      publish=lambda o: stages.__setitem__("e2e_prod", o))),
        # persistent workdir: a scale run that wedges mid-way leaves its
        # row-block shards for the next recovery window to finish from
        # (warm_start_shards marks such records; .bench_wd/ is gitignored)
        "scale": lambda: stages.__setitem__(
            f"e2e_{args.scale_n // 1000}k",
            bench_e2e(args.scale_n,
                      publish=lambda o: stages.__setitem__(
                          f"e2e_{args.scale_n // 1000}k", o),
                      workdir=os.path.join(
                          ".bench_wd", f"scale_{args.scale_n}"))),
        "ingest": lambda: stages.__setitem__("ingest", bench_ingest()),
        "greedy": lambda: stages.__setitem__(
            "greedy_secondary", bench_greedy()),
        "production": lambda: stages.__setitem__(
            "secondary_production",
            bench_secondary_production(publish=lambda o: stages.__setitem__(
                "secondary_production", o))),
        "crossover": lambda: stages.__setitem__(
            "dispatch_crossover",
            bench_dispatch_crossover(publish=lambda o: stages.__setitem__(
                "dispatch_crossover", o))),
        # per-comm-backend weak scaling of the host-stepped dense ring
        # (ISSUE 8): ppermute vs the fused pallas DMA ring on hardware;
        # CPU runs record dispatch-gap/parity proxies only
        "ring": lambda: stages.__setitem__(
            "ring_scaling",
            bench_ring_scaling(publish=lambda o: stages.__setitem__(
                "ring_scaling", o))),
        # the accelerator-less plan (auto-substituted by the parent when
        # the probe answers with a CPU backend): host-measurable proxies
        "proxy": lambda: stages.__setitem__("proxy_metrics", bench_proxy()),
        "link": lambda: stages.__setitem__("link", link_health()),
    }
    # link context first, under its own watchdog (a wedge here must still
    # emit an honest record): every later stage is read against these
    # latency/bandwidth numbers. Skipped when no stages run — `--stages
    # none` is the instant emit-contract probe and must not dispatch real
    # device work (a hung backend would turn it into a 120 s rc=3)
    # label -> the key the stage publishes under in `stages`: error records
    # must merge INTO that entry (a partial secondary_production record
    # with no error field is indistinguishable from a complete one).
    # "secondary" keeps its label — it fans into two sub-records and the
    # error cannot be attributed to one of them from here.
    stage_keys = {
        "e2e": f"e2e_{args.e2e_n // 1000}k",
        "prod": "e2e_prod",
        "scale": f"e2e_{args.scale_n // 1000}k",
        "greedy": "greedy_secondary",
        "production": "secondary_production",
        "crossover": "dispatch_crossover",
        "ring": "ring_scaling",
        "proxy": "proxy_metrics",
    }

    # NO link auto-prepend here: the parent schedules link as its own
    # isolation child ahead of the plan — a child runs exactly what it
    # was told (the contract tests invoke `--stages link` directly)
    plan: list[tuple[str, float, object]] = [
        (label, _stage_budget(label, args), registry[label]) for label in want
    ]

    for label, budget, thunk in plan:
        t0 = time.perf_counter()
        done = threading.Event()

        def run(thunk=thunk, label=label):
            try:
                thunk()
            except Exception as e:  # a broken stage must not kill the rest
                import traceback

                _record_stage_error(stages, stage_keys.get(label, label), repr(e))
                traceback.print_exc()  # the JSON repr alone is undebuggable
            finally:
                done.set()

        worker = threading.Thread(target=run, daemon=True)
        worker.start()
        if not done.wait(budget) and label == "link":
            # link is CONTEXT, not a measurement: a slow-but-alive link
            # (the documented 5.3x degradation mode) can overrun 120 s on
            # the 16 MiB transfers, and bailing here would starve every
            # real stage on every retry. Record and continue — a truly
            # hung backend is caught by the first real stage's own
            # watchdog, which does bail.
            stages["link"] = {"error": f"link probe exceeded {budget:.0f}s"}
            print(f"bench: link overran {budget:.0f}s, continuing", file=sys.stderr, flush=True)
            continue
        if not done.wait(0):
            # a wedged device call cannot be cancelled from Python; any
            # later stage would block on the same dead backend. Emit what
            # exists and exit nonzero so the run is visibly partial.
            # snapshot: the wedged worker thread may still be mutating
            # `stages` (e.g. between the two secondary sub-benches), and
            # json.dumps over a resizing dict raises — which would skip
            # the very output line this path exists to guarantee
            snap = dict(stages)
            key = stage_keys.get(label, label)
            _record_stage_error(
                snap,
                key,
                f"stage exceeded its {budget:.0f}s watchdog budget "
                "— remaining stages skipped",
            )
            # a TRACED wedge names its own stall site in the durable
            # record (trace_report.stall_diagnosis over the stage's own
            # event logs): which span was open, where the stream stopped
            stall = _stall_site()
            if stall is not None and isinstance(snap.get(key), dict):
                entry = dict(snap[key])
                entry["stall"] = stall
                snap[key] = entry
                site = stall.get("stall_site") or stall.get("last_event") or {}
                print(
                    f"bench: {label} stall site: {site}", file=sys.stderr, flush=True
                )
            print(f"bench: {label} WEDGED after {budget:.0f}s, bailing", file=sys.stderr, flush=True)
            _stamp_backend(snap)
            _emit(snap)
            # the wedge costs ONE cell: everything measured so far (plus
            # the wedged stage's error record) lands durably and the
            # merged artifact refreshes before the hard exit. The legacy
            # BENCH_PARTIAL belongs to the parent — untouched here.
            _persist_stages(snap)
            _auto_merge()
            os._exit(3)
        print(
            f"bench: {label} done in {time.perf_counter() - t0:.1f}s",
            file=sys.stderr,
            flush=True,
        )
        # durable per-stage record the moment the stage completes: an
        # external SIGKILL of this child (parent watchdog, driver
        # timeout) costs only the unfinished stage — everything else is
        # already atomic+checksummed on disk for the parent/auto-merge
        _persist_stages(stages)

    _stamp_backend(stages)
    _emit(stages)
    # this child's line is captured by the parent (which emits the ONE
    # driver line itself); the durable records + merged artifact are the
    # cross-process hand-off
    _persist_stages(stages)
    _auto_merge()
    if "primary" in want and "pairs_per_sec_per_chip" not in stages.get("primary", {}):
        # headline failed by exception (its stage entry is an {"error": ...}
        # record or absent): the JSON line above still carries every other
        # stage, but the run must read as broken (matching the pre-watchdog
        # behavior where bench_primary ran bare)
        sys.exit(1)


# plan label -> the durable stage-record key(s) a successful child leaves
# under .bench_stages/ (the parent re-assembles its emitted line from these)
def _label_record_keys(label: str, args) -> list:
    return {
        "link": ["link"],
        "primary": ["primary"],
        "secondary": ["secondary_matmul", "secondary_pallas"],
        "e2e": [f"e2e_{args.e2e_n // 1000}k"],
        "prod": ["e2e_prod"],
        "scale": [f"e2e_{args.scale_n // 1000}k"],
        "ingest": ["ingest"],
        "greedy": ["greedy_secondary"],
        "production": ["secondary_production"],
        "crossover": ["dispatch_crossover"],
        "ring": ["ring_scaling"],
        "proxy": ["proxy_metrics"],
    }.get(label, [label])


def _collect_records(keys) -> dict:
    """Current-version durable stage records for `keys`, checked reads —
    the parent's view of what its children measured (best-of across
    attempts by construction: children persist through prefer_new)."""
    out: dict = {}
    try:
        from drep_tpu.utils.durableio import read_json_checked

        for key in keys:
            loc = os.path.join(STAGE_DIR, f"{key}.json")
            if not os.path.exists(loc):
                continue
            try:
                doc = read_json_checked(loc, what="bench stage record")
            except Exception:
                continue  # rotted record: its stage reads as unmeasured
            if doc.get("version") != _version():
                continue
            out[key] = doc.get("record")
    except Exception:
        pass
    return out


_PROBE_BUDGET_S = 300.0  # > _require_devices' own 240 s watchdog


def _probe_subprocess(env=None):
    """The backend probe in its OWN process (ROADMAP bench
    self-resilience slice 2): a backend that hangs inside client init or
    the first dispatched op takes the CHILD with it, not the run.
    Returns ("ok", {platform, n_devices}) | ("failed", msg) |
    ("wedged", msg)."""
    import subprocess
    import sys

    cmd = [sys.executable, os.path.abspath(__file__), "--probe_child"]
    try:
        r = subprocess.run(
            cmd, capture_output=True, text=True, env=env, timeout=_PROBE_BUDGET_S
        )
    except subprocess.TimeoutExpired:
        return "wedged", (
            f"backend probe subprocess did not finish within "
            f"{_PROBE_BUDGET_S:.0f}s — killed"
        )
    if r.returncode == 0:
        for line in reversed(r.stdout.strip().splitlines() or [""]):
            try:
                info = json.loads(line)
                if isinstance(info, dict) and "platform" in info:
                    return "ok", info
            except json.JSONDecodeError:
                continue
        return "failed", "probe exited 0 without a platform verdict"
    msg = (r.stderr or r.stdout or "").strip()[-500:]
    return "failed", msg or f"probe exited {r.returncode}"


def _parent_main(want: list, args) -> None:
    """The isolation driver: probe in a subprocess, then one subprocess
    PER STAGE, each under the parent's own watchdog — a hung backend
    costs exactly the hung stage (its child is killed, its
    error recorded) and every other stage still runs and lands durable
    records. When the probe answers with no accelerator, the default
    plan degrades to the CPU-runnable stages (link + proxy) so a
    TPU-less machine still exits 0 with a full durable record set."""
    import subprocess
    import sys

    # drop any stale partial from a previous killed run — after stage
    # validation (usage errors must not destroy a recovery record) but
    # before any child runs
    _clear_partial()
    if not want:
        # `--stages none` is the instant emit-contract probe: no backend
        # touch at all (on a hung backend even the probe blocks for its
        # full watchdog before the error line)
        _emit({})
        _clear_partial()
        return

    child_env = None
    verdict, info = _probe_subprocess()
    probe_error = None
    if verdict != "ok":
        # whatever JAX_PLATFORMS selects is unusable — retry the probe
        # with the CPU backend pinned (ROADMAP S0 removes this: a
        # measurement path that finds no chip must fail)
        probe_error = info
        env_cpu = dict(os.environ, JAX_PLATFORMS="cpu")
        verdict2, info2 = _probe_subprocess(env=env_cpu)
        if verdict2 != "ok":
            # nothing executes anywhere: emit the honest error document
            # (same shape _require_devices prints) and exit 2
            try:
                from drep_tpu import __version__ as version
            except Exception:
                version = None
            err = f"backend probe failed ({info}); cpu fallback failed ({info2})"
            print(
                json.dumps(
                    {
                        "metric": "genome-pairs/sec/chip",
                        "value": None,
                        "unit": "pairs/s",
                        "vs_baseline": None,
                        "drep_tpu_version": version,
                        "error": err,
                        "stages": {"backend_probe": {"error": err}},
                    }
                ),
                flush=True,
            )
            sys.exit(2)
        child_env = env_cpu
        info = info2
    platform = info.get("platform")

    stages: dict = {}
    if probe_error is not None:
        # the wedged/failed probe is contained evidence, not a bail: it
        # rides the record while the CPU-runnable plan still measures
        stages["backend_probe"] = {
            "error": probe_error,
            "fallback": f"JAX_PLATFORMS=cpu ({platform})",
        }
    if platform != "tpu" and args.stages == "all":
        # the default plan is hardware measurement; without an
        # accelerator the honest substitute is the CPU proxy suite —
        # clearly marked, and refused as a speedup claim by the tooling
        print(
            f"bench: no accelerator reachable (backend {platform!r}) — "
            f"running CPU-runnable stages only (proxy)",
            file=sys.stderr, flush=True,
        )
        want = ["proxy"]

    plan = (["link"] if "link" not in want else []) + want
    wedged: list = []
    for label in plan:
        keys = _label_record_keys(label, args)
        err_key = {"secondary": "secondary"}.get(label, keys[0])
        budget = _stage_budget(label, args)  # same table as the child
        cmd = [
            sys.executable, os.path.abspath(__file__), "--child",
            "--stages", label,
            "--e2e_n", str(args.e2e_n), "--prod_n", str(args.prod_n),
            "--scale_n", str(args.scale_n),
        ]
        t0 = time.perf_counter()
        try:
            # child stdout (its own emitted line) is captured — the
            # parent prints the ONE driver line; stderr passes through
            # for live progress. Timeout = stage budget + startup slack:
            # the child's own watchdog bails first on a mid-stage wedge,
            # this outer kill covers a child wedged OUTSIDE a stage
            # (import, jax init, the bail path itself).
            r = subprocess.run(
                cmd, stdout=subprocess.PIPE, env=child_env,
                timeout=budget + 240,
            )
            rc = r.returncode
            child_stdout = r.stdout
        except subprocess.TimeoutExpired:
            rc = None  # parent-killed: wedged outside the child's watchdog
            child_stdout = b""
        recs = _collect_records(set(keys) | {err_key})
        if recs:
            stages.update(recs)
        # fallback: the child's own emitted JSON line. The durable store
        # is best-effort by contract (a read-only/full cwd must never
        # break a run) — a successful measurement whose _persist_stages
        # silently failed still rides the child's stdout, and dropping it
        # here would turn a complete stage into a phantom error record.
        missing_keys = [k for k in keys if k not in stages]
        if missing_keys and child_stdout:
            for line in reversed(child_stdout.decode(errors="replace").strip().splitlines()):
                try:
                    doc = json.loads(line)
                except json.JSONDecodeError:
                    continue
                if isinstance(doc, dict) and isinstance(doc.get("stages"), dict):
                    for k in missing_keys:
                        if k in doc["stages"]:
                            stages[k] = doc["stages"][k]
                    break
        if rc not in (0, 1) or not recs:
            note = (
                f"stage subprocess wedged (killed after {budget + 240:.0f}s)"
                if rc is None
                else f"stage subprocess exited {rc}"
            )
            for key in keys:
                if key not in stages:
                    stages[key] = {"error": note}
            if rc in (None, 3):
                wedged.append(label)
                print(
                    f"bench: {label} WEDGED — contained to its subprocess, "
                    f"continuing with the remaining stages",
                    file=sys.stderr, flush=True,
                )
        print(
            f"bench: {label} child finished in {time.perf_counter() - t0:.1f}s",
            file=sys.stderr, flush=True,
        )
        # legacy whole-run partial (driver recovery record), parent-owned;
        # best-effort like _emit/_auto_merge: nothing that can go wrong
        # here (full disk, injected io fault, broken install) may kill
        # the bench loop — the per-stage durable records are the real
        # recovery story
        try:
            from drep_tpu.utils.durableio import atomic_write_bytes

            atomic_write_bytes(
                "BENCH_PARTIAL.json",
                json.dumps(
                    {"completed_through": label, "stages": dict(stages)}
                ).encode(),
            )
        except Exception:
            pass

    _emit(stages)
    _auto_merge()
    _clear_partial()  # the emitted line carries everything
    if wedged:
        sys.exit(3)  # visibly partial: some stage hung mid-run
    if "primary" in want and "pairs_per_sec_per_chip" not in stages.get("primary", {}):
        sys.exit(1)


if __name__ == "__main__":
    main()
