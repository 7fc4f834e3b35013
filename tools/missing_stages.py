"""Which bench stages still need a (healthy-link) hardware number?

Prints a comma list of bench.py stage-plan names to run FIRST: a failure
mid-full-run must not cost the one number the round is still missing.

A stage is missing when the merged artifact (tools/merge_bench_partials.py
over the per-attempt partials) has no successful record for it, or when
the record's provenance carries no link-health stamp.
"""

from __future__ import annotations

import sys

# bench.py stage-plan name -> the stage-record key its success writes
PLAN_TO_RECORD = {
    "primary": "primary",
    "secondary": "secondary_matmul",
    "ring": "ring_scaling",
    "e2e": "e2e_10k",
    "prod": "e2e_prod",
    "scale": "e2e_50k",
    "ingest": "ingest",
    "greedy": "greedy_secondary",
    "production": "secondary_production",
    "crossover": "dispatch_crossover",
}


def _link_ok(link) -> bool:
    """A usable link-health stamp has real bandwidth numbers. A watchdog
    overrun stores {'error': ...} under stages['link'] and merge copies
    that into provenance — non-None but measurement-free; treating it as
    healthy would launder an unknown-link attempt's numbers (ADVICE r4)."""
    return (
        isinstance(link, dict)
        and "error" not in link
        and "h2d_gbps" in link
        and "d2h_gbps" in link
    )


def _has_error(rec) -> bool:
    """Any `{"error": ...}` ANYWHERE in the record — bench stages record
    sub-failures nested inside otherwise-successful dicts (e.g. a failed
    `rows_per_iter_N` variant inside a completed primary record, or a
    stage error merged into early-published partials), and a record
    carrying one wants a healthy re-measure, not trust."""
    if not isinstance(rec, dict):
        return False
    return "error" in rec or any(_has_error(v) for v in rec.values())


def _degraded(rec: dict) -> bool:
    """A record from a run that lost pod member(s) and completed via the
    elastic ownership-epoch protocol — streaming stripes OR dense-ring
    blocks (ISSUE 4) — or whose MEMBERSHIP CHURNED at all (ISSUE 9: a
    planned drain ran part of the stage on fewer chips, a mid-run join
    ran part of it on MORE chips — either way the wall-clock describes a
    chip count the record does not carry), or whose ring abandoned its
    collective schedule into per-block recovery, or that HEALED corrupt
    shards (ISSUE 5 — healing implies recompute the record does not
    time-attribute, exactly like degradation): results are correct, but
    the wall-clock was not produced on the claimed steady chip count —
    not measured perf (same contract as fault-stamped records). bench
    stamps the top-level keys into EVERY stage record; the
    fault_tolerance sub-dict catches any record that carried the raw
    counters without the stamp. Transient io_retries alone do NOT refuse
    a record — a retried write costs milliseconds, not recompute — but
    io_unrecoverable does: an op that failed past the budget forced a
    recompute (shard reads) or left the run limping, either way not the
    clean wall-clock the record claims."""
    ft = rec.get("fault_tolerance", {})
    return bool(
        rec.get("dead_processes")
        or rec.get("pod_epochs", 1) > 1
        or rec.get("pod_joins")
        or rec.get("planned_departures")
        # ISSUE 15: churn DECIDED by the autoscaling controller (the
        # join/drain notes carry its stamp) — the run's chip count was
        # policy-elastic, same refusal as hand-driven membership churn
        or rec.get("autoscale_decisions")
        or rec.get("corrupt_shards_healed")
        or rec.get("io_unrecoverable")
        or ft.get("dead_processes")
        or ft.get("pod_epoch_bumps")
        or ft.get("pod_joins")
        or ft.get("planned_departures")
        or ft.get("drain_announced")
        or ft.get("autoscale_churn")
        or ft.get("ring_step_failures")
        or ft.get("corrupt_shards_healed")
        or ft.get("io_unrecoverable")
    )


def _interpret_pallas(rec) -> bool:
    """Any row/field ANYWHERE in the record that ran the fused pallas
    ring in INTERPRET mode (`ring_comm: "pallas_interpret"` — the CPU
    equality oracle, ISSUE 8): the kernel's remote DMAs were discharged
    as host collectives, so its wall-clock says nothing about ICI overlap
    on hardware — never a speedup claim, exactly like proxy metrics."""
    if isinstance(rec, dict):
        if rec.get("ring_comm") == "pallas_interpret":
            return True
        return any(_interpret_pallas(v) for v in rec.values())
    if isinstance(rec, list):
        return any(_interpret_pallas(v) for v in rec)
    return False


def missing(merged: dict) -> list[str]:
    stages = merged.get("stages", {})
    prov = merged.get("stage_provenance", {})
    out = []
    for plan, key in PLAN_TO_RECORD.items():
        rec = stages.get(key)
        ok = (
            isinstance(rec, dict)
            and not _has_error(rec)
            # bench stamps DREP_TPU_FAULTS provenance into every stage it
            # emits: a chaos-mode run exercised the fault layer, it did
            # NOT measure clean hardware throughput — never count it done
            and not rec.get("faults_injected")
            # a degraded-pod run (dead member survived via an epoch bump)
            # finished on fewer chips than it claims — refuse as measured
            and not _degraded(rec)
            # a wedge between the fresh e2e leg and its resume leg
            # publishes the fresh number with this marker — keep the
            # stage on the re-measure list until the resume evidence lands
            and not rec.get("resume_pending")
            # early-published stages (production/crossover) carry this
            # until their first real measurement lands; a wedge before
            # then leaves a number-free record that must not count as
            # done (ADVICE r4 medium)
            and not rec.get("measurement_pending")
            # CPU-proxy records (bench_proxy, emitted when no accelerator
            # is reachable) characterize the scheduling/storage layers —
            # they are NOT hardware throughput and must never satisfy a
            # hardware stage or read as a speedup claim
            and not rec.get("proxy_metrics")
            # interpret-mode pallas rows (the fused ring's CPU equality
            # oracle) are correctness evidence, not hardware measurement
            and not _interpret_pallas(rec)
            # a hardware stage that RAN on a non-TPU backend (forced
            # JAX_PLATFORMS=cpu, a machine with no chip) carries a
            # `backend` stamp — its rate is not a chip measurement
            and rec.get("backend", "tpu") == "tpu"
        )
        if not ok or not _link_ok(prov.get(key, {}).get("link")):
            out.append(plan)
    # preserve bench.py's value ordering (its default_order) so the most
    # valuable missing number is measured first in the recovery window
    order = ["primary", "secondary", "ring", "e2e", "prod", "scale",
             "ingest", "greedy", "production", "crossover"]
    return sorted(out, key=order.index)


def main() -> None:
    import json

    path = sys.argv[1] if len(sys.argv) > 1 else "BENCH_r05_merged.json"
    try:
        with open(path) as f:
            merged = json.load(f)
    except Exception:
        print(",".join(PLAN_TO_RECORD))  # no merged record yet: everything
        return
    print(",".join(missing(merged)))


if __name__ == "__main__":
    main()
