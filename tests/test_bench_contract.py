"""The driver contract for bench.py: one JSON line on stdout, exit 0.

Pinned as a subprocess test with ONLY `JAX_PLATFORMS=cpu` in the env.
"""

import json
import os
import subprocess
import sys
from pathlib import Path

REPO = Path(__file__).resolve().parents[1]


def test_bench_emits_one_json_line_and_cleans_partials(tmp_path):
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=str(REPO))
    # tmp cwd: partial-record paths are cwd-relative, and the test must not
    # touch a real BENCH_PARTIAL.json recovery record in the checkout
    r = subprocess.run(
        [sys.executable, str(REPO / "bench.py"), "--stages", "none"],
        capture_output=True, text=True, env=env, cwd=str(tmp_path), timeout=300,
    )
    assert r.returncode == 0, r.stderr[-2000:]
    lines = [ln for ln in r.stdout.strip().splitlines() if ln.strip()]
    assert len(lines) == 1, r.stdout
    doc = json.loads(lines[0])
    assert doc["metric"] == "genome-pairs/sec/chip"
    assert set(doc) >= {"value", "unit", "vs_baseline", "stages"}
    assert not (tmp_path / "BENCH_PARTIAL.json").exists()


def test_bench_rejects_unknown_stage(tmp_path):
    """--stages is an ORDERED list (the wedge-retry loop feeds reversed
    orders so a repeatedly-wedging stage can't starve the ones behind it);
    a typo must fail loudly, not silently run nothing."""
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=str(REPO))
    r = subprocess.run(
        [sys.executable, str(REPO / "bench.py"), "--stages", "primary,typo"],
        capture_output=True, text=True, env=env, cwd=str(tmp_path), timeout=300,
    )
    assert r.returncode == 2
    assert "unknown stages" in r.stderr


def _load_bench_module():
    import importlib.util

    spec = importlib.util.spec_from_file_location("bench", str(REPO / "bench.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_bench_persists_durable_stage_records_and_automerges(tmp_path):
    """Bench self-resilience, first slice (ROADMAP item 1): every stage
    record lands in its own durable (atomic + checksummed) file the
    moment the stage completes, and the partial-merge runs automatically
    at exit — BENCH_merged.json never has to be hand-made again."""
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=str(REPO))
    r = subprocess.run(
        [sys.executable, str(REPO / "bench.py"), "--stages", "link"],
        capture_output=True, text=True, env=env, cwd=str(tmp_path), timeout=300,
    )
    assert r.returncode == 0, r.stderr[-2000:]
    rec = tmp_path / ".bench_stages" / "link.json"
    assert rec.exists(), "stage completed but left no durable record"
    # the record is a CHECKED payload: read through the durable layer so
    # a bit-rotted record classifies instead of being silently trusted
    sys.path.insert(0, str(REPO))
    from drep_tpu.utils.durableio import read_json_checked

    doc = read_json_checked(str(rec), what="bench stage record")
    assert doc["stage"] == "link" and "dispatch_ms_median" in doc["record"]
    merged = json.loads((tmp_path / "BENCH_merged.json").read_text())
    assert "link" in merged["stages"]


def test_killed_bench_leaves_readable_records_per_completed_stage(tmp_path, monkeypatch):
    """Killing bench after stage 1 of 3 leaves a readable durable record
    for stage 1 (the acceptance contract): persistence happens per-stage,
    so a later kill — simulated here by simply never reaching stages 2-3
    — costs the unmeasured cells only, and the next run's auto-merge
    recovers stage 1 from disk."""
    monkeypatch.chdir(tmp_path)
    bench = _load_bench_module()
    bench._persist_stages({"primary": {"pairs_per_sec_per_chip": 123.0, "vs_baseline": 1.0}})
    # <- SIGKILL would land here; stages 2-3 never persist
    sys.path.insert(0, str(REPO))
    from drep_tpu.utils.durableio import read_json_checked

    doc = read_json_checked(
        str(tmp_path / ".bench_stages" / "primary.json"), what="bench stage record"
    )
    assert doc["record"]["pairs_per_sec_per_chip"] == 123.0
    # a later (recovery) process merges what survived
    bench2 = _load_bench_module()
    bench2._auto_merge()
    merged = json.loads((tmp_path / "BENCH_merged.json").read_text())
    assert merged["value"] == 123.0
    assert merged["stages"]["primary"]["pairs_per_sec_per_chip"] == 123.0


def test_bench_tpuless_default_runs_proxy_and_exits_zero(tmp_path):
    """ISSUE 7 acceptance: `python bench.py` on a TPU-less machine exits
    0 with durable per-stage records for the CPU-runnable stages — the
    default hardware plan degrades to the proxy suite (clearly marked,
    value stays null) instead of wedging or erroring, and the merged
    round file lands."""
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=str(REPO))
    r = subprocess.run(
        [sys.executable, str(REPO / "bench.py")],
        capture_output=True, text=True, env=env, cwd=str(tmp_path), timeout=900,
    )
    assert r.returncode == 0, r.stderr[-2000:]
    lines = [ln for ln in r.stdout.strip().splitlines() if ln.strip()]
    assert len(lines) == 1, r.stdout  # the one-line driver contract holds
    doc = json.loads(lines[0])
    assert doc["value"] is None  # proxies are NOT a throughput claim
    rec = doc["stages"]["proxy_metrics"]
    proxies = rec["proxy_metrics"]
    assert proxies["pruned_edges_equal_dense"] is True
    assert proxies["skip_fraction"] > 0
    assert 0 < proxies["tile_fraction"] < 0.6
    assert "checksum_overhead_frac" in proxies
    assert "pairs_per_sec_per_chip" not in str(rec)
    # durable records + auto-merged round file
    assert (tmp_path / ".bench_stages" / "proxy_metrics.json").exists()
    merged = json.loads((tmp_path / "BENCH_merged.json").read_text())
    assert "proxy_metrics" in merged["stages"]
    # ... and the merge tooling refuses proxies as measured hardware perf
    import importlib.util

    spec = importlib.util.spec_from_file_location(
        "missing_stages", str(REPO / "tools" / "missing_stages.py")
    )
    ms = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(ms)
    assert set(ms.missing(merged)) == set(ms.PLAN_TO_RECORD)
    # a proxy-carrying record can never satisfy a hardware stage either
    fake = {
        "stages": {"primary": {"proxy_metrics": proxies}},
        "stage_provenance": {"primary": {"attempt": 1, "link": {
            "dispatch_ms_median": 1.0, "h2d_gbps": 1.0, "d2h_gbps": 1.0}}},
    }
    assert "primary" in ms.missing(fake)


def test_bench_probe_failure_contained_to_subprocess(tmp_path):
    """A backend that cannot even initialize costs only the probe child: the parent falls back to a
    CPU-pinned probe, records the failure as backend_probe evidence, and
    the CPU-runnable plan still completes with rc 0."""
    env = dict(os.environ, JAX_PLATFORMS="no_such_platform", PYTHONPATH=str(REPO))
    r = subprocess.run(
        [sys.executable, str(REPO / "bench.py"), "--stages", "proxy"],
        capture_output=True, text=True, env=env, cwd=str(tmp_path), timeout=900,
    )
    assert r.returncode == 0, r.stderr[-2000:]
    doc = json.loads(r.stdout.strip().splitlines()[-1])
    assert "error" in doc["stages"]["backend_probe"]
    assert doc["stages"]["proxy_metrics"]["proxy_metrics"]["skip_fraction"] > 0


def test_stage_record_preference_and_version_gate(tmp_path, monkeypatch):
    """Within a version the shared prefer_new rule keeps the better
    record (best-of, error never shadows success); records from an older
    code version are replaced unconditionally and never merged forward."""
    monkeypatch.chdir(tmp_path)
    bench = _load_bench_module()
    bench._persist_stages({"primary": {"pairs_per_sec_per_chip": 2.0}})
    bench._persist_stages({"primary": {"pairs_per_sec_per_chip": 1.0}})  # slower: kept out
    bench._persist_stages({"primary": {"error": "wedged"}})  # never shadows success
    from drep_tpu.utils.durableio import read_json_checked

    loc = str(tmp_path / ".bench_stages" / "primary.json")
    assert read_json_checked(loc, what="r")["record"]["pairs_per_sec_per_chip"] == 2.0
    # stale-version record: replaced by the current version's (slower) one
    import json as _json

    stale = _json.loads(open(loc).read())
    stale["version"] = "0.0.0-stale"
    from drep_tpu.utils.durableio import atomic_write_json

    doc = {k: v for k, v in stale.items() if k != "crc"}
    atomic_write_json(loc, doc)
    bench._persist_stages({"primary": {"pairs_per_sec_per_chip": 1.0}})
    assert read_json_checked(loc, what="r")["record"]["pairs_per_sec_per_chip"] == 1.0
    bench._auto_merge()
    merged = json.loads((tmp_path / "BENCH_merged.json").read_text())
    assert merged["stages"]["primary"]["pairs_per_sec_per_chip"] == 1.0
