"""Perf counters + profiler hook (SURVEY.md §5.1 rebuild requirement)."""

import json
import os

import pytest

from drep_tpu.utils.profiling import Counters, trace


def test_counters_stage_accumulates():
    c = Counters()
    with c.stage("primary_compare", pairs=10):
        pass
    with c.stage("primary_compare", pairs=5):
        pass
    rep = c.report()
    st = rep["stages"]["primary_compare"]
    assert st["pairs"] == 15
    assert st["calls"] == 2
    assert st["seconds"] >= 0
    assert rep["total"]["pairs"] == 15
    assert rep["n_chips"] >= 1


def test_counters_write(tmp_path):
    c = Counters()
    c.add("secondary_compare", pairs=100, seconds=0.5)
    path = c.write(str(tmp_path))
    with open(path) as f:
        rep = json.load(f)
    assert rep["stages"]["secondary_compare"]["pairs_per_sec"] == 200.0


def test_trace_noop_and_real(tmp_path):
    with trace(None):  # no-op path
        pass
    tdir = str(tmp_path / "trace")
    with trace(tdir):
        import jax.numpy as jnp

        (jnp.ones(8) * 2).block_until_ready()
    # jax wrote a plugins/profile tree
    assert os.path.isdir(tdir)
    assert any(os.scandir(tdir))


def test_pipeline_writes_counters(tmp_path, genome_paths):
    from drep_tpu.workflows import compare_wrapper

    compare_wrapper(str(tmp_path / "wd"), genome_paths, skip_plots=True)
    path = tmp_path / "wd" / "log" / "perf_counters.json"
    assert path.exists()
    with open(path) as f:
        rep = json.load(f)
    assert rep["stages"]["primary_compare"]["pairs"] == 10  # C(5,2)
    assert "secondary_compare" in rep["stages"]
    # events are OFF by default: the traced pipeline must leave no event
    # files and no metrics.prom (the zero-overhead-when-off contract)
    leftover = [
        f for f in (tmp_path / "wd" / "log").iterdir()
        if f.name.startswith("events.") or f.name == "metrics.prom"
    ]
    assert not leftover, leftover


def test_epoch_history_ordering_and_pod_epoch_gauge():
    """epoch_history records bumps in ORDER with their reasons (a
    drain-then-join churn and a join-then-drain churn must read as
    different stories), and pod_epoch mirrors the latest epoch."""
    c = Counters()
    c.note_epoch(1, "death")
    c.note_epoch(2, "drain")
    c.note_epoch(3, "join")
    rep = c.report()
    hist = rep["epoch_history"]
    assert [(h["epoch"], h["reason"]) for h in hist] == [
        (1, "death"), (2, "drain"), (3, "join"),
    ]
    ats = [h["at"] for h in hist]
    assert ats == sorted(ats)
    assert rep["gauges"]["pod_epoch"] == 3.0
    c.reset()
    assert c.report().get("epoch_history") is None


def test_report_names_the_device_and_a_dead_backend_is_an_error(monkeypatch):
    """The record names what the process ran on; a backend that cannot
    initialize raises instead of defaulting to one chip. Control-plane
    processes (route, supervise) never touch JAX: their record names no
    device and asks for none."""
    import jax

    c = Counters()
    c.add("primary_compare", pairs=100, seconds=0.5)
    rep = c.report()
    dev = jax.devices()
    assert (rep["platform"], rep["device_kind"], rep["n_devices"]) == (
        dev[0].platform, dev[0].device_kind, len(dev)
    )
    assert rep["n_chips"] == len(dev)

    def boom():
        raise RuntimeError("no backend")

    monkeypatch.setattr(jax, "devices", boom)
    with pytest.raises(RuntimeError, match="no backend"):
        c.report()
    rep = c.report(device=False)  # must not even ask
    assert rep["platform"] is None and rep["n_devices"] == 0
    assert rep["stages"]["primary_compare"]["pairs_per_sec_per_chip"] == 200.0


def test_drain_adoption_sets_latency_gauge_and_history(tmp_path):
    """The drain_adopt_latency_s gauge + the drain epoch-history entry,
    exercised DIRECTLY through the heartbeat protocol (previously only
    covered via the slow elastic suites): member 1 announces a planned
    departure, member 0's next check adopts it with no staleness wait."""
    from drep_tpu.parallel import faulttol
    from drep_tpu.utils.profiling import counters

    counters.reset()
    faulttol.reset_pod()
    hb0 = faulttol.HeartbeatManager(str(tmp_path), cadence=0.0, pc=2, pid=0)
    hb1 = faulttol.HeartbeatManager(str(tmp_path), cadence=0.0, pc=2, pid=1)
    try:
        hb0.start()
        hb1.start()
        hb1.announce_drain(pairs=7)
        assert counters.faults.get("drain_announced") == 1
        assert hb0.check() is True  # the drain scan runs BEFORE staleness
        assert hb0.live == [0] and hb0.drained == [1]
        assert hb0.dead == []  # never charged against the death budget
        lat = counters.gauges.get("drain_adopt_latency_s")
        assert lat is not None and 0.0 <= lat < 5.0, lat
        assert counters.gauges["pod_epoch"] == 1.0
        assert [(h["epoch"], h["reason"]) for h in counters.epoch_history] == [
            (1, "drain")
        ]
        # the departing member's honest pairs ride its note
        assert hb0.drain_payload(1)["pairs"] == 7
    finally:
        hb0.close()
        hb1.close()
        counters.reset()
        faulttol.reset_pod()


def test_prom_textfile_flush(tmp_path, monkeypatch):
    """The periodic Prometheus flush (DREP_TPU_METRICS_FLUSH_S): off by
    default (no thread, no file); when on, metrics.prom is published
    atomically and carries stage/fault/gauge lines a textfile collector
    can scrape before the run exits."""
    from drep_tpu.utils import profiling

    monkeypatch.delenv(profiling.METRICS_FLUSH_ENV, raising=False)
    assert profiling.start_metrics_flush(str(tmp_path)) is False
    assert not (tmp_path / "metrics.prom").exists()

    c = Counters()
    c.add("primary_compare", pairs=10, seconds=0.5)
    c.add_fault("retries", 2)
    c.set_gauge("skip_fraction", 0.5)
    c.note_epoch(1, "drain")
    text = profiling.prom_text(c)
    assert 'drep_tpu_stage_pairs_total{stage="primary_compare"} 10' in text
    assert 'drep_tpu_fault_events_total{kind="retries"} 2' in text
    assert 'drep_tpu_gauge{name="skip_fraction"} 0.5' in text
    assert "drep_tpu_epoch_bumps_total 1" in text

    monkeypatch.setenv(profiling.METRICS_FLUSH_ENV, "0.05")
    try:
        assert profiling.start_metrics_flush(str(tmp_path)) is True
        deadline = __import__("time").time() + 30
        while __import__("time").time() < deadline:
            if (tmp_path / "metrics.prom").exists():
                break
            __import__("time").sleep(0.02)
        assert (tmp_path / "metrics.prom").exists(), "flusher never published"
    finally:
        profiling.stop_metrics_flush(final=True)
    body = (tmp_path / "metrics.prom").read_text()
    assert "drep_tpu_metrics_flush_timestamp_seconds" in body
