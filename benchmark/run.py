#!/usr/bin/env python3
"""One run of one benchmark cell.

    python3 benchmark/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

A run is one new process on a machine that holds the cell's chips: it makes
the cell's data from --seed, warms up, measures for --seconds, compares its
outputs with the plain reference (benchmark/reference.py) and prints one JSON
object as the last line of its standard output. Without a TPU that
benchmark/peaks.json knows, or with fewer chips than the cell asks for, it
exits non-zero and prints no result line: there is no CPU fallback.

``--rehearse`` runs the cell at the toy sizes of its configuration's
`rehearse` block on whatever backend JAX finds, to debug the harness in a
sandbox. Its result line says so (``"rehearsal": true`` and the platform it ran
on) and can never be read as a chip result.
"""

from __future__ import annotations

import time

_T0 = time.monotonic()  # set-up is counted from here

import argparse  # noqa: E402
import importlib  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402

BENCH_DIR = os.path.dirname(os.path.abspath(__file__))
REPO = os.path.dirname(BENCH_DIR)
sys.path.insert(0, REPO)

from benchmark import cells  # noqa: E402

COMPILE_EVENTS = ("backend_compile", "cache_retrieval")  # a program built or loaded


def parse(argv):
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rehearse", action="store_true",
                    help="toy sizes on any backend; never a chip result")
    return ap.parse_args(argv)


def device_gate(chips: int, rehearse: bool) -> tuple[dict, dict | None]:
    """What JAX runs on, or an exit: a cell is measured on the chips it asks
    for and on a device whose peaks are known."""
    import jax

    devices = jax.devices()
    device = {"platform": devices[0].platform, "kind": devices[0].device_kind,
              "count": len(devices)}
    if rehearse:
        return device, None
    if device["platform"] != "tpu":
        raise SystemExit(f"JAX found no accelerator (platform {device['platform']!r}): "
                         f"nothing was measured")
    if device["count"] < chips:
        raise SystemExit(f"the cell asks for {chips} chip(s), JAX found {device['count']}")
    return device, cells.load_peaks(device["kind"])


def memory_peak_bytes() -> int:
    import jax

    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use", 0) for d in jax.local_devices()]
    return int(max(peaks)) if peaks else 0


def main(argv=None) -> int:
    args = parse(argv)
    if not os.path.isdir(os.path.join(REPO, "drep_tpu")):
        print("benchmark: no drep_tpu package beside benchmark/ — the benchmark drives the "
              "program, it is not the program", file=sys.stderr)
        return 1
    # a leftover knob must not steer the run under test
    for k in [k for k in os.environ if k.startswith("DREP_TPU_")]:
        del os.environ[k]
    loaded = cells.load_cell(args.workload)
    spec, cell, cfg = loaded["spec"], loaded["cell"], loaded["config"]
    if args.rehearse:
        cfg = {**cfg, "data": {**cfg["data"], **cfg.get("rehearse", {})}}
    device, peaks = device_gate(int(cell["chips"]), args.rehearse)
    print(f"device: {json.dumps(device)}", flush=True)

    import jax

    compiles: list = []
    jax.monitoring.register_event_duration_secs_listener(
        lambda name, *_a, **_k: compiles.append(name)
        if any(c in name for c in COMPILE_EVENTS) else None)

    def start_trace(trace_dir: str) -> None:
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0  # Python frames would swamp a whole job's trace
        opts.host_tracer_level = 2
        jax.profiler.start_trace(trace_dir, profiler_options=opts)

    work_dir = os.path.join(BENCH_DIR, ".work", f"{args.workload}-{args.seed}")
    shutil.rmtree(work_dir, ignore_errors=True)
    os.makedirs(work_dir)
    ctx = {
        "config": cfg, "traffic": loaded["traffic"], "generator": loaded["generator"],
        "cell": cell, "seed": args.seed, "seconds": args.seconds, "trace": bool(args.trace),
        "work_dir": work_dir,
        "device": device, "peaks": peaks, "compiles": compiles,
        "setup_clock": lambda: time.monotonic() - _T0,
        "start_trace": start_trace, "stop_trace": jax.profiler.stop_trace,
        "rehearse": args.rehearse,
    }
    # a traffic kind is a module of its own, found by the kind's name
    runner = importlib.import_module("benchmark." + loaded["traffic"]["kind"])
    try:
        result = runner.run(ctx)
        device_out = {**device, "memory_peak_bytes": memory_peak_bytes()}
        if args.trace:
            run = result["run"]
            if not run["trace"] or run["trace"]["busy_s"] <= 0:
                raise SystemExit("the trace shows no operation on the device")
            device_out["busy_s"] = run["trace"]["busy_s"]
            device_out["window_s"] = run["trace"]["window_s"]
            metrics = cells.read_layer_metrics(spec, args.workload, run)
        else:
            metrics = {}
            for m in cells.metrics_of(spec, args.workload, "end_to_end"):
                metrics[m["name"]] = {"value": float(result["end_to_end"][m["name"]]),
                                      "unit": m["unit"]}
    finally:
        shutil.rmtree(work_dir, ignore_errors=True)
    line = {"correct": bool(result["correct"]), "attempted": int(result["attempted"]),
            "failed": int(result["failed"]), "metrics": metrics, "device": device_out}
    if args.trace:
        line["breakdown"] = result["run"]["trace"]["breakdown"]
    if args.rehearse:
        line["rehearsal"] = True
    sys.stderr.flush()
    print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
