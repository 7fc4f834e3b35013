#!/usr/bin/env python
"""chip_smoke.py — does drep-tpu still start, and compute the right thing, on the chip?

Drives the system's main paths once, through the entry points a user calls
(``python -m drep_tpu compare | dereplicate | index build | index serve |
index classify``), at real widths on data generated from ``--seed``, and
checks every result against what was planted:

  A  FASTA -> Wdb   ``dereplicate`` on 48 planted 3.5 Mb genomes (native
                    ingest, dense primary, production-width secondary,
                    choose, evaluate, analyze): 6 primary / 12 secondary
                    clusters, 12 winners.
  B  compare, dense 5,000 genomes' planted sketches (bottom-k 1000, scaled
                    ~20k wide -> packed 32768): Cdb == the planted partition.
                    One chip: the symmetric Pallas grid; several: the ring.
  C  compare, streaming  the same sketches through ``--streaming_primary
                    --SkipSecondary``: primary clusters == leg B's.
  D  serve          ``index build`` from leg A's FASTAs (the index refuses a
                    workdir scored with --genomeInfo, which leg A is),
                    ``index serve``, four queries through the JAX-free
                    client (two of them pipelined so they coalesce),
                    SIGTERM -> exit 0; then one-shot ``index classify`` of
                    the same four: verdicts equal, and equal to the planted
                    truth.

One process per chip: THIS process never imports jax. Every leg is one child
process, run one at a time; the only overlap is the serve daemon holding the
chip while the client talks to it. Each child is judged from its OWN record
(perf_counters.json, the daemon's ready line): platform == "tpu", the probed
device kind and count, every "did not run where it was meant to" counter
zero, the native ingest library served.

Exit code 0 and a last stdout line ``{"ok": true, "device": {...}}`` mean all
of that held on an accelerator. Without one (``JAX_PLATFORMS=cpu``, no chip)
it fails within seconds, before any data is generated. ``--rehearse`` runs
the same legs at toy sizes on whatever backend JAX finds, to debug this
script in a sandbox; its last line says ``"ok": false, "rehearsal": true``
and can never be read as a chip pass. Wall-clock per leg is printed as
set-up information only — it is not a metric.
"""

from __future__ import annotations

import argparse
import importlib.util
import json
import os
import shutil
import signal
import subprocess
import sys
import threading
import time

REPO = os.path.dirname(os.path.abspath(__file__))

# what the contract gives the whole run (compilation included), minus a margin
TIME_LIMIT_S = 1200.0
MARGIN_S = 45.0

# fault_tolerance counters that mean "a dispatch did not run where it was
# meant to" — every one must be absent or zero in every child's record
HIDING_COUNTERS = (
    "retries", "watchdog_trips", "quarantined_devices", "cpu_fallback_tiles",
    "ring_step_failures", "ring_blocks_recovered", "serve_batch_poisoned",
)
# secondary kernel paths that are the CPU, or a fall-back
HIDING_PATHS = ("cpu_tiles",)

SIZES = {
    # widths are never cut: bottom-k 1000 (packed 1024), 3.5 Mb genomes at
    # --scale 200 -> ~17.5k-wide scaled sketches (packed 32768). N is.
    "full": dict(roots=6, secondary=2, members=4, genome_len=3_500_000,
                 n_planted=5_000, s_scaled=20_000),
    "rehearse": dict(roots=3, secondary=2, members=2, genome_len=300_000,
                     n_planted=300, s_scaled=1_200),
}
REDUCED = [
    "leg A: 48 genomes (6 roots x 2 x 4) — a collection, not a catalog",
    "legs B/C: N = 5,000 planted sketch sets (BASELINE configs run 10k-100k)",
    "leg D: a 48-genome index, four queries",
]


class SmokeFailure(Exception):
    """One check of the smoke did not hold."""


# ---- child processes --------------------------------------------------------

_CHILDREN: list[subprocess.Popen] = []


def _child_env() -> dict:
    """The caller's environment minus every DREP_TPU_* pin and XLA_FLAGS (a
    leftover knob must not steer the run under test); JAX_PLATFORMS stays as
    the machine set it."""
    env = {
        k: v for k, v in os.environ.items()
        if not k.startswith("DREP_TPU_") and k != "XLA_FLAGS"
    }
    env["PYTHONPATH"] = REPO + os.pathsep + env.get("PYTHONPATH", "")
    return env


def write_text(path: str, text: str) -> None:
    """Every file this script writes goes through the repo's durable-I/O
    funnel (atomic publish) — the contract drep-lint holds all code to."""
    from drep_tpu.utils.durableio import atomic_write_bytes

    atomic_write_bytes(path, text.encode())


def _kill(proc: subprocess.Popen) -> None:
    if proc.poll() is None:
        try:
            os.killpg(proc.pid, signal.SIGKILL)  # its own session: the whole group
        except (ProcessLookupError, PermissionError):
            proc.kill()
    try:
        proc.wait(timeout=30)
    except subprocess.TimeoutExpired:
        pass


def _kill_all() -> None:
    for proc in _CHILDREN:
        _kill(proc)


class Clock:
    def __init__(self) -> None:
        self.t0 = time.monotonic()

    def left(self) -> float:
        return TIME_LIMIT_S - MARGIN_S - (time.monotonic() - self.t0)


def run_child(argv: list[str], clock: Clock, log_path: str | None, what: str) -> tuple[str, str]:
    """Run one child to its end; returns (stdout, stderr). Non-zero exit or
    running out of the time limit is a failure; stderr is kept in `log_path`
    (when given)."""
    budget = clock.left()
    if budget <= 0:
        raise SmokeFailure(f"{what}: no time left inside the {TIME_LIMIT_S:.0f}s limit")
    proc = subprocess.Popen(
        argv, cwd=REPO, env=_child_env(), stdout=subprocess.PIPE,
        stderr=subprocess.PIPE, text=True, start_new_session=True,
    )
    _CHILDREN.append(proc)
    try:
        out, err = proc.communicate(timeout=budget)
    except subprocess.TimeoutExpired:
        _kill(proc)
        out, err = proc.communicate()
        if log_path:
            write_text(log_path, err or "")
        raise SmokeFailure(
            f"{what}: still running at the time limit — killed; stderr tail:\n"
            + (err or "")[-3000:]
        ) from None
    if log_path:
        write_text(log_path, err)
    if proc.returncode != 0:
        raise SmokeFailure(
            f"{what}: exit code {proc.returncode}; stderr tail:\n{err[-6000:]}"
        )
    return out, err


def drep(*args: str) -> list[str]:
    return [sys.executable, "-m", "drep_tpu", *args]


PROBE = r"""
import importlib.metadata as md, json, jax, jaxlib
d = jax.devices()
def v(name):
    try:
        return md.version(name)
    except md.PackageNotFoundError:
        return None
print(json.dumps({"platform": d[0].platform, "device_kind": d[0].device_kind,
                  "n_devices": len(d), "jax": jax.__version__,
                  "jaxlib": jaxlib.__version__, "libtpu": v("libtpu")}))
"""


# ---- checks on a child's own record -----------------------------------------


# every child record that was judged, slimmed, for the report
RECORDS: dict[str, dict] = {}
_RECORD_KEYS = ("platform", "device_kind", "n_devices", "stages", "fault_tolerance",
                "gauges", "notes", "secondary_paths")


def check_record(rec: dict, probe: dict, what: str, native: bool = False) -> None:
    RECORDS[what] = {k: rec[k] for k in _RECORD_KEYS if k in rec}
    for key in ("platform", "device_kind", "n_devices"):
        if rec.get(key) != probe[key]:
            raise SmokeFailure(
                f"{what}: record says {key}={rec.get(key)!r}, the probe saw "
                f"{probe[key]!r}"
            )
    bad = {k: v for k, v in (rec.get("fault_tolerance") or {}).items()
           if k in HIDING_COUNTERS and v}
    if bad:
        raise SmokeFailure(f"{what}: work did not run where it was meant to: {bad}")
    hidden = [p for p in (rec.get("secondary_paths") or {}) if p in HIDING_PATHS]
    if hidden:
        raise SmokeFailure(
            f"{what}: secondary compare served by {hidden} "
            f"(all paths: {rec.get('secondary_paths')})"
        )
    if native and (rec.get("notes") or {}).get("ingest_path") != "native":
        raise SmokeFailure(
            f"{what}: ingest ran on {(rec.get('notes') or {}).get('ingest_path')!r}, "
            f"not the native library"
        )


def read_json(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


def partition(labels_by_genome: dict) -> set:
    groups: dict = {}
    for g, lab in labels_by_genome.items():
        groups.setdefault(lab, set()).add(g)
    return {frozenset(v) for v in groups.values()}


def digest(part: set) -> str:
    """A partition's fingerprint, to compare one machine's run with another's."""
    import hashlib

    canon = sorted(",".join(sorted(group)) for group in part)
    return hashlib.sha1("\n".join(canon).encode()).hexdigest()[:16]


def read_cdb(wd: str) -> tuple[dict, dict]:
    import pandas as pd

    cdb = pd.read_csv(os.path.join(wd, "data_tables", "Cdb.csv"))
    return (
        dict(zip(cdb["genome"], cdb["primary_cluster"])),
        dict(zip(cdb["genome"], cdb["secondary_cluster"])),
    )


# ---- data, from the seed ----------------------------------------------------


def _generator():
    """tests/genomes/generate.py (numpy only) — the planted-divergence
    operators the ARI tests use."""
    spec = importlib.util.spec_from_file_location(
        "drep_smoke_generate", os.path.join(REPO, "tests", "genomes", "generate.py")
    )
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def plant_genomes(out_dir: str, seed: int, sz: dict):
    """The planted Mb-class set of tests/test_ari_production_depth.py: roots
    share nothing (primary clusters), each root's secondary ancestors sit
    ~3% apart (different secondary clusters at S_ani 0.95), members ~0.8%
    from their ancestor with size asymmetry (same secondary cluster).
    Returns (paths, truth {name: (root, secondary)}, sequences to mutate
    into queries)."""
    import numpy as np

    gen = _generator()
    rng = np.random.default_rng(seed)
    os.makedirs(out_dir, exist_ok=True)
    size_fracs = [0.0, 0.35, -0.2, 0.15]
    paths, truth, keep = [], {}, {}
    for p in range(sz["roots"]):
        root = gen.random_genome(rng, sz["genome_len"])
        for s in range(sz["secondary"]):
            ancestor = gen.evolve(
                rng, root, 0.03, indel_rate=1.5e-4, n_duplications=2, n_rearrangements=2,
            )
            for m in range(sz["members"]):
                seq = gen.evolve(
                    rng, ancestor, 0.008, indel_rate=1e-4, n_duplications=1,
                    n_rearrangements=1, size_frac=size_fracs[m % len(size_fracs)],
                )
                name = f"p{p}s{s}m{m}"
                path = os.path.join(out_dir, f"{name}.fasta")
                gen.write_fasta(path, seq, n_contigs=40, name=name)
                paths.append(path)
                truth[f"{name}.fasta"] = (p, s)
                if m == 0 and len(keep) < 3 and s == p % sz["secondary"]:
                    keep[f"{name}.fasta"] = seq
    return paths, truth, keep


def plant_queries(out_dir: str, seed: int, keep: dict, genome_len: int) -> dict:
    """{query path: source genome name | None}: 1%-mutated copies of indexed
    genomes, and one unrelated genome."""
    import numpy as np

    gen = _generator()
    rng = np.random.default_rng(seed)
    os.makedirs(out_dir, exist_ok=True)
    queries = {}
    for i, (src, seq) in enumerate(sorted(keep.items())):
        path = os.path.join(out_dir, f"query_{i}_of_{src}")
        gen.write_fasta(path, gen.mutate(rng, seq, 0.01), n_contigs=25, name=f"q{i}")
        queries[path] = src
    path = os.path.join(out_dir, "query_unrelated.fasta")
    gen.write_fasta(path, gen.random_genome(rng, genome_len), n_contigs=25, name="qx")
    queries[path] = None
    return queries


def plant_sketch_workdirs(wd_b: str, wd_c: str, seed: int, sz: dict) -> dict:
    """A workdir whose Bdb and sketch cache are planted — the supported
    resume state, so `compare <wd>` (no -g) starts at the cluster stage —
    and a hard-linked pristine twin for the streaming leg. Returns the
    planted partition {genome: cluster}."""
    import numpy as np
    import pandas as pd

    from drep_tpu.ingest import DEFAULT_SCALE, _save, sketch_args_snapshot
    from drep_tpu.utils.synth import plant_genome_sketches
    from drep_tpu.workdir import WorkDirectory

    gs, labels = plant_genome_sketches(
        sz["n_planted"], np.random.default_rng(seed), s_scaled=sz["s_scaled"]
    )
    wd = WorkDirectory(wd_b)
    wd.store_db(
        pd.DataFrame({"genome": gs.names,
                      "location": [f"/nonexistent/{g}" for g in gs.names]}),
        "Bdb",
    )
    _save(wd, gs)
    wd.store_arguments(
        "sketch",
        sketch_args_snapshot(gs.names, gs.k, gs.sketch_size, DEFAULT_SCALE, "splitmix64"),
    )
    shutil.copytree(wd_b, wd_c, copy_function=os.link)
    return dict(zip(gs.names, labels.tolist()))


# ---- the legs ---------------------------------------------------------------


def leg_a(out: str, probe: dict, clock: Clock, seed: int, sz: dict):
    paths, truth, keep = plant_genomes(os.path.join(out, "genomes"), seed, sz)
    info = os.path.join(out, "genomeInfo.csv")
    rows = ["genome,completeness,contamination"]
    for name in truth:
        m = int(name.split("m")[1].split(".")[0])
        rows.append(f"{name},{99 - m},{0.5 + 0.1 * m}")
    write_text(info, "\n".join(rows) + "\n")
    wd = os.path.join(out, "wd_a")
    run_child(drep("dereplicate", wd, "-g", *paths, "--genomeInfo", info),
              clock, os.path.join(out, "leg_a.stderr"), "leg A (dereplicate)")
    rec = read_json(os.path.join(wd, "log", "perf_counters.json"))
    check_record(rec, probe, "leg A", native=True)
    if "one_shot_clusterlocal" not in (rec.get("secondary_paths") or {}):
        raise SmokeFailure(
            f"leg A: the batched cluster-local one-shot secondary did not serve "
            f"(paths: {rec.get('secondary_paths')})"
        )
    primary, secondary = read_cdb(wd)
    want_p = partition({g: t[0] for g, t in truth.items()})
    want_s = partition(truth)
    if partition(primary) != want_p or partition(secondary) != want_s:
        raise SmokeFailure(
            f"leg A: Cdb is not the planted partition: "
            f"{len(partition(primary))} primary / {len(partition(secondary))} "
            f"secondary clusters, planted {len(want_p)} / {len(want_s)}"
        )
    import pandas as pd

    winners = list(pd.read_csv(os.path.join(wd, "data_tables", "Wdb.csv"))["genome"])
    if len(winners) != len(want_s) or len({secondary[w] for w in winners}) != len(want_s):
        raise SmokeFailure(
            f"leg A: {len(winners)} winners for {len(want_s)} secondary clusters"
        )
    return paths, truth, keep, (
        f"{len(want_p)} primary, {len(want_s)} secondary clusters, {len(winners)} "
        f"winners; Cdb digest {digest(partition(secondary))}"
    )


def _check_planted_compare(wd: str, planted: dict, leg: str) -> set:
    primary, _ = read_cdb(wd)
    got = partition(primary)
    if got != partition(planted):
        raise SmokeFailure(
            f"{leg}: primary clusters are not the planted partition "
            f"({len(got)} clusters, planted {len(partition(planted))})"
        )
    return got


def leg_b(out: str, wd: str, planted: dict, probe: dict, clock: Clock) -> str:
    # figures are host matplotlib, no part of the device path: leg A draws
    # them at defaults, the 5,000-genome legs skip them for the time limit
    run_child(drep("compare", wd, "--skip_plots"), clock,
              os.path.join(out, "leg_b.stderr"), "leg B (dense compare)")
    rec = read_json(os.path.join(wd, "log", "perf_counters.json"))
    check_record(rec, probe, "leg B")
    _check_planted_compare(wd, planted, "leg B")
    _, secondary = read_cdb(wd)
    if partition(secondary) != partition(planted):
        raise SmokeFailure(
            f"leg B: secondary clusters are not the planted partition "
            f"({len(partition(secondary))} clusters, planted {len(partition(planted))})"
        )
    from drep_tpu.workdir import WorkDirectory

    resolved = (WorkDirectory(wd).get_arguments("cluster") or {}).get(
        "primary_estimator_resolved"
    )
    how = f"primary estimator {resolved!r}"
    if probe["n_devices"] > 1:
        # several chips: the dense primary must be the mesh ring
        if resolved != "ring_sort":
            raise SmokeFailure(f"leg B: {probe['n_devices']} devices but {how}")
    elif resolved != "sort":
        raise SmokeFailure(f"leg B: one device but {how}")
    return (f"{len(partition(planted))} planted clusters recovered (Cdb digest "
            f"{digest(partition(secondary))}); {how}; secondary paths "
            f"{rec.get('secondary_paths')}")


def leg_c(out: str, wd: str, wd_b: str, planted: dict, probe: dict, clock: Clock) -> str:
    run_child(
        drep("compare", wd, "--streaming_primary", "--SkipSecondary", "--skip_plots"),
        clock, os.path.join(out, "leg_c.stderr"), "leg C (streaming compare)",
    )
    rec = read_json(os.path.join(wd, "log", "perf_counters.json"))
    check_record(rec, probe, "leg C")
    got = _check_planted_compare(wd, planted, "leg C")
    if got != partition(read_cdb(wd_b)[0]):
        raise SmokeFailure("leg C: streaming primary clusters differ from leg B's")
    used = (rec.get("gauges") or {}).get("streaming_devices_used")
    st = (rec.get("stages") or {}).get("primary_compare", {})
    # every device takes tiles (the full size walks 15 tiles, more than any host has chips)
    if used != min(probe["n_devices"], st.get("tiles_computed", 0)):
        raise SmokeFailure(
            f"leg C: {st.get('tiles_computed')} tiles reached {used} device(s) "
            f"of {probe['n_devices']}"
        )
    return (f"{st.get('tiles_computed')} tiles over {int(used)} device(s), primary "
            f"clusters == leg B's (digest {digest(got)})")


def _read_line(stream, box: dict) -> None:
    box["line"] = stream.readline()


def leg_d(out: str, genomes: list, truth: dict, queries: dict, probe: dict, clock: Clock) -> str:
    idx = os.path.join(out, "idx")
    run_child(drep("index", "build", idx, "-g", *genomes), clock,
              os.path.join(out, "leg_d_build.stderr"), "leg D (index build)")
    check_record(read_json(os.path.join(idx, "log", "perf_counters.json")), probe,
                 "leg D (index build)", native=True)

    from drep_tpu.serve.client import ServeClient

    servelog = os.path.join(out, "servelog")
    # drep-lint: allow[durable-funnel] — the daemon child's live stderr sink (a stream, not a published payload)
    with open(os.path.join(out, "leg_d_serve.stderr"), "w") as errf:
        proc = subprocess.Popen(
            drep("index", "serve", idx, "--log_dir", servelog), cwd=REPO,
            env=_child_env(), stdout=subprocess.PIPE, stderr=errf, text=True,
            start_new_session=True,
        )
    _CHILDREN.append(proc)
    box: dict = {}
    reader = threading.Thread(target=_read_line, args=(proc.stdout, box), daemon=True)
    reader.start()
    reader.join(timeout=max(1.0, clock.left()))
    if not box.get("line"):
        _kill(proc)
        raise SmokeFailure(
            "leg D: the daemon printed no ready line; stderr tail:\n"
            + open(os.path.join(out, "leg_d_serve.stderr")).read()[-4000:]
        )
    ready = json.loads(box["line"])
    check_record(ready, probe, "leg D (serve ready line)")
    paths = list(queries)
    budget_ms = max(1.0, clock.left()) * 1000.0
    served: dict = {}
    try:
        with ServeClient(ready["serving"], timeout_s=max(1.0, clock.left())) as c:
            first = c.classify(paths[0], deadline_ms=budget_ms)
            # two requests in flight on one connection: they must coalesce
            pair = c.classify_many(paths[1:3], deadline_ms=budget_ms)
            last = c.classify(paths[3], deadline_ms=budget_ms)
            status = c.status()
        for path, resp in zip(paths, [first, *pair, last]):
            if not resp.get("ok"):
                raise SmokeFailure(f"leg D: query {os.path.basename(path)} refused: {resp}")
            served[os.path.basename(path)] = resp["verdict"]
        if [r.get("batch_size") for r in pair] != [2, 2]:
            raise SmokeFailure(
                f"leg D: the pipelined pair did not coalesce "
                f"(batch sizes {[r.get('batch_size') for r in pair]})"
            )
        check_record(status, probe, "leg D (serve status)")
    except SmokeFailure:
        _kill(proc)
        raise
    except Exception as e:  # noqa: BLE001 — a dead connection, a protocol error
        _kill(proc)
        raise SmokeFailure(
            f"leg D: serving failed: {type(e).__name__}: {e}; daemon stderr tail:\n"
            + open(os.path.join(out, "leg_d_serve.stderr")).read()[-4000:]
        ) from e
    proc.send_signal(signal.SIGTERM)
    try:
        rc = proc.wait(timeout=max(1.0, min(120.0, clock.left())))
    except subprocess.TimeoutExpired:
        _kill(proc)
        raise SmokeFailure("leg D: the daemon did not drain on SIGTERM") from None
    if rc != 0:
        raise SmokeFailure(f"leg D: the daemon exited {rc} after SIGTERM, not 0")
    rec = read_json(os.path.join(servelog, "perf_counters.json"))
    check_record(rec, probe, "leg D (serve)", native=True)
    gauges = rec.get("gauges") or {}
    if gauges.get("serve_resident_uploads") != 1 or gauges.get("serve_resident_fallbacks"):
        raise SmokeFailure(
            f"leg D: the device-resident path did not serve cleanly: uploads="
            f"{gauges.get('serve_resident_uploads')} fallbacks="
            f"{gauges.get('serve_resident_fallbacks')}"
        )

    # only now, the chip free again: the one-shot classify of the same four
    stdout, stderr = run_child(drep("index", "classify", idx, "-g", *paths), clock,
                               os.path.join(out, "leg_d_classify.stderr"),
                               "leg D (index classify)")
    one_shot = {v["genome"]: v for v in map(json.loads, stdout.strip().splitlines())}
    recs = [ln.split("perf_counters: ", 1)[1] for ln in stderr.splitlines()
            if "perf_counters: " in ln]
    if not recs:
        raise SmokeFailure("leg D: index classify logged no perf_counters record")
    check_record(json.loads(recs[-1]), probe, "leg D (index classify)", native=True)
    for path, src in queries.items():
        name = os.path.basename(path)
        if served.get(name) != one_shot.get(name):
            raise SmokeFailure(
                f"leg D: served verdict != one-shot classify for {name}:\n"
                f"  served   {served.get(name)}\n  one-shot {one_shot.get(name)}"
            )
        v = served[name]
        if src is None:
            if not v["novel_primary"]:
                raise SmokeFailure(f"leg D: the unrelated genome was placed: {v}")
        elif v["novel_secondary"] or set(v["cluster_members"]) != {
            g for g, t in truth.items() if t == truth[src]
        }:
            raise SmokeFailure(
                f"leg D: a 1% copy of {src} (planted cluster {truth[src]}) got {v}"
            )
    return (f"4 verdicts == one-shot classify == truth; daemon drained with exit 0; "
            f"{status.get('requests_total')} requests in {status.get('batches_total')} batches")


# ---- main -------------------------------------------------------------------


def fail(msg: str) -> int:
    """A failed run: the details on stderr, and a last stdout line that is
    plain text — never a result."""
    first, _, rest = msg.partition("\n")
    if rest:
        print(rest, file=sys.stderr, flush=True)
    print(f"chip_smoke: FAILED — {first}", flush=True)
    return 1


def main(argv: list[str] | None = None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--seed", type=int, default=44,
                    help="every genome, sketch and query is generated from it "
                         "(default 44: the planted set PARITY.md's ARI-at-depth "
                         "numbers were taken on)")
    ap.add_argument("--out", default=os.path.join(REPO, "chip_smoke_out"),
                    help="output directory (git-ignored; wiped at start)")
    ap.add_argument("--report", default=None,
                    help="also write the run's report JSON here (e.g. under "
                         "chiprun_out/ to bring it back from the chip machine)")
    ap.add_argument("--rehearse", action="store_true",
                    help="toy sizes on whatever backend JAX finds — to debug this "
                         "script in a sandbox; never a chip pass")
    args = ap.parse_args(argv)
    clock = Clock()
    for k in [k for k in os.environ if k.startswith("DREP_TPU_")]:
        del os.environ[k]  # this process imports drep_tpu modules too
    if not os.path.isdir(os.path.join(REPO, "drep_tpu")):
        return fail(f"no drep_tpu package beside {os.path.basename(__file__)} — "
                    f"the smoke drives the program, it is not the program")
    sys.path.insert(0, REPO)
    out = os.path.abspath(args.out)
    sz = SIZES["rehearse" if args.rehearse else "full"]
    report: dict = {"seed": args.seed, "rehearsal": args.rehearse, "legs": {}}
    probe: dict | None = None
    try:
        # 1. what is there? — before any data is generated
        try:
            stdout, _ = run_child([sys.executable, "-c", PROBE], clock, None,
                                  "device probe")
            probe = json.loads(stdout.strip().splitlines()[-1])
        except SmokeFailure as e:
            return fail(f"no JAX backend came up: {e}")
        report["probe"] = probe
        print(f"probe: {json.dumps(probe)}", flush=True)
        if probe["platform"] != "tpu" and not args.rehearse:
            return fail(
                f"JAX found no accelerator (platform: {probe['platform']}); "
                f"nothing was generated, nothing was run"
            )

        shutil.rmtree(out, ignore_errors=True)
        os.makedirs(out)
        # what the machine allows a run to write — the first thing to read
        # when a leg dies of an OSError
        import resource

        fsize = resource.getrlimit(resource.RLIMIT_FSIZE)[0]
        print(f"machine: file-size limit "
              f"{'none' if fsize == resource.RLIM_INFINITY else f'{fsize} bytes'}, "
              f"{shutil.disk_usage(out).free / 2**30:.1f} GiB free under {out}", flush=True)
        # 2. the native ingest library, compiled HERE from the committed sources
        from drep_tpu import native

        shutil.rmtree(os.path.join(REPO, "drep_tpu", "native", "_build"),
                      ignore_errors=True)
        if native.get_library() is None:
            return fail("the native ingest library did not build (g++ output above)")

        # 3. the legs, one child at a time
        def leg(name: str, fn, *a):
            t0 = time.monotonic()
            result = fn(*a)
            dt = time.monotonic() - t0
            summary = result[-1] if isinstance(result, tuple) else result
            report["legs"][name] = {"seconds": round(dt, 1), "summary": summary}
            print(f"leg {name}: OK in {dt:.0f}s (set-up information, not a metric) "
                  f"— {summary}", flush=True)
            return result

        genomes, truth, keep, _ = leg("A", leg_a, out, probe, clock, args.seed, sz)
        t0 = time.monotonic()
        wd_b, wd_c = os.path.join(out, "wd_b"), os.path.join(out, "wd_c")
        planted = plant_sketch_workdirs(wd_b, wd_c, args.seed + 1, sz)
        print(f"planted {len(planted)} sketch sets in {time.monotonic() - t0:.0f}s", flush=True)
        leg("B", leg_b, out, wd_b, planted, probe, clock)
        leg("C", leg_c, out, wd_c, wd_b, planted, probe, clock)
        queries = plant_queries(os.path.join(out, "queries"), args.seed + 2, keep,
                                sz["genome_len"])
        leg("D", leg_d, out, genomes, truth, queries, probe, clock)
    except SmokeFailure as e:
        report["error"] = str(e)
        return fail(str(e))
    finally:
        _kill_all()
        report["seconds"] = round(time.monotonic() - clock.t0, 1)
        report["records"] = RECORDS
        for path in filter(None, [args.report,
                                  os.path.join(out, "report.json") if os.path.isdir(out) else None]):
            os.makedirs(os.path.dirname(os.path.abspath(path)), exist_ok=True)
            write_text(path, json.dumps(report, indent=1, sort_keys=True))

    device = {"platform": probe["platform"], "kind": probe["device_kind"],
              "count": probe["n_devices"]}
    reduced = f"toy sizes {sz}" if args.rehearse else REDUCED
    print(f"all legs passed in {report['seconds']:.0f}s; reduced: {reduced}", flush=True)
    if args.rehearse:
        print(json.dumps({"ok": False, "rehearsal": True, "rehearsal_ok": True,
                          "device": device}), flush=True)
        return 0
    print(json.dumps({"ok": True, "device": device}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
