"""drep-lint (tools/lint) — the static contract gate (ISSUE 12).

Two halves, both fast tier-1:

- **Fixture half**: every rule must DEMONSTRABLY FIRE on a planted
  bad-code mini-repo (a rule that silently stops matching is itself the
  regression these tests exist to catch), and the engine mechanics
  (waiver-with-reason suppresses, reasonless waiver does not, baseline
  fingerprints tolerate + report stale, edge waivers stop the purity
  walk) behave as documented.
- **Live-tree half**: the full suite over THIS repo exits clean modulo
  the checked-in waivers/baseline — the actual CI gate (the tier-1
  pytest run IS the lint wiring), plus the `python -m tools.lint` CLI
  contract (exit codes, --format json, --explain for every rule).

Fixture knob/site names are built by concatenation so the live-tree
scan of this very file never sees an undeclared DREP_TPU_* literal or a
bogus fault-spec string.
"""

from __future__ import annotations

import json
import os
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

from tools.lint import engine  # noqa: E402
from tools.lint.model import RepoModel  # noqa: E402

# built by concatenation: must never appear whole in this file
BOGUS_KNOB = "DREP_TPU_" + "BOGUS_KNOB"
GOOD_KNOB = "DREP_TPU_" + "FIXTURE_KNOB"
BOGUS_SITE = "bogus" + "_site"
BOGUS_SPEC = "streaming_tile:" + "explode"
# the waiver marker, split so the live-tree scan of THIS file's raw
# lines never sees fixture waivers as real ones
W = "# drep" + "-lint"


def _plant(root, rel: str, text: str) -> None:
    loc = os.path.join(root, rel)
    os.makedirs(os.path.dirname(loc), exist_ok=True)
    with open(loc, "w", encoding="utf-8") as f:
        f.write(text)


def _mini_repo(root) -> None:
    """The smallest tree the rules' anchors (registry paths, entrypoint
    list) resolve against."""
    _plant(root, "drep_tpu/utils/envknobs.py", (
        "KNOBS = {}\n"
        "def _declare(name, kind, default, doc):\n"
        "    KNOBS[name] = (kind, default, doc)\n"
        f'_declare("{GOOD_KNOB}", "int", 1, "fixture")\n'
    ))
    _plant(root, "drep_tpu/utils/faults.py", (
        'SITES = ("streaming_tile", "io")\n'
        'IO_MODES = ("io_error",)\n'
        'MODES = ("raise", "hang") + IO_MODES\n'
    ))


def _run_fixture(root, rule_ids):
    result, model = engine.run(
        str(root), rule_ids=rule_ids, baseline_path=None,
    )
    return result


# --- each rule fires on planted bad code -----------------------------------


def test_durable_funnel_fires_on_each_write_kind(tmp_path):
    _mini_repo(tmp_path)
    _plant(tmp_path, "drep_tpu/bad.py", (
        "import json, os\n"
        "import numpy as np\n"
        "from pathlib import Path\n"
        "def bad(p, arr, doc):\n"
        '    with open(p, "w") as f:\n'
        "        f.write('x')\n"
        "    np.savez(p, a=arr)\n"
        "    with open(p + '2', 'wb') as f:\n"
        "        json.dump(doc, f)\n"
        "    os.replace(p, p + '3')\n"
        "    Path(p).write_text('x')\n"
        "    with Path(p).open('w') as f:\n"
        "        f.write('x')\n"
        "def fine(p, zf):\n"
        '    with open(p) as f:\n'
        "        return f.read() + zf.open('extra.txt').read()\n"
    ))
    r = _run_fixture(tmp_path, ["durable-funnel"])
    kinds = sorted(f.message.split()[2] for f in r.findings)
    assert len(r.findings) == 7, r.findings
    assert any("np.savez" in k for k in kinds)
    assert any("os.replace" in k for k in kinds)
    assert any("json.dump" in k for k in kinds)
    assert any("Path.write_text" in k for k in kinds)
    assert all(f.path == "drep_tpu/bad.py" for f in r.findings)


def test_durable_funnel_allows_funnel_modules_and_waivers(tmp_path):
    _mini_repo(tmp_path)
    _plant(tmp_path, "drep_tpu/utils/durableio.py", (
        "def atomic_write_bytes(p, b):\n"
        '    with open(p, "wb") as f:\n'
        "        f.write(b)\n"
    ))
    _plant(tmp_path, "drep_tpu/waived.py", (
        "def ok(p):\n"
        f'    with open(p, "w") as f:  {W}: allow[durable-funnel] — fixture reason\n'
        "        f.write('x')\n"
    ))
    r = _run_fixture(tmp_path, ["durable-funnel"])
    assert r.findings == []
    assert len(r.waived) == 1 and r.waived[0].waive_reason == "fixture reason"


def test_reader_purity_fires_through_the_call_graph(tmp_path):
    _mini_repo(tmp_path)
    _plant(tmp_path, "tools/pod_status.py", (
        "import json, os\n"
        "def _dump(path, doc):\n"
        '    with open(path, "w") as f:\n'
        "        json.dump(doc, f)\n"
        "def collect(d):\n"
        '    _dump(os.path.join(d, "x.json"), {})\n'
        "    return {}\n"
        "def main():\n"
        "    collect('.')\n"
        "    return 0\n"
    ))
    r = _run_fixture(tmp_path, ["reader-purity"])
    hits = [f for f in r.findings if f.path == "tools/pod_status.py"]
    assert hits, r.findings
    assert any("_dump" in f.message and "collect" in f.message for f in hits)


def test_reader_purity_edge_waiver_stops_the_walk(tmp_path):
    _mini_repo(tmp_path)
    _plant(tmp_path, "tools/pod_status.py", (
        "import json, os\n"
        "def _dump(path, doc):\n"
        '    with open(path, "w") as f:\n'
        "        json.dump(doc, f)\n"
        "def collect(d):\n"
        f"    {W}: allow[reader-purity] — fixture gate reason\n"
        '    _dump(os.path.join(d, "x.json"), {})\n'
        "    return {}\n"
        "def main():\n"
        "    return 0\n"
    ))
    r = _run_fixture(tmp_path, ["reader-purity"])
    assert [f for f in r.findings if f.path == "tools/pod_status.py"] == []


def test_env_knob_fires_on_undeclared_literal_and_direct_read(tmp_path):
    _mini_repo(tmp_path)
    _plant(tmp_path, "drep_tpu/bad_env.py", (
        "import os\n"
        f'x = os.environ.get("{BOGUS_KNOB}")\n'
        f'y = os.environ.get("{GOOD_KNOB}", "1")\n'
        f'z = os.environ["{GOOD_KNOB}"]\n'
        f'os.environ["{GOOD_KNOB}"] = "1"\n'  # write: legal (child env setup)
    ))
    r = _run_fixture(tmp_path, ["env-knob"])
    msgs = [f.message for f in r.findings]
    assert any(BOGUS_KNOB in m and "undeclared" in m for m in msgs), msgs
    # .get() reads at lines 2-3 plus the subscript READ at line 4 (the
    # subscript WRITE at line 5 stays legal) => 3 direct-read findings
    assert sum("direct os.environ" in m for m in msgs) == 3, msgs


def test_env_knob_direct_read_via_module_constant(tmp_path):
    _mini_repo(tmp_path)
    _plant(tmp_path, "drep_tpu/bad_env2.py", (
        "import os\n"
        f'MY_ENV = "{GOOD_KNOB}"\n'
        "v = os.environ.get(MY_ENV, '0')\n"
    ))
    r = _run_fixture(tmp_path, ["env-knob"])
    assert any("direct os.environ read" in f.message for f in r.findings)


def test_clock_mono_fires_and_waives(tmp_path):
    _mini_repo(tmp_path)
    _plant(tmp_path, "drep_tpu/bad_clock.py", (
        "import time\n"
        "def elapsed(t0):\n"
        "    return time.time() - t0\n"
        "def stamp():\n"
        f"    return time.time()  {W}: allow[clock-mono] — fixture cross-host stamp\n"
        "def fine():\n"
        "    return time.monotonic()\n"
    ))
    r = _run_fixture(tmp_path, ["clock-mono"])
    assert len(r.findings) == 1 and r.findings[0].line == 3
    assert len(r.waived) == 1


def test_fault_site_fires_on_unknown_site_mode_and_uncovered_site(tmp_path):
    _mini_repo(tmp_path)
    _plant(tmp_path, "drep_tpu/bad_faults.py", (
        "from drep_tpu.utils.faults import fire\n"
        f'def f():\n    fire("{BOGUS_SITE}")\n'
        f'SPEC = "{BOGUS_SPEC}"\n'
    ))
    # tests reference streaming_tile but never the registered io site
    _plant(tmp_path, "tests/test_fixture.py", 'S = "streaming_tile:raise"\n')
    r = _run_fixture(tmp_path, ["fault-site"])
    msgs = [f.message for f in r.findings]
    assert any(BOGUS_SITE in m and "not in" in m for m in msgs), msgs
    assert any("unknown mode" in m for m in msgs), msgs
    assert any("'io'" in m and "no test" in m for m in msgs), msgs


def test_telemetry_gate_fires_on_private_use_and_adhoc_sink_write(tmp_path):
    _mini_repo(tmp_path)
    _plant(tmp_path, "drep_tpu/bad_tel.py", (
        "import os\n"
        "from drep_tpu.utils import telemetry\n"
        "from drep_tpu.utils.telemetry import _sink\n"
        "def bad(wd):\n"
        '    telemetry._emit("x", "i", None)\n'
        '    with telemetry.Span("stripe", {}):\n'
        "        pass\n"
        '    with open(os.path.join(wd, "log", "events.p9.jsonl"), "a") as f:\n'
        "        f.write('{}')\n"
    ))
    r = _run_fixture(tmp_path, ["telemetry-gate"])
    msgs = [f.message for f in r.findings]
    assert any("_emit" in m for m in msgs), msgs
    # one front door: a span on the event log's clock alone is a finding
    assert any("telemetry.Span" in m and "front door" in m for m in msgs), msgs
    assert any("_sink" in m and "from-imported" in m for m in msgs), msgs
    assert any("ad-hoc write" in m for m in msgs), msgs


# --- engine mechanics ------------------------------------------------------


def test_waiver_without_reason_does_not_suppress(tmp_path):
    _mini_repo(tmp_path)
    _plant(tmp_path, "drep_tpu/bad_clock.py", (
        "import time\n"
        f"t = time.time()  {W}: allow[clock-mono]\n"
    ))
    r = _run_fixture(tmp_path, ["clock-mono"])
    assert len(r.findings) == 1  # still active
    assert len(r.reasonless_waivers) == 1


def test_unknown_waiver_rule_is_reported(tmp_path):
    _mini_repo(tmp_path)
    _plant(tmp_path, "drep_tpu/w.py", (
        f"x = 1  {W}: allow[no-such-rule] — typo\n"
    ))
    r = _run_fixture(tmp_path, ["clock-mono"])
    assert any(rid == "no-such-rule" for _, rid in r.unknown_waiver_rules)


def test_baseline_tolerates_known_and_reports_stale(tmp_path):
    _mini_repo(tmp_path)
    _plant(tmp_path, "drep_tpu/bad_clock.py", (
        "import time\ndef f(t0):\n    return time.time() - t0\n"
    ))
    # first run: discover the fingerprint via --write-baseline semantics
    r1, model = engine.run(str(tmp_path), rule_ids=["clock-mono"], baseline_path=None)
    assert len(r1.findings) == 1
    bl = tmp_path / "bl.json"
    engine.write_baseline(str(bl), r1, model)
    r2, _ = engine.run(
        str(tmp_path), rule_ids=["clock-mono"], baseline_path=str(bl)
    )
    assert r2.findings == [] and len(r2.baselined) == 1 and r2.ok
    # fix the code: the baseline entry goes stale and is reported
    _plant(tmp_path, "drep_tpu/bad_clock.py", (
        "import time\ndef f(t0):\n    return time.monotonic() - t0\n"
    ))
    r3, _ = engine.run(
        str(tmp_path), rule_ids=["clock-mono"], baseline_path=str(bl)
    )
    assert r3.findings == [] and len(r3.stale_baseline) == 1


def test_parse_error_fails_the_gate(tmp_path):
    _mini_repo(tmp_path)
    _plant(tmp_path, "drep_tpu/broken.py", "def f(:\n")
    r = _run_fixture(tmp_path, ["clock-mono"])
    assert not r.ok and r.parse_errors


# --- envknobs runtime semantics --------------------------------------------


def test_envknobs_typed_accessors(monkeypatch):
    from drep_tpu.utils import envknobs

    crc = "DREP_TPU_IO_CRC"
    monkeypatch.delenv(crc, raising=False)
    assert envknobs.env_bool(crc) is True  # declared default
    monkeypatch.setenv(crc, "0")
    assert envknobs.env_bool(crc) is False
    monkeypatch.setenv(crc, "false")
    assert envknobs.env_bool(crc) is False
    monkeypatch.setenv(crc, "")  # set-but-empty falls back to default
    assert envknobs.env_bool(crc) is True
    monkeypatch.setenv(crc, "garbage")  # a typo is loud, never a silent flip
    with pytest.raises(ValueError, match=crc):
        envknobs.env_bool(crc)

    hb = "DREP_TPU_HEARTBEAT_S"
    monkeypatch.delenv(hb, raising=False)
    assert envknobs.env_float(hb) == 5.0
    monkeypatch.setenv(hb, "0.5")
    assert envknobs.env_float(hb) == 0.5
    monkeypatch.setenv(hb, "nope")
    with pytest.raises(ValueError, match=hb):
        envknobs.env_float(hb)

    rows = "DREP_TPU_MASH_ROWS_PER_ITER"
    monkeypatch.delenv(rows, raising=False)
    assert envknobs.env_int(rows) == 1
    monkeypatch.setenv(rows, " 4 ")
    assert envknobs.env_int(rows) == 4

    # per-call default override (the collective timeout's two contexts)
    ct = "DREP_TPU_COLLECTIVE_TIMEOUT_S"
    monkeypatch.delenv(ct, raising=False)
    assert envknobs.env_float(ct, default=21600.0) == 21600.0
    monkeypatch.setenv(ct, "7")
    assert envknobs.env_float(ct, default=21600.0) == 7.0


def test_envknobs_undeclared_name_raises():
    from drep_tpu.utils import envknobs

    with pytest.raises(KeyError, match="undeclared"):
        envknobs.env_str(BOGUS_KNOB)
    with pytest.raises(ValueError, match="duplicate"):
        envknobs._declare("DREP_TPU_FAULTS", "str", "", "dup")


def test_envknobs_registry_covers_every_knob_in_tree():
    """The registry and the tree agree both ways (the lint rule enforces
    tree->registry; this pins registry->accessor sanity)."""
    from drep_tpu.utils import envknobs

    assert len(envknobs.KNOBS) >= 19
    for k in envknobs.KNOBS.values():
        assert k.kind in ("str", "int", "float", "bool")
        assert k.doc
        # every declared default round-trips through its accessor
        fn = {
            "str": envknobs.env_str, "int": envknobs.env_int,
            "float": envknobs.env_float, "bool": envknobs.env_bool,
        }[k.kind]
        if os.environ.get(k.name) is None:
            fn(k.name)  # must not raise with the var unset


# --- the live tree is clean (the CI gate) ----------------------------------


def test_live_tree_clean_modulo_waivers_and_baseline():
    result, model = engine.run(REPO)
    assert not result.parse_errors, result.parse_errors
    assert result.findings == [], (
        "drep-lint violations in the live tree:\n"
        + "\n".join(f"{f.path}:{f.line}: [{f.rule}] {f.message}"
                    for f in result.findings)
    )
    assert not result.unknown_waiver_rules, result.unknown_waiver_rules
    assert not result.reasonless_waivers, [
        (w.path, w.line) for w in result.reasonless_waivers
    ]
    # every waiver in the tree earns its keep (no dead waivers drifting)
    unused = [
        (w.path, w.line)
        for sf in model.files.values()
        for ws in sf.waivers.values()
        for w in ws
        if not w.used
    ]
    assert unused == [], f"unused drep-lint waivers: {unused}"
    # the shipped baseline is EMPTY: the gate holds with waivers alone
    assert result.baselined == [] and result.stale_baseline == []


def test_live_tree_has_reasoned_waivers_for_wall_clock():
    """The staleness protocol's wall-clock comparisons stay wall BY
    DESIGN — pinned here so a future blanket s/time.time/monotonic/
    sweep cannot silently land."""
    result, _ = engine.run(REPO, rule_ids=["clock-mono"])
    waived_paths = {f.path for f in result.waived}
    assert "drep_tpu/parallel/faulttol.py" in waived_paths
    assert "drep_tpu/utils/telemetry.py" in waived_paths
    assert all(f.waive_reason for f in result.waived)


def test_cli_contract():
    env = dict(os.environ)
    env["PYTHONPATH"] = REPO + os.pathsep + env.get("PYTHONPATH", "")
    out = subprocess.run(
        [sys.executable, "-m", "tools.lint", "--format", "json"],
        cwd=REPO, env=env, capture_output=True, text=True, timeout=120,
    )
    assert out.returncode == 0, out.stdout + out.stderr
    doc = json.loads(out.stdout)
    assert doc["ok"] is True and doc["findings"] == []
    # --explain resolves for every rule id (the rationale helper)
    for rule in engine.all_rules():
        ex = subprocess.run(
            [sys.executable, "-m", "tools.lint", "--explain", rule.id],
            cwd=REPO, env=env, capture_output=True, text=True, timeout=60,
        )
        assert ex.returncode == 0 and rule.id in ex.stdout
    bad = subprocess.run(
        [sys.executable, "-m", "tools.lint", "--explain", "nope"],
        cwd=REPO, env=env, capture_output=True, text=True, timeout=60,
    )
    assert bad.returncode == 2


def test_cli_exits_nonzero_on_violation(tmp_path):
    _mini_repo(tmp_path)
    _plant(tmp_path, "drep_tpu/bad_clock.py", (
        "import time\ndef f(t0):\n    return time.time() - t0\n"
    ))
    env = dict(os.environ)
    env["PYTHONPATH"] = REPO + os.pathsep + env.get("PYTHONPATH", "")
    out = subprocess.run(
        [sys.executable, "-m", "tools.lint", "--root", str(tmp_path),
         "--baseline", ""],
        cwd=REPO, env=env, capture_output=True, text=True, timeout=120,
    )
    assert out.returncode == 1, out.stdout + out.stderr
    assert "clock-mono" in out.stdout
