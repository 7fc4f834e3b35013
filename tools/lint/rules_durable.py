"""Rule ``durable-funnel``: all shared-filesystem payload writes go
through utils/durableio.py (PR 5's pinned invariant)."""

from __future__ import annotations

from .engine import Finding, Rule
from .model import RepoModel, iter_calls, write_call_kind

RULE_ID = "durable-funnel"

# modules ALLOWED to write directly — each is its own durability story:
# - durableio.py IS the funnel (uuid-tmp + rename + fsync + crc).
# - workdir.py predates the funnel and routes its payloads through the
#   atomic/checksum helpers; its savez writer is the keep_suffix case.
# - telemetry.py's append-only flushed-whole-lines event sink is a
#   crash-safe format BY DESIGN (a torn final line is classified, PR 10)
#   — funnelling it through tmp+rename would destroy the append model.
ALLOWED = frozenset({
    "drep_tpu/utils/durableio.py",
    "drep_tpu/workdir.py",
    "drep_tpu/utils/telemetry.py",
})

EXPLAIN = """\
Every recovery path in this repo ASSUMES shared-filesystem payloads are
whole-file-or-nothing and checksummed: resume globs trust that a file
that exists is complete, scrub_store classifies torn bytes as damage.
A bare open(path, "w") (or
np.savez / json.dump / os.replace / Path.write_*) outside the funnel
publishes exactly the torn, CRC-less artifacts those paths misclassify.
Pinned by PR 5 (durable storage); the drifted writers it found
(cluster/external.py, tools/serve_client.py, tools/trace_report.py)
were fixed by PR 12.

Fix: route through drep_tpu.utils.durableio — atomic_write_bytes /
atomic_write_json / atomic_savez, or atomic_write(path, write_fn) when
you must stream. Writes INSIDE a write_fn body target the tmp path the
funnel hands you: waive those lines with
`# drep-lint: allow[durable-funnel] — write_fn body for durableio.atomic_write`.
"""


def run(model: RepoModel) -> list[Finding]:
    out: list[Finding] = []
    for sf in model.prod_files():
        if sf.path in ALLOWED:
            continue
        for call in iter_calls(sf.tree):
            kind = write_call_kind(call)
            if kind is None:
                continue
            out.append(Finding(
                rule=RULE_ID, path=sf.path, line=call.lineno,
                message=f"write-capable call {kind} outside the durable-I/O "
                        f"funnel",
                hint="route through drep_tpu.utils.durableio "
                     "(atomic_write_bytes/atomic_write_json/atomic_savez), "
                     "or waive with a reason if this is a write_fn body / "
                     "deliberate chaos injection",
            ))
    return out


RULES = [Rule(id=RULE_ID, title="durable-write funnel", run=run, explain=EXPLAIN)]
