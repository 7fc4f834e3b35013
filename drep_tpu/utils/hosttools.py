"""The host as library code sees it: the cores it may use (:func:`usable_cores`)
and a loader for host-side tools/ modules.

``tools/`` is deliberately NOT a package (standalone operator scripts),
but two library components consume ``tools/pod_status.py``'s
:func:`collect` — the serve daemon's ``/healthz`` (drep_tpu/serve/
daemon.py) and the autoscaling controller (drep_tpu/autoscale/
controller.py) — precisely so their snapshot can NEVER disagree with
the CLI watcher's. One shared loader keeps the resolution rule (and its
installed-package fallback behavior) from drifting between them.

Resolved once per process and cached: /healthz probes and controller
ticks fire every few seconds and must not re-execute the module.
Returns ``None`` when the file is unreachable (installed-package
deployments without the repo checkout) — callers degrade, never crash.
"""

from __future__ import annotations

import os

_POD_STATUS: list = []


def usable_cores() -> int:
    """Cores this process may run on (its affinity mask where the platform
    has one: a container's share, not the machine's count)."""
    if hasattr(os, "sched_getaffinity"):
        return len(os.sched_getaffinity(0))
    return os.cpu_count() or 1


def pod_status_collect():
    """``tools/pod_status.py``'s ``collect``, or None when unreachable."""
    if _POD_STATUS:
        return _POD_STATUS[0]
    collect = None
    try:
        from tools.pod_status import collect  # repo root on sys.path (CLI)
    except ImportError:
        import importlib.util

        repo = os.path.dirname(
            os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
        )
        path = os.path.join(repo, "tools", "pod_status.py")
        if os.path.exists(path):
            spec = importlib.util.spec_from_file_location(
                "_drep_pod_status", path
            )
            mod = importlib.util.module_from_spec(spec)
            spec.loader.exec_module(mod)
            collect = mod.collect
    _POD_STATUS.append(collect)
    return collect
