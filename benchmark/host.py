"""Reading what the host spent inside a job's spans: the fields every entry
of the record's `phases` carries beside its seconds (`Counters.span`,
drep_tpu/utils/profiling.py, "Host accounting"). `cpu_s` / `sys_s`: user +
kernel and kernel seconds of all threads of the process, whole and `self_`;
`self_minor_faults`: pages first touched; `self_thread_cpu_s`: the opening
thread's own CPU, so `self_seconds` less it is the thread off its CPU;
`self_invol_switches`: it was preempted; `gc_s`: the cyclic collector. The
`self_` values of the main thread's phases (`thread` is `main`) add up to the
`job` span's own. A job of several attempts comes as one record with the
numbers summed (`resume_jobs.merge_records`). A record without the fields
(the parent of the PR that brought them) gives every reader here None, and
so does a job whose kernel keeps no faults or switches (`_kept`) the readers
of those."""

from __future__ import annotations

import resource
import statistics

GIB = float(2**30)


def _median(per_job: list) -> float | None:
    return statistics.median(per_job) if per_job else None


def _main_phases(job: dict) -> dict:
    return {name: phase for name, phase in (job["record"].get("phases") or {}).items()
            if phase.get("thread") == "main"}


def _kept(job: dict) -> bool:
    """Whether this job's host keeps the kernel's counts of faults and
    switches at all. A sandboxed kernel (the chip host's) reports page faults
    as 0 for ever and context switches nearly so (one voluntary switch in
    112 jobs), and no job of seconds touches no page: no minor fault over
    the main thread's phases is a kernel that does not count, so the job has
    nothing to read of either."""
    return any(phase.get("self_minor_faults") for phase in _main_phases(job).values())


def of_span(run: dict, name: str, field: str, counted: bool = False) -> float | None:
    """`field` of the main thread's span `name`: the median over the
    window's jobs, or None where no job's record has it (`counted`: nor its
    host a source for it, `_kept`)."""
    per_job = []
    for job in run.get("jobs", []):
        phase = (job["record"].get("phases") or {}).get(name) or {}
        if field in phase and (not counted or _kept(job)):
            per_job.append(phase[field])
    return _median(per_job)


def summed(run: dict, value, counted: bool = False) -> float | None:
    """`value(name, phase)` summed over a job's main-thread phases, where it
    gives a number (None: the phase is skipped): the median over the window's
    jobs, or None where no job has a phase that gives one (`counted`: nor its
    host a source for it, `_kept`)."""
    per_job = []
    for job in run.get("jobs", []):
        found = [v for name, phase in _main_phases(job).items() if (v := value(name, phase)) is not None]
        if found and (not counted or _kept(job)):
            per_job.append(sum(found))
    return _median(per_job)


def pages_to_gib(pages: float | None) -> float | None:
    """First-touched pages as GiB at this host's page size: the job ran in
    this process. A transparent huge page counts as one fault, so under
    them this is a floor."""
    return None if pages is None else pages * resource.getpagesize() / GIB
