"""Seconds the main thread spent off its CPU outside the device waits:
`self_seconds - self_thread_cpu_s` summed over its phases whose name does not
end in `wait` (those have their own metrics): files, worker threads and
processes it waited for, the GIL, and time descheduled. Median over the
window's jobs; None where the record has no such field."""

from benchmark import host


def _off_cpu(name: str, phase: dict):
    if name.endswith("wait") or "self_thread_cpu_s" not in phase:
        return None
    return phase["self_seconds"] - phase["self_thread_cpu_s"]


def read(run: dict):
    return host.summed(run, _off_cpu)
