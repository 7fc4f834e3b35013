"""Kernel seconds of the whole process inside the `job` span (`sys_s`: page
faults, file creation and rename, `mmap`, all threads). Median over the
window's jobs; None where the record has no such field."""

from benchmark import host


def read(run: dict):
    return host.of_span(run, "job", "sys_s")
