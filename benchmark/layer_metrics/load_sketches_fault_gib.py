"""Memory first touched inside `stage:ingest_or_cache` (`self_minor_faults`
times the page size, GiB): whether `load_sketches_s` is the first touch of
the arrays the sketch cache is read into. A floor under transparent huge
pages. Median over the window's jobs; None where the record has no such
field, or the host's kernel counts no faults (`host._kept`: the chip host's
does not, so no cell lists this metric yet)."""

from benchmark import host


def read(run: dict):
    return host.pages_to_gib(host.of_span(run, "stage:ingest_or_cache", "self_minor_faults", counted=True))
