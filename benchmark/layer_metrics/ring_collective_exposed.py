"""Share of the traced job in which a collective runs on a device and no
other operation does, on the device where that is longest."""

from benchmark import tracered


def read(run: dict):
    trace = run.get("trace")
    if not trace or len(trace["events"]["devices"]) < 2:
        return None
    return 100.0 * tracered.exposed_collective_seconds(trace["events"]) / trace["window_s"]
