"""Share of the traced job in which no operation ran on the device: 1 - busy
over the job's wall, busy being the union of the device-op intervals, mean
over devices."""


def read(run: dict):
    trace = run.get("trace")
    if not trace or not run.get("jobs"):
        return None
    return 100.0 * (1.0 - trace["busy_s"] / trace["window_s"])
