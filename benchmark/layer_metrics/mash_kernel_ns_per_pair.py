"""Device nanoseconds of the Pallas Mash kernel per genome pair: the device
durations of the kernel's operations in the traced job, summed over devices,
over the pairs that job's record says the primary compared. The kernel is
bound by the vector unit, for which no peak is published, so it gets no
roofline share; the time its HBM traffic alone would need is printed beside
it (roofline.mash_hbm_bound_ns_per_pair)."""

from benchmark import roofline, tracered

KERNEL = r"mash"  # the names Mosaic gives the kernel's custom calls all carry it


def read(run: dict):
    trace = run.get("trace")
    if not trace or not run.get("jobs"):
        return None
    pairs = run["jobs"][0]["record"].get("stages", {}).get("primary_compare", {}).get("pairs", 0)
    seconds = tracered.op_seconds(trace["events"], KERNEL)
    if not pairs or not seconds:
        return None
    value = seconds * 1e9 / pairs
    bound = roofline.mash_hbm_bound_ns_per_pair(
        int(run["config"]["params"]["sketch_size"]), run["peaks"])
    print(f"layer: mash kernel {value:.3f} ns/pair; its HBM traffic alone would need "
          f"{bound:.5f} ns/pair (819 GB/s), so the kernel is not bound by memory", flush=True)
    return value
