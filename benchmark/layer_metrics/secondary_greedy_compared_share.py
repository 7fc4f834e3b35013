"""Share of the all-pairs comparisons of its primary clusters that the greedy
rule consumed: a genome is compared with the representatives that exist when
it is visited, not with every other genome, and what the rule spares is the
rest. Both routes: the engine's clusters (`secondary_greedy_calls`, one entry
a cluster) and the batched route's (`secondary_greedy_batched`), each with
`compared_pairs` of `all_pairs`; summed over the window's jobs."""


def read(run: dict):
    compared = all_pairs = 0
    for job in run.get("jobs", []):
        record = job["record"]
        entries = list(record.get("secondary_greedy_calls") or [])
        if record.get("secondary_greedy_batched"):
            entries.append(record["secondary_greedy_batched"])
        for entry in entries:
            compared += entry["compared_pairs"]
            all_pairs += entry["all_pairs"]
    return 100.0 * compared / all_pairs if all_pairs else None
