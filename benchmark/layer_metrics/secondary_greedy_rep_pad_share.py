"""Share of the representative rows the greedy engine's blocks are computed
against that hold no representative: a block meets the resident set padded to
whole tiles, 512 rows for a handful of representatives, and the first block
of every cluster meets a tile of padding alone. 1 - `rep_rows_real` /
`rep_rows_shipped` of the record's `secondary_greedy_calls`, both summed over
the clusters and over the window's jobs."""


def read(run: dict):
    real = shipped = 0
    for job in run.get("jobs", []):
        for call in job["record"].get("secondary_greedy_calls") or []:
            real += call["rep_rows_real"]
            shipped += call["rep_rows_shipped"]
    return 100.0 * (1.0 - real / shipped) if shipped else None
