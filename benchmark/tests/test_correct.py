"""What makes `correct` false, and what does not: the comparison, its
control (the reference in the precision below) and a job that hid its work."""

import numpy as np
import pytest

from benchmark import cells, check
from benchmark import reference as ref
from benchmark.generators import planted_sketches

PARAMS = {"kmer_size": 21, "sketch_size": 1000, "scale": 200, "P_ani": 0.9, "S_ani": 0.95,
          "cov_thresh": 0.1, "retention_dist": 0.25}
DATA = {"n": 120, "s_bottom": 1000, "s_scaled": 1200, "kmer_size": 21, "keep_bottom": 0.9,
        "own_bottom": 166, "keep_scaled": 0.97, "own_scaled_div": 25,
        "cluster_law": {"law": "geometric", "p": 0.35, "cap": 20}}
WANT = ["primary", "secondary", "mdb", "ndb"]
LIMITS = {"mash_dist": 1e-6, "ani": 1e-6, "coverage": 1e-6}


@pytest.fixture(scope="module")
def planted():
    return planted_sketches.generate(DATA, 5)


def sound_tables(data) -> dict:
    """Tables as a sound program writes them: the reference's own answers,
    rounded to float32 like the program's."""
    edges = ref.mash_edges(data.bottom, 1000, 21)
    primary = ref.primary_partition(len(data.names), edges, 0.1)
    lab = {g: c for c, members in enumerate(primary) for g in members}
    tables = {"primary": {data.names[g]: lab[g] for g in lab}, "secondary": {}, "ndb": {}}
    pairs = [(i, j, d) for (i, j), d in edges.items() if d <= 0.25]
    tables["mdb"] = (np.array([data.names[i] for i, _, _ in pairs] + [data.names[j] for _, j, _ in pairs]),
                     np.array([data.names[j] for _, j, _ in pairs] + [data.names[i] for i, _, _ in pairs]),
                     np.array([np.float32(d) for _, _, d in pairs] * 2, np.float64))
    for c, members in enumerate(primary):
        group = sorted(members)
        ani, cov, labels = ref.secondary_of_cluster([data.scaled[g] for g in group], 21, 0.95, 0.1)
        for x, g in enumerate(group):
            tables["secondary"][data.names[g]] = f"{c}_{labels[x]}"
            for y, h in enumerate(group):
                if x != y:
                    tables["ndb"][(data.names[g], data.names[h])] = (
                        float(np.float32(ani[x, y])), float(np.float32(cov[x, y])))
    return tables


def test_sound_answers_are_correct(planted):
    out = check.check_batch(sound_tables(planted), planted, PARAMS, WANT, LIMITS, seed=1)
    assert all(c["ok"] for c in out), [c for c in out if not c["ok"]]
    assert max(c["value"] for c in out if "error" in c["what"]) < LIMITS["mash_dist"] / 3


def test_a_distance_off_by_1e_4_is_wrong(planted):
    tables = sound_tables(planted)
    tables["mdb"][2][3] += 1e-4
    out = check.check_batch(tables, planted, PARAMS, WANT, LIMITS, seed=1)
    assert [c["ok"] for c in out if "Mash distance error" in c["what"]] == [False]


def test_a_moved_genome_and_a_missing_pair_are_wrong(planted):
    tables = sound_tables(planted)
    a, b = planted.names[0], planted.names[-1]
    tables["primary"][a] = tables["primary"][b]
    g1, g2, dd = tables["mdb"]
    tables["mdb"] = (g1[1:], g2[1:], dd[1:])  # one direction of one pair is gone: still listed once
    out = {c["what"]: c["ok"] for c in check.check_batch(tables, planted, PARAMS, WANT, LIMITS, seed=1)}
    assert not out["genomes in a primary cluster the reference does not have"]
    assert out["Mdb pairs missing, or present and not in the reference"]
    tables["mdb"] = tuple(x[(g1 != g1[0]) | (g2 != g2[0])] for x in (g1, g2, dd))
    tables["mdb"] = tuple(x[~((tables["mdb"][0] == g2[0]) & (tables["mdb"][1] == g1[0]))]
                          for x in tables["mdb"])
    out = {c["what"]: c["ok"] for c in check.check_batch(tables, planted, PARAMS, WANT, LIMITS, seed=1)}
    assert not out["Mdb pairs missing, or present and not in the reference"]


@pytest.mark.parametrize("seed", [1, 2, 3])
def test_control_the_reference_in_bfloat16_is_not_correct(seed):
    """The control: the reference put in the program's place, its distances
    and ANIs rounded to bfloat16, the precision a later PR would be tempted by.
    It has to fail, and by a wide margin over the limit."""
    data = planted_sketches.generate(DATA, seed)
    out = check.check_batch({}, data, PARAMS, WANT, LIMITS, seed=seed, lower_precision=True)
    bad = [c for c in out if not c["ok"]]
    assert bad and all("error" in c["what"] for c in bad)
    assert min(c["value"] for c in bad) > 3 * max(LIMITS.values())


def test_each_number_is_held_to_its_own_limit(planted):
    """A cell that states three limits is held to each: an ANI off by 5e-6
    passes the Mash limit here and fails its own, and the other way round."""
    tables = sound_tables(planted)
    pair = next(iter(tables["ndb"]))
    ani, cov = tables["ndb"][pair]
    tables["ndb"][pair] = (ani + 5e-6, cov + 5e-4)
    tables["mdb"][2][3] += 5e-5
    verdicts = lambda limits: {c["what"].split(" error")[0].split()[-1]: c["ok"]  # noqa: E731
                               for c in check.check_batch(tables, planted, PARAMS, WANT, limits, seed=1)
                               if "error" in c["what"]}
    assert verdicts({"mash_dist": 1e-4, "ani": 1e-6, "coverage": 1e-3}) == {
        "distance": True, "ANI": False, "coverage": True}
    assert verdicts({"mash_dist": 1e-6, "ani": 1e-5, "coverage": 1e-4}) == {
        "distance": False, "ANI": True, "coverage": False}


def test_hiding_counters_fail_a_job():
    from benchmark import batch_jobs

    device = {"platform": "tpu", "kind": "TPU v5 lite", "count": 1}
    rec = {"platform": "tpu", "device_kind": "TPU v5 lite", "n_devices": 1,
           "secondary_paths": {"one_shot_clusterlocal": 1}}
    expect = {"primary_estimator_resolved": "sort", "secondary_path": "one_shot_clusterlocal"}
    assert batch_jobs.record_faults(rec, device, expect, "sort") == []
    assert batch_jobs.record_faults({**rec, "fault_tolerance": {"cpu_fallback_tiles": 2}}, device, expect, "sort")
    assert batch_jobs.record_faults({**rec, "platform": "cpu"}, device, expect, "sort")
    assert batch_jobs.record_faults(rec, device, expect, "streaming_sort")
    assert batch_jobs.record_faults({**rec, "secondary_paths": {"cpu_tiles": 1}}, device, expect, "sort")


def test_a_metric_is_read_only_in_the_cells_it_lists():
    assert cells.metrics_of({"per_layer": [{"name": "x", "workloads": ["c"]}]}, "d", "per_layer") == []
    assert cells.metrics_of({"per_layer": [{"name": "x"}]}, "d", "per_layer") == [{"name": "x"}]
