"""Planted index: a release kept as an index and the batch that grows it,
both cut out of ONE planted release (``planted_release.py``, called and not
edited), in which THE SEED DRAWS THE HASH VALUES AND NOTHING ELSE.

The configuration's `clusters` table is the table of the UNION: for each size
of primary cluster how many there are and the sizes of the secondary groups
inside one, as ``planted_release.py`` reads it, and beside them `new`: how
many genomes of each group arrive with the batch (absent: none). What is left
of the table when the batch is taken out is the index's own table
(``old_table``). A joiner is a member of its group's tree like any other,
laid out by the release generator's own recipe, so every margin of the
release holds for the union, for the index and for the batch alike; WHICH
members of a group are the joiners is one more draw of the layout, made here
from `layout_seed` and the group's number alone. A cluster whose groups are
new whole is founded inside the batch; one that loses every member but one
to the batch is a singleton of the index that the batch joins.

The union comes back with the index's genomes first, in the release's
scattered order, and the batch after them, which is the order an `index
update` leaves the store in; names stay those of the union's slots.

``prepare`` writes what the cell's set-up needs: the planted work directory
of the index's genomes (``planted_release.write_workdir``: `compare <wd>`
clusters it, `index build --work_directory <wd>` snapshots it) and the batch
as the program's own sketch hand-off (``federation.write_params_handoff``,
the file `index update --params_file` reads), written once the build has
said which parameters the index pins (``write_batch``).

Importing this module imports neither jax nor the program; the writers use
the program's own, because a work directory and a hand-off in the program's
formats are the program's inputs.
"""

from __future__ import annotations

import os
from dataclasses import dataclass

import numpy as np

_HERE = os.path.dirname(os.path.abspath(__file__))


def _release():
    """``planted_release.py`` as a module, found beside this file."""
    import importlib.util
    import sys

    name = "bench_planted_release"
    if name not in sys.modules:
        spec = importlib.util.spec_from_file_location(name, os.path.join(_HERE, "planted_release.py"))
        mod = importlib.util.module_from_spec(spec)
        sys.modules[name] = mod
        spec.loader.exec_module(mod)
    return sys.modules[name]


@dataclass
class PlantedIndex:
    union: object  # PlantedRelease of old + batch, the index's genomes first
    n_old: int
    is_new: np.ndarray  # [n] bool over the union's order: False * n_old, then True * K

    @property
    def names(self) -> list[str]:
        return self.union.names

    def old(self):
        """The index's genomes alone, as a PlantedRelease."""
        return take(self.union, np.arange(self.n_old))


def take(data, rows: np.ndarray):
    """The genomes `rows` of a PlantedRelease, in that order."""
    rows = np.asarray(rows, np.int64)
    return type(data)(
        names=[data.names[i] for i in rows], bottom=[data.bottom[i] for i in rows],
        scaled=[data.scaled[i] for i in rows], primary_labels=data.primary_labels[rows],
        labels=data.labels[rows], length=data.length[rows], n_kmers=data.n_kmers[rows],
        k=data.k, s_bottom=data.s_bottom)


def _entries(params: dict):
    """The table row of every cluster, in the order the release generator
    lays them out."""
    for entry in params["clusters"]:
        for _ in range(int(entry["count"])):
            yield entry


def old_table(params: dict) -> list[dict]:
    """The index's own table: the union's with the batch taken out, rows of
    equal shape added up, largest first; the shape of
    ``gtdb_release_6k.json``'s `clusters`."""
    counted: dict[tuple, int] = {}
    for entry in params["clusters"]:
        new = entry.get("new") or [0] * len(entry["groups"])
        groups = tuple(g - k for g, k in zip(entry["groups"], new) if g - k)
        if groups:
            counted[groups] = counted.get(groups, 0) + int(entry["count"])
    rows = sorted(counted.items(), key=lambda kv: (-sum(kv[0]), [-g for g in kv[0]]))
    return [{"size": sum(g), "count": c, "groups": list(g)} for g, c in rows]


def batch_slots(params: dict, laid) -> np.ndarray:
    """[n] bool over the union's slots: which genomes arrive with the batch.
    From the table, the layout and `layout_seed`; no seed of a run reaches
    it. Groups are numbered as ``planted_release.plan`` numbers them."""
    is_new = np.zeros(len(laid.group), bool)
    by_group = np.argsort(laid.group, kind="stable")
    starts = np.searchsorted(laid.group[by_group], np.arange(int(laid.group.max()) + 2))
    number = 0
    for entry in _entries(params):
        new = entry.get("new") or [0] * len(entry["groups"])
        if len(new) != len(entry["groups"]) or any(k > g for k, g in zip(new, entry["groups"])):
            raise ValueError(f"`new` does not fit `groups` in {entry}")
        for k in new:
            members = by_group[starts[number]:starts[number + 1]]
            if k:
                rng = np.random.default_rng([int(params["layout_seed"]), number])
                is_new[rng.choice(members, size=int(k), replace=False)] = True
            number += 1
    return is_new


def generate(params: dict, seed: int) -> PlantedIndex:
    """The union of ``planted_release.generate(params, seed)``, the index's
    genomes first."""
    rel = _release()
    is_new = batch_slots(params, rel.plan(params))
    if int(is_new.sum()) != int(params["k_batch"]):
        raise ValueError(f"the table plants {int(is_new.sum())} new genomes, k_batch says {params['k_batch']}")
    union = rel.generate(params, seed)
    order = np.concatenate([np.flatnonzero(~is_new), np.flatnonzero(is_new)])
    n_old = int((~is_new).sum())
    return PlantedIndex(union=take(union, order), n_old=n_old,
                        is_new=np.arange(len(order)) >= n_old)


def write_batch(data: PlantedIndex, path: str, index_params: dict) -> None:
    """The batch as `index update --params_file` reads it: its sketches and
    stats as ``sketch_batch`` would give them, under the parameters the built
    index pins (a hand-off that pins others is refused)."""
    import pandas as pd

    from drep_tpu.index.federation import write_params_handoff

    u, rows = data.union, range(data.n_old, len(data.union.names))
    batch = pd.DataFrame({"genome": [u.names[i] for i in rows],
                          "location": [f"/nonexistent/{u.names[i]}" for i in rows]})
    contigs = _release().CONTIGS
    results = {u.names[i]: {"bottom": u.bottom[i], "scaled": u.scaled[i], "length": int(u.length[i]),
                            "N50": 50_000, "contigs": contigs, "n_kmers": int(u.n_kmers[i])}
               for i in rows}
    write_params_handoff(path, index_params, batch, results)


def prepare(cfg: dict, seed: int, out_dir: str) -> dict:
    """The planted work directory of the index's genomes under `out_dir`, and
    the planted data; the batch file is written by ``write_batch`` once the
    index is built."""
    data = generate(cfg["data"], seed)
    wd = os.path.join(out_dir, "pristine_wd")
    _release().write_workdir(data.old(), wd, cfg["data"])
    return {"workdir": wd, "data": data}
