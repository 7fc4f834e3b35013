"""Evaluate stage: warn about near-threshold cluster boundaries.

Reference parity: drep/d_evaluate.py (SURVEY.md §2; reference mount empty)
— defaults --warn_dist 0.25, --warn_sim 0.98, --warn_aln 0.25. Emits
`<wd>/log/warnings.txt` flagging (a) winner pairs whose primary (Mash)
distance is suspiciously close, (b) winner pairs in different secondary
clusters with high ANI, (c) secondary comparisons with low alignment
coverage — the clusters that might be over- or under-split.

The stage reads a pair table as columns (:class:`PairColumns`, ISSUE 35):
the distinct names once, each row's two names as integer codes into them,
the values as arrays. The three filters are integer and float comparisons
over whole columns, and the lines are assembled as bytes the way
`tablewriter` assembles a table's rows: each name encoded once, each
distinct value formatted once, the constant text of a message in place.
In the job that wrote the tables, `d_cluster_wrapper` leaves their columns
on the work directory (`WorkDirectory.hold`) and nothing is read back;
otherwise the tables are read from disk. Both write the same bytes.
"""

from __future__ import annotations

import io
import re
from dataclasses import dataclass
from typing import Any

import numpy as np
import pandas as pd

from drep_tpu.tablewriter import assemble_rows, distinct_floats, float_texts, plain
from drep_tpu.utils.ckptmeta import atomic_write_bytes
from drep_tpu.utils.logger import get_logger
from drep_tpu.utils.profiling import counters
from drep_tpu.workdir import WorkDirectory

EVALUATE_DEFAULTS: dict[str, Any] = {
    "warn_dist": 0.25,
    "warn_sim": 0.98,
    "warn_aln": 0.25,
}

# the columns of each pair table the stage reads: (first name, second name, values)
PAIR_TABLES: dict[str, tuple[str, str, tuple[str, ...]]] = {
    "Mdb": ("genome1", "genome2", ("dist",)),
    "Ndb": ("querry", "reference", ("ani", "alignment_coverage")),
}

# names `read_csv` would not hand back as the strings they are: what it takes
# for a number, a bool or a missing value (its documented defaults); beside
# them what `to_csv` quotes (`tablewriter.plain`) and blanks at either end
_READS_AS_NUMBER = re.compile(r"[+\-]?(inf|infinity|nan|true|false)|[+\-.\deE]*", re.IGNORECASE)
_READS_AS_MISSING = frozenset((
    "", "#N/A", "#N/A N/A", "#NA", "-1.#IND", "-1.#QNAN", "-NaN", "-nan", "1.#IND", "1.#QNAN",
    "<NA>", "N/A", "NA", "NULL", "NaN", "None", "n/a", "nan", "null",
))


def reads_back_as_text(name) -> bool:
    """Whether `name`, written to a table, comes back from `read_csv` as the
    same string whatever else its column holds."""
    return (
        plain(name)
        and name == name.strip()
        and name not in _READS_AS_MISSING
        and _READS_AS_NUMBER.fullmatch(name) is None
    )


@dataclass
class PairColumns:
    """A pair table as the stage reads it. `names` are the distinct names of
    both name columns; `first` and `second` are each row's codes into them
    (genome1 / genome2, querry / reference; -1 where the name is missing);
    `values` are the value columns as arrays. `held` says the values are
    those the job computed, not yet what a reader of the table sees
    (:func:`read_back`)."""

    names: pd.Index
    first: np.ndarray
    second: np.ndarray
    values: dict[str, np.ndarray]
    held: bool = False

    def __len__(self) -> int:
        return len(self.first)

    @classmethod
    def of(cls, df: pd.DataFrame, table: str, held: bool = False) -> "PairColumns":
        """The columns of the frame of `table`: as `get_db` read it, or
        `held` by the job that is storing it. Held columns share nothing
        with the frame, which can then go."""
        first, second, values = PAIR_TABLES[table]
        codes, names = pd.factorize(pd.concat([df[first], df[second]], ignore_index=True))
        codes = codes.astype(np.int32)
        n = len(df)
        return cls(names, codes[:n], codes[n:],
                   {v: np.array(df[v], copy=True) if held else df[v].to_numpy() for v in values}, held)

    def reads_back(self) -> bool:
        """Whether these held columns can stand for the table read back: no
        name that would come back as something else than the string it is,
        and float32 / float64 values, whose texts :func:`read_back` knows."""
        return (all(held.dtype in (np.float32, np.float64) for held in self.values.values())
                and all(map(reads_back_as_text, self.names.tolist())))


def read_back(held: np.ndarray) -> np.ndarray:
    """Held floats as `get_db` reads them from the table `store_db` wrote:
    what `read_csv` parses from each one's shortest text. That is not the
    float32 widened, and for one float64 in three not the float64 either
    (its fast parser is an ulp off), and a `.4f` can tell at a tie; each
    distinct value is rendered and parsed once."""
    distinct, codes = distinct_floats(held)
    if not len(distinct):
        return held.astype(np.float64)
    text = b"v\n" + b"\n".join(float_texts(distinct).tolist()) + b"\n"
    return pd.read_csv(io.BytesIO(text))["v"].to_numpy(np.float64)[codes]


def _between(values: np.ndarray, above, lo, hi) -> np.ndarray:
    """`above < values`, `lo <= values`, `values <= hi`: each where given."""
    ok = np.ones(len(values), bool)
    if above is not None:
        ok &= values > above
    if lo is not None:
        ok &= values >= lo
    if hi is not None:
        ok &= values <= hi
    return ok


def _loosened(bound, by: int):
    """`bound` moved `by` (-1 down, +1 up) further than reading a value back
    can move the value: half a float32 ulp and the parser's few float64
    ulps are under 1e-6 of it, an underflow under 1e-300."""
    if bound is None or not np.isfinite(bound):
        return bound
    return float(bound) + by * (abs(float(bound)) * 1e-6 + 1e-300)


def _passing(cols: PairColumns, keep: np.ndarray, value: str,
             above=None, lo=None, hi=None) -> tuple[np.ndarray, np.ndarray]:
    """(rows, values) of the rows among `keep` whose `value` is inside the
    bounds, the values as a reader of the table sees them. Held values are
    read back only where they are near enough to pass."""
    rows = np.flatnonzero(keep)
    values = cols.values[value][rows]
    if cols.held:
        with np.errstate(invalid="ignore"):  # a signalling NaN widened: it passes nothing either way
            wide = values.astype(np.float64)
        near = _between(wide, None, _loosened(lo if above is None else above, -1), _loosened(hi, +1))
        rows, values = rows[near], read_back(values[near])
    ok = _between(values, above, lo, hi)
    return rows[ok], values[ok]


def _warn(cols: PairColumns, passing: tuple[np.ndarray, np.ndarray], fmt: str,
          head: str, between: str, before_value: str, tail: str, out: list) -> tuple[int, int]:
    """The lines of one message for the (rows, values) `passing` of `cols`,
    in their order, appended to `out` as blocks of bytes: `head`, the first
    name, `between`, the second name, `before_value`, the value under
    `fmt`, `tail`. A name is encoded once and a distinct value formatted
    once, however many rows hold them. Returns (lines, texts rendered)."""
    rows, values = passing
    if not len(rows):
        return 0, 0
    first, second = cols.first[rows], cols.second[rows]
    used = np.zeros(len(cols.names), bool)
    used[first] = used[second] = True
    names = [str(name).encode() for name in cols.names[used].tolist()]
    if any(b"\x00" in name for name in names):
        raise ValueError("a genome name holds a NUL byte")
    name_texts = np.array(names, dtype="S")
    place = np.cumsum(used) - 1  # a name's code among the names used
    if values.dtype not in (np.float32, np.float64):
        values = values.astype(np.float64)
    distinct, codes = distinct_floats(values)
    # widened first: a row's own f-string formats a float32 as a Python float
    texts = np.array([(fmt % v).encode() for v in distinct.astype(np.float64).tolist()], dtype="S")
    parts = [head.encode(), (name_texts, place[first]), between.encode(), (name_texts, place[second]),
             before_value.encode(), (texts, codes), (tail + "\n").encode()]
    assemble_rows(parts, len(rows), out.append)
    return len(rows), len(names) + len(texts)


def _ordered(cols: PairColumns) -> np.ndarray:
    """Rows whose first name sorts before their second: the names ranked in
    their own order once, the rows compared by rank. A missing name is in no
    order with another."""
    rank = np.empty(len(cols.names), np.int64)
    rank[cols.names.argsort()] = np.arange(len(cols.names))
    return (cols.first >= 0) & (cols.second >= 0) & (rank[cols.first] < rank[cols.second])


def _cluster_codes(names: pd.Index, cluster_of: pd.Series) -> np.ndarray:
    """A code a name for its secondary cluster. A name the Cdb lacks gets a
    code of its own: it is in another cluster than anything, as NaN != NaN."""
    codes, _ = pd.factorize(pd.Series(names).map(cluster_of))
    lacking = np.flatnonzero(codes < 0)
    codes[lacking] = -1 - lacking
    return codes


def warning_blocks(mdb: PairColumns | None, ndb: PairColumns | None, cdb: pd.DataFrame,
                   wdb: pd.DataFrame, **kwargs) -> tuple[list, int]:
    """`warnings.txt` as blocks of bytes, and its lines: Primary, then
    Secondary, then Coverage, each in its table's row order. Booked in the
    record's `evaluate`."""
    kw = dict(EVALUATE_DEFAULTS)
    kw.update({k: v for k, v in kwargs.items() if v is not None and k in EVALUATE_DEFAULTS})
    winners = set(wdb["genome"])
    cluster_of = cdb.set_index("genome")["secondary_cluster"]
    out: list = []
    done = {"primary": (0, 0), "secondary": (0, 0), "coverage": (0, 0)}  # kind: (lines, texts)

    # every filter is a comparison over whole columns, of codes or of
    # values; only the distinct names and values of surviving rows are
    # rendered. (Per-row loops here walked the FULL Mdb/Ndb: millions of
    # Python iterations at 100k genomes.) A table whose names are all
    # missing has no row in order
    if mdb is not None and len(mdb.names):
        winner = mdb.names.isin(winners)
        pairs = _ordered(mdb) & winner[mdb.first] & winner[mdb.second]
        done["primary"] = _warn(
            mdb, _passing(mdb, pairs, "dist", hi=kw["warn_dist"]), "%.4f",
            "Primary: winners ", " and ", " have Mash distance ",
            f" (<= warn_dist {kw['warn_dist']})", out)

    if ndb is not None and len(ndb.names):
        ordered = _ordered(ndb)
        winner = ndb.names.isin(winners)
        cluster = _cluster_codes(ndb.names, cluster_of)
        split = (
            ordered & winner[ndb.first] & winner[ndb.second]
            & (cluster[ndb.first] != cluster[ndb.second])
        )
        done["secondary"] = _warn(
            ndb, _passing(ndb, split, "ani", lo=kw["warn_sim"]), "%.4f",
            "Secondary: winners ", " and ", " are in different secondary clusters but have ANI ",
            f" (>= warn_sim {kw['warn_sim']})", out)
        done["coverage"] = _warn(
            ndb, _passing(ndb, ordered, "alignment_coverage", above=0, hi=kw["warn_aln"]), "%.3f",
            "Coverage: ", " vs ", " aligned only ", f" (<= warn_aln {kw['warn_aln']})", out)
    lines = {kind: n for kind, (n, _) in done.items()}
    counters.add_evaluate_warnings(
        lines, sum(len(block) for block in out), sum(texts for _, texts in done.values()))
    return out, sum(lines.values())


def evaluate_warnings(
    mdb: pd.DataFrame | None,
    ndb: pd.DataFrame | None,
    cdb: pd.DataFrame,
    wdb: pd.DataFrame,
    **kwargs,
) -> list[str]:
    """The warnings of the four frames, a string a line."""
    blocks, _ = warning_blocks(
        None if mdb is None else PairColumns.of(mdb, "Mdb"),
        None if ndb is None else PairColumns.of(ndb, "Ndb"),
        cdb, wdb, **kwargs,
    )
    return b"".join(blocks).decode().split("\n")[:-1]


def make_widb(wdb: pd.DataFrame, cdb: pd.DataFrame, stats: pd.DataFrame | None, quality: pd.DataFrame | None) -> pd.DataFrame:
    """Winner-information table (upstream d_evaluate's Widb): one row per
    winner with its cluster and available stats/quality columns."""
    widb = wdb.merge(cdb[["genome", "primary_cluster", "secondary_cluster"]], on="genome", how="left")
    if stats is not None:
        widb = widb.merge(stats[["genome", "length", "N50"]], on="genome", how="left")
    if quality is not None:
        cols = [c for c in ("genome", "completeness", "contamination", "strain_heterogeneity") if c in quality.columns]
        widb = widb.merge(quality[cols], on="genome", how="left")
    return widb


def _pair_table(wd: WorkDirectory, table: str) -> tuple[PairColumns | None, str | None]:
    """(columns, source) of a pair table: what this process holds of it from
    the stage that wrote it (`"job"`), else the file read back (`"disk"`),
    else nothing."""
    cols, source = wd.take_held(table), "job"
    if cols is None or not cols.reads_back():
        if not wd.hasDb(table):
            return None, None
        cols, source = PairColumns.of(wd.get_db(table), table), "disk"
    counters.add_evaluate_table(table.lower(), source, len(cols))
    return cols, source


def d_evaluate_wrapper(wd: WorkDirectory, **kwargs) -> int:
    """Write Widb (where there are winners) and `log/warnings.txt`; returns
    the number of warnings."""
    logger = get_logger()
    with counters.span("evaluate/tables") as tables:
        # what the job held goes with these two names when the stage ends
        mdb, mdb_source = _pair_table(wd, "Mdb")
        ndb, ndb_source = _pair_table(wd, "Ndb")
        tables.note(source="disk" if "disk" in (mdb_source, ndb_source) else "job")
        cdb = wd.get_db("Cdb")
        has_wdb = wd.hasDb("Wdb")
        wdb = wd.get_db("Wdb") if has_wdb else pd.DataFrame({"genome": cdb["genome"]})
        if has_wdb:
            stats = wd.get_db("genomeInformation") if wd.hasDb("genomeInformation") else None
            quality = wd.get_db("genomeInfo") if wd.hasDb("genomeInfo") else None
            wd.store_db(make_widb(wdb, cdb, stats, quality), "Widb")

    with counters.span("evaluate/warnings", winners=len(wdb)) as written:
        blocks, lines = warning_blocks(mdb, ndb, cdb, wdb, **kwargs)
        data = b"".join(blocks)
        path = wd.get_loc("warnings")
        # atomic (utils/durableio.py): a SIGKILL mid-write must not leave a
        # torn warnings.txt a resumed run trusts as the stage's full output
        atomic_write_bytes(path, data)
        written.note(lines=lines, bytes=len(data))
    logger.info("evaluate: %d warnings -> %s", lines, path)
    return lines
