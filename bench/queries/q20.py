"""TPC-H Q20, potential part promotion, as the paper's Fig. 6 plan:
suppliers of one nation with excess stock of parts of one colour.

Substitution parameters (TPC-H clause 2.4.20.3): COLOR, one of the 92
P_NAME words (the generator's p_color, the first word of P_NAME); DATE,
January 1 of a year in [1993, 1997], and the shipments of that year;
NATION, one of the 25 nations.  The excess-stock test keeps the
program's serving form: SUM(l_quantity) > ps_availqty x 0.05, reweighted.

The answer table holds the most suppliers one nation can have: nations
are drawn uniformly, so a nation's count is Binomial(S, 1/25); the
table takes a third more than S/25, rounded up to a whole 64: 576 at
S = 10,000, 9 standard deviations above the mean.
"""
import numpy as np

from bench.data import tpch_gen as G
from bench.harness import answers, compare
from bench.reference import tpch_ref as R

AVAIL_FRAC = 0.05


def domain(opts: dict) -> dict:
    return dict(color=range(G.N_COLORS), year=range(1993, 1998),
                nation=range(25))


def max_suppliers(scale: dict) -> int:
    n = scale["n_suppliers"] / scale["n_nations"] * 4 / 3
    return int(-(-n // 64) * 64)


def plan(opts: dict, scale: dict):
    from repro.db.plans import (FKJoin, Map, Parameterized, Project,
                                ReweightGreater, Scan, Select)
    r1 = Select(Scan("part"), Parameterized(
        lambda t, color: t["p_color"] == color, ("color",)))
    r2 = FKJoin(Scan("partsupp"), r1, "ps_partkey", "p_partkey",
                ("p_color",))
    r3 = Select(Scan("lineitem"), Parameterized(
        lambda t, lo, hi: (t["l_shipdate"] >= lo) & (t["l_shipdate"] < hi),
        ("date_lo", "date_hi")))
    r4 = FKJoin(r3, r2, "l_pskey", "ps_pskey",
                ("ps_availqty", "ps_suppkey", "ps_pskey"))
    r4t = Map(r4, "q20_thresh",
              lambda t: t["ps_availqty"].astype(t.prob.dtype) * AVAIL_FRAC)
    r7 = ReweightGreater(r4t, ("ps_pskey",), "l_quantity", "q20_thresh",
                         scale["n_parts"] * 4, carry_cols=("ps_suppkey",))
    nat = Select(Scan("nation"), Parameterized(
        lambda t, n: t["n_name"] == n, ("nation",)))
    r9 = FKJoin(Scan("supplier"), nat, "s_nationkey", "n_nationkey",
                ("n_name",))
    r10 = FKJoin(r7, r9, "ps_suppkey", "s_suppkey", ("s_name", "s_address"))
    return Project(r10, ("s_name",), max_suppliers(scale))


def params(point: dict) -> dict:
    return dict(color=np.int32(point["color"]),
                date_lo=np.int32(G.jan_1(point["year"])),
                date_hi=np.int32(G.jan_1(point["year"] + 1)),
                nation=np.int32(point["nation"]))


def extract(out) -> dict:
    return answers.table(out, "s_name")


def reference(h: dict, point: dict, opts: dict, dt=np.float64) -> dict:
    return R.q20(h, point["color"], point["nation"], G.jan_1(point["year"]),
                 G.jan_1(point["year"] + 1), AVAIL_FRAC, dt)


compare_fn = compare.table


def work(ref: dict) -> list:
    return []
