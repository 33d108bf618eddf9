"""TPC-H Q6, forecasting revenue change: the scalar SUM(quantity x
discount) over one year of shipments, with its cumulants rider.

Substitution parameters (TPC-H clause 2.4.6.3): DATE, January 1 of a year
in [1993, 1997], and the shipments of that year; DISCOUNT in [0.02, 0.09]
(the generator's whole percents 2..9; the predicate keeps DISCOUNT +- 1
percent); QUANTITY in [24, 25].
"""
import numpy as np

from bench.data import tpch_gen as G
from bench.harness import answers, compare
from bench.reference import tpch_ref as R

AGGS = dict(sum="normal", cumulants="cumulants")


def domain(opts: dict) -> dict:
    return dict(year=range(1993, 1998), discount=range(2, 10),
                quantity=range(24, 26))


def plan(opts: dict, scale: dict):
    from repro.db.plans import GroupAgg, Map, Parameterized, Scan, Select
    sel = Select(Scan("lineitem"), Parameterized(
        lambda t, lo, hi, dlo, dhi, lim: (t["l_shipdate"] >= lo)
        & (t["l_shipdate"] < hi)
        & (t["l_discount"] >= dlo) & (t["l_discount"] <= dhi)
        & (t["l_quantity"] < lim),
        ("date_lo", "date_hi", "disc_lo", "disc_hi", "qty_lim")))
    val = Map(sel, "q6_value", lambda t: t["l_quantity"] * t["l_discount"])
    return GroupAgg(val, (), "q6_value", "SUM", 1, "normal",
                    extra=(("cumulants", "q6_value", "SUM", "cumulants"),))


def _args(point) -> tuple:
    return (G.jan_1(point["year"]), G.jan_1(point["year"] + 1),
            point["discount"] - 1, point["discount"] + 1,
            point["quantity"])


def params(point: dict) -> dict:
    lo, hi, dlo, dhi, lim = _args(point)
    return dict(date_lo=np.int32(lo), date_hi=np.int32(hi),
                disc_lo=np.int32(dlo), disc_hi=np.int32(dhi),
                qty_lim=np.int32(lim))


def extract(out) -> dict:
    return answers.groupagg(out, (), AGGS)


def reference(h: dict, point: dict, opts: dict, dt=np.float64) -> dict:
    return R.q6(h, *_args(point), dt)


compare_fn = compare.groupagg


def work(ref: dict) -> list:
    """The cumulants rider of a scalar aggregate runs the scalar
    cumulants kernel."""
    w = ref["work"]
    return [("cumulants", dict(rows=w["rows"], groups=1, orders=8,
                               grouped=False))]
