"""TPC-H Q1, pricing summary report, in the paper's aggregate mode.

Substitution parameter (TPC-H clause 2.4.1.3): DELTA, a whole number of
days in [60, 120]; the cut-off is 1998-12-01 - DELTA.
"""
import numpy as np

from bench.harness import answers, compare
from bench.reference import tpch_ref as R

DEC_1_1998 = 10561            # days since 1970-01-01
AGGS = dict(sum="normal", price="normal", count="normal",
            cumulants_qty="cumulants")


def domain(opts: dict) -> dict:
    return dict(delta=range(60, 121))


def plan(opts: dict, scale: dict):
    from repro.db.plans import GroupAgg, Parameterized, Scan, Select
    sel = Select(Scan("lineitem"), Parameterized(
        lambda t, cutoff: t["l_shipdate"] <= cutoff, ("cutoff",)))
    return GroupAgg(sel, ("l_returnflag", "l_linestatus"), "l_quantity",
                    "SUM", 8, "normal",
                    extra=(("price", "l_extendedprice", "SUM", "normal"),
                           ("count", "", "COUNT", "normal"),
                           ("cumulants_qty", "l_quantity", "SUM",
                            "cumulants")))


def _cutoff(point) -> int:
    return DEC_1_1998 - point["delta"]


def params(point: dict) -> dict:
    return dict(cutoff=np.int32(_cutoff(point)))


def extract(out) -> dict:
    return answers.groupagg(out, ("l_returnflag", "l_linestatus"), AGGS)


def reference(h: dict, point: dict, opts: dict, dt=np.float64) -> dict:
    return R.q1(h, _cutoff(point), dt)


compare_fn = compare.groupagg


def work(ref: dict) -> list:
    """The cumulants rider runs the grouped cumulants kernel."""
    w = ref["work"]
    return [("cumulants", dict(rows=w["rows"], groups=w["groups"], orders=8,
                               grouped=True))]
