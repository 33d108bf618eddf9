"""TPC-H Q3, shipping priority: revenue per order of one market segment,
in the paper's aggregate mode (Normal SUM and a cumulants rider).

Substitution parameters (TPC-H clause 2.4.3.3): SEGMENT, one of the five
market segments (the generator's c_mktsegment 0..4), and DATE, a day in
[1995-03-01, 1995-03-31].

The group table is sized from the configuration, not from the data, so
that every seed compiles the same programs.
"""
import numpy as np

from bench.data import tpch_gen as G
from bench.harness import answers, compare
from bench.reference import tpch_ref as R

MAR_1_1995 = 9190             # days since 1970-01-01
AGGS = dict(sum="normal", cumulants="cumulants")


def domain(opts: dict) -> dict:
    return dict(segment=range(5), day=range(1, 32))


def max_groups(scale: dict) -> int:
    """More orders than any (SEGMENT, DATE) keeps, for any seed.  An
    order survives when its customer is of the segment (1/5 of the
    orders), it is dated d >= 1 days before DATE (order dates are uniform
    over 2,406 days) and one of its L lines ships more than d days after
    it (each ships 1..121 days after, so 1 - (d / 121) ** L, L uniform
    in 1..7).  The expectation, 11,322 at SF1, with a fifth more, rounded
    up to a whole 1,024: 14,336.  (Seeds 1, 3 and 2**31 + 52 read 11,104
    to 11,713 over all 155 points.)"""
    days = G.ENDDATE - 151 - G.STARTDATE + 1
    reach = sum(1 - np.mean((d / 121) ** G.LINES) for d in range(1, 121))
    n = 1.2 * scale["n_orders"] / days * reach / G.N_SEGMENTS
    return int(-(-n // 1024) * 1024)


def plan(opts: dict, scale: dict):
    from repro.db.plans import (FKJoin, GroupAgg, Parameterized, Scan,
                                Select)
    cust = Select(Scan("customer"), Parameterized(
        lambda t, seg: t["c_mktsegment"] == seg, ("segment",)))
    orders = Select(Scan("orders"), Parameterized(
        lambda t, date: t["o_orderdate"] < date, ("date",)))
    o = FKJoin(orders, cust, "o_custkey", "c_custkey", ("c_mktsegment",))
    li = Select(Scan("lineitem"), Parameterized(
        lambda t, date: t["l_shipdate"] > date, ("date",)))
    j = FKJoin(li, o, "l_orderkey", "o_orderkey",
               ("o_orderdate", "o_custkey"),
               gather_budget=opts.get("order_join_budget"))
    return GroupAgg(j, ("l_orderkey",), "l_extendedprice", "SUM",
                    max_groups(scale), "normal",
                    extra=(("cumulants", "l_extendedprice", "SUM",
                            "cumulants"),))


def _date(point) -> int:
    return MAR_1_1995 + point["day"] - 1


def params(point: dict) -> dict:
    return dict(segment=np.int32(point["segment"]),
                date=np.int32(_date(point)))


def extract(out) -> dict:
    return answers.groupagg(out, ("l_orderkey",), AGGS)


def reference(h: dict, point: dict, opts: dict, dt=np.float64) -> dict:
    return R.q3(h, point["segment"], _date(point), dt)


compare_fn = compare.groupagg


def work(ref: dict) -> list:
    w = ref["work"]
    return [("cumulants", dict(rows=w["rows"], groups=w["groups"], orders=8,
                               grouped=True))]
