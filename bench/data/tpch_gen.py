"""TPC-H tables with a tuple probability, from a seed, as numpy columns.

The columns the benchmark's queries read, drawn by the rules of TPC-H
v3.0.1 clause 4.2.3, plus the paper's (arXiv:1307.0844 sec. VIII)
uniform-random probability ``p`` per tuple:

* ``part``: the colour of P_NAME's first word (one of 92), and
  P_RETAILPRICE by the clause's formula;
* ``partsupp``: 4 rows a part, supplier ``(part + i (S/4 + part/S)) mod
  S``, PS_AVAILQTY in [1, 9999];
* ``supplier``, ``customer``: nation in [0, 24], C_MKTSEGMENT one of 5;
* ``orders``: O_CUSTKEY among the customers whose (1-based) key is not a
  multiple of 3, O_ORDERDATE uniform in [STARTDATE, ENDDATE - 151];
* ``lineitem``: 1 to 7 lines an order; L_PARTKEY uniform, L_SUPPKEY one
  of the part's 4 suppliers; L_QUANTITY in [1, 50]; L_EXTENDEDPRICE =
  L_QUANTITY x P_RETAILPRICE; L_DISCOUNT in [0, 10] percent; L_SHIPDATE =
  O_ORDERDATE + [1, 121]; L_RECEIPTDATE = L_SHIPDATE + [1, 30];
  L_RETURNFLAG R or A at random where L_RECEIPTDATE <= CURRENTDATE, else
  N; L_LINESTATUS O where L_SHIPDATE > CURRENTDATE, else F.

Representation, not shape: keys are dense and 0-based (a relabelling of
TPC-H's keys that every join and grouping keeps); partsupp is keyed by
its row, ``4 part + i``, and each lineitem carries that key
(``l_pskey``); dates are days since 1970-01-01; flags are small integers
(returnflag A 0, N 1, R 2; linestatus F 0, O 1); L_EXTENDEDPRICE is in
whole units of ``price_unit_cents`` (rounded).  The lines of the orders
are a balanced set of the counts 1 to 7 (each a seventh of the orders, a
remainder at 4) in a seeded order, so every seed makes exactly 4 lines
an order.

Returns ``{table: {column: int64 array, "p": float64 array}}``.
"""
from __future__ import annotations

import numpy as np

STARTDATE = 8035            # 1992-01-01
CURRENTDATE = 9298          # 1995-06-17
ENDDATE = 10591             # 1998-12-31
N_COLORS = 92               # clause 4.2.2.13's P_NAME words
N_SEGMENTS = 5
LINES = np.arange(1, 8)     # lines an order


def jan_1(year: int) -> int:
    """January 1 of ``year`` in days since 1970-01-01."""
    return int(np.datetime64(f"{year}-01-01", "D").astype(np.int64))


def retail_price_cents(partkey):
    """P_RETAILPRICE in cents of 0-based part keys (clause 4.2.3)."""
    k = np.asarray(partkey, np.int64) + 1
    return 90000 + (k // 10) % 20001 + 100 * (k % 1000)


def part_suppliers(partkey, slot, n_suppliers: int):
    """The supplier of slot ``slot`` (0..3) of 0-based parts (clause
    4.2.3's PS_SUPPKEY, 0-based)."""
    k = np.asarray(partkey, np.int64)
    s = n_suppliers
    return (k + 1 + slot * (s // 4 + k // s)) % s


def line_counts(n_orders: int, rng) -> np.ndarray:
    """Lines of each order: each of 1..7 for a seventh of the orders, the
    remainder 4, in a seeded order."""
    base = n_orders // len(LINES)
    counts = np.concatenate([np.repeat(LINES, base),
                             np.full(n_orders - base * len(LINES), 4)])
    return rng.permutation(counts)


def generate(n_orders: int, n_parts: int, n_suppliers: int,
             n_customers: int, n_nations: int = 25,
             price_unit_cents: int = 1, seed: int = 0) -> dict:
    if n_suppliers < 4:
        raise ValueError("a part has 4 distinct suppliers: n_suppliers >= 4, "
                         f"got {n_suppliers}")
    rng = np.random.default_rng(seed)

    def probs(n):
        return rng.uniform(0.0, 1.0, n)

    def integers(lo, hi, n):            # inclusive, as the clause states
        return rng.integers(lo, hi + 1, n)

    out = {}
    out["nation"] = dict(n_nationkey=np.arange(n_nations),
                         n_name=np.arange(n_nations), p=probs(n_nations))
    out["supplier"] = dict(
        s_suppkey=np.arange(n_suppliers),
        s_nationkey=integers(0, n_nations - 1, n_suppliers),
        s_name=np.arange(n_suppliers), s_address=np.arange(n_suppliers),
        p=probs(n_suppliers))
    out["part"] = dict(p_partkey=np.arange(n_parts),
                       p_color=integers(0, N_COLORS - 1, n_parts),
                       p=probs(n_parts))
    ps_part = np.repeat(np.arange(n_parts), 4)
    ps_slot = np.tile(np.arange(4), n_parts)
    ps_supp = part_suppliers(ps_part, ps_slot, n_suppliers)
    slots = np.sort(ps_supp.reshape(-1, 4), axis=1)
    if np.any(slots[:, 1:] == slots[:, :-1]):
        raise ValueError("the clause's supplier formula repeats a supplier "
                         f"of a part at {n_parts} parts, {n_suppliers} "
                         "suppliers (TPC-H keeps parts = 20 suppliers)")
    out["partsupp"] = dict(
        ps_partkey=ps_part,
        ps_suppkey=ps_supp,
        ps_availqty=integers(1, 9999, n_parts * 4),
        ps_pskey=np.arange(n_parts * 4), p=probs(n_parts * 4))
    out["customer"] = dict(
        c_custkey=np.arange(n_customers),
        c_mktsegment=integers(0, N_SEGMENTS - 1, n_customers),
        p=probs(n_customers))
    # the k-th customer whose 1-based key is not a multiple of 3
    k = integers(0, n_customers - n_customers // 3 - 1, n_orders)
    orderdate = integers(STARTDATE, ENDDATE - 151, n_orders)
    out["orders"] = dict(o_orderkey=np.arange(n_orders),
                         o_custkey=(k // 2) * 3 + k % 2,
                         o_orderdate=orderdate, p=probs(n_orders))

    lines = line_counts(n_orders, rng)
    l_order = np.repeat(np.arange(n_orders), lines)
    n = l_order.size
    l_part = integers(0, n_parts - 1, n)
    l_slot = integers(0, 3, n)
    qty = integers(1, 50, n)
    ship = orderdate[l_order] + integers(1, 121, n)
    receipt = ship + integers(1, 30, n)
    returned = integers(0, 1, n)            # R or A, where received
    price = qty * retail_price_cents(l_part)
    out["lineitem"] = dict(
        l_orderkey=l_order, l_partkey=l_part,
        l_suppkey=part_suppliers(l_part, l_slot, n_suppliers),
        l_pskey=l_part * 4 + l_slot,
        l_quantity=qty,
        l_extendedprice=(price + price_unit_cents // 2) // price_unit_cents,
        l_discount=integers(0, 10, n),
        l_shipdate=ship,
        l_returnflag=np.where(receipt <= CURRENTDATE, 2 * returned, 1),
        l_linestatus=(ship > CURRENTDATE).astype(np.int64),
        p=probs(n))
    return out
