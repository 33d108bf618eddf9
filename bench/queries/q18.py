"""TPC-H Q18, large volume customer, as the paper's reweight (Table I
row III): every order kept with p = P(order non-empty) x
P(SUM(l_quantity) > QUANTITY) under the Normal approximation.

Substitution parameter (TPC-H clause 2.4.18.3): QUANTITY in [312, 315],
the threshold.
"""
import numpy as np

from bench.harness import answers, compare
from bench.reference import tpch_ref as R


def domain(opts: dict) -> dict:
    return dict(quantity=range(312, 316))


def plan(opts: dict, scale: dict):
    from repro.db.plans import Param, ReweightGreater, Scan
    return ReweightGreater(Scan("lineitem"), ("l_orderkey",), "l_quantity",
                           "", scale["n_orders"],
                           threshold=Param("qty_threshold"))


def _threshold(point) -> float:
    return float(point["quantity"])


def params(point: dict) -> dict:
    return dict(qty_threshold=np.float32(_threshold(point)))


def extract(out) -> dict:
    return answers.table(out, "l_orderkey")


def reference(h: dict, point: dict, opts: dict, dt=np.float64) -> dict:
    return R.q18(h, _threshold(point), dt)


compare_fn = compare.table


def work(ref: dict) -> list:
    """The reweight's Normal sums run as XLA ops, no kernel."""
    return []
