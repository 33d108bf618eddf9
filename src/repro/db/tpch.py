"""Synthetic TPC-H-like probabilistic workload (paper §VIII).

The paper evaluates on TPC-H with an added uniform-random `p` column per
relation ("a randomly selected number between 0.0 and 1.0").  We reproduce
the schema subset its queries touch, a size-parameterised generator (scale
factor ~ rows, CPU-feasible), and the probabilistic query variants in the
paper's four modes:

    deterministic      the plain query (p ignored)
    confidence         P(result non-empty)        = AtLeastOne over the result
    group_confidence   P(group non-empty) per group
    aggregate          full PGF aggregate distribution per group
                       (exact log-CF / Normal / moment-based, §V)

Queries: Q1, Q3, Q6, Q18 and the paper's worked example Q20 (Fig. 6).
Every probabilistic mode is expressed as a `Plan` DAG and executed through
``compile_plan`` — pass ``mesh=`` to any query and the same plan runs the
WHOLE pipeline sharded (scans, selects, FK joins, group-id assignment and
aggregation all consume row-partitioned shard-local tables inside one
shard_map; see db/plans.py), with results BIT-IDENTICAL to the
single-device compile and O(rows / shards) per-device memory.  This is how
the TPC-H benchmarks exercise the planner end-to-end on one device and on
a pod.  Dates are day numbers (int), prices/quantities integers — the
paper's own integer-grid restriction (§V-C.2).
"""
from __future__ import annotations

import dataclasses
from typing import Dict

import jax
import jax.numpy as jnp
import numpy as np

from . import operators as ops
from .plans import (FKJoin, GroupAgg, Map, Param, Parameterized, Project,
                    ReweightGreater, Scan, Select, compile_plan)
from .table import Table

DAY0_1995 = 9131          # days since epoch-ish origin for synthetic dates


@jax.tree_util.register_pytree_node_class
@dataclasses.dataclass
class TPCH:
    """A scale-parameterised synthetic TPC-H instance with p columns."""

    lineitem: Table
    orders: Table
    customer: Table
    part: Table
    partsupp: Table
    supplier: Table
    nation: Table
    scale: dict

    _TABLES = ("lineitem", "orders", "customer", "part", "partsupp",
               "supplier", "nation")

    def tree_flatten(self):
        return (tuple(getattr(self, t) for t in self._TABLES),
                (tuple(sorted(self.scale.items())),))

    @classmethod
    def tree_unflatten(cls, aux, children):
        return cls(*children, scale=dict(aux[0]))

    def tables(self) -> Dict[str, Table]:
        """The plan-compiler catalogue view: name -> Table."""
        return {t: getattr(self, t) for t in self._TABLES}


def generate(n_orders: int = 2000, lines_per_order: int = 4,
             n_parts: int = 200, n_suppliers: int = 50,
             n_customers: int = 300, n_nations: int = 25,
             seed: int = 0, prob_mode: str = "uniform") -> TPCH:
    rng = np.random.default_rng(seed)
    n_lineitem = n_orders * lines_per_order
    if n_suppliers < 4:
        raise ValueError(
            f"generate() needs n_suppliers >= 4 (got {n_suppliers}): the "
            "partsupp schema keys 4 DISTINCT suppliers per part")
    n_partsupp = n_parts * 4

    def probs(n):
        if prob_mode == "uniform":
            return rng.uniform(0.0, 1.0, n).astype(np.float64)
        return np.ones(n)

    nation = Table.from_columns({
        "n_nationkey": jnp.arange(n_nations),
        "n_name": jnp.arange(n_nations),          # name id; 3 == "CANADA"
    }, prob=jnp.asarray(probs(n_nations)))

    supplier = Table.from_columns({
        "s_suppkey": jnp.arange(n_suppliers),
        "s_nationkey": jnp.asarray(rng.integers(0, n_nations, n_suppliers)),
        "s_name": jnp.arange(n_suppliers),
        "s_address": jnp.arange(n_suppliers),
    }, prob=jnp.asarray(probs(n_suppliers)))

    part = Table.from_columns({
        "p_partkey": jnp.arange(n_parts),
        "p_name_forest": jnp.asarray(rng.uniform(0, 1, n_parts) < 0.1),
        "p_retailprice": jnp.asarray(rng.integers(100, 2000, n_parts)),
    }, prob=jnp.asarray(probs(n_parts)))

    ps_part = np.repeat(np.arange(n_parts), 4)
    # 4 DISTINCT suppliers per part: ps_pskey is an FK-join build key, and
    # fk_join's many-to-one contract rejects duplicate valid build keys (a
    # duplicate would silently drop one world's probability mass).
    ps_supp = np.argsort(rng.random((n_parts, n_suppliers)),
                         axis=1)[:, :4].reshape(-1)
    partsupp = Table.from_columns({
        "ps_partkey": jnp.asarray(ps_part),
        "ps_suppkey": jnp.asarray(ps_supp),
        "ps_availqty": jnp.asarray(rng.integers(1, 1000, n_partsupp)),
        "ps_pskey": jnp.asarray(ps_part * (1 << 10) + ps_supp),
    }, prob=jnp.asarray(probs(n_partsupp)))

    customer = Table.from_columns({
        "c_custkey": jnp.arange(n_customers),
        "c_mktsegment": jnp.asarray(rng.integers(0, 5, n_customers)),
    }, prob=jnp.asarray(probs(n_customers)))

    orders = Table.from_columns({
        "o_orderkey": jnp.arange(n_orders),
        "o_custkey": jnp.asarray(rng.integers(0, n_customers, n_orders)),
        "o_orderdate": jnp.asarray(rng.integers(DAY0_1995 - 800,
                                                DAY0_1995 + 800, n_orders)),
        "o_totalprice": jnp.asarray(rng.integers(1000, 100000, n_orders)),
    }, prob=jnp.asarray(probs(n_orders)))

    l_part = rng.integers(0, n_parts, n_lineitem)
    # pick a supplier that actually supplies the part (partsupp has 4/part)
    l_supp = ps_supp[l_part * 4 + rng.integers(0, 4, n_lineitem)]
    lineitem = Table.from_columns({
        "l_orderkey": jnp.asarray(np.repeat(np.arange(n_orders),
                                            lines_per_order)),
        "l_partkey": jnp.asarray(l_part),
        "l_suppkey": jnp.asarray(l_supp),
        "l_pskey": jnp.asarray(l_part * (1 << 10) + l_supp),
        "l_quantity": jnp.asarray(rng.integers(1, 51, n_lineitem)),
        "l_extendedprice": jnp.asarray(rng.integers(100, 10000, n_lineitem)),
        "l_discount": jnp.asarray(rng.integers(0, 11, n_lineitem)),  # percent
        "l_shipdate": jnp.asarray(rng.integers(DAY0_1995 - 900,
                                               DAY0_1995 + 900, n_lineitem)),
        "l_returnflag": jnp.asarray(rng.integers(0, 3, n_lineitem)),
        "l_linestatus": jnp.asarray(rng.integers(0, 2, n_lineitem)),
    }, prob=jnp.asarray(probs(n_lineitem)))

    return TPCH(lineitem, orders, customer, part, partsupp, supplier, nation,
                dict(n_orders=n_orders, n_lineitem=n_lineitem,
                     n_parts=n_parts, n_suppliers=n_suppliers,
                     n_customers=n_customers, n_nations=n_nations))


# ------------------------------------------------------ plan constructors
# The aggregate-mode logical plans, exposed as standalone constructors so
# the serving layer (repro.db.serving) can submit them without running a
# query function: two calls build STRUCTURALLY EQUAL plans
# (plans.plan_key), which is what the bounded plan cache keys on.
def _q1_select():
    return Select(Scan("lineitem"),
                  lambda t: t["l_shipdate"] <= DAY0_1995 + 500)


def q1_plan():
    """Q1 aggregate-mode plan: pricing summary GROUP BY (returnflag,
    linestatus) with SUM/COUNT/cumulant riders in one pass."""
    return GroupAgg(_q1_select(), ("l_returnflag", "l_linestatus"),
                    "l_quantity", "SUM", 8, "normal",
                    extra=(("price", "l_extendedprice", "SUM", "normal"),
                           ("count", "", "COUNT", "normal"),
                           ("cumulants_qty", "l_quantity", "SUM",
                            "cumulants")))


def _q3_join(segment: int = 1, order_join_budget: int | None = None):
    cust = Select(Scan("customer"), lambda t: t["c_mktsegment"] == segment)
    orders = Select(Scan("orders"), lambda t: t["o_orderdate"] < DAY0_1995)
    o = FKJoin(orders, cust, "o_custkey", "c_custkey", ("c_mktsegment",))
    li = Select(Scan("lineitem"), lambda t: t["l_shipdate"] > DAY0_1995)
    return FKJoin(li, o, "l_orderkey", "o_orderkey",
                  ("o_orderdate", "o_custkey"),
                  gather_budget=order_join_budget)


def q3_plan(segment: int = 1, max_groups: int = 512,
            order_join_budget: int | None = None):
    """Q3 aggregate-mode plan: revenue per order of one market segment."""
    return GroupAgg(_q3_join(segment, order_join_budget), ("l_orderkey",),
                    "l_extendedprice", "SUM", max_groups, "normal",
                    extra=(("cumulants", "l_extendedprice", "SUM",
                            "cumulants"),))


def _q6_select():
    return Select(
        Scan("lineitem"),
        lambda t: (t["l_shipdate"] >= DAY0_1995 - 400)
        & (t["l_shipdate"] < DAY0_1995)
        & (t["l_discount"] >= 5) & (t["l_discount"] <= 7)
        & (t["l_quantity"] < 24))


def q6_plan(num_freq: int | None = None):
    """Q6 aggregate-mode plan: the single-group scalar revenue SUM."""
    val = Map(_q6_select(), "q6_value",
              lambda t: t["l_quantity"] * t["l_discount"])
    extra = (("cumulants", "q6_value", "SUM", "cumulants"),)
    if num_freq:
        extra += (("exact", "q6_value", "SUM", "exact"),)
    return GroupAgg(val, (), "q6_value", "SUM", 1, "normal", extra=extra,
                    num_freq=num_freq or 0)


def q18_plan(qty_threshold: float = 150.0, max_groups: int = 2048):
    """Q18 reweight plan: keep each order with p *= P(SUM(qty) > cutoff)
    (Table I row III — the group_confidence shape)."""
    return ReweightGreater(Scan("lineitem"), ("l_orderkey",), "l_quantity",
                           "", max_groups, threshold=float(qty_threshold))


def _q20_r10(nation_name: int = 3, max_groups: int = 1024,
             avail_frac: float = 0.05):
    r1 = Select(Scan("part"), lambda t: t["p_name_forest"])
    r2 = FKJoin(Scan("partsupp"), r1, "ps_partkey", "p_partkey",
                ("p_name_forest",))
    r3 = Select(Scan("lineitem"),
                lambda t: (t["l_shipdate"] >= DAY0_1995 - 365)
                & (t["l_shipdate"] < DAY0_1995))
    r4 = FKJoin(r3, r2, "l_pskey", "ps_pskey",
                ("ps_availqty", "ps_suppkey", "ps_pskey"))
    r4t = Map(r4, "q20_thresh",
              lambda t: t["ps_availqty"].astype(t.prob.dtype) * avail_frac)
    r7 = ReweightGreater(r4t, ("ps_pskey",), "l_quantity", "q20_thresh",
                         max_groups, carry_cols=("ps_suppkey",))
    nat = Select(Scan("nation"), lambda t: t["n_name"] == nation_name)
    r9 = FKJoin(Scan("supplier"), nat, "s_nationkey", "n_nationkey",
                ("n_name",))
    return FKJoin(r7, r9, "ps_suppkey", "s_suppkey",
                  ("s_name", "s_address"))


def q20_plan(nation_name: int = 3, max_groups: int = 1024,
             avail_frac: float = 0.05):
    """Q20 plan (the paper's Fig. 6): project(s_name) of the reweighted
    excess-stock pipeline."""
    return Project(_q20_r10(nation_name, max_groups, avail_frac),
                   ("s_name",), 64)


def serving_plans(q3_groups: int = 512, q18_groups: int = 2048) -> dict:
    """One representative logical plan per TPC-H query — the serving
    workload (`launch/serve.py --db`) and the cache-hit bit-equality
    tests submit exactly these.  The group-table sizes of the two
    per-order plans default to small-scale values; size them from the
    data with :func:`serving_group_sizes` (a table too small overflows)."""
    return {"q1": q1_plan(), "q3": q3_plan(max_groups=q3_groups),
            "q6": q6_plan(), "q18": q18_plan(max_groups=q18_groups),
            "q20": q20_plan()}


def serving_group_sizes(db: TPCH) -> dict:
    """``serving_plans`` keywords counted from the data: the distinct
    order keys that survive Q3's join, and the distinct order keys of
    lineitem (Q18's groups)."""
    def distinct(t: Table, key: str) -> int:
        keys = np.asarray(t[key])[np.asarray(t.valid)]
        return max(1, np.unique(keys).size)

    return dict(q3_groups=distinct(compile_plan(_q3_join())(db.tables()),
                                   "l_orderkey"),
                q18_groups=distinct(db.lineitem, "l_orderkey"))


# ------------------------------------------------- parameterized families
def q6_family():
    """Q6 as a parameterized family: the discount window and quantity
    limit are lifted :class:`~repro.db.plans.Param` holes
    (``disc_lo`` / ``disc_hi`` / ``qty_lim``), so ONE compiled
    executable serves every setting and a what-if sweep over N settings
    runs as one batched device program
    (:meth:`repro.db.serving.QueryService.sweep`)."""
    sel = Select(Scan("lineitem"), Parameterized(
        lambda t, lo, hi, lim: (t["l_shipdate"] >= DAY0_1995 - 400)
        & (t["l_shipdate"] < DAY0_1995)
        & (t["l_discount"] >= lo) & (t["l_discount"] <= hi)
        & (t["l_quantity"] < lim),
        ("disc_lo", "disc_hi", "qty_lim")))
    val = Map(sel, "q6_value", lambda t: t["l_quantity"] * t["l_discount"])
    return GroupAgg(val, (), "q6_value", "SUM", 1, "normal",
                    extra=(("cumulants", "q6_value", "SUM", "cumulants"),))


def q18_family(max_groups: int = 2048):
    """Q18 as a parameterized family: the quantity cutoff is the lifted
    ``qty_threshold`` param of the reweight — threshold what-if sweeps
    share one executable."""
    return ReweightGreater(Scan("lineitem"), ("l_orderkey",), "l_quantity",
                           "", max_groups,
                           threshold=Param("qty_threshold"))


# --------------------------------------------------------------- queries
def _confidence_of(plan, db: TPCH, mesh, opts=None):
    """P(result non-empty): one-group AtLeastOne over the plan's output."""
    agg = GroupAgg(plan, keys=(), value="", agg="COUNT", max_groups=1)
    out = compile_plan(agg, mesh, **(opts or {}))(db.tables())
    return dict(confidence=out["confidence"][0])


def q1(db: TPCH, mode: str = "aggregate", mesh=None, plan_opts=None):
    """Pricing summary: GROUP BY (returnflag, linestatus); SUM(quantity),
    SUM(extendedprice), COUNT(*) over shipped lineitems.

    ``plan_opts`` (every query): extra ``compile_plan`` keywords —
    ``join_gather_budget``, ``shuffle_slack``, ``canonical_chunks``, ... —
    so callers steer the physical planner's strategy choices (e.g. force
    the shuffle-partitioned join with a tiny gather budget) without
    rebuilding the logical plans."""
    sel = _q1_select()
    keys = ("l_returnflag", "l_linestatus")
    if mode == "deterministic":
        li = compile_plan(sel)(db.tables())
        ids, _, gvalid = ops.group_ids(li, list(keys), 8)
        m = li.valid
        qty = jax.ops.segment_sum(jnp.where(m, li["l_quantity"], 0), ids,
                                  num_segments=8)
        price = jax.ops.segment_sum(jnp.where(m, li["l_extendedprice"], 0),
                                    ids, num_segments=8)
        cnt = jax.ops.segment_sum(m.astype(jnp.int32), ids, num_segments=8)
        return dict(valid=gvalid, sum_qty=qty, sum_price=price, count=cnt)
    if mode == "confidence":
        return _confidence_of(sel, db, mesh, plan_opts)
    if mode == "group_confidence":
        out = compile_plan(GroupAgg(sel, keys, "", "COUNT", 8), mesh,
                           **(plan_opts or {}))(db.tables())
        return dict(valid=out["valid"], confidence=out["confidence"])
    # aggregate: Normal + moment terms per group, all in ONE UDA pass
    out = compile_plan(q1_plan(), mesh, **(plan_opts or {}))(db.tables())
    return dict(valid=out["valid"], qty=out["sum"], price=out["price"],
                count=out["count"], cumulants_qty=out["cumulants_qty"])


def q3(db: TPCH, mode: str = "aggregate", segment: int = 1,
       max_groups: int = 512, mesh=None, plan_opts=None,
       order_join_budget: int | None = None):
    """Shipping priority: revenue per order for one market segment.

    The GROUP BY keys on ``l_orderkey`` — the probe key of the
    lineitem |x| orders join — so on a mesh the planner's cost model can
    fuse that join with the aggregation (CoPartitionedJoin +
    PartitionedAgg: matched rows stay at their ``l_orderkey % n_shards``
    owner, zero shuffle-home round-trips).  ``order_join_budget`` is the
    per-join gather budget of exactly that join: set it below the orders
    capacity to exercise the fused pipeline while the small customer
    dimension still gathers (``plan_opts=dict(join_gather_budget=...)``
    would shuffle both).  Results are bit-identical either way."""
    j = _q3_join(segment, order_join_budget)
    if mode == "deterministic":
        jt = compile_plan(j)(db.tables())
        ids, _, gvalid = ops.group_ids(jt, ["l_orderkey"], max_groups)
        rev = jax.ops.segment_sum(
            jnp.where(jt.valid, jt["l_extendedprice"], 0), ids,
            num_segments=max_groups)
        return dict(valid=gvalid, revenue=rev)
    if mode == "confidence":
        return _confidence_of(j, db, mesh, plan_opts)
    if mode == "group_confidence":
        out = compile_plan(GroupAgg(j, ("l_orderkey",), "", "COUNT",
                                    max_groups), mesh,
                           **(plan_opts or {}))(db.tables())
        return dict(valid=out["valid"], confidence=out["confidence"])
    plan = q3_plan(segment, max_groups, order_join_budget)
    out = compile_plan(plan, mesh, **(plan_opts or {}))(db.tables())
    return dict(valid=out["valid"], revenue=out["sum"],
                cumulants=out["cumulants"])


def q6(db: TPCH, mode: str = "aggregate", num_freq: int | None = None,
       mesh=None, plan_opts=None):
    """Forecast revenue change: scalar SUM over filtered lineitem.

    The single-group scalar aggregate — the paper's Figure 9 COUNT(*)
    experiment is this query with values == 1.
    """
    sel = _q6_select()
    if mode == "deterministic":
        li = compile_plan(sel)(db.tables())
        return dict(revenue=jnp.sum(jnp.where(li.valid, li["l_quantity"]
                                              * li["l_discount"], 0)))
    if mode in ("confidence", "group_confidence"):
        return _confidence_of(sel, db, mesh, plan_opts)
    # Integer-typed computed column (q6_plan's Map): keeps the exact-CF
    # aggregate eligible for the Pallas kernel's integer-phase arithmetic
    # (uda.accumulate casts to the prob dtype itself and tracks source
    # integrality).  num_freq requests the exact distribution (Figure 9).
    r = compile_plan(q6_plan(num_freq), mesh,
                     **(plan_opts or {}))(db.tables())
    mu, var = r["sum"]
    out = dict(normal=(mu[0], var[0]), cumulants=r["cumulants"][0])
    if num_freq:
        out["exact_coeffs"] = r["exact"][0]
    return out


def q18(db: TPCH, mode: str = "aggregate", qty_threshold: int = 150,
        max_groups: int = 2048, mesh=None, method: str = "normal",
        num_freq: int = 256, plan_opts=None):
    """Large-volume customers: orders whose SUM(l_quantity) > threshold.

    The probabilistic version keeps every order with
    p = p_order * P(SUM > threshold)  (Table I row III reweight).
    ``method="exact"`` (aggregate mode) computes the per-order quantity
    distribution with the grouped exact-CF planner path — ``num_freq``
    must exceed the max per-order quantity sum (lines_per_order * 50 for
    the synthetic generator) — and derives P(SUM > threshold) from the
    exact tail mass instead of the Normal approximation.

    The aggregations key on ``l_orderkey`` over a bare lineitem scan, so
    ``plan_opts=dict(agg_shuffle_budget=N)`` (rows above N) runs them as
    the co-partitioned pipeline on a mesh: tuples hash-exchange to their
    order's owner shard (``Repartition``) and aggregate in place
    (``PartitionedAgg``, one psum merge) — bit-identical to the default
    RowBlocked PartialAgg lowering."""
    li = Scan("lineitem")
    if mode == "deterministic":
        t = db.lineitem
        ids, _, gvalid = ops.group_ids(t, ["l_orderkey"], max_groups)
        qty = jax.ops.segment_sum(jnp.where(t.valid, t["l_quantity"], 0),
                                  ids, num_segments=max_groups)
        return dict(valid=gvalid & (qty > qty_threshold), sum_qty=qty)
    rew = q18_plan(qty_threshold, max_groups)
    if mode == "confidence":
        # P(at least one order qualifies) = 1 - prod_g (1 - conf_g * p_gt_g)
        return _confidence_of(rew, db, mesh, plan_opts)
    if mode == "group_confidence":
        t = compile_plan(rew, mesh, **(plan_opts or {}))(db.tables())
        return dict(valid=t.valid, confidence=t.prob)
    if method == "exact":
        plan = GroupAgg(li, ("l_orderkey",), "l_quantity", "SUM", max_groups,
                        "exact", num_freq=num_freq)
        out = compile_plan(plan, mesh, **(plan_opts or {}))(db.tables())
        coeffs = out["exact"]                        # (G, num_freq) rows
        gt = jnp.arange(num_freq) > qty_threshold
        p_gt = jnp.sum(coeffs * gt[None, :], axis=-1)
        return dict(valid=out["valid"], sum_dist=coeffs, p_qualifies=p_gt)
    plan = GroupAgg(li, ("l_orderkey",), "l_quantity", "SUM", max_groups,
                    "normal")
    out = compile_plan(plan, mesh, **(plan_opts or {}))(db.tables())
    mu, var = out["sum"]
    p_gt = ops.normal_greater(mu, var, jnp.asarray(qty_threshold, mu.dtype))
    return dict(valid=out["valid"], sum_qty=(mu, var), p_qualifies=p_gt)


def q18_topk(db: TPCH, max_groups: int = 2048, kappa: int = 8, mesh=None,
             plan_opts=None):
    """Top-k variant of Q18: the per-order MAX(l_quantity) distribution
    with the paper's §V-B.2 truncation bound exposed per group.

    The MinMax UDA keeps the ``kappa`` best distinct values per group
    (§V-B.1 masses are exact on that support).  What used to be invisible
    to callers is the truncation remainder: the probability that a
    group's true MAX lies STRICTLY beyond the kept support.  It is
    returned here as ``tail_mass`` — per group,

        tail_mass_g = prod_{kept values} Q_j * (1 - prod_{evicted} (1-p))

    (see :meth:`repro.core.uda.MinMax.tail_mass`), which §V-B.2 shows
    bounds the total probability unaccounted for by the reported
    per-value masses; it is exactly 0 when kappa covered every distinct
    value.  A caller ranking orders by MAX quantity can therefore certify
    each group's answer to that bound — or hand the plan to
    :func:`repro.db.plans.run_plan` with ``RetryPolicy(tail_tol=...)``,
    which doubles kappa until the bound is within tolerance.

    Returns per-run arrays (the flattened G*kappa support grid of
    ``operators.minmax_runs``) plus the per-group ``p_empty`` and
    ``tail_mass``.
    """
    plan = GroupAgg(Scan("lineitem"), ("l_orderkey",), "l_quantity",
                    "MAX", max_groups, kappa=kappa)
    out = compile_plan(plan, mesh, **(plan_opts or {}))(db.tables())
    mm = out["minmax"]
    return dict(valid=out["valid"], keys=out["keys"],
                run_group=mm["run_group"], run_value=mm["run_value"],
                run_mass=mm["run_mass"], run_valid=mm["run_valid"],
                p_empty=mm["p_empty"], tail_mass=mm["tail_mass"])


def q20(db: TPCH, mode: str = "aggregate", nation_name: int = 3,
        max_groups: int = 1024, avail_frac: float = 0.05, mesh=None,
        plan_opts=None):
    """The paper's Fig. 6 plan: suppliers in one nation with excess stock of
    'forest' parts.

        R1 = sigma_forest(part)
        R2 = partsupp |x| R1
        R3 = sigma_shipdate(lineitem)
        R4 = R3 |x| R2                       (on partkey & suppkey)
        R6 = GROUP R4 BY ps key; SUM(l_quantity)
        R7 = reweight p *= P(SUM > availqty) (Table I row III)
        R9 = supplier |x| sigma_CANADA(nation)
        Q  = project(s_name) of R7 |x| R9
    """
    r10 = _q20_r10(nation_name, max_groups, avail_frac)
    if mode == "deterministic":
        t = compile_plan(r10, mesh, **(plan_opts or {}))(db.tables())
        return dict(valid=t.valid & (t.prob > 0.5), s_name=t["s_name"])
    proj = Project(r10, ("s_name",), 64)
    if mode == "confidence":
        return _confidence_of(proj, db, mesh, plan_opts)
    result = compile_plan(proj, mesh, **(plan_opts or {}))(db.tables())
    if mode == "group_confidence":
        return dict(valid=result.valid, s_name=result["s_name"],
                    confidence=result.prob)
    return dict(valid=result.valid, s_name=result["s_name"],
                prob=result.prob)


QUERIES = {"q1": q1, "q3": q3, "q6": q6, "q18": q18, "q20": q20}
MODES = ("deterministic", "confidence", "group_confidence", "aggregate")
