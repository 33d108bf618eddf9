"""Plain numpy references of the benchmark's TPC-H queries.

Copied from the program's chip smoke test and given the queries'
substitution parameters.  Nothing here imports the program: the
references read only the host columns of ``bench/data/tpch_gen.py``, with
each probability rounded to f32 and widened back (the values the device
holds).

Every reference takes ``dt``: ``numpy.float64`` gives the reference that
decides ``correct``; ``ml_dtypes.bfloat16`` gives the control, the same
arithmetic with every float operation rounded to bfloat16 and every sum
accumulated in bfloat16 (a precision step below the f32 the configuration
states), which the comparison has to refuse.  Integer columns, predicates
and keys stay exact in both.

A reference returns a *summary*, the same structure that
``bench/harness/answers.py`` extracts from an engine answer:

* group aggregates: ``keys`` (G, k) int64, ``conf`` (G,), ``mean`` and
  ``var`` {aggregate: (G,)};
* reweighted or projected tables: ``keys`` (n,), ``prob`` (n,).

With ``dt`` float64 it also carries ``stats``, what the bounds of
``tolerances.py`` need, and ``work``, the selected tuples and output
groups the request needed.

A query whose tuple probabilities the program forms as a product of f32
factors (a join) passes ``p_roundings``, the roundings of that product,
and its bounds cover them (``tolerances.rounding_terms``).
"""
from __future__ import annotations

import math

import numpy as np
from scipy import special

from . import tolerances as T

F64 = np.float64


def _is64(dt) -> bool:
    return np.dtype(dt) == np.dtype(F64)


def _fn(f, x, dt):
    """A transcendental in precision ``dt``: f64 directly; a lower
    precision is evaluated in f32 and rounded to ``dt``."""
    if _is64(dt):
        return f(x)
    return f(np.asarray(x, np.float32)).astype(dt)


def group_sum(gid, ngroups: int, w, dt, order=None):
    """Per-group sums of ``w`` in precision ``dt``: f64 by bincount, a
    lower precision by sequential accumulation in that precision
    (``order``, a stable argsort of ``gid``, may be passed in)."""
    if _is64(dt):
        return np.bincount(gid, np.asarray(w, F64), ngroups)
    if order is None:
        order = np.argsort(gid, kind="stable")
    gs, ws = gid[order], np.asarray(w, dt)[order]
    starts = np.searchsorted(gs, np.arange(ngroups))
    counts = np.bincount(gid, minlength=ngroups)
    out = np.zeros(ngroups, dt)
    live = counts > 0
    if live.any():
        out[live] = np.add.reduceat(ws, starts[live])
    return out


def group_stats(gid, ngroups: int, p, v, dt=F64, order=None,
                log_none=None, p_roundings: int = 0) -> dict:
    """Closed forms per group: Normal mean sum p v, variance
    sum p (1-p) v^2 (= cumulants k1, k2), log P(no tuple) sum log1p(-p)
    (``log_none`` may be passed in: it does not depend on v); in f64 also
    ``d<term>``, the error ``p_roundings`` roundings of each p cause in
    each sum."""
    p = np.asarray(p, dt)
    v = np.asarray(v, dt)
    if not _is64(dt) and order is None:
        order = np.argsort(gid, kind="stable")
    if log_none is None:
        with np.errstate(divide="ignore"):
            log_none = group_sum(gid, ngroups, _fn(np.log1p, -p, dt), dt,
                                 order)
    one = np.asarray(1, dt)
    pv = p * v
    out = dict(mu=group_sum(gid, ngroups, pv, dt, order),
               var=group_sum(gid, ngroups, pv * (one - p) * v, dt, order),
               log_none=log_none, rows=np.bincount(gid, minlength=ngroups))
    if _is64(dt):
        for k, t in T.rounding_terms(p, v, p_roundings).items():
            out["d" + k] = (np.bincount(gid, t, ngroups) if np.ndim(t)
                            else np.zeros(ngroups))
    return out


def _conf(log_none, dt):
    return -_fn(np.expm1, log_none, dt)


def _group_ids(key_cols: list, mask) -> tuple:
    """Ascending distinct keys of the masked rows, (G, k), and each
    row's group id.  Non-negative integer keys are coded into one int64
    (row-major), so the sort is one-dimensional."""
    cols = [np.asarray(k)[mask].astype(np.int64) for k in key_cols]
    dims = [int(c.max(initial=0)) + 1 for c in cols]
    code = np.ravel_multi_index(cols, dims) if len(cols) > 1 else cols[0]
    size = int(np.prod(dims))
    if size <= 1 << 24:                 # dense: count instead of sort
        present = np.bincount(code, minlength=size) > 0
        codes = np.flatnonzero(present)
        gid = (np.cumsum(present) - 1)[code]
    else:
        codes, gid = np.unique(code, return_inverse=True)
    keys = np.stack(np.unravel_index(codes, dims), axis=1) if len(cols) > 1 \
        else codes[:, None]
    return keys, gid.reshape(-1)


def groupagg(mask, key_cols: list, p, values: dict, dt,
             p_roundings: int = 0) -> dict:
    """GroupAgg over the rows of ``mask``, grouped by ``key_cols``
    (ascending keys, the engine's group order), each aggregate
    ``{name: value column}`` summarised by mean and variance."""
    n = int(mask.sum())
    if key_cols:
        keys, gid = _group_ids(key_cols, mask)
    else:
        keys = np.zeros((1, 0), np.int64)
        gid = np.zeros(n, np.int64)
    g = keys.shape[0]
    order = None if _is64(dt) else np.argsort(gid, kind="stable")
    pm = np.asarray(p[mask], dt)
    st, log_none = {}, None
    for a, v in values.items():
        st[a] = group_stats(gid, g, pm, v[mask], dt, order, log_none,
                            p_roundings)
        log_none = st[a]["log_none"]
    s0 = next(iter(st.values()))
    out = dict(keys=keys, conf=_conf(s0["log_none"], dt),
               mean={a: s["mu"] for a, s in st.items()},
               var={a: s["var"] for a, s in st.items()})
    if _is64(dt):
        out["stats"] = st
        out["work"] = dict(rows=n, groups=g)
    return out


def groupagg_tols(ref: dict) -> dict:
    """The bounds of every number of a group-aggregate summary."""
    st = ref["stats"]
    s0 = next(iter(st.values()))
    return dict(conf=T.conf_tol(s0["rows"], s0["log_none"], s0["dlog_none"]),
                mean={a: T.sum_tol(s["rows"], s["mu"]) + s["dmu"]
                      for a, s in st.items()},
                var={a: T.sum_tol(s["rows"], s["var"]) + s["dvar"]
                     for a, s in st.items()})


def reweight(gid, ngroups: int, p, v, thr, dt, p_roundings: int = 0) -> tuple:
    """Table I row III: per group p = (1 - prod(1-p)) * P(N(mu, var) >
    thr), with (f64 only) the error bound of every stage."""
    s = group_stats(gid, ngroups, p, v, dt, p_roundings=p_roundings)
    conf = _conf(s["log_none"], dt)
    thr = np.asarray(thr, dt)
    sigma = _fn(np.sqrt, np.maximum(s["var"], np.asarray(1e-30, dt)), dt)
    z = (thr - s["mu"]) / (sigma * np.asarray(math.sqrt(2.0), dt))
    p_gt = np.asarray(0.5, dt) * _fn(special.erfc, z, dt)
    prob = conf * p_gt
    if not _is64(dt):
        return prob, None
    dmu = T.sum_tol(s["rows"], s["mu"]) + s["dmu"]
    dvar = T.sum_tol(s["rows"], s["var"]) + s["dvar"]
    tol = (T.conf_tol(s["rows"], s["log_none"], s["dlog_none"]) * p_gt
           + conf * T.tail_tol(s["mu"], s["var"], thr, dmu, dvar))
    return prob, tol


# --------------------------------------------------------------- queries
def q1(h: dict, cutoff: int, dt=F64) -> dict:
    """Pricing summary over l_shipdate <= cutoff, by (returnflag,
    linestatus): SUM(qty), SUM(price), COUNT, qty cumulants."""
    li = h["lineitem"]
    mask = li["valid"] & (li["l_shipdate"] <= cutoff)
    return groupagg(mask, [li["l_returnflag"], li["l_linestatus"]], li["p"],
                    dict(sum=li["l_quantity"], price=li["l_extendedprice"],
                         count=np.ones(mask.size, np.int64),
                         cumulants_qty=li["l_quantity"]), dt)


def q3_rows(h: dict, segment: int, date: int, dt=F64):
    """Q3's joined lineitem rows: (mask, joined probability)."""
    li, o, c = h["lineitem"], h["orders"], h["customer"]
    c_ok = c["valid"] & (c["c_mktsegment"] == segment)
    o_ok = o["valid"] & (o["o_orderdate"] < date) & c_ok[o["o_custkey"]]
    o_p = np.asarray(o["p"], dt) * np.asarray(c["p"], dt)[o["o_custkey"]]
    mask = li["valid"] & (li["l_shipdate"] > date) & o_ok[li["l_orderkey"]]
    return mask, np.asarray(li["p"], dt) * o_p[li["l_orderkey"]]


def q3(h: dict, segment: int, date: int, dt=F64) -> dict:
    """Revenue per order of one market segment, orders before ``date``
    with lines shipped after it.  A line's probability is
    p_line (p_order p_customer), two f32 products in the program."""
    li = h["lineitem"]
    mask, p = q3_rows(h, segment, date, dt)
    return groupagg(mask, [li["l_orderkey"]], p,
                    dict(sum=li["l_extendedprice"],
                         cumulants=li["l_extendedprice"]), dt,
                    p_roundings=2)


def q6_mask(li: dict, date_lo: int, date_hi: int, disc_lo: int,
            disc_hi: int, qty: int):
    return (li["valid"] & (li["l_shipdate"] >= date_lo)
            & (li["l_shipdate"] < date_hi)
            & (li["l_discount"] >= disc_lo) & (li["l_discount"] <= disc_hi)
            & (li["l_quantity"] < qty))


def q6(h: dict, date_lo: int, date_hi: int, disc_lo: int, disc_hi: int,
       qty: int, dt=F64) -> dict:
    """Forecast revenue change: the scalar SUM(qty * discount)."""
    li = h["lineitem"]
    mask = q6_mask(li, date_lo, date_hi, disc_lo, disc_hi, qty)
    v = li["l_quantity"] * li["l_discount"]
    return groupagg(mask, [], li["p"], dict(sum=v, cumulants=v), dt)


def q18(h: dict, threshold: float, dt=F64) -> dict:
    """Each order kept with p = P(order non-empty) * P(SUM(qty) >
    threshold) under the Normal approximation."""
    li = h["lineitem"]
    m = li["valid"]
    keys, gid = np.unique(li["l_orderkey"][m], return_inverse=True)
    prob, tol = reweight(gid, keys.shape[0], np.asarray(li["p"][m], dt),
                         li["l_quantity"][m], threshold, dt)
    out = dict(keys=keys, prob=prob)
    if _is64(dt):
        out.update(tol=tol, work=dict(rows=int(m.sum()), groups=keys.size))
    return out


def q20(h: dict, color: int, nation: int, date_lo: int, date_hi: int,
        avail_frac: float, dt=F64) -> dict:
    """The paper's Fig. 6 pipeline: suppliers of one nation with excess
    stock of parts of one colour, by supplier name.  A shipment's
    probability is p_line (p_partsupp p_part), two f32 products in the
    program."""
    li, ps, part = h["lineitem"], h["partsupp"], h["part"]
    sup, nat = h["supplier"], h["nation"]
    colored = part["valid"] & (part["p_color"] == color)
    r2_ok = ps["valid"] & colored[ps["ps_partkey"]]
    r2_p = np.asarray(ps["p"], dt) * np.asarray(part["p"], dt)[
        ps["ps_partkey"]]
    order = np.argsort(ps["ps_pskey"])
    pos = np.searchsorted(ps["ps_pskey"][order], li["l_pskey"])
    idx = order[np.clip(pos, 0, order.size - 1)]
    hit = ps["ps_pskey"][idx] == li["l_pskey"]
    r4 = (li["valid"] & (li["l_shipdate"] >= date_lo)
          & (li["l_shipdate"] < date_hi) & hit & r2_ok[idx])
    r4_p = (np.asarray(li["p"], dt) * r2_p[idx])[r4]
    pskeys, gid = np.unique(ps["ps_pskey"][idx][r4], return_inverse=True)
    first = np.zeros(pskeys.shape[0], np.int64)
    first[gid] = np.flatnonzero(r4)
    grp_ps = idx[first]                       # partsupp row of each group
    thr = (ps["ps_availqty"][grp_ps].astype(np.float32)
           * np.float32(avail_frac)).astype(F64)
    r7_p, r7_tol = reweight(gid, pskeys.shape[0], r4_p,
                            li["l_quantity"][r4], thr, dt, p_roundings=2)
    supp = ps["ps_suppkey"][grp_ps]
    r9_ok = (sup["valid"] & nat["valid"][sup["s_nationkey"]]
             & (nat["n_name"][sup["s_nationkey"]] == nation))
    r9_p = np.asarray(sup["p"], dt) * np.asarray(nat["p"], dt)[
        sup["s_nationkey"]]
    ok = r9_ok[supp]
    p10 = r7_p[ok] * r9_p[supp[ok]]
    names, sid = np.unique(sup["s_name"][supp[ok]], return_inverse=True)
    st = group_stats(sid, names.shape[0], p10, np.ones(p10.shape, np.int64),
                     dt)
    out = dict(keys=names, prob=_conf(st["log_none"], dt))
    if _is64(dt):
        tol10 = r7_tol[ok] * r9_p[supp[ok]] + 4 * T.U
        out["tol"] = (np.bincount(sid, tol10, names.shape[0])
                      + T.conf_tol(st["rows"], st["log_none"]))
        out["work"] = dict(rows=int(r4.sum()), groups=pskeys.size)
    return out
