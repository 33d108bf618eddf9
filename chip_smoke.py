"""Smoke test of the served probabilistic TPC-H path on a TPU.

    python chip_smoke.py              # one chip: served, exact, streamed
    python chip_smoke.py --chips 4    # a 2x2 host: the sharded plans only

Everything runs in this one process, through the entry points a user
calls (``QueryService.submit``/``sweep`` over ``compile_plan``), in f32 --
the precision the Pallas kernels take.  Data is TPC-H-shaped lineitem at
the SF1 row count (1.5M orders x 4 lines = 6M rows), made from ``--seed``
on the machine.  Every answer is checked against a numpy float64
reference computed from the same generated columns; every plan that
should run a Pallas kernel must show ``tpu_custom_call`` in its compiled
program, so a silent fallback to the jnp oracle fails the smoke.

Each phase prints one line: wall time (results waited for), the number of
XLA programs built in it, and what it checked.  On success the last line
is ``{"ok": true, "device": {...}}``.  With no TPU, or after any failed
check, the script exits nonzero and prints no such line.

Cuts against a TPC-H SF1 deployment: only lineitem and orders have SF1
row counts (the generator keeps 200 parts, 800 partsupp, 50 suppliers,
300 customers); scalar exact-CF runs COUNT over the lineitems of the
first ``COUNT_ORDERS`` orders (the exact grid must hold the largest
COUNT, and a full-table SUM does not fit 2^20 frequencies); grouped
exact-CF runs over the first ``EXACT_ORDERS`` orders (a key-range
Select) because the kernel's grid grows with groups x tuples.
"""
from __future__ import annotations

import argparse
import json
import math
import os
import sys
import time

import numpy as np

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
from jax.sharding import NamedSharding, PartitionSpec as P  # noqa: E402
from scipy import special  # noqa: E402

from repro.core.config import use_compile_cache  # noqa: E402
from repro.db import physical as phys  # noqa: E402
from repro.db import tpch  # noqa: E402
from repro.db.plans import (GroupAgg, RetryPolicy, Scan, Select,  # noqa: E402
                            compile_plan)
from repro.db.serving import QueryService  # noqa: E402
from repro.launch.serve import serve_rounds, sweep_points  # noqa: E402

DAY0 = tpch.DAY0_1995
U = 2.0 ** -24                  # f32 unit roundoff
KERNEL_MARK = "tpu_custom_call"
ORDERS = 1_500_000              # x 4 lineitem rows: the SF1 row count
COUNT_ORDERS = 2000             # exact COUNT over these orders' lineitems
EXACT_ORDERS = 4096             # grouped exact-CF over these orders


class SmokeFailure(RuntimeError):
    """A check of the smoke did not hold."""


def require(cond, what: str) -> None:
    if not cond:
        raise SmokeFailure(what)


class CompileCounter:
    """Counts XLA programs built in this process (compiled or loaded from
    the persistent cache) through JAX's monitoring events."""

    def __init__(self):
        self.count = 0
        jax.monitoring.register_event_duration_secs_listener(self._on_event)

    def _on_event(self, event: str, _duration: float, **_kw) -> None:
        if event == "/jax/core/compile/backend_compile_duration":
            self.count += 1


def run_phase(name: str, counter: CompileCounter, fn, **kw):
    """Run one phase; print its wall time, compile count and checks."""
    c0, t0 = counter.count, time.perf_counter()
    checks, result = fn(**kw)
    dt = time.perf_counter() - t0
    print(f"[smoke] {name}: {dt:.3f} s, {counter.count - c0} compiles; "
          + "; ".join(checks), flush=True)
    return result


# ---------------------------------------------------------------- tolerances
# Every engine number below is an f32 sum over m rows of terms that are
# each a few roundings away from exact; the references are f64.  The
# device's f32 log1p, exp and erfc are not correctly rounded, so the
# bounds that pass through them take those functions' errors as fixed
# constants, and each run checks that the device stays within them.
def sum_tol(rows, scale):
    """f32 accumulation of m terms of one sign: the roundings fall on
    either side, so the error grows like sqrt(m) * 2^-24 of the sum's
    magnitude; 16x headroom and a few-ulp floor for m = 1."""
    return (4.0 + 16.0 * np.sqrt(np.maximum(rows, 1))) * U * np.abs(scale)


# Largest relative error of the device's f32 log1p(-p), exp and erfc, in
# f32 ulps (2^-24).  Measured on a TPU v5e over the grids of
# device_accuracy(): log1p 6,165, exp 97, erfc 110; about 1.3x that here,
# because the grids sample about one f32 value in a thousand.
DEVICE_ULPS = dict(log1p=8192, exp=128, erfc=160)
ACC = {k: v * U for k, v in DEVICE_ULPS.items()}


def device_accuracy() -> dict:
    """Relative error of the device's f32 ``log1p(-p)`` over p in (0, 1),
    ``exp`` over [-40, 0] and ``erfc`` over [-6, 6]: the largest on 2^20
    points against numpy in f64, in ulps."""
    n = 1 << 20
    p = np.concatenate([np.geomspace(1e-12, 0.5, n // 2),
                        np.linspace(0.5, 1 - 2 ** -20, n // 2)])
    grids = dict(log1p=(p, lambda a: jnp.log1p(-a), lambda a: np.log1p(-a)),
                 exp=(np.linspace(-40, 0, n), jnp.exp, np.exp),
                 erfc=(np.linspace(-6, 6, n), jax.lax.erfc, special.erfc))
    out = {}
    for name, (x, dev, ref) in grids.items():
        x = x.astype(np.float32)
        got = np.asarray(jax.jit(dev)(jnp.asarray(x)), np.float64)
        want = ref(x.astype(np.float64))
        out[name] = float(np.max(np.abs(got - want) / np.abs(want))) / U
    return out


def check_device_accuracy() -> str:
    """The device's transcendentals must stay within the errors the
    bounds assume."""
    ulps = device_accuracy()
    for name, got in ulps.items():
        require(got <= DEVICE_ULPS[name],
                f"device f32 {name} off by {got:.0f} ulps, the bounds "
                f"assume at most {DEVICE_ULPS[name]}")
    return "device f32 rel. error (ulps) " + ", ".join(
        f"{k} {v:.0f} <= {DEVICE_ULPS[k]}" for k, v in ulps.items())


def conf_tol(rows, log_none):
    """1 - exp(L) with L = sum log1p(-p): L is off by the device log1p's
    relative error plus :func:`sum_tol`; exp adds its own relative error
    of exp(L); a few ulps for the subtraction."""
    dlog = sum_tol(rows, log_none) + ACC["log1p"] * np.abs(log_none)
    e = np.exp(log_none)
    return e * dlog + ACC["exp"] * e + 4 * U


def tail_tol(mu, var, thr, dmu, dvar):
    """P(N(mu, var) > thr): dP <= phi(z) * dz with
    dz <= (dmu + |z| * dsigma) / sigma, phi <= 1/sqrt(2 pi); plus the
    device erfc's relative error of P."""
    sigma = np.sqrt(np.maximum(var, 1e-30))
    z = (thr - mu) / sigma
    dz = (dmu + np.abs(z) * dvar / (2 * sigma)) / sigma
    p_gt = 0.5 * special.erfc(z / math.sqrt(2.0))
    return dz / math.sqrt(2 * math.pi) + ACC["erfc"] * p_gt + 4 * U


def close(name: str, got, want, tol) -> str:
    """Check |got - want| <= tol elementwise; return the worst err/tol."""
    got = np.asarray(got, np.float64)
    want = np.asarray(want, np.float64)
    if want.ndim == 0:
        want = np.broadcast_to(want, got.shape)
    tol = np.broadcast_to(np.asarray(tol, np.float64), want.shape)
    require(got.shape == want.shape,
            f"{name}: shape {got.shape} != reference {want.shape}")
    err = np.abs(got - want)
    r = err / np.maximum(tol, 1e-300)
    ratio = float(np.max(r, initial=0.0))
    require(np.all(np.isfinite(got)), f"{name}: non-finite values")
    if not np.all(err <= tol):
        i = np.unravel_index(np.argmax(r), r.shape)
        require(False, f"{name}: err/tol {ratio:.3g} > 1 at {i}: got "
                f"{got[i]!r}, reference {want[i]!r}, tol {tol[i]:.3g}; "
                f"{int(np.sum(err > tol))} of {err.size} out")
    return f"{name} {ratio:.2g}"


def bit_equal(name: str, a, b) -> None:
    """Per-leaf bit equality (NaN == NaN), as the mesh_equiv harness."""
    la, ta = jax.tree.flatten(a)
    lb, tb = jax.tree.flatten(b)
    require(str(ta) == str(tb), f"{name}: tree {ta} != {tb}")
    for i, (x, y) in enumerate(zip(la, lb)):
        x, y = np.asarray(x), np.asarray(y)
        require(x.shape == y.shape and x.dtype == y.dtype,
                f"{name} leaf {i}: {x.shape}/{x.dtype} vs {y.shape}/{y.dtype}")
        same = x == y
        if np.issubdtype(x.dtype, np.inexact):
            same |= np.isnan(x) & np.isnan(y)
        require(np.all(same),
                f"{name} leaf {i}: {int(np.sum(~same))} of {x.size} differ")


# ----------------------------------------------------------------- reference
def host(db: tpch.TPCH) -> dict:
    """The generated tables as numpy: int64 columns, f64 probabilities
    (the f32 values the device holds, widened), bool valid."""
    out = {}
    for name, t in db.tables().items():
        cols = {k: np.asarray(v).astype(np.int64) for k, v in t.columns.items()}
        cols["p"] = np.asarray(t.prob).astype(np.float64)
        cols["valid"] = np.asarray(t.valid)
        out[name] = cols
    return out


def group_stats(gid, ngroups: int, p, v) -> dict:
    """Closed forms per group: Normal mean sum p v, variance
    sum p (1-p) v^2 (= cumulants k1, k2), log P(no tuple) sum log1p(-p)."""
    with np.errstate(divide="ignore"):
        log_none = np.bincount(gid, np.log1p(-p), ngroups)
    return dict(mu=np.bincount(gid, p * v, ngroups),
                var=np.bincount(gid, p * (1 - p) * v * v, ngroups),
                log_none=log_none,
                rows=np.bincount(gid, minlength=ngroups))


def check_groups(name: str, out: dict, key_names: tuple, keys, st: dict,
                 aggs: dict) -> list:
    """Compare a GroupAgg result to per-key reference stats.  ``keys``
    are the reference group keys, one column per name of ``key_names``,
    in ascending order (the engine's group order); ``aggs`` maps output
    names to (stats, method)."""
    valid = np.asarray(out["valid"])
    require(int(valid.sum()) == keys.shape[0],
            f"{name}: {int(valid.sum())} groups, reference {keys.shape[0]}")
    for i, k in enumerate(key_names):
        want = keys if keys.ndim == 1 else keys[:, i]
        require(np.array_equal(np.asarray(out["keys"][k])[valid], want),
                f"{name}: group keys {k}")
    s0 = next(iter(st.values()))
    checks = [close(f"{name}.confidence",
                    np.asarray(out["confidence"])[valid],
                    -np.expm1(s0["log_none"]),
                    conf_tol(s0["rows"], s0["log_none"]))]
    for agg, (s, method) in aggs.items():
        if method == "normal":
            mu, var = (np.asarray(x)[valid] for x in out[agg])
        else:
            terms = np.asarray(out[agg])[valid]
            mu, var = terms[:, 0], terms[:, 1]
        checks.append(close(f"{name}.{agg}.mean", mu, s["mu"],
                            sum_tol(s["rows"], s["mu"])))
        checks.append(close(f"{name}.{agg}.var", var, s["var"],
                            sum_tol(s["rows"], s["var"])))
    return checks


def _stats_on(mask, key, p, values: dict) -> tuple:
    """Reference stats of each value column, grouped by ``key`` over
    ``mask``: (ascending distinct keys, {name: stats})."""
    keys, gid = np.unique(key[mask], return_inverse=True, axis=0)
    return keys, {n: group_stats(gid, keys.shape[0], p[mask], v[mask])
                  for n, v in values.items()}


def ref_q1(h: dict):
    li = h["lineitem"]
    mask = li["valid"] & (li["l_shipdate"] <= DAY0 + 500)
    key = np.stack([li["l_returnflag"], li["l_linestatus"]], axis=1)
    keys, st = _stats_on(mask, key, li["p"],
                         dict(qty=li["l_quantity"],
                              price=li["l_extendedprice"],
                              count=np.ones_like(li["p"])))
    return keys, st


def q3_rows(h: dict):
    """Q3's joined lineitem rows: (mask, joined probability)."""
    li, o, c = h["lineitem"], h["orders"], h["customer"]
    c_ok = c["valid"] & (c["c_mktsegment"] == 1)
    o_ok = (o["valid"] & (o["o_orderdate"] < DAY0) & c_ok[o["o_custkey"]])
    o_p = o["p"] * c["p"][o["o_custkey"]]
    mask = li["valid"] & (li["l_shipdate"] > DAY0) & o_ok[li["l_orderkey"]]
    return mask, li["p"] * o_p[li["l_orderkey"]]


def ref_q3(h: dict):
    li = h["lineitem"]
    mask, p = q3_rows(h)
    return _stats_on(mask, li["l_orderkey"], p,
                     dict(rev=li["l_extendedprice"]))


def q6_mask(li: dict, lo=5, hi=7, lim=24):
    return (li["valid"] & (li["l_shipdate"] >= DAY0 - 400)
            & (li["l_shipdate"] < DAY0) & (li["l_discount"] >= lo)
            & (li["l_discount"] <= hi) & (li["l_quantity"] < lim))


def ref_sweep(h: dict, lims: np.ndarray) -> dict:
    """Q6 closed forms at every quantity limit at once: per-quantity
    partial sums, cumulated."""
    li = h["lineitem"]
    mask = q6_mask(li, lim=np.iinfo(np.int64).max)
    p, q = li["p"][mask], li["l_quantity"][mask]
    v = q * li["l_discount"][mask]
    nq = int(max(q.max(initial=0), lims.max())) + 1

    def upto(w):       # sum over rows with quantity < lim, for each lim
        return np.concatenate([[0.0], np.cumsum(np.bincount(q, w, nq))])[lims]

    return dict(mu=upto(p * v), var=upto(p * (1 - p) * v * v),
                rows=upto(np.ones_like(p)))


def reweight_ref(gid, ngroups, p, v, thr):
    """Table I row III: per group p = (1 - prod(1-p)) * P(N(mu, var) > thr),
    with the error bound of every stage."""
    s = group_stats(gid, ngroups, p, v)
    conf = -np.expm1(s["log_none"])
    sigma = np.sqrt(np.maximum(s["var"], 1e-30))
    p_gt = 0.5 * special.erfc((thr - s["mu"]) / (sigma * math.sqrt(2.0)))
    dmu, dvar = sum_tol(s["rows"], s["mu"]), sum_tol(s["rows"], s["var"])
    tol = (conf_tol(s["rows"], s["log_none"]) * p_gt
           + conf * tail_tol(s["mu"], s["var"], thr, dmu, dvar))
    return conf * p_gt, tol


def ref_q18(h: dict, threshold: float = 150.0):
    li = h["lineitem"]
    keys, gid = np.unique(li["l_orderkey"][li["valid"]], return_inverse=True)
    m = li["valid"]
    prob, tol = reweight_ref(gid, keys.shape[0], li["p"][m],
                             li["l_quantity"][m], threshold)
    return keys, prob, tol


def ref_q20(h: dict, nation: int = 3,
            avail_frac: float = 0.05):
    """The paper's Fig. 6 pipeline in numpy (see ``tpch.q20``)."""
    li, ps, part = h["lineitem"], h["partsupp"], h["part"]
    sup, nat = h["supplier"], h["nation"]
    forest = part["valid"] & (part["p_name_forest"] != 0)
    r2_ok = ps["valid"] & forest[ps["ps_partkey"]]
    r2_p = ps["p"] * part["p"][ps["ps_partkey"]]
    order = np.argsort(ps["ps_pskey"])
    pos = np.searchsorted(ps["ps_pskey"][order], li["l_pskey"])
    idx = order[np.clip(pos, 0, order.size - 1)]
    hit = ps["ps_pskey"][idx] == li["l_pskey"]
    r4 = (li["valid"] & (li["l_shipdate"] >= DAY0 - 365)
          & (li["l_shipdate"] < DAY0) & hit & r2_ok[idx])
    r4_p = (li["p"] * r2_p[idx])[r4]
    pskeys, gid = np.unique(ps["ps_pskey"][idx][r4], return_inverse=True)
    first = np.zeros(pskeys.shape[0], np.int64)
    first[gid] = np.flatnonzero(r4)
    grp_ps = idx[first]                       # partsupp row of each group
    thr = (ps["ps_availqty"][grp_ps].astype(np.float32)
           * np.float32(avail_frac)).astype(np.float64)
    r7_p, r7_tol = reweight_ref(gid, pskeys.shape[0], r4_p,
                                li["l_quantity"][r4], thr)
    supp = ps["ps_suppkey"][grp_ps]
    r9_ok = (sup["valid"] & nat["valid"][sup["s_nationkey"]]
             & (nat["n_name"][sup["s_nationkey"]] == nation))
    r9_p = sup["p"] * nat["p"][sup["s_nationkey"]]
    ok = r9_ok[supp]
    p10 = r7_p[ok] * r9_p[supp[ok]]
    tol10 = r7_tol[ok] * r9_p[supp[ok]] + 4 * U
    names, sid = np.unique(sup["s_name"][supp[ok]], return_inverse=True)
    st = group_stats(sid, names.shape[0], p10, np.ones_like(p10))
    tol = (np.bincount(sid, tol10, names.shape[0])
           + conf_tol(st["rows"], st["log_none"]))
    return names, -np.expm1(st["log_none"]), tol


def check_table(name: str, t, key: str, keys, prob, tol) -> list:
    valid = np.asarray(t.valid)
    require(np.array_equal(np.asarray(t[key])[valid], keys),
            f"{name}: {int(valid.sum())} output rows do not match the "
            f"reference's {keys.shape[0]} keys")
    return [close(f"{name}.prob", np.asarray(t.prob)[valid], prob, tol)]


def pb_pmf(p: np.ndarray, v: np.ndarray, width: int) -> np.ndarray:
    """Exact distribution of sum v_i B(p_i) on [0, width), by convolving
    one tuple at a time (f64)."""
    pmf = np.zeros(width)
    pmf[0] = 1.0
    for pi, vi in zip(p, v):
        nxt = (1.0 - pi) * pmf
        nxt[vi:] += pi * pmf[:width - vi]
        pmf = nxt
    return pmf


def freq_tol(rows: int) -> float:
    """Relative error of one frequency Q_k of an f32 log-CF over m
    tuples: log Q_k sums m terms of magnitude <= ~20 (the p = 1/2
    cancellation is floored at log 1e-30 / 2 < 35, and rarely near it),
    so it carries ~20 sqrt(m) * 2^-24; 16x headroom."""
    return 16 * U * 20 * math.sqrt(max(rows, 1))


def check_pmf(name: str, pmf, want, rows: int, mean, var) -> list:
    """An exact distribution against the f64 convolution ``want``, the
    closed-form ``mean`` sum p v and ``var`` sum p (1-p) v^2.

    Coefficient j is (1/N) sum_k Q_k w^-jk, so errors of relative size e
    in every Q_k move it by at most e (1/N) sum_k |Q_k| (the reference's
    |Q_k|, which decay with the distribution's width); the inverse FFT
    adds log2(N) roundings of sqrt(sum_k |Q_k|^2 / N) = ||pmf||_2
    (Parseval), 16x headroom.  The total is Q_0 = 1 up to e; clipping
    the noise of the N coefficients at 0 adds at most its l1 norm, which
    Cauchy-Schwarz and Parseval bound by the per-frequency error times
    sqrt(sum_k |Q_k|^2) = sqrt(N) ||pmf||_2.  Moments are taken over the
    window within 10 sigma of the closed-form mean, where the true pmf
    holds all but ~1e-20 of its mass; their tolerance is the coefficient
    tolerance weighted by |k - mean| and (k - mean)^2."""
    pmf = np.asarray(pmf, np.float64)
    n = pmf.shape[-1]
    e = freq_tol(rows)
    fft = 16 * U * math.log2(n)
    l2 = np.sqrt(np.sum(want * want, axis=-1))
    q1 = np.abs(np.fft.fft(want, axis=-1)).sum(-1) / n
    tol = (e * q1 + fft * l2)[..., None]
    total_tol = (e + fft) * (1 + np.sqrt(n) * l2)
    k = np.arange(n)
    mean, var = np.asarray(mean, np.float64), np.asarray(var, np.float64)
    d = k - mean[..., None]
    win = np.abs(d) <= 10 * np.sqrt(var[..., None]) + 1
    first = np.sum(np.where(win, d * pmf, 0.0), axis=-1)
    second = np.sum(np.where(win, d * d * pmf, 0.0), axis=-1)
    return [close(f"{name}.pmf", pmf, want, tol),
            close(f"{name}.total", pmf.sum(-1), 1.0, total_tol),
            close(f"{name}.mean", first, 0.0,
                  np.sum(np.where(win, np.abs(d) * tol, 0.0), axis=-1)),
            close(f"{name}.var", second, var,
                  np.sum(np.where(win, d * d * tol, 0.0), axis=-1))]


# -------------------------------------------------------------- engine glue
def kernel_check(name: str, fn, *args, expect: bool) -> str:
    """The program ``fn`` runs for ``args`` (its cached compile) must hold
    a Pallas call where the plan dispatches one."""
    text = fn.lower(*args).compile().as_text()
    if expect:
        require(KERNEL_MARK in text,
                f"{name}: no {KERNEL_MARK} in the compiled program (the "
                "plan fell back to the jnp oracle)")
        return f"{name} kernel"
    return f"{name} lowered"


def clean(name: str, info: dict) -> None:
    """No exchange or group overflow, no NaN state, one attempt."""
    report = info["report"]
    require(report.ok(), f"{name}: {report.describe()}")
    require(info["attempts"] == 1,
            f"{name}: {info['attempts']} attempts")


def setup(orders: int, seed: int):
    """The tables, made from the seed and put on the device."""
    db = tpch.generate(n_orders=orders, seed=seed)
    jax.block_until_ready(jax.tree.leaves(db))
    nbytes = sum(int(x.nbytes) for x in jax.tree.leaves(db.lineitem))
    return [f"{orders} orders, lineitem {db.lineitem.capacity} rows "
            f"{nbytes} bytes"], db


# -------------------------------------------------------------------- phases
KERNEL_PLANS = ("q1", "q3", "q6")      # cumulants riders -> Pallas kernels


def served(db, h, *, rounds: int = 3, sweep: int = 64,
           expect_kernels: bool = True, compiles=None):
    """QueryService over the TPC-H plans: a cold round, then rounds that
    must be plan-cache hits with bit-identical answers and no compiles;
    then the batched Q6 sweep.  Every answer is checked against h."""
    svc = QueryService(db.tables(), policy=RetryPolicy())
    sizes = tpch.serving_group_sizes(db)
    plans = tpch.serving_plans(**sizes)
    c0 = compiles.count if compiles else 0
    history = serve_rounds(svc, plans, 1)
    c1 = compiles.count if compiles else 0
    history += serve_rounds(svc, plans, rounds - 1)
    warm_compiles = (compiles.count - c1) if compiles else 0
    checks = [check_device_accuracy(), f"groups {sizes}", f"cold round {history[0][2]:.3f} s "
              f"({c1 - c0} compiles)"]
    first = history[0][0]
    for r, (outs, infos, dt) in enumerate(history):
        for name, info in infos.items():
            clean(f"round {r} {name}", info)
            if r:
                require(info["hit"], f"round {r} {name}: plan-cache miss")
                bit_equal(f"round {r} {name}", outs[name], first[name])
        if r:
            checks.append(f"round {r} {dt:.3f} s all hits, bit-equal")
    if compiles:
        require(warm_compiles == 0,
                f"warm rounds built {warm_compiles} programs")
        checks.append("warm rounds 0 compiles")
    for name in KERNEL_PLANS:
        (entry,) = [e for e in svc.cache.entries(plans[name])
                    if hasattr(e.call, "lower")]
        checks.append(kernel_check(name, entry.call, svc.tables, None,
                                   expect=expect_kernels))

    keys, st = ref_q1(h)
    checks += check_groups("q1", first["q1"],
                           ("l_returnflag", "l_linestatus"), keys, st,
                           dict(sum=(st["qty"], "normal"),
                                price=(st["price"], "normal"),
                                count=(st["count"], "normal"),
                                cumulants_qty=(st["qty"], "cumulants")))
    keys, st = ref_q3(h)
    checks += check_groups("q3", first["q3"], ("l_orderkey",), keys, st,
                           dict(sum=(st["rev"], "normal"),
                                cumulants=(st["rev"], "cumulants")))
    checks += check_scalar_q6("q6", first["q6"], h)
    checks += check_table("q18", first["q18"], "l_orderkey",
                          *ref_q18(h))
    checks += check_table("q20", first["q20"], "s_name", *ref_q20(h))

    batch = sweep_points(sweep)
    outs = []
    for i in range(2):                  # cold (compiles), then warm
        t0 = time.perf_counter()
        out, info = svc.sweep(tpch.q6_family(), batch)
        jax.block_until_ready(jax.tree.leaves(out))
        outs.append(out)
        checks.append(f"sweep {sweep} points {time.perf_counter() - t0:.3f} "
                      f"s in {info['launches']} launch(es)"
                      + (", hit, bit-equal" if i else ""))
    require(info["hit"], "warm sweep: plan-cache miss")
    bit_equal("warm sweep", outs[1], outs[0])
    (entry,) = svc.cache.entries(tpch.q6_family())
    checks.append(kernel_check("sweep", entry.batched, svc.tables, batch,
                               expect=expect_kernels))
    ref = ref_sweep(h, np.asarray(batch["qty_lim"]))
    mu, var = (np.asarray(x)[:, 0] for x in out["sum"])
    checks.append(close("sweep.mean", mu, ref["mu"],
                        sum_tol(ref["rows"], ref["mu"])))
    checks.append(close("sweep.var", var, ref["var"],
                        sum_tol(ref["rows"], ref["var"])))
    terms = np.asarray(out["cumulants"])[:, 0]
    checks.append(close("sweep.k1", terms[:, 0], ref["mu"],
                        sum_tol(ref["rows"], ref["mu"])))
    return checks, (svc, first)


def check_scalar_q6(name: str, out: dict, h: dict) -> list:
    li = h["lineitem"]
    m = q6_mask(li)
    p, v = li["p"][m], (li["l_quantity"] * li["l_discount"])[m]
    mu, var = np.sum(p * v), np.sum(p * (1 - p) * v * v)
    rows = int(m.sum())
    got_mu, got_var = (np.asarray(x)[0] for x in out["sum"])
    terms = np.asarray(out["cumulants"])[0]
    return [close(f"{name}.mean", got_mu, mu, sum_tol(rows, mu)),
            close(f"{name}.var", got_var, var, sum_tol(rows, var)),
            close(f"{name}.k1", terms[0], mu, sum_tol(rows, mu)),
            close(f"{name}.k2", terms[1], var, sum_tol(rows, var))]


def count_plan(count_orders: int, num_freq: int):
    """Fig. 9: the exact COUNT distribution over a lineitem selection."""
    return GroupAgg(Select(Scan("lineitem"),
                           lambda t: t["l_orderkey"] < count_orders),
                    (), "", "COUNT", 1, "exact", num_freq=num_freq)


def per_order_plan(exact_orders: int, num_freq: int = 256):
    """Exact per-order SUM(l_quantity) distribution (grouped exact-CF)
    over the orders with keys below ``exact_orders``."""
    return GroupAgg(Select(Scan("lineitem"),
                           lambda t: t["l_orderkey"] < exact_orders),
                    ("l_orderkey",), "l_quantity", "SUM", exact_orders,
                    "exact", num_freq=num_freq)


def per_order_pmf(h: dict, exact_orders: int, width: int) -> np.ndarray:
    """Reference pmf of each order's quantity sum: one tuple per order
    at a time, all orders at once (f64)."""
    li = h["lineitem"]
    m = li["valid"] & (li["l_orderkey"] < exact_orders)
    key, p, v = li["l_orderkey"][m], li["p"][m], li["l_quantity"][m]
    order = np.argsort(key, kind="stable")
    key, p, v = key[order], p[order], v[order]
    rank = np.arange(key.size) - np.searchsorted(key, key)
    pmf = np.zeros((exact_orders, width))
    pmf[:, 0] = 1.0
    cols = np.arange(width)
    for r in range(int(rank.max(initial=-1)) + 1):
        sel = rank == r
        g, pr, vr = key[sel], p[sel], v[sel]
        src = cols[None, :] - vr[:, None]
        shifted = np.where(src >= 0, np.take_along_axis(
            pmf[g], np.clip(src, 0, None), axis=1), 0.0)
        pmf[g] = (1 - pr)[:, None] * pmf[g] + pr[:, None] * shifted
    return pmf


def exact(db, h, svc, *, count_orders: int, exact_orders: int,
          expect_kernels: bool = True):
    """Exact distributions: scalar COUNT (pb_cf kernel) and per-order
    SUM (group_cf kernel), each against an f64 convolution."""
    li = h["lineitem"]
    m = li["valid"] & (li["l_orderkey"] < count_orders)
    rows = int(m.sum())
    num_freq = 1 << max(7, rows.bit_length())      # > max COUNT
    checks = [f"count over {rows} rows, {num_freq} frequencies"]
    plan = count_plan(count_orders, num_freq)
    out, info = svc.submit(plan)
    jax.block_until_ready(jax.tree.leaves(out))
    clean("count", info)
    (entry,) = svc.cache.entries(plan)
    checks.append(kernel_check("count", entry.call, svc.tables, None,
                               expect=expect_kernels))
    p = li["p"][m]
    want = pb_pmf(p, np.ones(rows, np.int64), num_freq)
    checks += check_pmf("count", np.asarray(out["exact"])[0], want, rows,
                        np.sum(p), np.sum(p * (1 - p)))

    plan = per_order_plan(exact_orders)
    out, info = svc.submit(plan)
    jax.block_until_ready(jax.tree.leaves(out))
    clean("per_order", info)
    (entry,) = svc.cache.entries(plan)
    checks.append(kernel_check("per_order", entry.call, svc.tables, None,
                               expect=expect_kernels))
    checks += check_per_order("per_order", out, h, exact_orders)
    checks.append(f"per_order {exact_orders} groups")
    return checks, None


def check_per_order(name: str, out: dict, h: dict, exact_orders: int):
    """The per-order exact SUM(l_quantity) against its f64 convolution."""
    li = h["lineitem"]
    valid = np.asarray(out["valid"])
    require(valid.all(), f"{name}: some order has no group")
    require(np.array_equal(np.asarray(out["keys"]["l_orderkey"]),
                           np.arange(exact_orders)), f"{name}: keys")
    want = per_order_pmf(h, exact_orders, 256)
    m = li["valid"] & (li["l_orderkey"] < exact_orders)
    st = group_stats(li["l_orderkey"][m], exact_orders, li["p"][m],
                     li["l_quantity"][m])
    return check_pmf(name, np.asarray(out["exact"]), want,
                     int(st["rows"].max()), st["mu"], st["var"])


def streamed(db, svc, *, min_waves: int = 8):
    """Q6 streamed from host in waves (buffer donation on), bit-equal to
    the resident eager run."""
    cap = db.lineitem.capacity
    budget = max(1, cap // (4 * min_waves))
    out, info = svc.submit(tpch.q6_plan(), device_row_budget=budget)
    jax.block_until_ready(jax.tree.leaves(out))
    clean("streamed", info)
    waves = info["report"].waves
    require(waves.get("total", 0) >= min_waves,
            f"streamed: {waves} (want >= {min_waves} waves)")
    resident = compile_plan(tpch.q6_plan())(db.tables())
    bit_equal("streamed vs resident", out, resident)
    return [f"{waves['total']} waves, budget {budget} rows",
            "bit-equal to resident"], out


# ----------------------------------------------------------------- 4 chips
def shard_tables(tables: dict, mesh) -> dict:
    """Row-partition every table whose row count the data axis divides
    (replicated over any other axis); replicate the rest."""
    n = mesh.shape["data"]

    def put(t):
        spec = P("data") if t.capacity % n == 0 else P()
        return jax.device_put(t, NamedSharding(mesh, spec))

    return {k: put(t) for k, t in tables.items()}


def spans(name: str, tree, n: int) -> None:
    for i, leaf in enumerate(jax.tree.leaves(tree)):
        got = len(leaf.sharding.device_set)
        require(got == n, f"{name} leaf {i} spans {got} devices, not {n}")


def leaf_diffs(got, want) -> list:
    """Leaves where ``got`` and ``want`` differ: (path, count, size,
    largest difference in f32 ulps of ``want``)."""
    out = []
    for (path, x), y in zip(jax.tree_util.tree_leaves_with_path(got),
                            jax.tree.leaves(want)):
        x, y = np.asarray(x), np.asarray(y)
        same = x == y
        if np.issubdtype(x.dtype, np.inexact):
            same |= np.isnan(x) & np.isnan(y)
        if same.all():
            continue
        ulps = float("nan")
        if np.issubdtype(x.dtype, np.inexact):
            d = np.abs(x.astype(np.float64) - y)[~same]
            ulps = float(np.max(d / np.spacing(np.abs(y[~same]))))
        out.append((jax.tree_util.keystr(path), int((~same).sum()),
                    x.size, ulps))
    return out


def mesh_vs_single(name: str, got, want, failures: list) -> str:
    """Bit equality per leaf; a difference is described (count, size and
    largest size in ulps) and kept in ``failures``."""
    diffs = leaf_diffs(got, want)
    if not diffs:
        return f"{name} bit-equal"
    failures.append(f"{name}: " + "; ".join(
        f"{p} {n} of {size} differ, max {u:.3g} ulps"
        for p, n, size, u in diffs))
    return f"{name} NOT bit-equal"


def sharded(db, h, *, exact_orders: int, devices=None):
    """Q3 through the co-partitioned join + partitioned aggregation on a
    4-way data mesh, and per-order exact-CF on a (2, 2) data x model
    mesh, each bit-equal to mesh=None in this process and within the f64
    reference's bounds.  Both sides run jitted (the contract holds jit
    against jit as it does eager against eager; eager execution builds
    over a thousand programs per side)."""
    devices = devices or jax.devices()[:4]
    require(len(devices) == 4, f"sharded: {len(devices)} devices, need 4")
    auto = jax.sharding.AxisType.Auto
    tables = db.tables()
    checks, failures = [], []

    mesh = jax.make_mesh((4,), ("data",), axis_types=(auto,),
                         devices=devices)
    keys, st = ref_q3(h)
    plan = tpch.q3_plan(max_groups=keys.size, order_join_budget=4)
    caps = {k: t.capacity for k, t in tables.items()}
    lowered = phys.explain(phys.lower_plan(plan, caps, n_shards=4,
                                           sharded=True))
    require("CoPartitionedJoin" in lowered and "PartitionedAgg" in lowered,
            f"q3 lowering:\n{lowered}")
    placed = shard_tables(tables, mesh)
    spans("q3 input", placed["lineitem"], 4)
    got, report = jax.jit(compile_plan(plan, mesh, with_report=True))(placed)
    jax.block_until_ready(jax.tree.leaves(got))
    require(report.ok(), f"q3 mesh: {report.describe()}")
    require(any(k.startswith("copart_join")
                for k in report.exchange_overflow),
            f"q3 mesh ran no co-partitioned join: {report.exchange_overflow}")
    spans("q3 result", got, 4)
    want, _ = jax.jit(compile_plan(plan, None, with_report=True))(tables)
    checks.append("q3 CoPartitionedJoin+PartitionedAgg on 4 devices")
    checks.append(mesh_vs_single("q3 mesh vs single", got, want, failures))
    checks += check_groups("q3 mesh", got, ("l_orderkey",), keys, st,
                           dict(sum=(st["rev"], "normal"),
                                cumulants=(st["rev"], "cumulants")))

    mesh = jax.make_mesh((2, 2), ("data", "model"), axis_types=(auto,) * 2,
                         devices=devices)
    plan = per_order_plan(exact_orders)
    placed = shard_tables(tables, mesh)
    spans("exact input", placed["lineitem"], 4)
    got, report = jax.jit(compile_plan(plan, mesh, with_report=True))(placed)
    jax.block_until_ready(jax.tree.leaves(got))
    require(report.ok(), f"exact mesh: {report.describe()}")
    spans("exact result", got, 4)
    want, _ = jax.jit(compile_plan(plan, None, with_report=True))(tables)
    checks.append(f"per-order exact-CF ({exact_orders} groups) on a 2x2 "
                  "data x model mesh")
    checks.append(mesh_vs_single("exact 2x2 vs single", got, want,
                                 failures))
    checks += check_per_order("exact 2x2", got, h, exact_orders)
    if failures:
        print("[smoke] sharded: " + "; ".join(checks), flush=True)
    require(not failures, " | ".join(failures))
    return checks, None


# -------------------------------------------------------------------- main
def parse(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: run only the sharded phase on a 2x2 host")
    ap.add_argument("--seed", type=int, default=0)
    return ap.parse_args(argv)


def main(argv=None) -> int:
    args = parse(argv)
    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: JAX found no TPU (platform {dev.platform!r}); "
              "nothing was run", file=sys.stderr)
        return 1
    if len(devices) < args.chips:
        print(f"chip_smoke: --chips {args.chips} but JAX sees "
              f"{len(devices)} device(s)", file=sys.stderr)
        return 1
    used = devices[:args.chips]
    print(f"[smoke] compile cache: {use_compile_cache()}", flush=True)
    counter = CompileCounter()
    db = run_phase("setup", counter, setup, orders=ORDERS, seed=args.seed)
    h = host(db)
    if args.chips == 4:
        run_phase("sharded", counter, sharded, db=db, h=h,
                  exact_orders=EXACT_ORDERS, devices=used)
    else:
        svc, _ = run_phase("served", counter, served, db=db, h=h,
                           compiles=counter)
        run_phase("exact", counter, exact, db=db, h=h, svc=svc,
                  count_orders=COUNT_ORDERS, exact_orders=EXACT_ORDERS)
        run_phase("streamed", counter, streamed, db=db, svc=svc)
    stats = dev.memory_stats() or {}
    print(f"[smoke] peak_bytes_in_use: {stats.get('peak_bytes_in_use')}",
          flush=True)
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(used)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
