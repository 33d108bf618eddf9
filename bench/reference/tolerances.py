"""Written error bounds of an f32 engine answer against an f64 reference.

Copied from the program's chip smoke test so that the yardstick stays
fixed.  Every engine number is an f32 sum over m rows of terms that are
each a few roundings away from exact; the references are f64.  The
device's f32 log1p, exp and erfc are not correctly rounded, so the bounds
that pass through them take those functions' errors as fixed constants
(``DEVICE_ULPS``), and every run checks that the device stays within
them (:func:`device_accuracy`): a run whose device exceeds one is not
correct.

A tuple probability that a join formed as a product of f32 factors
carries one rounding a product (relative error 2^-24 each), which the
sums and the logarithm amplify: :func:`rounding_terms` gives, per group,
the first-order error that those roundings can cause in each closed-form
sum, and the bounds add it.
"""
from __future__ import annotations

import math

import numpy as np
from scipy import special

U = 2.0 ** -24                  # f32 unit roundoff

# Largest relative error of the device's f32 log1p(-p), exp and erfc, in
# f32 ulps.  Measured on a TPU v5e over the grids of device_accuracy():
# log1p 6,165, exp 97, erfc 110; about 1.3x that here, because the grids
# sample about one f32 value in a thousand.
DEVICE_ULPS = dict(log1p=8192, exp=128, erfc=160)
ACC = {k: v * U for k, v in DEVICE_ULPS.items()}


def sum_tol(rows, scale):
    """f32 accumulation of m terms of one sign: the roundings fall on
    either side, so the error grows like sqrt(m) * 2^-24 of the sum's
    magnitude; 16x headroom and a few-ulp floor for m = 1."""
    return (4.0 + 16.0 * np.sqrt(np.maximum(rows, 1))) * U * np.abs(scale)


def rounding_terms(p, v, roundings: int) -> dict:
    """First-order error, per tuple, of the closed-form terms when each
    probability ``p`` is off by ``roundings`` f32 roundings (relative
    error ``roundings`` x 2^-24): p v moves by that share of itself;
    p (1 - p) v^2 by |1 - 2p| p v^2 times it; log(1 - p) by p / (1 - p)
    times it.  Sum them per group with the terms they bound."""
    if not roundings:
        return dict(mu=0.0, var=0.0, log_none=0.0)
    p = np.asarray(p, np.float64)
    v = np.asarray(v, np.float64)
    e = roundings * U
    # a product of f32 factors that is exactly 1 has every factor 1 and
    # no rounding
    q = np.where(p < 1, 1 - p, 1.0)
    return dict(mu=e * np.abs(p * v), var=e * np.abs(1 - 2 * p) * p * v * v,
                log_none=np.where(p < 1, e * p / q, 0.0))


def conf_tol(rows, log_none, dlog_p=0.0):
    """1 - exp(L) with L = sum log1p(-p): L is off by the device log1p's
    relative error plus :func:`sum_tol`, plus ``dlog_p`` (the rounding of
    each p, :func:`rounding_terms`); exp adds its own relative error of
    exp(L); a few ulps for the subtraction."""
    with np.errstate(invalid="ignore"):
        dlog = (sum_tol(rows, log_none) + ACC["log1p"] * np.abs(log_none)
                + dlog_p)
        e = np.exp(log_none)
        # a tuple with p = 1 makes L = -inf and the confidence exactly 1
        return np.where(e > 0, e * dlog, 0.0) + ACC["exp"] * e + 4 * U


def tail_tol(mu, var, thr, dmu, dvar):
    """P(N(mu, var) > thr): dP <= phi(z) * dz with
    dz <= (dmu + |z| * dsigma) / sigma, phi <= 1/sqrt(2 pi); plus the
    device erfc's relative error of P."""
    sigma = np.sqrt(np.maximum(var, 1e-30))
    z = (thr - mu) / sigma
    dz = (dmu + np.abs(z) * dvar / (2 * sigma)) / sigma
    p_gt = 0.5 * special.erfc(z / math.sqrt(2.0))
    return dz / math.sqrt(2 * math.pi) + ACC["erfc"] * p_gt + 4 * U


def device_accuracy() -> dict:
    """Relative error of the device's f32 ``log1p(-p)`` over p in (0, 1),
    ``exp`` over [-40, 0] and ``erfc`` over [-6, 6]: the largest on 2^20
    points against numpy in f64, in ulps."""
    import jax
    import jax.numpy as jnp
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
