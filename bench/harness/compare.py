"""The comparison that decides ``correct``: an answer summary against
the f64 reference summary of its parameter point.

Each comparison gives two numbers:

* ``keys``: how many output keys differ from the reference's (missing,
  extra, or duplicated), an exact comparison;
* ``err``: the largest error of any float of the answer over its written
  bound (``bench/reference/tolerances.py``), over the keys both sides
  hold.  A NaN or infinite answer reads infinite.

and ``part``, the name of the float that read the largest ``err``.
"""
from __future__ import annotations

import numpy as np

from bench.reference import tpch_ref as R


def _codes(a, b):
    """One int64 code per key row of two key arrays, on one code book
    (keys are non-negative integers; several columns are coded
    row-major)."""
    a, b = np.asarray(a, np.int64), np.asarray(b, np.int64)
    a = a.reshape(a.shape[0], -1) if a.ndim > 1 else a[:, None]
    b = b.reshape(b.shape[0], -1) if b.ndim > 1 else b[:, None]
    if a.shape[1] == 0:
        return np.zeros(len(a), np.int64), np.zeros(len(b), np.int64)
    if a.shape[1] == 1:
        return a[:, 0], b[:, 0]
    both = np.concatenate([a, b], axis=0)
    dims = both.max(axis=0, initial=0) + 1
    code = np.ravel_multi_index(tuple(both.T), tuple(dims))
    return code[:len(a)], code[len(a):]


def align(got_keys, ref_keys) -> tuple:
    """-> (key mismatches, got index, ref index) of the keys both hold."""
    cg, cr = _codes(got_keys, ref_keys)
    dup = len(cg) - np.unique(cg).size
    common, ig, ir = np.intersect1d(cg, cr, assume_unique=False,
                                    return_indices=True)
    miss = (len(cg) - dup - common.size) + (len(cr) - common.size)
    return int(miss + dup), ig, ir


class _Worst:
    """The largest err/tol ratio seen, and where."""

    def __init__(self):
        self.err, self.part = 0.0, ""

    def add(self, part: str, got, want, tol) -> None:
        got = np.asarray(got, np.float64)
        want = np.broadcast_to(np.asarray(want, np.float64), got.shape)
        tol = np.broadcast_to(np.asarray(tol, np.float64), got.shape)
        if got.size == 0:
            return
        if not np.all(np.isfinite(got)):
            r = np.inf
        else:
            with np.errstate(invalid="ignore"):
                ratio = np.abs(got - want) / np.maximum(tol, 1e-300)
            # a NaN bound or reference reads as a failure, never as 0
            r = float(np.max(np.where(np.isnan(ratio), np.inf, ratio)))
        if r > self.err or not self.part:
            self.err, self.part = r, part

    def result(self, keys: int) -> dict:
        return dict(keys=keys, err=self.err, part=self.part)


def groupagg(got: dict, ref: dict) -> dict:
    """A group-aggregate summary: keys, confidence, each aggregate's mean
    and variance."""
    miss, ig, ir = align(got["keys"], ref["keys"])
    tol = R.groupagg_tols(ref)
    w = _Worst()
    w.add("conf", np.asarray(got["conf"])[ig], ref["conf"][ir],
          tol["conf"][ir])
    for a in ref["mean"]:
        w.add(f"{a}.mean", np.asarray(got["mean"][a])[ig],
              ref["mean"][a][ir], tol["mean"][a][ir])
        w.add(f"{a}.var", np.asarray(got["var"][a])[ig],
              ref["var"][a][ir], tol["var"][a][ir])
    return w.result(miss)


def table(got: dict, ref: dict) -> dict:
    """A reweighted or projected table: keys and tuple probabilities."""
    miss, ig, ir = align(got["keys"], ref["keys"])
    w = _Worst()
    w.add("prob", np.asarray(got["prob"])[ig], ref["prob"][ir],
          ref["tol"][ir])
    return w.result(miss)
