"""Host summaries of engine answers, in the structure the references
give (see ``bench/reference/tpch_ref.py``)."""
from __future__ import annotations

import numpy as np


def groupagg(out: dict, key_names: tuple, aggs: dict) -> dict:
    """A GroupAgg result: ``aggs`` maps each output name to its method
    (``normal`` gives (mean, variance); ``cumulants`` gives terms whose
    first two are k1 = mean and k2 = variance)."""
    valid = np.asarray(out["valid"]).astype(bool).reshape(-1)
    if key_names:
        keys = np.stack([np.asarray(out["keys"][k]).reshape(-1)[valid]
                         for k in key_names], axis=1).astype(np.int64)
    else:
        keys = np.zeros((int(valid.sum()), 0), np.int64)
    mean, var = {}, {}
    for a, method in aggs.items():
        if method == "normal":
            mu, v = out[a]
            mean[a] = np.asarray(mu).reshape(-1)[valid]
            var[a] = np.asarray(v).reshape(-1)[valid]
        else:
            terms = np.asarray(out[a]).reshape(valid.size, -1)[valid]
            mean[a], var[a] = terms[:, 0], terms[:, 1]
    return dict(keys=keys,
                conf=np.asarray(out["confidence"]).reshape(-1)[valid],
                mean=mean, var=var)


def table(t, key: str) -> dict:
    """A Table result: its valid rows' key column and probabilities."""
    valid = np.asarray(t.valid).astype(bool)
    return dict(keys=np.asarray(t[key])[valid].astype(np.int64),
                prob=np.asarray(t.prob)[valid])
