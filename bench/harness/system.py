"""The system under test, as the benchmark drives it: the program's
tables on the device, its mesh, and ``QueryService.submit``.

This is the one module of the harness that imports the program (apart
from the plan IR the query modules build plans from).
"""
from __future__ import annotations

import numpy as np


def host_tables(cols: dict) -> dict:
    """The generator's columns as the host reference reads them: int64
    columns, probabilities rounded to the f32 the device holds (widened
    back to f64), every row valid."""
    out = {}
    for name, t in cols.items():
        c = {k: (np.asarray(v) if k != "p" else
                 np.asarray(v, np.float32).astype(np.float64))
             for k, v in t.items()}
        c["valid"] = np.ones(c["p"].shape[0], bool)
        out[name] = c
    return out


class System:
    def __init__(self, h: dict, config: dict, devices: list):
        import jax
        from jax.sharding import NamedSharding, PartitionSpec as P

        from repro.db.plans import RetryPolicy
        from repro.db.serving import QueryService
        from repro.db.table import Table

        mesh_cfg = config.get("mesh")
        shards = 1
        self.mesh = None
        if mesh_cfg:
            auto = jax.sharding.AxisType.Auto
            self.mesh = jax.make_mesh(
                tuple(mesh_cfg["shape"]), tuple(mesh_cfg["axes"]),
                axis_types=(auto,) * len(mesh_cfg["axes"]),
                devices=devices)
            shards = self.mesh.shape["data"]
        host = {}
        for name, t in h.items():
            cols = {k: (v.astype(np.int32) if v.dtype != bool else v)
                    for k, v in t.items() if k not in ("p", "valid")}
            host[name] = (cols, t["p"].astype(np.float32), t["valid"])
        if self.mesh is None:
            placed = jax.device_put(host, devices[0])
        else:
            placed = {}
            for name, leaves in host.items():
                n = leaves[1].shape[0]
                spec = P("data") if n % shards == 0 else P()
                placed[name] = jax.device_put(
                    leaves, NamedSharding(self.mesh, spec))
        tables = {name: Table.from_columns(cols, prob=p, valid=v)
                  for name, (cols, p, v) in placed.items()}
        jax.block_until_ready(tables)
        self.service = QueryService(tables, self.mesh, policy=RetryPolicy())

    def submit(self, plan, params: dict):
        """One request through ``QueryService.submit``; returns its answer
        with every leaf ready, and the service's info (``attempts``,
        ``report``)."""
        import jax
        out, info = self.service.submit(plan, params)
        jax.block_until_ready(jax.tree.leaves(out))
        return out, info

    def close(self) -> None:
        self.service.cache.clear()
        self.service = None

