"""The query-serving layer: bounded structural plan cache + batched
parameterized execution — the front end that turns the engine from a
script into a server.

A production deployment answers MANY queries against one catalogue, and
the raw compiler is the wrong interface for that twice over:

* every ``compile_plan`` call re-traces from scratch, even for a plan
  structurally identical to one compiled a moment ago (logical nodes
  carry lambdas, which hash by identity); and
* the CPU jaxlib backend SEGFAULTS once a single process accretes a few
  hundred live compiled executables — the failure PR 7 documented, which
  the test suite masks by clearing jit caches at module boundaries.  A
  long-lived server cannot use that workaround; it must BOUND its
  executable population instead.

:class:`PlanCache` solves both: compiled executables are cached under a
STRUCTURAL key — :func:`repro.db.plans.plan_key` (node structure +
predicate bytecode + captured constants) + mesh identity + every
lowering parameter — in a bounded LRU whose evictions drop the evicted
executables' compiled code (``jit.clear_cache``).  A cache hit returns
the SAME executable object, so hit results are BIT-IDENTICAL to the cold
compile by construction; distinct plans past the capacity recycle slots
instead of accreting.

:class:`QueryService` is the request loop over one catalogue: submit a
logical plan (optionally with a :class:`~repro.db.plans.RetryPolicy` —
the self-healing controller compiles each attempt through the cache, and
the service REMEMBERS the converged ``final_params`` per plan so later
identical submits start at the healed point and hit the cache in one
clean attempt), or sweep a PARAMETERIZED plan family over N parameter
points: the plan's :class:`~repro.db.plans.Param` holes become traced
arguments, one executable is compiled for the family, and the whole
sweep runs as ONE device program — a 64-point what-if sweep costs one
compile instead of 64 (``benchmarks/smoke.py`` gates the speedup).

Two batching modes.  The default, ``sweep_mode='scan'``, lowers the
sweep as ``jax.lax.map`` — each point executes the IDENTICAL unbatched
graph inside one device loop, so per-point slices are bit-equal to N
sequential runs of the family's jitted executable (the engine's
determinism contract extended to the batch).  ``sweep_mode='vmap'``
vectorises across points instead; XLA fuses batched shapes differently
(FMA/reassociation differs per batch size on CPU), so vmap trades the
bit-equality guarantee for lane-parallel throughput — results still
match sequential runs to ~1 ULP.  Both modes pass the catalogue as an
executable ARGUMENT, never a closure: constant-folding baked-in table
columns changes fusion rounding, which is exactly the bug class this
layer exists to keep out of cached paths.  See docs/serving.md.
"""
from __future__ import annotations

import time
from typing import Dict

import jax
import jax.numpy as jnp

from . import cost as C
from . import physical as phys
from .plans import (LRUCache, Node, Scan, compile_plan, mesh_fingerprint,
                    plan_key, plan_params, run_plan)
from .report import ServingStats
from .table import Table


def cache_key(root: Node, mesh=None, jit: bool = True,
              opts: dict | None = None) -> tuple:
    """The plan cache's full key: plan structure + mesh identity + jit
    wrapping + every lowering option (frozen structurally, so option
    values like a CostModel dataclass key by content)."""
    frozen = tuple(sorted((k, phys.structural_key(v))
                          for k, v in (opts or {}).items()))
    return ("serve", plan_key(root), mesh_fingerprint(mesh), bool(jit),
            frozen)


def _scan_names(root: Node) -> tuple:
    """Base tables a logical plan reads (for sweep residency sizing)."""
    names: set = set()

    def walk(n):
        if isinstance(n, Scan):
            names.add(n.name)
        for f in ("child", "left", "right"):
            c = getattr(n, f, None)
            if isinstance(c, Node):
                walk(c)

    walk(root)
    return tuple(sorted(names))


class _Entry:
    """One cached plan: the raw compiled closure, the submit-path
    callable (jit-wrapped unless the plan streams), and the lazily built
    batched sweep executable (tables are an argument, so one executable
    serves any catalogue of the same shapes)."""
    __slots__ = ("fn", "call", "batched", "batched_mode", "__weakref__")

    def __init__(self, fn, call):
        self.fn = fn
        self.call = call
        self.batched = None
        self.batched_mode = None


class PlanCache:
    """Bounded LRU of compiled plan executables, keyed structurally.

    A hit returns the same executable object — results bit-identical to
    the cold compile by construction.  Evictions call ``clear_cache`` on
    the evicted jit wrappers so the process's live-executable count
    stays flat (the accretion-segfault guard a long-lived server needs).
    """

    def __init__(self, capacity: int = 16):
        self._lru = LRUCache(capacity, on_evict=self._drop)

    @staticmethod
    def _drop(entry: _Entry) -> None:
        for f in (entry.call, entry.batched):
            clear = getattr(f, "clear_cache", None)
            if clear is not None:
                clear()

    def entry(self, root: Node, mesh=None, jit: bool = True,
              **opts) -> tuple:
        """-> (cache entry, was it a hit).  ``jit=True`` wraps the
        compiled function in ``jax.jit`` (illegal for streamed plans —
        the wave loop runs on host; callers gate on
        ``device_row_budget``)."""
        key = cache_key(root, mesh, jit, opts)
        e = self._lru.get(key)
        if e is not None:
            return e, True
        fn = compile_plan(root, mesh, **opts)
        e = _Entry(fn, jax.jit(fn) if jit else fn)
        self._lru.put(key, e)
        return e, False

    def compile(self, root: Node, mesh=None, jit: bool = False, **opts):
        """:func:`repro.db.plans.run_plan`-compatible compiler hook: the
        cached executable for THIS attempt's exact (plan, lowering
        params).  Each escalation attempt keys its own entry, so retries
        never poison or duplicate the base entry, and a later submit at
        the converged ``final_params`` hits the final attempt's entry."""
        return self.entry(root, mesh, jit=jit, **opts)[0].call

    def entries(self, root: Node) -> list:
        """Every cached entry compiled for ``root``, whatever the mesh or
        lowering options: what a caller inspects to see which programs a
        plan runs (``entry.call``, ``entry.batched``)."""
        pk = plan_key(root)
        return [e for k, e in self._lru.items() if k[1] == pk]

    @property
    def hits(self) -> int:
        return self._lru.hits

    @property
    def misses(self) -> int:
        return self._lru.misses

    def info(self) -> dict:
        return self._lru.info()

    def clear(self) -> None:
        self._lru.clear()


class QueryService:
    """The serving loop over one catalogue (name -> Table) and one mesh.

    ``capacity`` bounds the plan cache; ``jit=True`` (default) serves
    resident submits through ``jax.jit`` (streamed plans — any submit
    with a ``device_row_budget`` — always run eagerly); ``policy`` is
    the default self-healing :class:`~repro.db.plans.RetryPolicy`
    (None = no retry loop); ``batch_row_budget`` caps a sweep's batched
    peak rows, splitting it into chunked launches
    (:func:`repro.db.cost.sweep_chunk_points`); ``sweep_mode`` is
    ``'scan'`` (bit-exact, default) or ``'vmap'`` (lane-parallel, ~1 ULP
    — see the module docstring).  Remaining keywords become default
    ``compile_plan`` options for every request.
    """

    def __init__(self, tables: Dict[str, Table], mesh=None, *,
                 capacity: int = 16, jit: bool = True, policy=None,
                 batch_row_budget: int | None = None,
                 sweep_mode: str = "scan", **default_opts):
        if sweep_mode not in ("scan", "vmap"):
            raise ValueError(f"sweep_mode must be 'scan' or 'vmap', "
                             f"got {sweep_mode!r}")
        self.tables = tables
        self.mesh = mesh
        self.cache = PlanCache(capacity)
        self.jit = jit
        self.policy = policy
        self.batch_row_budget = batch_row_budget
        self.sweep_mode = sweep_mode
        self.default_opts = default_opts
        self.stats = ServingStats()
        #: plan-key -> remembered run_plan escalation overrides, so a
        #: resubmit of a healed plan starts AT its final_params.
        self._healed: Dict[tuple, dict] = {}

    # ------------------------------------------------------------ helpers
    def _merged(self, opts: dict) -> dict:
        return {**self.default_opts, **opts}

    def _use_jit(self, opts: dict) -> bool:
        # Streamed plans execute a host-side wave loop: never jit them.
        return self.jit and opts.get("device_row_budget") is None

    # ------------------------------------------------------------ submit
    def submit(self, root: Node, params: dict | None = None, *,
               policy=None, **opts):
        """Run one query: ``-> (result, info)``.

        ``info`` is a dict with ``hit`` (was the first compile served
        from the plan cache), ``seconds``, ``attempts`` and — when a
        retry policy ran — the final :class:`~repro.db.report.
        ExecutionReport` under ``report``.  Cached hits are bit-identical
        to a cold compile (same executable object); post-retry resubmits
        replay the remembered ``final_params`` and hit the final
        attempt's cache entry in one clean attempt.
        """
        merged = self._merged(opts)
        use_jit = self._use_jit(merged)
        policy = policy if policy is not None else self.policy
        t0 = time.perf_counter()
        h0 = self.cache.hits
        if policy is not None:
            key = cache_key(root, self.mesh, use_jit, merged)
            healed = self._healed.get(key, {})
            out, report = run_plan(root, self.tables, self.mesh,
                                   policy=policy, jit=use_jit,
                                   params=params,
                                   compiler=self.cache.compile,
                                   **{**merged, **healed})
            self._healed[key] = {
                k: v for k, v in report.final_params.items()
                if v is not None
                and not (k in ("kappa_scale", "groups_scale") and v == 1)}
            attempts = int(report.waves.get("attempts", 1))
            hit = self.cache.hits > h0
            self.stats.record(hit=hit, attempts=attempts)
            return out, dict(hit=hit, attempts=attempts,
                             seconds=time.perf_counter() - t0,
                             report=report)
        fn = self.cache.compile(root, self.mesh, jit=use_jit, **merged)
        out = fn(self.tables, params)
        hit = self.cache.hits > h0
        self.stats.record(hit=hit)
        return out, dict(hit=hit, attempts=1,
                         seconds=time.perf_counter() - t0)

    # ------------------------------------------------------------- sweep
    def sweep(self, root: Node, param_batch: Dict[str, jnp.ndarray],
              **opts):
        """Run a parameterized plan family over N parameter points as
        ONE device program: ``-> (batched result, info)``.

        ``param_batch`` maps each of the plan's :class:`~repro.db.plans.
        Param` names to a length-N vector; the result pytree gains a
        leading N axis.  In the default ``sweep_mode='scan'`` each point
        runs the identical unbatched graph inside one device loop, so
        point i of any leaf is BIT-EQUAL to a sequential run of the
        family's jitted executable at point i's scalars — regardless of
        N or chunking; ``'vmap'`` vectorises across points instead (~1
        ULP, see module docstring).  One executable is compiled (and
        cached) for the FAMILY; every further sweep of any size is a
        cache hit.  ``batch_row_budget`` (service-level) caps the
        batched residency by splitting the sweep into chunked launches.
        Streamed plans are not batchable (host wave loop).
        """
        merged = self._merged(opts)
        if merged.get("device_row_budget") is not None:
            raise NotImplementedError(
                "parameter sweeps run the plan under vmap, which cannot "
                "drive the streamed executor's host wave loop: drop "
                "device_row_budget for batched families")
        names = sorted(plan_params(root))
        if not names:
            raise ValueError("sweep() needs a parameterized plan (no "
                             "Param holes found — use submit())")
        batch = {k: jnp.asarray(v) for k, v in param_batch.items()}
        sizes = {k: v.shape[0] for k, v in batch.items()}
        if sorted(batch) != names or len(set(sizes.values())) != 1:
            raise ValueError(
                f"param_batch must map exactly {names} to equal-length "
                f"vectors, got { {k: v.shape for k, v in batch.items()} }")
        n = next(iter(sizes.values()))
        t0 = time.perf_counter()
        h0 = self.cache.hits
        # The entry is cached UNJITTED (jit=False key): the sweep path
        # jits the batched wrapper itself.  Tables are an ARGUMENT of
        # the wrapper — closed-over columns would be constant-folded,
        # and XLA folds/fuses constants with different rounding than the
        # sequential executable sees (breaking bit-equality).
        entry, _ = self.cache.entry(root, self.mesh, jit=False, **merged)
        if entry.batched is None or entry.batched_mode != self.sweep_mode:
            fn = entry.fn
            if self.sweep_mode == "scan":
                entry.batched = jax.jit(lambda tb, pv: jax.lax.map(
                    lambda p: fn(tb, p), pv))
            else:
                entry.batched = jax.jit(lambda tb, pv: jax.vmap(
                    lambda p: fn(tb, p))(pv))
            entry.batched_mode = self.sweep_mode
        chunk = C.sweep_chunk_points(self._per_point_rows(root),
                                     self.batch_row_budget, n)
        outs = [entry.batched(self.tables,
                              {k: v[lo:lo + chunk]
                               for k, v in batch.items()})
                for lo in range(0, n, chunk)]
        out = outs[0] if len(outs) == 1 else jax.tree.map(
            lambda *xs: jnp.concatenate(xs, axis=0), *outs)
        hit = self.cache.hits > h0
        self.stats.record(hit=hit, points=n)
        return out, dict(hit=hit, points=n, chunk=chunk,
                         launches=len(outs),
                         seconds=time.perf_counter() - t0)

    def _per_point_rows(self, root: Node) -> float:
        """Residency one sweep point adds: the referenced base tables'
        column elements (a vmap lane materialises its own intermediates;
        scan chunks bound the stacked OUTPUT slab the same way —
        :func:`repro.db.cost.batched`)."""
        total = 0.0
        for name in _scan_names(root):
            t = self.tables.get(name)
            if t is not None:
                total += t.capacity * (len(t.columns) + 2)
        return total
