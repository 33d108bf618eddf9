"""Serving launcher: batched prefill + decode loop (CPU-runnable demo) and
the probabilistic-DB query service (the paper's workload as a server).

    PYTHONPATH=src python -m repro.launch.serve --arch yi_6b --reduced \
        --batch 4 --prompt-len 32 --gen 16

    PYTHONPATH=src python -m repro.launch.serve --db --scale 200 \
        --rounds 3 --sweep 64 --cache-capacity 16

The ``--db`` loop drives :class:`repro.db.serving.QueryService`: each
round submits every TPC-H serving plan (round 0 compiles cold, later
rounds are structural plan-cache hits — same executables, bit-identical
results), then a parameterized Q6 what-if sweep runs ``--sweep`` points
as ONE vmapped device program.  The loop prints per-round latency,
cached queries-per-second and the service's hit/miss/eviction counters.
"""
from __future__ import annotations

import argparse
import time

import jax
import jax.numpy as jnp

from ..configs import base as cfgs
from ..core.config import use_compile_cache
from ..models import api


def generate(cfg, params, prompt, max_len: int, gen: int, greedy=True):
    """Prefill the prompt token-by-token into the cache, then decode."""
    b, t = prompt.shape[:2]
    dt = jnp.dtype(cfg.dtype)
    cache = api.init_cache(cfg, b, max_len, dtype=dt)
    step = jax.jit(lambda p, tok, c, l: api.decode_step(cfg, p, tok, c, l))
    cl = jnp.zeros((), jnp.int32)
    logits = None
    for i in range(t):
        logits, cache, cl = step(params, prompt[:, i:i + 1], cache, cl)
    out = []
    tok = jnp.argmax(logits[:, -1:], axis=-1)
    for _ in range(gen):
        out.append(tok)
        logits, cache, cl = step(params, tok, cache, cl)
        tok = jnp.argmax(logits[:, -1:], axis=-1)
    return jnp.concatenate(out, axis=1)


def serve_rounds(svc, plans: dict, rounds: int) -> list:
    """Submit every plan once per round, each result waited for.  Returns
    one ``(outs, infos, seconds)`` per round, keyed by plan name."""
    history = []
    for _ in range(rounds):
        t0 = time.perf_counter()
        outs, infos = {}, {}
        for name, plan in plans.items():
            out, info = svc.submit(plan)
            jax.block_until_ready(jax.tree.leaves(out))
            outs[name], infos[name] = out, info
        history.append((outs, infos, time.perf_counter() - t0))
    return history


def sweep_points(n: int) -> dict:
    """The Q6 what-if sweep's parameter batch: discount 5..7, quantity
    limit 1..n."""
    return dict(disc_lo=jnp.full((n,), 5), disc_hi=jnp.full((n,), 7),
                qty_lim=jnp.arange(1, n + 1))


def serve_db(args) -> int:
    """The ``--db`` service loop: submit / cached-hit / evict over the
    TPC-H plan library, then the batched parameterized Q6 sweep.  Every
    submit runs under a retry policy, so an overflowing group table is
    healed or reported instead of answered truncated."""
    from ..db import tpch
    from ..db.plans import RetryPolicy
    from ..db.serving import QueryService

    db = tpch.generate(n_orders=args.scale)
    svc = QueryService(db.tables(), capacity=args.cache_capacity,
                       policy=RetryPolicy())
    plans = tpch.serving_plans(**tpch.serving_group_sizes(db))
    history = serve_rounds(svc, plans, max(1, args.rounds))
    for r, (_, infos, dt) in enumerate(history):
        hits = sum(int(info["hit"]) for info in infos.values())
        print(f"[serve-db] round {r}: {len(plans)} queries in {dt:.3f}s "
              f"({hits}/{len(plans)} cache hits)")
        bad = {name: info["report"].describe()
               for name, info in infos.items() if not info["report"].ok()}
        if bad:
            print(f"[serve-db] round {r} unresolved: {bad}")
            return 1
    if len(history) > 1:              # warm rounds measure serving QPS
        warm = sum(dt for _, _, dt in history[1:])
        print(f"[serve-db] cached throughput: "
              f"{len(plans) * (len(history) - 1) / warm:.1f} queries/s")
    if args.sweep > 0:
        t0 = time.perf_counter()
        out, info = svc.sweep(tpch.q6_family(), sweep_points(args.sweep))
        jax.block_until_ready(jax.tree.leaves(out))
        print(f"[serve-db] batched q6 sweep: {args.sweep} points as "
              f"{info['launches']} device program(s) in "
              f"{time.perf_counter() - t0:.3f}s")
    print(f"[serve-db] stats: {svc.stats.as_dict()}")
    print(f"[serve-db] plan cache: {svc.cache.info()}")
    return 0


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="yi_6b")
    ap.add_argument("--reduced", action="store_true", default=True)
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=32)
    ap.add_argument("--gen", type=int, default=16)
    ap.add_argument("--db", action="store_true",
                    help="serve probabilistic TPC-H queries instead")
    ap.add_argument("--scale", type=int, default=200)
    ap.add_argument("--rounds", type=int, default=3,
                    help="--db: request rounds over the plan library "
                         "(round 0 is cold, later rounds hit the cache)")
    ap.add_argument("--sweep", type=int, default=64,
                    help="--db: parameter points of the batched Q6 "
                         "what-if sweep (0 disables)")
    ap.add_argument("--cache-capacity", type=int, default=16,
                    help="--db: bounded plan-cache entries")
    args = ap.parse_args(argv)
    use_compile_cache()

    if args.db:
        return serve_db(args)

    cfg = cfgs.get_reduced(args.arch)
    params = api.init_params(cfg, jax.random.PRNGKey(0))
    key = jax.random.PRNGKey(1)
    if cfg.embedding_inputs:
        prompt = jax.random.normal(
            key, (args.batch, args.prompt_len, cfg.d_model), jnp.float32)
    else:
        prompt = jax.random.randint(key, (args.batch, args.prompt_len), 0,
                                    cfg.vocab_size)
    t0 = time.time()
    toks = generate(cfg, params, prompt,
                    args.prompt_len + args.gen + 1, args.gen)
    dt = time.time() - t0
    print(f"[serve] arch={cfg.name} generated {toks.shape} in {dt:.2f}s "
          f"({args.batch * args.gen / dt:.1f} tok/s)")
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
