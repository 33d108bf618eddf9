"""One run of one cell: set-up, the measured closed loop, the check.

``run_cell`` does, in order: turn on the compile cache at the checkout's
fixed path; make the cell's data from the seed on the host and put it on
the device; build the cell's plans and warm each once; run the closed
loop for ``seconds``; read the device's peak memory; free the program's state;
check the device's f32 transcendental errors against the constants the
written bounds assume, and the window's answers against the f64
reference of their parameter points.  It returns the result line's
object and the lines for standard error.
"""
from __future__ import annotations

import contextlib
import gc
import hashlib
import os
import shutil
import statistics
import time

import numpy as np

from bench.data import tpch_gen
from bench.harness import system as system_mod
from bench.harness import trace as trace_mod
from bench.harness.spec import Cell
from bench.reference import tolerances as T

TRACE_DIR = os.path.join(".bench_out", "trace")   # under the checkout
# Distinct parameter points of one query whose answers a run compares:
# the f64 references of more would take longer than the window.
POINTS_CHECKED = 4


class CompileCounter:
    """Counts XLA programs built in this process (compiled or loaded from
    the persistent cache) through JAX's monitoring events, and the
    persistent cache's hits."""

    def __init__(self):
        import jax
        self.count = 0
        self.cache_hits = 0
        jax.monitoring.register_event_duration_secs_listener(self._on_event)
        jax.monitoring.register_event_listener(self._on_plain_event)

    def _on_event(self, event: str, _duration: float, **_kw) -> None:
        if event == "/jax/core/compile/backend_compile_duration":
            self.count += 1

    def _on_plain_event(self, event: str, **_kw) -> None:
        if event == "/jax/compilation_cache/cache_hits":
            self.cache_hits += 1


def requests(cell: Cell, seed: int):
    """The cell's request sequence from the seed, as ``(stream, query,
    point)``: TPC-H streams, each a seeded permutation of the cell's
    queries (clause 5.3.5.4).  Each substitution parameter of a query
    takes the values of its domain (clause 2.4) in a seeded permutation,
    one after another, a new permutation when one is used up: every seed
    draws the same balanced set of points, in another order."""
    rng = np.random.default_rng([seed, 0x5EED])
    names = list(cell.queries)
    perms = {}

    def value(q, p, values):
        vals, i = perms.get((q, p), (None, 0))
        if vals is None or i == len(vals):
            vals, i = rng.permutation(np.asarray(values)), 0
        perms[(q, p)] = (vals, i + 1)
        return int(vals[i])

    stream = 0
    while True:
        for i in rng.permutation(len(names)):
            q = names[i]
            dom = cell.queries[q].domain(cell.options[q])
            yield stream, q, {p: value(q, p, list(v))
                              for p, v in sorted(dom.items())}
        stream += 1


def checked_points(kept: dict, seed: int) -> list:
    """The parameter points whose answers the check compares: every point
    of a query that met at most ``POINTS_CHECKED`` distinct points in the
    window, else a sample of that many drawn from the seed.  Every answer
    of a chosen point is compared."""
    rng = np.random.default_rng([seed, 0xC4EC])
    by_query: dict = {}
    for key in kept:                  # insertion order: first seen first
        by_query.setdefault(key[0], []).append(key)
    out = []
    for keys in by_query.values():
        if len(keys) > POINTS_CHECKED:
            pick = rng.choice(len(keys), POINTS_CHECKED, replace=False)
            keys = [keys[i] for i in sorted(pick)]
        out += keys
    return out


def _digest(summary) -> str:
    h = hashlib.blake2b(digest_size=16)

    def walk(x):
        if isinstance(x, dict):
            for k in sorted(x):
                h.update(k.encode())
                walk(x[k])
        else:
            a = np.ascontiguousarray(x)
            h.update(str(a.dtype).encode() + str(a.shape).encode())
            h.update(a.tobytes())

    walk(summary)
    return h.hexdigest()


class _Ctx:
    """What a per-layer reader reads: the reduced trace, the answers and
    UDA work of the traced window, the device's peaks."""

    def __init__(self, cell, reduced, answers, work, peaks):
        self.cell, self.reduced, self.answers = cell, reduced, answers
        self.work, self.peaks = work, peaks

    def layer_ms_per_answer(self, layer: str):
        sec = self.reduced.layer_s.get(layer, 0.0)
        if sec <= 0.0 or not self.answers or not self.reduced.devices_found:
            return None
        return 1e3 * sec / self.reduced.devices_found / self.answers

    def ops_bytes(self, kind: str, shape: dict) -> tuple:
        return self.cell.work_module(kind).ops_bytes(**shape)


def run_cell(root: str, workload: str, seed: int, seconds: float,
             trace: bool, devices: list, t_process: float) -> tuple:
    import jax

    from repro.core.config import use_compile_cache

    log = []
    use_compile_cache()
    counter = CompileCounter()
    cell = Cell(root, workload)
    dev0 = devices[0]

    t_run = time.perf_counter()
    h = system_mod.host_tables(tpch_gen.generate(seed=seed, **cell.scale))
    t_gen = time.perf_counter()
    sysm = system_mod.System(h, cell.config, devices)
    log.append(f"[bench] set-up: start to harness {t_run - t_process:.3f} "
               f"s, data {t_gen - t_run:.3f} s, tables on the device "
               f"{time.perf_counter() - t_gen:.3f} s")
    plans = {q: m.plan(cell.options[q], cell.scale)
             for q, m in cell.queries.items()}
    stream = requests(cell, seed)
    first = {}
    while len(first) < len(plans):
        _s, q, point = next(stream)
        first.setdefault(q, point)
    for q in plans:                   # the traffic file's order, every seed
        point = first[q]
        t0, c0, h0 = time.perf_counter(), counter.count, counter.cache_hits
        _out, info = sysm.submit(plans[q], cell.queries[q].params(point))
        rep = info.get("report")
        legs = sorted(rep.exchange_overflow) if rep is not None else []
        log.append(f"[bench] warm {q}: {time.perf_counter() - t0:.3f} s, "
                   f"{counter.count - c0} programs built "
                   f"({counter.cache_hits - h0} from the cache), "
                   f"{info['attempts']} attempt(s), exchange legs {legs}")
    del _out
    c_warm = counter.count

    trace_dir = os.path.join(root, TRACE_DIR)
    if trace:
        shutil.rmtree(trace_dir, ignore_errors=True)
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        jax.profiler.start_trace(trace_dir, profiler_options=opts)
    span = (jax.profiler.TraceAnnotation if trace
            else lambda _name: contextlib.nullcontext())

    stream = requests(cell, seed)
    records = []                      # (query, point key, latency, ok)
    kept = {}                         # point key -> {digest: summary}
    answer_of = []                    # per record: (point key, digest)
    errors = []
    gc.collect()
    gc.freeze()         # collections in the window scan only new objects
    t_start = time.perf_counter()
    setup_s = t_start - t_process
    deadline = t_start + seconds
    t_last = t_start
    cur_stream = 0
    with span("window"):
        while True:
            s_idx, q, point = next(stream)
            if s_idx != cur_stream:
                # The window holds whole streams: it closes at the first
                # stream boundary at or after the deadline.
                if time.perf_counter() >= deadline:
                    break
                cur_stream = s_idx
            mod = cell.queries[q]
            key = (q, tuple(sorted(point.items())))
            with span(f"request.{q}"):
                t0 = time.perf_counter()
                try:
                    out, info = sysm.submit(plans[q], mod.params(point))
                    ok, attempts = True, info["attempts"]
                except Exception as e:          # a failed request counts
                    ok, attempts = False, 0
                    errors.append(f"{q} {point}: {e!r}"[:300])
                t1 = time.perf_counter()
            t_last = t1
            records.append((q, key, t1 - t0, ok, attempts))
            if ok:
                with span("collect"):
                    summary = mod.extract(jax.device_get(out))
                    d = _digest(summary)
                    kept.setdefault(key, {}).setdefault(d, summary)
                    answer_of.append((key, d))
                del out
            else:
                answer_of.append((key, None))
    gc.unfreeze()
    window_compiles = counter.count - c_warm
    stats = [d.memory_stats() or {} for d in devices]
    peak = max(int(s.get("peak_bytes_in_use", 0)) for s in stats)
    reduced = None
    if trace:
        jax.profiler.stop_trace()
    sysm.close()
    del sysm
    gc.collect()

    # ---------------------------------------------------------- the check
    t_check = time.perf_counter()
    acc = T.device_accuracy()
    t_acc = time.perf_counter()
    refs, results = {}, {}
    for key in checked_points(kept, seed):
        q, point = key[0], dict(key[1])
        refs[key] = cell.queries[q].reference(h, point, cell.options[q],
                                              np.float64)
        for d, summary in kept[key].items():
            results[(key, d)] = cell.queries[q].compare_fn(summary,
                                                           refs[key])
    t_ref = time.perf_counter()
    numbers, worst_part = {}, {}
    for (key, _d), res in results.items():
        q = key[0]
        for n in ("keys", "err"):
            name = f"{q}.{n}"
            if res[n] >= numbers.get(name, -1.0):
                numbers[name] = res[n]
                if n == "err":
                    worst_part[name] = f"{res['part']} at {dict(key[1])}"
    failed = 0
    for (q, key, _lat, ok, _a), (_k, d) in zip(records, answer_of):
        lim = cell.limits[q]
        res = results.get((key, d))
        if not ok or (res is not None and not (res["keys"] <= lim["keys"]
                                               and res["err"] <= lim["err"])):
            failed += 1
    checks = {name: dict(value=float(v),
                         limit=float(cell.limits[name.split(".")[0]][
                             name.split(".")[1]]))
              for name, v in sorted(numbers.items())}
    for k, v in acc.items():            # the bounds hold only within these
        checks[f"device.{k}_ulps"] = dict(value=float(v),
                                          limit=float(T.DEVICE_ULPS[k]))
    within = all(np.isfinite(c["value"]) and c["value"] <= c["limit"]
                 for c in checks.values())
    correct = bool(records) and failed == 0 and within

    # ----------------------------------------------------------- metrics
    ok_lat = [lat for _q, _k, lat, ok, _a in records if ok]
    n_ok = len(ok_lat)
    device = dict(platform=dev0.platform, kind=dev0.device_kind,
                  count=len(devices), memory_peak_bytes=peak)
    values = {}
    if n_ok:
        values["answers_per_s"] = n_ok / (t_last - t_start)
        values["latency_p50_ms"] = 1e3 * statistics.median(ok_lat)
        if n_ok >= 2:
            values["latency_p95_ms"] = 1e3 * statistics.quantiles(
                ok_lat, n=20, method="inclusive")[18]
    values["peak_hbm_gb"] = peak / 1e9
    values["setup_s"] = setup_s
    breakdown = None
    if trace:
        reduced = trace_mod.Reduced(trace_mod.load(trace_dir),
                                    [d.id for d in devices], cell.matchers)
        work = []
        for q, key, _lat, ok, _a in records:
            if ok:
                work += cell.queries[q].work(refs.get(key) or
                                             cell.queries[q].reference(
                                                 h, dict(key[1]),
                                                 cell.options[q],
                                                 np.float64))
        ctx = _Ctx(cell, reduced, n_ok, work, cell.peaks(dev0.device_kind))
        values = {}
        for name, reader in cell.layers.items():
            v = reader.read(ctx)
            if v is not None:
                values[name] = v
        device.update(busy_s=reduced.busy_s, window_s=reduced.window_s)
        breakdown = reduced.breakdown()
    units = {m["name"]: m["unit"] for m in
             (cell.per_layer if trace else cell.end_to_end)}
    metrics = {k: dict(value=float(v), unit=units[k])
               for k, v in values.items() if k in units}

    per_query = {}
    for q, _k, lat, ok, a in records:
        e = per_query.setdefault(q, [0, 0.0, 0, []])
        e[0] += 1
        e[1] += lat
        e[2] += max(a - 1, 0)
        e[3].append(round(1e3 * lat, 1))
    log.append(f"[bench] check {time.perf_counter() - t_check:.3f} s "
               f"(device accuracy {t_acc - t_check:.3f} s, references and "
               f"comparison {t_ref - t_acc:.3f} s)")
    log.append(f"[bench] {workload} seed {seed}: setup {setup_s:.3f} s, "
               f"{len(records)} requests in {t_last - t_start:.3f} s, "
               f"{window_compiles} compiles in the window, peak {peak} B")
    for q, (n, tot, retries, lats) in per_query.items():
        points = sum(1 for k in refs if k[0] == q)
        seen = sum(1 for k in kept if k[0] == q)
        log.append(f"[bench] {q}: {n} requests, mean {1e3 * tot / n:.3f} ms,"
                   f" {retries} retries, {points} of {seen} points checked;"
                   f" ms {lats}")
    for name, part in sorted(worst_part.items()):
        log.append(f"[bench] {name} worst: {part}")
    log += [f"[bench] failed request: {e}" for e in errors[:5]]
    if trace and breakdown:
        log.append(f"[bench] trace: busy {reduced.busy_s:.6f} s of "
                   f"{reduced.window_s:.6f} s on {reduced.devices_found} "
                   f"device(s); ops {breakdown['device_ops'][:5]}")
    log += [f"[check] {name} {c['value']!r} limit {c['limit']!r}"
            for name, c in checks.items()]
    result = dict(correct=correct, attempted=len(records), failed=failed,
                  metrics=metrics, device=device)
    if breakdown is not None:
        result["breakdown"] = breakdown
    result["checks"] = checks
    return result, log
