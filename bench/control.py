"""The control of the comparison that decides ``correct``: the f64
reference put in the program's place, computed in bfloat16 (the
precision step below the f32 the configurations state), read by the same
comparison.  Each number it reads sets the upper end of that number's
limit; it has to fail at least one number of every cell.

    python3 bench/control.py --workload <cell> --seeds 1,2,3 --requests 40

For each seed it makes the cell's data, draws the cell's first
``--requests`` requests and compares the bf16 control of each distinct
parameter point with the f64 reference.  It prints one JSON line per
seed, ``{"seed", "numbers": {name: worst reading}}``.  It needs no
accelerator (the control is host arithmetic); run it at the cell's own
size on the machine that holds the chip.
"""
import argparse
import itertools
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def readings(cell, seed: int, n_requests: int) -> dict:
    import ml_dtypes
    import numpy as np

    from bench.data import tpch_gen
    from bench.harness import system
    from bench.harness.runner import requests

    h = system.host_tables(tpch_gen.generate(seed=seed, **cell.scale))
    numbers = {}
    seen = set()
    for _s, q, point in itertools.islice(requests(cell, seed),
                                             n_requests):
        key = (q, tuple(sorted(point.items())))
        if key in seen:
            continue
        seen.add(key)
        mod, opts = cell.queries[q], cell.options[q]
        ref = mod.reference(h, point, opts, np.float64)
        low = mod.reference(h, point, opts, ml_dtypes.bfloat16)
        res = mod.compare_fn(low, ref)
        for n in ("keys", "err"):
            name = f"{q}.{n}"
            numbers[name] = max(numbers.get(name, 0.0), float(res[n]))
    return numbers


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--requests", type=int, default=40)
    args = ap.parse_args(argv)
    sys.path[:0] = [ROOT]
    from bench.harness.spec import Cell
    cell = Cell(ROOT, args.workload)
    for seed in (int(s) for s in args.seeds.split(",")):
        print(json.dumps(dict(seed=seed, workload=args.workload,
                              numbers=readings(cell, seed, args.requests))),
              flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
