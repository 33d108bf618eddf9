"""The benchmark's one command: one run of one cell.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> \
        --trace <0|1>

Cells, their configurations, traffic mixes and metrics are named in
``BENCHMARK.json`` and found by name under ``bench/``.  The run needs the
accelerator the cell names: with no TPU, or fewer chips than the cell
asks for, it exits 2 and prints no result.  The last line of standard
output is the result's JSON object; the last lines of standard error are
the numbers compared, each beside its limit.
"""
import time

T_PROCESS = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def parse(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


def main(argv=None) -> int:
    args = parse(argv)
    if args.seed < 0:
        print("bench: --seed must be a whole number >= 0", file=sys.stderr)
        return 2
    sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]
    # JAX's persistent compile cache lives at a fixed path inside the
    # checkout (the program's own cache hook takes the directory set
    # here); the TPU runtime writes no log files.
    os.environ["JAX_COMPILATION_CACHE_DIR"] = os.path.join(ROOT,
                                                           ".jax_cache")
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from bench.harness.spec import Cell
    try:
        chips = Cell(ROOT, args.workload).chips
    except (KeyError, FileNotFoundError) as e:
        print(f"bench: {e}", file=sys.stderr)
        return 2
    import jax
    devices = jax.devices()
    if devices[0].platform != "tpu":
        print(f"bench: JAX found no TPU (platform "
              f"{devices[0].platform!r}); nothing was run", file=sys.stderr)
        return 2
    if len(devices) < chips:
        print(f"bench: {args.workload} needs {chips} chips, JAX sees "
              f"{len(devices)}; nothing was run", file=sys.stderr)
        return 2
    from bench.harness.runner import run_cell
    result, log = run_cell(ROOT, args.workload, args.seed, args.seconds,
                           bool(args.trace), devices[:chips], T_PROCESS)
    for line in log:
        print(line, file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(result))
    return 0


if __name__ == "__main__":
    # The program's lowered modules depend on Python's string hashing
    # (set iteration order), which is salted per process: without a
    # fixed salt no later process finds a compiled program in the cache.
    if os.environ.get("PYTHONHASHSEED") != "0":
        os.environ["PYTHONHASHSEED"] = "0"
        os.execv(sys.executable, [sys.executable] + sys.argv)
    sys.exit(main())
