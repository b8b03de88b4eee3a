#!/usr/bin/env python3
"""Run one cell of the chip benchmark of the served DVV store.

    python3 benchmarks/chip/run.py --workload ycsb-a.riak5 --seed 7 \\
        --seconds 30 --trace 0

from the root of a checkout, on a machine with the chips the cell asks
for.  The cells, their deployments and their mixes are named in
``BENCHMARK.json``.  With ``--trace 0`` the result carries the cell's
end-to-end metrics; with ``--trace 1`` the window runs under the profiler
and the result carries its per-layer metrics.  Either way every GET the
window served is judged against the causal-history reference, and the
last lines on standard error give each number compared with its limit.
The last line of standard output is the JSON result.  Without a TPU, or
with fewer chips than the cell asks for, the run exits non-zero and
prints no result.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

HERE = Path(__file__).resolve().parent
ROOT = HERE.parents[1]
sys.path.insert(0, str(HERE))
sys.path.insert(0, str(ROOT / "src"))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    from chipbench.harness import Bench, run_cell
    bench = Bench(ROOT)
    cell = bench.cell(args.workload)

    import jax
    backend = jax.default_backend()
    if backend != "tpu":
        print(f"run.py: the benchmark measures a TPU; JAX's default backend "
              f"is {backend!r}", file=sys.stderr)
        return 2
    if len(jax.devices()) < int(cell["chips"]):
        print(f"run.py: {args.workload} needs {cell['chips']} chips, JAX "
              f"finds {len(jax.devices())}", file=sys.stderr)
        return 2
    from repro.launch.compile_cache import enable_compile_cache
    dev = jax.devices()[0]
    print(f"device: {dev.platform} {dev.device_kind} x{len(jax.devices())}; "
          f"compile cache: {enable_compile_cache()}", flush=True)

    result = run_cell(bench, args.workload, args.seed, args.seconds,
                      bool(args.trace), t_start=T_START)
    for name, c in result["checks"].items():
        limit = (f"limit {c['limit']}" if "limit" in c
                 else f"at least {c['at_least']}")
        print(f"check {name}: {c['value']} ({limit})", file=sys.stderr)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
