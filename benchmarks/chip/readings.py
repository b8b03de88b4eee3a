#!/usr/bin/env python3
"""Readings behind the limits of ``correct``: one cell, many seeds, in one
process (set-up then repeats per seed, JAX's start-up does not).

    python3 benchmarks/chip/readings.py --workload ycsb-a.riak5 \\
        --seeds 11,12,13 --seconds 30 [--control]

Without ``--control`` each seed is a sound run of the cell as committed.
With it the cell runs its control, which breaks one guarantee the
configuration states: the serving cells swap DVVs for version vectors
with server ids (the store's own ``vv_server`` mechanism, which silently
drops concurrent writes), the repair cell pushes to one peer per node and
round instead of all (``fanout`` 1).  Prints one JSON line per seed with
every number compared; the benchmark's own runs never run a control.
Needs a TPU, like ``run.py``.
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

#: What the control changes, by traffic kind: (mechanism, mix overrides).
CONTROLS = {"closed_loop": ("vv_server", {}),
            "repair_cycles": (None, {"fanout": 1})}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--control", action="store_true")
    args = ap.parse_args(argv)

    from chipbench.harness import Bench, run_cell
    import jax
    if jax.default_backend() != "tpu":
        print("readings.py: needs a TPU", file=sys.stderr)
        return 2
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    bench = Bench(ROOT)
    kind = bench.traffic(bench.cell(args.workload)["traffic"])["kind"]
    mechanism, overrides = CONTROLS[kind] if args.control else (None, {})
    t = T_START
    for seed in (int(s) for s in args.seeds.split(",")):
        result = run_cell(bench, args.workload, seed, args.seconds, False,
                          t_start=t, mechanism=mechanism,
                          traffic_overrides=overrides,
                          log=lambda s: print(s, file=sys.stderr,
                                              flush=True))
        print(json.dumps({"workload": args.workload, "seed": seed,
                          "control": args.control,
                          "correct": result["correct"],
                          "metrics": result["metrics"],
                          "checks": result["checks"]}), flush=True)
        t = time.perf_counter()
    return 0


if __name__ == "__main__":
    sys.exit(main())
