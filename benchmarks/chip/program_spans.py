#!/usr/bin/env python3
"""One traced run of a cell, with the device's idle time charged to the
program's own spans as well as to the harness's annotations.

    python3 benchmarks/chip/program_spans.py --workload ycsb-a.riak5 \\
        --seed 7 --seconds 51

runs exactly what ``run.py --trace 1`` runs and prints the same result as
its last line.  Before it, one line starting ``program:`` holds
``idle_by_program_span`` (seconds of device idle time by the innermost
span of ``repro.trace`` that covers each gap, ``(no program span)`` where
none does), ``idle_s``, ``uncovered_share`` and ``program``, the
program's span and counter table over the window.  Needs a TPU, like
``run.py``.
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
    args = ap.parse_args(argv)

    import jax
    if jax.default_backend() != "tpu":
        print("program_spans.py: needs a TPU", file=sys.stderr)
        return 2
    from chipbench.harness import Bench
    from chipbench.program_idle import traced_run
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    result, program = traced_run(Bench(ROOT), args.workload, args.seed,
                                 args.seconds, t_start=T_START)
    print("program: " + json.dumps(program), flush=True)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
