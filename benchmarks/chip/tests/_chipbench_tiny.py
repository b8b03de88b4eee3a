"""Shared by the benchmark's CPU tests: the path set-up and a tiny run.

A tiny run keeps each cell's mix and deployment but shrinks the scale
(records, shards, clients, burst, warm-up) so that the kernels, which run
in interpret mode on the CPU, finish in seconds.  It calls the harness
below ``run.py``'s look for a chip.
"""
from __future__ import annotations

import sys
import time
from pathlib import Path

BENCH = Path(__file__).resolve().parents[1]
ROOT = BENCH.parents[1]
for p in (str(BENCH), str(ROOT / "src")):
    if p not in sys.path:
        sys.path.insert(0, p)

from chipbench.harness import Bench, run_cell  # noqa: E402

TINY_CONFIG = {"records": 2000}
TINY_TRAFFIC = {
    "warmup_seconds": 0.5, "warmup_cycles": 1, "clients": 8, "burst": 32,
    "readback_keys": 32,
    "warm_buckets": {"read_sweep": {"n": [8], "k": [2, 4], "r": [8]},
                     "sync_mask": {"n": [8], "k": [2, 4], "r": [8]}},
}


def tiny_run(workload: str, *, seed: int = 2 ** 33 + 7, seconds: float = 1.5,
             root: Path = ROOT, trace: bool = False, **kw):
    bench = Bench(root)
    cfg = bench.config(bench.cell(workload)["config"])
    overrides = dict(TINY_CONFIG,
                     deployment=dict(cfg["deployment"], shards=4))
    traffic = dict(TINY_TRAFFIC, **kw.pop("traffic_overrides", {}))
    return run_cell(bench, workload, seed, seconds, trace,
                    t_start=time.perf_counter(), config_overrides=overrides,
                    traffic_overrides=traffic, log=lambda s: None, **kw)
