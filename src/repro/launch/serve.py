"""Store workload launcher: the coalescing serving plane under load.

Drives the store's serving plane with the closed-loop workload engine
(store/serving.py): zipfian GET → think → PUT(token) traffic from up to
millions of logical sessions, scheduler flush deadlines and (with
``--gossip-period``) continuous anti-entropy all on one simulated clock:

    PYTHONPATH=src python -m repro.launch.serve --store-workload \
        --store-mode both --sessions 1000000 --store-steps 1500

``--store-workload`` names the only mode there is; it stays accepted so
existing command lines keep working.

``--use-kernel`` routes every clock sweep the workload makes (reads,
writes, gossip) through the DVV Pallas kernels: compiled on a TPU,
interpreted on the CPU backend.  The persistent compile cache goes where
``JAX_COMPILATION_CACHE_DIR`` says, else to ``<repo>/.jax_cache``.

``--trace-dir DIR`` writes one profile of the workload under ``DIR``,
which turns on the store's own spans and counters (``repro.trace``): the
spans land on the host plane, on the device planes' clock (open it with
TensorBoard or Perfetto), and each mode's span and counter table is added
to its JSON summary as ``"trace"``.
"""
from __future__ import annotations

import argparse
import contextlib
import json
import sys

import jax

from .. import trace
from ..core import DVV_MECHANISM
from ..store import ClosedLoopEngine, GossipDriver, KVCluster, SimNetwork
from .compile_cache import enable_compile_cache


def store_workload_main(args: argparse.Namespace) -> int:
    """Drive the coalescing serving plane with the closed-loop engine;
    prints one JSON summary per mode."""
    modes = (("coalesced", "direct") if args.store_mode == "both"
             else (args.store_mode,))
    summaries = {}
    profile = contextlib.nullcontext()
    if args.trace_dir:
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0     # the store's spans, not every call
        profile = jax.profiler.trace(args.trace_dir, profiler_options=opts)
    with profile:
        for mode in modes:
            net = SimNetwork(seed=7, jitter=0.0)
            cluster = KVCluster(tuple(f"n{i}" for i in range(5)),
                                DVV_MECHANISM, replication=3, network=net,
                                read_quorum=2, write_quorum=2, seed=7)
            driver = None
            if args.gossip_period > 0:
                driver = GossipDriver(cluster, period=args.gossip_period,
                                      seed=7, use_kernel=args.use_kernel)
                driver.start()          # timers interleave with the engine
            eng = ClosedLoopEngine(
                cluster, sessions=args.sessions, keys=args.keys,
                zipf_s=args.zipf, concurrency=args.concurrency,
                mode=mode, via="n0", seed=args.seed, read_repair=True,
                max_batch=args.max_batch, max_delay=args.max_delay,
                use_kernel=args.use_kernel)
            before = trace.snapshot()
            out = eng.run(args.store_steps)
            if args.trace_dir:
                out["trace"] = trace.delta(before, trace.snapshot())
            if driver is not None:
                out["gossip"] = {"rounds": driver.rounds,
                                 "wire_bytes": driver.wire_bytes()}
                driver.stop()
            summaries[mode] = out
            print(json.dumps(out, indent=1))
    if len(summaries) == 2:
        d, c = summaries["direct"], summaries["coalesced"]
        if c["plane_per_1k_ops"]:
            print(f"plane ratio direct/coalesced: "
                  f"{d['plane_per_1k_ops'] / c['plane_per_1k_ops']:.1f}x, "
                  f"bytes/op {c['bytes_per_op']:.1f} vs "
                  f"{d['bytes_per_op']:.1f}")
    return 0


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--store-workload", action="store_true",
                    help="run the closed-loop store workload engine "
                         "(the only mode)")
    ap.add_argument("--store-mode", default="both",
                    choices=["coalesced", "direct", "both"])
    ap.add_argument("--sessions", type=int, default=1_000_000)
    ap.add_argument("--keys", type=int, default=10_000)
    ap.add_argument("--zipf", type=float, default=0.9)
    ap.add_argument("--concurrency", type=int, default=256)
    ap.add_argument("--store-steps", type=int, default=500)
    ap.add_argument("--max-batch", type=int, default=256)
    ap.add_argument("--max-delay", type=float, default=2.0)
    ap.add_argument("--gossip-period", type=float, default=0.0,
                    help="anti-entropy period in sim ticks (0 = off)")
    ap.add_argument("--seed", type=int, default=11)
    ap.add_argument("--use-kernel", action="store_true",
                    help="run the clock sweeps on the DVV Pallas kernels")
    ap.add_argument("--trace-dir", default=None,
                    help="write a profile with the store's spans here and "
                         "add the span table to the summary")
    args = ap.parse_args()
    enable_compile_cache()
    return store_workload_main(args)


if __name__ == "__main__":
    sys.exit(main())
