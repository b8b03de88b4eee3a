"""Serving launcher: batched decode with DVV-replicated session state.

    PYTHONPATH=src python -m repro.launch.serve --arch gemma-2b --smoke \
        --requests 8 --tokens 24

Implements continuous-batching-lite: a fixed decode batch of slots;
finished requests release their slot and queued requests claim it at the
next step boundary (cache slot re-initialized).  Session cursors persist
through the replicated store, so a different serving node can adopt any
session (see examples/serve_replicated.py for the failover drill).

``--store-workload`` skips the model entirely and drives the store's
coalescing serving plane with the closed-loop workload engine
(store/serving.py): zipfian GET → think → PUT(token) traffic from up to
millions of logical sessions, scheduler flush deadlines and (with
``--gossip-period``) continuous anti-entropy all on one simulated clock:

    PYTHONPATH=src python -m repro.launch.serve --store-workload \
        --store-mode both --sessions 1000000 --store-steps 1500

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
from dataclasses import dataclass, field
from typing import Dict, List, Optional

import jax
import jax.numpy as jnp

from .. import trace
from ..configs import ARCH_IDS, get_config
from ..core import DVV_MECHANISM
from ..models import decode_step, init_cache, init_params
from ..store import KVCluster, SimNetwork
from .compile_cache import enable_compile_cache


@dataclass
class Request:
    rid: int
    prompt_token: int
    max_tokens: int
    generated: List[int] = field(default_factory=list)
    slot: Optional[int] = None

    @property
    def done(self) -> bool:
        return len(self.generated) >= self.max_tokens


class BatchScheduler:
    """Slot-based continuous batching over one shared decode cache."""

    def __init__(self, cfg, params, batch_slots: int, max_len: int,
                 store: KVCluster, node: str):
        self.cfg = cfg
        self.params = params
        self.slots = batch_slots
        self.max_len = max_len
        self.store = store
        self.node = node
        self.cache = init_cache(cfg, batch_slots, max_len)
        self.slot_req: List[Optional[Request]] = [None] * batch_slots
        self.pos = 0
        self._step = jax.jit(
            lambda p, c, t, pos: decode_step(p, c, t, pos, self.cfg))

    def _free_slots(self) -> List[int]:
        return [i for i, r in enumerate(self.slot_req) if r is None]

    def admit(self, queue: List[Request]) -> None:
        for slot in self._free_slots():
            if not queue:
                break
            req = queue.pop(0)
            req.slot = slot
            self.slot_req[slot] = req

    def step(self) -> None:
        toks = jnp.asarray(
            [r.generated[-1] if (r and r.generated) else
             (r.prompt_token if r else 0)
             for r in self.slot_req], jnp.int32)
        logits, self.cache = self._step(
            self.params, self.cache, toks, jnp.asarray(self.pos, jnp.int32))
        nxt = jnp.argmax(logits, axis=-1)
        self.pos += 1
        for i, req in enumerate(self.slot_req):
            if req is None:
                continue
            req.generated.append(int(nxt[i]))
            if req.done:
                self._persist(req)
                self.slot_req[i] = None

    def _persist(self, req: Request) -> None:
        key = f"session/{req.rid}"
        res = self.store.get(key, via=self.node)
        self.store.put(key, json.dumps(
            {"tokens": req.generated, "pos": self.pos}),
            context=res.context, via=self.node, client_id=self.node)


def store_workload_main(args: argparse.Namespace) -> int:
    """Drive the coalescing serving plane with the closed-loop engine
    (no model in the loop); prints one JSON summary per mode."""
    from ..store import ClosedLoopEngine, GossipDriver

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
    ap.add_argument("--arch", default=None, choices=list(ARCH_IDS))
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--requests", type=int, default=8)
    ap.add_argument("--tokens", type=int, default=16)
    ap.add_argument("--batch-slots", type=int, default=4)
    ap.add_argument("--max-len", type=int, default=256)
    g = ap.add_argument_group("store workload (no model in the loop)")
    g.add_argument("--store-workload", action="store_true",
                   help="run the closed-loop store workload engine")
    g.add_argument("--store-mode", default="both",
                   choices=["coalesced", "direct", "both"])
    g.add_argument("--sessions", type=int, default=1_000_000)
    g.add_argument("--keys", type=int, default=10_000)
    g.add_argument("--zipf", type=float, default=0.9)
    g.add_argument("--concurrency", type=int, default=256)
    g.add_argument("--store-steps", type=int, default=500)
    g.add_argument("--max-batch", type=int, default=256)
    g.add_argument("--max-delay", type=float, default=2.0)
    g.add_argument("--gossip-period", type=float, default=0.0,
                   help="anti-entropy period in sim ticks (0 = off)")
    g.add_argument("--seed", type=int, default=11)
    g.add_argument("--use-kernel", action="store_true",
                   help="run the clock sweeps on the DVV Pallas kernels")
    g.add_argument("--trace-dir", default=None,
                   help="write a profile with the store's spans here and "
                        "add the span table to the summary")
    args = ap.parse_args()
    enable_compile_cache()

    if args.store_workload:
        return store_workload_main(args)
    if args.arch is None:
        ap.error("--arch is required unless --store-workload is given")

    cfg = get_config(args.arch)
    if args.smoke:
        cfg = cfg.smoke()
    if not cfg.is_decoder:
        print(f"{cfg.name} is encoder-only; nothing to decode",
              file=sys.stderr)
        return 2
    if cfg.input_mode != "tokens":
        print(f"{cfg.name} needs a modality frontend; serve the backbone "
              f"via examples/serve_replicated.py patterns", file=sys.stderr)
        return 2

    params = init_params(jax.random.key(0), cfg)
    store = KVCluster(("srv1", "srv2"), DVV_MECHANISM,
                      network=SimNetwork(seed=0))
    sched = BatchScheduler(cfg, params, args.batch_slots, args.max_len,
                           store, "srv1")
    queue = [Request(rid=i, prompt_token=i % cfg.vocab_size,
                     max_tokens=args.tokens)
             for i in range(args.requests)]
    completed = 0
    steps = 0
    while (queue or any(sched.slot_req)) and steps < args.max_len - 1:
        sched.admit(queue)
        before = sum(1 for r in sched.slot_req if r is None)
        sched.step()
        after = sum(1 for r in sched.slot_req if r is None)
        completed += max(after - before, 0)
        steps += 1
    print(f"served {args.requests} requests in {steps} decode steps "
          f"({args.batch_slots} slots, continuous batching)")
    for i in range(args.requests):
        res = store.get(f"session/{i}", via="srv1")
        toks = json.loads(res.values[0])["tokens"] if res.values else []
        print(f"  r{i}: {len(toks)} tokens {toks[:6]}...")
    return 0


if __name__ == "__main__":
    sys.exit(main())
