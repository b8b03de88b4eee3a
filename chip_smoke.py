#!/usr/bin/env python3
"""Chip smoke: the served store path on one TPU, checked against the numpy
reference plane.

Deployment — YCSB core workload A (Cooper et al., SoCC 2010,
``workloads/workloada``): 50% reads and 50% updates, zipfian key choice
(constant 0.99), records of 10 fields x 100 B, on a Riak-style cluster of
5 nodes with ``n_val = 3`` and ``r = w = quorum = 2``, 64 shards.

Phases, every clock sweep on the DVV Pallas kernels (``use_kernel=True``):

1. load: ``--records`` records through ``KVCluster.put_many``;
2. serve: ``--steps`` coalesced GET -> PUT steps through
   ``ClosedLoopEngine`` (one read and one update each);
3. anti-entropy: a burst of ``--burst`` read-modify-writes whose
   replication is still in flight when one
   ``delta_antientropy_round(use_kernel=True)`` runs;
4. read-back at R = 2 of a sample of the acknowledged load and burst writes.

The same seeded run on a twin cluster on the numpy reference plane
(``use_kernel=False``) must give identical GET results (values, sibling
counts, context bytes), identical anti-entropy accounting and identical
replica digests.  Any failed op is a failure: the run injects no faults.

    python chip_smoke.py            # on a TPU host; needs the chip

Exits non-zero, printing no result, unless JAX's default backend is a TPU.
The last line of standard output is the JSON result.
"""
from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path
from typing import Any, Dict, List, Optional

import numpy as np

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

FIELDS, FIELD_BYTES = 10, 100
RECORD_BYTES = FIELDS * FIELD_BYTES
NODES, REPLICATION, QUORUM, SHARDS = 5, 3, 2, 64
ZIPF = 0.99
LOAD_CHUNK = 65536
SAMPLE = 256


def make_records(n: int, seed: int) -> List[str]:
    """``n`` YCSB records: 10 fields x 100 random lowercase bytes each."""
    rng = np.random.default_rng(seed)
    out: List[str] = []
    for s in range(0, n, LOAD_CHUNK):
        raw = rng.integers(ord("a"), ord("z") + 1, dtype=np.uint8,
                           size=(min(LOAD_CHUNK, n - s), RECORD_BYTES))
        out.extend(row.tobytes().decode("ascii") for row in raw)
    return out


def _result_key(res: Any) -> Any:
    """What two planes must agree on for one GET."""
    if res is None:
        return None
    return res.values, res.siblings, res.context.to_bytes()


def run_ycsb_a(records: List[str], *, steps: int, burst: int,
               shards: int = SHARDS, seed: int = 0, use_kernel: bool,
               concurrency: int = 64) -> Dict[str, Any]:
    """Load, serve, anti-entropy and read back on one fresh cluster.

    Returns what the two planes are compared on, plus wall times.
    """
    from repro.core import DVV_MECHANISM
    from repro.store import ClosedLoopEngine, KVCluster, SimNetwork

    cluster = KVCluster(tuple(f"n{i}" for i in range(NODES)), DVV_MECHANISM,
                        replication=REPLICATION, read_quorum=QUORUM,
                        write_quorum=QUORUM, shards=shards, seed=seed,
                        network=SimNetwork(seed=seed, jitter=0.0))
    n = len(records)
    keys = [f"k{i}" for i in range(n)]          # the engine's key names
    wall: Dict[str, float] = {}

    t = time.perf_counter()
    for s in range(0, n, LOAD_CHUNK):
        chunk = zip(keys[s:s + LOAD_CHUNK], records[s:s + LOAD_CHUNK])
        cluster.put_many({k: (v, None) for k, v in chunk}, via="n0",
                         use_kernel=use_kernel)
        cluster.deliver_replication()
    wall["load_s"] = time.perf_counter() - t

    reads: List[Any] = []
    engine = ClosedLoopEngine(
        cluster, sessions=100_000, keys=n, zipf_s=ZIPF,
        concurrency=concurrency, mode="coalesced", via="n0", seed=seed,
        read_repair=True, use_kernel=use_kernel, max_batch=256,
        record_bytes=RECORD_BYTES,
        observe=lambda sid, key, res: reads.append(
            (sid, key, _result_key(res))))
    t = time.perf_counter()
    summary = engine.run(steps)
    cluster.deliver_replication()        # drain writes still in flight
    wall["serve_s"] = time.perf_counter() - t

    # Read-modify-write burst through another proxy; its replication stays
    # queued while the anti-entropy round runs, so the round has real
    # divergence to repair.
    rng = np.random.default_rng(seed + 1)
    burst_keys = [keys[i] for i in rng.choice(n, size=min(burst, n),
                                                replace=False)]
    burst_values = make_records(len(burst_keys), seed + 1)
    t = time.perf_counter()
    before = cluster.get_many(burst_keys, via="n1", use_kernel=use_kernel)
    cluster.put_many({k: (v, before[k].context)
                      for k, v in zip(burst_keys, burst_values)},
                     via="n1", use_kernel=use_kernel)
    rounds = cluster.delta_antientropy_round(use_kernel=use_kernel)
    cluster.deliver_replication()
    wall["antientropy_s"] = time.perf_counter() - t

    # Read back acknowledged writes at R = 2: burst keys, and loaded keys
    # that neither the traffic nor the burst touched.
    touched = {key for _, key, _ in reads} | set(burst_keys)
    untouched = [i for i in rng.permutation(n)[: 4 * SAMPLE]
                 if keys[i] not in touched][:SAMPLE]
    expect = dict(zip(burst_keys[:SAMPLE], burst_values[:SAMPLE]))
    expect.update((keys[i], records[i]) for i in untouched)
    t = time.perf_counter()
    got = cluster.get_many(list(expect), via="n2", quorum=QUORUM,
                           use_kernel=use_kernel)
    wall["readback_s"] = time.perf_counter() - t

    return {
        "summary": summary,
        "reads": reads,
        "burst_reads": [_result_key(before[k]) for k in burst_keys],
        "antientropy": [(r.buckets_divergent, r.payload_slots,
                         r.payload_bytes, r.digest_bytes, r.changed)
                        for r in rounds],
        "readback": {k: _result_key(got[k]) for k in expect},
        "expected": expect,
        "digests": {(nid, s): (st.digest_root(), st.value_root())
                    for nid, node in cluster.nodes.items()
                    for s, st in enumerate(node.backend.stores)},
        "wall": wall,
    }


def check_run(run: Dict[str, Any]) -> List[str]:
    """What one plane must satisfy on its own; returns the problems."""
    problems = []
    s = run["summary"]
    if s["ops_failed"] or s["scheduler"]["ops_failed"]:
        problems.append(f"{s['ops_failed']} ops failed with no fault injected")
    if not run["reads"] or any(r is None for _, _, r in run["reads"]):
        problems.append("a GET returned no result")
    if not any(slots for _, slots, _, _, _ in run["antientropy"]):
        problems.append("the anti-entropy round shipped nothing")
    for k, want in run["expected"].items():
        values, siblings, _ = run["readback"][k]
        if want not in values:
            problems.append(f"acknowledged write to {k} not read back at "
                            f"R={QUORUM} ({siblings} siblings)")
    return problems


def diff_runs(a: Dict[str, Any], b: Dict[str, Any]) -> List[str]:
    """Where two planes' runs of one seeded workload differ."""
    problems = []
    if len(a["reads"]) != len(b["reads"]):
        problems.append(f"{len(a['reads'])} vs {len(b['reads'])} GETs")
    for i, (x, y) in enumerate(zip(a["reads"], b["reads"])):
        if x != y:
            problems.append(f"GET #{i} on {x[1]} differs")
            break
    for name in ("burst_reads", "antientropy", "readback", "digests"):
        if a[name] != b[name]:
            problems.append(f"{name} differ")
    for name in ("ops", "steps", "plane_invocations", "bytes_per_op"):
        if a["summary"][name] != b["summary"][name]:
            problems.append(f"summary {name}: {a['summary'][name]} vs "
                            f"{b['summary'][name]}")
    return problems


def compiled_kernel_check() -> List[str]:
    """The kernels that ran are Mosaic custom calls, not interpreted."""
    import jax
    import jax.numpy as jnp

    from repro.kernels.dvv_ops import dvv_read_sweep_bucketed, \
        dvv_sync_mask_bucketed
    from repro.kernels.dvv_ops.dvv_ops import dvv_read_sweep_pallas, \
        dvv_sync_mask_pallas
    from repro.kernels.dvv_ops.ops import _interpret

    problems = []
    for fn, cache in ((dvv_sync_mask_pallas, dvv_sync_mask_bucketed),
                      (dvv_read_sweep_pallas, dvv_read_sweep_bucketed)):
        buckets = cache.cache_info()["buckets"]
        if not buckets:
            problems.append(f"{fn.__name__} never ran")
            continue
        N, K, R = buckets[-1]
        i32 = jnp.int32
        hlo = fn.lower(jax.ShapeDtypeStruct((N, K, R), i32),
                       jax.ShapeDtypeStruct((N, K), i32),
                       jax.ShapeDtypeStruct((N, K), i32),
                       jax.ShapeDtypeStruct((N, K), jnp.bool_),
                       interpret=_interpret()).as_text()
        if "tpu_custom_call" not in hlo:
            problems.append(f"{fn.__name__} at {(N, K, R)} is not compiled "
                            f"to a tpu_custom_call")
    return problems


def main(argv: Optional[List[str]] = None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--records", type=int, default=1_000_000)
    ap.add_argument("--steps", type=int, default=2000)
    ap.add_argument("--burst", type=int, default=4096)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    import jax
    backend = jax.default_backend()
    if backend != "tpu":
        print(f"chip_smoke: needs a TPU, JAX's default backend is "
              f"{backend!r}", file=sys.stderr)
        return 1
    from repro.kernels.dvv_ops import dvv_read_sweep_bucketed, \
        dvv_sync_mask_bucketed
    from repro.launch.compile_cache import enable_compile_cache

    dev = jax.devices()[0]
    print(f"device: {dev.platform} {dev.device_kind} x{len(jax.devices())}; "
          f"compile cache: {enable_compile_cache()}", flush=True)
    t = time.perf_counter()
    records = make_records(args.records, args.seed)
    print(f"records: {len(records)} x {RECORD_BYTES} B, made in "
          f"{time.perf_counter() - t:.1f} s", flush=True)

    runs = {}
    for plane, use_kernel in (("kernel", True), ("reference", False)):
        run = runs[plane] = run_ycsb_a(records, steps=args.steps,
                                       burst=args.burst, seed=args.seed,
                                       use_kernel=use_kernel)
        s = run["summary"]
        print(f"{plane} plane: wall {json.dumps(run['wall'])}; "
              f"{s['ops']} ops, {s['ops_failed']} failed, "
              f"{s['scheduler']['flushes']} flushes", flush=True)
    for name, cache in (("sync mask", dvv_sync_mask_bucketed),
                        ("read sweep", dvv_read_sweep_bucketed)):
        info = cache.cache_info()
        print(f"{name} buckets: {len(info['buckets'])}, hits {info['hits']}, "
              f"misses {info['misses']}", flush=True)

    problems = (compiled_kernel_check() + check_run(runs["kernel"])
                + check_run(runs["reference"])
                + diff_runs(runs["kernel"], runs["reference"]))
    for p in problems:
        print(f"FAIL: {p}", file=sys.stderr)
    if problems:
        return 1
    print(f"kernel plane matches the reference: "
          f"{len(runs['kernel']['reads'])} GETs, "
          f"{len(runs['kernel']['readback'])} writes read back", flush=True)
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices())}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
