"""Serving-plane benchmark: coalesced vs per-session plane invocations.

The closed-loop engine (store/serving.py) drives the same zipfian
GET → think → PUT(token) workload twice per operating point — once with
every session op as its own synchronous plane call (``direct``), once
through the ``OpScheduler`` (``coalesced``) — and records what coalescing
buys and what it costs:

* **plane invocations per 1k ops** — the headline: one flush executes as
  one shared read sweep plus a handful of per-coordinator write groups,
  so the coalesced plane count must be ≥5x below direct's 1000/1k (the
  DESIGN.md §11 acceptance bar);
* **bytes per op** — coalesced put groups share per-destination payloads
  and the union read repairs each stale replica once, so wire bytes drop
  too (the workload's read-modify-write gap keeps sibling pressure — and
  with it payload sizes — honest in both modes);
* **p50/p99 op latency in sim ticks** — the queueing delay coalescing
  pays; p99 tracks ``max_delay`` by construction, which is the knob's
  meaning;
* **ops/sec (wall)** — simulator throughput, i.e. the CPU cost of the
  serving plane itself.

Three sections: the session-count sweep (10k → 1M logical sessions), the
flush-policy frontier (``max_delay`` x ``max_batch`` at 1M sessions), and
the §6.4 kernel-path leg reporting cross-flush shape-bucket cache hit
rates (``hits``/``misses`` taken as differences across the measured
window).

Run ``make bench-serving`` → ``BENCH_serving.json``.
"""
from __future__ import annotations

import json
from typing import Any, Dict, List, Optional, Sequence, Tuple

from repro.core import DVV_MECHANISM
from repro.store import ClosedLoopEngine, KVCluster, SimNetwork

NODES = tuple(f"n{i}" for i in range(5))


def _run_mode(mode: str, sessions: int, steps: int, *, seed: int = 11,
              keys: int = 10_000, zipf_s: float = 0.9,
              concurrency: int = 256, think_time: float = 8.0,
              rmw_time: float = 1.0, max_batch: int = 256,
              max_delay: float = 2.0, use_kernel: bool = False
              ) -> Dict[str, Any]:
    """One engine run on a fresh cluster (5 nodes, replication 3,
    R=W=2, packed DVV store).  Same seed ⇒ both modes draw the same
    key/session/think sequences — the workloads are identical."""
    net = SimNetwork(seed=7, jitter=0.0)
    cluster = KVCluster(NODES, DVV_MECHANISM, replication=3, network=net,
                        read_quorum=2, write_quorum=2, seed=7)
    eng = ClosedLoopEngine(
        cluster, sessions=sessions, keys=keys, zipf_s=zipf_s,
        concurrency=concurrency, think_time=think_time, rmw_time=rmw_time,
        mode=mode, via="n0", seed=seed, read_repair=True,
        use_kernel=use_kernel, max_batch=max_batch, max_delay=max_delay)
    return eng.run(steps)


def _pair_row(section: str, d: Dict[str, Any], c: Dict[str, Any],
              **extra: Any) -> Dict[str, Any]:
    ratio = (d["plane_per_1k_ops"] / c["plane_per_1k_ops"]
             if c["plane_per_1k_ops"] else 0.0)
    row = {
        "section": section,
        "sessions": d["sessions"], "keys": d["keys"],
        "zipf_s": d["zipf_s"], "concurrency": d["concurrency"],
        "ops": d["ops"],
        "direct": {k: d[k] for k in (
            "plane_per_1k_ops", "bytes_per_op", "p50_latency_ticks",
            "p99_latency_ticks", "ops_per_sec_wall", "ops_failed")},
        "coalesced": {k: c[k] for k in (
            "plane_per_1k_ops", "bytes_per_op", "p50_latency_ticks",
            "p99_latency_ticks", "ops_per_sec_wall", "ops_failed")},
        "plane_ratio_direct_over_coalesced": round(ratio, 2),
        "bytes_per_op_saved": round(
            d["bytes_per_op"] - c["bytes_per_op"], 1),
        "scheduler": c.get("scheduler"),
        "codec_coalesced": c.get("codec"),
    }
    row.update(extra)
    return row


# ---------------------------------------------------------------------------
# Section 1: session-count sweep — the headline >=5x claim.
# ---------------------------------------------------------------------------

def session_sweep_rows(sessions_list: Sequence[int], steps: int,
                       trace: list, **wk: Any) -> List[str]:
    out = []
    for sessions in sessions_list:
        d = _run_mode("direct", sessions, steps, **wk)
        c = _run_mode("coalesced", sessions, steps, **wk)
        row = _pair_row("coalescing", d, c)
        trace.append(row)
        out.append(
            f"serving_s{sessions},{c['plane_per_1k_ops']:.0f},"
            f"ratio={row['plane_ratio_direct_over_coalesced']:.1f}x;"
            f"bytes/op={c['bytes_per_op']:.1f}vs{d['bytes_per_op']:.1f};"
            f"p99={c['p99_latency_ticks']:.2f}ticks")
    return out


# ---------------------------------------------------------------------------
# Section 2: flush-policy frontier — latency bought per plane call saved.
# ---------------------------------------------------------------------------

def policy_rows(points: Sequence[Tuple[float, int]], sessions: int,
                steps: int, trace: list, **wk: Any) -> List[str]:
    out = []
    d = _run_mode("direct", sessions, steps, **wk)
    for max_delay, max_batch in points:
        c = _run_mode("coalesced", sessions, steps,
                      max_delay=max_delay, max_batch=max_batch, **wk)
        row = _pair_row("flush_policy", d, c,
                        max_delay=max_delay, max_batch=max_batch)
        trace.append(row)
        out.append(
            f"serving_policy_d{max_delay}_b{max_batch},"
            f"{c['plane_per_1k_ops']:.0f},"
            f"ratio={row['plane_ratio_direct_over_coalesced']:.1f}x;"
            f"p99={c['p99_latency_ticks']:.2f}ticks")
    return out


# ---------------------------------------------------------------------------
# Section 3: kernel-path leg — cross-flush shape-bucket cache hit rates
# (DESIGN.md §6.4: coalesced flushes land in a handful of power-of-two
# buckets, so the compiled-kernel cache goes warm after the first flush).
# ---------------------------------------------------------------------------

def kernel_cache_rows(sessions: int, steps: int, trace: list,
                      **wk: Any) -> List[str]:
    from repro.core.batched import sync_mask_bucketed
    from repro.kernels.dvv_ops.ops import dvv_read_sweep_bucketed, \
        dvv_sync_mask_bucketed
    caches = {"read_sweep": dvv_read_sweep_bucketed,
              "sync_mask_kernel": dvv_sync_mask_bucketed,
              "sync_mask_jnp": sync_mask_bucketed}
    warm = _run_mode("coalesced", sessions, max(steps // 4, 50),
                     use_kernel=True, **wk)      # compile/warm the buckets
    before = {name: (cache.hits, cache.misses)
              for name, cache in caches.items()}
    c = _run_mode("coalesced", sessions, steps, use_kernel=True, **wk)
    info = {}
    for name, cache in caches.items():
        hits = cache.hits - before[name][0]
        misses = cache.misses - before[name][1]
        info[name] = {"hits": hits, "misses": misses,
                      "hit_rate": round(hits / (hits + misses), 4)
                      if hits + misses else 0.0,
                      "buckets": cache.cache_info()["buckets"]}
    row = {
        "section": "kernel_bucket_cache",
        "sessions": sessions, "ops": c["ops"],
        "warmup_ops": warm["ops"],
        "plane_per_1k_ops": c["plane_per_1k_ops"],
        "flushes": c["scheduler"]["flushes"],
        "caches": info,
    }
    trace.append(row)
    used = {n: i for n, i in info.items() if i["hits"] + i["misses"]}
    return [
        "serving_kernel_cache,%d,%s" % (
            c["scheduler"]["flushes"],
            ";".join(f"{n}_hit_rate={i['hit_rate']:.3f}"
                     for n, i in used.items()) or "unused")]


# ---------------------------------------------------------------------------
# Harness
# ---------------------------------------------------------------------------

def serving_rows(sessions_list: Sequence[int] = (10_000, 100_000,
                                                 1_000_000),
                 steps: int = 1500,
                 policy_points: Sequence[Tuple[float, int]] = (
                     (1.0, 128), (2.0, 256), (4.0, 512)),
                 json_path: Optional[str] = "BENCH_serving.json",
                 kernel_leg: bool = True,
                 **wk: Any) -> List[str]:
    out, trace = [], []
    out += session_sweep_rows(sessions_list, steps, trace, **wk)
    out += policy_rows(policy_points, max(sessions_list), steps, trace,
                       **wk)
    if kernel_leg:
        out += kernel_cache_rows(max(sessions_list), steps, trace, **wk)
    if json_path:
        with open(json_path, "w") as f:
            json.dump({
                "bench": "serving",
                "note": ("Closed-loop zipfian GET->think->PUT(token) "
                         "workload on the simulated cluster (5 nodes, "
                         "replication 3, R=W=2, packed DVV, read-repair "
                         "on), identical seeds per mode. direct = one "
                         "plane invocation per session op; coalesced = "
                         "OpScheduler flushes (shared read sweep + "
                         "per-coordinator write groups). Latency is "
                         "simulated ticks of queueing delay; ops/sec is "
                         "simulator wall throughput; bytes/op is wire "
                         "bytes over ops. kernel_bucket_cache: "
                         "cross-flush shape-bucket hit rates on the "
                         "use_kernel=True path, stats reset after "
                         "warm-up."),
                "rows": trace}, f, indent=1)
    return out


def rows() -> List[str]:
    """The benchmark-harness smoke hook (`make bench-serving` sweeps)."""
    return serving_rows((2_000,), steps=120, policy_points=((2.0, 64),),
                        json_path=None, keys=500, concurrency=32)


if __name__ == "__main__":
    print("\n".join(serving_rows()))
