"""One run of one cell: set-up, warm-up, the measured window, the check.

Everything that belongs to one configuration, mix or per-layer metric is
found by name from ``BENCHMARK.json``:

* a configuration is the file its ``configs`` entry names;
* a mix is ``benchmarks/chip/traffic/<traffic>.json``;
* a per-layer metric is ``benchmarks/chip/metrics/<metric>.py``, whose
  ``read(window)`` returns the metric's value or ``None`` where the window
  holds nothing for it to read.

The ``window`` a reader gets is a dict of window differences:
``window_s``; ``ops`` and ``failed`` (serving) or ``repaired_keys``,
``cycles`` and ``ae`` (summed ``DeltaSyncStats``, repair); ``scheduler``
(``OpScheduler.stats()`` fields, serving); ``plane_s`` and ``plane_calls``
(the cluster-plane span); ``kernel_hits``, ``kernel_misses`` and
``kernel_shapes`` (calls by logical ``[N, K, R]``) by kernel; ``peaks``
(the device's row of ``peaks.json``, ``None`` off a TPU); and ``trace``
(``trace_reduce.reduce_trace`` of the window in a traced run, else
``None``).
"""
from __future__ import annotations

import importlib.util
import json
import math
import shutil
import statistics
import time
from collections import Counter
from pathlib import Path
from typing import Any, Callable, Dict, List, Optional

import numpy as np

from . import deploy, kernel_cost
from .generator import ClosedLoop, make_traffic
from .oracle import CHECKS, CausalOracle
from .probes import Probes, window_delta

BENCH_DIR = Path(__file__).resolve().parents[1]
ROOT = BENCH_DIR.parents[1]
#: Where a traced run's profile is written, inside the checkout; removed
#: once it has been read.
TRACE_DIR = ".bench_trace"


class Bench:
    """``BENCHMARK.json`` and the files it names, under ``root``."""

    def __init__(self, root: Path = ROOT):
        self.root = Path(root)
        self.spec = json.loads((self.root / "BENCHMARK.json").read_text())
        self.dir = self.root / "benchmarks" / "chip"
        self.peaks = json.loads((self.dir / "peaks.json").read_text())

    def cell(self, name: str) -> Dict[str, Any]:
        for w in self.spec["workloads"]:
            if w["name"] == name:
                return w
        raise KeyError(f"no workload {name!r} in BENCHMARK.json")

    def config(self, name: str) -> Dict[str, Any]:
        for c in self.spec["configs"]:
            if c["name"] == name:
                return deploy.read_config(self.root / c["file"])
        raise KeyError(f"no configuration {name!r} in BENCHMARK.json")

    def traffic(self, name: str) -> Dict[str, Any]:
        return json.loads((self.dir / "traffic" / f"{name}.json").read_text())

    def end_to_end(self, cell: str) -> List[Dict[str, Any]]:
        """The end-to-end metrics the cell reports: those that list it, and
        those that list no cells."""
        return [m for m in self.spec["end_to_end"]
                if cell in m.get("workloads", [cell])]

    def per_layer(self, cell: str) -> List[Dict[str, Any]]:
        """The per-layer metrics the cell reports: those that list it, and
        those that list no cells but move an end-to-end metric it reports."""
        reported = {m["name"] for m in self.end_to_end(cell)}
        return [m for m in self.spec["per_layer"]
                if (cell in m["workloads"] if "workloads" in m
                    else m["moves"] in reported)]

    def reader(self, metric: str) -> Callable[[Dict[str, Any]], Any]:
        path = self.dir / "metrics" / f"{metric}.py"
        spec = importlib.util.spec_from_file_location(
            "chipbench_metric_" + metric.replace(".", "_").replace("-", "_"),
            path)
        mod = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(mod)
        return mod.read

    def device_peaks(self, kind: str) -> Dict[str, float]:
        table = self.peaks["devices"]
        if kind not in table:
            raise KeyError(f"device {kind!r} is not in peaks.json")
        return table[kind]


class CompileCounter:
    """Counts programs that JAX compiled or fetched from its persistent
    cache, and the seconds its backend spent compiling."""

    def __init__(self):
        from jax import monitoring
        self.requests = 0
        self.cache_hits = 0
        self.compile_s = 0.0
        monitoring.register_event_listener(self._event)
        monitoring.register_event_duration_secs_listener(self._duration)

    def _event(self, name: str, **_: Any) -> None:
        if name == "/jax/compilation_cache/compile_requests_use_cache":
            self.requests += 1
        elif name == "/jax/compilation_cache/cache_hits":
            self.cache_hits += 1

    def _duration(self, name: str, secs: float, **_: Any) -> None:
        if name == "/jax/core/compile/backend_compile_duration":
            self.compile_s += secs

    def snapshot(self) -> Dict[str, Any]:
        return {"programs": self.requests, "from_cache": self.cache_hits,
                "compile_s": self.compile_s}


def _quantile(xs: List[float], q: float) -> float:
    """Nearest-rank quantile."""
    s = sorted(xs)
    return s[max(0, math.ceil(q * len(s)) - 1)]


def _by_k(shapes: Dict[Any, int]) -> Dict[int, int]:
    """Calls by their logical K (clock slots per key)."""
    out: Dict[int, int] = Counter()
    for (_, k, _), c in shapes.items():
        out[k] += c
    return out


def _by_bucket(shapes: Dict[Any, int]) -> Dict[Any, int]:
    """Calls by the shape bucket the cache pads them to."""
    from repro.core.batched import bucket_shape
    out: Dict[Any, int] = Counter()
    for s, c in shapes.items():
        out[bucket_shape(*s)] += c
    return out


def _warm_grid(probes: Probes, grid: Dict[str, Dict[str, List[int]]],
               dtypes: Dict[str, Any]) -> int:
    """Call each bucket cache once at every bucket of ``grid``."""
    caches = probes.caches()
    n_calls = 0
    for kind, axes in grid.items():
        vv_t, id_t, n_t, ok_t = dtypes.get(
            kind, (np.int32, np.int32, np.int32, np.bool_))
        for n in axes["n"]:
            for k in axes["k"]:
                for r in axes["r"]:
                    caches[kind](np.zeros((n, k, r), vv_t),
                                 np.full((n, k), -1, id_t),
                                 np.zeros((n, k), n_t),
                                 np.zeros((n, k), ok_t))
                    n_calls += 1
    return n_calls


def device_info() -> Dict[str, Any]:
    import jax
    devs = jax.devices()
    peak = 0
    for d in devs:
        stats = d.memory_stats() or {}
        peak = max(peak, int(stats.get("peak_bytes_in_use", 0)))
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs), "memory_peak_bytes": peak}


def run_cell(bench: Bench, workload: str, seed: int, seconds: float,
             trace: bool, *, t_start: float,
             config_overrides: Optional[Dict[str, Any]] = None,
             traffic_overrides: Optional[Dict[str, Any]] = None,
             mechanism: Optional[str] = None,
             tamper: Optional[Callable[[Any, Any], None]] = None,
             log: Callable[[str], None] = lambda s: print(s, flush=True)
             ) -> Dict[str, Any]:
    """Run one cell and return its result line as a dict.

    ``config_overrides``/``traffic_overrides`` replace top-level keys of the
    configuration and the mix (small sizes for the CPU tests; the controls).
    ``mechanism`` swaps the clock mechanism (the control's).  ``tamper``
    is called with the cluster and the traffic driver right before the
    window (the fault tests)."""
    cell = bench.cell(workload)
    cfg = dict(bench.config(cell["config"]), **(config_overrides or {}))
    spec = dict(bench.traffic(cell["traffic"]), **(traffic_overrides or {}))
    compiles = CompileCounter()
    nbytes = deploy.record_bytes(cfg)
    proxy = cfg["deployment"]["proxy"]

    t = time.perf_counter()
    records = deploy.make_records(int(cfg["records"]), nbytes, seed)
    t_records = time.perf_counter() - t
    cluster = deploy.build_cluster(cfg, seed, mechanism=mechanism)
    t = time.perf_counter()
    load_dots = deploy.load(cluster, records, proxy)
    t_load = time.perf_counter() - t
    oracle = CausalOracle(
        lambda k: records[deploy.key_index(k)],
        lambda k: load_dots[deploy.key_index(k)])

    probes = Probes(annotate=trace)
    probes.install_kernels()
    probes.install_cluster(cluster)
    try:
        driver = make_traffic(spec["kind"])(cluster, cfg, spec, seed, oracle,
                                            nbytes)
        if isinstance(driver, ClosedLoop):
            probes.install_scheduler(driver.scheduler)
        t = time.perf_counter()
        if isinstance(driver, ClosedLoop):
            driver.run(float(spec["warmup_seconds"]))
        else:
            for _ in range(int(spec["warmup_cycles"])):
                driver.cycle()
        grid_calls = _warm_grid(probes, spec.get("warm_buckets", {}),
                                probes.dtypes)
        t_warm = time.perf_counter() - t
        if tamper is not None:
            tamper(cluster, driver)

        before = probes.counters()
        compiles_before = compiles.snapshot()
        sched_before = (driver.scheduler.stats()
                        if isinstance(driver, ClosedLoop) else None)
        split_before = (None if isinstance(driver, ClosedLoop)
                        else driver.split())
        ae_before = len(getattr(driver, "ae_stats", []))
        gets_before = oracle.gets_observed
        setup_s = time.perf_counter() - t_start
        log(f"setup: {setup_s:.3f} s (records {t_records:.3f} s, load "
            f"{t_load:.3f} s, warm-up {t_warm:.3f} s with {grid_calls} "
            f"bucket calls); programs {json.dumps(compiles_before)}")

        trace_path = bench.root / TRACE_DIR / workload
        if trace:
            import jax
            shutil.rmtree(trace_path, ignore_errors=True)
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            opts.host_tracer_level = 1
            jax.profiler.start_trace(str(trace_path), profiler_options=opts)
        w: Dict[str, Any] = {"trace": None}
        with probes.span("window"):
            if isinstance(driver, ClosedLoop):
                ops, window_s = driver.run(seconds)
                w.update(ops=ops.ops, failed=ops.failed, window_s=window_s)
            else:
                repaired, window_s, cycles = driver.run(seconds)
                w.update(repaired_keys=repaired, window_s=window_s,
                         cycles=cycles, failed=0)
        if trace:
            import jax
            jax.profiler.stop_trace()
        device = device_info()
        after = probes.counters()
        compiles_after = compiles.snapshot()
    finally:
        probes.uninstall()

    delta = window_delta(before, after)
    w.update(delta)
    w["window_programs"] = (compiles_after["programs"]
                            - compiles_before["programs"])
    w["peaks"] = bench.device_peaks(device["kind"]) \
        if device["platform"] == "tpu" else None
    if sched_before is not None:
        sched_after = driver.scheduler.stats()
        w["scheduler"] = {k: sched_after[k] - sched_before[k]
                          for k in ("ops_submitted", "flushes", "phases",
                                    "get_calls", "put_calls")}
    else:
        stats = driver.ae_stats[ae_before:]
        w["ae"] = {"payload_slots": sum(s.payload_slots for s in stats),
                   "payload_bytes": sum(s.payload_bytes for s in stats),
                   "digest_bytes": sum(s.digest_bytes for s in stats),
                   "buckets_divergent": sum(s.buckets_divergent
                                            for s in stats),
                   "rounds_pairs": len(stats)}
        w["repair_split_s"] = {k: v - split_before[k]
                               for k, v in driver.split().items()}
    if trace:
        from .trace_reduce import reduce_trace
        w["trace"] = reduce_trace(trace_path)
        shutil.rmtree(bench.root / TRACE_DIR, ignore_errors=True)

    # -- after the window: read back, then judge against the reference
    siblings = oracle.sibling_counts(since=gets_before)
    if isinstance(driver, ClosedLoop):
        _read_back(cluster, oracle, spec, cfg, seed)
    gets_checked = oracle.gets_observed
    counts = oracle.judge()

    e2e: Dict[str, float] = {"setup_s": setup_s}
    info: Dict[str, Any] = {
        "window_s": w["window_s"], "window_programs": w["window_programs"],
        "kernel_misses_in_window": delta["kernel_misses"],
        "kernel_calls_in_window": {k: delta["kernel_hits"][k]
                                   + delta["kernel_misses"][k]
                                   for k in delta["kernel_hits"]},
        "siblings_per_get": siblings,
        "kernel_calls_by_k": {
            kind: dict(sorted(_by_k(shapes).items()))
            for kind, shapes in delta["kernel_shapes"].items()},
        "kernel_calls_by_bucket": {
            kind: {"x".join(map(str, b)): c
                   for b, c in sorted(_by_bucket(shapes).items())}
            for kind, shapes in delta["kernel_shapes"].items()},
        "kernel_bytes": {
            kind: kernel_cost.total_bytes(kind, shapes)
            for kind, shapes in delta["kernel_shapes"].items()},
        "kernel_comparisons": {
            kind: kernel_cost.total_comparisons(shapes)
            for kind, shapes in delta["kernel_shapes"].items()},
        "memory_peak_bytes": device["memory_peak_bytes"],
    }
    if isinstance(driver, ClosedLoop):
        lat_ms = [x * 1e3 for x in ops.latency_s]
        e2e["ops_per_s"] = ops.ops / w["window_s"]
        e2e["op_p95_ms"] = _quantile(lat_ms, 0.95)
        by_kind = {kind: statistics.median(
            [x for x, k in zip(lat_ms, ops.kinds) if k == kind])
            for kind in sorted(set(ops.kinds))}
        info.update(ops=ops.ops, op_median_ms=statistics.median(lat_ms),
                    median_ms_by_op=by_kind,
                    op_p90_ms=_quantile(lat_ms, 0.90),
                    op_p99_ms=_quantile(lat_ms, 0.99),
                    op_p95_samples_beyond=sum(1 for x in lat_ms
                                              if x > e2e["op_p95_ms"]),
                    scheduler=w["scheduler"])
        attempted, failed = ops.ops, ops.failed
    else:
        e2e["repair_keys_per_s"] = w["repaired_keys"] / w["window_s"]
        info.update(repaired_keys=w["repaired_keys"], cycles=w["cycles"],
                    **w["repair_split_s"], ae=w["ae"])
        attempted, failed = w["repaired_keys"], counts["replica_split"]
    log("window: " + json.dumps(info))

    # the serving cells observe no replicas; the repair cell's calls raise
    # rather than fail op by op
    if isinstance(driver, ClosedLoop):
        compared = [c for c in CHECKS if c != "replica_split"] + ["failed_ops"]
        counts["failed_ops"] = failed
    else:
        compared = list(CHECKS)
    checks = {name: {"value": counts[name], "limit": 0} for name in compared}
    correct = gets_checked > 0 and all(
        c["value"] <= c["limit"] for c in checks.values())

    if trace:
        metrics = {}
        for m in bench.per_layer(workload):
            value = bench.reader(m["name"])(w)
            if value is not None:
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}
        device.update(busy_s=w["trace"]["busy_s"],
                      window_s=w["trace"]["window_s"])
    else:
        metrics = {}
        for m in bench.end_to_end(workload):
            if m["name"] not in e2e:
                raise KeyError(f"{workload} does not measure {m['name']}")
            value = e2e[m["name"]]
            if not math.isfinite(value):
                value = 1e12
            metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    result: Dict[str, Any] = {
        "correct": bool(correct), "attempted": attempted, "failed": failed,
        "metrics": metrics, "device": device}
    if trace:
        result["breakdown"] = w["trace"]["breakdown"]
    result["checks"] = dict(checks, gets_checked={"value": gets_checked,
                                                  "at_least": 1})
    return result


def _read_back(cluster, oracle: CausalOracle, spec: Dict[str, Any],
               cfg: Dict[str, Any], seed: int) -> None:
    """Read a seeded sample of keys back at R through another proxy, on the
    timed path's own entry, once all replication is delivered: the hottest
    keys (every acknowledged write among them) and keys drawn uniformly."""
    n = int(cfg["records"])
    want = int(spec["readback_keys"])
    rng = np.random.default_rng([seed, 1])
    keys = list(dict.fromkeys(
        [deploy.key_name(i) for i in range(min(want // 2, n))]
        + [deploy.key_name(int(i))
           for i in rng.choice(n, size=min(want // 2, n), replace=False)]))
    nodes = list(cluster.nodes)
    via = nodes[(nodes.index(cfg["deployment"]["proxy"]) + 2) % len(nodes)]
    cluster.deliver_replication()
    got = cluster.get_many(keys, via=via, quorum=int(cfg["deployment"]["r"]),
                           use_kernel=True)
    for k in keys:
        oracle.observe_get(k, got[k].values, got[k].context,
                           oracle.acked_now(k))
