"""The store's spans and counters (``repro.trace``): free when off, exact
totals and self times when on, one flush's spans tagged with its number on
the profile, and names apart from the chip benchmark's own annotations."""
import argparse
import json
import sys
from pathlib import Path

import jax
import numpy as np
import pytest

from repro import trace
from repro.core import DVV_MECHANISM
from repro.core.batched import BucketedSyncMask
from repro.launch.serve import store_workload_main
from repro.store import KVCluster, OpScheduler, SimNetwork

BENCH = Path(__file__).resolve().parents[1] / "benchmarks" / "chip"


@pytest.fixture
def table():
    """A clean table, tracing off before and after."""
    trace.disable()
    trace.reset()
    yield
    trace.disable()
    trace.reset()


def _cluster():
    return KVCluster(("a", "b", "c"), DVV_MECHANISM, replication=3,
                     network=SimNetwork(seed=1, jitter=0.0), read_quorum=2,
                     write_quorum=2, seed=1)


def _serve(cluster, n_ops=6):
    """``n_ops`` PUT ops and as many GETs through one scheduler flush."""
    sch = OpScheduler(cluster, via="a", max_batch=1000)
    for i in range(n_ops):
        sch.submit_put({f"k{i}": (f"v{i}", None)})
    sch.flush()
    for i in range(n_ops):
        sch.submit_get([f"k{i}"])
    sch.flush()
    cluster.deliver_replication()
    cluster.delta_antientropy_round()
    return sch


def test_off_records_nothing_and_reads_no_clock(table, monkeypatch):
    def no_clock():
        raise AssertionError("a span read the clock while tracing was off")

    monkeypatch.setattr(trace, "_clock", no_clock)
    assert not trace.active()
    first = trace.span(trace.PACKED_GATHER)
    assert trace.span(trace.AE_APPLY) is first
    with first:
        pass
    trace.count(trace.SCHED_OPS_FLUSHED, 3)
    _serve(_cluster())
    assert trace.snapshot() == {"spans": {}, "counters": {}}


def test_nested_spans_total_and_self(table, monkeypatch):
    ticks = iter([0, 10, 15, 40, 50, 80, 90, 100])
    monkeypatch.setattr(trace, "_clock", lambda: next(ticks))
    trace.enable()
    with trace.span("outer"):                  # 0 .. 100
        with trace.span("inner"):              # 10 .. 15
            pass
        with trace.span("inner"):              # 40 .. 90
            with trace.span("leaf"):           # 50 .. 80
                pass
    spans = trace.snapshot()["spans"]
    assert spans["leaf"] == {"calls": 1, "total_ns": 30, "self_ns": 30}
    assert spans["inner"] == {"calls": 2, "total_ns": 55, "self_ns": 25}
    assert spans["outer"] == {"calls": 1, "total_ns": 100, "self_ns": 45}


def test_delta_is_what_happened_between(table):
    trace.enable()
    trace.count("c", 2)
    with trace.span("s"):
        pass
    a = trace.snapshot()
    trace.count("c", 5)
    trace.count("d")
    with trace.span("s"):
        pass
    with trace.span("t"):
        pass
    b = trace.snapshot()
    d = trace.delta(a, b)
    assert d["counters"] == {"c": 5, "d": 1}
    assert set(d["spans"]) == {"s", "t"}
    assert d["spans"]["s"]["calls"] == 1
    assert d["spans"]["s"]["total_ns"] == \
        b["spans"]["s"]["total_ns"] - a["spans"]["s"]["total_ns"]
    assert trace.delta(b, b) == {"spans": {}, "counters": {}}


def test_served_path_spans_and_queue_wait(table):
    trace.enable()
    sch = _serve(_cluster())
    snap = trace.snapshot()
    spans, counters = snap["spans"], snap["counters"]
    assert counters[trace.SCHED_OPS_FLUSHED] == sch.ops_submitted == 12
    assert counters[trace.SCHED_QUEUE_WAIT_NS] > 0
    assert spans[trace.SCHED_FLUSH]["calls"] == sch.flushes == 2
    for name in (trace.SCHED_ADMIT, trace.SCHED_PLAN, trace.SCHED_COMPLETE,
                 trace.PLANE_GET_ADMIT, trace.PLANE_GET_RESULT,
                 trace.PLANE_PUT_ADMIT, trace.PLANE_PUT_UPDATE,
                 trace.PLANE_PUT_REPLICATE, trace.PACKED_GATHER,
                 trace.PACKED_MASK, trace.PACKED_CEILING,
                 trace.PACKED_SCATTER, trace.NET_DELIVER_SCAN,
                 trace.NET_APPLY, trace.AE_DIGEST):
        assert spans[name]["calls"] > 0, name
    # phases, not keys: one admission span per flush for 6 ops each
    assert spans[trace.SCHED_ADMIT]["calls"] == 2
    assert spans[trace.PLANE_GET_ADMIT]["calls"] == 1
    for row in spans.values():
        assert 0 <= row["self_ns"] <= row["total_ns"]
    # the flush's spans are its children: its self time is the rest
    flush = spans[trace.SCHED_FLUSH]
    assert flush["self_ns"] < flush["total_ns"]
    assert set(spans) | set(counters) <= set(trace.NAMES)


def test_kernel_front_spans(table):
    trace.enable()
    cache = BucketedSyncMask()
    args = (np.zeros((3, 2, 4), np.int32), np.full((3, 2), -1, np.int32),
            np.zeros((3, 2), np.int32), np.ones((3, 2), bool))
    cache(*args)
    cache(*args)
    spans = trace.snapshot()["spans"]
    assert spans[trace.KERNEL_FRONT_COLD]["calls"] == 1
    assert spans[trace.KERNEL_FRONT]["calls"] == 1
    for child in (trace.KERNEL_PAD, trace.KERNEL_DISPATCH,
                  trace.KERNEL_FETCH):
        assert spans[child]["calls"] == 2
    assert (cache.hits, cache.misses) == (1, 1)


def test_stack_counters_and_their_reader(table):
    """The cluster plane counts the tensors it stacks and the launches it
    makes, only while on; ``tensors_per_launch`` reads their ratio and
    gives nothing without them."""
    import importlib.util
    spec = importlib.util.spec_from_file_location(
        "tensors_per_launch", BENCH / "metrics" / "tensors_per_launch.py")
    reader = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(reader)
    c = KVCluster(("a", "b", "c", "d", "e"), DVV_MECHANISM, replication=3,
                  shards=8, network=SimNetwork(seed=1), read_quorum=2,
                  write_quorum=2, seed=1)
    keys = [f"k{i}" for i in range(40)]
    c.put_many({k: (k, None) for k in keys}, via="a", use_kernel=True)
    assert trace.snapshot()["counters"] == {}
    assert reader.read({}) is None
    trace.enable()
    c.get_many(keys, via="a", use_kernel=True)
    counters = trace.snapshot()["counters"]
    assert counters[trace.PLANE_STACK_LAUNCHES] == 2
    assert counters[trace.PLANE_STACK_TENSORS] >= 8   # one per shard group
    # stacking pads each launch to its largest K and R, never below
    assert counters[trace.PLANE_STACK_LAUNCHED_CELLS] >= \
        counters[trace.PLANE_STACK_CELLS] > 0
    # and the clock-pair comparisons of the tensors stacked
    assert counters[trace.PLANE_STACK_COMPARISONS] > 0
    assert reader.read({}) == counters[trace.PLANE_STACK_TENSORS] / 2


def test_profile_turns_spans_on_and_tags_the_flush(table, tmp_path):
    assert not trace.active()
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 1
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    try:
        assert trace.active()
        _serve(_cluster())
    finally:
        jax.profiler.stop_trace()
    assert not trace.active()
    assert trace.snapshot()["spans"][trace.SCHED_FLUSH]["calls"] == 2
    [path] = tmp_path.glob("**/*.xplane.pb")
    flushes = {}
    for plane in jax.profiler.ProfileData.from_file(str(path)).planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for e in line.events:
                if e.name in (trace.SCHED_FLUSH, trace.SCHED_ADMIT,
                              trace.PLANE_GET_ADMIT):
                    stats = dict(e.stats)
                    flushes.setdefault(e.name, []).append(stats.get("flush"))
    assert sorted(flushes[trace.SCHED_FLUSH]) == [1, 2]
    assert sorted(flushes[trace.SCHED_ADMIT]) == [1, 2]
    assert flushes[trace.PLANE_GET_ADMIT] == [2]


def test_names_are_apart_from_the_benchmark_annotations():
    sys.path.insert(0, str(BENCH))
    try:
        from chipbench.trace_reduce import ANNOTATIONS
    finally:
        sys.path.remove(str(BENCH))
    assert not set(trace.NAMES) & set(ANNOTATIONS)
    assert all(kind in ("span", "counter") and layer and metric
               for kind, layer, metric in trace.NAMES.values())


def test_serve_trace_dir_writes_a_profile_and_the_table(table, tmp_path,
                                                        capsys):
    args = argparse.Namespace(
        store_mode="coalesced", trace_dir=str(tmp_path), sessions=200,
        keys=50, zipf=0.9, concurrency=8, store_steps=20, max_batch=256,
        max_delay=2.0, gossip_period=0.0, seed=3, use_kernel=False)
    assert store_workload_main(args) == 0
    out = json.loads(capsys.readouterr().out)
    assert out["trace"]["spans"][trace.SCHED_FLUSH]["calls"] > 0
    assert out["trace"]["counters"][trace.SCHED_OPS_FLUSHED] == out["ops"]
    assert len(list(tmp_path.glob("**/*.xplane.pb"))) == 1
    assert not trace.active()
