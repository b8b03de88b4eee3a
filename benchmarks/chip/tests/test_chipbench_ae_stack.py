"""The reader of ``ae_payloads_per_launch``: the ratio of the delta
anti-entropy pushes' stack counters in a traced tiny repair run, nothing
without them, and a delta round that leaves the cluster plane's stack
counters (``tensors_per_launch``) alone."""
from __future__ import annotations

import importlib.util
import shutil

import pytest

from _chipbench_tiny import BENCH, ROOT, tiny_run

from repro import trace


@pytest.fixture
def reader():
    spec = importlib.util.spec_from_file_location(
        "ae_payloads_per_launch",
        BENCH / "metrics" / "ae_payloads_per_launch.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    trace.disable()
    trace.reset()
    yield mod
    trace.disable()
    trace.reset()


def test_traced_repair_run_reads_payloads_per_launch(reader, tmp_path):
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path / "BENCHMARK.json")
    shutil.copytree(BENCH, tmp_path / "benchmarks" / "chip",
                    ignore=shutil.ignore_patterns("tests", "__pycache__"))
    r = tiny_run("ycsb-a.repair", root=tmp_path, trace=True)
    assert r["correct"]
    counters = trace.snapshot()["counters"]
    payloads = counters[trace.AE_STACK_PAYLOADS]
    launches = counters[trace.AE_STACK_LAUNCHES]
    # four shards a push, each payload a few keys: they share launches
    assert payloads > launches > 0
    got = r["metrics"]["ae_payloads_per_launch"]
    assert got == {"value": payloads / launches, "unit": "payloads"}
    assert reader.read({}) == payloads / launches


def test_payloads_per_launch_without_counters_is_none(reader):
    assert reader.read({}) is None
    trace.enable()
    trace.count(trace.PLANE_STACK_TENSORS, 9)   # the serving plane's own
    trace.count(trace.PLANE_STACK_LAUNCHES, 3)
    assert reader.read({}) is None


def test_delta_round_leaves_the_plane_stack_counters(reader):
    from repro.core import DVV_MECHANISM
    from repro.store import KVCluster, SimNetwork

    nodes = ("a", "b", "c", "d", "e")
    c = KVCluster(nodes, DVV_MECHANISM, replication=3, shards=8,
                  network=SimNetwork(seed=3))
    keys = [f"p{i}" for i in range(40)]
    c.put_many({k: (f"base-{k}", None) for k in keys}, via="a")
    c.deliver_replication()
    c.network.partition({"a", "b"}, {"c", "d", "e"})
    c.put_many({k: (f"z-{k}", None) for k in keys}, via="c")
    c.network.heal()
    c.network.queue.clear()
    trace.enable()
    trace.count(trace.PLANE_STACK_TENSORS, 5)
    trace.count(trace.PLANE_STACK_LAUNCHES, 2)
    stats = c.delta_antientropy_round(use_kernel=True)
    counters = trace.snapshot()["counters"]
    assert counters[trace.PLANE_STACK_TENSORS] == 5
    assert counters[trace.PLANE_STACK_LAUNCHES] == 2
    for name in (trace.PLANE_STACK_CELLS, trace.PLANE_STACK_LAUNCHED_CELLS,
                 trace.PLANE_STACK_COMPARISONS):
        assert name not in counters
    pairs = sum(1 for st in stats for p in st.per_shard if p.payload_slots)
    assert counters[trace.AE_STACK_PAYLOADS] == pairs
    assert 0 < counters[trace.AE_STACK_LAUNCHES] < pairs
    assert reader.read({}) == pairs / counters[trace.AE_STACK_LAUNCHES]
