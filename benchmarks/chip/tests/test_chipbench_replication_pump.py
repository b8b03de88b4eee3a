"""The reader of ``parts_per_store_apply``: the ratio of the replication
pump's apply counters in a traced tiny serving run, and nothing in an
untraced run or without the counters."""
from __future__ import annotations

import importlib.util
import shutil

import pytest

from _chipbench_tiny import BENCH, ROOT, tiny_run

from repro import trace


@pytest.fixture
def reader():
    spec = importlib.util.spec_from_file_location(
        "parts_per_store_apply", BENCH / "metrics" / "parts_per_store_apply.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    trace.disable()
    trace.reset()
    yield mod
    trace.disable()
    trace.reset()


def _copy(tmp_path):
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path / "BENCHMARK.json")
    shutil.copytree(BENCH, tmp_path / "benchmarks" / "chip",
                    ignore=shutil.ignore_patterns("tests", "__pycache__"))
    return tmp_path


def test_traced_serving_run_reads_parts_per_store_apply(reader, tmp_path):
    r = tiny_run("ycsb-a.riak5", root=_copy(tmp_path), trace=True)
    assert r["correct"]
    counters = trace.snapshot()["counters"]
    parts = counters[trace.REPLICA_APPLY_PARTS]
    stores = counters[trace.REPLICA_APPLY_STORES]
    assert parts >= stores > 0
    got = r["metrics"]["parts_per_store_apply"]
    assert got == {"value": parts / stores, "unit": "parts"}
    assert got["value"] >= 1
    assert reader.read({}) == parts / stores


def test_untraced_serving_run_reports_no_parts_per_store_apply(reader,
                                                               tmp_path):
    r = tiny_run("ycsb-a.riak5", root=_copy(tmp_path))
    assert r["correct"]
    assert "parts_per_store_apply" not in r["metrics"]
    assert reader.read({}) is None


def test_parts_per_store_apply_without_counters_is_none(reader):
    trace.enable()
    trace.count(trace.AE_STACK_PAYLOADS, 6)     # another layer's counters
    trace.count(trace.AE_STACK_LAUNCHES, 2)
    assert reader.read({}) is None
