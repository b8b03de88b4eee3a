"""The program's own spans in the benchmark: the six metrics that read
them, a table that stays empty outside a profile, and the second charge of
the device's idle gaps to the innermost program span, which leaves the
harness's breakdown as it was."""
from __future__ import annotations

import math
import shutil
from types import SimpleNamespace as NS

import pytest

from _chipbench_tiny import BENCH, ROOT, Bench, tiny_run

from chipbench.program_idle import UNCOVERED, idle_by_program_span, \
    traced_run
from chipbench.trace_reduce import reduce_planes
from repro import trace

NEW = {"ycsb-a.riak5": {"queue_wait_ms_per_op", "kernel_front_ms_per_call",
                        "gather_ms_per_op", "deliver_scan_ms_per_op"},
       "ycsb-a.repair": {"ae_digest_ms_per_repaired_key",
                         "ae_apply_ms_per_repaired_key"}}
MS = 1_000_000


def _copy(tmp_path):
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path / "BENCHMARK.json")
    shutil.copytree(BENCH, tmp_path / "benchmarks" / "chip",
                    ignore=shutil.ignore_patterns("tests", "__pycache__"))
    return tmp_path


@pytest.fixture
def table():
    trace.disable()
    trace.reset()
    yield
    trace.reset()


@pytest.mark.parametrize("workload", sorted(NEW))
def test_traced_run_reports_the_program_span_metrics(workload, tmp_path,
                                                     table):
    r = tiny_run(workload, root=_copy(tmp_path), trace=True)
    assert r["correct"]
    assert NEW[workload] <= set(r["metrics"])
    for name in NEW[workload]:
        value = r["metrics"][name]["value"]
        assert math.isfinite(value) and value > 0, (name, value)
        assert r["metrics"][name]["unit"] == "ms"
    others = set().union(*NEW.values()) - NEW[workload]
    assert not others & set(r["metrics"])
    assert not trace.active()


def test_untraced_run_leaves_the_table_empty(table):
    r = tiny_run("ycsb-a.riak5")
    assert r["correct"]
    assert trace.snapshot() == {"spans": {}, "counters": {}}


def test_traced_run_charges_the_program_spans(tmp_path, table):
    result, seen = traced_run(Bench(_copy(tmp_path)), "ycsb-a.repair",
                              2 ** 33 + 7, 1.5, t_start=0.0,
                              config_overrides={"records": 2000},
                              traffic_overrides={"burst": 32,
                                                 "warmup_cycles": 1},
                              log=lambda s: None)
    assert result["correct"]
    spans = seen["program"]["spans"]
    assert spans[trace.AE_DIGEST]["calls"] > 0
    assert spans[trace.AE_APPLY]["calls"] > 0
    # no device plane on the CPU: nothing idle to charge
    assert seen["idle_s"] == 0 and seen["uncovered_share"] is None


def _ev(name, start_ms, end_ms):
    return NS(name=name, start_ns=start_ms * MS,
              duration_ns=(end_ms - start_ms) * MS)


def _planes(program_spans):
    device = NS(name="/device:TPU:0", lines=[
        NS(name="XLA Ops", events=[_ev("fusion", 10, 12),
                                   _ev("dvv_sync_mask", 30, 31)]),
        NS(name="XLA Modules", events=[
            _ev("jit_dvv_read_sweep_pallas(1)", 10, 14),
            _ev("jit_dvv_sync_mask_pallas(2)", 30, 31)])])
    host = NS(name="/host:CPU", lines=[NS(name="main", events=[
        _ev("window", 8, 48), _ev("flush", 9, 40),
        _ev("cluster.get_many", 9, 20), _ev("kernel.read_sweep", 10, 15),
        _ev("cluster.put_many", 25, 35), _ev("kernel.sync_mask", 29, 32)]
        + program_spans)])
    return [device, host]


def test_program_spans_leave_the_breakdown_and_take_the_second_charge():
    program = [_ev(trace.SCHED_FLUSH, 9, 40),
               _ev(trace.PLANE_GET_ADMIT, 9, 20),
               _ev(trace.PACKED_GATHER, 9, 10),
               _ev(trace.KERNEL_FRONT, 10, 15),
               _ev(trace.PLANE_PUT_UPDATE, 25, 35),
               _ev(trace.PACKED_SCATTER, 31, 34)]
    with_program = reduce_planes(_planes(program))
    assert with_program == reduce_planes(_planes([]))
    assert dict(with_program["breakdown"]["idle_gaps"]) == pytest.approx(
        {"cluster.get_many": 0.002, "flush": 0.016 + 0.017})

    # each idle gap is split at span boundaries: [8,10] half before any
    # span, half in packed.gather; [14,30] kernel.front to 15,
    # plane.get.admit to 20, sched.flush to 25, plane.put.update;
    # [31,48] packed.scatter to 34, plane.put.update to 35, sched.flush
    # to 40, then no span
    out = idle_by_program_span(_planes(program), trace.NAMES)
    assert dict(out["idle_by_program_span"]) == pytest.approx(
        {UNCOVERED: 0.009, trace.PACKED_GATHER: 0.001,
         trace.KERNEL_FRONT: 0.001, trace.PLANE_GET_ADMIT: 0.005,
         trace.SCHED_FLUSH: 0.010, trace.PLANE_PUT_UPDATE: 0.006,
         trace.PACKED_SCATTER: 0.003})
    assert out["idle_s"] == pytest.approx(0.035)
    assert out["uncovered_share"] == pytest.approx(0.009 / 0.035)

    out = idle_by_program_span(_planes(program[1:]), trace.NAMES)
    # without the flush span its time falls under no program span
    assert dict(out["idle_by_program_span"])[UNCOVERED] == \
        pytest.approx(0.019)
    assert out["uncovered_share"] == pytest.approx(0.019 / 0.035)


def test_recorded_chip_trace_without_program_spans():
    """A trace taken before the program wrote spans: every idle instant
    falls under no program span."""
    import jax
    path = str(BENCH / "tests" / "data" / "ycsb-a-small.xplane.pb")
    out = idle_by_program_span(
        jax.profiler.ProfileData.from_file(path).planes, trace.NAMES)
    red = reduce_planes(jax.profiler.ProfileData.from_file(path).planes)
    assert out["idle_s"] == pytest.approx(red["window_s"] - red["busy_s"])
    assert out["uncovered_share"] == pytest.approx(1.0)
