"""The reduction from a profiler trace to busy time, kernel time and idle
gaps: exact on a hand-built trace, and sane on a small trace recorded on a
TPU v5e (``data/``)."""
from __future__ import annotations

from types import SimpleNamespace as NS

import pytest

from _chipbench_tiny import BENCH

from chipbench.trace_reduce import gaps, op_kind, reduce_planes, reduce_trace, \
    union

MS = 1_000_000


def _ev(name, start_ms, end_ms):
    return NS(name=name, start_ns=start_ms * MS,
              duration_ns=(end_ms - start_ms) * MS)


def _plane(name, lines):
    return NS(name=name, lines=[NS(name=n, events=evs)
                                for n, evs in lines.items()])


def test_union_and_gaps():
    assert union([(5, 7), (0, 2), (1, 3), (7, 8)]) == [(0, 3), (5, 8)]
    assert gaps([(2, 3), (5, 8)], 0, 10) == [(0, 2), (3, 5), (8, 10)]


def test_hand_built_trace_reduces_exactly():
    device = _plane("/device:TPU:0", {
        "XLA Ops": [_ev("fusion", 10, 12), _ev("custom-call", 11, 14),
                    _ev("copy", 30, 31), _ev("early", 0, 5)],
        "XLA Modules": [_ev("jit_dvv_read_sweep_pallas(1)", 10, 14),
                        _ev("jit_dvv_sync_mask_pallas(2)", 30, 31)],
    })
    host = _plane("/host:CPU", {"main": [
        _ev("window", 8, 48), _ev("flush", 9, 40),
        _ev("cluster.get_many", 9, 20), _ev("kernel.read_sweep", 10, 15),
        _ev("cluster.put_many", 25, 35), _ev("kernel.sync_mask", 29, 32),
        _ev("unrelated", 8, 48)]})
    other = _plane("/device:TPU:0 SparseCore", {"XLA Ops": [
        _ev("sc", 8, 48)]})
    out = reduce_planes([device, host, other])
    assert out["window_s"] == pytest.approx(0.040)
    assert out["busy_s"] == pytest.approx(0.005)       # [10,14] + [30,31]
    assert out["devices"] == 1
    assert out["kernel_s"]["read_sweep"] == pytest.approx(0.004)
    assert out["kernel_s"]["sync_mask"] == pytest.approx(0.001)
    idle = dict(out["breakdown"]["idle_gaps"])
    # gaps [8,10] in get_many, [14,30] mid 22 in flush, [31,48] mid 39.5
    # in flush
    assert idle == pytest.approx({"cluster.get_many": 0.002,
                                  "flush": 0.016 + 0.017})
    ops = dict(out["breakdown"]["device_ops"])
    assert ops["custom-call"] == pytest.approx(0.003)
    assert op_kind("%copy-start.2 = (s32[8]) copy-start(s32[8] %a)") == \
        "copy-start"
    assert op_kind("%dvv_sync_mask_pallas.1 = s32[2,128] custom-call()") \
        == "dvv_sync_mask_pallas"
    assert "early" not in ops


def test_recorded_chip_trace():
    out = reduce_trace(BENCH / "tests" / "data" / "ycsb-a-small.xplane.pb")
    assert out["devices"] == 1
    assert 0 < out["busy_s"] <= out["window_s"]
    assert out["kernel_s"]["read_sweep"] > 0
    assert out["kernel_s"]["sync_mask"] > 0
    assert out["kernel_s"]["read_sweep"] + out["kernel_s"]["sync_mask"] \
        <= out["busy_s"] * 1.0001
    for key in ("device_ops", "idle_gaps"):
        rows = out["breakdown"][key]
        assert 0 < len(rows) <= 10
        assert all(isinstance(n, str) and s > 0 for n, s in rows)
