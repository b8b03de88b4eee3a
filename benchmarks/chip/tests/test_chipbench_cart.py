"""Tiny CPU runs of the cart cell: 256 clients racing on a few hundred
carts drive sibling sets past 32, so both kernels run at K buckets of 64
and more, and the run still comes out correct; an altered answer does
not.  And the reader of ``stack_comparison_padding``."""
from __future__ import annotations

import json
import time

import numpy as np
import pytest

from _chipbench_tiny import BENCH, Bench, run_cell

#: 200 carts instead of 100,000: the same 256 clients then race on every
#: cart, not only the hottest, within a window of seconds.
CARTS = 200


def _cart_run(seconds=2.0, **kw):
    bench = Bench()
    cfg = bench.config(bench.cell("cart.shared")["config"])
    lines = []
    r = run_cell(
        bench, "cart.shared", 2 ** 33 + 11, seconds, False,
        t_start=time.perf_counter(),
        config_overrides={"records": CARTS,
                          "deployment": dict(cfg["deployment"], shards=4)},
        traffic_overrides={
            "warmup_seconds": 0.5, "readback_keys": 32,
            "warm_buckets": {"read_sweep": {"n": [8], "k": [2], "r": [8]},
                             "sync_mask": {"n": [8], "k": [2], "r": [8]}}},
        log=lines.append, **kw)
    [window] = [json.loads(s[len("window: "):]) for s in lines
                if s.startswith("window: ")]
    return r, window


def test_sound_cart_run_is_correct_past_32_siblings():
    r, w = _cart_run()
    checks = {k: v["value"] for k, v in r["checks"].items()}
    assert r["correct"], checks
    assert r["failed"] == 0 and checks["gets_checked"] > 0
    assert set(r["metrics"]) == {"ops_per_s", "op_p95_ms", "setup_s"}
    assert r["attempted"] >= 2 * 256
    assert max(int(s) for s in w["siblings_per_get"]) > 32
    read_ks = [int(k) for k in w["kernel_calls_by_k"]["read_sweep"]]
    assert max(read_ks) > 32              # a K = 64 bucket or wider ran


def _alter_one_answer(cluster, driver):
    from dataclasses import replace
    real = cluster.get_many
    done = []

    def get_many(keys, **kw):
        out = real(keys, **kw)
        if not done and out:
            k = next(iter(out))
            out[k] = replace(out[k], values=("altered",) + out[k].values[1:])
            done.append(k)
        return out

    cluster.get_many = get_many


def test_cart_run_with_one_altered_get_is_not_correct():
    r, _ = _cart_run(seconds=1.0, tamper=_alter_one_answer)
    assert not r["correct"]
    assert r["checks"]["unknown_value"]["value"] > 0


@pytest.fixture
def reader():
    import importlib.util
    spec = importlib.util.spec_from_file_location(
        "stack_comparison_padding",
        BENCH / "metrics" / "stack_comparison_padding.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    from repro import trace
    trace.disable()
    trace.reset()
    yield mod
    trace.disable()
    trace.reset()


def _tensor(n, k, r):
    return (np.zeros((n, k, r), np.int32), np.full((n, k), -1, np.int32),
            np.zeros((n, k), np.int32), np.ones((n, k), bool))


def test_stack_comparison_padding_reads_launched_over_given_pairs(reader):
    """Two tensors, ``[3, 5, 2]`` and ``[40, 3, 4]``, stack into a launch
    of 32 keys padded to K 5 and R 4 (bucket 32 x 8 x 8) and one of the
    last 11 keys (bucket 16 x 4 x 8); the window's ``kernel_shapes`` are
    those launches' shapes."""
    from collections import Counter

    from repro import trace
    from repro.core.batched import sync_mask_np
    from repro.store.packed import stacked_launches

    given = 3 * 5 * 5 * 2 + 40 * 3 * 3 * 4
    launched = 32 * 8 * 8 * 8 + 16 * 4 * 4 * 8
    shapes = Counter()

    def fn(vvs, *rest):
        shapes[vvs.shape] += 1
        return sync_mask_np(vvs, *rest)

    tensors = [_tensor(3, 5, 2), _tensor(40, 3, 4)]
    stacked_launches(fn, tensors)
    window = {"kernel_shapes": {"sync_mask": shapes}}
    assert reader.read(window) is None    # tracing off: nothing counted
    trace.enable()
    shapes.clear()
    stacked_launches(fn, tensors)
    assert shapes == {(32, 5, 4): 1, (11, 3, 4): 1}
    assert trace.snapshot()["counters"][trace.PLANE_STACK_COMPARISONS] \
        == given
    assert reader.read(window) == pytest.approx(launched / given)


def test_stack_comparison_padding_without_counters_is_none(reader):
    from repro import trace
    window = {"kernel_shapes": {"sync_mask": {(8, 2, 8): 1}}}
    assert reader.read(window) is None
    trace.enable()
    trace.count(trace.PLANE_STACK_LAUNCHES, 3)  # counters of another name
    assert reader.read(window) is None
