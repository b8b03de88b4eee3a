"""Each mix's op sequence is fixed by the seed; bytes are counted from the
logical shape of a kernel call, whatever bucket it is padded to."""
from __future__ import annotations

import json

import numpy as np
import pytest

from _chipbench_tiny import BENCH, Bench

from chipbench import kernel_cost
from chipbench.generator import KeyStream, ValueMaker
from chipbench.probes import Probes

MIXES = sorted(p.stem for p in (BENCH / "traffic").glob("*.json"))


def _ops(mix, seed, n=3000):
    bench = Bench()
    spec = bench.traffic(mix)
    cell = next(w for w in bench.spec["workloads"] if w["traffic"] == mix)
    records = int(bench.config(cell["config"])["records"])
    keys = KeyStream(records, float(spec["zipf"]), seed)
    values = ValueMaker(100, "w")
    return [(keys.next_key(), values.next()) for _ in range(n)]


@pytest.mark.parametrize("mix", MIXES)
def test_op_sequence_repeats_for_a_seed(mix):
    big = 2 ** 31 + 12345
    assert _ops(mix, big) == _ops(mix, big)
    assert _ops(mix, 7) != _ops(mix, 8)


def test_zipf_draws_favour_low_ranks():
    keys = KeyStream(1_000_000, 0.99, 3)
    drawn = [keys.next_index() for _ in range(20000)]
    share = sum(1 for i in drawn if i == 0) / len(drawn)
    assert 0.04 < share < 0.09          # zipf(0.99) over 1M: about 6.5%


class _Cache:
    """A bucket cache stand-in that pads to a fixed bucket."""

    def __init__(self, pad):
        self.pad, self.hits, self.misses, self.seen = pad, 0, 0, []

    def __call__(self, vvs, dot_ids, dot_ns, valid):
        self.seen.append(tuple(max(a, b) for a, b in zip(vvs.shape,
                                                           self.pad)))
        self.hits += 1
        return np.ones(vvs.shape[:2], bool)


@pytest.mark.parametrize("kind", ["sync_mask", "read_sweep"])
def test_bytes_come_from_logical_shapes(kind, monkeypatch):
    import repro.kernels.dvv_ops as pkg
    counted = []
    for pad in ((8, 2, 8), (64, 16, 128)):
        monkeypatch.setattr(pkg, {"sync_mask": "dvv_sync_mask_bucketed",
                                  "read_sweep": "dvv_read_sweep_bucketed"}
                            [kind], _Cache(pad))
        probes = Probes(annotate=False)
        probes.install_kernels()
        try:
            fn = getattr(pkg, "dvv_sync_mask_bucketed" if kind == "sync_mask"
                         else "dvv_read_sweep_bucketed")
            fn(np.zeros((5, 3, 5), np.int32), np.zeros((5, 3), np.int32),
               np.zeros((5, 3), np.int32), np.ones((5, 3), bool))
            assert fn.inner.seen == [tuple(max(a, b) for a, b in
                                           zip((5, 3, 5), pad))]
        finally:
            probes.uninstall()
        counted.append(kernel_cost.total_bytes(kind,
                                               probes.kernel_shapes[kind]))
    mask = 4 * 5 * 3 * 5 + 8 * 5 * 3 + 2 * 5 * 3
    want = mask if kind == "sync_mask" else mask + 4 * 5 * 5
    assert counted == [want, want]
    assert kernel_cost.total_comparisons({(5, 3, 5): 2}) == 2 * 5 * 3 * 3 * 5


def test_roofline_share_is_bytes_over_peak_over_time():
    w = {"trace": {"kernel_s": {"read_sweep": 1e-6}},
         "peaks": {"hbm_bytes_per_s": 819e9},
         "kernel_shapes": {"read_sweep": {(100, 2, 5): 1}}}
    pct = kernel_cost.window_roofline(w, "read_sweep")
    want = 100.0 * kernel_cost.read_sweep_bytes(100, 2, 5) / 819e9 / 1e-6
    assert pct == pytest.approx(want)
    w["trace"]["kernel_s"]["read_sweep"] = 0.0
    assert kernel_cost.window_roofline(w, "read_sweep") is None


def test_peaks_table_names_the_v5e():
    peaks = json.loads((BENCH / "peaks.json").read_text())["devices"]
    v5e = peaks["TPU v5 lite"]
    assert v5e["hbm_bytes_per_s"] == 819e9 and v5e["hbm_bytes"] == 16e9
    with pytest.raises(KeyError):
        Bench().device_peaks("TPU v9 imaginary")
