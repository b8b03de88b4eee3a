"""``chip_smoke.py`` on the CPU: its workload at a tiny size, and its guard.

The workload function is the one the script runs on the chip; here the
kernel plane runs the DVV kernels in interpret mode and must match the
numpy reference plane exactly.  The TPU check lives only in ``main``, which
must refuse to report a result on this backend.
"""
import importlib.util
from pathlib import Path

import jax
import pytest

ROOT = Path(__file__).resolve().parents[1]


@pytest.fixture(scope="module")
def chip_smoke():
    spec = importlib.util.spec_from_file_location("chip_smoke",
                                                  ROOT / "chip_smoke.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_chip_smoke_planes_agree_at_tiny_size(chip_smoke):
    from repro.kernels.dvv_ops import dvv_read_sweep_bucketed, \
        dvv_sync_mask_bucketed

    def kernel_calls():
        return sum(c.hits + c.misses for c in (dvv_read_sweep_bucketed,
                                               dvv_sync_mask_bucketed))

    records = chip_smoke.make_records(200, seed=0)
    kw = dict(steps=50, burst=16, shards=4, seed=0)
    calls = kernel_calls()
    kernel = chip_smoke.run_ycsb_a(records, use_kernel=True, **kw)
    assert kernel_calls() > calls
    reference = chip_smoke.run_ycsb_a(records, use_kernel=False, **kw)
    assert kernel["summary"]["ops"] == 100
    assert chip_smoke.check_run(kernel) == []
    assert chip_smoke.check_run(reference) == []
    assert chip_smoke.diff_runs(kernel, reference) == []
    # the comparison sees a single differing GET
    sid, key, (values, siblings, ctx) = reference["reads"][7]
    reference["reads"][7] = (sid, key, (values, siblings, ctx + b"\0"))
    assert chip_smoke.diff_runs(kernel, reference)


def test_chip_smoke_refuses_without_tpu(chip_smoke, capsys):
    assert jax.default_backend() != "tpu"
    assert chip_smoke.main([]) != 0
    assert '"ok"' not in capsys.readouterr().out
