"""Tiny CPU runs of the repair cell: a sound run comes out correct; the
anti-entropy exchange left out, an altered answer, and the control (one
peer per node and round) come out not correct."""
from __future__ import annotations

from _chipbench_tiny import tiny_run


def _checks(result):
    return {k: v["value"] for k, v in result["checks"].items()}


def test_sound_run_is_correct():
    r = tiny_run("ycsb-a.repair")
    c = _checks(r)
    assert r["correct"], c
    assert r["attempted"] > 0 and c["gets_checked"] > 0
    assert set(r["metrics"]) == {"repair_keys_per_s", "setup_s"}


def test_exchange_left_out_is_not_correct():
    def no_exchange(cluster, driver):
        cluster.delta_antientropy_round = lambda **kw: []

    r = tiny_run("ycsb-a.repair", tamper=no_exchange)
    assert not r["correct"]
    assert _checks(r)["replica_split"] > 0


def test_altered_answer_is_not_correct():
    def alter(cluster, driver):
        real = cluster.get_many

        def get_many(keys, **kw):
            out = real(keys, **kw)
            from dataclasses import replace
            k = next(iter(out))
            out[k] = replace(out[k], values=("altered",))
            return out

        cluster.get_many = get_many

    r = tiny_run("ycsb-a.repair", tamper=alter)
    assert not r["correct"]
    assert _checks(r)["unknown_value"] > 0


def test_control_is_not_correct():
    r = tiny_run("ycsb-a.repair", traffic_overrides={"fanout": 1})
    assert not r["correct"]
    assert _checks(r)["replica_split"] > 0
