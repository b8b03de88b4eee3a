"""Tiny CPU runs of the serving cell: sound runs come out correct; an
altered answer, a superseded sibling kept, a write batch half left out,
writes that change nothing, an emptied context, refused reads, and the
control (version vectors with server ids) come out not correct."""
from __future__ import annotations

import pytest

from _chipbench_tiny import tiny_run


def _checks(result):
    return {k: v["value"] for k, v in result["checks"].items()}


@pytest.mark.parametrize("workload", ["ycsb-a.riak5"])
def test_sound_run_is_correct(workload):
    r = tiny_run(workload)
    c = _checks(r)
    assert r["correct"], c
    assert c["gets_checked"] > 0 and r["attempted"] > 0 and r["failed"] == 0
    assert set(r["metrics"]) == {"ops_per_s", "op_p95_ms", "setup_s"}
    assert all(m["value"] > 0 for m in r["metrics"].values())


def _alter_one_answer(cluster, driver):
    real = cluster.get_many
    done = []

    def get_many(keys, **kw):
        out = real(keys, **kw)
        if not done and out:
            from dataclasses import replace
            k = next(iter(out))
            out[k] = replace(out[k], values=("altered",) + out[k].values[1:])
            done.append(k)
        return out

    cluster.get_many = get_many


def _half_batch_left_out(cluster, driver):
    real = cluster.put_many

    def put_many(items, **kw):
        keys = list(items)
        if len(keys) < 2:
            return real(items, **kw)
        kept = keys[: len(keys) // 2]
        acks = real({k: items[k] for k in kept}, **kw)
        return {k: acks.get(k, acks[kept[0]]) for k in keys}

    cluster.put_many = put_many


def _state_unchanged(cluster, driver):
    from repro.core.dvv import DVV
    from repro.store.cluster import PutAck

    def put_many(items, **kw):
        return {k: PutAck(clock=DVV((("n0", 0, 1),)), coordinator="n0",
                          replicated_to=("n0",)) for k in items}

    cluster.put_many = put_many


def _stale_sibling_kept(cluster, driver):
    """A superseded version comes back beside what superseded it, as if the
    survival mask let a dominated row live."""
    real = cluster.get_many
    first_seen = {}

    def get_many(keys, **kw):
        from dataclasses import replace
        out = real(keys, **kw)
        for k, res in out.items():
            old = first_seen.setdefault(k, res.values[0])
            if old not in res.values:
                out[k] = replace(res, values=res.values + (old,))
        return out

    cluster.get_many = get_many


def _context_emptied(cluster, driver):
    from dataclasses import replace
    from repro.store.context import CausalContext
    real = cluster.get_many

    def get_many(keys, **kw):
        out = real(keys, **kw)
        return {k: replace(r, context=CausalContext()) for k, r in out.items()}

    cluster.get_many = get_many


def _reads_refused(cluster, driver):
    from repro.store.network import Unavailable
    real = cluster.get_many
    calls = []

    def get_many(keys, **kw):
        calls.append(1)
        if len(calls) == 3:
            raise Unavailable("read refused")
        return real(keys, **kw)

    cluster.get_many = get_many


@pytest.mark.parametrize("fault,check", [
    (_alter_one_answer, "unknown_value"),
    (_stale_sibling_kept, "stale_sibling"),
    (_half_batch_left_out, "lost_write"),
    (_state_unchanged, "lost_write"),
    (_context_emptied, "context_gap"),
    (_reads_refused, "failed_ops"),
])
def test_fault_is_not_correct(fault, check):
    r = tiny_run("ycsb-a.riak5", tamper=fault)
    assert not r["correct"]
    assert _checks(r)[check] > 0


def test_control_is_not_correct():
    """Server-id version vectors drop concurrent writes: the control."""
    r = tiny_run("ycsb-a.riak5", mechanism="vv_server")
    assert not r["correct"]
    assert _checks(r)["lost_write"] > 0


def test_clients_stay_under_the_flush_size():
    """A closed loop as wide as the flush would flush by size at one
    simulated instant, with no replication delivered: it is refused."""
    with pytest.raises(ValueError, match="max_batch"):
        tiny_run("ycsb-a.riak5", traffic_overrides={"clients": 256})
