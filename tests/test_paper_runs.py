"""The paper's example runs (Figures 1, 2, 3, 4, 7) as executable tests.

Three clients concurrently modify the same key on two replica nodes
(Ra, Rb).  Each figure exercises one causality mechanism; the assertions
encode the outcome the paper derives for it — including the *failures* of
the baselines (lost updates, false dominance), which are the paper's
motivation for DVV.
"""
import pytest

from repro.core import (
    DVV, VV, CausalHistory, LamportClock, WallClock,
    sync, update, downset,
)
from repro.core.version_vector import (
    merge_all, sync_vv, update_per_server, update_per_client_inferred,
)
from repro.core.lww import lamport_update


# ---------------------------------------------------------------------------
# Figure 1 — causal histories (the oracle).
# ---------------------------------------------------------------------------

def test_fig1_causal_histories():
    # C1: PUT v at Rb with context {} -> {b1}
    v = CausalHistory.of(("b", 1))
    # C2: PUT w at Rb with context {} -> {b2}; Rb keeps both (concurrent)
    w = CausalHistory.of(("b", 2))
    assert v.concurrent(w)
    # C3: PUT x at Ra -> {a1}; then reads it and PUTs y -> {a1, a2}
    x = CausalHistory.of(("a", 1))
    y = CausalHistory.of(("a", 1), ("a", 2))
    assert x.lt(y)           # y supersedes x at Ra
    assert y.concurrent(v) and y.concurrent(w)


# ---------------------------------------------------------------------------
# Figure 2 — perfectly synchronized real-time clocks: total order, and
# a concurrent update is silently lost under last-writer-wins.
# ---------------------------------------------------------------------------

def test_fig2_wallclock_lww_loses_concurrent_update():
    v = (WallClock(1.0, "C1"), "v")
    w = (WallClock(2.0, "C2"), "w")
    # Rb applies LWW: w overwrites v although they are causally concurrent.
    kept = w if v[0].lt(w[0]) else v
    assert kept[1] == "w"        # v is lost — the paper's complaint
    assert not v[0].concurrent(w[0])  # total order admits no concurrency


def test_fig2_skewed_clock_always_loses():
    # A client whose clock is persistently behind never gets its write kept.
    slow = WallClock(0.5, "slow")       # real time was later, clock says 0.5
    fast = WallClock(10.0, "fast")
    assert slow.lt(fast)


# ---------------------------------------------------------------------------
# Figure 3 — version vectors with per-server entries (Dynamo).
# Cross-server concurrency is detected, same-server concurrency is NOT.
# ---------------------------------------------------------------------------

def test_fig3_vv_per_server():
    # C1: PUT v at Rb, context {} -> {(b,1)}
    v = update_per_server(VV.zero(), frozenset(), "b")
    assert v == VV.from_dict({"b": 1})
    Sb = frozenset({v})
    # C2: PUT w at Rb, context {} -> {(b,2)}: FALSELY dominates v.
    w = update_per_server(VV.zero(), Sb, "b")
    assert w == VV.from_dict({"b": 2})
    assert v.lt(w)                      # false dominance (should be concurrent)
    Sb = sync_vv(Sb, frozenset({w}))
    assert Sb == frozenset({w})         # v was silently lost
    # C3 at Ra: PUT x {} -> {(a,1)}; read; PUT y -> {(a,2)}
    x = update_per_server(VV.zero(), frozenset(), "a")
    Sa = frozenset({x})
    y = update_per_server(x, Sa, "a")
    assert y == VV.from_dict({"a": 2})
    # Cross-server concurrency IS detected: {(a,2)} || {(b,2)}
    assert y.concurrent(w)


# ---------------------------------------------------------------------------
# Figure 4 — per-client entries with stateless clients (inferred counter):
# switching replicas repeats a counter and loses an update.
# ---------------------------------------------------------------------------

def test_fig4_vv_per_client_inferred_loses_update():
    # C1: PUT v at Rb, context {} -> {(C1,1)}
    v = update_per_client_inferred(VV.zero(), frozenset(), "C1")
    assert v == VV.from_dict({"C1": 1})
    # C3: PUT x at Ra -> {(C3,1)}
    x = update_per_client_inferred(VV.zero(), frozenset(), "C3")
    Sa = frozenset({x})
    # C1 (no affinity) reads x's context from Ra and PUTs y at Ra.
    # Ra has never seen C1, so it re-issues (C1,1):
    y = update_per_client_inferred(x, Sa, "C1")
    assert y == VV.from_dict({"C1": 1, "C3": 1})
    # v now appears dominated by y although they are causally concurrent:
    assert v.lt(y)                      # the Fig. 4 lost update


# ---------------------------------------------------------------------------
# Lamport clocks (§3.1) — total order, no concurrency.
# ---------------------------------------------------------------------------

def test_lamport_total_order():
    c1 = lamport_update(frozenset(), frozenset(), "b")
    c2 = lamport_update(frozenset(), frozenset({c1}), "b")
    assert c1.lt(c2) and not c1.concurrent(c2)


# ---------------------------------------------------------------------------
# Figure 7 — dotted version vectors: full causality with per-server ids.
# ---------------------------------------------------------------------------

def test_fig7_dvv_full_run():
    empty = frozenset()
    # C1: PUT v at Rb, context {} -> (b,0,1)
    Sb = frozenset()
    cv = update(empty, Sb, "b")
    assert cv == DVV.from_dict({"b": (0, 1)})
    Sb = sync(Sb, frozenset({cv}))
    # C2: PUT w at Rb, context {} -> (b,0,2); concurrent sibling KEPT
    cw = update(empty, Sb, "b")
    assert cw == DVV.from_dict({"b": (0, 2)})
    assert cv.concurrent(cw)
    Sb = sync(Sb, frozenset({cw}))
    assert Sb == frozenset({cv, cw})    # no lost update (unlike Fig. 3)
    # C3: PUT x at Ra -> (a,0,1); read; PUT y -> (a,1,2) replacing x
    Sa = frozenset()
    cx = update(empty, Sa, "a")
    assert cx == DVV.from_dict({"a": (0, 1)})
    Sa = sync(Sa, frozenset({cx}))
    cy = update(frozenset({cx}), Sa, "a")
    assert cy == DVV.from_dict({"a": (1, 2)})
    Sa = sync(Sa, frozenset({cy}))
    assert Sa == frozenset({cy})
    # anti-entropy Rb -> Ra
    Sa = sync(Sa, Sb)
    assert Sa == frozenset({cy, cv, cw})
    # C2 reads {v,w} from Rb, writes z at Ra: z = {(a,0,3),(b,2)}
    cz = update(Sb, Sa, "a")
    assert cz == DVV.from_dict({"a": (0, 3), "b": (2,)})
    Sa = sync(Sa, frozenset({cz}))
    assert Sa == frozenset({cy, cz})    # z subsumed v,w; concurrent with y
    assert cz.concurrent(cy)
    assert downset(Sa) and downset(Sb)


def test_paper_52_example_same_server_concurrency():
    """§5.2: {(r,4)} || {(r,3,5)} — concurrency within one replica's id."""
    a = DVV.from_dict({"r": (4,)})
    b = DVV.from_dict({"r": (3, 5)})
    assert a.concurrent(b)
    # and their histories confirm it
    assert a.to_history().concurrent(b.to_history())


def test_dvv_semantics_examples():
    """§5.1: {(a,2),(b,1),(c,3,7)} represents {a1,a2,b1,c1,c2,c3,c7}."""
    c = DVV.from_dict({"a": (2,), "b": (1,), "c": (3, 7)})
    expected = CausalHistory.of(
        ("a", 1), ("a", 2), ("b", 1), ("c", 1), ("c", 2), ("c", 3), ("c", 7))
    assert c.to_history() == expected


# ---------------------------------------------------------------------------
# The canonical run through the replicated store, under each mechanism:
# C1 and C2 write v and w blind at Rb; C3 writes x at Ra, reads it and
# writes y; anti-entropy Rb -> Ra; C2 reads {v, w} at Rb and writes z at Ra.
# ---------------------------------------------------------------------------

# mechanism -> (z and y both survive at Ra?, v survives w's write at Rb?)
CANONICAL_OUTCOME = {
    "oracle": (True, True),
    "dvv": (True, True),
    "vv_client": (True, True),        # stateful clients: accurate (§3.3)
    "vv_server": (False, False),      # Fig. 3: w overwrites v; z overwrites y
    "wallclock_lww": (False, False),  # Fig. 2: total order, one survivor
    "lamport": (False, False),        # §3.1: total order, one survivor
}


@pytest.mark.parametrize("mech", list(CANONICAL_OUTCOME))
def test_canonical_run_through_the_store(mech):
    from repro.core import ALL_MECHANISMS
    from repro.store import KVCluster, SimNetwork

    c = KVCluster(("a", "b"), ALL_MECHANISMS[mech], network=SimNetwork(seed=0))
    c.put("k", "v", via="b", coordinator="b", client_id="C1",
          client_counter=1, wall_time=1.0)
    c.put("k", "w", via="b", coordinator="b", client_id="C2",
          client_counter=1, wall_time=2.0)
    c.put("k", "x", via="a", coordinator="a", client_id="C3",
          client_counter=1, wall_time=3.0)
    ctx = c.get("k", via="a").context
    c.put("k", "y", context=ctx, via="a", coordinator="a", client_id="C3",
          client_counter=2, wall_time=4.0)
    c.antientropy("b", "a")
    ctx_b = c.get("k", via="b").context
    c.put("k", "z", context=ctx_b, via="a", coordinator="a", client_id="C2",
          client_counter=2, wall_time=5.0)
    zy_both = {"z", "y"} <= set(c.get("k", via="a").values)
    v_survived = "v" in c.all_values("k")
    assert (zy_both, v_survived) == CANONICAL_OUTCOME[mech]
