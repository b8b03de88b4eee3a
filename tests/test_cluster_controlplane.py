"""Control-plane tests: the store's membership, failure-detector and lease
services, and manifest sibling resolution — coordination state that needs
DVV causality tracking."""
from repro.ckpt import Manifest, resolve_manifest_siblings
from repro.core import ALL_MECHANISMS
from repro.store import (
    FailureDetector, KVCluster, MembershipService, MemberView, NodeStatus,
    SimNetwork, WorkStealer,
)

STORE_NODES = ("s1", "s2", "s3")


def fresh_store(seed=0, mech="dvv"):
    return KVCluster(STORE_NODES, ALL_MECHANISMS[mech],
                     network=SimNetwork(seed=seed))


# ---------------------------------------------------------------------------
# Membership
# ---------------------------------------------------------------------------

def test_membership_join_leave():
    store = fresh_store()
    svc = MembershipService(store, "s1")
    svc.join("w0")
    svc.join("w1")
    store.deliver_replication()
    view = svc.view()
    assert set(view.alive()) == {"w0", "w1"}
    svc.mark_dead("w1")
    assert set(svc.view().alive()) == {"w0"}


def test_membership_concurrent_joins_both_survive():
    """Two nodes join through different coordinators during a partition —
    with DVV both joins survive the heal (LWW would drop one)."""
    store = fresh_store(seed=1)
    net = store.network
    a = MembershipService(store, "s1")
    b = MembershipService(store, "s2")
    net.partition({"s1"}, {"s2", "s3"})
    a.join("w-left")
    b.join("w-right")
    net.heal()
    store.antientropy_round()
    merged = a.reconcile()
    assert set(merged.alive()) == {"w-left", "w-right"}
    # and the reconciliation converges: the merged view replaces siblings
    store.antientropy_round()
    assert set(b.view().alive()) == {"w-left", "w-right"}


def test_membership_concurrent_joins_lost_under_lww():
    """The same schedule under wall-clock LWW silently loses one join —
    the paper's §3.1 failure, at the framework level."""
    store = fresh_store(seed=1, mech="wallclock_lww")
    net = store.network
    a = MembershipService(store, "s1")
    b = MembershipService(store, "s2")
    net.partition({"s1"}, {"s2", "s3"})
    a.join("w-left")
    b.join("w-right")
    net.heal()
    store.antientropy_round()
    merged = a.reconcile()
    assert set(merged.alive()) != {"w-left", "w-right"}  # one join vanished


def test_member_view_merge_epoch_priority():
    v1 = MemberView.from_dict({"n": (int(NodeStatus.DEAD), 3)})
    v2 = MemberView.from_dict({"n": (int(NodeStatus.ALIVE), 4)})  # rejoined
    merged = MemberView.merge((v1, v2))
    assert merged.to_dict()["n"] == (int(NodeStatus.ALIVE), 4)


# ---------------------------------------------------------------------------
# Failure detector
# ---------------------------------------------------------------------------

def test_failure_detector_suspect_and_dead():
    fd = FailureDetector(heartbeat_interval=1.0)
    for t in range(5):
        fd.record("w0", float(t))
        fd.record("w1", float(t))
    # w1 goes silent
    fd.record("w0", 9.0)
    assert "w1" in fd.suspects(8.0)
    assert "w1" in fd.dead(14.0)
    assert "w0" in fd.alive(9.5)


# ---------------------------------------------------------------------------
# Work stealing / straggler mitigation
# ---------------------------------------------------------------------------

def test_concurrent_claims_same_coordinator_one_winner():
    store = fresh_store(seed=2)
    w1 = WorkStealer(store, "worker1")
    w2 = WorkStealer(store, "worker2")
    # both claim with empty context through the same coordinator — Fig. 3!
    got1 = w1.try_claim("shard-7", now=0.0, via="s1")
    got2 = w2.try_claim("shard-7", now=0.0, via="s1")
    assert got1 != got2 or not (got1 and got2)  # never both owners
    owner = w1.owner("shard-7", via="s1")
    assert owner in ("worker1", "worker2")


def test_steal_expired_lease():
    store = fresh_store(seed=3)
    w1 = WorkStealer(store, "worker1", lease_duration=5.0)
    w2 = WorkStealer(store, "worker2", lease_duration=5.0)
    assert w1.try_claim("shard-0", now=0.0, via="s1")
    # worker1 stalls; at t=6 its lease expired and worker2 steals
    assert not w2.try_claim("shard-0", now=3.0, via="s1")
    assert w2.steal_expired("shard-0", now=6.0, via="s1")
    assert w2.owner("shard-0", via="s1") == "worker2"
    # the straggler coming back cannot renew
    assert not w1.renew("shard-0", now=7.0, via="s1")


# ---------------------------------------------------------------------------
# Manifests
# ---------------------------------------------------------------------------

def test_resolve_manifest_siblings_deterministic():
    a = Manifest("r", 5, (), 0, 0, 0, (1,), "s1")
    b = Manifest("r", 6, (), 0, 0, 0, (1,), "s2")
    assert resolve_manifest_siblings((a, b)).step == 6
    assert resolve_manifest_siblings((b, a)).step == 6
