"""Fault-injection matrix + self-driving membership (DESIGN.md §13).

Three layers, one suite:

* **Detector regressions** — the three latent ``FailureDetector`` bugs the
  loop exposed: join-then-silent nodes invisible forever, partition gaps
  inflating the expected-interval mean, and departed-node state leaking
  across a remove/re-add cycle.
* **Fault matrix conformance** — the churn interpreter (imported from
  ``test_churn``) extended with asymmetric link cuts, slow-not-dead nodes,
  seeded duplication/reordering and flapping links; every mode must end
  with replica agreement and packed==object (trajectory included when the
  membership controller drives evictions).  Duplicated and reordered
  deliveries must never double-apply — DVV sync is a join, so re-applying
  a payload is a no-op.
* **The closed loop end-to-end** — zero hand-called ``remove_node``/
  ``add_node``: a failed node is auto-evicted (fabric queue purged), a
  falsely-suspected *reachable* node is evicted WITH handoff (its
  sole-copy quorum-1 write survives) and immediately re-admitted, and a
  recovered node re-enters through the warm digest-diffed bootstrap.

The hypothesis phase fuzzes fault schedules (``slow`` marker — the
``make test-faults`` lane / nightly CI are its home).
"""
import random

import pytest

from repro.core import DVV_MECHANISM
from repro.store import (FailureDetector, GossipDriver, KVCluster,
                         MembershipController, SimNetwork, Unavailable,
                         cluster_converged)

from test_churn import KEYS, _assert_backends_agree, _assert_replicas_agree, \
    _conformance, _run_schedule

pytestmark = pytest.mark.faults


# ---------------------------------------------------------------------------
# FailureDetector regressions (the satellite bugfixes).
# ---------------------------------------------------------------------------

def test_registered_member_with_zero_beats_is_visible():
    """A node that joins and immediately goes silent must show up in
    ``suspects()``/``dead()`` — registration starts the clock; before the
    fix only nodes with a recorded beat were ever iterated."""
    fd = FailureDetector(heartbeat_interval=1.0)
    fd.register("ghost", now=0.0)
    assert fd.suspicion("ghost", 0.5) < fd.suspect_threshold
    assert "ghost" in fd.suspects(4.0)
    assert "ghost" in fd.dead(9.0)
    # registration is idempotent: it must not touch an existing beat
    fd.record("live", 0.0)
    fd.record("live", 1.0)
    fd.register("live", 100.0)
    assert fd.last_beat["live"] == 1.0


def test_expected_interval_resists_partition_inflation():
    """One long partition gap must not suppress suspicion after the heal.
    The old mean-based estimate let a single 80s outage gap drag the
    expected interval to ~3.2s, so 10 silent seconds scored under the
    dead threshold; the clamped median stays at the true 1s cadence."""
    fd = FailureDetector(heartbeat_interval=1.0)
    t = 0.0
    for _ in range(30):                       # steady 1s beats
        fd.record("n", t)
        t += 1.0
    t += 79.0                                 # 80s partition gap …
    fd.record("n", t)                         # … heals with one beat
    for _ in range(5):                        # cadence resumes
        t += 1.0
        fd.record("n", t)
    assert fd._expected_interval("n") <= fd.suspect_threshold
    # 10 silent seconds is 10 expected intervals: dead, promptly
    assert "n" in fd.dead(t + 10.0)
    # the control: with the historical mean the same silence scores ~3.1
    mean = sum(fd.history["n"]) / len(fd.history["n"])
    assert 10.0 / mean < fd.dead_threshold    # the bug this guards against


def test_forget_clears_departed_node_state():
    """``forget`` must drop both maps, and a re-added node starts with a
    fresh history instead of inheriting its previous life's gaps."""
    fd = FailureDetector(heartbeat_interval=1.0)
    for t in range(5):
        fd.record("n", float(t))
    assert "n" in fd.last_beat and "n" in fd.history
    fd.forget("n")
    assert "n" not in fd.last_beat and "n" not in fd.history
    assert fd.suspicion("n", 100.0) == float("inf")
    assert "n" not in fd.dead(100.0)          # unknown, not dead
    fd.register("n", 200.0)
    assert fd.history.get("n") is None        # fresh life, no stale gaps
    assert "n" in fd.alive(200.5)


# ---------------------------------------------------------------------------
# The closed loop, end to end — zero hand-called membership.
# ---------------------------------------------------------------------------

def _loop_cluster(packed, seed=3, period=5.0, **mem_kw):
    net = SimNetwork(seed=seed)
    c = KVCluster(("a", "b", "c", "d"), DVV_MECHANISM, packed=packed,
                  network=net, seed=seed)
    driver = GossipDriver(c, period=period, seed=seed)
    mem = MembershipController(c, period=period, seed=seed, **mem_kw)
    return net, c, driver, mem


@pytest.mark.parametrize("packed", [True, False])
def test_auto_evicts_failed_node_and_purges_queue(packed):
    """A crashed node leaves the replica set by itself: suspicion crosses
    the dead threshold, the controller evicts (purging queued messages
    toward the corpse — the fabric-leak bugfix), and the crash state
    survives the eviction (no bogus instant re-admission)."""
    net, c, driver, mem = _loop_cluster(packed)
    for i in range(6):
        c.put(f"k{i}", f"v{i}", via="a", coordinator="a")
    driver.run_for(30.0)
    # in-flight replication toward c when it crashes: held in the queue
    # (unreachable dst) — before the fix it sat there forever
    c.put("k0", "in-flight", via="a", coordinator="a")
    net.fail_node("c")
    assert net.queued_for("c") > 0
    driver.run_for(300.0)
    assert "c" not in c.nodes
    assert mem.evictions == 1 and mem.readmissions == 0
    assert net.queued_for("c") == 0           # purge on eviction
    assert "c" in net.down                    # the crash outlives eviction
    assert cluster_converged(c)
    # detection is bounded: dead_threshold intervals + one probe period
    bound = (mem.detector.dead_threshold + 2) * mem.period
    assert net.now <= 30.0 + 300.0 and bound < 300.0


def _crash_under_writes(mode, seed=0):
    """5 nodes, ``replication=3, write_quorum=3``; a write every 2 s for
    900 s and ``n2`` crashes at 200 s.  ``mode``: ``none`` (nobody removes
    it), ``manual`` (an operator oracle removes it the instant it dies) or
    ``auto`` (the ``MembershipController`` evicts it).  Returns (writes
    failed after the crash, eviction latency or None, the controller)."""
    net = SimNetwork(seed=seed)
    c = KVCluster(tuple(f"n{i}" for i in range(5)), DVV_MECHANISM,
                  network=net, seed=seed, replication=3, write_quorum=3)
    driver = GossipDriver(c, period=10.0, seed=seed)
    mem = MembershipController(c, period=10.0, seed=seed, readmit=False) \
        if mode == "auto" else None
    rng = random.Random(seed * 13 + 7)
    failed = 0
    evicted_at = None
    while net.now < 900.0:
        driver.run_for(2.0)
        if "n2" not in net.down and net.now >= 200.0 and \
                "n2" in c.nodes:
            net.fail_node("n2")
            if mode == "manual":
                c.remove_node("n2", handoff=True)
        if evicted_at is None and "n2" not in c.nodes:
            evicted_at = net.now
        live = [n for n in c.nodes if n not in net.down]
        try:
            c.put(f"k{rng.randrange(24)}", f"v@{net.now:.0f}",
                  via=live[rng.randrange(len(live))])
        except Unavailable:
            assert net.now >= 200.0          # no failure before the crash
            failed += 1
    latency = None if evicted_at is None else evicted_at - 200.0
    return failed, latency, mem


@pytest.mark.parametrize("mode", ["none", "manual", "auto"])
def test_eviction_bounds_unavailability_after_a_crash(mode):
    """Without eviction every write whose replica set holds the corpse
    fails its quorum for the rest of the run; the operator oracle fails
    none; the controller evicts within ``dead_threshold`` expected
    heartbeat intervals of the crash and fails fewer writes than doing
    nothing, all on simulated time."""
    failed, latency, mem = _crash_under_writes(mode)
    none_failed = _crash_under_writes("none")[0]
    if mode == "none":
        assert latency is None and failed > 0
    elif mode == "manual":
        assert latency == 0.0 and failed == 0
    else:
        assert latency is not None
        assert latency <= mem.detector.dead_threshold * mem.period
        assert failed < none_failed


@pytest.mark.parametrize("packed", [True, False])
def test_recovered_node_auto_readmitted_via_warm_bootstrap(packed):
    """Recovery re-admits through the warm digest-diffed bootstrap: the
    returnee holds full causal state (digest-equal to its peers), not an
    empty store."""
    net, c, driver, mem = _loop_cluster(packed)
    for i in range(8):
        c.put(f"k{i}", f"v{i}", via="a", coordinator="a")
    driver.run_for(30.0)
    net.fail_node("c")
    driver.run_for(300.0)
    assert "c" not in c.nodes
    net.recover_node("c")
    driver.run_for(300.0)
    c.deliver_replication()
    assert "c" in c.nodes
    assert mem.evictions == 1 and mem.readmissions == 1
    assert cluster_converged(c)
    for i in range(8):
        assert {v.value for v in c.nodes["c"].versions(f"k{i}")} == \
            {v.value for v in c.nodes["a"].versions(f"k{i}")}


@pytest.mark.parametrize("packed", [True, False])
def test_false_eviction_handoff_saves_sole_copy_write(packed):
    """The acceptance scenario: a node partitioned long enough to be
    nearly dead heals just before the threshold; another node's probe
    sweep then evicts it while it is *reachable* — so the final handoff
    push saves the quorum-1 write only it held — and the same sweep
    re-admits it warm.  (With jitter=0, probes fire at exact period
    multiples in arm order a, b, c — the window is deterministic.)"""
    net = SimNetwork(seed=11)
    c = KVCluster(("a", "b", "c"), DVV_MECHANISM, packed=packed,
                  network=net, seed=11)
    driver = GossipDriver(c, period=5.0, seed=11)
    mem = MembershipController(c, period=5.0, jitter=0.0, seed=11)
    c.put("warm", "w", via="a", coordinator="a")
    driver.run_for(30.0)                       # last c beat at t=30
    net.partition({"c"}, {"a", "b"})
    net.run_until(66.0)
    c.put("sole", "precious", via="c", coordinator="c", quorum=1)
    net.run_until(68.0)
    net.heal()                                 # susp(c)=7.6 < 8: no evict
    assert mem.evictions == 0
    net.run_until(90.0)                        # a's t=70 sweep: susp=8.0
    assert mem.evictions == 1 and mem.readmissions == 1
    driver.run_for(200.0)
    c.deliver_replication()
    assert list(c.nodes) == ["a", "b", "c"]
    for n in c.nodes:                          # handoff saved the sole copy
        assert {v.value for v in c.nodes[n].versions("sole")} == \
            {"precious"}, n
    assert cluster_converged(c)


def test_suspect_deprioritized_and_probed():
    """A slow-silenced node becomes suspect (not dead): quorum assembly
    sorts it last, and the driver aims dedicated probe rounds at it
    instead of regular rotation traffic."""
    net, c, driver, mem = _loop_cluster(True, period=5.0,
                                        dead_threshold=1e9)
    for i in range(4):
        c.put(f"k{i}", f"v{i}", via="a", coordinator="a")
    driver.run_for(30.0)
    # cut every OUTBOUND link of d: it hears everyone, nobody hears it —
    # the asymmetric mode a symmetric partition cannot express
    for peer in ("a", "b", "c"):
        net.cut_link("d", peer)
    driver.run_for(120.0)
    assert mem.is_suspect("d")
    assert "d" in mem.suspect_nodes()
    assert mem.evictions == 0                  # dead_threshold unreachable
    # quorum assembly puts the suspect last
    reachable = c._reachable_replicas("a", "k0")
    assert reachable[-1] == "d" and reachable[0] == "a"
    # and the write path avoids coordinating there
    assert c._pick_coordinator("b", "k0") != "d"
    assert driver.suspect_probes > 0           # targeted catch-up rounds
    for peer in ("a", "b", "c"):
        net.heal_link("d", peer)
    driver.run_for(60.0)
    assert not mem.is_suspect("d")             # beats resume, trust returns


def test_controller_rejects_degenerate_parameters():
    net = SimNetwork(seed=0)
    c = KVCluster(("a", "b"), DVV_MECHANISM, network=net, seed=0)
    with pytest.raises(ValueError):
        MembershipController(c, period=0.0)
    with pytest.raises(ValueError):
        MembershipController(c, jitter=1.0)
    with pytest.raises(ValueError):
        MembershipController(c, suspect_threshold=8.0, dead_threshold=3.0)
    geo = KVCluster(("e0", "w0"), DVV_MECHANISM, seed=0,
                    datacenters={"e": ("e0",), "w": ("w0",)})
    with pytest.raises(ValueError):
        MembershipController(geo)


def test_min_members_floor_blocks_eviction():
    """The controller never shrinks the cluster below ``min_members`` —
    a 2-node cluster keeps its dead peer rather than becoming a
    singleton (split-brain guard)."""
    net = SimNetwork(seed=5)
    c = KVCluster(("a", "b"), DVV_MECHANISM, network=net, seed=5)
    GossipDriver(c, period=5.0, seed=5)
    mem = MembershipController(c, period=5.0, seed=5, min_members=2)
    net.fail_node("b")
    net.advance(500.0)
    assert list(c.nodes) == ["a", "b"] and mem.evictions == 0


def test_controller_same_seed_identical_decisions():
    """Seed determinism for the control loop itself: same seed ⇒ same
    probe count, same eviction/re-admission trajectory, same timer
    totals."""
    def run():
        net, c, driver, mem = _loop_cluster(True, seed=7)
        for i in range(4):
            c.put(f"k{i}", f"v{i}", via="a", coordinator="a")
        driver.run_for(20.0)
        net.fail_node("b")
        driver.run_for(250.0)
        net.recover_node("b")
        driver.run_for(250.0)
        return (mem.probes, mem.evictions, mem.readmissions,
                list(c.nodes), net.timers_fired, net.bytes_sent)
    assert run() == run()


# ---------------------------------------------------------------------------
# Idempotence under duplication/reordering (apply is a join).
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("packed", [True, False])
def test_duplicate_apply_is_noop(packed):
    """Applying the same anti-entropy payload twice changes nothing the
    second time — the property that makes ``dup_rate`` safe."""
    net = SimNetwork(seed=0)
    c = KVCluster(("a", "b"), DVV_MECHANISM, packed=packed, network=net,
                  seed=0)
    for i in range(5):
        c.put(f"k{i}", f"v{i}", via="a", coordinator="a")
    payload = c.nodes["a"].antientropy_payload([f"k{i}" for i in range(5)])
    first = c.nodes["b"].receive_antientropy(payload)
    second = c.nodes["b"].receive_antientropy(payload)
    assert first > 0 and second == 0
    for i in range(5):
        assert c.nodes["b"].versions(f"k{i}") == \
            c.nodes["a"].versions(f"k{i}")


@pytest.mark.parametrize("packed", [True, False])
def test_duplicated_deliveries_never_double_apply(packed):
    """A run with every message duplicated ends in exactly the state of
    the dup-free twin: duplicates cost wire bytes, not state."""
    def run(dup):
        net = SimNetwork(seed=9)
        c = KVCluster(("a", "b", "c"), DVV_MECHANISM, packed=packed,
                      network=net, seed=9)
        if dup:
            net.set_duplication(1.0)
        for i in range(6):
            c.put(f"k{i}", f"v{i}", via="a", coordinator="a")
            c.put(f"k{i}", f"w{i}", via="b", coordinator="b")
        c.deliver_replication()
        return c, net

    c1, n1 = run(dup=False)
    c2, n2 = run(dup=True)
    assert n2.duplicated > 0
    assert n2.delivered == n1.delivered + n2.duplicated
    assert n2.bytes_sent > n1.bytes_sent      # duplicates are priced
    for i in range(6):
        for n in c1.nodes:
            assert c1.nodes[n].versions(f"k{i}") == \
                c2.nodes[n].versions(f"k{i}"), (n, i)


@pytest.mark.parametrize("packed", [True, False])
def test_reordered_deliveries_converge_to_same_state(packed):
    """Scrambled delivery order (fault-stream extra latency) cannot change
    the converged state — version-set join is order-independent."""
    def run(reorder):
        net = SimNetwork(seed=13)
        c = KVCluster(("a", "b", "c"), DVV_MECHANISM, packed=packed,
                      network=net, seed=13)
        if reorder:
            net.set_reorder(0.8, spread=50.0)
        for i in range(8):
            c.put(f"k{i % 4}", f"v{i}", via="a", coordinator="a")
            c.put(f"k{i % 4}", f"w{i}", via="c", coordinator="c")
        c.deliver_replication()
        return c, net

    c1, _ = run(reorder=False)
    c2, n2 = run(reorder=True)
    assert n2.reordered > 0
    for i in range(4):
        for n in c1.nodes:
            assert c1.nodes[n].versions(f"k{i}") == \
                c2.nodes[n].versions(f"k{i}"), (n, i)


def test_fault_knobs_off_keep_trace_byte_identical():
    """Installing the fault machinery must not shift the no-fault RNG
    stream: a run on the faulted fabric with all knobs at their defaults
    equals the run before this PR existed (regression canary: compare two
    identical configs through the full churn interpreter)."""
    from test_churn import _random_ops
    ops = _random_ops(21, 30)
    c1, d1 = _run_schedule(21, ops, packed=True)
    c2, d2 = _run_schedule(21, ops, packed=True)
    assert c1.network.bytes_sent == c2.network.bytes_sent
    assert c1.network.duplicated == 0 and c1.network.reordered == 0


# ---------------------------------------------------------------------------
# Matrix lanes: pinned schedules per fault mode, conformance asserted.
# ---------------------------------------------------------------------------

def _fault_ops(seed, n_ops=34, modes=("cut", "slow", "dup", "reorder",
                                      "flap")):
    """A pinned pseudo-random schedule mixing traffic with the requested
    fault modes (plus fail/recover/partition/heal) — and NO hand-called
    membership ops, so the same schedules drive the self-driving lanes."""
    rng = random.Random(seed)
    ops = []
    for _ in range(n_ops):
        p = rng.random()
        if p < 0.30:
            ops.append(("put", rng.randrange(8), rng.randrange(8),
                        rng.random() < 0.5))
        elif p < 0.42:
            ops.append(("get", rng.randrange(8), rng.randrange(8)))
        elif p < 0.50:
            ops.append(("advance", rng.randrange(1, 25)))
        elif p < 0.56:
            ops.append(("fail", rng.randrange(8)))
        elif p < 0.62:
            ops.append(("recover", rng.randrange(8)))
        elif p < 0.66:
            ops.append(("partition", rng.randrange(1, 6)))
        elif p < 0.70:
            ops.append(("heal",))
        elif p < 0.92:
            mode = modes[rng.randrange(len(modes))]
            if mode == "cut":
                ops.append(("cut", rng.randrange(8), rng.randrange(8)))
            elif mode == "slow":
                ops.append(("slow", rng.randrange(8),
                            rng.choice([1.0, 2.0, 8.0])))
            elif mode == "dup":
                ops.append(("dup", rng.choice([0.0, 0.3, 0.9])))
            elif mode == "reorder":
                ops.append(("reorder", rng.choice([0.0, 0.4, 0.8])))
            elif mode == "flap":
                ops.append(("flap", rng.randrange(8), rng.randrange(8)))
        elif p < 0.96:
            ops.append(("heal_link", rng.randrange(8), rng.randrange(8)))
        else:
            ops.append(("advance", rng.randrange(20, 60)))
    return ops


@pytest.mark.parametrize("mode", ["cut", "slow", "dup", "reorder", "flap"])
def test_fault_mode_conformance_pinned(mode):
    """Each fault mode alone: packed==object and replica agreement after
    quiescence."""
    _conformance(31, _fault_ops(31, modes=(mode,)), ("mode", mode))


@pytest.mark.parametrize("seed", [2, 37])
def test_fault_matrix_combined_conformance_pinned(seed):
    """All modes interleaved in one schedule."""
    _conformance(seed, _fault_ops(seed), ("matrix", seed))


@pytest.mark.parametrize("seed", [5, 43])
def test_fault_matrix_with_self_driving_membership_pinned(seed):
    """The full loop under the full matrix: the controller evicts and
    re-admits on its own (zero hand-called membership in the schedule),
    and the membership trajectory is part of the conformance check."""
    cp, co = _conformance(seed, _fault_ops(seed, n_ops=28),
                          ("auto-membership", seed), membership=True)
    assert list(cp.nodes) == list(co.nodes)


def test_fault_matrix_sharded_conformance_pinned():
    _conformance(17, _fault_ops(17), ("matrix-sharded", 17), shards=4)


# ---------------------------------------------------------------------------
# Hypothesis phase (`make test-faults` / nightly lane; slow-deselected
# from tier-1).
# ---------------------------------------------------------------------------

try:
    from hypothesis import HealthCheck, given, settings
    from hypothesis import strategies as st

    _fop = st.one_of(
        st.tuples(st.just("put"), st.integers(0, 7), st.integers(0, 7),
                  st.booleans()),
        st.tuples(st.just("put"), st.integers(0, 7), st.integers(0, 7),
                  st.booleans()),               # twice: writes dominate
        st.tuples(st.just("get"), st.integers(0, 7), st.integers(0, 7)),
        st.tuples(st.just("cut"), st.integers(0, 7), st.integers(0, 7)),
        st.tuples(st.just("heal_link"), st.integers(0, 7),
                  st.integers(0, 7)),
        st.tuples(st.just("slow"), st.integers(0, 7),
                  st.sampled_from([1.0, 2.0, 8.0])),
        st.tuples(st.just("dup"), st.sampled_from([0.0, 0.3, 0.9])),
        st.tuples(st.just("reorder"), st.sampled_from([0.0, 0.4, 0.8])),
        st.tuples(st.just("flap"), st.integers(0, 7), st.integers(0, 7)),
        st.tuples(st.just("partition"), st.integers(1, 5)),
        st.tuples(st.just("heal")),
        st.tuples(st.just("fail"), st.integers(0, 7)),
        st.tuples(st.just("recover"), st.integers(0, 7)),
        st.tuples(st.just("advance"), st.integers(1, 25)),
        st.tuples(st.just("advance"), st.integers(1, 25)),
        st.tuples(st.just("deliver")),
    )

    @pytest.mark.slow
    @settings(max_examples=120, deadline=None,
              suppress_health_check=[HealthCheck.too_slow])
    @given(st.integers(min_value=0, max_value=1 << 20),
           st.lists(_fop, min_size=4, max_size=26),
           st.booleans())
    def test_fault_matrix_conformance_fuzzed(seed, ops, membership):
        _conformance(seed, ops, (seed, len(ops), membership),
                     membership=membership)

    @pytest.mark.slow
    @settings(max_examples=25, deadline=None,
              suppress_health_check=[HealthCheck.too_slow])
    @given(st.integers(min_value=0, max_value=1 << 20))
    def test_fault_determinism_fuzzed(seed):
        """Same seed ⇒ identical wire totals and final state under the
        full matrix with the controller attached."""
        ops = _fault_ops(seed, 26)
        c1, d1 = _run_schedule(seed, ops, packed=True, membership=True)
        c2, d2 = _run_schedule(seed, ops, packed=True, membership=True)
        assert c1.network.bytes_sent == c2.network.bytes_sent
        assert c1.network.timers_fired == c2.network.timers_fired
        assert (c1.network.duplicated, c1.network.reordered) == \
            (c2.network.duplicated, c2.network.reordered)
        assert (c1.membership.probes, c1.membership.evictions,
                c1.membership.readmissions) == \
            (c2.membership.probes, c2.membership.evictions,
             c2.membership.readmissions)
        for k in KEYS:
            for n in c1.nodes:
                assert c1.nodes[n].versions(k) == c2.nodes[n].versions(k)
except ImportError:     # pinned lanes above still run
    pass
