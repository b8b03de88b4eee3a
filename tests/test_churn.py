"""Randomized churn conformance: the store under a changing universe.

One schedule interpreter drives *everything the system can do at once* —
puts (with and without causal context), gets, partitions, heals, node
failures/recoveries, joins (with warm bootstrap) and departures — against
a cluster whose gossip runs continuously off simulated time
(``GossipDriver``).  After the schedule, the world is quiesced (heal,
recover, drain, gossip to convergence) and three properties must hold:

* **replica agreement** — every live replica holds the identical sibling
  set for every key (and ``cluster_converged`` says so);
* **backend agreement** — the packed int32 store and the object-clock
  store, driven by the same schedule, end observationally equal
  (version sets, metadata sizes, resolved register values);
* **seed determinism** — the same seed replays the identical message
  trace, byte for byte, timers included (churn must not introduce
  iteration-order or hash-order nondeterminism anywhere).

The hypothesis phase (``slow``+``churn`` markers — the ``make
test-churn`` lane is its dedicated home) fuzzes schedules; a few pinned
seeds run in tier-1 so the machinery never rots unexercised.
"""
import random
import shutil
import tempfile

import pytest

from repro.core import DVV_MECHANISM
from repro.store import (GossipDriver, KVCluster, MembershipController,
                         SimNetwork, Unavailable, cluster_converged)

pytestmark = pytest.mark.churn

KEYS = tuple(f"k{i}" for i in range(5))
BASE_NODES = ("n0", "n1", "n2")
MAX_NODES = 6


# ---------------------------------------------------------------------------
# The schedule interpreter (shared by both backends and the fuzzer).
# ---------------------------------------------------------------------------

def _run_schedule(seed, ops, packed, quiesce=True, shards=1,
                  membership=False, wal_dir=None):
    """Interpret one churn schedule.  All choices are resolved against
    *current* membership (indices mod the live node list), so the same op
    list is meaningful whatever the interleaving did to the cluster.

    ``membership=True`` attaches a ``MembershipController`` — the
    self-driving loop then evicts/re-admits nodes on its own (schedules
    exercising it use fault ops, never hand-called add/remove), and the
    conformance helpers verify the membership *trajectory* is identical
    across backends too.  The fault ops (``cut``/``heal_link``/``slow``/
    ``dup``/``reorder``/``flap``) drive the SimNetwork fault matrix.

    ``wal_dir`` turns on the durable segment logs (small snapshot/seal
    knobs so schedules cross many snapshot and seal boundaries) and
    enables the ``crash_restart`` op: discard a node's process state and
    rebuild it warm from disk mid-schedule (DESIGN.md §14)."""
    net = SimNetwork(seed=seed)
    wal_kwargs = {} if wal_dir is None else dict(
        wal_dir=wal_dir, wal_snapshot_every=6, wal_seal_bytes=2048)
    c = KVCluster(BASE_NODES, DVV_MECHANISM, packed=packed, network=net,
                  seed=seed, shards=shards, **wal_kwargs)
    driver = GossipDriver(c, period=6.0, seed=seed)
    controller = MembershipController(c, period=6.0, seed=seed) \
        if membership else None
    contexts = {}
    next_id = len(BASE_NODES)
    for t, op in enumerate(ops):
        kind = op[0]
        nodes = list(c.nodes)
        if kind == "put":
            _, ki, ni, use_ctx = op
            node = nodes[ni % len(nodes)]
            key = KEYS[ki % len(KEYS)]
            ctx = contexts.get((node, key)) if use_ctx else None
            try:
                c.put(key, f"v{t}", context=ctx, via=node, coordinator=node)
            except Unavailable:
                pass
        elif kind == "get":
            _, ki, ni = op
            node = nodes[ni % len(nodes)]
            key = KEYS[ki % len(KEYS)]
            try:
                contexts[(node, key)] = c.get(key, via=node).context
            except Unavailable:
                pass
        elif kind == "partition":
            _, p = op
            g1 = {n for i, n in enumerate(nodes) if (i + p) % 2}
            g2 = set(nodes) - g1
            if g1 and g2:
                net.partition(g1, g2)
        elif kind == "heal":
            net.heal()
        elif kind == "fail":
            _, ni = op
            node = nodes[ni % len(nodes)]
            if len(net.down) < len(nodes) - 1:   # keep one node alive
                net.fail_node(node)
        elif kind == "recover":
            _, ni = op
            net.recover_node(nodes[ni % len(nodes)])
        elif kind == "add":
            if len(c.nodes) < MAX_NODES:
                c.add_node(f"n{next_id}")
                next_id += 1
        elif kind == "remove":
            _, ni = op
            if len(c.nodes) > 2:
                c.remove_node(nodes[ni % len(nodes)])
        elif kind == "advance":
            _, dt = op
            driver.run_for(float(dt))
        elif kind == "deliver":
            c.deliver_replication()
        elif kind == "cut":                      # one-directional link cut
            _, i, j = op
            a, b = nodes[i % len(nodes)], nodes[j % len(nodes)]
            if a != b:
                net.cut_link(a, b)
        elif kind == "heal_link":
            _, i, j = op
            net.heal_link(nodes[i % len(nodes)], nodes[j % len(nodes)])
        elif kind == "slow":                     # slow-not-dead node
            _, ni, factor = op
            net.set_delay_factor(nodes[ni % len(nodes)], float(factor))
        elif kind == "dup":
            _, rate = op
            net.set_duplication(float(rate))
        elif kind == "reorder":
            _, rate = op
            net.set_reorder(float(rate), spread=25.0)
        elif kind == "flap":
            _, i, j = op
            a, b = nodes[i % len(nodes)], nodes[j % len(nodes)]
            if a != b and len(net._flaps) < 2:   # bound concurrent flaps
                net.flap_link(a, b, up_for=8.0, down_for=8.0)
        elif kind == "crash_restart":
            # process crash + immediate warm restart from the durable log
            # (the old replica object is discarded, so any state the log
            # failed to carry would be *observably* lost here)
            _, ni = op
            node = nodes[ni % len(nodes)]
            if wal_dir is not None and node in c.wal:
                c.restart_node(node)
        else:                                    # pragma: no cover
            raise AssertionError(op)
    if quiesce:
        net.stop_flaps()
        net.heal()
        for n in list(net.down):
            net.recover_node(n)
        for n in list(net.delay_factors):
            net.set_delay_factor(n, 1.0)
        net.set_duplication(0.0)
        net.set_reorder(0.0)
        c.deliver_replication()
        driver.run_for(60.0 * len(c.nodes))
        # slow-node stragglers may still be queued with due times past the
        # run_for horizon; a second unbounded drain flushes them
        c.deliver_replication()
        # belt and braces: bounded explicit rounds prove a fixpoint even
        # if the adaptive cadence backed off right before the deadline
        for _ in range(len(c.nodes) + 1):
            c.delta_antientropy_round()
        # queue-leak probe (satellite bugfix): nothing may remain queued
        # toward a node that is no longer a member — eviction must purge
        assert all(m.dst in c.nodes for m in net.queue), \
            [(m.src, m.dst) for m in net.queue if m.dst not in c.nodes]
        if controller is not None:
            # zero hand-called membership: after full heal + recovery the
            # loop must have re-admitted every evicted node by itself
            assert not controller.evicted_nodes(), controller.evicted_nodes()
    return c, driver


def _assert_replicas_agree(c, tag):
    assert cluster_converged(c), tag
    for k in KEYS:
        ref = None
        for n in c.nodes:
            vs = c.nodes[n].versions(k)
            if ref is None:
                ref = vs
            assert vs == ref, (tag, n, k)


def _assert_backends_agree(cp, co, tag):
    assert list(cp.nodes) == list(co.nodes), tag
    for k in KEYS:
        for n in cp.nodes:
            vp, vo = cp.nodes[n].versions(k), co.nodes[n].versions(k)
            assert vp == vo, (tag, n, k, vp, vo)
            assert cp.nodes[n].metadata_size(k) == \
                co.nodes[n].metadata_size(k), (tag, n, k)
        gp, go = cp.get(k), co.get(k)
        assert gp.values == go.values, (tag, k)
        assert gp.value == go.value, (tag, k)
        assert gp.context == go.context, (tag, k)


def _conformance(seed, ops, tag, shards=1, membership=False, wal=False):
    tmp = tempfile.mkdtemp(prefix="churnwal-") if wal else None
    try:
        cp, _ = _run_schedule(
            seed, ops, packed=True, shards=shards, membership=membership,
            wal_dir=tmp and f"{tmp}/packed")
        co, _ = _run_schedule(
            seed, ops, packed=False, shards=shards, membership=membership,
            wal_dir=tmp and f"{tmp}/object")
        _assert_replicas_agree(cp, ("packed", tag))
        _assert_replicas_agree(co, ("object", tag))
        _assert_backends_agree(cp, co, tag)
        if membership:
            # the self-driving loop's decisions are part of conformance:
            # same probes, same evictions, same re-admissions on both
            # backends
            mp, mo = cp.membership, co.membership
            assert (mp.probes, mp.evictions, mp.readmissions) == \
                (mo.probes, mo.evictions, mo.readmissions), tag
        if wal:
            # every packed store must come out of replay + catch-up with
            # coherent digest trees and bucket indexes
            for n in cp.nodes.values():
                for st in n.shard_stores:
                    assert st.check_digests(), tag
                    assert st.check_bucket_index(), tag
        return cp, co
    finally:
        if tmp is not None:
            shutil.rmtree(tmp, ignore_errors=True)


def _random_ops(seed, n_ops=40):
    rng = random.Random(seed)
    ops = []
    for _ in range(n_ops):
        p = rng.random()
        if p < 0.35:
            ops.append(("put", rng.randrange(8), rng.randrange(8),
                        rng.random() < 0.5))
        elif p < 0.50:
            ops.append(("get", rng.randrange(8), rng.randrange(8)))
        elif p < 0.58:
            ops.append(("partition", rng.randrange(1, 6)))
        elif p < 0.64:
            ops.append(("heal",))
        elif p < 0.70:
            ops.append(("fail", rng.randrange(8)))
        elif p < 0.76:
            ops.append(("recover", rng.randrange(8)))
        elif p < 0.81:
            ops.append(("add",))
        elif p < 0.86:
            ops.append(("remove", rng.randrange(8)))
        elif p < 0.96:
            ops.append(("advance", rng.randrange(1, 25)))
        else:
            ops.append(("deliver",))
    return ops


# ---------------------------------------------------------------------------
# Tier-1 pinned schedules (fast lane: the machinery never rots).
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("seed", [0, 7, 23])
def test_churn_conformance_pinned(seed):
    _conformance(seed, _random_ops(seed), seed)


@pytest.mark.parametrize("seed", [0, 23])
def test_churn_conformance_pinned_sharded(seed):
    """The same schedules with the store split across 4 hash shards:
    placement, per-shard gossip, rebalance-on-join and handoff-on-depart
    must leave the sharded stores observationally identical to the
    single-dict object backend."""
    _conformance(seed, _random_ops(seed), ("sharded", seed), shards=4)


def _random_durable_ops(seed, n_ops=36):
    """Churn ops with warm restarts in the mix: crashes land between
    partitions, failures and membership changes, so replay + one-delta-pass
    recovery is exercised against every kind of concurrent divergence."""
    rng = random.Random(f"durable:{seed}")
    ops = []
    for _ in range(n_ops):
        p = rng.random()
        if p < 0.34:
            ops.append(("put", rng.randrange(8), rng.randrange(8),
                        rng.random() < 0.5))
        elif p < 0.46:
            ops.append(("get", rng.randrange(8), rng.randrange(8)))
        elif p < 0.54:
            ops.append(("crash_restart", rng.randrange(8)))
        elif p < 0.61:
            ops.append(("partition", rng.randrange(1, 6)))
        elif p < 0.66:
            ops.append(("heal",))
        elif p < 0.71:
            ops.append(("fail", rng.randrange(8)))
        elif p < 0.76:
            ops.append(("recover", rng.randrange(8)))
        elif p < 0.80:
            ops.append(("add",))
        elif p < 0.84:
            ops.append(("remove", rng.randrange(8)))
        elif p < 0.95:
            ops.append(("advance", rng.randrange(1, 25)))
        else:
            ops.append(("deliver",))
    return ops


@pytest.mark.durable
@pytest.mark.parametrize("shards", [1, 4])
@pytest.mark.parametrize("seed", [2, 11])
def test_durable_churn_conformance_pinned(seed, shards):
    """Warm restarts mixed with partitions and membership churn: packed
    and object backends must stay observationally equal when every node
    logs durably and some get crash-restarted mid-schedule."""
    _conformance(seed, _random_durable_ops(seed), ("durable", seed, shards),
                 shards=shards, wal=True)


@pytest.mark.durable
def test_durable_restart_during_partition_schedule():
    """Hand-written worst case: a node restarts *while partitioned away*
    (its recovery delta pass reaches only its own side), then the heal
    must reconcile both the restart and the partition divergence."""
    ops = [
        ("put", 0, 0, False), ("put", 1, 1, False), ("advance", 10),
        ("partition", 1), ("put", 0, 0, True), ("put", 2, 2, False),
        ("crash_restart", 1),                # restart inside the partition
        ("put", 3, 1, False), ("advance", 15),
        ("heal",), ("crash_restart", 0),     # restart right after heal
        ("put", 4, 2, True), ("advance", 20),
        ("fail", 2), ("crash_restart", 2),   # restart a failed-dead node
        ("advance", 25), ("deliver",),
    ]
    _conformance(5, ops, "durable-partition-restart", wal=True)


def test_churn_heavy_membership_schedule():
    """A hand-written worst case: join during partition, write to the
    joiner, depart an original node while its writes are still in flight."""
    ops = [
        ("put", 0, 0, False), ("put", 1, 1, False), ("advance", 10),
        ("partition", 1), ("put", 0, 0, True), ("add",),
        ("put", 2, 3, False),                # write lands on the joiner
        ("advance", 15), ("heal",), ("add",),
        ("fail", 1), ("put", 3, 0, False), ("advance", 20),
        ("remove", 1),                       # depart one of the originals
        ("recover", 1), ("put", 4, 2, True), ("advance", 30),
    ]
    _conformance(3, ops, "heavy-membership")


def test_same_seed_identical_message_trace():
    """The seed-determinism probe: two runs of one seed produce the same
    message trace (src, dst, kind, size, send-time), the same timer count,
    and the same wire totals — churn introduces no hidden ordering."""
    from repro.store.network import payload_nbytes

    def run_with_trace():
        trace = []
        orig_send = SimNetwork.send

        def send(self, src, dst, payload):
            ok = orig_send(self, src, dst, payload)
            trace.append((round(self.now, 9), src, dst, payload[0],
                          payload_nbytes(payload), ok))
            return ok

        SimNetwork.send = send
        try:
            c, d = _run_schedule(17, _random_ops(17, 50), packed=True)
        finally:
            SimNetwork.send = orig_send
        return trace, c, d

    t1, c1, d1 = run_with_trace()
    t2, c2, d2 = run_with_trace()
    assert t1 == t2
    assert c1.network.timers_fired == c2.network.timers_fired
    assert c1.network.bytes_sent == c2.network.bytes_sent
    assert (d1.ticks, d1.rounds, d1.wire_bytes()) == \
        (d2.ticks, d2.rounds, d2.wire_bytes())
    for k in KEYS:
        for n in c1.nodes:
            assert c1.nodes[n].versions(k) == c2.nodes[n].versions(k)


# ---------------------------------------------------------------------------
# Geo schedules: whole-DC WAN cuts (+ the usual churn) against a
# two-datacenter cluster; snapshot reads probed mid-schedule.
# ---------------------------------------------------------------------------

GEO_DCS = {"east": ("e0", "e1", "e2"), "west": ("w0", "w1", "w2")}
GEO_NODES = tuple(n for ns in GEO_DCS.values() for n in ns)


def _run_geo_schedule(seed, ops, packed, quiesce=True, shards=1):
    """The churn interpreter's geo twin: fixed membership (mirror placement
    is static), WAN latency classes, a ``partition_dc`` action that cuts a
    whole datacenter off the WAN, and ``snapshot_get`` probes whose
    results are collected for cross-backend comparison."""
    net = SimNetwork(seed=seed)
    net.set_latency_classes(lan=(1.0, 0.5), wan=(30.0, 10.0))
    c = KVCluster(GEO_NODES, DVV_MECHANISM, packed=packed, network=net,
                  seed=seed, shards=shards, datacenters=GEO_DCS,
                  wan_period=12.0)
    driver = GossipDriver(c, period=6.0, seed=seed)
    contexts = {}
    snaps = []
    for t, op in enumerate(ops):
        kind = op[0]
        if kind == "put":
            _, ki, ni, use_ctx = op
            node = GEO_NODES[ni % len(GEO_NODES)]
            key = KEYS[ki % len(KEYS)]
            ctx = contexts.get((node, key)) if use_ctx else None
            try:
                c.put(key, f"v{t}", context=ctx, via=node, coordinator=node)
            except Unavailable:
                pass
        elif kind == "get":
            _, ki, ni = op
            node = GEO_NODES[ni % len(GEO_NODES)]
            key = KEYS[ki % len(KEYS)]
            try:
                contexts[(node, key)] = c.get(key, via=node).context
            except Unavailable:
                pass
        elif kind == "snapshot_get":
            _, ki, ni = op
            node = GEO_NODES[ni % len(GEO_NODES)]
            key = KEYS[ki % len(KEYS)]
            try:
                r = c.snapshot_get(key, via=node)
                snaps.append((t, key, node, r.values, r.context))
                contexts[(node, key)] = r.context
            except Unavailable:
                snaps.append((t, key, node, None, None))
        elif kind == "partition_dc":
            _, di = op
            dc = list(GEO_DCS)[di % len(GEO_DCS)]
            cut = set(GEO_DCS[dc])
            net.partition(cut, set(GEO_NODES) - cut)
        elif kind == "heal":
            net.heal()
        elif kind == "fail":
            _, ni = op
            node = GEO_NODES[ni % len(GEO_NODES)]
            if len(net.down) < len(GEO_NODES) - 1:
                net.fail_node(node)
        elif kind == "recover":
            _, ni = op
            net.recover_node(GEO_NODES[ni % len(GEO_NODES)])
        elif kind == "advance":
            _, dt = op
            driver.run_for(float(dt))
        elif kind == "deliver":
            c.deliver_replication()
        else:                                    # pragma: no cover
            raise AssertionError(op)
    if quiesce:
        net.heal()
        for n in list(net.down):
            net.recover_node(n)
        c.deliver_replication()
        driver.run_for(60.0 * len(c.nodes))
        for _ in range(len(c.nodes) + 1):
            c.geo.wan_round()
            c.delta_antientropy_round()
    return c, driver, snaps


def _assert_geo_frontier_converged(c, tag):
    """Post-heal: every DC's frontier covers every live wall, so snapshot
    reads equal quorum reads for every key at every proxy."""
    top = 0.0
    for k in KEYS:
        for n in c.nodes:
            for v in c.nodes[n].versions(k):
                top = max(top, v.wall)
    for dc in GEO_DCS:
        assert c.geo.stable_frontier(dc) >= top, (tag, dc, top)
        assert c.geo.frontier_lag(dc) == 0.0, (tag, dc)
    for k in KEYS:
        ref = c.get(k)
        for dc, members in GEO_DCS.items():
            s = c.snapshot_get(k, via=members[0])
            assert s.values == ref.values, (tag, dc, k)
            assert s.value == ref.value, (tag, dc, k)


def _geo_conformance(seed, ops, tag, shards=1):
    cp, _, sp = _run_geo_schedule(seed, ops, packed=True, shards=shards)
    co, _, so = _run_geo_schedule(seed, ops, packed=False, shards=shards)
    _assert_replicas_agree(cp, ("geo-packed", tag))
    _assert_replicas_agree(co, ("geo-object", tag))
    _assert_backends_agree(cp, co, ("geo", tag))
    assert sp == so, ("geo-snapshots", tag)       # mid-schedule snapshots
    _assert_geo_frontier_converged(cp, ("geo-packed", tag))
    _assert_geo_frontier_converged(co, ("geo-object", tag))


def _random_geo_ops(seed, n_ops=32):
    rng = random.Random(seed)
    ops = []
    for _ in range(n_ops):
        p = rng.random()
        if p < 0.30:
            ops.append(("put", rng.randrange(8), rng.randrange(8),
                        rng.random() < 0.5))
        elif p < 0.42:
            ops.append(("get", rng.randrange(8), rng.randrange(8)))
        elif p < 0.54:
            ops.append(("snapshot_get", rng.randrange(8), rng.randrange(8)))
        elif p < 0.62:
            ops.append(("partition_dc", rng.randrange(2)))
        elif p < 0.68:
            ops.append(("heal",))
        elif p < 0.73:
            ops.append(("fail", rng.randrange(8)))
        elif p < 0.80:
            ops.append(("recover", rng.randrange(8)))
        elif p < 0.95:
            ops.append(("advance", rng.randrange(1, 25)))
        else:
            ops.append(("deliver",))
    return ops


@pytest.mark.geo
@pytest.mark.parametrize("shards", [1, 4])
@pytest.mark.parametrize("seed", [1, 19])
def test_geo_churn_conformance_pinned(seed, shards):
    _geo_conformance(seed, _random_geo_ops(seed), (seed, shards),
                     shards=shards)


@pytest.mark.geo
def test_geo_churn_dc_cut_heal_schedule():
    """Hand-written worst case: writes on both sides of a WAN cut, causal
    chains crossing the heal, snapshots probed throughout."""
    ops = [
        ("put", 0, 0, False), ("advance", 10), ("snapshot_get", 0, 4),
        ("partition_dc", 0),
        ("put", 0, 1, True), ("put", 1, 4, False),   # both sides write
        ("snapshot_get", 0, 4), ("snapshot_get", 1, 1),
        ("advance", 20), ("heal",), ("advance", 40),
        ("get", 0, 5), ("put", 2, 5, True),          # chain across the heal
        ("snapshot_get", 2, 0), ("advance", 30),
        ("fail", 3), ("snapshot_get", 1, 3), ("recover", 3),
        ("advance", 25), ("deliver",),
    ]
    _geo_conformance(29, ops, "geo-cut-heal")


# ---------------------------------------------------------------------------
# Adaptive gossip against a fixed cadence under churn (DESIGN.md §8.1).
# ---------------------------------------------------------------------------

GOSSIP_PERIOD = 10.0     # base gossip period (simulated seconds)


def _churn_event(c, rng, next_id):
    """One membership/fault event, chosen and applied deterministically."""
    nodes = list(c.nodes)
    kind = rng.choice(("partition", "heal", "fail", "recover",
                       "add", "remove"))
    if kind == "partition":
        p = rng.randrange(1, 4)
        g1 = {n for i, n in enumerate(nodes) if (i + p) % 2}
        if g1 and set(nodes) - g1:
            c.network.partition(g1, set(nodes) - g1)
    elif kind == "heal":
        c.network.heal()
    elif kind == "fail":
        if len(c.network.down) < len(nodes) - 2:
            c.network.fail_node(rng.choice(nodes))
    elif kind == "recover":
        if c.network.down:
            c.network.recover_node(rng.choice(sorted(c.network.down)))
    elif kind == "add":
        if len(c.nodes) < 7:
            c.add_node(f"x{next_id[0]}")
            next_id[0] += 1
    elif kind == "remove":
        if len(c.nodes) > 3:
            c.remove_node(rng.choice(nodes))


def _gossip_under_churn(churn_per_1k, adaptive, seed=0):
    """1500 simulated seconds of bursty writes (80 s active, one put every
    4 s, then 170 s calm) over 64 keys on 5 nodes, with churn events at
    ``churn_per_1k`` per 1000 s; then quiesce.  Workload and churn come from
    their own rng streams, so both schedulers see the same schedule.
    Returns (wire bytes incl. a 500 s idle tail, convergence lag, idle-tail
    bytes, converged)."""
    net = SimNetwork(seed=seed)
    c = KVCluster(tuple(f"n{i}" for i in range(5)), DVV_MECHANISM,
                  network=net, seed=seed)
    driver = GossipDriver(c, period=GOSSIP_PERIOD,
                          max_period=8 * GOSSIP_PERIOD, adapt=adaptive,
                          seed=seed)
    write_rng = random.Random(seed * 7 + 1)
    churn_rng = random.Random(seed * 7 + 2)
    next_id = [0]
    next_write = 0.0
    for _ in range(1500):
        driver.run_for(1.0)
        if net.now % 250.0 < 80.0 and net.now >= next_write:
            next_write = net.now + 4.0
            node = write_rng.choice(list(c.nodes))
            try:
                c.put(f"k{write_rng.randrange(64)}", f"v@{net.now:.0f}",
                      via=node, coordinator=node)
            except Unavailable:
                pass
        if churn_rng.random() < churn_per_1k / 1000.0:
            _churn_event(c, churn_rng, next_id)
    net.heal()
    for n in sorted(net.down):
        net.recover_node(n)
    c.deliver_replication()
    t0 = net.now
    while not cluster_converged(c) and net.now - t0 < 2000.0:
        driver.run_for(1.0)
    lag = net.now - t0
    converged = cluster_converged(c)
    idle0 = driver.wire_bytes()
    driver.run_for(500.0)
    return (driver.wire_bytes(), lag, driver.wire_bytes() - idle0,
            converged)


@pytest.mark.parametrize("churn_per_1k", [2.0, 8.0, 20.0])
def test_adaptive_gossip_ships_fewer_bytes_than_fixed_cadence(churn_per_1k):
    """Anti-entropy cost tracks observed divergence, not a fixed cadence:
    on the same bursty workload and churn schedule, adaptive gossip moves
    at least 2x fewer wire bytes (digest + payload phases) than the same
    driver at a fixed cadence, backs off further once the cluster is idle,
    and converges within two base periods of it."""
    fixed, fixed_lag, fixed_idle, fixed_ok = _gossip_under_churn(
        churn_per_1k, adaptive=False)
    adapt, adapt_lag, adapt_idle, adapt_ok = _gossip_under_churn(
        churn_per_1k, adaptive=True)
    assert fixed_ok and adapt_ok
    assert 2 * adapt < fixed, (adapt, fixed)
    assert adapt_idle < fixed_idle, (adapt_idle, fixed_idle)
    assert adapt_lag <= fixed_lag + 2 * GOSSIP_PERIOD, (adapt_lag, fixed_lag)


# ---------------------------------------------------------------------------
# Hypothesis phase: ≥200 randomized schedules across BOTH backends
# (`make test-churn`; deselected from tier-1 via the slow marker).
# ---------------------------------------------------------------------------

try:
    from hypothesis import HealthCheck, given, settings
    from hypothesis import strategies as st

    _op = st.one_of(
        st.tuples(st.just("put"), st.integers(0, 7), st.integers(0, 7),
                  st.booleans()),
        st.tuples(st.just("put"), st.integers(0, 7), st.integers(0, 7),
                  st.booleans()),               # twice: writes dominate
        st.tuples(st.just("get"), st.integers(0, 7), st.integers(0, 7)),
        st.tuples(st.just("partition"), st.integers(1, 5)),
        st.tuples(st.just("heal")),
        st.tuples(st.just("fail"), st.integers(0, 7)),
        st.tuples(st.just("recover"), st.integers(0, 7)),
        st.tuples(st.just("add")),
        st.tuples(st.just("remove"), st.integers(0, 7)),
        st.tuples(st.just("advance"), st.integers(1, 25)),
        st.tuples(st.just("advance"), st.integers(1, 25)),
        st.tuples(st.just("deliver")),
    )

    # slow + churn only (no `property` marker): the churn lane is these
    # tests' dedicated home — carrying `property` too would run the same
    # 200 examples again in the nightly test-property lane.
    @pytest.mark.slow
    @settings(max_examples=200, deadline=None,
              suppress_health_check=[HealthCheck.too_slow])
    @given(st.integers(min_value=0, max_value=1 << 20),
           st.lists(_op, min_size=4, max_size=28),
           st.sampled_from([1, 4]))
    def test_churn_conformance_fuzzed(seed, ops, shards):
        _conformance(seed, ops, (seed, len(ops), shards), shards=shards)

    _geo_op = st.one_of(
        st.tuples(st.just("put"), st.integers(0, 7), st.integers(0, 7),
                  st.booleans()),
        st.tuples(st.just("put"), st.integers(0, 7), st.integers(0, 7),
                  st.booleans()),               # twice: writes dominate
        st.tuples(st.just("get"), st.integers(0, 7), st.integers(0, 7)),
        st.tuples(st.just("snapshot_get"), st.integers(0, 7),
                  st.integers(0, 7)),
        st.tuples(st.just("partition_dc"), st.integers(0, 1)),
        st.tuples(st.just("heal")),
        st.tuples(st.just("fail"), st.integers(0, 7)),
        st.tuples(st.just("recover"), st.integers(0, 7)),
        st.tuples(st.just("advance"), st.integers(1, 25)),
        st.tuples(st.just("advance"), st.integers(1, 25)),
        st.tuples(st.just("deliver")),
    )

    @pytest.mark.slow
    @pytest.mark.geo
    @settings(max_examples=100, deadline=None,
              suppress_health_check=[HealthCheck.too_slow])
    @given(st.integers(min_value=0, max_value=1 << 20),
           st.lists(_geo_op, min_size=4, max_size=24),
           st.sampled_from([1, 4]))
    def test_geo_churn_conformance_fuzzed(seed, ops, shards):
        _geo_conformance(seed, ops, (seed, len(ops), shards), shards=shards)

    _durable_op = st.one_of(
        st.tuples(st.just("put"), st.integers(0, 7), st.integers(0, 7),
                  st.booleans()),
        st.tuples(st.just("put"), st.integers(0, 7), st.integers(0, 7),
                  st.booleans()),               # twice: writes dominate
        st.tuples(st.just("get"), st.integers(0, 7), st.integers(0, 7)),
        st.tuples(st.just("crash_restart"), st.integers(0, 7)),
        st.tuples(st.just("crash_restart"), st.integers(0, 7)),
        st.tuples(st.just("partition"), st.integers(1, 5)),
        st.tuples(st.just("heal")),
        st.tuples(st.just("fail"), st.integers(0, 7)),
        st.tuples(st.just("recover"), st.integers(0, 7)),
        st.tuples(st.just("add")),
        st.tuples(st.just("remove"), st.integers(0, 7)),
        st.tuples(st.just("advance"), st.integers(1, 25)),
        st.tuples(st.just("deliver")),
    )

    @pytest.mark.slow
    @pytest.mark.durable
    @settings(max_examples=100, deadline=None,
              suppress_health_check=[HealthCheck.too_slow])
    @given(st.integers(min_value=0, max_value=1 << 20),
           st.lists(_durable_op, min_size=4, max_size=24),
           st.sampled_from([1, 4]))
    def test_durable_churn_conformance_fuzzed(seed, ops, shards):
        _conformance(seed, ops, ("durable", seed, len(ops), shards),
                     shards=shards, wal=True)

    @pytest.mark.slow
    @settings(max_examples=25, deadline=None,
              suppress_health_check=[HealthCheck.too_slow])
    @given(st.integers(min_value=0, max_value=1 << 20))
    def test_churn_determinism_fuzzed(seed):
        """Same seed ⇒ identical final state AND identical wire totals."""
        ops = _random_ops(seed, 30)
        c1, d1 = _run_schedule(seed, ops, packed=True)
        c2, d2 = _run_schedule(seed, ops, packed=True)
        assert c1.network.bytes_sent == c2.network.bytes_sent
        assert c1.network.timers_fired == c2.network.timers_fired
        assert (d1.ticks, d1.rounds, d1.wire_bytes()) == \
            (d2.ticks, d2.rounds, d2.wire_bytes())
        for k in KEYS:
            for n in c1.nodes:
                assert c1.nodes[n].versions(k) == c2.nodes[n].versions(k)
except ImportError:     # pinned schedules above still run
    pass
