"""Straggler mitigation through the store's services: work-stealing over
the DVV lease ledger (``repro.store.WorkStealer``) with the store's
``FailureDetector`` guarantees every shard is processed exactly once even
when workers stall, die, or race through the same coordinator."""
import random

from repro.core import DVV_MECHANISM
from repro.store import FailureDetector, KVCluster, SimNetwork, WorkStealer

STORE = ("s1", "s2", "s3")


def test_stolen_shards_process_exactly_once():
    store = KVCluster(STORE, DVV_MECHANISM, network=SimNetwork(seed=0))
    shards = [f"shard-{i}" for i in range(12)]
    workers = {w: WorkStealer(store, w, lease_duration=5.0)
               for w in ("w0", "w1", "w2")}
    fd = FailureDetector(heartbeat_interval=1.0)
    processed = {}          # shard -> worker (the commit ledger)
    now = 0.0
    straggler = "w1"
    rng = random.Random(3)

    pending = set(shards)
    for round_ in range(40):
        now += 1.0
        for w, stealer in workers.items():
            if w == straggler and now > 3.0:
                continue            # w1 stalls forever after t=3
            fd.record(w, now)
            for shard in sorted(pending):
                owner = stealer.owner(shard, via=rng.choice(STORE))
                claimed = False
                if owner is None or owner == w:
                    claimed = stealer.try_claim(shard, now,
                                                via=rng.choice(STORE))
                elif owner in fd.suspects(now) or owner in fd.dead(now):
                    claimed = stealer.steal_expired(shard, now,
                                                    via=rng.choice(STORE))
                if claimed:
                    # process + commit (idempotence guard: the ledger is
                    # the source of truth, not the worker's belief)
                    if shard not in processed:
                        processed[shard] = w
                        pending.discard(shard)
                    break           # one shard per worker per tick
        if not pending:
            break

    assert not pending, f"unprocessed shards: {pending}"
    assert len(processed) == len(shards)
    # the straggler contributed at most its pre-stall work
    assert sum(1 for w in processed.values() if w == straggler) <= 3
    # live workers split the rest
    assert {w for w in processed.values()} <= {"w0", "w1", "w2"}


def test_concurrent_claims_during_partition_one_winner_after_heal():
    net = SimNetwork(seed=1)
    store = KVCluster(STORE, DVV_MECHANISM, network=net)
    w0 = WorkStealer(store, "w0", lease_duration=100.0)
    w1 = WorkStealer(store, "w1", lease_duration=100.0)
    net.partition({"s1"}, {"s2", "s3"})
    # both sides claim the same shard concurrently
    got0 = w0.try_claim("shard-X", now=0.0, via="s1")
    got1 = w1.try_claim("shard-X", now=0.0, via="s2")
    assert got0 and got1            # split brain: both believe they own it
    net.heal()
    store.antientropy_round()
    # after heal both leases surface as DVV siblings; the deterministic
    # resolver yields ONE owner everywhere
    owner_via_s1 = w0.owner("shard-X", via="s1")
    owner_via_s3 = w1.owner("shard-X", via="s3")
    assert owner_via_s1 == owner_via_s3
    assert owner_via_s1 in ("w0", "w1")
    # the loser observes it lost and cannot renew
    loser = "w1" if owner_via_s1 == "w0" else "w0"
    stealer = w1 if loser == "w1" else w0
    assert not stealer.renew("shard-X", now=1.0, via="s1")
