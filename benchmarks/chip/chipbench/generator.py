"""The one traffic generator: reads a mix's data file and drives the store.

A mix is a JSON file of parameters under ``traffic/``; its ``kind`` picks
one of two drivers, both copied from the program's own generators
(``ClosedLoopEngine`` in ``store/serving.py`` and the burst of
``chip_smoke.py``) and not imported from them, so a change to the program
cannot change the yardstick:

* ``closed_loop``: ``clients`` client threads with no think time.  Each
  step reads one zipfian key through ``KVClient.submit_get`` (R =
  ``read_quorum``) and, once the read completes, writes it through
  ``KVClient.submit_put`` (W = ``write_quorum``) with the context token of
  that read.  The client is bound to an ``OpScheduler`` on the
  deployment's proxy that flushes through ``KVCluster.get_many/put_many``
  with ``use_kernel=True``.  Each op is timed on the host's wall clock
  from submission to completion.
* ``repair_cycles``: each cycle reads and rewrites a burst of zipfian keys
  through proxy ``via`` (``get_many``/``put_many``, ``use_kernel=True``),
  runs one all-pairs ``delta_antientropy_round(use_kernel=True)`` while the
  burst's replication is still queued, then delivers the queued
  replication.

Keys are drawn from a stream fixed by the seed: the same seed gives the
same sequence of keys and values, another seed another sequence over the
same zipfian ranks.
"""
from __future__ import annotations

import time
from typing import Any, Callable, Dict, List, Tuple

import numpy as np

from .deploy import dot_of, key_name
from .oracle import CausalOracle

#: Keys drawn per refill of a ``KeyStream``.
DRAW_BLOCK = 65536


class KeyStream:
    """Zipfian key draws over ``n`` keys ranked ``k0`` (hottest) upward."""

    def __init__(self, n: int, s: float, seed: int):
        ranks = np.arange(1, n + 1, dtype=np.float64)
        weights = ranks ** -float(s)
        self._cdf = np.cumsum(weights / weights.sum())
        self._n = n
        self._rng = np.random.default_rng(seed)
        self._block: List[int] = []
        self._pos = 0

    def next_index(self) -> int:
        if self._pos == len(self._block):
            u = self._rng.random(DRAW_BLOCK)
            self._block = np.minimum(np.searchsorted(self._cdf, u),
                                     self._n - 1).tolist()
            self._pos = 0
        i = self._block[self._pos]
        self._pos += 1
        return i

    def next_key(self) -> str:
        return key_name(self.next_index())


class ValueMaker:
    """Unique record values of the deployment's record size."""

    def __init__(self, nbytes: int, tag: str):
        self._nbytes = nbytes
        self._tag = tag
        self.made = 0

    def next(self) -> str:
        self.made += 1
        return f"{self._tag}{self.made}:".ljust(self._nbytes, ".")


class OpLog:
    """Wall-clock latency and outcome of every op of one window."""

    def __init__(self):
        self.latency_s: List[float] = []
        self.kinds: List[str] = []
        self.failed = 0

    def add(self, kind: str, seconds: float, ok: bool) -> None:
        self.kinds.append(kind)
        if ok:
            self.latency_s.append(seconds)
        else:
            self.failed += 1
            self.latency_s.append(float("inf"))

    @property
    def ops(self) -> int:
        return len(self.latency_s)


class ClosedLoop:
    """Closed-loop GET -> PUT clients on the coalescing serving plane."""

    def __init__(self, cluster, cfg: Dict[str, Any], spec: Dict[str, Any],
                 seed: int, oracle: CausalOracle, nbytes: int):
        from repro.store import OpScheduler

        sch = cfg["scheduler"]
        self.cluster = cluster
        self.network = cluster.network
        self.scheduler = OpScheduler(
            cluster, via=cfg["deployment"]["proxy"],
            max_batch=int(sch["max_batch"]),
            max_delay=float(sch["max_delay_ticks"]),
            read_quorum=int(spec["read_quorum"]),
            write_quorum=int(spec["write_quorum"]),
            read_repair=bool(sch["read_repair"]), use_kernel=True,
            pump=bool(sch["pump"]))
        self.client = self.scheduler.session("bench")
        self.clients = int(spec["clients"])
        if self.clients >= self.scheduler.max_batch:
            # a flush by size completes at one simulated instant, and its
            # callbacks would fill and flush the next batch inside it
            raise ValueError(f"{self.clients} clients need a max_batch "
                             f"above {self.scheduler.max_batch}, so that "
                             f"batches flush on the timer")
        self.keys = KeyStream(int(cfg["records"]), float(spec["zipf"]), seed)
        self.values = ValueMaker(nbytes, "w")
        self.oracle = oracle

    def run(self, seconds: float) -> Tuple[OpLog, float]:
        """Clients start steps until ``seconds`` of wall time have passed;
        returns the window's ops and its length, from the first submission
        to the completion of the last op."""
        log = OpLog()
        oracle, client = self.oracle, self.client
        t_start = time.perf_counter()
        deadline = t_start + seconds
        active = [self.clients]

        def start() -> None:
            if time.perf_counter() >= deadline:
                active[0] -= 1
                return
            key = self.keys.next_key()
            acked = oracle.acked_now(key)
            t = time.perf_counter()
            client.submit_get([key]).on_done(
                lambda op: after_get(op, key, acked, t))

        def after_get(op, key: str, acked: int, t: float) -> None:
            log.add("get", time.perf_counter() - t, op.error is None)
            if op.error is not None:
                start()
                return
            res = op.result()[key]
            oracle.observe_get(key, res.values, res.context, acked)
            value = self.values.next()
            event = oracle.write(key, value,
                                 oracle.history_of(key, res.values))
            token = client.encode_context(res.context)
            t2 = time.perf_counter()
            client.submit_put({key: (value, token)}).on_done(
                lambda op2: after_put(op2, key, event, t2))

        def after_put(op, key: str, event: int, t: float) -> None:
            log.add("put", time.perf_counter() - t, op.error is None)
            if op.error is None:
                oracle.acknowledged(key, event,
                                    dot_of(op.result()[key].clock))
            start()

        for _ in range(self.clients):
            start()
        while active[0] > 0:
            due = self.network.next_timer_due()
            if due is None:
                raise RuntimeError("closed loop stalled with clients active")
            self.network.advance(max(due - self.network.now, 0.0))
        window_s = time.perf_counter() - t_start
        self.cluster.deliver_replication()
        return log, window_s


class RepairCycles:
    """Burst, anti-entropy round, delivery — repeated."""

    def __init__(self, cluster, cfg: Dict[str, Any], spec: Dict[str, Any],
                 seed: int, oracle: CausalOracle, nbytes: int):
        self.cluster = cluster
        self.via = spec["via"]
        self.burst = int(spec["burst"])
        self.read_quorum = int(spec["read_quorum"])
        self.write_quorum = int(spec["write_quorum"])
        # peers each node pushes to per round; None is every peer
        self.fanout = spec.get("fanout")
        self.keys = KeyStream(int(cfg["records"]), float(spec["zipf"]), seed)
        self.values = ValueMaker(nbytes, "b")
        self.oracle = oracle
        self.ae_stats: List[Any] = []
        self.burst_s = 0.0
        self.round_s = 0.0
        self.deliver_s = 0.0

    def split(self) -> Dict[str, float]:
        """Seconds spent so far in each part of the cycles."""
        return {"burst_s": self.burst_s, "round_s": self.round_s,
                "deliver_s": self.deliver_s}

    def _burst_keys(self) -> List[str]:
        seen: Dict[str, None] = {}
        for _ in range(self.burst):
            seen.setdefault(self.keys.next_key(), None)
        return list(seen)

    def cycle(self) -> Tuple[int, float]:
        """One cycle; returns (keys repaired, seconds).  The
        replicas of the burst's keys are read for the reference between the
        round and the delivery, outside the cycle's time."""
        cluster, oracle = self.cluster, self.oracle
        keys = self._burst_keys()
        acked = {k: oracle.acked_now(k) for k in keys}
        t0 = time.perf_counter()
        read = cluster.get_many(keys, via=self.via, quorum=self.read_quorum,
                                use_kernel=True)
        items, events = {}, {}
        for k in keys:
            value = self.values.next()
            events[k] = oracle.write(k, value,
                                     oracle.history_of(k, read[k].values))
            items[k] = (value, read[k].context)
        acks = cluster.put_many(items, via=self.via,
                                quorum=self.write_quorum, use_kernel=True)
        t1 = time.perf_counter()
        stats = cluster.delta_antientropy_round(use_kernel=True,
                                                fanout=self.fanout)
        t2 = time.perf_counter()
        for k in keys:
            oracle.observe_get(k, read[k].values, read[k].context, acked[k])
            oracle.acknowledged(k, events[k], dot_of(acks[k].clock))
            oracle.observe_replicas(
                k, [[v.value for v in cluster.nodes[r].versions(k)]
                    for r in cluster.replicas_for(k)])
        t3 = time.perf_counter()
        cluster.deliver_replication()
        t4 = time.perf_counter()
        self.ae_stats.extend(stats)
        self.burst_s += t1 - t0
        self.round_s += t2 - t1
        self.deliver_s += t4 - t3
        return len(keys), (t2 - t0) + (t4 - t3)

    def run(self, seconds: float) -> Tuple[int, float, int]:
        """Cycles until their summed time reaches ``seconds``; returns
        (keys repaired, seconds, cycles)."""
        keys = cycles = 0
        spent = 0.0
        while spent < seconds:
            n, dt = self.cycle()
            keys += n
            spent += dt
            cycles += 1
        return keys, spent, cycles


def make_traffic(kind: str) -> Callable[..., Any]:
    table = {"closed_loop": ClosedLoop, "repair_cycles": RepairCycles}
    if kind not in table:
        raise ValueError(f"unknown traffic kind {kind!r}")
    return table[kind]
