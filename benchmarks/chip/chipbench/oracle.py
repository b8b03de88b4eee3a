"""The plain reference: causal histories kept as explicit event sets.

Every write the benchmark makes is one event, named by the unique value it
writes.  Its causal history is itself plus the union of the histories of
the siblings that the GET it carries the context of returned.  That is the
paper's definition of the semantics DVVs encode (and of what a
``dvv_enabled`` Riak bucket promises); nothing here imports the store or
reads a clock it computed, apart from the dot each acknowledgement names,
which ties an event to the counter the context speaks in.

Histories are Python ints used as bitsets over a key's events (event 0 is
the key's load write).  After the window the benchmark judges every GET it
observed, and the replicas of every repaired key, by five exact counts:

* ``unknown_value``  a returned value that no write to that key wrote;
* ``stale_sibling``  a returned sibling lying in the history of another;
* ``lost_write``     an acknowledged write that is missing from the history
  of a GET submitted after the acknowledgement (or of a replica that a
  repair has brought up to date);
* ``context_gap``    a context whose per-node counters do not denote
  exactly the union of the returned siblings' histories;
* ``replica_split``  replicas of a repaired key holding different siblings.

Each has the limit 0.
"""
from __future__ import annotations

from typing import Any, Callable, Dict, Iterable, List, Optional, Sequence, \
    Tuple

Dot = Tuple[str, int]

CHECKS = ("unknown_value", "stale_sibling", "lost_write", "context_gap",
          "replica_split")


class _KeyEvents:
    __slots__ = ("event_of", "hist", "dots", "acked")

    def __init__(self, load_value: str, load_dot: Optional[Dot]):
        self.event_of: Dict[str, int] = {load_value: 0}
        self.hist: List[int] = [1]
        self.dots: List[Optional[Dot]] = [load_dot]
        self.acked = 1


class CausalOracle:
    """Explicit causal histories of every key the run touched.

    ``load_value(key)`` and ``load_dot(key)`` give a key's load write, so
    untouched keys cost nothing."""

    def __init__(self, load_value: Callable[[str], str],
                 load_dot: Callable[[str], Optional[Dot]]):
        self._load_value = load_value
        self._load_dot = load_dot
        self._keys: Dict[str, _KeyEvents] = {}
        self._gets: List[Tuple[str, Tuple[Any, ...], Any, int]] = []
        self._replicas: List[Tuple[str, List[frozenset], int]] = []
        self.counts: Dict[str, int] = dict.fromkeys(CHECKS, 0)

    def _key(self, key: str) -> _KeyEvents:
        ev = self._keys.get(key)
        if ev is None:
            ev = self._keys[key] = _KeyEvents(self._load_value(key),
                                              self._load_dot(key))
        return ev

    # -- what the run tells the reference ----------------------------------

    def acked_now(self, key: str) -> int:
        """The acknowledged events of ``key``, as a GET submitted now must
        see them."""
        return self._key(key).acked

    def history_of(self, key: str, values: Iterable[Any]) -> int:
        """Union of the histories of ``values`` (unknown values add
        nothing; the GET that returned them is judged for them)."""
        ev = self._key(key)
        h = 0
        for v in values:
            e = ev.event_of.get(v)
            if e is not None:
                h |= ev.hist[e]
        return h

    def write(self, key: str, value: str, context_history: int) -> int:
        """A PUT was submitted: register its event before anything can
        return it.  Returns the event's index."""
        ev = self._key(key)
        if value in ev.event_of:
            raise ValueError(f"value written twice to {key}")
        e = len(ev.hist)
        ev.event_of[value] = e
        ev.hist.append(context_history | (1 << e))
        ev.dots.append(None)
        return e

    def acknowledged(self, key: str, event: int, dot: Optional[Dot]) -> None:
        ev = self._key(key)
        ev.dots[event] = dot
        ev.acked |= 1 << event

    def observe_get(self, key: str, values: Sequence[Any], context: Any,
                    acked_at_submit: int) -> None:
        """Keep one GET's answer to judge once the window has closed."""
        self._gets.append((key, tuple(values), context, acked_at_submit))

    def observe_replicas(self, key: str,
                         replica_values: Sequence[Iterable[Any]]) -> None:
        """Keep the value sets of one key's replicas after a repair, which
        must hold every write acknowledged by now."""
        self._replicas.append(
            (key, [frozenset(vs) for vs in replica_values],
             self._key(key).acked))

    # -- judging -----------------------------------------------------------

    @property
    def gets_observed(self) -> int:
        return len(self._gets)

    def sibling_counts(self, since: int = 0) -> Dict[int, int]:
        """How many GETs kept so far returned each number of siblings."""
        out: Dict[int, int] = {}
        for _, values, _, _ in self._gets[since:]:
            out[len(values)] = out.get(len(values), 0) + 1
        return dict(sorted(out.items()))

    def _judge_set(self, ev: _KeyEvents, values: Iterable[Any],
                   must_cover: int) -> Tuple[int, List[int]]:
        events = []
        for v in values:
            e = ev.event_of.get(v)
            if e is None:
                self.counts["unknown_value"] += 1
            else:
                events.append(e)
        h = 0
        for e in events:
            h |= ev.hist[e]
        for e in events:
            if any(f != e and ev.hist[f] >> e & 1 for f in events):
                self.counts["stale_sibling"] += 1
        if must_cover & ~h:
            self.counts["lost_write"] += 1
        return h, events

    def _context_matches(self, ev: _KeyEvents, h: int, context: Any) -> bool:
        """The context's counters denote exactly the events in ``h``."""
        entries = dict(getattr(context, "entries", ()) or ())
        if getattr(context, "residue", ()):
            return False
        seen: Dict[str, List[int]] = {}
        e = 0
        while h:
            if h & 1:
                dot = ev.dots[e]
                if dot is None:
                    return False
                seen.setdefault(dot[0], []).append(dot[1])
            h >>= 1
            e += 1
        if set(seen) != set(entries):
            return False
        return all(sorted(ns) == list(range(1, entries[r] + 1))
                   for r, ns in seen.items())

    def judge(self) -> Dict[str, int]:
        """Judge every kept observation; returns the counts (limit 0)."""
        for key, values, context, acked in self._gets:
            ev = self._key(key)
            h, _ = self._judge_set(ev, values, acked)
            if not self._context_matches(ev, h, context):
                self.counts["context_gap"] += 1
        for key, replicas, acked in self._replicas:
            ev = self._key(key)
            if any(r != replicas[0] for r in replicas[1:]):
                self.counts["replica_split"] += 1
            for vs in replicas:
                self._judge_set(ev, vs, acked)
        self._gets.clear()
        self._replicas.clear()
        return dict(self.counts)
