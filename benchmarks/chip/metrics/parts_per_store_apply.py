"""Shard parts of a replication pump joined into each store apply: the
program's counters ``replica.apply.parts`` over ``replica.apply.stores``
(``repro.trace``; ``PackedBackend.receive_pump``), recorded while a
profile is being taken, which in a traced run is the window alone.  A
program without those counters gives nothing."""


def read(w):
    try:
        from repro import trace
    except ImportError:
        return None
    counters = trace.snapshot()["counters"]
    stores = counters.get("replica.apply.stores", 0)
    if not stores:
        return None
    return counters.get("replica.apply.parts", 0) / stores
