"""Grouped clock tensors (quorum groups of a ``get_many``, per-shard write
batches of a ``put_many``) folded into each stacked kernel launch of the
cluster plane: the program's counters ``plane.stack.tensors`` over
``plane.stack.launches`` (``repro.trace``), recorded while a profile is
being taken, which in a traced run is the window alone.  A program
without those counters gives nothing."""


def read(w):
    try:
        from repro import trace
    except ImportError:
        return None
    counters = trace.snapshot()["counters"]
    launches = counters.get("plane.stack.launches", 0)
    if not launches:
        return None
    return counters.get("plane.stack.tensors", 0) / launches
