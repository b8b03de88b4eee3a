"""Shard payloads of the delta anti-entropy pushes folded into each stacked
sync-mask launch: the program's counters ``ae.stack.payloads`` over
``ae.stack.launches`` (``repro.trace``), recorded while a profile is being
taken, which in a traced run is the window alone.  A program without those
counters gives nothing."""


def read(w):
    try:
        from repro import trace
    except ImportError:
        return None
    counters = trace.snapshot()["counters"]
    launches = counters.get("ae.stack.launches", 0)
    if not launches:
        return None
    return counters.get("ae.stack.payloads", 0) / launches
