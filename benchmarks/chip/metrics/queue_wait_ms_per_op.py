"""Wall milliseconds an op waits in the ``OpScheduler`` queue, from its
enqueue to the start of the flush that serves it: the program's counters
``sched.queue_wait_ns`` over ``sched.ops_flushed`` (``repro.trace``).  The
program records them only while a profile is being taken, which in a
traced run is the window alone.  A program without ``repro.trace`` gives
nothing."""


def read(w):
    if not w.get("ops"):
        return None
    try:
        from repro import trace
    except ImportError:
        return None
    counters = trace.snapshot()["counters"]
    n = counters.get(trace.SCHED_OPS_FLUSHED, 0)
    if not n:
        return None
    return counters[trace.SCHED_QUEUE_WAIT_NS] / n / 1e6
