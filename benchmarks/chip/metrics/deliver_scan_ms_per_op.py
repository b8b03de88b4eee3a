"""Host milliseconds per client op that ``SimNetwork.deliver`` spends
choosing the next message (the scan, sort and removal over its whole
queue), apart from applying it: self time of the program's span
``net.deliver.scan`` over the window's ops (``repro.trace``, recorded
while a profile is being taken, which in a traced run is the window
alone).  A program without ``repro.trace`` gives nothing."""


def read(w):
    if not w.get("ops"):
        return None
    try:
        from repro import trace
    except ImportError:
        return None
    row = trace.snapshot()["spans"].get(trace.NET_DELIVER_SCAN)
    if not row:
        return None
    return row["self_ns"] / w["ops"] / 1e6
