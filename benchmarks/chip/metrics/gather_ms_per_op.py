"""Host milliseconds per client op in the packed store's gathers (quorum
groups' union universe, remap and stack; writes' grouped encode; a
payload's remap and grouping): self time of the program's span
``packed.gather`` over the window's ops (``repro.trace``, recorded while a
profile is being taken, which in a traced run is the window alone).  A
program without ``repro.trace`` gives nothing."""


def read(w):
    if not w.get("ops"):
        return None
    try:
        from repro import trace
    except ImportError:
        return None
    row = trace.snapshot()["spans"].get(trace.PACKED_GATHER)
    if not row:
        return None
    return row["self_ns"] / w["ops"] / 1e6
