"""Host milliseconds per call of the two DVV bucket caches' front ends,
pad, transfer, dispatch and fetch included: total time of the program's
spans ``kernel.front`` and ``kernel.front.cold`` over their calls
(``repro.trace``, recorded while a profile is being taken, which in a
traced run is the window alone).  A program without ``repro.trace``
gives nothing."""


def read(w):
    if not w.get("ops"):
        return None
    try:
        from repro import trace
    except ImportError:
        return None
    spans = trace.snapshot()["spans"]
    rows = [spans[n] for n in (trace.KERNEL_FRONT, trace.KERNEL_FRONT_COLD)
            if n in spans]
    calls = sum(r["calls"] for r in rows)
    if not calls:
        return None
    return sum(r["total_ns"] for r in rows) / calls / 1e6
