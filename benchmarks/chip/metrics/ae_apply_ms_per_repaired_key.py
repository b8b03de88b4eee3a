"""Host milliseconds per repaired key in applying the delta anti-entropy
payloads at their receivers (``apply_payload``: gather, survival mask on
the kernel, write-back): total time of the program's span ``ae.apply``
over the window's repaired keys (``repro.trace``, recorded while a
profile is being taken, which in a traced run is the window alone).  A
program without ``repro.trace`` gives nothing."""


def read(w):
    if not w.get("repaired_keys"):
        return None
    try:
        from repro import trace
    except ImportError:
        return None
    row = trace.snapshot()["spans"].get(trace.AE_APPLY)
    if not row:
        return None
    return row["total_ns"] / w["repaired_keys"] / 1e6
