"""Host milliseconds per repaired key in the digest phase of the delta
anti-entropy rounds (root probes, digest trees, fold, diff, range
ranking): self time of the program's span ``ae.digest`` over the window's
repaired keys (``repro.trace``, recorded while a profile is being taken,
which in a traced run is the window alone).  A program without
``repro.trace`` gives nothing."""


def read(w):
    if not w.get("repaired_keys"):
        return None
    try:
        from repro import trace
    except ImportError:
        return None
    row = trace.snapshot()["spans"].get(trace.AE_DIGEST)
    if not row:
        return None
    return row["self_ns"] / w["repaired_keys"] / 1e6
