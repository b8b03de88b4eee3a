"""Calls into the two DVV bucket caches (hits plus misses, window
differences) per client op."""


def read(w):
    if not w.get("ops"):
        return None
    calls = sum(w["kernel_hits"].values()) + sum(w["kernel_misses"].values())
    return calls / w["ops"]
