"""Host milliseconds per client op spent inside ``KVCluster.get_many`` and
``put_many`` (the benchmark's span around the two calls), over the window."""


def read(w):
    if not w.get("ops"):
        return None
    return 1e3 * w["plane_s"] / w["ops"]
