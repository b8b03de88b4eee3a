"""Share of a repair window in which no operation ran on the device:
1 minus the union of device-op intervals over the traced window.  A trace
with no device plane (a CPU run) gives nothing."""


def read(w):
    tr = w.get("trace")
    if not tr or not tr["devices"] or not w.get("repaired_keys"):
        return None
    return 100.0 * (1.0 - tr["busy_s"] / tr["window_s"])
