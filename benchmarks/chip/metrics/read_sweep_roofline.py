"""``dvv_read_sweep_pallas``'s share of its HBM roofline in a serving
window (bytes from logical shapes, time from the trace)."""
from chipbench.kernel_cost import window_roofline


def read(w):
    return window_roofline(w, "read_sweep") if w.get("ops") else None
