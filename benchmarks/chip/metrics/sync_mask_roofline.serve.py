"""``dvv_sync_mask_pallas``'s share of its HBM roofline in a serving
window, where PUTs run it (bytes from logical shapes, time from the
trace)."""
from chipbench.kernel_cost import window_roofline


def read(w):
    return window_roofline(w, "sync_mask") if w.get("ops") else None
