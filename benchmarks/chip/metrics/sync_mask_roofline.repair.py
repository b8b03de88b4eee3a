"""``dvv_sync_mask_pallas``'s share of its HBM roofline in a repair window,
where the anti-entropy rounds run it at large N (bytes from logical
shapes, time from the trace)."""
from chipbench.kernel_cost import window_roofline


def read(w):
    return (window_roofline(w, "sync_mask") if w.get("repaired_keys")
            else None)
