"""The fused sparse-update kernel's share of its roofline: the least time
the chip could take for the update's algorithmic bytes (every distinct
row of every table read and written once over all slabs, the bags'
float32 cotangents and the sorted stream, counted from the batch itself)
at peak HBM bandwidth, over the kernel's device time per step.  The
bytes bound it: the update's FLOPs (one multiply-add per looked-up
element) take a thousandth of the time at peak."""


def read(r):
    if r.trace is None or not r.trace.devices or not r.steps:
        return None
    kernel_s = r.trace.layer_seconds(r.layers).get("sparse_update", 0.0) / r.steps
    if kernel_s <= 0:
        return None
    return 100.0 * (r.update_bytes / r.peak["hbm_bytes_per_s"]) / kernel_s
