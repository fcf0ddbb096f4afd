"""The whole train step's share of the chips' HBM bandwidth: the step's
algorithmic bytes (forward gather, the sparse update's distinct rows read
and written over every slab, the bags' cotangents, the sorted stream, the
dense weights), counted from the batches, times steps per second, over
chips x peak bytes per second."""


def read(r):
    if not getattr(r, "step_bytes", None):
        return None
    return (100.0 * r.step_bytes * r.steps / r.window_s
            / (r.chips * r.peak["hbm_bytes_per_s"]))
