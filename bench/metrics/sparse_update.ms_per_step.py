"""Device self time per train step of the fused sparse-update kernel's events (the Pallas ``tpu_custom_call`` made by ``sparse_row_update_pallas``)
(layer ``sparse_update`` in the rules of ``bench/layers/<system>/``), in ms."""


def read(r):
    if r.trace is None or not r.trace.devices or not r.steps:
        return None
    s = r.trace.layer_seconds(r.layers).get("sparse_update")
    return None if s is None else 1e3 * s / r.steps
