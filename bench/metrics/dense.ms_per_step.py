"""Device self time per train step of the dense MLPs and the interaction, forward and backward, and the dense update
(layer ``dense`` in the rules of ``bench/layers/<system>/``), in ms."""


def read(r):
    if r.trace is None or not r.trace.devices or not r.steps:
        return None
    s = r.trace.layer_seconds(r.layers).get("dense")
    return None if s is None else 1e3 * s / r.steps
