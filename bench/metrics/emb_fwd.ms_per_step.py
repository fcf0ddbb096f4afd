"""Device self time per train step of the embedding forward: the bag gathers and the layout copies that feed them
(layer ``emb_fwd`` in the rules of ``bench/layers/<system>/``), in ms."""


def read(r):
    if r.trace is None or not r.trace.devices or not r.steps:
        return None
    s = r.trace.layer_seconds(r.layers).get("emb_fwd")
    return None if s is None else 1e3 * s / r.steps
