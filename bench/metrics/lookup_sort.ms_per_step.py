"""Device self time per train step of the lookup sort: the ``sort`` ops and the ops made by ``_row_sorted_streams`` / ``sort_lookups``
(layer ``lookup_sort`` in the rules of ``bench/layers/<system>/``), in ms."""


def read(r):
    if r.trace is None or not r.trace.devices or not r.steps:
        return None
    s = r.trace.layer_seconds(r.layers).get("lookup_sort")
    return None if s is None else 1e3 * s / r.steps
