"""Share of lane slots that held a request, over the fused steps of the
window: the window's delta of the server's ``layer_calls`` over its
``dense_steps`` times the lanes."""


def read(w):
    c = w["counters"]
    if not c["dense_steps"]:
        return None
    return 100.0 * c["layer_calls"] / (c["dense_steps"] * w["lanes"])
