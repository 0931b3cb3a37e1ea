"""99th percentile of latency over the requests due in the window, from
when each fell due to when ``poll()`` handed its answer back."""
import numpy as np


def read(w):
    if w["latencies_s"] is None or not len(w["latencies_s"]):
        return None
    return float(np.percentile(w["latencies_s"], 99) * 1e3)
