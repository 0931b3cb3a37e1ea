"""The fused step's share of its roofline: the least time of the window's
steps (each the larger of its operations over the bf16 peak and its bytes
over the memory bandwidth, from ``chipbench/flops.py``) over their
device time."""


def read(w):
    t = w["trace"]
    if not t or not t["step_device_s"]:
        return None
    return 100.0 * t["least_s"] / t["step_device_s"]
