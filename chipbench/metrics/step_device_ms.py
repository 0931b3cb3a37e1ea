"""Device time of one fused step (the ``jit(step_fn)`` program's events
in the trace), averaged over the window's steps and the chips."""


def read(w):
    t = w["trace"]
    if not t or not t["steps"]:
        return None
    return 1e3 * t["step_device_s"] / t["steps"]
