"""Model operations of the requests answered in the traced window (each
at its own length and exit depth) over the device time of the window's
fused steps and the bf16 peak: the whole step's share of the chip while it
steps."""


def read(w):
    t = w["trace"]
    if not t or not t["step_device_s"]:
        return None
    peak = w["peaks"]["bf16_flops_per_s"] * w["chips"]
    return 100.0 * w["answer_flops"] / (t["step_device_s"] * peak)
