"""Model operations of the requests answered in the window (each at its
own length and exit depth, pruned MLP at its kept tiles) over the window
and the chips' bf16 peak."""


def read(w):
    if not w["completed"]:
        return None
    peak = w["peaks"]["bf16_flops_per_s"] * w["chips"]
    return 100.0 * w["answer_flops"] / (w["seconds"] * peak)
