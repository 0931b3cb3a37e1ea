"""Operations and bytes of one application of the ALBERT encoder layer with
its off-ramp, computed from shapes.

Operations count the matrix products only (2 per multiply-add): the Q, K,
V and output projections, the attention scores and their weighted sum,
the two MLP matrices at the share of their 128x128 tiles that pruning
kept, and the off-ramp's pooler and classifier on token 0.  Elementwise
work (softmax, LayerNorm, GELU) is left out, so the count is a lower
bound on the work.

Bytes are the least traffic to and from device memory for one fused step
over all lanes: every weight the step reads, once (pruned MLP tiles are
never read), plus the hidden state read and written.  Intermediates are
assumed to stay on chip, so this too is a lower bound, and a roofline
share built from these counts cannot pass 100% unless the time is short.
"""
from __future__ import annotations

from dataclasses import dataclass

F32 = 4


@dataclass(frozen=True)
class Layer:
    d_model: int
    d_ff: int
    classes: int
    kept_up: float = 1.0      # share of w_up's tiles kept by pruning
    kept_down: float = 1.0    # share of w_down's tiles kept by pruning
    bytes_per_value: int = F32

    def flops(self, seq: int) -> float:
        """One lane, one layer plus its off-ramp, at ``seq`` positions."""
        d, f = self.d_model, self.d_ff
        proj = 4 * 2 * seq * d * d
        attn = 2 * 2 * seq * seq * d
        mlp = 2 * seq * d * f * (self.kept_up + self.kept_down)
        offramp = 2 * d * d + 2 * d * self.classes
        return float(proj + attn + mlp + offramp)

    def weight_bytes(self) -> float:
        d, f, c = self.d_model, self.d_ff, self.classes
        values = (4 * d * d + d * f * (self.kept_up + self.kept_down)
                  + d * d + d * c + 4 * d)          # + the two norms' scale, bias
        return float(values * self.bytes_per_value)

    def step(self, lanes: int, bucket: int) -> tuple[float, float]:
        """(operations, bytes) of one fused step: every lane at the padded
        bucket, since the step computes inactive lanes too."""
        flops = lanes * self.flops(bucket)
        state = 2 * lanes * bucket * self.d_model * self.bytes_per_value
        return flops, self.weight_bytes() + state


def least_seconds(flops: float, nbytes: float, peak_flops: float, peak_bw: float) -> float:
    """Roofline bound of one call: the larger of its compute and memory time."""
    return max(flops / peak_flops, nbytes / peak_bw)
