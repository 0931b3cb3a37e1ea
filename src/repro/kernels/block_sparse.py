"""Block-sparse matmul Pallas kernel (paper §V-C bitmask + zero-skip, TPU-adapted).

The paper's PU skips VMAC products when an operand vector is all-zero, with
bitmask-encoded storage.  The MXU has no element-granular skip, so the TPU
adaptation prunes at (bk x bn) tile granularity (PruneConfig.block_size) and
skips *whole tiles*: a CSR-of-blocks index list (one list of occupied k-blocks
per n-block, built host-side from the static pruning mask) drives the kernel's
k-loop via scalar-prefetch indirection, so pruned tiles are never DMA'd from
HBM and never touch the MXU — compute AND memory traffic scale with density.
"""
from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def build_block_index(block_mask: np.ndarray) -> Tuple[np.ndarray, np.ndarray, int]:
    """CSR-of-blocks: for each n-block, the occupied k-block indices.

    Returns (indices [Nb, max_nnz] int32, counts [Nb] int32, max_nnz).
    Padded entries repeat the last valid index (clamped DMA, masked compute).
    """
    block_mask = np.asarray(block_mask, bool)
    Kb, Nb = block_mask.shape
    counts = block_mask.sum(axis=0).astype(np.int32)
    max_nnz = max(int(counts.max()) if counts.size else 0, 1)
    indices = np.zeros((Nb, max_nnz), np.int32)
    for j in range(Nb):
        ks = np.nonzero(block_mask[:, j])[0]
        if len(ks):
            indices[j, : len(ks)] = ks
            indices[j, len(ks) :] = ks[-1]
    return indices, counts, max_nnz


def _bs_kernel(idx_ref, cnt_ref, x_ref, w_ref, o_ref, acc_ref, *, n_s: int):
    j = pl.program_id(1)
    s = pl.program_id(2)

    @pl.when(s == 0)
    def _zero():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    @pl.when(s < cnt_ref[j])
    def _accum():
        x = x_ref[...].astype(jnp.float32)
        w = w_ref[...].astype(jnp.float32)
        acc_ref[...] += jnp.dot(x, w, preferred_element_type=jnp.float32)

    @pl.when(s == n_s - 1)
    def _emit():
        o_ref[...] = acc_ref[...].astype(o_ref.dtype)


def block_sparse_matmul(
    x: jnp.ndarray,              # [M, K]
    w: jnp.ndarray,              # [K, N] (zeros outside occupied blocks)
    block_mask: np.ndarray,      # STATIC [K//bk, N//bn] occupancy
    *,
    bm: int = 128,
    bk: int = 128,
    bn: int = 128,
    interpret: bool,
    name: Optional[str] = None,   # the kernel's name in compiled HLO and traces
) -> jnp.ndarray:
    M, K = x.shape
    K2, N = w.shape
    assert K == K2 and K % bk == 0 and N % bn == 0, (K, N, bk, bn)
    bm_ = min(bm, M)
    pm = (-M) % bm_
    if pm:
        x = jnp.pad(x, ((0, pm), (0, 0)))
    Mp = x.shape[0]

    indices, counts, max_nnz = build_block_index(block_mask)

    grid = (Mp // bm_, N // bn, max_nnz)
    kernel = functools.partial(_bs_kernel, n_s=max_nnz)
    out = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=grid,
            in_specs=[
                pl.BlockSpec((bm_, bk), lambda i, j, s, idx, cnt: (i, idx[j, s])),
                pl.BlockSpec((bk, bn), lambda i, j, s, idx, cnt: (idx[j, s], j)),
            ],
            out_specs=pl.BlockSpec((bm_, bn), lambda i, j, s, idx, cnt: (i, j)),
            scratch_shapes=[pltpu.VMEM((bm_, bn), jnp.float32)],
        ),
        out_shape=jax.ShapeDtypeStruct((Mp, N), jnp.float32),
        interpret=interpret,
        name=name,
    )(jnp.asarray(indices), jnp.asarray(counts), x, w)
    return out[:M]
