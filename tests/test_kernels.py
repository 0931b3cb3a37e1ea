"""Per-kernel allclose sweeps vs the ref.py oracles (interpret mode on CPU;
``tests/test_tpu_compile.py`` compiles the same kernels for a v5e).

Every Pallas kernel is swept over shapes (incl. non-multiples forcing padding)
and dtypes; hypothesis drives the AdaptivFloat property sweep.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis_compat import given, st

from repro.core.adaptivfloat import AFFormat, af_encode
from repro.kernels import ref
from repro.kernels.adaptivfloat_k import af_matmul, quantize
from repro.kernels.block_sparse import block_sparse_matmul, build_block_index
from repro.kernels.layernorm import layernorm
from repro.kernels.softmax_entropy import softmax_entropy
from repro.kernels.span_attention import span_attention
from repro.kernels import ops


def _r(shape, seed=0, dtype=jnp.float32, scale=1.0):
    return (jax.random.normal(jax.random.PRNGKey(seed), shape) * scale).astype(dtype)


class TestLayerNorm:
    @pytest.mark.parametrize("rows,d", [(4, 8), (100, 128), (257, 96), (1, 512)])
    @pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
    def test_matches_ref(self, rows, d, dtype):
        x = _r((rows, d), 1, dtype, 3.0)
        g, b = _r((d,), 2), _r((d,), 3)
        got = layernorm(x, g, b, block_rows=64, interpret=True)
        want = ref.layernorm(x, g, b)
        atol = 1e-5 if dtype == jnp.float32 else 0.05
        np.testing.assert_allclose(
            np.asarray(got, np.float32), np.asarray(want, np.float32), atol=atol
        )


class TestSoftmaxEntropy:
    @pytest.mark.parametrize("rows,n", [(3, 4), (100, 64), (130, 3)])
    def test_matches_ref(self, rows, n):
        x = _r((rows, n), 4, scale=5.0)
        mask = (jax.random.uniform(jax.random.PRNGKey(5), (rows, n)) > 0.3).astype(
            jnp.float32
        )
        p1, h1 = softmax_entropy(x, mask, block_rows=32, interpret=True)
        p2, h2 = ref.softmax_entropy(x, mask)
        np.testing.assert_allclose(np.asarray(p1), np.asarray(p2), atol=1e-6)
        np.testing.assert_allclose(np.asarray(h1), np.asarray(h2), atol=1e-6)

    def test_entropy_matches_core(self):
        from repro.core.entropy import entropy_from_logits

        x = _r((64, 16), 6, scale=8.0)
        _, h = softmax_entropy(x, jnp.ones_like(x), interpret=True)
        np.testing.assert_allclose(
            np.asarray(h), np.asarray(entropy_from_logits(x)), atol=1e-5
        )


class TestAFQuantKernel:
    @given(st.integers(5, 8), st.sampled_from([0.01, 1.0, 50.0]))
    def test_matches_ref(self, n_bits, scale):
        fmt = AFFormat(n_bits, 3)
        x = _r((100, 32), n_bits, scale=scale)
        got = quantize(x, fmt=fmt, block_rows=32, interpret=True)
        want = ref.adaptivfloat_quantize(x, fmt)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=0, atol=0)


class TestAFMatmul:
    @pytest.mark.parametrize("m,k,n", [(16, 32, 16), (70, 96, 50), (128, 128, 128)])
    def test_matches_ref(self, m, k, n):
        w = _r((k, n), 7, scale=2.0)
        codes, e_min = af_encode(w)
        x = _r((m, k), 8)
        got = af_matmul(x, codes, e_min, bm=32, bk=32, bn=32, interpret=True)
        want = ref.af_matmul(x, codes, e_min)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=2e-5, atol=2e-4)


class TestBlockSparse:
    @pytest.mark.parametrize("density", [0.0, 0.3, 1.0])
    def test_matches_ref(self, density):
        rng = np.random.default_rng(9)
        K, N, bk, bn = 128, 128, 32, 32
        bmask = rng.random((K // bk, N // bn)) < density
        full = np.repeat(np.repeat(bmask, bk, 0), bn, 1)
        w = jnp.asarray(rng.normal(size=(K, N)) * full, jnp.float32)
        x = _r((48, K), 10)
        got = block_sparse_matmul(x, w, bmask, bm=16, bk=bk, bn=bn,
                                  interpret=True)
        want = ref.block_sparse_matmul(x, w, jnp.asarray(bmask), bk, bn)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=2e-4)

    def test_index_list(self):
        bmask = np.array([[1, 0], [0, 0], [1, 1]], bool)
        idx, counts, mx = build_block_index(bmask)
        assert list(counts) == [2, 1] and mx == 2
        assert list(idx[0]) == [0, 2] and idx[1][0] == 2


class TestSpanAttention:
    @pytest.mark.parametrize("causal", [True, False])
    @pytest.mark.parametrize(
        "B,H,KV,S,dh,window", [(1, 2, 1, 64, 8, 16), (2, 4, 2, 100, 16, 37)]
    )
    def test_matches_ref(self, causal, B, H, KV, S, dh, window):
        q = _r((B, H, S, dh), 11)
        k = _r((B, KV, S, dh), 12)
        v = _r((B, KV, S, dh), 13)
        spans = jnp.asarray(
            np.random.default_rng(14).integers(1, window + 1, H), jnp.int32
        )
        want = ref.span_attention(q, k, v, spans, causal=causal)
        G = H // KV
        ke = jnp.repeat(k, G, axis=1).reshape(B * H, S, dh)
        ve = jnp.repeat(v, G, axis=1).reshape(B * H, S, dh)
        got = span_attention(
            q.reshape(B * H, S, dh), ke, ve, jnp.tile(spans, B), window,
            causal=causal, bq=32, bk=32, interpret=True,
        ).reshape(B, H, S, dh)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=2e-5)

    def test_ops_gathers_dead_heads(self):
        """Full deploy path with paper Table I QQP spans (8/12 heads off)."""
        B, S, H, dh = 2, 128, 12, 16
        q = _r((B, S, H, dh), 15)
        k = _r((B, S, H, dh), 16)
        v = _r((B, S, H, dh), 17)
        spans = [16, 0, 0, 0, 0, 0, 40, 75, 0, 0, 0, 2]
        got = ops.span_attention_op(q, k, v, spans, causal=False, bq=32, bk=32)
        want = ref.span_attention(
            q.transpose(0, 2, 1, 3), k.transpose(0, 2, 1, 3), v.transpose(0, 2, 1, 3),
            jnp.asarray(spans), causal=False,
        ).transpose(0, 2, 1, 3)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=2e-5)
        dead = [i for i, s in enumerate(spans) if s == 0]
        assert (np.asarray(got)[:, :, dead] == 0).all()

    def test_all_heads_off(self):
        B, S, H, dh = 1, 32, 4, 8
        q, k, v = _r((B, S, H, dh)), _r((B, S, H, dh)), _r((B, S, H, dh))
        out = ops.span_attention_op(q, k, v, [0, 0, 0, 0], causal=True)
        assert (np.asarray(out) == 0).all()

    def test_ops_traced_spans_under_jit(self):
        """Regression: ``span_attention_op`` used host-side numpy indexing on
        the span vector, so TRACED spans (e.g. learned spans flowing through
        a jit'd serving step) crashed at trace time.  Traced spans must now
        route through the kernel's scalar-prefetch operand and match the
        static-span result."""
        B, S, H, KV, dh = 2, 64, 4, 2, 8
        q, k, v = _r((B, S, H, dh), 20), _r((B, S, KV, dh), 21), _r((B, S, KV, dh), 22)
        spans = [9, 0, 33, 17]

        @jax.jit
        def f(q, k, v, sp):
            return ops.span_attention_op(q, k, v, sp, causal=True, bq=32, bk=32)

        got = f(q, k, v, jnp.asarray(spans, jnp.int32))   # spans TRACED
        want = ops.span_attention_op(q, k, v, spans, causal=True, bq=32, bk=32)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=2e-5)

    @pytest.mark.parametrize("causal", [True, False])
    def test_kv_lens_masks_padded_keys(self, causal):
        """Per-row kv_len (bucket padding) must compute the SAME function as
        physically truncating the key/value rows — incl. under jit(vmap) with
        a traced per-row length, the shape the serving lane vmap produces."""
        BH, S, dh, window = 4, 64, 8, 64
        q, k, v = _r((BH, S, dh), 23), _r((BH, S, dh), 24), _r((BH, S, dh), 25)
        spans = jnp.full((BH,), window, jnp.int32)
        kvl = 23
        got = span_attention(q, k, v, spans, window, causal=causal, bq=32,
                             bk=32, interpret=True,
                             kv_lens=jnp.full((BH,), kvl, jnp.int32))
        # oracle: the first kvl query rows of the padded run must equal a run
        # on the physically truncated arrays (rows past kvl are padding)
        want = ref.span_attention(
            q[:, None, :kvl], k[:, None, :kvl], v[:, None, :kvl],
            jnp.asarray([window]), causal=causal,
        )[:, 0]
        np.testing.assert_allclose(
            np.asarray(got)[:, :kvl], np.asarray(want), atol=2e-5
        )

        @jax.jit
        def lane_step(q, k, v, lens):
            def one(ql, kl, vl, n):
                return span_attention(
                    ql[None], kl[None], vl[None],
                    jnp.full((1,), window, jnp.int32), window,
                    causal=causal, bq=32, bk=32, interpret=True,
                    kv_lens=n[None],
                )[0]
            return jax.vmap(one)(q, k, v, lens)

        lens = jnp.asarray([23, 64, 1, 40], jnp.int32)   # per-lane, TRACED
        got_v = lane_step(q, k, v, lens)
        for i, n in enumerate([23, 64, 1, 40]):
            want = ref.span_attention(
                q[i : i + 1, None, :n], k[i : i + 1, None, :n],
                v[i : i + 1, None, :n], jnp.asarray([window]), causal=causal,
            )[0, 0]
            np.testing.assert_allclose(
                np.asarray(got_v)[i, :n], np.asarray(want), atol=2e-5,
                err_msg=str(i),
            )
