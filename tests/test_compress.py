"""int8 error-feedback gradient compression: unbiasedness via error feedback +
convergence parity on a toy problem (single-device axis: psum is identity,
which still exercises quantize/dequantize + EF accumulation)."""
import jax
import jax.numpy as jnp
import numpy as np

from repro.common.jax_compat import make_auto_mesh
from repro.training.compress import EFState, compressed_psum, ef_init


def _dp_mesh():
    return make_auto_mesh((1,), ("dp",))


def test_error_feedback_accumulates():
    g = {"w": jnp.array([0.001, 1.0, -0.3])}
    ef = ef_init(g)

    def run(g, ef):
        return jax.shard_map(
            lambda gg: compressed_psum(gg, ef, "dp", 1),
            mesh=_dp_mesh(),
            in_specs=(jax.sharding.PartitionSpec(),),
            out_specs=(jax.sharding.PartitionSpec(), jax.sharding.PartitionSpec()),
        )(g)

    out, ef2 = run(g, ef)
    # quantization error captured in residual: g == out + residual
    np.testing.assert_allclose(
        np.asarray(g["w"]),
        np.asarray(out["w"]) + np.asarray(ef2.residual["w"]),
        atol=1e-6,
    )


def test_convergence_parity():
    """SGD with compressed grads converges to the same optimum (EF theory)."""
    target = jnp.array([0.5, -1.5, 2.0, 0.01])

    def loss(w):
        return 0.5 * jnp.sum((w - target) ** 2)

    mesh = _dp_mesh()
    P = jax.sharding.PartitionSpec

    w_plain = jnp.zeros(4)
    w_comp = jnp.zeros(4)
    ef = ef_init({"w": w_comp})
    lr = 0.2
    for _ in range(80):
        g_plain = jax.grad(loss)(w_plain)
        w_plain = w_plain - lr * g_plain

        g = {"w": jax.grad(loss)(w_comp)}
        out, ef = jax.shard_map(
            lambda gg: compressed_psum(gg, ef, "dp", 1),
            mesh=mesh, in_specs=(P(),), out_specs=(P(), P()),
        )(g)
        w_comp = w_comp - lr * out["w"]

    np.testing.assert_allclose(np.asarray(w_comp), np.asarray(target), atol=1e-2)
    np.testing.assert_allclose(np.asarray(w_comp), np.asarray(w_plain), atol=1e-2)


def test_wire_payload_is_int8():
    """The all-reduced payload is the int8 code (4x compression vs fp32)."""
    g = {"w": jnp.linspace(-3, 3, 101)}
    ef = ef_init(g)

    def fake(gg):
        out, ef2 = compressed_psum(gg, ef, "dp", 1)
        return out, ef2

    jaxpr = jax.make_jaxpr(
        lambda gg: jax.shard_map(
            fake,
            mesh=_dp_mesh(),
            in_specs=(jax.sharding.PartitionSpec(),),
            out_specs=(jax.sharding.PartitionSpec(), jax.sharding.PartitionSpec()),
        )(gg)
    )(g)
    txt = str(jaxpr)
    assert "convert_element_type[new_dtype=int8" in txt
