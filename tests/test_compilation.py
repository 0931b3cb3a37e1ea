"""Compile-cache placement, compile counting from JAX's compile events, and
the kernels' interpret-mode rule."""
import jax
import jax.numpy as jnp
import pytest

from repro.common import compilation
from repro.common.compilation import CompileCounter, compile_cache_dir
from repro.kernels import dispatch


def test_cache_dir_is_the_env_var_when_set(monkeypatch, tmp_path):
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    assert compile_cache_dir() == str(tmp_path)


def test_cache_dir_defaults_to_a_fixed_path_in_the_checkout(monkeypatch):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    path = compile_cache_dir()
    assert path == str(compilation.CHECKOUT / ".jax_cache")
    assert (compilation.CHECKOUT / "src" / "repro").is_dir()
    assert path == compile_cache_dir()            # no pid, time or temp dir


def test_counter_counts_cache_misses_only():
    f = jax.jit(lambda x: jnp.sin(x) * 3.0)
    x = jnp.ones((7, 5))
    with CompileCounter() as cold:
        f(x).block_until_ready()
    with CompileCounter() as warm:
        f(x).block_until_ready()
    with CompileCounter() as new_shape:
        f(jnp.ones((3,))).block_until_ready()
    assert cold.by_name["jit(<lambda>)"] == 1
    assert warm.count == 0
    assert new_shape.by_name["jit(<lambda>)"] == 1


@pytest.mark.parametrize("backend,interpret", [("cpu", True), ("tpu", False)])
def test_interpret_only_on_cpu(monkeypatch, backend, interpret):
    monkeypatch.setattr(jax, "default_backend", lambda: backend)
    assert dispatch.interpret_mode() is interpret


def test_other_backends_refuse_to_interpret(monkeypatch):
    monkeypatch.setattr(jax, "default_backend", lambda: "gpu")
    with pytest.raises(RuntimeError, match="gpu"):
        dispatch.interpret_mode()
