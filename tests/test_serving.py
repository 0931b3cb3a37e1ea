"""Serving engine: early-exit classification with lane recycling; LM decode;
multi-task shared-embedding routing."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.base import get_smoke_config
from repro.data.synthetic import SyntheticCLS, SyntheticLM
from repro.models.model import build_model
from repro.serving.engine import ClassifierServer, DecoderServer, MultiTaskRouter, Request


def _albert_model(threshold=0.6):
    cfg = get_smoke_config("albert_edgebert")
    cfg = dataclasses.replace(cfg, dtype="float32", remat_policy="none")
    cfg = cfg.with_edgebert(
        early_exit=dataclasses.replace(cfg.edgebert.early_exit, entropy_threshold=threshold)
    )
    model = build_model(cfg)
    params = model.init_params(jax.random.PRNGKey(0))
    return model, params, cfg


class TestClassifierServer:
    def test_results_match_direct_forward(self):
        model, params, cfg = _albert_model(threshold=0.5)
        data = SyntheticCLS(cfg.vocab_size, 32, 8, num_classes=3, seed=0)
        batch = data.batch(0)
        server = ClassifierServer(model, params, batch_lanes=3)
        for i in range(8):
            server.submit(Request(uid=i, tokens=batch["tokens"][i]))
        stats = server.run()
        assert stats["sentences"] == 8
        # compare against the dense all-layers forward for each sentence
        out = model.apply_train(params, {"tokens": jnp.asarray(batch["tokens"])})
        for i in range(8):
            req = server.done[i]
            assert req.exit_layer == int(out.exit_layer[i])
            want = np.asarray(out.all_cls_logits[req.exit_layer - 1, i])
            # lanes run with different batch shapes than the dense pass ->
            # different XLA:CPU vectorization/reassociation; small fp drift
            # compounds through LN+tanh layers. Decisions must agree exactly;
            # logits agree to ~1e-2.
            assert np.argmax(req.result) == np.argmax(want)
            np.testing.assert_allclose(req.result, want, atol=5e-2)

    def test_padding_rows_do_not_move_actquant_bias(self):
        """A lane right-padded to its bucket must quantize its activations
        on the grid of its own tokens: the AdaptivFloat bias comes from the
        first ``kv_len`` rows, not from the padding."""
        model, params, cfg = _albert_model()
        assert cfg.edgebert.quant.enabled and cfg.edgebert.quant.quantize_activations
        L, S, D = 10, 16, cfg.d_model
        h = jax.random.normal(jax.random.PRNGKey(3), (1, L, D))
        padded = jnp.concatenate([h, jnp.full((1, S - L, D), 100.0)], axis=1)
        want = model._maybe_actquant(h)
        assert not np.array_equal(                   # padding would move it
            np.asarray(model._maybe_actquant(padded)[:, :L]), np.asarray(want)
        )
        for use_pallas in (False, True):
            got = model._maybe_actquant(padded, use_pallas=use_pallas, kv_len=L)
            np.testing.assert_array_equal(np.asarray(got[:, :L]), np.asarray(want))

    def test_layer_calls_reflect_early_exit(self):
        """Continuation batching: total layer computations ~ sum(exit layers),
        NOT n_sentences * n_layers — the throughput form of Fig. 4 savings."""
        model, params, cfg = _albert_model(threshold=10.0)  # exit immediately
        data = SyntheticCLS(cfg.vocab_size, 32, 6, num_classes=3, seed=1)
        batch = data.batch(0)
        server = ClassifierServer(model, params, batch_lanes=2)
        for i in range(6):
            server.submit(Request(uid=i, tokens=batch["tokens"][i]))
        stats = server.run()
        assert stats["avg_exit_layer"] == 1.0
        assert stats["layer_calls"] == 6  # one layer per sentence
        assert stats["runtime_savings"] == pytest.approx(1 - 1 / cfg.n_layers)


class TestDecoderServer:
    def test_completes_requests(self):
        cfg = dataclasses.replace(
            get_smoke_config("deepseek_7b"), dtype="float32", remat_policy="none"
        )
        model = build_model(cfg)
        params = model.init_params(jax.random.PRNGKey(1))
        data = SyntheticLM(cfg.vocab_size, 16, 4, seed=0)
        batch = data.batch(0)
        server = DecoderServer(model, params, batch_lanes=2, max_seq=48, eos_id=-1)
        for i in range(3):
            server.submit(Request(uid=i, tokens=batch["tokens"][i][:8], max_new_tokens=4))
        stats = server.run()
        assert stats["completed"] == 3
        assert all(len(server.done[i].generated) == 4 for i in range(3))


class TestMultiTask:
    def test_shared_embeddings_single_copy(self):
        model, params, cfg = _albert_model()
        # two "tasks" share embeddings, differ in encoder/classifier
        p2 = build_model(cfg).init_params(jax.random.PRNGKey(2))
        router = MultiTaskRouter(
            model,
            shared_embed=params["embed"],
            task_params={"mnli": params, "qqp": p2},
        )
        # both servers point at the SAME embedding object (eNVM residency)
        assert router.tasks["mnli"].params["embed"] is router.tasks["qqp"].params["embed"]
        data = SyntheticCLS(cfg.vocab_size, 32, 4, num_classes=3, seed=3)
        b = data.batch(0)
        router.submit("mnli", Request(uid=0, tokens=b["tokens"][0]))
        router.submit("qqp", Request(uid=1, tokens=b["tokens"][1]))
        out = router.run_all()
        assert set(out) == {"mnli", "qqp"}
        assert router.embed_reloads == 1  # never reloaded on switch
