"""Unified lane scheduler: length-bucketed fixed shapes (one compile per
bucket), bucket padding parity, per-lane KV-length decode parity against
isolated single-request decoding, and the step()-clocked API: mid-flight
submit parity, EDF-beats-FIFO cross-bucket preemption, poll(), and run()
back-compat."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.base import get_smoke_config
from repro.core.early_exit import offramp_logits
from repro.core.entropy import entropy_from_logits
from repro.data.synthetic import SyntheticCLS
from repro.models.model import build_model
from repro.serving.engine import ClassifierServer, DecoderServer, Request
from repro.serving.scheduler import (
    EDFPolicy,
    FIFOPolicy,
    LaneScheduler,
    WeightedRoundRobinPolicy,
)


def _albert_model(threshold=0.6):
    cfg = get_smoke_config("albert_edgebert")
    cfg = dataclasses.replace(cfg, dtype="float32", remat_policy="none")
    cfg = cfg.with_edgebert(
        early_exit=dataclasses.replace(
            cfg.edgebert.early_exit, entropy_threshold=threshold
        )
    )
    model = build_model(cfg)
    params = model.init_params(jax.random.PRNGKey(0))
    return model, params, cfg


def _decoder_model():
    cfg = dataclasses.replace(
        get_smoke_config("deepseek_7b"), dtype="float32", remat_policy="none"
    )
    model = build_model(cfg)
    params = model.init_params(jax.random.PRNGKey(1))
    return model, params, cfg


class TestBucketAssignment:
    def test_smallest_fitting_bucket(self):
        class _E:  # minimal engine: bucket key = token length
            def bucket_key(self, req):
                return len(req.tokens)

        sched = LaneScheduler(2, _E(), buckets=(32, 64, 128))
        assert sched.bucket_for(10) == 32
        assert sched.bucket_for(32) == 32
        assert sched.bucket_for(33) == 64
        assert sched.bucket_for(128) == 128
        with pytest.raises(ValueError):
            sched.bucket_for(129)

    def test_exact_shape_buckets_when_unconfigured(self):
        class _E:
            def bucket_key(self, req):
                return len(req.tokens)

        sched = LaneScheduler(2, _E())          # buckets=None
        assert sched.bucket_for(17) == 17       # every length its own bucket


class TestBucketedCompileCount:
    def test_one_step_trace_per_bucket_not_per_length(self):
        """Five distinct request lengths over two buckets must compile the
        fused step exactly twice — the bucketed-engine regression."""
        model, params, cfg = _albert_model(threshold=0.5)
        data = SyntheticCLS(cfg.vocab_size, 32, 10, num_classes=3, seed=0)
        batch = data.batch(0)
        server = ClassifierServer(model, params, batch_lanes=3, buckets=(16, 32))
        lengths = [10, 13, 16, 24, 32]          # 3 -> bucket 16, 2 -> bucket 32
        for i, L in enumerate(lengths * 2):
            server.submit(Request(uid=i, tokens=batch["tokens"][i % 10][:L]))
        stats = server.run()
        assert stats["sentences"] == 10
        assert stats["step_traces"] == 2
        assert stats["step_traces_per_bucket"] == {16: 1, 32: 1}
        assert stats["embed_traces"] == 2       # one embed shape per bucket
        assert stats["buckets_used"] == 2

    def test_second_drain_same_buckets_no_retrace(self):
        model, params, cfg = _albert_model(threshold=0.6)
        data = SyntheticCLS(cfg.vocab_size, 32, 4, num_classes=3, seed=1)
        batch = data.batch(0)
        server = ClassifierServer(model, params, batch_lanes=2, buckets=(16, 32))
        for i, L in enumerate((12, 30, 16, 32)):
            server.submit(Request(uid=i, tokens=batch["tokens"][i][:L]))
        server.run()
        for i, L in enumerate((11, 29, 15, 31)):
            server.submit(Request(uid=4 + i, tokens=batch["tokens"][i][:L]))
        stats = server.run()
        assert stats["sentences"] == 8
        assert stats["step_traces"] == 2        # still one per bucket

    def test_padded_result_matches_native_length_reference(self):
        """Bucket padding must NOT change the computed function: a short
        sentence padded up to its bucket produces the same logits and exit
        layer as the straight-line reference at its NATIVE length (pad
        positions are masked out of attention via per-lane kv_len)."""
        thr = 0.5
        model, params, cfg = _albert_model(threshold=thr)
        data = SyntheticCLS(cfg.vocab_size, 32, 4, num_classes=3, seed=2)
        batch = data.batch(0)
        server = ClassifierServer(model, params, batch_lanes=2, buckets=(16,))
        for i in range(4):
            server.submit(Request(uid=i, tokens=batch["tokens"][i][:11]))
        server.run()
        for i in range(4):
            # reference: UNPADDED, exact 11-token shapes, no bucket, no mask
            h = model.embed(params, jnp.asarray(batch["tokens"][i][:11])[None])
            want_exit, want_lg = None, None
            for li in range(cfg.n_layers):
                span_z = model._span_for_layer(params, 0)
                h, _, _ = model._dense_layer_step(
                    params["layer"], h, causal=False, span_z=span_z
                )
                lg = offramp_logits(h, model._offramp(params))
                ent = float(entropy_from_logits(lg)[0])
                if ent < thr or li == cfg.n_layers - 1:
                    want_exit, want_lg = li + 1, np.asarray(lg[0])
                    break
            req = server.done[i]
            assert req.exit_layer == want_exit
            np.testing.assert_allclose(req.result, want_lg, atol=5e-2)
            assert np.argmax(req.result) == np.argmax(want_lg)


class TestSteppedAPI:
    def test_mid_drain_submit_parity_and_no_new_traces(self):
        """Submitting BETWEEN steps must produce the same per-request outputs
        as submitting everything up front, and must not add compiled traces
        (the step shapes are fixed per bucket)."""
        thr = 0.5
        model, params, cfg = _albert_model(threshold=thr)
        data = SyntheticCLS(cfg.vocab_size, 32, 8, num_classes=3, seed=4)
        batch = data.batch(0)
        lengths = [10, 30, 14, 28, 12, 26, 16, 32]

        # reference: everything submitted up front, drained with run()
        ref = ClassifierServer(model, params, batch_lanes=2, buckets=(16, 32))
        for i, L in enumerate(lengths):
            ref.submit(Request(uid=i, tokens=batch["tokens"][i][:L]))
        ref_stats = ref.run()

        # stepped: half up front, the rest injected mid-drain
        srv = ClassifierServer(model, params, batch_lanes=2, buckets=(16, 32))
        for i, L in enumerate(lengths[:4]):
            srv.submit(Request(uid=i, tokens=batch["tokens"][i][:L]))
        steps = 0
        while True:
            rep = srv.step()
            if rep is None:
                break
            steps += 1
            if steps == 2:
                for i, L in enumerate(lengths[4:], start=4):
                    srv.submit(Request(uid=i, tokens=batch["tokens"][i][:L]))
        stats = srv.telemetry()
        assert len(srv.done) == 8
        for i in range(8):
            assert srv.done[i].exit_layer == ref.done[i].exit_layer, i
            np.testing.assert_allclose(
                srv.done[i].result, ref.done[i].result, atol=1e-5
            )
        # no extra compiles vs the up-front drain: one step trace per bucket
        assert stats["step_traces_per_bucket"] == ref_stats["step_traces_per_bucket"]
        assert stats["step_traces"] == 2

    def test_poll_returns_each_completion_exactly_once(self):
        model, params, cfg = _albert_model(threshold=0.5)
        data = SyntheticCLS(cfg.vocab_size, 32, 6, num_classes=3, seed=5)
        batch = data.batch(0)
        srv = ClassifierServer(model, params, batch_lanes=2, buckets=(32,))
        for i in range(6):
            srv.submit(Request(uid=i, tokens=batch["tokens"][i]))
        polled = []
        while srv.step() is not None:
            polled.extend(r.uid for r in srv.poll())
        polled.extend(r.uid for r in srv.poll())
        assert sorted(polled) == list(range(6))   # each exactly once
        assert srv.poll() == []                    # drained

    def test_run_is_equivalent_to_step_loop(self):
        """run() is a thin `while work: step()` wrapper — same completions,
        same telemetry counters as driving step() by hand."""
        model, params, cfg = _albert_model(threshold=0.5)
        data = SyntheticCLS(cfg.vocab_size, 32, 6, num_classes=3, seed=6)
        batch = data.batch(0)
        a = ClassifierServer(model, params, batch_lanes=2, buckets=(16, 32))
        b = ClassifierServer(model, params, batch_lanes=2, buckets=(16, 32))
        for i in range(6):
            L = 12 if i % 2 else 30
            a.submit(Request(uid=i, tokens=batch["tokens"][i][:L]))
            b.submit(Request(uid=i, tokens=batch["tokens"][i][:L]))
        st_a = a.run()
        while b.step() is not None:
            pass
        st_b = b.telemetry()
        assert len(a.done) == len(b.done) == 6
        for i in range(6):
            assert a.done[i].exit_layer == b.done[i].exit_layer
        for k in ("sentences", "dense_steps", "layer_calls", "step_traces",
                  "bucket_steps", "lane_occupancy"):
            assert st_a[k] == st_b[k], k

    def test_queue_delay_telemetry(self):
        """arrival_step -> first_compute_step -> retire_step stamps and the
        p50/p95 queue-delay telemetry: more requests than lanes means later
        requests provably wait in queue."""
        model, params, cfg = _albert_model(threshold=0.5)
        data = SyntheticCLS(cfg.vocab_size, 32, 8, num_classes=3, seed=7)
        batch = data.batch(0)
        srv = ClassifierServer(model, params, batch_lanes=2, buckets=(32,))
        for i in range(8):
            srv.submit(Request(uid=i, tokens=batch["tokens"][i]))
        st = srv.run()
        for r in srv.done.values():
            assert r.arrival_step == 0
            assert r.first_compute_step is not None and r.retire_step is not None
            assert r.first_compute_step >= r.arrival_step
            assert r.retire_step >= r.first_compute_step
        delays = [r.first_compute_step - r.arrival_step for r in srv.done.values()]
        assert max(delays) > 0                 # someone actually queued
        assert (
            st["queue_delay_steps_p99"]
            >= st["queue_delay_steps_p95"]
            >= st["queue_delay_steps_p50"]
            >= 0.0
        )
        assert st["queue_delay_steps_max"] == max(delays)
        assert st["queue_delay_steps_p99"] <= st["queue_delay_steps_max"]


class TestCrossBucketPolicies:
    def _mk(self, policy):
        model, params, cfg = _albert_model(threshold=1e-9)  # never early-exit
        data = SyntheticCLS(cfg.vocab_size, 32, 8, num_classes=3, seed=8)
        batch = data.batch(0)
        srv = ClassifierServer(
            model, params, batch_lanes=2, buckets=(16, 32), policy=policy
        )
        return srv, batch, cfg

    def test_edf_short_deadline_preempts_deep_drain(self):
        """The acceptance property: a short-deadline 16-token request
        submitted DURING a deep 32-token drain retires before the drain
        completes under EDF, and the drain's results are unaffected."""
        srv, batch, cfg = self._mk(EDFPolicy())
        for i in range(4):                      # deep drain: full-depth, no SLO
            srv.submit(Request(uid=i, tokens=batch["tokens"][i][:32]))
        srv.step()
        srv.step()
        # tight-but-feasible SLO: needs n_layers steps, deadline has headroom
        srv.submit(Request(
            uid=99, tokens=batch["tokens"][4][:12],
            deadline_s=float(cfg.n_layers + 2),
        ))
        while srv.step() is not None:
            pass
        short = srv.done[99]
        drain_last = max(srv.done[i].retire_step for i in range(4))
        assert short.retire_step < drain_last, (
            "EDF must retire the short-deadline request before the deep "
            "drain finishes"
        )
        assert short.exit_layer == cfg.n_layers       # threshold ~0: full depth
        st = srv.telemetry()
        assert st["step_traces"] == 2                 # interleaving: no retrace

    def test_fifo_finishes_deep_drain_first(self):
        """The FIFO baseline the EDF property beats: same workload, but the
        late short request waits until the earlier-submitted drain is done."""
        srv, batch, cfg = self._mk(FIFOPolicy())
        for i in range(4):
            srv.submit(Request(uid=i, tokens=batch["tokens"][i][:32]))
        srv.step()
        srv.step()
        srv.submit(Request(
            uid=99, tokens=batch["tokens"][4][:12],
            deadline_s=float(cfg.n_layers + 2),
        ))
        while srv.step() is not None:
            pass
        drain_last = max(srv.done[i].retire_step for i in range(4))
        assert srv.done[99].retire_step > drain_last

    def test_explicit_slo_jumps_queue_inside_its_own_bucket(self):
        """An explicit-SLO request queued BEHIND deadline-free work in the
        SAME bucket must be admitted at the next free lane, not after the
        whole FIFO backlog (intra-bucket priority, not just cross-bucket)."""
        srv, batch, cfg = self._mk(EDFPolicy())
        for i in range(6):                      # backlog: one bucket, no SLOs
            srv.submit(Request(uid=i, tokens=batch["tokens"][i][:12]))
        srv.step()                              # lanes now hold uid 0 and 1
        srv.submit(Request(
            uid=77, tokens=batch["tokens"][6][:12],
            deadline_s=float(cfg.n_layers + 2),
        ))
        while srv.step() is not None:
            pass
        # admitted at the FIRST refill after submission: only the two
        # in-flight requests may retire before it
        assert srv.done[77].first_compute_step <= srv.done[77].arrival_step + cfg.n_layers
        before = [u for u in range(6) if srv.done[u].retire_step < srv.done[77].retire_step]
        assert len(before) <= 2, before

    def test_wrr_time_slices_both_buckets(self):
        """Weighted round robin: with no deadlines anywhere, both buckets
        advance in alternation instead of one draining to completion first."""
        srv, batch, cfg = self._mk(WeightedRoundRobinPolicy())
        for i in range(2):
            srv.submit(Request(uid=i, tokens=batch["tokens"][i][:32]))
        for i in range(2, 4):
            srv.submit(Request(uid=i, tokens=batch["tokens"][i][:12]))
        buckets_seen = []
        for _ in range(4):
            buckets_seen.append(srv.step().bucket)
        assert set(buckets_seen) == {16, 32}, buckets_seen
        while srv.step() is not None:
            pass
        assert len(srv.done) == 4


class TestPerLaneKVDecode:
    def _reference_decode(self, model, params, prompt, max_new, max_seq):
        """Isolated single-request greedy decode — the ground truth a lane
        must reproduce regardless of what its neighbours are doing."""
        cache = model.init_cache(1, max_seq)
        for t in range(len(prompt) - 1):
            _, cache = model.decode_step(
                params, cache, jnp.asarray([[int(prompt[t])]]), t
            )
        pos = len(prompt) - 1
        cur = int(prompt[-1])
        outs = []
        for _ in range(max_new):
            lg, cache = model.decode_step(params, cache, jnp.asarray([[cur]]), pos)
            cur = int(jnp.argmax(lg[0, -1]))
            outs.append(cur)
            pos += 1
        return outs

    def test_staggered_lengths_with_refill_match_isolated(self):
        """Prompts of different lengths + a mid-drain refill: every lane must
        decode from its OWN position.  The old lock-step loop stepped refilled
        lanes at the max active position (burning pad positions and attending
        a zero gap) and cannot pass this."""
        model, params, cfg = _decoder_model()
        rng = np.random.default_rng(0)
        prompts = [
            rng.integers(4, cfg.vocab_size, size=L).astype(np.int32)
            for L in (6, 9, 4, 7, 5)
        ]
        server = DecoderServer(model, params, batch_lanes=2, max_seq=32, eos_id=-1)
        for i, p in enumerate(prompts):
            server.submit(Request(uid=i, tokens=p, max_new_tokens=4))
        stats = server.run()
        assert stats["completed"] == 5
        assert stats["decode_traces"] == 1 and stats["prefill_traces"] == 1
        for i, p in enumerate(prompts):
            want = self._reference_decode(model, params, p, 4, 32)
            assert server.done[i].generated == want, i

    def test_bucketed_caches_one_trace_per_bucket(self):
        model, params, cfg = _decoder_model()
        rng = np.random.default_rng(1)
        # needs (len + max_new + 1): 4+3+1=8 -> bucket 8; 10+3+1=14 -> bucket 16
        prompts = [rng.integers(4, cfg.vocab_size, size=L).astype(np.int32)
                   for L in (4, 10, 4, 10)]
        server = DecoderServer(
            model, params, batch_lanes=2, max_seq=64, eos_id=-1, buckets=(8, 16)
        )
        for i, p in enumerate(prompts):
            server.submit(Request(uid=i, tokens=p, max_new_tokens=3))
        stats = server.run()
        assert stats["completed"] == 4
        assert stats["buckets_used"] == 2
        assert stats["decode_traces"] == 2      # one per cache bucket
        assert stats["decode_traces_per_bucket"] == {8: 1, 16: 1}
        for i, p in enumerate(prompts):
            bucket = 8 if len(p) == 4 else 16
            want = self._reference_decode(model, params, p, 3, bucket)
            assert server.done[i].generated == want, i

    def test_lane_occupancy_beats_lockstep_accounting(self):
        """Per-lane positions mean decode steps track the LONGEST remaining
        lane, not a global max position; total steps equal the work of the
        slowest chain under continuation batching."""
        model, params, cfg = _decoder_model()
        rng = np.random.default_rng(2)
        prompts = [rng.integers(4, cfg.vocab_size, size=L).astype(np.int32)
                   for L in (5, 5, 5, 5)]
        server = DecoderServer(model, params, batch_lanes=2, max_seq=32, eos_id=-1)
        for i, p in enumerate(prompts):
            server.submit(Request(uid=i, tokens=p, max_new_tokens=3))
        stats = server.run()
        # 4 requests x 3 tokens over 2 lanes = 12 lane-steps in 6 fused steps
        assert stats["decode_steps"] == 6
        assert stats["lane_occupancy"] == 1.0


class _NullEngine:
    """Minimal host-only engine: every request retires after ``steps_per_req``
    fused steps — lets the scheduler churn 10k requests in milliseconds."""

    def __init__(self, steps_per_req=1):
        self.steps_per_req = steps_per_req

    def bucket_key(self, req):
        return len(req.tokens)

    def bucket_begin(self, bucket):
        pass

    def lane_load(self, bucket, lane, req):
        pass

    def lanes_step(self, bucket, active):
        return None

    def lane_advance(self, bucket, lane, req, out, depth):
        return depth >= self.steps_per_req

    def lane_finish(self, bucket, lane, req, depth):
        pass

    def bucket_end(self, bucket):
        pass


class TestPolicyContract:
    def test_policy_without_a_choice_fails_loudly(self):
        """A policy that picks no bucket while work is queued trips the
        step's assertion; it never passes for "no work left", which would
        end a ``while step() is not None`` drain with requests stranded."""

        class _NoChoice:
            def choose(self, views, now_s):
                return None

        sched = LaneScheduler(2, _NullEngine(), buckets=(8,), policy=_NoChoice())
        assert sched.step() is None          # nothing queued: no work left
        sched.submit(Request(uid=0, tokens=np.zeros(4, np.int32)))
        with pytest.raises(AssertionError, match="policy chose bucket None"):
            sched.step()


class TestRetiredRequestRetention:
    """ROADMAP retention item: a long-running submit/step/poll server must
    not accumulate every retired Request forever — poll() releases payloads
    (unless pinned) and telemetry folds incrementally."""

    def test_poll_drops_payloads_unless_pinned(self):
        sched = LaneScheduler(2, _NullEngine(), buckets=(8,))
        for i in range(4):
            sched.submit(Request(uid=i, tokens=np.zeros(4, np.int32)))
        while sched.step() is not None:
            pass
        assert len(sched.done) == 4          # nothing polled yet: all resident
        got = sched.poll(pin=True)
        assert len(got) == 4 and len(sched.done) == 4   # pinned: kept
        for i in range(4, 8):
            sched.submit(Request(uid=i, tokens=np.zeros(4, np.int32)))
        while sched.step() is not None:
            pass
        got = sched.poll()                   # default: payloads released
        assert sorted(r.uid for r in got) == [4, 5, 6, 7]
        assert sorted(sched.done) == [0, 1, 2, 3]

    def test_ten_thousand_request_drain_stays_bounded(self):
        """The acceptance drain: 10k requests through submit/step/poll keep
        ``done`` at O(outstanding) and the queue-delay reservoir at O(cap) —
        while the lifetime telemetry still counts every retiree."""
        lanes, wave = 4, 100
        sched = LaneScheduler(lanes, _NullEngine(), buckets=(8,))
        total, max_done = 10_000, 0
        uid = 0
        for _ in range(total // wave):
            for _ in range(wave):
                sched.submit(Request(uid=uid, tokens=np.zeros(4, np.int32)))
                uid += 1
            while sched.step() is not None:
                sched.poll()
                max_done = max(max_done, len(sched.done))
            sched.poll()
        # retired-but-unpolled work is bounded by one wave, nowhere near 10k
        assert max_done <= wave
        assert len(sched.done) == 0
        st = sched.telemetry()
        assert st["sentences"] == total      # accounting survived every drop
        assert len(sched._delays.buf) <= sched._delays.cap
        assert (
            st["queue_delay_steps_p99"]
            >= st["queue_delay_steps_p95"]
            >= st["queue_delay_steps_p50"]
            >= 0.0
        )

    def test_incremental_delay_stats_match_rescan_semantics(self):
        """Below the reservoir cap the incremental percentiles are EXACT —
        identical to rescanning the retirees like the old telemetry did."""
        sched = LaneScheduler(2, _NullEngine(), buckets=(8,))
        for i in range(12):
            sched.submit(Request(uid=i, tokens=np.zeros(4, np.int32)))
        delays = []
        while sched.step() is not None:
            for r in sched.poll():
                delays.append(r.first_compute_step - r.arrival_step)
        for r in sched.poll():
            delays.append(r.first_compute_step - r.arrival_step)
        st = sched.telemetry()
        assert st["queue_delay_steps_p50"] == float(np.percentile(delays, 50))
        assert st["queue_delay_steps_p95"] == float(np.percentile(delays, 95))
        assert st["queue_delay_steps_p99"] == float(np.percentile(delays, 99))
        assert st["queue_delay_steps_max"] == float(max(delays))

    def test_slo_miss_counter_survives_poll_drop(self):
        """accepted_slo_misses is folded at retirement: dropping payloads
        via poll() must not erase recorded misses."""
        sched = LaneScheduler(1, _NullEngine(steps_per_req=3), buckets=(8,))
        sched.submit(Request(
            uid=0, tokens=np.zeros(4, np.int32), deadline_s=0.5
        ))                                   # 3 steps at 1.0s/step: missed
        sched.submit(Request(
            uid=1, tokens=np.zeros(4, np.int32), deadline_s=100.0
        ))                                   # met
        while sched.step() is not None:
            pass
        assert sched.telemetry()["accepted_slo_misses"] == 1
        sched.poll()
        assert len(sched.done) == 0
        assert sched.telemetry()["accepted_slo_misses"] == 1
