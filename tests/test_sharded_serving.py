"""Multi-device lane sharding: replica parity, placement routing, and
per-replica clock domains.

The tentpole guarantee is layered:

* IN-PROCESS (single real CPU device): a 1-replica ``shard_map`` drain must
  be BIT-IDENTICAL to the unsharded path for both engines — logits, exit
  depths, and every trace-count telemetry counter.  Plus pure units for the
  placement policies, the scheduler's replica-pinned refill, and the
  cross-arbiter lane-clock round-trip (checkpoint on replica A's arbiter,
  restore on replica B's, re-checkpoint: the frozen budget is unchanged).
* SUBPROCESS (forced host devices, ``multidevice`` marker, same idiom as
  test_dryrun_small.py): real 4-replica drains — classifier results
  bitwise-match an unsharded server with the same per-device lane count
  (lane math is embarrassingly parallel) and match the flat 8-lane server to
  f32 rounding (only the per-device batch shape differs), one step trace per
  (bucket, mesh), and a mid-flight
  preemption checkpointed on replica A restored on replica B reproducing the
  uninterrupted run exactly.
"""
import dataclasses
import os
import subprocess
import sys
import textwrap

import jax
import numpy as np
import pytest

from repro.common.jax_compat import make_auto_mesh
from repro.configs.base import get_smoke_config
from repro.data.synthetic import SyntheticCLS, SyntheticLM
from repro.models.model import build_model
from repro.serving.admission import (
    AdmissionController,
    DeadlinePackedPlacement,
    LeastLoadedPlacement,
    Quote,
)
from repro.serving.dvfs import (
    BatchedDVFSArbiter,
    LatencyAwareDVFSController,
    no_early_exit_baseline,
)
from repro.serving.engine import ClassifierServer, DecoderServer, Request
from repro.hwmodel.edgebert_accel import albert_layer_stats

SRC = os.path.join(os.path.dirname(__file__), "..", "src")


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


def _mesh1():
    return make_auto_mesh((1,), ("data",))


# ===========================================================================
# Acceptance bit: 1-replica shard_map == unsharded, bit for bit
# ===========================================================================


class TestOneReplicaParity:
    def test_classifier_sharded_r1_bit_identical(self):
        model, params, cfg = _albert_model(threshold=0.5)
        batch = SyntheticCLS(cfg.vocab_size, 32, 8, num_classes=3, seed=0).batch(0)
        ref = ClassifierServer(model, params, batch_lanes=2, buckets=(16, 32))
        shd = ClassifierServer(
            model, params, batch_lanes=2, buckets=(16, 32), mesh=_mesh1()
        )
        assert shd._mesh is not None and shd.replicas == 1
        for s in (ref, shd):
            for i, L in enumerate((10, 16, 24, 32, 12, 30)):
                s.submit(Request(uid=i, tokens=batch["tokens"][i][:L]))
        t_ref, t_shd = ref.run(), shd.run()
        for i in range(6):
            assert shd.done[i].exit_layer == ref.done[i].exit_layer, i
            assert np.array_equal(shd.done[i].result, ref.done[i].result), i
        # telemetry counters bit-identical, including the per-(bucket, mesh)
        # trace counts: both paths key (S, 1)
        for k in (
            "sentences", "layer_calls", "dense_steps", "avg_exit_layer",
            "step_traces", "embed_traces", "insert_traces",
            "step_traces_per_bucket", "step_traces_per_bucket_replica",
        ):
            assert t_shd[k] == t_ref[k], k
        assert t_shd["replicas"] == 1

    def test_classifier_sharded_r1_one_compile_per_bucket(self):
        """Counted from JAX's compile events, not the engine's counters:
        one fused-step compile per bucket, none in a second drain."""
        from repro.common.compilation import CompileCounter

        model, params, cfg = _albert_model(threshold=0.5)
        batch = SyntheticCLS(cfg.vocab_size, 32, 8, num_classes=3, seed=0).batch(0)
        shd = ClassifierServer(
            model, params, batch_lanes=2, buckets=(16, 32), mesh=_mesh1()
        )
        drains = []
        for rnd in range(2):
            with CompileCounter() as cc:
                for i, L in enumerate((10, 16, 24, 32)):
                    shd.submit(Request(uid=10 * rnd + i, tokens=batch["tokens"][i][:L]))
                shd.run()
            drains.append(cc)
        assert drains[0].by_name["jit(step_fn)"] == 2
        assert drains[1].count == 0, drains[1].by_name

    def test_classifier_sharded_r1_pallas_eligible(self):
        """The Pallas-dispatch path must stay eligible INSIDE shard_map
        (pallas_call has no replication rule — shard_map_norep turns the
        check off), and stay bit-identical to the unsharded Pallas run."""
        model, params, cfg = _albert_model(threshold=0.5)
        batch = SyntheticCLS(cfg.vocab_size, 32, 4, num_classes=3, seed=3).batch(0)
        ref = ClassifierServer(
            model, params, batch_lanes=2, buckets=(16,), use_pallas=True
        )
        shd = ClassifierServer(
            model, params, batch_lanes=2, buckets=(16,), use_pallas=True,
            mesh=_mesh1(),
        )
        for s in (ref, shd):
            for i in range(4):
                s.submit(Request(uid=i, tokens=batch["tokens"][i][:12]))
        ref.run(), shd.run()
        for i in range(4):
            assert shd.done[i].exit_layer == ref.done[i].exit_layer, i
            assert np.array_equal(shd.done[i].result, ref.done[i].result), i

    def test_decoder_sharded_r1_bit_identical(self):
        model, params, cfg = _decoder_model()
        batch = SyntheticLM(cfg.vocab_size, 16, 4, seed=0).batch(0)
        ref = DecoderServer(
            model, params, batch_lanes=2, max_seq=48, eos_id=-1, buckets=(16,)
        )
        shd = DecoderServer(
            model, params, batch_lanes=2, max_seq=48, eos_id=-1, buckets=(16,),
            mesh=_mesh1(),
        )
        assert shd._mesh is not None and shd.replicas == 1
        for s in (ref, shd):
            for i in range(3):
                s.submit(
                    Request(uid=i, tokens=batch["tokens"][i][:8], max_new_tokens=4)
                )
        t_ref, t_shd = ref.run(), shd.run()
        for i in range(3):
            assert shd.done[i].generated == ref.done[i].generated, i
        for k in (
            "completed", "tokens", "decode_steps", "decode_traces",
            "prefill_traces", "step_traces_per_bucket",
            "step_traces_per_bucket_replica",
        ):
            assert t_shd[k] == t_ref[k], k

    def test_decoder_ee_sharded_r1_bit_identical(self):
        """Early-exit decode (per-token exit depths) through the sharded
        wrapper: generated tokens AND exit-depth telemetry must match."""
        model, params, cfg = _decoder_model()
        batch = SyntheticLM(cfg.vocab_size, 16, 4, seed=1).batch(0)
        kw = dict(batch_lanes=2, max_seq=48, eos_id=-1, buckets=(16,),
                  exit_threshold=2.0)
        ref = DecoderServer(model, params, **kw)
        shd = DecoderServer(model, params, mesh=_mesh1(), **kw)
        for s in (ref, shd):
            for i in range(3):
                s.submit(
                    Request(uid=i, tokens=batch["tokens"][i][:8], max_new_tokens=4)
                )
        t_ref, t_shd = ref.run(), shd.run()
        for i in range(3):
            assert shd.done[i].generated == ref.done[i].generated, i
        for k in ("tokens", "token_layer_calls", "avg_token_exit_layer",
                  "decode_traces", "step_traces_per_bucket_replica"):
            assert t_shd[k] == t_ref[k], k


# ===========================================================================
# Placement policies (pure units)
# ===========================================================================


def _q(replica, min_deadline, wait=0.0, feasible=True):
    return Quote(bucket=16, service_s=0.1, wait_s=wait,
                 min_deadline_s=min_deadline, feasible=feasible,
                 replica=replica)


class TestPlacementPolicies:
    def test_least_loaded_picks_earliest_feasible_deadline(self):
        quotes = [_q(0, 3.0), _q(1, 1.5), _q(2, 2.0)]
        assert LeastLoadedPlacement().choose(quotes).replica == 1

    def test_deadline_packed_picks_busiest_feasible(self):
        quotes = [_q(0, 3.0), _q(1, 1.5), _q(2, 2.0)]
        assert DeadlinePackedPlacement().choose(quotes).replica == 0

    def test_wait_breaks_ties(self):
        quotes = [_q(0, 2.0, wait=0.5), _q(1, 2.0, wait=0.1)]
        assert LeastLoadedPlacement().choose(quotes).replica == 1
        assert DeadlinePackedPlacement().choose(quotes).replica == 0


# ===========================================================================
# Replica-pinned refill on the bare scheduler
# ===========================================================================


class _RecordingEngine:
    """Bare-scheduler stub: retires every lane after one step and records
    ``(step_index, lane, uid)`` for each ``lane_load``."""

    def __init__(self, lanes_per_replica):
        self.lpr = lanes_per_replica
        self.loads = []
        self._steps = 0

    def bucket_key(self, req):
        return len(req.tokens)

    def lane_domain(self, lane):
        return lane // self.lpr

    def bucket_begin(self, bucket):
        pass

    def lane_load(self, bucket, lane, req):
        self.loads.append((self._steps, lane, req.uid))

    def lanes_step(self, bucket, active):
        self._steps += 1
        return None

    def lane_advance(self, bucket, lane, req, out, depth):
        return True                          # retire after one fused step

    def lane_finish(self, bucket, lane, req, depth):
        pass

    def bucket_end(self, bucket):
        pass


class TestDomainRouting:
    def _sched(self, lanes_per_replica=1, replicas=2):
        from repro.serving.scheduler import LaneScheduler

        eng = _RecordingEngine(lanes_per_replica)
        return (
            LaneScheduler(lanes_per_replica * replicas, eng, buckets=(16,)),
            eng,
        )

    def test_pinned_request_only_fills_its_domain(self):
        sched, eng = self._sched()
        toks = np.arange(1, 9, dtype=np.int32)
        r0 = Request(uid=0, tokens=toks)
        r0.replica = 1                       # pinned to domain 1 (lane 1)
        sched.submit(r0)
        rep = sched.step()
        assert rep is not None and rep.n_active == 1
        # lane 0 (domain 0) must stay empty; lane 1 carries the request
        assert [(l, u) for _, l, u in eng.loads] == [(1, 0)]

    def test_unpinned_requests_fill_any_domain(self):
        sched, eng = self._sched()
        toks = np.arange(1, 9, dtype=np.int32)
        for i in range(2):
            sched.submit(Request(uid=i, tokens=toks))
        rep = sched.step()
        assert rep.n_active == 2
        assert sorted(l for _, l, _ in eng.loads) == [0, 1]

    def test_incompatible_pin_does_not_block_compatible_younger(self):
        """Two requests pinned to domain 0 ahead of one pinned to domain 1:
        the domain-1 lane must take the YOUNGER compatible request instead
        of idling behind the incompatible queue head."""
        sched, eng = self._sched()
        toks = np.arange(1, 9, dtype=np.int32)
        pins = [0, 0, 1]
        for i, pin in enumerate(pins):
            r = Request(uid=i, tokens=toks)
            r.replica = pin
            sched.submit(r)
        rep = sched.step()
        assert rep.n_active == 2
        first = {(l, u) for s, l, u in eng.loads if s == 0}
        assert first == {(0, 0), (1, 2)}
        sched.step()                         # uid 1 takes domain 0 next
        assert (1, 0, 1) in eng.loads


# ===========================================================================
# Cross-replica lane-clock round-trip (per-replica DVFS domains)
# ===========================================================================


class TestCrossReplicaClockCheckpoint:
    def test_restore_on_either_replica_bit_identical(self):
        """Restoring a checkpointed lane clock is a pure function of the
        payload and the (barrier-synced) fleet clock — NO replica-local
        state leaks in.  After the ``advance_to`` barrier both arbiters sit
        at the same now_s, and restoring A's checkpoint on A or on B yields
        bit-identical lane state field for field."""
        import copy

        stats = albert_layer_stats(seq_len=16)
        ctrl = LatencyAwareDVFSController(
            stats, no_early_exit_baseline(stats)["latency_s"] * 1.5
        )
        arb_a, arb_b = BatchedDVFSArbiter(ctrl), BatchedDVFSArbiter(ctrl)
        arb_a.admit("lane", deadline_s=0.5)
        for _ in range(3):
            arb_a.step(["lane"])
        clk = arb_a.checkpoint_lane("lane")
        # lockstep barrier: both replicas fast-forward to the fleet max,
        # exactly what the engines do after every fused step
        t = max(arb_a.now_s, arb_b.now_s)
        arb_a.advance_to(t)
        arb_b.advance_to(t)
        assert arb_a.now_s == arb_b.now_s
        pay_a, pay_b = copy.deepcopy(clk), copy.deepcopy(clk)
        arb_a.restore_lane("lane", pay_a)
        arb_b.restore_lane("lane", pay_b)
        sa, sb = arb_a._lanes["lane"], arb_b._lanes["lane"]
        for f in ("admit_s", "deadline_s", "target_s", "cycles_per_layer",
                  "depth", "energy_j", "pred_layers_remaining"):
            assert getattr(sa, f) == getattr(sb, f), f
        assert sa.slowest_op == sb.slowest_op

    def test_advance_to_is_monotone_noop_when_behind(self):
        stats = albert_layer_stats(seq_len=16)
        ctrl = LatencyAwareDVFSController(
            stats, no_early_exit_baseline(stats)["latency_s"] * 1.5
        )
        arb = BatchedDVFSArbiter(ctrl)
        arb.advance_to(1.0)
        assert arb.now_s == 1.0
        arb.advance_to(0.5)                  # never rewinds
        assert arb.now_s == 1.0

    def test_expanded_arbiters_share_controller_not_clocks(self):
        """``replicas`` arbiters from one seed share the controller (one
        op table / hw model) but are INDEPENDENT clock domains."""
        from repro.serving.engine import _expand_arbiters

        stats = albert_layer_stats(seq_len=16)
        ctrl = LatencyAwareDVFSController(
            stats, no_early_exit_baseline(stats)["latency_s"] * 1.5
        )
        arbs = _expand_arbiters(BatchedDVFSArbiter(ctrl), 3)
        assert len(arbs) == 3
        assert len({id(a) for a in arbs}) == 3
        assert all(a.c is ctrl for a in arbs)
        arbs[0].admit("lane", deadline_s=0.5)
        arbs[0].step(["lane"])
        assert arbs[0].now_s > 0.0 and arbs[1].now_s == 0.0


# ===========================================================================
# Per-replica admission quoting
# ===========================================================================


class _StubSharded:
    """Minimal sharded-server facade over a bare LaneScheduler: exposes the
    attributes the admission controller prices with (replicas, lane slabs)
    without needing a device mesh."""

    def __init__(self, sched, replicas, lanes_per_replica):
        self.sched = sched
        self.replicas = replicas
        self.lanes_per_replica = lanes_per_replica

    def submit(self, req):
        req.bucket = self.sched.submit(req)


class TestPerReplicaQuoting:
    def _make(self, replicas=2, lpr=1):
        from repro.serving.scheduler import LaneScheduler

        class _E:
            def bucket_key(self, req):
                return len(req.tokens)

            def lane_domain(self, lane, lpr=lpr):
                return lane // lpr

            def bucket_begin(self, bucket):
                pass

            def lane_load(self, bucket, lane, req):
                pass

            def lanes_step(self, bucket, active):
                return None

            def lane_advance(self, bucket, lane, req, out, depth):
                return False                 # contracts stay in flight

            def lane_finish(self, bucket, lane, req, depth):
                pass

            def bucket_end(self, bucket):
                pass

        sched = LaneScheduler(replicas * lpr, _E(), buckets=(16,),
                              step_time_fn=lambda b: 1.0)
        return _StubSharded(sched, replicas, lpr)

    def test_quotes_fan_out_and_route_least_loaded(self):
        srv = self._make()
        ac = AdmissionController(srv, fallback_steps=2.0)
        toks = np.arange(1, 9, dtype=np.int32)
        # occupy replica 0's lane with a long outstanding contract
        busy = Request(uid=0, tokens=toks, deadline_s=50.0)
        busy.replica = 0
        d0 = ac.submit(busy)
        assert d0.admitted
        srv.sched.step()                     # in flight on lane 0
        q = ac.quote(Request(uid=1, tokens=toks, deadline_s=1e9))
        # replica 1 is idle: the routed quote must come from it and be
        # cheaper than replica 0's (which waits behind the contract)
        assert q.replica == 1
        assert q.min_deadline_s < ac.quote(
            Request(uid=2, tokens=toks, deadline_s=1e9), replica=0
        ).min_deadline_s

    def test_accept_pins_request_to_quoted_replica(self):
        srv = self._make()
        ac = AdmissionController(srv, fallback_steps=2.0)
        toks = np.arange(1, 9, dtype=np.int32)
        busy = Request(uid=0, tokens=toks, deadline_s=50.0)
        busy.replica = 0
        ac.submit(busy)
        srv.sched.step()
        req = Request(uid=1, tokens=toks, deadline_s=1e9)
        d = ac.submit(req)
        assert d.admitted and d.quote.replica == 1
        assert req.replica == 1

    def test_single_replica_quote_unchanged(self):
        """replicas == 1 must price exactly the legacy single-domain path
        (replica stays None — no pinning, no fan-out)."""
        srv = self._make(replicas=1, lpr=2)
        ac = AdmissionController(srv, fallback_steps=2.0)
        toks = np.arange(1, 9, dtype=np.int32)
        q = ac.quote(Request(uid=0, tokens=toks, deadline_s=1e9))
        assert q.replica is None
        d = ac.submit(Request(uid=1, tokens=toks, deadline_s=1e9))
        assert d.admitted and getattr(d.quote, "replica", None) is None


# ===========================================================================
# Forced-multi-device end-to-end (subprocess; multidevice marker)
# ===========================================================================


def _run(code: str, devices: int = 4, timeout: int = 900):
    env = dict(os.environ)
    env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={devices}"
    env["PYTHONPATH"] = SRC
    r = subprocess.run(
        [sys.executable, "-c", textwrap.dedent(code)],
        capture_output=True, text=True, timeout=timeout, env=env,
    )
    assert r.returncode == 0, (
        f"stderr:\n{r.stderr[-3000:]}\nstdout:\n{r.stdout[-1000:]}"
    )
    return r.stdout


@pytest.mark.multidevice
class TestForcedFourDevices:
    """Real 4-replica drains on forced host devices, one process each."""

    def test_classifier_r4_matches_unsharded_zero_extra_traces(self):
        _run("""
            import dataclasses, json
            import jax, numpy as np
            from repro.configs.base import get_smoke_config
            from repro.data.synthetic import SyntheticCLS
            from repro.models.model import build_model
            from repro.serving.engine import ClassifierServer, Request

            cfg = get_smoke_config("albert_edgebert")
            cfg = dataclasses.replace(cfg, dtype="float32", remat_policy="none")
            cfg = cfg.with_edgebert(early_exit=dataclasses.replace(
                cfg.edgebert.early_exit, entropy_threshold=0.5))
            model = build_model(cfg)
            params = model.init_params(jax.random.PRNGKey(0))
            batch = SyntheticCLS(cfg.vocab_size, 32, 16, num_classes=3,
                                 seed=0).batch(0)

            ref = ClassifierServer(model, params, batch_lanes=8, buckets=(16,))
            same = ClassifierServer(model, params, batch_lanes=2, buckets=(16,))
            shd = ClassifierServer(model, params, batch_lanes=2, buckets=(16,),
                                   replicas=4)
            assert shd.lanes == 8 and shd.replicas == 4
            for s in (ref, same, shd):
                for i in range(16):
                    s.submit(Request(uid=i, tokens=batch["tokens"][i][:12]))
            t_ref, _, t_shd = ref.run(), same.run(), shd.run()
            for i in range(16):
                assert shd.done[i].exit_layer == ref.done[i].exit_layer, i
                # each device runs the unsharded step on a 2-lane slab, so
                # R=4 is bitwise-equal to an unsharded 2-lane server
                assert np.array_equal(shd.done[i].result, same.done[i].result), i
                # against the flat 8-lane step only f32 rounding differs:
                # XLA picks its matmul blocking by batch size, so an [8, S, D]
                # and a [2, S, D] step sum in different orders (3.6e-7 seen)
                np.testing.assert_allclose(
                    shd.done[i].result, ref.done[i].result, rtol=0, atol=1e-5)
            # one fused-step trace per (bucket, mesh)
            assert t_shd["step_traces_per_bucket_replica"] == {"16x4": 1}, (
                t_shd["step_traces_per_bucket_replica"])
        """)

    def test_decoder_r4_drains_zero_extra_traces(self):
        _run("""
            import dataclasses
            import jax, numpy as np
            from repro.configs.base import get_smoke_config
            from repro.data.synthetic import SyntheticLM
            from repro.models.model import build_model
            from repro.serving.engine import DecoderServer, Request

            cfg = dataclasses.replace(get_smoke_config("deepseek_7b"),
                                      dtype="float32", remat_policy="none")
            model = build_model(cfg)
            params = model.init_params(jax.random.PRNGKey(1))
            batch = SyntheticLM(cfg.vocab_size, 16, 8, seed=0).batch(0)

            shd = DecoderServer(model, params, batch_lanes=2, max_seq=48,
                                eos_id=-1, buckets=(16,), replicas=4)
            ref = DecoderServer(model, params, batch_lanes=2, max_seq=48,
                                eos_id=-1, buckets=(16,))
            for s in (shd, ref):
                for i in range(8):
                    s.submit(Request(uid=i, tokens=batch["tokens"][i][:8],
                                     max_new_tokens=4))
            t_shd, t_ref = shd.run(), ref.run()
            assert t_shd["completed"] == 8
            assert all(len(shd.done[i].generated) == 4 for i in range(8))
            # greedy argmax decode is robust to the fp drift of different
            # shard batch shapes on this smoke config
            for i in range(8):
                assert shd.done[i].generated == ref.done[i].generated, i
            assert t_shd["step_traces_per_bucket_replica"] == {"16x4": 1}, (
                t_shd["step_traces_per_bucket_replica"])
        """)

    def test_checkpoint_on_replica_a_restores_on_replica_b(self):
        _run("""
            import dataclasses
            import jax, numpy as np
            from repro.configs.base import get_smoke_config
            from repro.data.synthetic import SyntheticCLS
            from repro.models.model import build_model
            from repro.serving.engine import ClassifierServer, Request

            cfg = get_smoke_config("albert_edgebert")
            cfg = dataclasses.replace(cfg, dtype="float32", remat_policy="none")
            cfg = cfg.with_edgebert(early_exit=dataclasses.replace(
                cfg.edgebert.early_exit, entropy_threshold=1e-9))
            model = build_model(cfg)
            params = model.init_params(jax.random.PRNGKey(0))
            batch = SyntheticCLS(cfg.vocab_size, 32, 8, num_classes=3,
                                 seed=0).batch(0)

            # uninterrupted reference (unsharded, single lane)
            ref = ClassifierServer(model, params, batch_lanes=1, buckets=(16,))
            ref.submit(Request(uid=0, tokens=batch["tokens"][0][:12]))
            ref.run()

            # sharded run: uid 0 starts on replica 0's only lane, an explicit
            # arrival pinned there evicts it mid-flight, and the checkpoint
            # resumes on replica 1's lane
            srv = ClassifierServer(model, params, batch_lanes=1, buckets=(16,),
                                   replicas=2, preempt=True)
            srv.submit(Request(uid=0, tokens=batch["tokens"][0][:12]))
            srv.step()
            srv.step()                       # a few layers deep on lane 0
            tight = Request(uid=99, tokens=batch["tokens"][1][:12],
                            deadline_s=float(cfg.n_layers * 6))
            tight.replica = 0
            srv.submit(tight)
            # ONE step: domain-0 eviction checkpoints uid 0 off replica 0,
            # and the same refill restores it into replica 1's free lane —
            # checkpoint on A, restore on B, through the real machinery
            srv.step()
            assert srv.telemetry()["preemptions"] == 1
            run = srv.sched._open[16]
            assert run.lane_req[0].uid == 99      # replica 0: the contract
            assert run.lane_req[1].uid == 0       # replica 1: the restoree
            assert srv.done.get(0) is None
            while srv.step() is not None:
                pass
            assert 0 in srv.done and 99 in srv.done
            assert srv.done[0].exit_layer == ref.done[0].exit_layer
            assert np.array_equal(srv.done[0].result, ref.done[0].result)
        """)
