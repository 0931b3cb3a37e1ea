"""Admission control subsystem: SLO feasibility quoting (reject / re-quote
instead of accept-then-miss), best-effort load shedding (bounded queue,
oldest-drop), and preemptive lane checkpointing (evict a budget-free lane for
a tighter-SLO arrival, restore it later with zero re-run layers and zero new
traces)."""
import dataclasses
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.base import get_smoke_config
from repro.data.synthetic import SyntheticCLS
from repro.hwmodel.edgebert_accel import albert_layer_stats
from repro.models.model import build_model
from repro.serving.admission import AdmissionController
from repro.serving.dvfs import (
    BatchedDVFSArbiter,
    LatencyAwareDVFSController,
    no_early_exit_baseline,
)
from repro.serving.engine import ClassifierServer, DecoderServer, Request


def _albert_model(threshold=1e-9):
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


def _batch(cfg, n=8, seed=0):
    return SyntheticCLS(cfg.vocab_size, 32, n, num_classes=3, seed=seed).batch(0)


class TestFeasibilityQuote:
    def test_infeasible_slo_rejected_with_min_feasible_quote(self):
        """An SLO below the full-depth service floor never enters a queue;
        the caller gets the minimum feasible deadline instead of a miss."""
        model, params, cfg = _albert_model()
        batch = _batch(cfg)
        srv = ClassifierServer(model, params, batch_lanes=2, buckets=(16,))
        ac = AdmissionController(srv)
        d = ac.submit(Request(uid=0, tokens=batch["tokens"][0][:12], deadline_s=1.0))
        assert not d.admitted and d.action == "rejected"
        # cold request quotes conservative full depth (steps at 1.0 s/step)
        assert d.quote.min_deadline_s >= cfg.n_layers
        assert not d.quote.feasible
        assert srv.pending == 0 and srv.sched.idle
        assert srv.telemetry()["rejected"] == 1

    def test_quote_is_honored_on_resubmission(self):
        """Resubmitting at exactly the quoted deadline must be accepted (the
        headroom lives inside the quote, not on top of it) and then met."""
        model, params, cfg = _albert_model()
        batch = _batch(cfg)
        srv = ClassifierServer(model, params, batch_lanes=2, buckets=(16,))
        ac = AdmissionController(srv)
        d = ac.submit(Request(uid=0, tokens=batch["tokens"][0][:12], deadline_s=1.0))
        d2 = ac.submit(Request(
            uid=1, tokens=batch["tokens"][0][:12], deadline_s=d.quote.min_deadline_s
        ))
        assert d2.admitted and d2.action == "accepted"
        srv.run()
        r = srv.done[1]
        # deadline math in steps: retire time minus submission, on the
        # modeled clock the quote was priced in
        assert r.retire_step - r.arrival_step <= r.deadline_s

    def test_backlog_inflates_the_quote(self):
        """Accepted explicit commitments push later quotes out: with one lane
        the accepted contract occupies it up to ITS absolute deadline (the
        DVFS layer stretches slack-rich lanes just-in-time), so the next
        identical request is quoted strictly later."""
        model, params, cfg = _albert_model()
        batch = _batch(cfg)
        srv = ClassifierServer(model, params, batch_lanes=1, buckets=(16,))
        ac = AdmissionController(srv)
        q0 = ac.quote(Request(uid=0, tokens=batch["tokens"][0][:12], deadline_s=1.0))
        ac.submit(Request(
            uid=1, tokens=batch["tokens"][1][:12], deadline_s=q0.min_deadline_s
        ))
        q1 = ac.quote(Request(uid=2, tokens=batch["tokens"][2][:12], deadline_s=1.0))
        assert q1.min_deadline_s > q0.min_deadline_s
        assert q1.wait_s > q0.wait_s
        # the wait is the accepted contract's absolute deadline, not its
        # max-op completion time
        assert q1.wait_s == pytest.approx(q0.min_deadline_s)

    def test_requote_mode_admits_at_the_quoted_deadline(self):
        model, params, cfg = _albert_model()
        batch = _batch(cfg)
        srv = ClassifierServer(model, params, batch_lanes=2, buckets=(16,))
        ac = AdmissionController(srv, on_infeasible="requote")
        d = ac.submit(Request(uid=0, tokens=batch["tokens"][0][:12], deadline_s=1.0))
        assert d.admitted and d.action == "requoted"
        req = next(iter(srv.sched.queues[16]))
        assert req.quoted_deadline_s == 1.0          # the original SLO
        assert req.deadline_s == pytest.approx(d.quote.min_deadline_s)
        srv.run()
        assert srv.telemetry()["requoted"] == 1
        r = srv.done[0]
        assert r.retire_step - r.arrival_step <= r.deadline_s

    def test_arbiter_quote_prices_bucket_cycles_at_max_op(self):
        """With a shared-clock arbiter the quote uses the per-bucket cycle
        model at the MAX operating point plus one worst-case switch stall —
        below the controller-target service time, above the raw layer time."""
        model, params, cfg = _albert_model()
        stats = albert_layer_stats(seq_len=16)
        stats.n_layers = cfg.n_layers
        ctrl = LatencyAwareDVFSController(
            stats, no_early_exit_baseline(stats)["latency_s"] * 2.0
        )
        arb = BatchedDVFSArbiter(ctrl)
        srv = ClassifierServer(
            model, params, batch_lanes=2, buckets=(16,), arbiter=arb
        )
        ac = AdmissionController(srv, headroom=1.0)
        batch = _batch(cfg)
        q = ac.quote(Request(uid=0, tokens=batch["tokens"][0][:12], deadline_s=1.0))
        floor = cfg.n_layers * ctrl.cycles_for_seq_len(16) / ctrl.max_op.freq_hz
        assert q.service_s >= floor                   # stall included
        assert q.service_s == pytest.approx(
            arb.min_latency_quote(
                cfg.n_layers, cycles_per_layer=ctrl.cycles_for_seq_len(16)
            )
        )

    def test_queued_contract_claims_the_first_freed_lane(self):
        """Without preemption, a queued accepted contract takes the first
        lane that frees (EDF pops it first) — a later arrival must be quoted
        the SECOND freed lane, not the first, or it gets accepted and then
        starved behind the earlier contract."""
        model, params, cfg = _albert_model()
        batch = _batch(cfg)
        srv = ClassifierServer(model, params, batch_lanes=1, buckets=(16,))
        ac = AdmissionController(srv)
        # occupy the single lane with best-effort work (full depth ahead)
        srv.submit(Request(uid=0, tokens=batch["tokens"][0][:12]))
        srv.step()
        q_empty = ac.quote(Request(uid=90, tokens=batch["tokens"][1][:12],
                                   deadline_s=1.0))
        # accept one contract at its quote: it now waits for the lane
        d1 = ac.submit(Request(
            uid=1, tokens=batch["tokens"][1][:12],
            deadline_s=q_empty.min_deadline_s,
        ))
        assert d1.admitted
        # the next arrival must be priced BEHIND uid 1's whole occupancy
        # (its absolute deadline), not just the best-effort retire
        q2 = ac.quote(Request(uid=2, tokens=batch["tokens"][2][:12],
                              deadline_s=1.0))
        assert q2.wait_s > q_empty.wait_s
        assert q2.wait_s >= q_empty.min_deadline_s - srv.sched.now_s - 1e-9
        # both accepted contracts must then actually be met
        d2 = ac.submit(Request(
            uid=2, tokens=batch["tokens"][2][:12],
            deadline_s=q2.min_deadline_s,
        ))
        assert d2.admitted
        srv.run()
        for uid in (1, 2):
            r = srv.done[uid]
            assert r.retire_step - r.arrival_step <= r.deadline_s, uid

    def test_shared_arbiter_syncs_interleaved_scheduler_clocks(self):
        """Two servers on ONE arbiter, hand-interleaved: each scheduler's
        modeled clock must track the SHARED hardware timeline (the arbiter
        clock), not just its own steps — otherwise EDF slack and admission
        quotes judge deadlines on a stale 'now'."""
        model, params, cfg = _albert_model()
        batch = _batch(cfg)
        stats = albert_layer_stats(seq_len=16)
        stats.n_layers = cfg.n_layers
        ctrl = LatencyAwareDVFSController(
            stats, no_early_exit_baseline(stats)["latency_s"] * 1.5
        )
        arb = BatchedDVFSArbiter(ctrl)
        s1 = ClassifierServer(model, params, batch_lanes=2, buckets=(16,),
                              arbiter=arb)
        s2 = ClassifierServer(model, params, batch_lanes=2, buckets=(16,),
                              arbiter=arb)
        for i in range(2):
            s1.submit(Request(uid=i, tokens=batch["tokens"][i][:12]))
            s2.submit(Request(uid=10 + i, tokens=batch["tokens"][2 + i][:12]))
        s1.step()
        s2.step()
        s1.step()
        # after each server's step its clock equals the shared arbiter clock
        assert s1.sched.now_s == pytest.approx(arb.now_s)
        s2.step()
        assert s2.sched.now_s == pytest.approx(arb.now_s)
        # a submit() to the OTHER server stamps arrival on the shared
        # timeline too — an explicit SLO's queue wait starts at the true
        # hardware now, not at a clock frozen while this server was idle
        s1.step()
        late = Request(uid=50, tokens=batch["tokens"][5][:12],
                       deadline_s=ctrl.target_latency_s)
        s2.submit(late)
        assert late.arrival_s == pytest.approx(arb.now_s)

    def test_best_effort_always_admitted(self):
        model, params, cfg = _albert_model()
        batch = _batch(cfg)
        srv = ClassifierServer(model, params, batch_lanes=2, buckets=(16,))
        ac = AdmissionController(srv)
        d = ac.submit(Request(uid=0, tokens=batch["tokens"][0][:12]))
        assert d.admitted and d.quote is None and d.shed == []


class TestLoadShedding:
    def test_bounded_queue_drops_oldest_best_effort(self):
        model, params, cfg = _albert_model()
        batch = _batch(cfg)
        srv = ClassifierServer(model, params, batch_lanes=2, buckets=(16,))
        ac = AdmissionController(srv, max_best_effort_queue=2)
        shed = []
        for i in range(6):
            d = ac.submit(Request(uid=i, tokens=batch["tokens"][i][:12]))
            shed += d.shed
        # queue bound 2: four oldest dropped, in arrival order
        assert [r.uid for r in shed] == [0, 1, 2, 3]
        assert all(r.shed for r in shed)
        srv.run()
        assert sorted(srv.done) == [4, 5]             # shed requests never ran
        st = srv.telemetry()
        assert st["shed"] == 4 and st["sentences"] == 2

    def test_explicit_slo_never_shed(self):
        """A storm of best-effort submissions must drop best-effort work, not
        the accepted contract sitting in the same queue."""
        model, params, cfg = _albert_model()
        batch = _batch(cfg)
        srv = ClassifierServer(model, params, batch_lanes=2, buckets=(16,))
        ac = AdmissionController(srv, max_best_effort_queue=1)
        ac.submit(Request(
            uid=100, tokens=batch["tokens"][0][:12],
            deadline_s=float(cfg.n_layers * 4),
        ))
        for i in range(4):
            ac.submit(Request(uid=i, tokens=batch["tokens"][i][:12]))
        srv.run()
        assert 100 in srv.done
        assert srv.telemetry()["shed"] == 3

    def test_checkpointed_request_never_shed(self):
        """A preempted request waiting with its checkpoint holds completed
        layers — the oldest-drop policy must skip it."""
        model, params, cfg = _albert_model()
        batch = _batch(cfg)
        srv = ClassifierServer(
            model, params, batch_lanes=1, buckets=(16,), preempt=True
        )
        ac = AdmissionController(srv, max_best_effort_queue=1)
        ac.submit(Request(uid=0, tokens=batch["tokens"][0][:12]))
        srv.step()                                    # uid 0 in flight
        # explicit arrival preempts uid 0 back into the queue, checkpointed
        ac.submit(Request(
            uid=99, tokens=batch["tokens"][1][:12],
            deadline_s=float(cfg.n_layers * 6),
        ))
        srv.step()
        assert srv.telemetry()["preemptions"] == 1
        # queue bound 1 with uid 0 (checkpointed) waiting: new best-effort
        # submissions shed EACH OTHER, never uid 0
        d = ac.submit(Request(uid=1, tokens=batch["tokens"][2][:12]))
        d2 = ac.submit(Request(uid=2, tokens=batch["tokens"][3][:12]))
        assert d.shed == [] and [r.uid for r in d2.shed] == [1]
        srv.run()
        assert 0 in srv.done and 99 in srv.done


class TestPreemption:
    def test_classifier_checkpoint_restore_parity(self):
        """Acceptance criterion: a preempted-then-restored sentence produces
        BIT-IDENTICAL logits and the same exit depth as an uninterrupted run,
        with zero additional jit traces."""
        model, params, cfg = _albert_model()
        batch = _batch(cfg)
        srv = ClassifierServer(
            model, params, batch_lanes=2, buckets=(16,), preempt=True
        )
        ref = ClassifierServer(model, params, batch_lanes=2, buckets=(16,))
        for s in (srv, ref):
            for i in range(3):
                s.submit(Request(uid=i, tokens=batch["tokens"][i][:12]))
        srv.step()
        srv.step()
        # tight-SLO arrival with all lanes busy on budget-free work
        srv.submit(Request(
            uid=99, tokens=batch["tokens"][4][:12],
            deadline_s=float(cfg.n_layers + 3),
        ))
        while srv.step() is not None:
            pass
        while ref.step() is not None:
            pass
        st, st_ref = srv.telemetry(), ref.telemetry()
        assert st["preemptions"] >= 1
        assert st["restored_steps_saved"] >= 1
        preempted = [i for i in range(3) if srv.done[i].preempted]
        assert preempted, "scenario must actually preempt a lane"
        for i in range(3):
            assert srv.done[i].exit_layer == ref.done[i].exit_layer, i
            assert np.array_equal(srv.done[i].result, ref.done[i].result), i
        # zero ADDITIONAL traces: same per-bucket compile counts as the
        # uninterrupted run (restore reuses the bucket's insert trace)
        assert st["step_traces"] == st_ref["step_traces"] == 1
        assert st["insert_traces"] == st_ref["insert_traces"] == 1

    def test_preemption_bounds_explicit_wait_by_one_step(self):
        """With every lane busy on budget-free work, an explicit arrival is
        admitted at the NEXT fused step under preemption; without it, only
        after a retire (full depth away)."""
        model, params, cfg = _albert_model()
        batch = _batch(cfg)
        outcomes = {}
        for preempt in (True, False):
            srv = ClassifierServer(
                model, params, batch_lanes=2, buckets=(16,), preempt=preempt
            )
            for i in range(4):
                srv.submit(Request(uid=i, tokens=batch["tokens"][i][:12]))
            srv.step()
            srv.submit(Request(
                uid=99, tokens=batch["tokens"][5][:12],
                deadline_s=float(cfg.n_layers + 2),
            ))
            while srv.step() is not None:
                pass
            r = srv.done[99]
            outcomes[preempt] = r.first_compute_step - r.arrival_step
        assert outcomes[True] == 0                    # evicted at next refill
        assert outcomes[False] >= cfg.n_layers - 1    # waited for a retire

    def test_preempted_lane_resumes_at_saved_depth(self):
        """The restored request's total layer count equals its exit layer —
        completed layers are not re-run (the depth carries over)."""
        model, params, cfg = _albert_model()
        batch = _batch(cfg)
        srv = ClassifierServer(
            model, params, batch_lanes=1, buckets=(16,), preempt=True
        )
        srv.submit(Request(uid=0, tokens=batch["tokens"][0][:12]))
        srv.step()
        srv.step()                                    # uid 0 at depth 2
        srv.submit(Request(
            uid=99, tokens=batch["tokens"][1][:12],
            deadline_s=float(cfg.n_layers * 4),
        ))
        while srv.step() is not None:
            pass
        st = srv.telemetry()
        assert st["restored_steps_saved"] == 2
        r = srv.done[0]
        assert r.exit_layer == cfg.n_layers           # threshold ~0
        # entropy trace has exactly one entry per executed layer: no layer
        # ran twice across the preemption boundary
        assert len(r.entropy_trace) == cfg.n_layers

    def test_arbiter_clock_survives_checkpoint(self):
        """Under a shared-clock arbiter, a preempted lane's DVFS clock is
        frozen while parked (no budget burn, no energy) and resumes with its
        depth/energy intact — retire reconciles without assertion."""
        model, params, cfg = _albert_model()
        batch = _batch(cfg)
        stats = albert_layer_stats(seq_len=16)
        stats.n_layers = cfg.n_layers
        ctrl = LatencyAwareDVFSController(
            stats, no_early_exit_baseline(stats)["latency_s"] * 2.0
        )
        arb = BatchedDVFSArbiter(ctrl)
        srv = ClassifierServer(
            model, params, batch_lanes=2, buckets=(16,), arbiter=arb,
            preempt=True,
        )
        for i in range(3):
            srv.submit(Request(uid=i, tokens=batch["tokens"][i][:12]))
        srv.step()
        srv.step()
        t_layer = ctrl.cycles_for_seq_len(16) / ctrl.max_op.freq_hz
        srv.submit(Request(
            uid=99, tokens=batch["tokens"][4][:12],
            deadline_s=t_layer * cfg.n_layers * 8,
        ))
        while srv.step() is not None:
            pass
        st = srv.telemetry()
        assert st["preemptions"] >= 1
        assert st["accepted_slo_misses"] == 0
        for i in range(3):
            r = srv.done[i]
            assert r.exit_layer == cfg.n_layers
            assert r.energy_j is not None and r.energy_j > 0
            # latency excludes the parked interval: it can never exceed the
            # arbiter's whole modeled drain time
            assert r.latency_s <= arb.now_s

    def test_decoder_checkpoint_restore_parity(self):
        """Decoder acceptance: a preempted-then-restored decode generates the
        same tokens as an isolated single-request decode, with one decode
        and one prefill trace total."""
        model, params, cfg = _decoder_model()
        rng = np.random.default_rng(0)
        prompts = [
            rng.integers(4, cfg.vocab_size, size=L).astype(np.int32)
            for L in (6, 5, 7)
        ]

        def reference(p, max_new, max_seq):
            cache = model.init_cache(1, max_seq)
            for t in range(len(p) - 1):
                _, cache = model.decode_step(
                    params, cache, jnp.asarray([[int(p[t])]]), t
                )
            pos, cur, outs = len(p) - 1, int(p[-1]), []
            for _ in range(max_new):
                lg, cache = model.decode_step(params, cache, jnp.asarray([[cur]]), pos)
                cur = int(jnp.argmax(lg[0, -1]))
                outs.append(cur)
                pos += 1
            return outs

        srv = DecoderServer(
            model, params, batch_lanes=2, max_seq=32, eos_id=-1, preempt=True
        )
        for i, p in enumerate(prompts):
            srv.submit(Request(uid=i, tokens=p, max_new_tokens=6))
        srv.step()
        srv.step()
        srv.submit(Request(
            uid=99, tokens=prompts[0][:4], max_new_tokens=2, deadline_s=30.0
        ))
        stats = srv.run()
        assert stats["preemptions"] >= 1
        assert stats["restored_steps_saved"] >= 1
        for i, p in enumerate(prompts):
            assert srv.done[i].generated == reference(p, 6, 32), i
        assert stats["decode_traces"] == 1 and stats["prefill_traces"] == 1

    def test_preempt_flag_off_is_inert(self):
        """preempt=False (the default): no eviction ever happens, matching
        the pre-admission scheduler exactly."""
        model, params, cfg = _albert_model()
        batch = _batch(cfg)
        srv = ClassifierServer(model, params, batch_lanes=2, buckets=(16,))
        for i in range(3):
            srv.submit(Request(uid=i, tokens=batch["tokens"][i][:12]))
        srv.step()
        srv.submit(Request(
            uid=99, tokens=batch["tokens"][4][:12],
            deadline_s=float(cfg.n_layers + 2),
        ))
        st = srv.run()
        assert st["preemptions"] == 0 and st["restored_steps_saved"] == 0


class TestOversubscriptionStorm:
    def test_zero_accepted_slo_misses_under_storm(self):
        """The benchmark property at test scale: an oversubscribed tight-SLO
        storm through admission control rejects the infeasible tail and
        misses ZERO accepted SLOs, while the same storm without admission
        misses some; best-effort completes in both."""
        model, params, cfg = _albert_model()
        stats = albert_layer_stats(seq_len=16)
        stats.n_layers = cfg.n_layers
        batch = _batch(cfg, n=16)
        t_layer_max = None
        results = {}
        for admission in (True, False):
            ctrl = LatencyAwareDVFSController(
                stats, no_early_exit_baseline(stats)["latency_s"] * 1.5
            )
            arb = BatchedDVFSArbiter(ctrl)
            srv = ClassifierServer(
                model, params, batch_lanes=2, buckets=(16,), arbiter=arb,
                preempt=admission,
            )
            ac = AdmissionController(srv, max_best_effort_queue=4)
            t_layer = ctrl.cycles_for_seq_len(16) / ctrl.max_op.freq_hz
            deadline = cfg.n_layers * t_layer * 4.0
            for i in range(4):                       # best-effort floor
                (ac.submit if admission else srv.submit)(
                    Request(uid=i, tokens=batch["tokens"][i][:12])
                )
            for j in range(10):                      # tight-SLO storm
                (ac.submit if admission else srv.submit)(Request(
                    uid=100 + j, tokens=batch["tokens"][(j + 4) % 16][:12],
                    deadline_s=deadline,
                ))
            st = srv.run()
            results[admission] = st
        with_ac, without = results[True], results[False]
        assert with_ac["rejected"] > 0
        assert with_ac["accepted_slo_misses"] == 0
        assert without["accepted_slo_misses"] > 0
        # best-effort completed under the storm in the admission run
        assert with_ac["sentences"] >= 4


class TestTelemetryGuards:
    def test_zero_retirees_all_keys_present(self):
        """telemetry() on a fresh server (ctrl attached, nothing retired):
        every percentile / miss / energy key exists and is zero."""
        model, params, cfg = _albert_model()
        stats = albert_layer_stats(seq_len=16)
        stats.n_layers = cfg.n_layers
        ctrl = LatencyAwareDVFSController(
            stats, no_early_exit_baseline(stats)["latency_s"] * 1.5
        )
        srv = ClassifierServer(
            model, params, batch_lanes=2, buckets=(16,),
            arbiter=BatchedDVFSArbiter(ctrl),
        )
        st = srv.telemetry()
        for key in (
            "queue_delay_steps_p50", "queue_delay_steps_p95",
            "queue_delay_steps_p99",
            "queue_delay_steps_max", "deadline_misses", "accepted_slo_misses",
            "energy_j", "modeled_latency_s", "rejected", "requoted", "shed",
            "preemptions", "restored_steps_saved",
        ):
            assert st[key] == 0, key

    def test_no_explicit_slo_retirees(self):
        """deadline-miss accounting with ONLY best-effort retirees: the
        explicit-SLO miss counter exists and is zero, not absent/crashing."""
        model, params, cfg = _albert_model(threshold=0.5)
        stats = albert_layer_stats(seq_len=16)
        stats.n_layers = cfg.n_layers
        ctrl = LatencyAwareDVFSController(
            stats, no_early_exit_baseline(stats)["latency_s"] * 1.5
        )
        srv = ClassifierServer(
            model, params, batch_lanes=2, buckets=(16,),
            arbiter=BatchedDVFSArbiter(ctrl),
        )
        batch = _batch(cfg)
        for i in range(3):
            srv.submit(Request(uid=i, tokens=batch["tokens"][i][:12]))
        st = srv.run()
        assert st["accepted_slo_misses"] == 0
        assert st["deadline_misses"] >= 0


class TestModeledClockOnly:
    def test_modeled_clock_ignores_wall_stamps(self, monkeypatch):
        """Deadline math must not mix in the wall clock.  submit() writes
        the wall stamp ``queued_at`` beside the modeled ``arrival_s`` /
        ``arrival_step``, which stay what the modeled clock says, and a wall
        clock that jumps about changes no scheduling decision: every step,
        retirement and modeled stamp matches a run on the real clock."""
        import types

        from repro.serving import scheduler

        model, params, cfg = _albert_model()
        batch = _batch(cfg)
        wild = iter(np.random.default_rng(0).normal(0.0, 1e9, 10_000))

        def serve(clock):
            monkeypatch.setattr(scheduler, "time", types.SimpleNamespace(perf_counter=clock))
            srv = ClassifierServer(model, params, batch_lanes=2, buckets=(16, 32))
            reqs = [
                Request(uid=i, tokens=batch["tokens"][i][: 12 if i % 2 else 24],
                        deadline_s=float(cfg.n_layers * 3) if i == 3 else None)
                for i in range(5)
            ]
            srv.submit(reqs[0])
            stamped = (reqs[0].queued_at, reqs[0].arrival_s, reqs[0].arrival_step,
                       srv.sched.now_s)
            for r in reqs[1:]:
                srv.submit(r)
            steps = []
            while (rep := srv.step()) is not None:
                steps.append((rep.bucket, rep.n_active, [r.uid for r in rep.retired]))
            modeled = [(r.arrival_s, r.arrival_step, r.admit_s, r.first_compute_step,
                        r.retire_s, r.retire_step) for r in reqs]
            return stamped, steps, modeled, [(r.queued_at, r.admitted_at, r.retired_at)
                                            for r in reqs]

        real = serve(time.perf_counter)
        jumpy = serve(lambda: float(next(wild)))
        queued_at, arrival_s, arrival_step, now_s = jumpy[0]
        assert queued_at is not None and queued_at != real[0][0]   # the wall stamp is written
        assert arrival_s == now_s and arrival_step == 0            # the modeled ones unchanged
        assert jumpy[1] == real[1] and jumpy[2] == real[2]
        assert all(None not in stamps for stamps in jumpy[3])
