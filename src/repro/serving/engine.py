"""Serving engines on the unified lane scheduler: length-bucketed fixed
shapes, cross-bucket time slicing, per-request deadlines + shared-clock
batched DVFS.

Architecture (this module + ``serving/scheduler.py`` + ``serving/dvfs.py``):

* ``LaneScheduler`` owns the lifecycle both engines used to duplicate —
  submit -> length-bucketed queues -> refill free lanes -> fused step ->
  retire -> telemetry — and clocks it INCREMENTALLY: each ``step()`` advances
  exactly one bucket, chosen by a pluggable policy (default: EDF on
  per-request deadlines with a weighted-round-robin fallback), so a deep
  128-token drain no longer starves queued 32-token traffic.  Requests may be
  submitted between steps; ``poll()`` returns completions; ``run()`` remains
  the drain-everything back-compat wrapper.  The queue is partitioned into
  ``[lanes, S_bucket]`` buckets (e.g. 32/64/128): a request lands in the
  smallest bucket that fits and is padded up to it, so jit compiles EXACTLY
  ONE step per bucket instead of one per distinct request length; several
  buckets can be open at once, so engines key ALL their device state by
  bucket.  ``buckets=None`` keeps exact-shape buckets (one per distinct
  length).
* ``Request`` carries an optional per-request SLO: ``deadline_s`` (modeled
  seconds from submission; ``None`` falls back to the DVFS controller's
  global target).  The deadline drives both the scheduler's EDF policy and —
  threaded through ``BatchedDVFSArbiter.admit`` — the shared-clock (V, f)
  decision, which maximizes slack per lane against THAT lane's deadline.
* ``ClassifierServer`` — ALBERT-style classification with entropy early exit
  as a fixed-shape, mask-vectorized continuation-batching engine: a static
  ``[lanes, S_bucket, H]`` hidden tensor plus an active mask; one fused,
  jitted step runs encoder layer -> off-ramp logits -> entropy -> retire
  mask.  Retired lanes refill from the bucket queue between steps, so average
  depth/sentence ~ average exit layer — the batched form of the paper's
  runtime saving.
* DVFS, two modes.  Per-sentence (``dvfs=``): a ``LatencyAwareDVFSController``
  replays Alg. 1 over each sentence's entropy trace after retirement — the
  paper's single-stream analysis, which pretends every sentence owns the
  clock.  Shared-clock (``arbiter=``): the accelerator has ONE LDO/ADPLL
  pair, so a ``BatchedDVFSArbiter`` makes one (V, f) decision per fused step
  — the max over per-lane required frequencies from the entropy->exit-layer
  predictor — with misprediction escalation and the LDO/ADPLL switching
  stall charged on every operating-point change.  Each lane is budgeted at
  ITS bucket's per-layer cycle cost (``hwmodel`` stats rescaled per bucket),
  so short buckets are no longer overcharged at the largest bucket's rate.
  Retired sentences feed the controller's online per-bin quantile
  calibration when enabled.
* ``DecoderServer`` — LM decode with PER-LANE KV lengths: a vmapped decode
  step advances every lane at its OWN position (refilled lanes decode from
  their actual prompt end instead of the max active position — no pad-
  position burn), with EOS retirement + refill and a jitted fixed-shape
  masked prefill.  Cache shapes bucket by prompt + generation budget.
  With ``exit_threshold=`` the fused step additionally runs the paper's
  entropy off-ramp PER TOKEN (``Model.decode_step_ee``: layer -> LM-head ->
  entropy -> masked freeze), realized exit depths feed a position-binned
  online LUT, and with ``arbiter=`` each token is charged at its exit depth
  while the lane's required frequency budgets the predicted remaining
  layers of its remaining tokens — classifier and decoder traffic arbitrate
  on one shared timeline.
* ``MultiTaskRouter`` — the paper's multi-task scenario: one shared
  (eNVM-resident) embedding + per-task encoder/classifier weights; switching
  tasks swaps only task weights (paper §III-D).  All task servers can share
  ONE arbiter — the hardware has one clock.

Trace-count telemetry: every jitted function increments a host-side,
bucket-keyed counter *inside its traced body*, i.e. it only advances when XLA
actually retraces.  ``telemetry()`` reports totals and per-bucket counts
(``step_traces`` must equal the number of buckets used, and stay there across
repeat drains, mid-flight submits, and interleaved stepping) so recompile
regressions fail loudly in tests and CI.
"""
from __future__ import annotations

import functools
import itertools
from dataclasses import dataclass, field
from typing import Any, Dict, List, Optional, TYPE_CHECKING

import jax
import jax.numpy as jnp
import numpy as np
from jax.profiler import TraceAnnotation

from repro.core.early_exit import (
    PositionBinnedExitCalibrator,
    predicted_remaining_layers,
    predicted_token_layers,
)
from repro.models.model import Model
from repro.serving import step_math
from repro.serving.scheduler import LaneScheduler, SchedulingPolicy, StepReport

if TYPE_CHECKING:  # typing-only: dvfs is not a runtime dependency of the engine
    from repro.serving.dvfs import BatchedDVFSArbiter, LatencyAwareDVFSController


@dataclass
class Request:
    uid: int
    tokens: np.ndarray                  # [S] int32
    max_new_tokens: int = 16
    deadline_s: Optional[float] = None  # per-request SLO from SUBMISSION on the
                                        # modeled clock; None = controller target
    result: Optional[np.ndarray] = None
    exit_layer: Optional[int] = None
    generated: List[int] = field(default_factory=list)
    # decoder early exit: 1-based off-ramp exit depth of each generated token
    # (full depth when per-token exit is disabled)
    token_exit_layers: List[int] = field(default_factory=list)
    bucket: Optional[int] = None        # length bucket the scheduler assigned
    replica: Optional[int] = None       # device replica the request is pinned
                                        # to (admission placement routing);
                                        # None = any replica may take it
    # ---- admission / preemption lifecycle ----
    checkpoint: Optional[Any] = None    # engine-opaque lane snapshot while
                                        # the request sits preempted in queue
    ckpt_depth: int = 0                 # depth the checkpoint resumes at
    preempted: int = 0                  # times this request was evicted
    shed: bool = False                  # dropped by load shedding (never ran)
    quoted_deadline_s: Optional[float] = None  # original SLO before a re-quote
    # ---- scheduler lifecycle stamps (queue-delay telemetry) ----
    arrival_step: Optional[int] = None        # dense-step count at submit()
    first_compute_step: Optional[int] = None  # step index of its first lane step
    retire_step: Optional[int] = None         # step index it retired on
    arrival_s: float = 0.0                    # modeled clock at submit()
    admit_s: float = 0.0                      # modeled clock at lane admission
    retire_s: float = 0.0                     # modeled clock at retirement
    seq: int = 0                              # global submission order
    # ---- wall stamps (``time.perf_counter``), for observability only: no
    # scheduling, DVFS or admission code reads them ----
    queued_at: Optional[float] = None         # at submit()
    admitted_at: Optional[float] = None       # at its FIRST lane admission
    retired_at: Optional[float] = None        # at retirement
    # per-layer off-ramp entropies observed while the sentence was in flight;
    # the DVFS controller replays this trace through Alg. 1
    entropy_trace: List[float] = field(default_factory=list)
    energy_j: Optional[float] = None    # modeled accelerator energy (DVFS)
    latency_s: Optional[float] = None   # modeled accelerator latency (DVFS)
    op_vdd: Optional[float] = None      # selected / slowest operating point
    op_freq_hz: Optional[float] = None


def _expand_arbiters(arbiter, replicas: int) -> list:
    """Normalize the ``arbiter=`` ctor argument to one arbiter PER replica.

    Replicated serving models each device as its OWN LDO/ADPLL clock domain:
    a single arbiter is kept for replica 0 and siblings sharing its
    controller (cycle model, DVFS table, online calibrator) are built for
    the rest, so every replica makes independent (V, f) decisions while
    pricing work identically.  A sequence is taken verbatim (it must have
    one arbiter per replica)."""
    if arbiter is None:
        return []
    if isinstance(arbiter, (list, tuple)):
        arbs = list(arbiter)
        assert len(arbs) == replicas, (
            f"need one arbiter per replica: got {len(arbs)} for {replicas}"
        )
        return arbs
    if replicas == 1:
        return [arbiter]
    from repro.serving.dvfs import BatchedDVFSArbiter

    return [arbiter] + [
        BatchedDVFSArbiter(arbiter.c) for _ in range(replicas - 1)
    ]


def _on_lanes(mesh, tree, lane_axis: int):
    """Commit bucket state to the mesh's lane sharding (``lane_axis`` split
    over ``"data"``) when it is created.  The sharded fused step returns its
    state with that sharding, so state that started uncommitted on one
    device would hand the step's jit a second input sharding on the next
    call — a second compile per (bucket, replica).  No-op without a mesh."""
    if mesh is None:
        return tree
    spec = jax.sharding.PartitionSpec(*([None] * lane_axis), "data")
    return jax.device_put(tree, jax.sharding.NamedSharding(mesh, spec))


def _resolve_mesh(replicas: int, mesh):
    """Resolve the (replicas, mesh) ctor pair: ``replicas > 1`` without a
    mesh builds one over the data axis; a mesh alone sets the replica count;
    both must agree.  Returns ``(replicas, mesh)`` — mesh None means the
    engine runs the unsharded single-device path."""
    assert replicas >= 1
    if mesh is None and replicas == 1:
        return 1, None
    if mesh is None:
        from repro.common.jax_compat import make_auto_mesh

        mesh = make_auto_mesh((replicas,), ("data",))
    if replicas == 1:
        replicas = mesh.size
    assert mesh.size == replicas, (
        f"mesh has {mesh.size} devices but replicas={replicas}"
    )
    return replicas, mesh


def _pack_step_outputs(lg, ent, retire) -> jnp.ndarray:
    """One f32 ``[lanes, C + 2]`` array — logits, then entropy, then retire as
    0.0/1.0 — so the host reads a fused step in one transfer.  Every value is
    exact in f32; the concat runs along the last axis and keeps a lane
    sharding."""
    f32 = jnp.float32
    return jnp.concatenate(
        [lg.astype(f32), ent.astype(f32)[:, None], retire.astype(f32)[:, None]],
        axis=-1,
    )


def _unpack_step_outputs(out: np.ndarray, dtypes):
    """Host views of ``_pack_step_outputs``: ``(logits, entropy, retire)``
    in the dtypes the step computed them in."""
    lg_dtype, ent_dtype = dtypes
    return (
        out[:, :-2].astype(lg_dtype, copy=False),
        out[:, -2].astype(ent_dtype, copy=False),
        out[:, -1] > 0,
    )


# unique per-server prefix for arbiter lane keys: with cross-bucket time
# slicing several buckets (and, via a shared arbiter, several servers) can
# hold lanes in flight at once, so the raw lane index no longer identifies a
# request
_SERVER_IDS = itertools.count()

# admission/preemption lifecycle counters every server's telemetry() forwards
# verbatim from the scheduler — one shared tuple so the engines cannot drift
_LIFECYCLE_KEYS = (
    "accepted", "rejected", "requoted", "shed",
    "preemptions", "restored_steps_saved", "accepted_slo_misses",
)


def _fold_miss(
    acc: Dict[str, Any], req: Request, latency_s: float, target_s: float
) -> None:
    """THE per-request deadline-miss rule, shared by both engines: an
    explicit SLO is submission-anchored (modeled queue wait counts), a
    deadline-free request is judged against the admission-anchored
    controller target.  Folds into the incremental accumulators."""
    if req.deadline_s is not None:
        latency_s += req.admit_s - req.arrival_s        # queue wait
        limit = req.deadline_s
    else:
        limit = target_s
    if latency_s > limit * (1 + 1e-9):
        acc["deadline_misses"] += 1
        if req.deadline_s is not None:
            acc["accepted_slo_misses"] += 1


# ===========================================================================
# Classifier (early-exit) server — bucketed fixed-shape continuation batching
# ===========================================================================


class ClassifierServer:
    """Continuation-batching early-exit classifier with static traced shapes.

    Engine state is a dense ``[lanes, S_bucket, D]`` tensor per bucket, kept
    in a bucket-keyed dict because the scheduler time-slices across buckets;
    every step runs the full lane set under an active mask, so the fused step
    has one trace per bucket.  ``layer_calls`` telemetry counts *active*
    lane-layer executions — the quantity the accelerator actually computes.

    ``dvfs``    — per-sentence Alg. 1 replay after retirement (single-stream).
    ``arbiter`` — shared-clock batched arbitration: one (V, f) per fused step.
    The two model different hardware assumptions; pass at most one.
    ``policy``  — scheduling policy for ``step()`` (default EDF + WRR).
    ``preempt`` — allow the scheduler to evict budget-free lanes for queued
    explicit-SLO requests via ``lane_checkpoint``/``lane_restore`` (the
    checkpointed ``(h, depth, kv_len)`` round-trips through the bucket's
    existing compiled insert, so preemption adds zero traces).
    ``use_pallas`` — route the fused step's inner math (attention, layernorm,
    off-ramp entropy, activation quant, pruned MLP tiles) to the Pallas
    kernels via ``serving.step_math`` / ``kernels.dispatch``.  The flag is a
    static Python bool closed over by the jit'd closures, so it preserves
    one-compile-per-bucket and adds zero traces; on CPU the kernels run in
    interpret mode, on TPU they compile to Mosaic.
    """

    def __init__(
        self,
        model: Model,
        params: Any,
        batch_lanes: int = 8,
        dvfs: Optional["LatencyAwareDVFSController"] = None,
        arbiter: Optional["BatchedDVFSArbiter"] = None,
        buckets=None,
        policy: Optional[SchedulingPolicy] = None,
        preempt: bool = False,
        use_pallas: bool = False,
        replicas: int = 1,
        mesh=None,
        task: Optional[str] = None,
        residency: Optional["TaskResidencyManager"] = None,
        deployment: Optional["TaskDeployment"] = None,
    ):
        assert model.cfg.family == "albert", "classifier server drives the albert family"
        assert dvfs is None or arbiter is None, (
            "pass either a per-sentence controller (dvfs=) or a shared-clock "
            "arbiter (arbiter=), not both — they model different hardware"
        )
        self.model = model
        self.params = params
        # ``replicas > 1`` (or an explicit mesh) shards the fused step over a
        # device mesh: ``batch_lanes`` lanes PER replica, flat global lane
        # indices, replica of lane i = i // lanes_per_replica (contiguous
        # slabs match the leading-axis sharding), one DVFS arbiter (clock
        # domain) per replica
        self.replicas, self._mesh = _resolve_mesh(replicas, mesh)
        self.lanes_per_replica = batch_lanes
        self.lanes = batch_lanes * self.replicas
        self.cfg = model.cfg
        self.threshold = model.cfg.edgebert.early_exit.entropy_threshold
        self.dvfs = dvfs
        self.arbiters = _expand_arbiters(arbiter, self.replicas)
        self.arbiter = self.arbiters[0] if self.arbiters else None
        self.use_pallas = use_pallas
        # STATIC block-occupancy masks for the shared encoder MLP, derived
        # host-side from the concrete (post-pruning) weights; None entries /
        # None dict mean the matmul stays dense (ref path)
        self._block_masks = None
        if use_pallas and "mlp" in params.get("layer", {}):
            from repro.kernels import dispatch

            self._block_masks = dispatch.mlp_block_masks(params["layer"]["mlp"])
        self._sid = next(_SERVER_IDS)
        ctrl = self.arbiter.c if self.arbiter is not None else dvfs
        # multi-task residency: which task this server serves, the shared
        # SRAM-over-eNVM working set, and this task's compression deployment.
        # A deployment reprices the hw model: cycles/quotes route through a
        # controller over the COMPRESSED stats, and lane energy is scaled by
        # the deployment's power ratio vs the anchor stats at admit.
        self.task = task
        self.residency = residency
        self.deployment = deployment
        self._dep_ctrl = None
        self._energy_scale = 1.0
        if deployment is not None and ctrl is not None:
            from repro.serving.residency import (      # lazy: engine <-> residency
                deployment_controller,
                deployment_energy_scale,
            )

            self._dep_ctrl = deployment_controller(ctrl, deployment)
            self._energy_scale = deployment_energy_scale(ctrl, deployment)
        self.sched = LaneScheduler(
            self.lanes, self, buckets=buckets, policy=policy,
            step_time_fn=self._step_time_s,
            # with a hw model every request carries at least the controller
            # target as an implicit deadline, so EDF slack — not blind round
            # robin — decides which bucket gets each time slice
            default_deadline_s=ctrl.target_latency_s if ctrl is not None else None,
            preempt=preempt,
        )
        # per-bucket engine state: {"h": [lanes, S, D], "len": [lanes],
        # "out": last step's (logits, entropy, retire, decision), host views
        # into the one packed [lanes, C + 2] read} — several buckets open
        self._bstate: Dict[int, Dict[str, Any]] = {}
        # blocking device-to-host reads made by lanes_step (one per step),
        # and the dtypes of the logits and entropy that the packing widens
        self._host_reads = 0
        self._out_dtypes = None
        # "embed"/"step"/"insert" keyed by S; "step_replica" keyed by
        # (S, replicas) — the per-(bucket, mesh) recompile telemetry the
        # sharded CI gates read (identical to (S, 1) on the unsharded path,
        # so 1-replica sharded and unsharded counters match bit-for-bit)
        self._traces = {"embed": {}, "step": {}, "insert": {}, "step_replica": {}}
        # arbiter counters attributable to THIS server's drains (the arbiter
        # itself is drain-global and may be shared across task servers)
        self._arb_acc = {
            "op_switches": 0, "switch_time_s": 0.0,
            "switch_energy_j": 0.0, "total_energy_j": 0.0,
        }
        # incremental per-retiree accounting: telemetry() must not rescan
        # ``done`` (whose payloads poll() is allowed to drop) — every sum /
        # max / miss count folds in at lane_finish instead
        self._acc = {
            "retired": 0, "exit_sum": 0.0, "energy_j": 0.0, "lat_max": 0.0,
            "deadline_misses": 0, "accepted_slo_misses": 0,
        }

        # thin wrappers around serving.step_math: the closures own ONLY the
        # host-side trace counters (bumped inside the traced body, so they
        # advance exactly when XLA retraces); the step math itself — and the
        # static use_pallas routing — lives in step_math
        def embed_fn(params, tokens):
            S = tokens.shape[1]                  # static at trace time
            self._traces["embed"][S] = self._traces["embed"].get(S, 0) + 1
            return step_math.classifier_embed(model, params, tokens)

        def step_fn(params, h, active, lengths, threshold):
            S = h.shape[1]                       # static at trace time
            self._traces["step"][S] = self._traces["step"].get(S, 0) + 1
            rk = (S, self.replicas)
            self._traces["step_replica"][rk] = (
                self._traces["step_replica"].get(rk, 0) + 1
            )
            if self._mesh is None:
                h, lg, ent, retire = step_math.classifier_fused_step(
                    model, params, h, active, lengths, threshold,
                    use_pallas=self.use_pallas, block_masks=self._block_masks,
                )
            else:
                h, lg, ent, retire = step_math.sharded_classifier_fused_step(
                    model, params, h, active, lengths, threshold,
                    mesh=self._mesh, use_pallas=self.use_pallas,
                    block_masks=self._block_masks,
                )
            self._out_dtypes = (lg.dtype, ent.dtype)
            return h, _pack_step_outputs(lg, ent, retire)

        def insert_fn(h, lane, h_new):
            S = h.shape[1]
            self._traces["insert"][S] = self._traces["insert"].get(S, 0) + 1
            return step_math.lane_insert(h, lane, h_new)

        jit = functools.partial(step_math.jit_at_config_precision, model.cfg)
        self._embed = jit(embed_fn)
        self._step = jit(step_fn)
        self._insert = jax.jit(insert_fn)

    # ---------------------------------------------------------- DVFS helpers
    @property
    def _ctrl(self) -> Optional["LatencyAwareDVFSController"]:
        return self.arbiter.c if self.arbiter is not None else self.dvfs

    def _cycles_for(self, bucket: int) -> Optional[float]:
        """Per-bucket layer cycles from the controller's hw stats rescaled to
        the bucket's sequence length (the controller memoizes per length).
        With a compressed ``TaskDeployment`` attached, the deployment's
        controller prices the bucket instead — span/pruning savings flow
        into step times, arbiter budgets, and admission quotes."""
        ctrl = self._dep_ctrl if self._dep_ctrl is not None else self._ctrl
        return None if ctrl is None else ctrl.cycles_for_seq_len(bucket)

    def _step_time_s(self, bucket: int) -> float:
        """NOMINAL duration of one fused step (the bucket's layer time at the
        max operating point when a hw model is attached, else 1.0 step
        units) — the EDF slack estimate.  The clock itself advances by the
        arbiter's ACTUAL step duration via ``step_dt_s`` when available."""
        ctrl = self._ctrl
        if ctrl is None:
            return 1.0
        return self._cycles_for(bucket) / ctrl.max_op.freq_hz

    def step_dt_s(self, bucket: int) -> Optional[float]:
        """Actual modeled duration of the step just run: the arbiter's chosen
        op period plus any LDO/ADPLL switching stall, so the scheduler's EDF
        clock tracks the clock deadlines are judged by."""
        if self.arbiter is None:
            return None
        st = self._bstate.get(bucket)
        return None if st is None else st.get("dt")

    def clock_s(self) -> Optional[float]:
        """Authoritative shared timeline: the arbiter's clock.  One LDO/ADPLL
        serves every server sharing the arbiter, so arrival stamps and EDF
        slack must fast-forward past time OTHER servers spent on it (the
        scheduler syncs at every submit() and step()).  With replicated
        clock domains the fleet clock is the max — ``lanes_step``'s barrier
        sync keeps the replicas within one fused step of it anyway."""
        if not self.arbiters:
            return None
        return max(a.now_s for a in self.arbiters)

    def _arb_key(self, bucket: int, lane: int):
        return (self._sid, bucket, lane)

    def lane_domain(self, lane: int) -> int:
        """Scheduler routing hook: the replica (clock domain) a lane belongs
        to.  Lane slabs are contiguous so slab r is exactly the rows device r
        computes under the leading-axis sharding."""
        return lane // self.lanes_per_replica

    def _arb_of(self, lane: int) -> "BatchedDVFSArbiter":
        return self.arbiters[self.lane_domain(lane)]

    def _explicit_budget_remaining(self, req: Request) -> Optional[float]:
        """An explicit SLO is submission-anchored (queue wait counts), but
        the DVFS layer budgets from ADMISSION — so hand it only what is LEFT
        of the request's budget after its time in queue (floored at a sliver:
        an already-late request races at max V/f and reports its miss)."""
        if req.deadline_s is None:
            return None
        spent_in_queue = self.sched.now_s - req.arrival_s
        return max(req.deadline_s - spent_in_queue, 1e-12)

    # ---------------------------------------------------------------- public
    def submit(self, req: Request):
        req.bucket = self.sched.submit(req)

    @property
    def done(self) -> Dict[int, Request]:
        return self.sched.done

    @property
    def pending(self) -> int:
        return self.sched.pending

    def step(self) -> Optional[StepReport]:
        """Advance one bucket by one fused step (see ``LaneScheduler.step``)."""
        return self.sched.step()

    def poll(self, *, pin: bool = False) -> List[Request]:
        """Requests retired since the last poll (completion order).  By
        default the polled requests' payloads are DROPPED from ``done`` —
        the caller now owns them; ``pin=True`` keeps them resident."""
        return self.sched.poll(pin=pin)

    def run(self) -> Dict[str, float]:
        """Drain every bucket with continuation batching. Returns telemetry.
        (Arbiter deltas accrue per step inside ``lanes_step``, so hand-stepped
        and run()-driven work are accounted identically.)"""
        self.sched.run()
        return self.telemetry()

    # ------------------------------------------------------- scheduler hooks
    def bucket_key(self, req: Request) -> int:
        return len(req.tokens)

    def bucket_begin(self, bucket: int) -> None:
        with TraceAnnotation("engine.bucket_begin", bucket=bucket):
            D = self.cfg.d_model
            dtype = jnp.asarray(self.params["embed"]["tok"]).dtype
            self._bstate[bucket] = {
                "h": _on_lanes(
                    self._mesh, jnp.zeros((self.lanes, bucket, D), dtype), 0
                ),
                "len": np.full(self.lanes, bucket, np.int32),
                "out": None,
            }

    def lane_load(self, bucket: int, lane: int, req: Request) -> None:
        with TraceAnnotation("engine.lane_load", uid=req.uid, bucket=bucket, lane=lane):
            st = self._bstate[bucket]
            toks = np.zeros(bucket, np.int32)
            toks[: len(req.tokens)] = req.tokens     # pad up to the bucket shape
            st["h"] = self._insert(
                st["h"], jnp.int32(lane), self._embed(self.params, jnp.asarray(toks)[None])
            )
            st["len"][lane] = len(req.tokens)
            if self.residency is not None:
                # task residency: refilling a lane touches this task's weights
                # — a miss swaps them in from eNVM and the stall burns wall
                # time on the shared clock BEFORE the lane's budget is
                # computed (the stall spends the request's
                # submission-anchored SLO budget)
                stall = self.residency.acquire(self.task)
                if stall > 0.0 and self.arbiters:
                    arb = self._arb_of(lane)
                    arb.advance_to(arb.now_s + stall)
                    self.sched.sync_clock()
            if self.arbiters:
                with TraceAnnotation("dvfs.admit"):
                    self._arb_of(lane).admit(
                        self._arb_key(bucket, lane),
                        deadline_s=self._explicit_budget_remaining(req),
                        cycles_per_layer=self._cycles_for(bucket),
                        energy_scale=self._energy_scale,
                    )

    def _arbitrate(self, bucket: int, active: np.ndarray, st: Dict[str, Any]):
        """ONE (V, f) PER CLOCK DOMAIN for this fused step: each replica's
        arbiter arbitrates its own active lane slab independently, then every
        clock fast-forwards to the fleet max — the SPMD barrier (devices
        leave the collective step together; waiting burns wall time, not
        operating-point state).  Telemetry deltas accrue HERE (not in run())
        so step()-driven serving attributes its arbiter work to this server
        too; the actual step duration feeds the scheduler clock via
        step_dt_s.  With one replica this is exactly the single shared-clock
        arbitration.  Returns the step's decision (a tuple, one per domain
        that stepped, with several replicas)."""
        before = [a.telemetry() for a in self.arbiters]
        decisions = []
        L = self.lanes_per_replica
        slabs = [
            (arb, [
                self._arb_key(bucket, i)
                for i in range(r * L, (r + 1) * L) if active[i]
            ])
            for r, arb in enumerate(self.arbiters)
        ]
        # barrier-aware pacing: the fleet step lasts as long as its slowest
        # domain, so no domain may pick a point below the fleet's tightest
        # lane requirement (see BatchedDVFSArbiter.step)
        floor = max(
            (arb.required_hz(k) for arb, keys in slabs for k in keys),
            default=0.0,
        )
        for arb, keys in slabs:
            if keys:
                decisions.append(arb.step(keys, floor_hz=floor))
        t = max(a.now_s for a in self.arbiters)
        for a in self.arbiters:
            a.advance_to(t)
        for b4, a in zip(before, self.arbiters):
            after = a.telemetry()
            for k in self._arb_acc:
                self._arb_acc[k] += after[k] - b4[k]
        # advance the scheduler clock TO the shared arbiter clock rather than
        # by an independently summed dt: combined with the clock_s() sync at
        # submit()/step(), every server sharing the arbiter judges EDF slack,
        # queue waits, and admission quotes on the one hardware timeline
        # deadlines are judged by
        st["dt"] = max(t - self.sched.now_s, 0.0)
        return decisions[0] if len(decisions) == 1 else tuple(decisions)

    def lanes_step(self, bucket: int, active: np.ndarray):
        with TraceAnnotation("engine.lanes_step", bucket=bucket,
                             n_active=int(active.sum())):
            st = self._bstate[bucket]
            decision = None
            if self.arbiters:
                with TraceAnnotation("dvfs.step"):
                    decision = self._arbitrate(bucket, active, st)
            with TraceAnnotation("engine.dispatch"):
                h, packed = self._step(
                    self.params, st["h"], jnp.asarray(active), jnp.asarray(st["len"]),
                    jnp.float32(self.threshold),
                )
                packed.copy_to_host_async()
            st["h"] = h
            # the host waits here for the step's one packed output: the rest
            # of the device time, then a single D2H copy of [lanes, C + 2]
            with TraceAnnotation("engine.fetch"):
                out = np.asarray(packed)
            self._host_reads += 1
            st["out"] = (*_unpack_step_outputs(out, self._out_dtypes), decision)
            return st["out"]

    def lane_advance(
        self, bucket: int, lane: int, req: Request, out, depth: int
    ) -> bool:
        _, ent, retire, _ = out
        req.entropy_trace.append(float(ent[lane]))
        if self.arbiters and depth == 1:
            # first off-ramp evaluated: Alg. 1 line 2 prediction goes live
            self._arb_of(lane).observe_entropy(
                self._arb_key(bucket, lane), float(ent[lane])
            )
        return bool(retire[lane]) or depth >= self.cfg.n_layers

    def lane_finish(self, bucket: int, lane: int, req: Request, depth: int) -> None:
        lg, _, _, _ = self._bstate[bucket]["out"]
        req.result = lg[lane]
        req.exit_layer = depth
        if self.arbiters:
            with TraceAnnotation("dvfs.retire"):
                rep = self._arb_of(lane).retire(self._arb_key(bucket, lane), depth)
            req.energy_j = rep.energy_j
            req.latency_s = rep.latency_s
            req.op_vdd = rep.slowest_op.vdd
            req.op_freq_hz = rep.slowest_op.freq_hz
        elif self.dvfs is not None:
            # per-request deadline overrides the controller-global target —
            # minus the time the request already spent in queue (the SLO is
            # submission-anchored, Alg. 1 budgets from compute start)
            target = None
            if req.deadline_s is not None:
                target = max(req.deadline_s - (req.admit_s - req.arrival_s), 1e-12)
            rep = self.dvfs.sentence_report(
                req.entropy_trace, exit_layer=depth,
                target_latency_s=target,
            )
            req.energy_j = rep.energy_j
            req.latency_s = rep.latency_s
            req.op_vdd = rep.op.vdd
            req.op_freq_hz = rep.op.freq_hz
            # online calibration AFTER the report: a sentence's own exit must
            # not leak into its own prediction
            self.dvfs.observe_exit(req.entropy_trace[0], depth)
        self._account_retiree(req, depth)

    def _account_retiree(self, req: Request, depth: int) -> None:
        """Fold one retirement into the incremental telemetry accumulators
        (``telemetry()`` never rescans ``done`` — retired payloads may have
        been dropped by ``poll()``)."""
        acc = self._acc
        acc["retired"] += 1
        acc["exit_sum"] += depth
        ctrl = self._ctrl
        if ctrl is None:
            return
        acc["energy_j"] += req.energy_j or 0.0
        acc["lat_max"] = max(acc["lat_max"], req.latency_s or 0.0)
        _fold_miss(acc, req, req.latency_s or 0.0, ctrl.target_latency_s)

    def bucket_end(self, bucket: int) -> None:
        with TraceAnnotation("engine.bucket_end", bucket=bucket):
            del self._bstate[bucket]

    def lane_checkpoint(self, bucket: int, lane: int, req: Request):
        """Snapshot ``(h, kv_len)`` at the layer boundary (the scheduler
        keeps the depth) plus the arbiter's lane clock, so an evicted
        sentence resumes without re-running completed layers.  Pure host-side
        reads — no new compiled traces."""
        st = self._bstate[bucket]
        payload = {
            "h": np.asarray(st["h"][lane]),
            "len": int(st["len"][lane]),
        }
        if self.arbiters:
            # the clock payload is RELATIVE (remaining budget + elapsed run
            # time), so it restores onto ANY replica's arbiter bit-identically
            payload["clock"] = self._arb_of(lane).checkpoint_lane(
                self._arb_key(bucket, lane)
            )
        return payload

    def lane_restore(self, bucket: int, lane: int, req: Request, payload) -> None:
        """Reload a checkpointed sentence into a (possibly different) free
        lane.  Reuses the bucket's existing ``_insert`` trace — the payload
        has the same ``[1, S_bucket, D]`` shape as an embed — so restore is
        bit-exact and adds zero traces."""
        st = self._bstate[bucket]
        st["h"] = self._insert(
            st["h"], jnp.int32(lane), jnp.asarray(payload["h"])[None]
        )
        st["len"][lane] = payload["len"]
        if self.arbiters:
            self._arb_of(lane).restore_lane(
                self._arb_key(bucket, lane), payload["clock"]
            )

    def predict_remaining_steps(
        self, bucket: int, req: Request, depth: int
    ) -> float:
        """EDF slack input: entropy-LUT predicted exit depth minus progress,
        using the SAME prediction chain the DVFS controller arbitrates with."""
        ctrl = self._ctrl
        return predicted_remaining_layers(
            req.entropy_trace, depth, self.cfg.n_layers,
            predict_fn=ctrl.predict if ctrl is not None else None,
        )

    # ------------------------------------------------------------- telemetry
    def telemetry(self) -> Dict[str, float]:
        st = self.sched.telemetry()
        acc = self._acc
        avg_exit = acc["exit_sum"] / acc["retired"] if acc["retired"] else 0.0
        out = {
            "sentences": st["sentences"],
            "layer_calls": st["lane_steps"],
            "dense_steps": st["dense_steps"],
            "avg_exit_layer": avg_exit,
            "runtime_savings": 1.0 - avg_exit / self.cfg.n_layers,
            "step_traces": sum(self._traces["step"].values()),
            "host_reads": self._host_reads,
            "embed_traces": sum(self._traces["embed"].values()),
            "insert_traces": sum(self._traces["insert"].values()),
            "step_traces_per_bucket": dict(self._traces["step"]),
            # per-(bucket, mesh) recompile telemetry: JSON-safe "SxR" keys,
            # identical between unsharded and 1-replica sharded runs
            "step_traces_per_bucket_replica": {
                f"{s}x{r}": n
                for (s, r), n in sorted(self._traces["step_replica"].items())
            },
            "replicas": self.replicas,
            "buckets_used": st["buckets_used"],
            "bucket_steps": st["bucket_steps"],
            "lane_occupancy": st["lane_occupancy"],
            "queue_delay_steps_p50": st["queue_delay_steps_p50"],
            "queue_delay_steps_p95": st["queue_delay_steps_p95"],
            "queue_delay_steps_p99": st["queue_delay_steps_p99"],
            "queue_delay_steps_max": st["queue_delay_steps_max"],
            **{k: st[k] for k in _LIFECYCLE_KEYS},
        }
        if self._ctrl is not None:
            # incremental accumulators (folded in at lane_finish): every
            # DVFS-accounting key exists even when NOTHING has retired yet,
            # and none of them depends on ``done`` still holding payloads
            # (poll() may have dropped them)
            out["energy_j"] = float(acc["energy_j"])
            out["modeled_latency_s"] = float(acc["lat_max"])
            out["deadline_misses"] = acc["deadline_misses"]
            out["accepted_slo_misses"] = acc["accepted_slo_misses"]
        if self.arbiter is not None:
            # deltas accumulated across THIS server's drains only: a shared
            # arbiter keeps drain-global counters, and copying those verbatim
            # would multi-count other servers' work in per-task stats
            out["op_switches"] = self._arb_acc["op_switches"]
            out["switch_energy_j"] = self._arb_acc["switch_energy_j"]
            out["switch_time_s"] = self._arb_acc["switch_time_s"]
            out["arb_energy_j"] = self._arb_acc["total_energy_j"]
        return out


# ===========================================================================
# Decoder (LM) server — per-lane KV lengths on the shared scheduler
# ===========================================================================


class DecoderServer:
    """Continuation-batching LM decode with PER-LANE cache positions and
    (optionally) PER-TOKEN entropy early exit under shared-clock DVFS.

    The decode step is vmapped over lanes, so every lane attends its own
    ``[0, pos_lane]`` cache window and refilled lanes continue from their
    actual prompt end — the lock-step max-position loop (which burned pad
    positions for refilled lanes) is gone.  Cache shapes bucket by
    prompt-plus-generation budget; one decode/prefill trace per bucket.
    Caches live in a bucket-keyed dict: the scheduler time-slices across
    buckets, so several caches can be live at once.

    Per-token early exit (``exit_threshold=``): the fused decode step runs
    ``Model.decode_step_ee`` per lane — after every layer the shared LM head
    is evaluated and a token whose entropy drops below the threshold FREEZES
    (hidden-state propagation keeps the remaining layers' KV rows defined),
    so a lane that exits at layer k skips layers k+1..L for that token while
    the traced shapes stay fixed: one compile per bucket, and the per-lane
    exit-depth vector is just another masked output.  Exit depths feed a
    ``PositionBinnedExitCalibrator`` (EdgeBERT's LUT keyed by decode
    position instead of first-off-ramp entropy; cold bins predict the
    conservative full depth), and that ONE prediction chain drives all three
    consumers on the same timeline: the scheduler's EDF slack
    (``predict_remaining_steps`` in fractional full-depth steps), the
    arbiter's required frequency (``set_remaining_layers``: predicted layers
    for ALL remaining tokens over remaining time-to-deadline), and the
    admission feasibility quote (``_cycles_for`` full-depth step cycles x
    predicted fractional steps at the max operating point).

    Shared-clock DVFS (``arbiter=``): one (V, f) per fused step across every
    lane the arbiter serves — classifier and decoder traffic arbitrate on
    one hardware timeline when they share the arbiter.  Each decode token is
    charged at its realized exit depth and at this bucket's PER-TOKEN layer
    cost (the bucket layer cycles amortized per position: decode processes
    one token against <= bucket cached positions).  Prefill is not charged —
    the DVFS model budgets the decode phase, matching the paper's
    per-sentence accounting which starts at layer 1 of compute.
    """

    def __init__(
        self,
        model: Model,
        params: Any,
        batch_lanes: int = 4,
        max_seq: int = 256,
        eos_id: int = 2,
        buckets=None,
        policy: Optional[SchedulingPolicy] = None,
        preempt: bool = False,
        arbiter: Optional["BatchedDVFSArbiter"] = None,
        exit_threshold: Optional[float] = None,
        exit_calibrator: Optional[Any] = None,
        use_pallas: bool = False,
        replicas: int = 1,
        mesh=None,
        task: Optional[str] = None,
        residency: Optional["TaskResidencyManager"] = None,
        spec_window: int = 1,
        threshold_schedule: Optional[Any] = None,
    ):
        self.model = model
        self.params = params
        # multi-task residency (see ClassifierServer): decoder lanes touch
        # the task's weights at refill too, paying the eNVM swap stall on
        # the shared clock when the task is not SRAM-resident
        self.task = task
        self.residency = residency
        # replicated decode: ``batch_lanes`` lanes per replica, the KV cache
        # sharded on its lane axis, one DVFS clock domain per replica (see
        # ClassifierServer — the lane-slab layout is identical)
        self.replicas, self._mesh = _resolve_mesh(replicas, mesh)
        self.lanes_per_replica = batch_lanes
        self.lanes = batch_lanes * self.replicas
        self.max_seq = max_seq
        self.eos_id = eos_id
        self.n_layers = model.cfg.n_layers
        self.arbiters = _expand_arbiters(arbiter, self.replicas)
        self.arbiter = self.arbiters[0] if self.arbiters else None
        self.threshold = exit_threshold
        # static routing of the fused step's eligible inner math to the
        # Pallas kernels (decode attention stays ref — it fuses the KV
        # update/codec — but norms, LM-head entropy, and act quant route);
        # closed over by the jit'd closures, so zero extra traces
        self.use_pallas = use_pallas
        # ---- self-speculative decode (exit-at-k draft / remaining-layer
        # verify): ``spec_window`` tokens per fused step per lane, gated by a
        # per-slot threshold row; an ``ExitThresholdSchedule`` generalizes
        # the scalar threshold per position / entropy band.  ``spec_window=1``
        # with no schedule keeps the existing per-token EE trace untouched.
        self.spec_window = int(spec_window)
        assert self.spec_window >= 1, "spec_window must be >= 1"
        self.schedule = threshold_schedule
        if threshold_schedule is not None and exit_threshold is None:
            exit_threshold = threshold_schedule.base
        self.threshold = exit_threshold
        assert self.spec_window == 1 or exit_threshold is not None, (
            "speculative decode drafts via the entropy off-ramp: spec_window"
            " > 1 needs exit_threshold (or a threshold_schedule)"
        )
        self._spec = exit_threshold is not None and (
            self.spec_window > 1 or threshold_schedule is not None
        )
        if (
            exit_calibrator is None
            and threshold_schedule is not None
            and threshold_schedule.calibrator is not None
        ):
            # the schedule's backing calibrator IS the prediction chain
            exit_calibrator = threshold_schedule.calibrator
        if exit_threshold is not None and exit_calibrator is None:
            exit_calibrator = PositionBinnedExitCalibrator(
                self.n_layers, max_pos=max_seq
            )
        self.calib = exit_calibrator
        self._sid = next(_SERVER_IDS)
        ctrl = self.arbiter.c if self.arbiter is not None else None
        self.sched = LaneScheduler(
            self.lanes, self, buckets=buckets, policy=policy, preempt=preempt,
            step_time_fn=self._step_time_s,
            default_deadline_s=ctrl.target_latency_s if ctrl is not None else None,
        )
        self._bucketed = buckets is not None
        # per-bucket engine state: {"cache", "pos": [lanes], "cur": [lanes, 1],
        # "reqs": per-lane Request refs, "out"} — several buckets open at once
        self._bstate: Dict[int, Dict[str, Any]] = {}
        # "decode"/"prefill" keyed by bucket; "decode_replica" keyed by
        # (bucket, replicas) — per-(bucket, mesh) recompile telemetry
        self._traces = {"decode": {}, "prefill": {}, "decode_replica": {}}
        self._arb_acc = {
            "op_switches": 0, "switch_time_s": 0.0,
            "switch_energy_j": 0.0, "total_energy_j": 0.0,
        }
        # incremental per-retiree accounting (telemetry() must not rescan
        # ``done`` — poll() may drop retired payloads)
        self._acc = {
            "retired": 0, "tokens": 0, "token_layers": 0.0,
            "energy_j": 0.0, "lat_max": 0.0,
            "deadline_misses": 0, "accepted_slo_misses": 0,
            # throughput numerator/denominator for tokens-per-fused-step:
            # one lane_step per lane per fused step (so the per-token EE
            # baseline is exactly 1.0), adv_tokens = tokens actually
            # appended (speculation appends the accepted block)
            "lane_steps": 0, "adv_tokens": 0, "accepted_blocks": 0,
        }

        # thin wrappers around serving.step_math (pure per-lane vmapped step
        # math): the closures own ONLY the host-side trace counters — decode
        # advances every lane at its own position, the EE variant adds the
        # per-token off-ramp, prefill is one fixed-shape trace per bucket
        def _bump_decode(bucket):
            self._traces["decode"][bucket] = self._traces["decode"].get(bucket, 0) + 1
            rk = (bucket, self.replicas)
            self._traces["decode_replica"][rk] = (
                self._traces["decode_replica"].get(rk, 0) + 1
            )

        def decode_fn(params, cache, tokens, pos, bucket):
            _bump_decode(bucket)
            if self._mesh is None:
                return step_math.decoder_decode(
                    model, params, cache, tokens, pos, use_pallas=self.use_pallas
                )
            return step_math.sharded_decoder_decode(
                model, params, cache, tokens, pos,
                mesh=self._mesh, use_pallas=self.use_pallas,
            )

        def decode_ee_fn(params, cache, tokens, pos, threshold, bucket):
            _bump_decode(bucket)
            if self._mesh is None:
                return step_math.decoder_decode_ee(
                    model, params, cache, tokens, pos, threshold,
                    use_pallas=self.use_pallas,
                )
            return step_math.sharded_decoder_decode_ee(
                model, params, cache, tokens, pos, threshold,
                mesh=self._mesh, use_pallas=self.use_pallas,
            )

        def decode_spec_fn(params, cache, tokens, pos, thresholds, bucket):
            # speculative fused step: spec_window and eos_id are server
            # constants closed over, thresholds is a fixed-shape [lanes, W]
            # array operand — one trace per (bucket, replica), threshold
            # VALUES never retrace
            _bump_decode(bucket)
            if self._mesh is None:
                return step_math.decoder_decode_spec(
                    model, params, cache, tokens, pos, thresholds,
                    self.spec_window, eos_id=self.eos_id,
                    use_pallas=self.use_pallas,
                )
            return step_math.sharded_decoder_decode_spec(
                model, params, cache, tokens, pos, thresholds,
                self.spec_window, eos_id=self.eos_id,
                mesh=self._mesh, use_pallas=self.use_pallas,
            )

        def prefill_fn(params, cache, tokens, lane, length):
            bucket = tokens.shape[0]             # static at trace time
            self._traces["prefill"][bucket] = self._traces["prefill"].get(bucket, 0) + 1
            return step_math.decoder_prefill(
                model, params, cache, tokens, lane, length, self.lanes,
                use_pallas=self.use_pallas,
            )

        jit = functools.partial(step_math.jit_at_config_precision, model.cfg)
        self._decode = jit(decode_fn, static_argnums=(4,))
        self._decode_ee = jit(decode_ee_fn, static_argnums=(5,))
        self._decode_spec = jit(decode_spec_fn, static_argnums=(5,))
        self._prefill = jit(prefill_fn)

    # ---------------------------------------------------------- DVFS helpers
    @property
    def _ctrl(self) -> Optional["LatencyAwareDVFSController"]:
        return self.arbiter.c if self.arbiter is not None else None

    def _cycles_token_layer(self, bucket: int) -> Optional[float]:
        """Modeled cycles for ONE decode token through ONE layer at this
        bucket: the bucket's full-sequence layer cycles amortized per
        position (matmul work is token-linear and attention-score work
        token-quadratic, so both divide out to a per-token cost that scales
        with the cache window)."""
        ctrl = self._ctrl
        if ctrl is None:
            return None
        return ctrl.cycles_for_seq_len(bucket) / bucket

    def _cycles_for(self, bucket: int) -> Optional[float]:
        """Cycles of one FULL-DEPTH fused decode step (one token through all
        layers) — the unit ``predict_remaining_steps`` counts in, so the
        admission quote (steps x this at the max op) prices decode SLOs at
        the token-level predicted depth."""
        cyc = self._cycles_token_layer(bucket)
        return None if cyc is None else cyc * self.n_layers

    def _step_time_s(self, bucket: int) -> float:
        """NOMINAL duration of one full-depth fused decode step at the max
        operating point (1.0 step units without a hw model)."""
        ctrl = self._ctrl
        if ctrl is None:
            return 1.0
        return self._cycles_for(bucket) / ctrl.max_op.freq_hz

    def step_dt_s(self, bucket: int) -> Optional[float]:
        """Actual modeled duration of the step just run (arbiter op period
        at realized exit depths + any switching stall)."""
        if self.arbiter is None:
            return None
        st = self._bstate.get(bucket)
        return None if st is None else st.get("dt")

    def clock_s(self) -> Optional[float]:
        """Authoritative shared timeline: the arbiter's clock (classifier and
        decoder servers sharing one arbiter arbitrate on ONE timeline).
        Replicated domains report the fleet max (barrier-synced anyway)."""
        if not self.arbiters:
            return None
        return max(a.now_s for a in self.arbiters)

    def _arb_key(self, bucket: int, lane: int):
        return (self._sid, bucket, lane)

    def lane_domain(self, lane: int) -> int:
        """Scheduler routing hook: the replica (clock domain) of a lane."""
        return lane // self.lanes_per_replica

    def _arb_of(self, lane: int) -> "BatchedDVFSArbiter":
        return self.arbiters[self.lane_domain(lane)]

    def _explicit_budget_remaining(self, req: Request) -> Optional[float]:
        """Submission-anchored SLO minus time already spent in queue (the
        DVFS layer budgets from admission; floored at a sliver so an
        already-late request races at max V/f)."""
        if req.deadline_s is None:
            return None
        spent_in_queue = self.sched.now_s - req.arrival_s
        return max(req.deadline_s - spent_in_queue, 1e-12)

    def _predicted_layers_remaining(self, req: Request) -> float:
        """Predicted layers for ALL of this request's remaining tokens via
        the position-binned LUT (conservative full depth per token when the
        calibrator is cold or per-token exit is disabled)."""
        start = len(req.generated)
        end = req.max_new_tokens
        if end <= start:                 # the retiring token is still due
            end = start + 1
        if self.calib is None:
            return float(end - start) * self.n_layers
        fast = getattr(self.calib, "predict_range", None)
        if fast is not None:             # vectorized: this runs per lane per step
            return fast(start, end)
        return predicted_token_layers(
            self.calib.predict, start, end, self.n_layers
        )

    def _lane_thresholds(self, bucket: int) -> np.ndarray:
        """Per-lane, per-slot threshold rows for one speculative fused step:
        slot j gates the token at generation index ``len(generated) + j``.
        The scalar threshold broadcasts (degenerate schedule); an
        ``ExitThresholdSchedule`` prices each speculated position and the
        lane's last first-off-ramp entropy reading individually."""
        st = self._bstate[bucket]
        W = self.spec_window
        thr = np.full((self.lanes, W), self.threshold, np.float32)
        if self.schedule is not None:
            for i in range(self.lanes):
                req = st["reqs"][i]
                if req is None:
                    continue
                last_ent = (
                    req.entropy_trace[-1] if req.entropy_trace else None
                )
                thr[i] = self.schedule.thresholds(
                    len(req.generated), W, last_ent
                )
        return thr

    # ---------------------------------------------------------------- public
    def submit(self, req: Request):
        req.bucket = self.sched.submit(req)

    @property
    def done(self) -> Dict[int, Request]:
        return self.sched.done

    @property
    def pending(self) -> int:
        return self.sched.pending

    def step(self) -> Optional[StepReport]:
        return self.sched.step()

    def poll(self, *, pin: bool = False) -> List[Request]:
        return self.sched.poll(pin=pin)

    def run(self) -> Dict[str, float]:
        self.sched.run()
        return self.telemetry()

    # ------------------------------------------------------- scheduler hooks
    def bucket_key(self, req: Request) -> int:
        if not self._bucketed:
            return self.max_seq              # legacy: one cache of max_seq
        need = len(req.tokens) + req.max_new_tokens + 1
        assert need <= self.max_seq, f"request needs {need} > max_seq {self.max_seq}"
        return need

    def bucket_begin(self, bucket: int) -> None:
        self._bstate[bucket] = {
            "cache": _on_lanes(
                self._mesh, self.model.init_cache(self.lanes, bucket), 1
            ),
            "pos": np.zeros(self.lanes, np.int32),
            "cur": np.zeros((self.lanes, 1), np.int32),
            "reqs": [None] * self.lanes,
            "out": None,
        }

    def lane_load(self, bucket: int, lane: int, req: Request) -> None:
        st = self._bstate[bucket]
        toks = np.zeros(bucket, np.int32)
        toks[: len(req.tokens)] = req.tokens
        st["cache"] = self._prefill(
            self.params,
            st["cache"],
            jnp.asarray(toks),
            jnp.int32(lane),
            jnp.int32(len(req.tokens)),
        )
        st["pos"][lane] = len(req.tokens) - 1
        st["cur"][lane, 0] = req.tokens[-1]
        st["reqs"][lane] = req
        if self.residency is not None:
            # eNVM task residency: a miss stalls the shared clock for the
            # swap-in before this lane's budget is computed
            stall = self.residency.acquire(self.task)
            if stall > 0.0 and self.arbiters:
                a = self._arb_of(lane)
                a.advance_to(a.now_s + stall)
                self.sched.sync_clock()
        if self.arbiters:
            key = self._arb_key(bucket, lane)
            arb = self._arb_of(lane)
            arb.admit(
                key,
                deadline_s=self._explicit_budget_remaining(req),
                cycles_per_layer=self._cycles_token_layer(bucket),
            )
            arb.set_remaining_layers(
                key, self._predicted_layers_remaining(req)
            )

    def lanes_step(self, bucket: int, active: np.ndarray):
        st = self._bstate[bucket]
        if self.arbiters:
            # refresh every active lane's predicted remaining layers BEFORE
            # the shared-clock decision: the (V, f) pick budgets the
            # position-binned token predictions against each lane's deadline
            for i in range(self.lanes):
                if active[i] and st["reqs"][i] is not None:
                    self._arb_of(i).set_remaining_layers(
                        self._arb_key(bucket, i),
                        self._predicted_layers_remaining(st["reqs"][i]),
                    )
        if self._spec:
            # self-speculative fused step: every lane drafts/verifies up to
            # spec_window tokens; the host truncates each lane's accepted
            # prefix to what the request and cache have room for BEFORE the
            # arbiter charges the block (lane_advance replays exactly this
            # truncation, keeping arbiter depth == sum(token_exit_layers))
            thr = self._lane_thresholds(bucket)
            toks_d, logits, st["cache"], xl, fe, acc_m = self._decode_spec(
                self.params,
                st["cache"],
                jnp.asarray(st["cur"]),
                jnp.asarray(st["pos"]),
                jnp.asarray(thr),
                bucket,
            )
            spec_toks = np.asarray(toks_d)          # [lanes, W]
            exit_layers = np.asarray(xl)            # [lanes, W]
            first_ent = np.asarray(fe)              # [lanes, W]
            accepted = np.asarray(acc_m)            # [lanes, W]
            keep = np.zeros(self.lanes, np.int32)
            for i in range(self.lanes):
                req = st["reqs"][i]
                if not active[i] or req is None:
                    continue
                a = int(accepted[i].sum())          # >= 1: slot 0 is alive
                room_req = req.max_new_tokens - len(req.generated)
                room_cache = (bucket - 1) - int(st["pos"][i])
                keep[i] = max(1, min(a, room_req, room_cache))
            st["keep"] = keep
        elif self.threshold is not None:
            logits, st["cache"], xl, fe = self._decode_ee(
                self.params,
                st["cache"],
                jnp.asarray(st["cur"]),
                jnp.asarray(st["pos"]),
                jnp.float32(self.threshold),
                bucket,
            )
            exit_layers = np.asarray(xl)
            first_ent = np.asarray(fe)
        else:
            logits, st["cache"] = self._decode(
                self.params,
                st["cache"],
                jnp.asarray(st["cur"]),
                jnp.asarray(st["pos"]),
                bucket,
            )
            exit_layers = np.full(self.lanes, self.n_layers, np.int32)
            first_ent = None
        if self.arbiters:
            # one (V, f) PER CLOCK DOMAIN across the stepped lanes, each
            # token charged at its REALIZED exit depth (the decision was made
            # from pre-step predictions above); after arbitration every
            # replica clock barrier-syncs to the fleet max (SPMD lockstep —
            # see ClassifierServer.lanes_step).  Deltas accrue per server
            # like the classifier, and the actual dt feeds the scheduler
            # clock.
            before = [a.telemetry() for a in self.arbiters]
            L = self.lanes_per_replica
            slabs = [
                (arb, [
                    self._arb_key(bucket, i)
                    for i in range(r * L, (r + 1) * L) if active[i]
                ])
                for r, arb in enumerate(self.arbiters)
            ]
            # barrier-aware pacing floor, as in ClassifierServer.lanes_step
            floor = max(
                (arb.required_hz(k) for arb, keys in slabs for k in keys),
                default=0.0,
            )
            for r, (arb, keys) in enumerate(slabs):
                if not keys:
                    continue
                if self._spec:
                    # an accepted BLOCK per lane: charge the summed realized
                    # exit depth of the kept slots (layer-true energy/clock)
                    # and report the accepted token count (throughput)
                    arb.step(
                        keys,
                        layers={
                            self._arb_key(bucket, i): int(
                                exit_layers[i, : st["keep"][i]].sum()
                            )
                            for i in range(r * L, (r + 1) * L)
                            if active[i]
                        },
                        floor_hz=floor,
                        tokens={
                            self._arb_key(bucket, i): int(st["keep"][i])
                            for i in range(r * L, (r + 1) * L)
                            if active[i]
                        },
                    )
                else:
                    arb.step(
                        keys,
                        layers={
                            self._arb_key(bucket, i): int(exit_layers[i])
                            for i in range(r * L, (r + 1) * L)
                            if active[i]
                        },
                        floor_hz=floor,
                        tokens={
                            self._arb_key(bucket, i): 1
                            for i in range(r * L, (r + 1) * L)
                            if active[i]
                        },
                    )
            t = max(a.now_s for a in self.arbiters)
            for a in self.arbiters:
                a.advance_to(t)
            for b4, a in zip(before, self.arbiters):
                after = a.telemetry()
                for k in self._arb_acc:
                    self._arb_acc[k] += after[k] - b4[k]
            st["dt"] = max(t - self.sched.now_s, 0.0)
        if self._spec:
            # block-shaped outputs: tokens/depths/entropies [lanes, W] on
            # host (needed to advance), full block logits ON DEVICE — only a
            # retiring lane's accepted-tail row is materialized
            st["out"] = (spec_toks, exit_layers, first_ent, logits)
        else:
            st["out"] = (
                np.asarray(jnp.argmax(logits[:, -1], axis=-1)),
                exit_layers,
                first_ent,
                # EE path: keep final-token logits ON DEVICE — only a retiring
                # lane's row is materialized (in lane_finish), so the hot loop
                # never pays a [lanes, vocab] host transfer; plain decode keeps
                # the old argmax-only transfer
                logits[:, -1] if self.threshold is not None else None,
            )
        return st["out"]

    def lane_advance(
        self, bucket: int, lane: int, req: Request, out, depth: int
    ) -> bool:
        st = self._bstate[bucket]
        toks, exit_layers, first_ent, _ = out
        acc = self._acc
        acc["lane_steps"] += 1
        if self._spec:
            # advance by the accepted prefix (host-truncated in lanes_step —
            # the same count the arbiter was charged for); every accepted
            # token's realized depth feeds the calibrator at its OWN position
            # (one observation per TOKEN, not per block: blocks would starve
            # the bins covering positions inside accepted prefixes)
            k = int(st["keep"][lane])
            acc["adv_tokens"] += k
            acc["accepted_blocks"] += 1
            for j in range(k):
                tok = int(toks[lane, j])
                req.generated.append(tok)
                xl = int(exit_layers[lane, j])
                req.token_exit_layers.append(xl)
                fe = float(first_ent[lane, j])
                req.entropy_trace.append(fe)
                if self.calib is not None:
                    self.calib.observe(len(req.generated) - 1, xl)
                if (
                    self.schedule is not None
                    and self.schedule.calibrator is not None
                    and self.schedule.calibrator is not self.calib
                ):
                    self.schedule.observe(len(req.generated) - 1, fe, xl)
            st["pos"][lane] += k
            st["cur"][lane, 0] = int(toks[lane, k - 1])
            return (
                int(toks[lane, k - 1]) == self.eos_id
                or len(req.generated) >= req.max_new_tokens
                or int(st["pos"][lane]) >= bucket - 1
            )
        tok = int(toks[lane])
        acc["adv_tokens"] += 1
        req.generated.append(tok)
        xl = int(exit_layers[lane])
        req.token_exit_layers.append(xl)
        if first_ent is not None:
            req.entropy_trace.append(float(first_ent[lane]))
        if self.calib is not None:
            # observe AFTER the step: the token's own exit fed neither this
            # step's arbitration nor its own prediction
            self.calib.observe(len(req.generated) - 1, xl)
        st["pos"][lane] += 1                 # this lane's OWN position only
        st["cur"][lane, 0] = tok
        return (
            tok == self.eos_id
            or len(req.generated) >= req.max_new_tokens
            or int(st["pos"][lane]) >= bucket - 1   # this lane's cache is full
        )

    def lane_finish(self, bucket: int, lane: int, req: Request, depth: int) -> None:
        st = self._bstate[bucket]
        _, _, _, logits = st["out"]
        if logits is not None:               # EE path: one lane row, host-side
            if self._spec:
                # last ACCEPTED slot's verified logits (block logits stay on
                # device; only the retiring row is materialized)
                req.result = np.asarray(
                    logits[lane, int(st["keep"][lane]) - 1]
                )
            else:
                req.result = np.asarray(logits[lane])
        st["reqs"][lane] = None
        acc = self._acc
        acc["retired"] += 1
        acc["tokens"] += len(req.token_exit_layers)
        acc["token_layers"] += float(sum(req.token_exit_layers))
        if self.arbiters:
            # the lane's total arbiter depth is the summed realized exit
            # depth of every token it generated (across preemption stints)
            rep = self._arb_of(lane).retire(
                self._arb_key(bucket, lane), int(sum(req.token_exit_layers))
            )
            req.energy_j = rep.energy_j
            req.latency_s = rep.latency_s
            req.op_vdd = rep.slowest_op.vdd
            req.op_freq_hz = rep.slowest_op.freq_hz
            acc["energy_j"] += rep.energy_j
            acc["lat_max"] = max(acc["lat_max"], rep.latency_s)
            _fold_miss(acc, req, rep.latency_s, self.arbiter.c.target_latency_s)

    def bucket_end(self, bucket: int) -> None:
        del self._bstate[bucket]

    def lane_checkpoint(self, bucket: int, lane: int, req: Request):
        """Snapshot the lane's KV cache row, cache position, and pending
        token so a preempted decode resumes exactly where it stopped (the
        generated tokens and their exit depths already live on the request);
        with an arbiter, the lane clock is frozen alongside."""
        st = self._bstate[bucket]
        payload = {
            "cache": jax.tree_util.tree_map(
                lambda x: np.asarray(x[:, lane]), st["cache"]
            ),
            "pos": int(st["pos"][lane]),
            "cur": int(st["cur"][lane, 0]),
        }
        st["reqs"][lane] = None
        if self.arbiters:
            # relative clock payload: restores onto ANY replica's arbiter
            payload["clock"] = self._arb_of(lane).checkpoint_lane(
                self._arb_key(bucket, lane)
            )
        return payload

    def lane_restore(self, bucket: int, lane: int, req: Request, payload) -> None:
        """Write the checkpointed cache row back into a (possibly different)
        free lane.  Eager fixed-shape updates on the bucket's existing cache
        — the counted decode/prefill traces are untouched."""
        st = self._bstate[bucket]
        st["cache"] = jax.tree_util.tree_map(
            lambda full, row: jax.lax.dynamic_update_slice_in_dim(
                full, jnp.asarray(row)[:, None].astype(full.dtype), lane, axis=1
            ),
            st["cache"],
            payload["cache"],
        )
        st["pos"][lane] = payload["pos"]
        st["cur"][lane, 0] = payload["cur"]
        st["reqs"][lane] = req
        if self.arbiters:
            self._arb_of(lane).restore_lane(
                self._arb_key(bucket, lane), payload["clock"]
            )

    def predict_remaining_steps(
        self, bucket: int, req: Request, depth: int
    ) -> float:
        """EDF slack input in FRACTIONAL full-depth fused steps: the
        position-binned LUT's predicted layers for the remaining tokens over
        the full depth (plain remaining-token count when per-token exit is
        off — every token then costs one full-depth step)."""
        if self.calib is None:
            return float(max(req.max_new_tokens - len(req.generated), 1))
        return max(
            self._predicted_layers_remaining(req) / self.n_layers,
            1.0 / self.n_layers,             # the step that retires it
        )

    # ------------------------------------------------------------- telemetry
    def telemetry(self) -> Dict[str, float]:
        st = self.sched.telemetry()
        acc = self._acc
        avg_exit = (
            acc["token_layers"] / acc["tokens"] if acc["tokens"] else 0.0
        )
        out = {
            "decode_steps": st["dense_steps"],
            "completed": st["sentences"],
            "sentences": st["sentences"],
            "tokens": acc["tokens"],
            "token_layer_calls": acc["token_layers"],
            "avg_token_exit_layer": avg_exit,
            "decode_runtime_savings": (
                1.0 - avg_exit / self.n_layers if acc["tokens"] else 0.0
            ),
            # speculative decode throughput: tokens appended per lane per
            # fused step (exactly 1.0 for the per-token paths — the bench
            # gate's baseline denominator)
            "spec_window": self.spec_window,
            "tokens_per_fused_step": (
                acc["adv_tokens"] / acc["lane_steps"]
                if acc["lane_steps"] else 0.0
            ),
            "avg_accepted_block": (
                acc["adv_tokens"] / acc["accepted_blocks"]
                if acc["accepted_blocks"] else 0.0
            ),
            "decode_traces": sum(self._traces["decode"].values()),
            "prefill_traces": sum(self._traces["prefill"].values()),
            "decode_traces_per_bucket": dict(self._traces["decode"]),
            "step_traces": sum(self._traces["decode"].values()),
            "step_traces_per_bucket": dict(self._traces["decode"]),
            "step_traces_per_bucket_replica": {
                f"{b}x{r}": n
                for (b, r), n in sorted(self._traces["decode_replica"].items())
            },
            "replicas": self.replicas,
            "buckets_used": st["buckets_used"],
            "bucket_steps": st["bucket_steps"],
            "lane_occupancy": st["lane_occupancy"],
            "queue_delay_steps_p50": st["queue_delay_steps_p50"],
            "queue_delay_steps_p95": st["queue_delay_steps_p95"],
            "queue_delay_steps_p99": st["queue_delay_steps_p99"],
            "queue_delay_steps_max": st["queue_delay_steps_max"],
            **{k: st[k] for k in _LIFECYCLE_KEYS},
        }
        if self.arbiter is not None:
            out["energy_j"] = float(acc["energy_j"])
            out["modeled_latency_s"] = float(acc["lat_max"])
            out["deadline_misses"] = acc["deadline_misses"]
            out["accepted_slo_misses"] = acc["accepted_slo_misses"]
            out["op_switches"] = self._arb_acc["op_switches"]
            out["switch_energy_j"] = self._arb_acc["switch_energy_j"]
            out["switch_time_s"] = self._arb_acc["switch_time_s"]
            out["arb_energy_j"] = self._arb_acc["total_energy_j"]
        return out


def probe_exit_threshold(
    model: Model,
    params: Any,
    prompts,
    *,
    batch_lanes: int = 2,
    max_seq: int = 32,
    eos_id: int = -1,
    buckets=(16,),
    max_new_tokens: int = 5,
    quantile: float = 0.5,
) -> float:
    """Pick a decode off-ramp entropy threshold from observed traffic.

    Drains ``prompts`` through a throwaway ``DecoderServer`` whose threshold
    sits below any entropy (no token exits, but first-off-ramp telemetry is
    live) and cuts at the ``quantile`` of the observed readings, so the
    exit-enabled deployment genuinely spreads exits across layers instead
    of all-or-nothing — the decode analogue of the classifier demos'
    dense-profiling-pass threshold pick.  The ONE probe recipe shared by
    the benchmark, the example, and the parity tests."""
    probe = DecoderServer(
        model, params, batch_lanes=batch_lanes, max_seq=max_seq,
        eos_id=eos_id, buckets=buckets, exit_threshold=-1.0,
    )
    for i, p in enumerate(prompts):
        probe.submit(Request(
            uid=i, tokens=np.asarray(p, np.int32), max_new_tokens=max_new_tokens
        ))
    probe.run()
    ents = [e for r in probe.done.values() for e in r.entropy_trace]
    assert ents, "probe produced no off-ramp readings"
    return float(np.quantile(ents, quantile))


# ===========================================================================
# Multi-task router (shared eNVM embeddings)
# ===========================================================================


class MultiTaskRouter:
    """Holds ONE shared embedding table (the eNVM-resident, frozen, pruned
    weights) and per-task encoder/head weights; dispatches requests by task.

    Models the paper's measurement (Fig. 11): task switches swap SRAM-class
    weights only; embedding reload cost is paid once at power-on.  A single
    ``arbiter`` may be shared across all task servers — the hardware has one
    LDO/ADPLL, and drains are sequential, so the shared modeled clock simply
    keeps advancing across task switches.
    """

    def __init__(
        self,
        model: Model,
        shared_embed: Any,
        task_params: Dict[str, Any],
        dvfs: Optional["LatencyAwareDVFSController"] = None,
        arbiter: Optional["BatchedDVFSArbiter"] = None,
        buckets=None,
        policy_factory: Optional[Any] = None,
        preempt: bool = False,
        residency: Optional["TaskResidencyManager"] = None,
        deployments: Optional[Dict[str, "TaskDeployment"]] = None,
        batch_lanes: int = 8,
    ):
        self.model = model
        self.shared_embed = shared_embed
        self.tasks: Dict[str, ClassifierServer] = {}
        self.switches = 0
        self.embed_reloads = 1          # power-on load only
        for name, tp in task_params.items():
            params = dict(tp, embed=shared_embed)
            # a FACTORY, not a shared instance: policies carry per-scheduler
            # mutable state (WRR credits, quantum position) that must not
            # leak between the task servers' independent schedulers
            self.tasks[name] = ClassifierServer(
                model, params, batch_lanes=batch_lanes,
                dvfs=dvfs, arbiter=arbiter, buckets=buckets,
                policy=policy_factory() if policy_factory is not None else None,
                preempt=preempt,
                task=name, residency=residency,
                deployment=(deployments or {}).get(name),
            )

    def submit(self, task: str, req: Request):
        self.tasks[task].submit(req)

    def run_all(self) -> Dict[str, Dict[str, float]]:
        out = {}
        for name, server in self.tasks.items():
            # queued OR mid-flight (a caller may have hand-stepped a server
            # and left lanes in flight): both need draining
            if not server.sched.idle:
                self.switches += 1
                out[name] = server.run()
        return out
