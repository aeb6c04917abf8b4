"""The Trainer: jit-compiled train step under the production sharding, with
checkpoint/restart, failure recovery, elastic rescale and straggler handling.

The train step itself is assembled from the substrate layers:

* model loss from ``repro.models.api`` (any assigned architecture);
* sharding from ``repro.sharding.rules`` (FSDP/TP/EP plans);
* AdamW from ``repro.optim`` (moments inherit the param shardings);
* data from ``repro.data`` (deterministic, stateless resume);
* checkpoints from ``repro.checkpoint`` (async, atomic, elastic).

Distribution is GSPMD-first: the step is a plain ``jax.jit`` with
``in_shardings``/``out_shardings`` derived from the rules, so the same step
function lowers for 8 CPU devices here and 512 TPU chips on the production
mesh (the dry-run proves the latter).  The explicit-collective path
(``shard_map`` + ``repro.core``) backs the overlap/compression features.

**Persistent execution engine** (default): the step is built *once* as a
:class:`~repro.core.futures.PersistentRequest` — AOT-lowered and compiled
with params/opt-state donated — and every step is an ``MPI_Start``-style
re-fire of the compiled executable.  The hot loop can never re-trace (the
``trace:train_step`` pvar counts traces; it stays at 1), argument
shape/sharding drift raises ``ERR_REQUEST`` instead of silently recompiling,
and donation makes steady-state steps allocation-free.  Because donated
buffers cannot be re-dispatched, the straggler policy runs with
``retry_safe=False``: a straggler goes straight to the failure path
(checkpoint restore), the production behaviour for donated step buffers.
``TrainerConfig(persistent=False)`` restores the plain-``jit`` path.

**Layout** comes from one :class:`~repro.configs.base.ParallelPlan`
(``TrainerConfig.plan``, or the deprecated ``pipeline_stages``/
``ring_attention`` int knobs shimmed through ``resolved_plan()``).

**Pipeline-parallel mode** (``plan.stage > 1``): the
trainer re-forms its process set as a ``(data, stage)`` Cartesian topology
(``cart_create`` — MPI 4.0 ch. 8) and the step streams microbatches through
the stages with :func:`repro.core.overlap.pipeline_spmd`; every stage
boundary is one ``cart_shift(+1)`` axis-local ``collective-permute``.  The
pipeline step rides the same persistent engine — still exactly one trace.

**Async checkpointing on the same engine** (default): ``ckpt.save`` gathers
device state synchronously (donation-safe) and runs the file writes as I/O
requests overlapping the next persistent step; the single manifest commit
is the durability point.  A failed save surfaces as ``ERR_IO`` at the next
join — the trainer counts it (``ckpt_failures`` in the result, the
``ckpt_save_failed`` pvar), logs it and keeps training from device state
(``latest`` stays at the previous complete step); it is never reported as
success.  The straggler/failure recovery path restores elastically through
the checkpoint's ``set_view`` storage representation.
"""

from __future__ import annotations

import dataclasses
import logging
import time
import warnings
from typing import Callable

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding
from jax.sharding import PartitionSpec as P

from repro.checkpoint import CheckpointManager
from repro.configs.base import ModelConfig, ParallelConfig, ParallelPlan
from repro.core import errors, tool
from repro.core.communicator import Communicator
from repro.core.epoch import ELASTIC, CommEpoch, TopologySpec
from repro.core.futures import PersistentRequest
from repro.data import TokenPipeline
from repro.models import api as model_api
from repro.optim import AdamW, clip_by_global_norm, cosine_warmup
from repro.runtime.faults import (
    FaultInjector,
    RankEvicted,
    StepGuard,
    StragglerPolicy,
    WorkerFailure,
)
from repro.sharding import rules

log = logging.getLogger("repro.trainer")

tool.pvar_register("trace:train_step", "train-step executables traced (want exactly 1 per epoch)")
tool.pvar_register(
    "elastic:recovery_steps",
    "steps replayed per eviction (restore point back to eviction point)",
)
tool.pvar_register(
    "config:deprecated_knob",
    "TrainerConfig layouts built through the deprecated "
    "pipeline_stages/ring_attention int knobs instead of a ParallelPlan",
)
tool.span_register("repro.trainer.step", "one step of the trainer's loop; stat step")
tool.span_register("repro.trainer.batch", "the step's batch: device_batch and device_put")
tool.span_register("repro.trainer.wait", "the host's wait for the step's loss")
tool.span_register("repro.trainer.record",
                   "the log_every bookkeeping: loss and norm to host, pvar_read, log")

_deprecated_knob_warned = False


def _warn_deprecated_knobs() -> None:
    """One DeprecationWarning per process for the legacy int knobs; the pvar
    still counts every shimmed construction so the lint sees the usage."""

    global _deprecated_knob_warned
    tool.pvar_count("config:deprecated_knob")
    if _deprecated_knob_warned:
        return
    _deprecated_knob_warned = True
    warnings.warn(
        "TrainerConfig.pipeline_stages/pipeline_microbatches/ring_attention "
        "are deprecated; pass plan=ParallelPlan(stage=..., ring=..., "
        "microbatches=...) instead",
        DeprecationWarning,
        stacklevel=3,
    )


@dataclasses.dataclass
class TrainerConfig:
    steps: int = 100
    lr: float = 3e-4
    warmup_steps: int = 10
    clip_norm: float = 1.0
    weight_decay: float = 0.1
    seed: int = 0
    checkpoint_dir: str | None = None
    checkpoint_every: int = 50
    keep_checkpoints: int = 3
    log_every: int = 10
    max_restarts: int = 3
    # persistent execution engine: AOT-compile the step once, MPI_Start it
    # every iteration (zero re-traces); donate aliases params/opt-state.
    persistent: bool = True
    donate: bool = True
    # checkpoint writes ride the I/O request engine and overlap the next
    # step; False joins each save before the next step starts
    async_checkpoint: bool = True
    # the unified layout: one frozen ParallelPlan covers the cart fold
    # (data x stage x ring x tensor), microbatching, grad-sync buckets and
    # remat — what `python -m repro.tune` emits and `--plan` parses.
    # None = a pure data plan (adopt the communicator's own shape), unless
    # the deprecated knobs below ask for a fold.
    plan: ParallelPlan | None = None
    # DEPRECATED pipeline/ring int knobs — shims that construct the
    # equivalent ParallelPlan via resolved_plan() and warn once.  Kept so
    # pre-plan examples and configs run unchanged; pvar
    # `config:deprecated_knob` counts every shimmed construction.
    pipeline_stages: int = 0
    pipeline_microbatches: int = 2
    ring_attention: int = 0

    def resolved_plan(self) -> ParallelPlan:
        """The one layout truth: ``plan`` when set, else the deprecated int
        knobs shimmed through :meth:`ParallelPlan.from_legacy` (warning
        once), else the pure data plan."""

        legacy = self.pipeline_stages > 1 or self.ring_attention > 1
        if self.plan is not None:
            errors.check(
                not legacy,
                errors.ErrorClass.ERR_ARG,
                "TrainerConfig.plan and the deprecated pipeline_stages/"
                "ring_attention knobs are both set; the plan is the only "
                "layout input — drop the legacy knobs",
            )
            return self.plan
        if legacy:
            _warn_deprecated_knobs()
            return ParallelPlan.from_legacy(
                pipeline_stages=self.pipeline_stages,
                pipeline_microbatches=self.pipeline_microbatches,
                ring_attention=self.ring_attention,
            )
        return ParallelPlan()


def make_train_step(
    cfg: ModelConfig,
    pcfg: ParallelConfig,
    tcfg: TrainerConfig,
    opt: AdamW,
    mesh: Mesh | None = None,
):
    """Build the pure train-step function (params, opt_state, batch) ->
    (params, opt_state, metrics).  ``mesh`` is forwarded to the model loss
    for the explicitly sharded attention paths (ring attention)."""

    bundle = model_api.build(cfg)

    def train_step(params, opt_state, batch):
        def loss_fn(p):
            loss, metrics = bundle.loss(p, batch, pcfg, mesh)
            return loss, metrics

        (loss, metrics), grads = jax.value_and_grad(loss_fn, has_aux=True)(params)
        grads, gnorm = clip_by_global_norm(grads, tcfg.clip_norm)
        params, opt_state = opt.update(grads, opt_state, params)
        metrics = dict(metrics)
        metrics["grad_norm"] = gnorm
        return params, opt_state, metrics

    return train_step


def _pipeline_param_specs(params, stages: int):
    """Pipeline placement: the stacked ``layers`` leading (unit) dim is
    sharded over the cart ``stage`` axis — each stage holds its slice of
    the layer stack; embedding/head/norms replicate."""

    for leaf in jax.tree.leaves(params["layers"]):
        errors.check(
            np.shape(leaf)[0] % stages == 0,
            errors.ErrorClass.ERR_DIMS,
            f"{np.shape(leaf)[0]} scanned units do not split over "
            f"{stages} pipeline stages",
        )
    specs = jax.tree.map(lambda _: P(), params)
    return {**specs, "layers": jax.tree.map(lambda _: P("stage"), params["layers"])}


def make_pipeline_train_step(
    cfg: ModelConfig,
    pcfg: ParallelConfig,
    tcfg: TrainerConfig,
    opt: AdamW,
    cart,
    plan: ParallelPlan | None = None,
):
    """Pipeline-parallel train step over a ``(data, stage)`` Cartesian
    topology (MPI 4.0 ch. 8 as the pipeline fabric).

    The loss runs under ``shard_map``: ``params['layers']`` is sharded over
    the ``stage`` axis, the batch over ``data``, and
    :func:`repro.core.overlap.pipeline_spmd` streams
    ``pipeline_microbatches`` through the stages — every stage boundary is
    one ``cart_shift(+1)`` axis-local ``collective-permute``, never a dense
    world collective.  AD differentiates through the schedule (the permute
    transposes to the reverse shift), so data-parallel gradient reduction
    over ``data`` and stage-local layer gradients emerge from the shard_map
    transpose without further plumbing.  The whole step still compiles once
    into the persistent engine: ``trace:train_step`` stays at 1.
    """

    from repro.core import _compat
    from repro.core import overlap as core_overlap
    from repro.models import transformer

    embed_mb, apply_units, loss_mb = transformer.pipeline_stage_fns(cfg, pcfg)
    plan = plan if plan is not None else tcfg.resolved_plan()
    m = max(1, plan.microbatches)
    mesh = cart.mesh

    def spmd_loss(params, batch):
        tokens = batch["tokens"]                     # local (b_loc, T)
        errors.check(
            tokens.shape[0] % m == 0,
            errors.ErrorClass.ERR_COUNT,
            f"local batch {tokens.shape[0]} does not split into {m} microbatches",
        )
        mb = tokens.shape[0] // m
        toks = tokens.reshape(m, mb, tokens.shape[1])
        losses = core_overlap.pipeline_spmd(
            cart,
            stage_dim=1,
            num_microbatches=m,
            inject=lambda i: embed_mb(params, toks[i]),
            stage_fn=lambda state, t: apply_units(params["layers"], state),
            extract=lambda i, state, is_last: jnp.where(
                is_last, loss_mb(params, state, toks[i]), 0.0
            ),
        )
        loss = sum(losses) / m
        # only the last stage contributed; the stage psum replicates it and
        # the data psum averages the per-shard token means
        return jax.lax.psum(loss, ("data", "stage")) / cart.dims[0]

    def train_step(params, opt_state, batch):
        def loss_fn(p):
            pspecs = _pipeline_param_specs(p, cart.dims[1])
            bspecs = jax.tree.map(lambda _: P("data"), batch)
            mapped = _compat.shard_map(
                spmd_loss, mesh=mesh, in_specs=(pspecs, bspecs), out_specs=P()
            )
            loss = mapped(p, batch)
            return loss, {"loss": loss}

        (loss, metrics), grads = jax.value_and_grad(loss_fn, has_aux=True)(params)
        grads, gnorm = clip_by_global_norm(grads, tcfg.clip_norm)
        params, opt_state = opt.update(grads, opt_state, params)
        metrics = dict(metrics)
        metrics["grad_norm"] = gnorm
        return params, opt_state, metrics

    return train_step


class Trainer:
    def __init__(
        self,
        cfg: ModelConfig,
        pcfg: ParallelConfig,
        tcfg: TrainerConfig,
        comm: Communicator | Mesh,
        *,
        seq_len: int = 512,
        global_batch: int = 8,
        injector: FaultInjector | None = None,
        straggler: StragglerPolicy | None = None,
        clock: Callable[[], float] | None = None,
    ):
        self.cfg, self.pcfg, self.tcfg = cfg, pcfg, tcfg
        self.injector = injector
        # Session-derived communicator is the canonical handle onto the
        # training process set; a bare Mesh is wrapped unmanaged.  All comm
        # state lives in the current CommEpoch — the rebuildable fabric the
        # elastic shrink/grow path advances — and `self.comm`/`self.mesh`
        # read through to it.
        comm = comm if isinstance(comm, Communicator) else Communicator(comm)
        self._epoch = self._reform_topology(comm)
        self.seq_len, self.global_batch = seq_len, global_batch
        self.bundle = model_api.build(cfg)
        self.opt = AdamW(
            lr=cosine_warmup(tcfg.lr, tcfg.warmup_steps, tcfg.steps),
            weight_decay=tcfg.weight_decay,
            moment_dtype=self.pcfg.moment_dtype,
        )
        self.guard = StepGuard(
            straggler or StragglerPolicy(), injector,
            clock if clock is not None else time.perf_counter,
        )
        self.ckpt = (
            CheckpointManager(
                tcfg.checkpoint_dir,
                keep=tcfg.keep_checkpoints,
                async_save=tcfg.async_checkpoint,
                injector=injector,
            )
            if tcfg.checkpoint_dir
            else None
        )
        self.ckpt_failures = 0
        self.pipeline = TokenPipeline(
            vocab_size=cfg.vocab_size,
            seq_len=seq_len,
            global_batch=global_batch,
            seed=tcfg.seed,
            modality={"encdec": "audio", "vlm": "vlm"}.get(cfg.family, "lm"),
            frame_dim=cfg.d_model,
            frame_len=max(8, seq_len // 8),
            image_tokens=cfg.num_image_tokens,
            image_dim=1152,
        )
        self._compiled = None
        self._bshard = None
        self.metrics_history: list[dict] = []
        self.restarts = 0
        self.evictions = 0
        self.joins = 0

    # -- the fabric: everything comm-shaped reads through the current epoch ---

    @property
    def epoch(self) -> CommEpoch:
        return self._epoch

    @property
    def comm(self) -> Communicator:
        return self._epoch.comm

    @property
    def mesh(self):
        return self._epoch.comm.mesh

    def _reform_topology(self, comm: Communicator) -> CommEpoch:
        """The one place the trainer shapes its fabric: resolve the
        :class:`~repro.configs.base.ParallelPlan` (pipeline and ring were
        two near-identical cart-reform special cases before the plan
        subsumed them), derive the epoch's :class:`TopologySpec` from it,
        and bundle it with the communicator's group into generation 0.  The
        data axis is the elastic dim — shrink/grow re-folds it; the plan's
        stage/ring/tensor dims are fixed."""

        self.plan = plan = self.tcfg.resolved_plan()
        size = comm.group().size()
        if plan.remat is not None:
            self.pcfg = dataclasses.replace(self.pcfg, remat=plan.remat)
        if plan.reforms_fabric:
            errors.check(
                size % plan.fixed_size == 0,
                errors.ErrorClass.ERR_DIMS,
                f"{size} devices do not fold onto plan {plan.slug()!r} "
                f"(fixed axes need a multiple of {plan.fixed_size})",
            )
            spec = TopologySpec.from_plan(plan)
            if plan.ring > 1:
                # the periodic ring dim rides the model axis: attention
                # shards the sequence over the ring and rotates KV via
                # cart_shift(+1) collective-permutes hidden behind compute
                self.pcfg = dataclasses.replace(self.pcfg, ring_attention=True)
        else:
            spec = None  # adopt the communicator's own shape
        return CommEpoch.create(comm, spec, name="train")

    # -- assembly -------------------------------------------------------------

    def init_state(self):
        # params and optimiser state are built under their declared
        # shardings (the whole tree never lands on one device first); the
        # persistent executable is bound to them (ERR_REQUEST on drift)
        key = jax.random.PRNGKey(self.tcfg.seed)
        p_shapes = jax.eval_shape(self.bundle.init, key)
        o_shapes = jax.eval_shape(self.opt.init, p_shapes)
        pshard, oshard = self._state_shardings(p_shapes, o_shapes)
        with self.mesh:
            params = jax.jit(self.bundle.init, out_shardings=pshard)(key)
            opt_state = jax.jit(self.opt.init, out_shardings=oshard)(params)
        return params, opt_state

    def _param_pspecs(self, params):
        if self.plan.stage > 1:
            return _pipeline_param_specs(params, self.plan.stage)
        return rules.param_specs(params, self.mesh, self.pcfg)

    def _state_shardings(self, params, opt_state):
        pspecs = self._param_pspecs(params)
        pshard = rules.shardings(pspecs, self.mesh)
        oshard = jax.tree.map(
            lambda leaf: NamedSharding(self.mesh, P()),
            opt_state,
        )
        # moments inherit the matching parameter's sharding where shapes agree
        flat_p = jax.tree.leaves(pshard)
        shapes = [tuple(np.shape(x)) for x in jax.tree.leaves(params)]
        by_shape = {}
        for s, sh in zip(shapes, flat_p):
            by_shape.setdefault(s, sh)

        def moment_shard(leaf, cur):
            s = tuple(np.shape(leaf))
            return by_shape.get(s, cur)

        oshard = jax.tree.map(moment_shard, opt_state, oshard)
        return pshard, oshard

    def _shardings_for(self, params, opt_state, batch):
        pshard, oshard = self._state_shardings(params, opt_state)
        bshard = {
            k: NamedSharding(self.mesh, s)
            for k, s in zip(
                batch.keys(), jax.tree.leaves(rules.batch_spec(batch, self.mesh, self.pcfg))
            )
        }
        return pshard, oshard, bshard

    def compile(self, params, opt_state):
        """The epoch's persistent step executable, built lazily exactly once
        per epoch (``epoch.cached``).  A shrink/grow revokes the old epoch —
        and with it the request whose shardings the new mesh would reject
        with ``ERR_REQUEST`` — so the successor epoch rebuilds here on first
        use: ``trace:train_step`` is 1 per epoch by construction."""

        self._compiled, self._bshard = self._epoch.cached(
            "train_step", lambda _ep: self._build_step(params, opt_state)
        )
        return self._compiled

    def _build_step(self, params, opt_state):
        batch = self.pipeline.device_batch(0, self.mesh, self.pcfg)
        if self.plan.stage > 1:
            base_step = make_pipeline_train_step(
                self.cfg, self.pcfg, self.tcfg, self.opt, self.comm,
                plan=self.plan,
            )
        else:
            base_step = make_train_step(
                self.cfg, self.pcfg, self.tcfg, self.opt,
                mesh=self.mesh if self.pcfg.ring_attention else None,
            )

        def step_fn(params, opt_state, batch):
            # a python side effect at trace time: the pvar counts every trace
            # of the step, so tests can assert the hot loop never re-traces
            tool.pvar_count("trace:train_step")
            return base_step(params, opt_state, batch)

        pshard, oshard, bshard = self._shardings_for(params, opt_state, batch)
        with self.mesh:
            if self.tcfg.persistent:
                # persistent execution engine: AOT lower+compile once against
                # the canonical shardings; every step is an MPI_Start re-fire
                # of the executable (donated params/opt-state alias outputs).
                example = (
                    jax.device_put(params, pshard),
                    jax.device_put(opt_state, oshard),
                    jax.device_put(batch, bshard),
                )
                donate = (0, 1) if self.tcfg.donate else ()
                jitted = jax.jit(
                    step_fn,
                    in_shardings=(pshard, oshard, bshard),
                    out_shardings=(pshard, oshard, None),
                    donate_argnums=donate,
                )
                return (
                    PersistentRequest(jitted, example, donate_argnums=donate),
                    bshard,
                )
            else:
                # NOTE: no donation here — the straggler policy re-dispatches
                # the same step with the same inputs, which donated buffers
                # forbid.  The production lowering (launch/dryrun.py) donates
                # params and opt state; at scale the straggler retry path
                # instead restores from the last checkpoint (the failure
                # path below).
                return (
                    jax.jit(
                        step_fn,
                        in_shardings=(pshard, oshard, bshard),
                        out_shardings=(pshard, oshard, None),
                    ),
                    bshard,
                )

    # -- the loop --------------------------------------------------------------

    def run(self, steps: int | None = None) -> dict:
        steps = steps if steps is not None else self.tcfg.steps
        params, opt_state = self.init_state()
        start = 0
        if self.ckpt is not None and self.ckpt.latest_step() is not None:
            params, opt_state, start = self._restore(params, opt_state)
        self.compile(params, opt_state)

        step = start
        while step < steps:
            try:
                params, opt_state, step = self._run_span(
                    params, opt_state, step, steps
                )
            except RankEvicted as e:
                # ULFM path: no job restart — revoke, shrink to survivors,
                # rebuild the fabric, restore the last committed manifest
                self.evictions += 1
                if self.evictions + self.restarts > self.tcfg.max_restarts:
                    raise
                log.warning("rank %d evicted at step %d; shrinking", e.rank, e.step)
                params, opt_state, step = self._shrink(e)
            except WorkerFailure as e:
                self.restarts += 1
                if self.restarts > self.tcfg.max_restarts:
                    raise
                log.warning("worker failure at step %d (%s); restarting", step, e)
                params, opt_state, step = self._recover()
        if self.ckpt is not None:
            self._checkpoint(step, params, opt_state, join=True)
        return {
            "final_step": step,
            "restarts": self.restarts,
            "evictions": self.evictions,
            "joins": self.joins,
            "epoch": self._epoch.generation,
            "world_size": self.comm.size(),
            "ckpt_failures": self.ckpt_failures,
            "metrics": self.metrics_history,
        }

    def _checkpoint(self, step, params, opt_state, *, join: bool = False) -> None:
        """Issue the (async) checkpoint save; ``join=True`` additionally
        waits for durability.  A failed save — surfaced as ``ERR_IO`` from
        the request join, typically when the *previous* save's completion is
        collected — is counted and logged, never silently dropped: training
        continues from device state and ``latest`` stays at the last
        complete step (the production policy for checkpoint I/O errors)."""

        try:
            # collect the previous save's outcome first, so its failure is
            # reported without skipping this step's save
            self.ckpt.wait()
        except errors.IoError as e:
            self._note_ckpt_failure(step, e)
        try:
            self.ckpt.save(
                step,
                {"params": params, "opt": opt_state},
                extra={"step": step},
                # manifests carry the fabric they were written under, so an
                # elastic restore knows it is resharding across world sizes
                meta={
                    "epoch": self._epoch.generation,
                    "world_size": self.comm.size(),
                },
            )
            if join:
                self.ckpt.wait()
        except errors.IoError as e:
            self._note_ckpt_failure(step, e)

    def _note_ckpt_failure(self, step: int, e: Exception) -> None:
        self.ckpt_failures += 1
        tool.pvar_count("ckpt_save_failed")
        log.warning("checkpoint save failed at step %d: %s", step, e)

    def _run_span(self, params, opt_state, step, steps):
        # donated buffers cannot be re-dispatched: stragglers under the
        # persistent engine take the failure path (checkpoint restore)
        retry_safe = not (self.tcfg.persistent and self.tcfg.donate)
        while step < steps:
            with tool.span("repro.trainer.step", step=step):
                if self.injector is not None:
                    joiners = self.injector.take_admissions(step)
                    if joiners:
                        params, opt_state = self._grow(joiners, params, opt_state)
                step_fn = self._compiled
                with tool.span("repro.trainer.batch"):
                    batch = self.pipeline.device_batch(step, self.mesh, self.pcfg)
                    if self.tcfg.persistent:
                        # no-op when device_batch already matches the bound sharding
                        batch = jax.device_put(batch, self._bshard)

                def do_step():
                    new_p, new_o, metrics = step_fn(params, opt_state, batch)
                    with tool.span("repro.trainer.wait"):
                        jax.block_until_ready(metrics["loss"])
                    return new_p, new_o, metrics

                (params, opt_state, metrics), info = self.guard.run(
                    step,
                    do_step,
                    retry_safe=retry_safe,
                    # a step sharing the host with an in-flight checkpoint save
                    # is slow from known interference, not worker sickness
                    exempt=self.ckpt is not None and self.ckpt.pending(),
                )
                step += 1
                if step % self.tcfg.log_every == 0 or step == steps:
                    with tool.span("repro.trainer.record"):
                        pvars = tool.pvar_read()
                        rec = {
                            "step": step,
                            "loss": float(metrics["loss"]),
                            "grad_norm": float(metrics["grad_norm"]),
                            **{k: float(v) for k, v in info.items() if k != "straggled"},
                            "persistent_start": pvars.get("persistent_start", 0),
                            "partition_ready": pvars.get("partition_ready", 0),
                        }
                        self.metrics_history.append(rec)
                        log.info(
                            "step %(step)d loss %(loss).4f "
                            "persistent_start %(persistent_start)d "
                            "partition_ready %(partition_ready)d", rec,
                        )
                if (
                    self.ckpt is not None
                    and self.tcfg.checkpoint_every
                    and step % self.tcfg.checkpoint_every == 0
                ):
                    # the save's file I/O overlaps the following steps; the next
                    # save (or run-end/exit) joins it and surfaces any failure
                    self._checkpoint(step, params, opt_state)
        return params, opt_state, step

    # -- recovery ---------------------------------------------------------------

    def _shrink(self, evt: RankEvicted):
        """The ULFM recovery loop, one method: revoke → ``Group.difference``
        shrink → ``Communicator.from_group`` / cart re-fold rebuild →
        restore from the last committed manifest → continue on the
        survivors.  The old epoch's persistent request dies with it (its
        shardings would raise ``ERR_REQUEST`` on the shrunken mesh); the
        successor epoch rebuilds it lazily in :meth:`compile`."""

        self._epoch = self._epoch.shrink([evt.rank])
        log.warning(
            "epoch %d: %s survivors fold onto %s",
            self._epoch.generation, self._epoch.pool.size(), self._epoch.dims,
        )
        params, opt_state = self.init_state()
        if self.ckpt is not None and self.ckpt.latest_step() is not None:
            params, opt_state, step = self._restore(params, opt_state)
        else:
            step = 0
        tool.pvar_add("elastic:recovery_steps", max(0, evt.step - step))
        self.compile(params, opt_state)
        return params, opt_state, step

    def _grow(self, count: int, params, opt_state):
        """The reverse path: hot-join up to ``count`` spare ranks (world
        minus the epoch's pool), re-fold the elastic data axis, and reshard
        the *live* state onto the grown mesh — growing loses no steps, so
        there is nothing to restore."""

        spares = (
            self._epoch.session.group("repro://world")
            .difference(self._epoch.pool)
            .devices[:count]
        )
        if not spares:
            log.warning("admission requested but no spare ranks; continuing")
            return params, opt_state
        self._epoch = self._epoch.grow(spares)
        self.joins += len(spares)
        tool.pvar_count("elastic:joins")
        log.warning(
            "epoch %d: %d rank(s) joined, folding onto %s",
            self._epoch.generation, len(spares), self._epoch.dims,
        )
        with self.mesh:
            pshard, oshard = self._state_shardings(params, opt_state)
            params = jax.device_put(params, pshard)
            opt_state = jax.device_put(opt_state, oshard)
        self.compile(params, opt_state)
        return params, opt_state

    def _recover(self):
        """Restart protocol: re-form mesh (elastic), restore newest complete
        checkpoint, resume from its step (data is stateless)."""

        if self.ckpt is not None:
            # join the in-flight save first (tolerantly): without this,
            # latest_step() cannot see a save that is mid-commit and
            # recovery would reinitialise, discarding the steps that save
            # was about to preserve
            try:
                self.ckpt.wait()
            except errors.IoError as e:
                self._note_ckpt_failure(-1, e)
        if self.ckpt is None or self.ckpt.latest_step() is None:
            params, opt_state = self.init_state()
            return params, opt_state, 0
        params, opt_state = self.init_state()
        return self._restore(params, opt_state)

    def _restore(self, params, opt_state):
        # collect any in-flight save first, tolerantly: recovery must
        # proceed from the newest COMPLETE checkpoint even if the save that
        # was pending when the worker failed has itself failed
        try:
            self.ckpt.wait()
        except errors.IoError as e:
            self._note_ckpt_failure(-1, e)
        pshard, oshard, _ = self._shardings_for(
            params, opt_state, self.pipeline.device_batch(0, self.mesh, self.pcfg)
        )
        tree, step = self.ckpt.restore(
            {"params": params, "opt": opt_state},
            shardings={"params": pshard, "opt": oshard},
        )
        extra_step = self.ckpt.extra(step).get("step", step)
        return tree["params"], tree["opt"], int(extra_step)
