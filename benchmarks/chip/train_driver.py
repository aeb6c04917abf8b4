"""The training cells: the program's ``Trainer`` and its persistent step,
on as many chips as the cell asks for (``--mesh auto``: every chip on the
FSDP data axis).

Set-up builds one trainer (weights from the seed), compiles its step, and
drives it through its first three steps with the trainer's own loop
(``Trainer._run_span``, the body of ``Trainer.run``), reading what the
check needs between them; the same trainer and state then run the window.
The feed draws each step's tokens on the device from the seed and the step
number, so every row of every step differs and the reference can draw the
same ones.
"""

from __future__ import annotations

import gc
import math
import time

import jax
import jax.numpy as jnp
import numpy as np

import bench_weights as bw
import harness

CHECK_STEPS = 3


class Feed:
    """Stands in for the trainer's token pipeline: ``device_batch(step)``
    draws uniform tokens below the real vocabulary from (seed, step)."""

    def __init__(self, seed: int, batch: int, seq: int, vocab: int):
        key = bw.seed_key(seed)
        self._key = jax.random.fold_in(key, 0x7EED)
        self._draw = jax.jit(lambda k, s: {"tokens": jax.random.randint(
            jax.random.fold_in(k, s), (batch, seq), 0, vocab, jnp.int32)})

    def device_batch(self, step, mesh=None, pcfg=None):
        return self._draw(self._key, step)

    def host_batch(self, step):
        return {"tokens": np.asarray(self.device_batch(step)["tokens"])}


def hyper(job: dict) -> dict:
    return {k: job[k] for k in ("lr", "warmup_steps", "total_steps", "clip_norm",
                                "weight_decay", "b1", "b2", "eps")}


def build(fam, c: dict, job: dict, seed: int, chips: int):
    from repro.launch.mesh import make_host_communicator
    from repro.runtime.faults import StragglerPolicy
    from repro.runtime.trainer import Trainer, TrainerConfig

    tcfg = TrainerConfig(
        steps=job["total_steps"], lr=job["lr"], warmup_steps=job["warmup_steps"],
        clip_norm=job["clip_norm"], weight_decay=job["weight_decay"],
        seed=bw.weight_seed(seed), checkpoint_dir=None, log_every=1,
    )
    comm = make_host_communicator(chips, 1)
    # The straggler deadline is off.  With donated buffers and no checkpoint
    # the trainer answers a step over three times the median (a host stall
    # of a second or two, seen now and then on the chip's host) by starting
    # again from step 0, which ends a measured window; a step that does not
    # straggle pays the same either way.
    with bw.program_weights(fam, c):
        trainer = Trainer(fam.program_config(c), harness.parallel_config(c), tcfg,
                          comm, seq_len=job["seq_len"], global_batch=job["batch"],
                          straggler=StragglerPolicy(deadline_factor=math.inf))
    if (trainer.opt.b1, trainer.opt.b2, trainer.opt.eps) != (job["b1"], job["b2"], job["eps"]):
        raise ValueError("the trainer's AdamW moments differ from the job file")
    trainer.pipeline = Feed(seed, job["batch"], job["seq_len"], c["vocab_size"])
    return trainer


def _norms(tree, fam, c: dict):
    """Norm of each leaf in float32; a stacked leaf gets one per layer."""

    def one(path, x):
        x = x.astype(jnp.float32)
        if bw.leaf(fam, c, path).stacked:
            return jnp.sqrt(jnp.sum(x * x, axis=tuple(range(1, x.ndim))))
        return jnp.linalg.norm(x.ravel())

    return jax.tree_util.tree_map_with_path(one, tree)


def _keyed(norms, fam, c: dict, scale: float = 1.0) -> dict:
    """Per-weight norms keyed as the family's reference keys them, by
    :func:`bench_weights.check_key`: ``name`` or ``name.layer``."""

    out = {}
    for path, v in jax.tree_util.tree_flatten_with_path(norms)[0]:
        lf = bw.leaf(fam, c, path)
        v = np.asarray(v)
        for i, x in enumerate(v if lf.stacked else [v]):
            out[bw.check_key(lf, i)] = float(x) * scale
    return out


def run(spec: dict, seed: int, seconds: float, traced: bool, setup_t0: float,
        fault=None) -> dict:
    c, job, chips, fam = spec["config"], spec["traffic"], spec["cell"]["chips"], spec["family"]
    counters = harness.Counters()
    trainer = build(fam, c, job, seed, chips)
    if fault is not None:
        fault(trainer)
    params, opt_state = trainer.init_state()
    trainer.compile(params, opt_state)
    start = jax.jit(bw.program_init(jax.eval_shape(lambda: params), fam, c))
    norms = jax.jit(lambda tree: _norms(tree, fam, c))

    # the first steps, through the trainer's own loop; the check reads the
    # optimizer's first moment after step 1 (the first gradient, as the
    # optimizer got it) and the weights' change after step 3
    check = {"losses": []}
    step = 0
    while step < CHECK_STEPS:
        params, opt_state, step = trainer._run_span(params, opt_state, step, step + 1)
        if step == 1:
            check["grad_norms"] = _keyed(norms(opt_state.mu), fam, c, 1.0 / (1.0 - job["b1"]))
    # the change's norms in one program, so that no second copy of the
    # weights is held beside the training state
    change = jax.jit(lambda p, k: _norms(jax.tree.map(
        lambda a, b: a.astype(jnp.float32) - b.astype(jnp.float32), p, start(k)), fam, c))
    check["change_norms"] = _keyed(change(params, bw.seed_key(seed)), fam, c)
    check["losses"] = [m["loss"] for m in trainer.metrics_history[:CHECK_STEPS]]
    jax.block_until_ready(params)

    gc.collect()
    gc.freeze()
    before = counters.read()
    setup_s = time.perf_counter() - setup_t0
    trace_dir = None
    if traced:
        import tempfile

        seconds = min(seconds, job["trace_seconds"])
        trace_dir = tempfile.mkdtemp(prefix="bench_trace_")
        jax.profiler.start_trace(trace_dir)
    first = step
    steps = []
    with harness.span("bench.window", traced):
        t0 = time.perf_counter()
        while time.perf_counter() - t0 < seconds:
            with harness.span("bench.step", traced):
                params, opt_state, step = trainer._run_span(params, opt_state, step, step + 1)
            steps.append(time.perf_counter() - t0)
        t1 = time.perf_counter() - t0
    gc.unfreeze()
    if traced:
        jax.profiler.stop_trace()
    in_window = harness.Counters.diff(counters.read(), before)
    peak = harness.memory_peak(jax.devices()[:chips])
    losses = [m["loss"] for m in trainer.metrics_history[CHECK_STEPS:]]
    del params, opt_state, trainer
    gc.collect()
    return {
        "setup_s": setup_s,
        "window": {"seconds": t1, "steps": step - first, "step_ends": steps,
                   "tokens": (step - first) * job["batch"] * job["seq_len"],
                   "losses_finite": bool(np.all(np.isfinite(losses)))},
        "in_window": in_window,
        "memory_peak_bytes": peak,
        "check": check,
        "trace_dir": trace_dir,
    }


def reference(fam, c: dict, job: dict, seed: int, fp8: bool = False,
              rows_used: float = 1.0) -> dict:
    feed = Feed(seed, job["batch"], job["seq_len"], c["vocab_size"])
    batches = [feed.host_batch(s)["tokens"] for s in range(CHECK_STEPS)]
    return fam.train(seed, c, batches, hyper(job), fp8=fp8, rows_used=rows_used)


def end_to_end(res: dict, job: dict) -> dict:
    win = res["window"]
    return {
        "train_tokens_per_s": win["tokens"] / win["seconds"],
        "_counts": {"steps": win["steps"], "losses_finite": win["losses_finite"],
                    "step_s": _step_spread(win["step_ends"])},
    }


def _step_spread(ends: list) -> dict:
    """Median and longest step of the window, to show a host stall."""

    d = np.diff([0.0, *ends])
    return {"median": float(np.median(d)), "longest": float(d.max())} if len(d) else {}
