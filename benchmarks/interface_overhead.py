"""The paper's experiment (Fig. 1), adapted: runtime of the mpiBench
operation set through (a) the raw substrate — bare ``jax.lax`` collectives
inside ``shard_map`` — and (b) this library's modern interface, for varying
message lengths and device counts.  The paper's claim to reproduce: *no
recognizable disparity* between the two.

Extends the figure with the **persistent-vs-per-call** series (MPI 4.0
persistent collectives): for each ``<op>_init``-capable operation it also
measures (c) the per-call path paying full setup — trace + lower + compile —
every call, (d) the one-time ``<op>_init`` setup cost, and (e) the
persistent steady state (``MPI_Start`` re-fires of the compiled executable).
The claim: setup is amortized — persistent steady state ≤ the per-call path.

And with the **RMA series** (MPI 4.0 chapter 12, one-sided): window
``put``/``get``/``accumulate`` against the raw collective each lowers to
(``collective-permute`` / masked ``psum``), plus the window-epoch
(``fence``/``fence``) cost against a bare ``optimization_barrier`` — the
interface tax of the epoch machinery, masking and datatype plumbing.

And with the **neighborhood series** (MPI 4.0 chapter 8, virtual
topologies): the cart ``neighbor_allgather`` against the two hand-written
halo permutes it lowers to (interface tax ≈ 1), and the sparse
``neighbor_alltoall`` against the dense world ``all_to_all`` one would use
without topologies, at equal per-neighbor payload.

And with the **I/O series** (MPI 4.0 chapter 14, nonblocking collective
file I/O): checkpoint write bandwidth, the issue latency of a request-based
async save (the synchronous part is only the device→host gather), and the
**overlap** claim — an async save plus a compute span costs ~max(I/O,
compute) wall-clock where the synchronous form costs the sum — with the
manifest-commit count per save (exactly one: the single sync point).

Run directly (spawns subprocesses with N virtual devices):

    PYTHONPATH=src python -m benchmarks.interface_overhead [--quick]

Writes artifacts/bench/interface_overhead.json + a markdown table.
"""

from __future__ import annotations

import argparse
import json
import os
import subprocess
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]
OUT = ROOT / "artifacts" / "bench"

# the measurement body executed in a subprocess with N virtual devices
CHILD = r"""
import json, sys, time
import jax, jax.numpy as jnp
from jax.sharding import PartitionSpec as P
from repro import core as mpx

msg_lens = json.loads(sys.argv[1])   # element counts (f32)
reps = int(sys.argv[2])

comm = mpx.world()
N = comm.size()
name = comm.axis_names[0]
lax = jax.lax

def _perm():
    return [(i, (i + 1) % N) for i in range(N)]

# (op, raw-lax implementation, interface implementation) — the mpiBench set
OPS = {
    "barrier":        (lambda x: lax.psum(jnp.zeros((), x.dtype), name),
                       lambda x: (comm.barrier(), x)[1] * 0.0),
    "broadcast":      (lambda x: lax.all_gather(x[None] * 0, name)[0] + x,
                       lambda x: comm.broadcast(x, root=0)),
    "allreduce":      (lambda x: lax.psum(x, name),
                       lambda x: comm.allreduce(x)),
    "reduce":         (lambda x: lax.psum(x, name),
                       lambda x: comm.reduce(x, root=0)),
    "allgather":      (lambda x: lax.all_gather(x, name),
                       lambda x: comm.allgather(x)),
    "gather":         (lambda x: lax.all_gather(x, name),
                       lambda x: comm.gather(x, root=0)),
    "scatter":        (lambda x: lax.dynamic_slice_in_dim(
                           lax.all_to_all(x, name, 0, 0, tiled=True),
                           0, x.shape[0] // N, axis=0),
                       lambda x: comm.scatter(x, root=0)),
    "alltoall":       (lambda x: lax.all_to_all(x, name, 0, 0, tiled=True),
                       lambda x: comm.alltoall(x)),
    "reduce_scatter": (lambda x: lax.psum_scatter(x, name, tiled=True),
                       lambda x: comm.reduce_scatter(x)),
    "sendrecv":       (lambda x: lax.ppermute(x, name, _perm()),
                       lambda x: comm.shift(x, offset=1)),
    "scan":           (lambda x: jax.lax.associative_scan(
                           jnp.add, lax.all_gather(x, name), axis=0)[
                           lax.axis_index(name)],
                       lambda x: comm.scan(x)),
}

def bench(fn, n_elems):
    x = jnp.ones((max(N, n_elems // N * N),), jnp.float32)  # divisible shape
    jitted = comm.spmd(fn)
    out = jitted(x); jax.block_until_ready(out)              # compile + warm
    t0 = time.perf_counter()
    for _ in range(reps):
        out = jitted(x)
    jax.block_until_ready(out)
    return (time.perf_counter() - t0) / reps * 1e6           # us/call

# ops with persistent (MPI_*_init) constructors: persistent-vs-per-call series
PERSISTENT_OPS = ("allreduce", "allgather", "reduce_scatter", "alltoall")

def bench_persistent(op, n_elems):
    x = jnp.ones((max(N, n_elems // N * N),), jnp.float32)
    iface = OPS[op][1]
    # (d) one-time setup: trace + lower + AOT compile + first fire
    t0 = time.perf_counter()
    req = getattr(comm, op + "_init")(x)
    call = req.requests[0]                                   # the MPI_Start path
    out = call(x); jax.block_until_ready(out)
    init_us = (time.perf_counter() - t0) * 1e6
    # (e) persistent steady state: re-fire the compiled executable
    t0 = time.perf_counter()
    for _ in range(reps):
        out = call(x)
    jax.block_until_ready(out)
    persist_us = (time.perf_counter() - t0) / reps * 1e6
    # (c) per-call path: pay full setup every call (a fresh function object
    # defeats the jit cache, exactly what a non-persistent MPI op does to
    # its argument-list setup)
    pc_reps = min(reps, 3)
    t0 = time.perf_counter()
    for _ in range(pc_reps):
        fresh = comm.spmd((lambda f: lambda xx: f(xx))(iface))
        out = fresh(x)
    jax.block_until_ready(out)
    percall_us = (time.perf_counter() - t0) / pc_reps * 1e6
    return init_us, persist_us, percall_us

# RMA series: window operations vs the raw collective each lowers to, and
# the window-epoch cost vs a bare optimization barrier
from repro.core import onesided
from repro.core.descriptors import ReduceOp

RING = _perm()

def _win(x):
    w = onesided.Window(comm, x)
    w.fence()
    return w

RMA_OPS = {
    "win_put":        (lambda x: lax.ppermute(x, name, RING),
                       lambda x: _win(x).put(x, RING).fence().buffer),
    # get(RING) lowers to the same s->d permute as put (origin d reads s)
    "win_get":        (lambda x: lax.ppermute(x, name, RING),
                       lambda x: _win(x).get(RING)),
    "win_accumulate": (lambda x: jnp.where(lax.axis_index(name) == 0,
                                           x + lax.psum(x, name), x),
                       lambda x: _win(x).accumulate(x, target=0).fence().buffer),
    "win_fence":      (lambda x: lax.optimization_barrier(x),
                       lambda x: _win(x).fence().buffer),
}

# neighborhood series (MPI 4.0 ch. 8): (a) interface tax of the cart
# neighbor_allgather vs the two hand-written halo permutes it lowers to
# (claim: ~1.0), and (b) the sparse neighbor_alltoall vs the dense world
# all_to_all you would use without topologies, at equal per-neighbor
# payload (claim: < 1 once N outgrows the degree)
from repro.core import topology

cart = topology.cart_create(comm, (N,), (True,))
PLUS = [(i, (i + 1) % N) for i in range(N)]
MINUS = [(i, (i - 1) % N) for i in range(N)]

def bench_on(spmd, fn, x):
    jitted = spmd(fn)
    out = jitted(x); jax.block_until_ready(out)
    t0 = time.perf_counter()
    for _ in range(reps):
        out = jitted(x)
    jax.block_until_ready(out)
    return (time.perf_counter() - t0) / reps * 1e6

def raw_halo(x):
    return jnp.stack([lax.ppermute(x, name, PLUS), lax.ppermute(x, name, MINUS)])

def bench_neighbor(n_elems):
    blk = max(1, n_elems // N)
    x_blk = jnp.ones((blk,), jnp.float32)
    x_nb = jnp.ones((2, blk), jnp.float32)
    x_dense = jnp.ones((N * blk,), jnp.float32)
    return [
        {"op": "neighbor_allgather", "series": "neighbor",
         "raw_us": bench_on(comm.spmd, raw_halo, x_blk),
         "iface_us": bench_on(cart.spmd,
                              lambda x: cart.neighbor_allgather(x).get(), x_blk)},
        {"op": "neighbor_alltoall", "series": "neighbor",
         "raw_us": bench_on(comm.spmd,
                            lambda x: lax.all_to_all(x, name, 0, 0, tiled=True),
                            x_dense),
         "iface_us": bench_on(cart.spmd,
                              lambda x: cart.neighbor_alltoall(x).get(), x_nb)},
    ]

rows = []
for n in msg_lens:
    for op, (raw, iface) in OPS.items():
        row = {
            "devices": N, "msg_elems": n, "op": op,
            "raw_us": bench(raw, n), "iface_us": bench(iface, n),
        }
        if op in PERSISTENT_OPS:
            row["init_us"], row["persist_us"], row["percall_us"] = bench_persistent(op, n)
        rows.append(row)
    for op, (raw, iface) in RMA_OPS.items():
        rows.append({
            "devices": N, "msg_elems": n, "op": op, "series": "rma",
            "raw_us": bench(raw, n), "iface_us": bench(iface, n),
        })
    for row in bench_neighbor(n):
        rows.append({"devices": N, "msg_elems": n, **row})
print("RESULT " + json.dumps(rows))
"""


# ring-attention series: the fused path (cart ring + TraceFuture/when_all
# rotate-while-compute + custom_vjp, kernels/ring_attention/ops.py) against
# the raw hand-written schedule — bare lax.ppermute and the same online-block
# update, no futures, no cart, no VJP boundary.  Both are trace-time
# abstractions over the same dataflow, so the claim is the zero-overhead one:
# tax ~ 1.0 (gated at <= 1.05 in baseline.json).
RING_CHILD = r"""
import gc, json, sys, time
import jax, jax.numpy as jnp
from jax import lax
from jax.sharding import PartitionSpec as P
from repro import core as mpx
from repro.core import _compat, topology
from repro.kernels.ring_attention import kernel as rk
from repro.kernels.ring_attention import ops as ring_ops

reps = int(sys.argv[1])
comm = mpx.world()
N = comm.size()
cart = topology.cart_create(comm, (N,), (True,), tag="repro://cart/ring-bench")
name = cart.axis_names[0]
mesh = cart.mesh
B, S, H, D = 1, 128 * N, 4, 64
shard = S // N
scale = D ** -0.5
spec = P(None, name, None, None)
perm = [(i, (i + 1) % N) for i in range(N)]

def fused(q, k, v):
    return ring_ops.ring_attention(cart, q, k, v, causal=True, impl="ref")

def raw(q, k, v):
    qt = q.transpose(0, 2, 1, 3)
    kv = jnp.stack([k.transpose(0, 2, 1, 3), v.transpose(0, 2, 1, 3)])
    idx = lax.axis_index(name)
    m = jnp.full((B, H, shard, 1), rk.NEG_INF, jnp.float32)
    l = jnp.zeros((B, H, shard, 1), jnp.float32)
    acc = jnp.zeros((B, H, shard, D), jnp.float32)
    for step in range(N):
        src = jnp.mod(idx - step, N)
        m, l, acc = rk.ring_step_ref(
            qt, kv[0], kv[1], m, l, acc,
            q_offset=(idx * shard).astype(jnp.int32),
            k_offset=(src * shard).astype(jnp.int32),
            kv_len=jnp.int32(shard), scale=scale, causal=True,
        )
        if step < N - 1:
            kv = lax.ppermute(kv, name, perm)
    return (acc / jnp.maximum(l, 1e-30)).transpose(0, 2, 1, 3).astype(q.dtype)

ks = jax.random.split(jax.random.PRNGKey(0), 3)
q = jax.random.normal(ks[0], (B, S, H, D))
k = jax.random.normal(ks[1], (B, S, H, D))
v = jax.random.normal(ks[2], (B, S, H, D))

def jit_of(fn):
    return jax.jit(_compat.shard_map(
        fn, mesh=mesh, in_specs=(spec, spec, spec), out_specs=spec))

with mesh:
    j_fused, j_raw = jit_of(fused), jit_of(raw)
    import numpy as np
    np.testing.assert_allclose(                     # same math before timing
        np.asarray(j_fused(q, k, v)), np.asarray(j_raw(q, k, v)),
        atol=1e-5, rtol=1e-5)
    # interleaved chunks so machine drift hits both sides alike; median ratio
    chunk, nchunks = max(3, reps // 5), 5
    gc.collect(); gc.disable()
    try:
        ftimes, rtimes = [], []
        for _ in range(nchunks):
            t0 = time.perf_counter()
            for _ in range(chunk):
                out = j_fused(q, k, v)
            jax.block_until_ready(out)
            ftimes.append((time.perf_counter() - t0) / chunk * 1e6)
            t0 = time.perf_counter()
            for _ in range(chunk):
                out = j_raw(q, k, v)
            jax.block_until_ready(out)
            rtimes.append((time.perf_counter() - t0) / chunk * 1e6)
    finally:
        gc.enable()
ratios = sorted(f / r for f, r in zip(ftimes, rtimes))
tax = ratios[len(ratios) // 2]
raw_us = sorted(rtimes)[len(rtimes) // 2]
rows = [{"devices": N, "msg_elems": S, "op": "ring_attention",
         "series": "ring", "raw_us": raw_us, "iface_us": raw_us * tax}]
print("RESULT " + json.dumps(rows))
"""


def ring_series(reps: int) -> list[dict]:
    env = {
        **os.environ,
        "XLA_FLAGS": "--xla_force_host_platform_device_count=8",
        "JAX_PLATFORMS": "cpu",  # virtual devices: a CPU measurement
        "PYTHONPATH": str(ROOT / "src"),
    }
    proc = subprocess.run(
        [sys.executable, "-c", RING_CHILD, str(reps)],
        capture_output=True, text=True, env=env, timeout=1800, cwd=str(ROOT),
    )
    if proc.returncode != 0:
        raise RuntimeError(proc.stderr[-3000:])
    for line in proc.stdout.splitlines():
        if line.startswith("RESULT "):
            return json.loads(line[len("RESULT "):])
    raise RuntimeError("no RESULT line")


def run(devices: int, msg_lens: list[int], reps: int) -> list[dict]:
    env = {
        **os.environ,
        "XLA_FLAGS": f"--xla_force_host_platform_device_count={devices}",
        "JAX_PLATFORMS": "cpu",  # virtual devices: a CPU measurement
        "PYTHONPATH": str(ROOT / "src"),
    }
    proc = subprocess.run(
        [sys.executable, "-c", CHILD, json.dumps(msg_lens), str(reps)],
        capture_output=True, text=True, env=env, timeout=1800, cwd=str(ROOT),
    )
    if proc.returncode != 0:
        raise RuntimeError(proc.stderr[-3000:])
    for line in proc.stdout.splitlines():
        if line.startswith("RESULT "):
            return json.loads(line[len("RESULT "):])
    raise RuntimeError("no RESULT line")


def geomean(xs):
    import math

    return math.exp(sum(math.log(max(x, 1e-9)) for x in xs) / len(xs))


def io_series(reps: int, quick: bool) -> list[dict]:
    """Checkpoint I/O bandwidth + async-overlap measurements (main process —
    file I/O needs no virtual devices)."""

    import tempfile
    import time

    import jax
    import jax.numpy as jnp

    sys.path.insert(0, str(ROOT / "src"))  # when PYTHONPATH was not exported

    from repro.checkpoint import CheckpointManager
    from repro.core import tool

    sizes = [1 << 18, 1 << 20] if quick else [1 << 18, 1 << 20, 1 << 22]
    reps = max(2, min(reps, 5))
    x = jnp.ones((512, 512), jnp.float32)
    step_fn = jax.jit(lambda a: a @ a.T / 512.0 + 1.0)
    jax.block_until_ready(step_fn(x))

    rows = []
    for n in sizes:
        # two dtype buckets (f32 + bf16) → two I/O requests per save
        state = {
            "w32": jnp.arange(n, dtype=jnp.float32),
            "w16": jnp.ones((n // 2,), jnp.bfloat16),
        }
        jax.block_until_ready(state)
        nbytes = 4 * n + 2 * (n // 2)

        with tempfile.TemporaryDirectory() as d:
            mgr = CheckpointManager(d, keep=2, async_save=False, verify=False)
            mgr.save(0, state)  # warm path/allocators
            c0 = tool.pvar_read().get("io_manifest_commit", 0)
            t0 = time.perf_counter()
            for r in range(reps):
                mgr.save(r + 1, state)
            sync_s = (time.perf_counter() - t0) / reps
            commits = (tool.pvar_read().get("io_manifest_commit", 0) - c0) / reps

        # calibrate a compute span comparable to one save
        t0 = time.perf_counter()
        jax.block_until_ready(step_fn(x))
        step_s = max(time.perf_counter() - t0, 1e-5)
        k = max(1, int(sync_s / step_s))

        def compute():
            y = x
            for _ in range(k):
                y = step_fn(y)
            jax.block_until_ready(y)

        # serial: blocking save then compute; overlapped: async save + the
        # same compute while the I/O requests run, then join
        with tempfile.TemporaryDirectory() as d:
            mgr = CheckpointManager(d, keep=2, async_save=False, verify=False)
            mgr.save(0, state)
            t0 = time.perf_counter()
            for r in range(reps):
                mgr.save(r + 1, state)
                compute()
            serial_s = (time.perf_counter() - t0) / reps
        with tempfile.TemporaryDirectory() as d:
            mgr = CheckpointManager(d, keep=2, async_save=True, verify=False)
            mgr.save(0, state)
            mgr.wait()
            issue_us = []
            t0 = time.perf_counter()
            for r in range(reps):
                # join the previous save outside the issue timer: save()'s
                # internal wait would otherwise charge residual I/O from the
                # last iteration to this iteration's "issue latency"
                mgr.wait()
                t1 = time.perf_counter()
                mgr.save(r + 1, state)
                issue_us.append((time.perf_counter() - t1) * 1e6)
                compute()
            mgr.wait()
            overlap_s = (time.perf_counter() - t0) / reps

        rows.append(
            {
                "series": "io",
                "state_mb": nbytes / 2**20,
                "sync_save_ms": sync_s * 1e3,
                "write_MBps": nbytes / 2**20 / sync_s,
                "issue_us": sum(issue_us) / len(issue_us),
                "serial_ms": serial_s * 1e3,
                "overlapped_ms": overlap_s * 1e3,
                "overlap_ratio": overlap_s / serial_s,
                "manifest_commits_per_save": commits,
            }
        )
        print(f"io: state={nbytes / 2**20:.1f}MB done")
    return rows


def serving_series(reps: int) -> list[dict]:
    """Continuous-batching scheduler tax: a full `engine.step()` — admission
    check, block-growth accounting, persistent decode re-fire, sampling,
    retirement bookkeeping — against the raw loop body it wraps (the same
    compiled decode executable fired directly, sampled and materialized).
    The claim: the scheduler adds <= 10% per step (main process, one
    device — the decode step itself is the unit under test)."""

    import gc
    import time

    import numpy as np

    sys.path.insert(0, str(ROOT / "src"))  # when PYTHONPATH was not exported

    import jax

    from repro.configs.base import ModelConfig, ParallelConfig
    from repro.launch.mesh import make_host_communicator
    from repro.runtime.engine import Engine, EngineConfig
    from repro.runtime.server import Server, ServerConfig

    chunk, nchunks = max(10, reps // 3), 8
    # a realistically-sized decode step (a few ms): the scheduler's per-step
    # cost is constant, so a toy-model step would overstate the tax by an
    # order of magnitude against any real serving workload
    cfg = ModelConfig(
        name="bench-engine", family="dense", num_layers=4, d_model=256,
        num_heads=8, num_kv_heads=4, head_dim=32, d_ff=1024, vocab_size=2048,
        dtype="float32",
    )
    # budget deep enough that no row retires inside the measurement window
    scfg = ServerConfig(
        max_batch=4, max_new_tokens=nchunks * chunk + 16, temperature=0.0
    )
    srv = Server(cfg, ParallelConfig(), scfg, make_host_communicator())
    rng = np.random.default_rng(0)

    # two engines over the same server (shared compiles): one driven by the
    # scheduler, one donating its state to the raw loop — so the engine and
    # raw chunks can be INTERLEAVED and machine drift hits both alike
    def fresh_engine():
        e = Engine(srv, EngineConfig(prompt_bucket=8, block_tokens=4))
        for _ in range(scfg.max_batch):
            e.submit(rng.integers(1, 128, size=(8,), dtype=np.int32))
        for _ in range(5):
            e.step()                                 # admit + warm compiles
        return e

    eng = fresh_engine()
    raw = fresh_engine()
    cache, tok = raw.cache, raw.tok
    decode = raw._decode_req
    key = jax.random.PRNGKey(0)

    # interleaved chunk pairs with GC parked: each pair times the engine
    # loop and the raw loop back-to-back in the same load window, so machine
    # drift cancels inside the pair; the tax is the trimmed mean of the pair
    # ratios (extremes are windows where one side ate a scheduler quantum —
    # the claim is about work, not jitter), reported at the median raw time
    gc.collect()
    gc.disable()
    try:
        etimes, rtimes = [], []
        for _ in range(nchunks):
            t0 = time.perf_counter()
            for _ in range(chunk):
                eng.step()
            etimes.append((time.perf_counter() - t0) / chunk * 1e6)
            with srv.mesh:
                t0 = time.perf_counter()
                for _ in range(chunk):
                    logits, cache = decode(srv.params, cache, tok)
                    t = srv._sample(logits, key)
                    tok = t[:, None]
                    np.asarray(t)
                rtimes.append((time.perf_counter() - t0) / chunk * 1e6)
    finally:
        gc.enable()
    ratios = sorted(e / r for e, r in zip(etimes, rtimes))
    inner = ratios[2:-2] if len(ratios) >= 6 else ratios
    tax = sum(inner) / len(inner)
    raw_us = sorted(rtimes)[len(rtimes) // 2]
    engine_us = raw_us * tax

    return [{
        "devices": 1, "msg_elems": 0, "op": "engine_step", "series": "serving",
        "raw_us": raw_us, "iface_us": engine_us,
    }]


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true")
    ap.add_argument("--reps", type=int, default=30)
    args = ap.parse_args(argv)

    device_counts = [1, 2, 4, 8]
    msg_lens = [2 ** n for n in range(1, 18, 4 if args.quick else 2)]
    if args.quick:
        device_counts = [1, 8]

    all_rows = []
    for d in device_counts:
        all_rows += run(d, msg_lens, args.reps)
        print(f"devices={d}: done")
    io_rows = io_series(args.reps, args.quick)
    # fresh subprocess: the scheduler-tax measurement is Python-loop bound
    # and the checkpoint series leaves worker threads behind that would
    # bleed GIL time into it asymmetrically
    proc = subprocess.run(
        [sys.executable, "-c",
         "import json, sys\n"
         "from benchmarks.interface_overhead import serving_series\n"
         "print('RESULT ' + json.dumps(serving_series(int(sys.argv[1]))))",
         str(args.reps)],
        capture_output=True, text=True, timeout=1800, cwd=str(ROOT),
        env={**os.environ, "PYTHONPATH": str(ROOT / "src"), "JAX_PLATFORMS": "cpu"},
    )
    if proc.returncode != 0:
        raise RuntimeError(proc.stderr[-3000:])
    serving_rows = next(
        json.loads(line[len("RESULT "):])
        for line in proc.stdout.splitlines() if line.startswith("RESULT ")
    )
    all_rows += serving_rows
    ring_rows = ring_series(args.reps)
    all_rows += ring_rows

    OUT.mkdir(parents=True, exist_ok=True)
    (OUT / "interface_overhead.json").write_text(json.dumps(all_rows, indent=1))
    (OUT / "io_overhead.json").write_text(json.dumps(io_rows, indent=1))

    # paper-style summary: geometric mean over the op set per (devices, len)
    lines = ["| devices | msg elems | raw µs (geo) | interface µs (geo) | ratio |",
             "|---|---|---|---|---|"]
    worst = 0.0
    for d in device_counts:
        for n in msg_lens:
            rows = [r for r in all_rows if r["devices"] == d
                    and r["msg_elems"] == n and "series" not in r]
            g_raw = geomean([r["raw_us"] for r in rows])
            g_ifc = geomean([r["iface_us"] for r in rows])
            ratio = g_ifc / g_raw
            worst = max(worst, ratio)
            lines.append(f"| {d} | {n} | {g_raw:.1f} | {g_ifc:.1f} | {ratio:.3f} |")
    # persistent-vs-per-call series (MPI 4.0 persistent collectives):
    # per-call pays setup every call; persistent amortizes it into *_init
    plines = ["", "| devices | msg elems | per-call µs (geo) | init µs (geo) | "
              "persistent µs (geo) | amortization |",
              "|---|---|---|---|---|---|"]
    worst_persist = 0.0
    for d in device_counts:
        for n in msg_lens:
            prows = [r for r in all_rows
                     if r["devices"] == d and r["msg_elems"] == n and "persist_us" in r]
            if not prows:
                continue
            g_pc = geomean([r["percall_us"] for r in prows])
            g_init = geomean([r["init_us"] for r in prows])
            g_p = geomean([r["persist_us"] for r in prows])
            ratio = g_p / g_pc
            worst_persist = max(worst_persist, ratio)
            plines.append(
                f"| {d} | {n} | {g_pc:.1f} | {g_init:.1f} | {g_p:.1f} | {ratio:.4f} |"
            )
    # RMA series: window ops vs their raw lowering + epoch cost
    rlines = ["", "| devices | msg elems | op | raw µs | window µs | ratio |",
              "|---|---|---|---|---|---|"]
    worst_rma = 0.0
    for d in device_counts:
        for n in msg_lens:
            for r in all_rows:
                if (r["devices"] != d or r["msg_elems"] != n
                        or r.get("series") != "rma"):
                    continue
                ratio = r["iface_us"] / max(r["raw_us"], 1e-9)
                worst_rma = max(worst_rma, ratio)
                rlines.append(
                    f"| {d} | {n} | {r['op']} | {r['raw_us']:.1f} | "
                    f"{r['iface_us']:.1f} | {ratio:.3f} |"
                )
    # neighborhood series: interface tax vs the raw halo permutes, and the
    # sparse-vs-dense claim (neighbor exchange vs world alltoall at equal
    # per-neighbor payload)
    nlines = ["", "| devices | msg elems | op | raw µs | neighbor µs | ratio |",
              "|---|---|---|---|---|---|"]
    neigh_ratios = []
    for d in device_counts:
        for n in msg_lens:
            for r in all_rows:
                if (r["devices"] != d or r["msg_elems"] != n
                        or r.get("series") != "neighbor"):
                    continue
                ratio = r["iface_us"] / max(r["raw_us"], 1e-9)
                if r["op"] == "neighbor_allgather":
                    neigh_ratios.append(ratio)
                nlines.append(
                    f"| {d} | {n} | {r['op']} | {r['raw_us']:.1f} | "
                    f"{r['iface_us']:.1f} | {ratio:.3f} |"
                )
    # I/O series: checkpoint bandwidth + async overlap (single manifest
    # commit per save — the sync-point count is part of the claim)
    iolines = ["", "| state MB | sync save ms | MB/s | issue µs | serial ms | "
               "overlapped ms | overlap | commits/save |",
               "|---|---|---|---|---|---|---|---|"]
    worst_overlap = 0.0
    worst_commits = 0.0
    for r in io_rows:
        worst_overlap = max(worst_overlap, r["overlap_ratio"])
        worst_commits = max(worst_commits, r["manifest_commits_per_save"])
        iolines.append(
            f"| {r['state_mb']:.1f} | {r['sync_save_ms']:.1f} | "
            f"{r['write_MBps']:.0f} | {r['issue_us']:.0f} | "
            f"{r['serial_ms']:.1f} | {r['overlapped_ms']:.1f} | "
            f"{r['overlap_ratio']:.3f} | {r['manifest_commits_per_save']:.1f} |"
        )
    # serving series: continuous-batching scheduler tax over the raw
    # persistent-decode loop body it wraps
    slines = ["", "| op | raw step µs | engine step µs | scheduler tax |",
              "|---|---|---|---|"]
    serving_ratio = 0.0
    for r in serving_rows:
        ratio = r["iface_us"] / max(r["raw_us"], 1e-9)
        serving_ratio = max(serving_ratio, ratio)
        slines.append(f"| {r['op']} | {r['raw_us']:.1f} | {r['iface_us']:.1f} | "
                      f"{ratio:.3f} |")
    # ring-attention series: the fused futures-scheduled ring vs the raw
    # hand-written ppermute schedule (same math, same collectives)
    glines = ["", "| devices | seq | raw ring µs | fused ring µs | ring tax |",
              "|---|---|---|---|---|"]
    ring_tax = 0.0
    for r in ring_rows:
        ratio = r["iface_us"] / max(r["raw_us"], 1e-9)
        ring_tax = max(ring_tax, ratio)
        glines.append(
            f"| {r['devices']} | {r['msg_elems']} | {r['raw_us']:.1f} | "
            f"{r['iface_us']:.1f} | {ratio:.3f} |"
        )
    table = "\n".join(lines + plines + rlines + nlines + iolines + slines + glines)
    (OUT / "interface_overhead.md").write_text(table + "\n")
    print(table)
    print(f"worst geomean ratio: {worst:.3f} (paper claim: ~1.0, 'no recognizable disparity')")
    print(f"worst persistent/per-call ratio: {worst_persist:.4f} "
          "(claim: <= 1.0 — setup cost amortized by *_init + Start)")
    print(f"worst RMA/raw ratio: {worst_rma:.3f} "
          "(window epoch + masking tax over the bare collective)")
    if neigh_ratios:
        print(f"neighbor_allgather/raw-halo geomean ratio: "
              f"{geomean(neigh_ratios):.3f} "
              "(ch. 8 interface tax over hand-written halo permutes)")
    print(f"worst async/serial checkpoint ratio: {worst_overlap:.3f} "
          "(claim: < 1.0 — I/O requests overlap compute; "
          f"manifest commits per save: {worst_commits:.1f}, claim: exactly 1)")
    print(f"continuous-batching scheduler tax: {serving_ratio:.3f} "
          "(claim: <= 1.10 — engine.step() over the raw decode loop body)")
    print(f"ring attention tax: {ring_tax:.3f} "
          "(claim: <= 1.05 — fused futures-scheduled ring over the raw "
          "hand-written ppermute schedule)")
    ok = (worst_persist <= 1.0 and worst_commits == 1.0
          and serving_ratio <= 1.10 and ring_tax <= 1.05)
    return 0 if ok else 1


if __name__ == "__main__":
    raise SystemExit(main())
