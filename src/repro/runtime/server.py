"""The Server: batched prefill + decode serving loop.

Continuous-batching-lite: requests are grouped into fixed-size batches
(padded to ``max_batch``), prefilled once, then decoded step-by-step over
the persistent KV/SSM cache.  The cache is sharded per
``repro.sharding.rules`` (batch over data axes, heads or sequence over model
axis; int8 cache when configured).

**Persistent decode engine**: the single-token decode step — the serving hot
loop — is built once per argument signature as a
:class:`~repro.core.futures.PersistentRequest` (AOT lower + compile, cache
donated) and re-fired ``MPI_Start``-style for every token; the prefill step
is persistent per prompt-shape bucket the same way.  Steady-state decode can
never re-trace (``trace:decode_step`` pvar stays at one per signature).

**Disaggregated prefill/decode** (:class:`DisaggregatedServer`): the serving
process set is split into a *prefill* group and a *decode* group (PR 1 group
algebra); prefill ranks compute the KV cache and ``rput`` it page-by-page
into an RMA window on the decode ranks (C1 one-sided, MPI 4.0 chapter 12),
and the decode group rides its existing persistent decode request.  At
``temperature=0`` the disaggregated pipeline is token-for-token identical to
the single-group :meth:`Server.generate`.
"""

from __future__ import annotations

import dataclasses
import time
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh
from jax.sharding import PartitionSpec as P

from repro.configs.base import ModelConfig, ParallelConfig
from repro.core import collectives, errors, futures, onesided, tool, topology
from repro.core.communicator import Communicator
from repro.core.futures import PersistentRequest, argument_signature
from repro.core.session import Session, default_session
from repro.models import api as model_api
from repro.sharding import rules

tool.pvar_register("trace:prefill_step", "prefill executables traced (want 1 per shape bucket)")
tool.pvar_register("trace:decode_step", "decode executables traced (want 1 per shape bucket)")
tool.pvar_register("trace:kv_transfer", "KV-transfer executables traced (want 1 per shape)")


@dataclasses.dataclass
class ServerConfig:
    max_batch: int = 8
    max_new_tokens: int = 16
    temperature: float = 0.0  # 0 = greedy
    seed: int = 0
    # generation stops for a row once it emits this token; ``None`` decodes
    # the full ``max_new_tokens`` budget for every row
    stop_token: int | None = None


@dataclasses.dataclass
class Request:
    tokens: np.ndarray             # (prompt_len,) int32
    extra: dict = dataclasses.field(default_factory=dict)


def generation_lengths(tokens: np.ndarray, stop_token: int | None) -> np.ndarray:
    """Per-request generated length: tokens up to and including the first
    stop token; the full row when it never stops (or no stop is configured).
    Tokens a row emits *after* its stop are padding, not throughput — the
    old ``tokens.size`` accounting billed them as served work."""

    b, n = tokens.shape
    if stop_token is None:
        return np.full((b,), n, np.int64)
    hit = tokens == stop_token
    return np.where(hit.any(axis=1), hit.argmax(axis=1) + 1, n).astype(np.int64)


class Server:
    def __init__(
        self,
        cfg: ModelConfig,
        pcfg: ParallelConfig,
        scfg: ServerConfig,
        comm: Communicator | Mesh,
    ):
        self.cfg, self.pcfg, self.scfg = cfg, pcfg, scfg
        # serving owns its process set: a session-derived communicator (a
        # bare Mesh is wrapped unmanaged for older call sites)
        self.comm = comm if isinstance(comm, Communicator) else Communicator(comm)
        self.mesh = mesh = self.comm.mesh
        self.bundle = model_api.build(cfg)
        # built under its target shardings: the whole tree never lands on
        # one device first
        key = jax.random.PRNGKey(scfg.seed)
        shapes = jax.eval_shape(self.bundle.init, key)
        pshard = rules.shardings(rules.param_specs(shapes, mesh, pcfg), mesh)
        with mesh:
            self.params = jax.jit(self.bundle.init, out_shardings=pshard)(key)
        # persistent steps, keyed by argument signature (shape bucket): one
        # AOT compile per bucket, MPI_Start re-fires ever after
        self._prefill_reqs: dict[tuple, PersistentRequest] = {}
        self._decode_reqs: dict[tuple, PersistentRequest] = {}
        # per-call PRNG counter: each generate() folds this into the seed key
        self._generate_calls = 0

    # -- persistent step construction -------------------------------------------

    def _prefill_request(self, batch, extra_capacity: int | None = None) -> PersistentRequest:
        # the decode headroom is part of the bucket key: the engine re-prefills
        # resumed requests with a *shrunken* extra so cache capacity stays at
        # the fixed prompt_bucket + max_new invariant
        extra = self.scfg.max_new_tokens if extra_capacity is None else int(extra_capacity)
        key = (argument_signature(batch), extra)
        req = self._prefill_reqs.get(key)
        if req is None:
            def prefill_step(p, b):
                tool.pvar_count("trace:prefill_step")
                # ring attention shards the prompt sequence over the model
                # axis (long prompts whose KV exceeds one device's budget);
                # the prefill needs the mesh to fold the cart ring onto
                mesh = self.mesh if self.pcfg.ring_attention else None
                return self.bundle.prefill(
                    p, b, self.pcfg, mesh, extra_capacity=extra,
                )

            req = PersistentRequest(jax.jit(prefill_step), (self.params, batch))
            self._prefill_reqs[key] = req
        return req

    def _decode_request(self, cache, tok) -> PersistentRequest:
        key = argument_signature((cache, tok))
        req = self._decode_reqs.get(key)
        if req is None:
            def decode_step(p, c, t):
                tool.pvar_count("trace:decode_step")
                return self.bundle.decode(p, c, t, self.pcfg, None)

            req = PersistentRequest(
                jax.jit(decode_step, donate_argnums=(1,)),
                (self.params, cache, tok),
                donate_argnums=(1,),
            )
            self._decode_reqs[key] = req
        return req

    # -- batching ---------------------------------------------------------------

    def _pad_batch(self, requests: list[Request]) -> tuple[dict, np.ndarray]:
        b = len(requests)
        pl = max(len(r.tokens) for r in requests)
        toks = np.zeros((b, pl), np.int32)
        lens = np.zeros((b,), np.int32)
        for i, r in enumerate(requests):
            toks[i, pl - len(r.tokens):] = r.tokens  # left-pad: last token aligned
            lens[i] = len(r.tokens)
        batch = {"tokens": jnp.asarray(toks)}
        # the key set is the UNION over the batch (keying off requests[0]
        # would silently drop extras it happens to lack), and every request
        # must supply every key — a ragged batch is an argument error
        extra_keys = sorted({k for r in requests for k in r.extra})
        for k in extra_keys:
            vals = []
            for i, r in enumerate(requests):
                errors.check(
                    k in r.extra,
                    errors.ErrorClass.ERR_ARG,
                    f"request {i} is missing extra {k!r} present elsewhere in "
                    f"the batch (keys: {extra_keys})",
                )
                vals.append(jnp.asarray(r.extra[k]))
            batch[k] = jnp.stack(vals)
        return batch, lens

    # -- serving ------------------------------------------------------------------

    def _sample(self, logits: jax.Array, key) -> jax.Array:
        logits = logits[:, -1, : self.cfg.vocab_size]
        if self.scfg.temperature <= 0.0:
            return jnp.argmax(logits, axis=-1).astype(jnp.int32)
        return jax.random.categorical(key, logits / self.scfg.temperature).astype(jnp.int32)

    def _next_key(self) -> jax.Array:
        """Per-call PRNG key: the seed key folded with a call counter, so
        successive batches at ``temperature > 0`` sample fresh keys (a fixed
        ``PRNGKey(seed)`` made every batch sample identically)."""

        key = jax.random.fold_in(
            jax.random.PRNGKey(self.scfg.seed), self._generate_calls
        )
        self._generate_calls += 1
        return key

    def _decode_loop(self, cache, tok, key) -> list[jax.Array]:
        """The persistent decode loop: ``max_new_tokens - 1`` re-fires of the
        compiled decode step (shared verbatim by the disaggregated server so
        both paths are token-for-token identical)."""

        outs = [tok]
        decode = self._decode_request(cache, tok[:, None])
        for _ in range(self.scfg.max_new_tokens - 1):
            key, sub = jax.random.split(key)
            logits, cache = decode(self.params, cache, tok[:, None])
            tok = self._sample(logits, sub)
            outs.append(tok)
        jax.block_until_ready(tok)
        return outs

    def generate(self, requests: list[Request]) -> tuple[np.ndarray, dict]:
        """Prefill + greedy/temperature decode.  Returns (tokens
        (B, max_new), stats)."""

        t0 = time.perf_counter()
        batch, _lens = self._pad_batch(requests)
        key = self._next_key()
        with self.mesh:
            logits, cache = self._prefill_request(batch)(self.params, batch)
            tok = self._sample(logits, key)
            jax.block_until_ready(tok)
            t_prefill = time.perf_counter() - t0

            t1 = time.perf_counter()
            outs = self._decode_loop(cache, tok, key)
            t_decode = time.perf_counter() - t1
        tokens = np.stack([np.asarray(t) for t in outs], axis=1)
        gen_lens = generation_lengths(tokens, self.scfg.stop_token)
        stats = {
            "prefill_s": t_prefill,
            "decode_s": t_decode,
            "gen_lens": gen_lens.tolist(),
            "generated_tokens": int(gen_lens.sum()),
            "tokens_per_s": int(gen_lens.sum()) / max(t_decode, 1e-9),
            "batch": len(requests),
        }
        return tokens, stats


# ---------------------------------------------------------------------------
# disaggregated prefill/decode serving (the RMA transport)
# ---------------------------------------------------------------------------


class DisaggregatedServer:
    """Prefill and decode on *disjoint* groups of one serving process set,
    with the KV cache crossing between them through an RMA window.

    The session pset is split with the PR 1 group algebra: the leading
    ``prefill_fraction`` of the set becomes ``<pset>/prefill``, the rest
    ``<pset>/decode`` (both registered on the session).  Three communicators
    are carved out of it:

    * ``prefill`` — a ``(k, 1)`` data×model grid; runs the persistent
      prefill request and samples the first token;
    * ``decode``  — a ``(m, 1)`` grid; rides the existing persistent decode
      request for every subsequent token;
    * ``bridge``  — one axis over the union, ordered prefill-then-decode;
      carries the KV handoff.

    The handoff itself is a :class:`~repro.core.futures.PersistentRequest`
    over the bridge (compiled once per cache signature) whose body is pure
    chapter-12 RMA: the decode ranks expose a zero-initialised window over
    the cache's derived datatype, prefill rank ``i`` ``rput``\\ s the packed
    cache page-by-page into decode rank ``i``'s window (each page's request
    chained onto the previous with ``then()``, joined with ``when_all``
    before the closing fence), and the epoch-close fence completes the
    transfer.  At ``temperature=0`` the generated tokens are identical to
    the single-group :meth:`Server.generate` baseline.

    With a single-device process set the groups degenerate to the same
    device (prefill == decode == the set); the transport still runs, over a
    one-rank bridge.
    """

    def __init__(
        self,
        cfg: ModelConfig,
        pcfg: ParallelConfig,
        scfg: ServerConfig,
        session: Session | None = None,
        *,
        pset: str = "repro://world",
        prefill_fraction: float = 0.5,
        kv_pages: int = 4,
        fanout: tuple[int, int] | None = None,
    ):
        sess = session if session is not None else default_session()
        g = sess.group(pset)
        n = g.size()
        if fanout is not None:
            # explicit heterogeneous P:D split (2:6, 3:5, ...) — the KV
            # routing follows the dist-graph adjacency rather than the
            # paired i -> k+i bridge permutation
            pf, df = int(fanout[0]), int(fanout[1])
            errors.check(
                pf + df == n and n > 1,
                errors.ErrorClass.ERR_TOPOLOGY,
                f"fan-out {pf}:{df} needs a {pf + df}-rank process set, "
                f"pset {pset!r} has {n}",
            )
            k, prefill_g, decode_g = pf, g.incl(range(pf)), g.excl(range(pf))
        else:
            errors.check(
                0.0 < prefill_fraction < 1.0,
                errors.ErrorClass.ERR_ARG,
                f"prefill_fraction must be in (0, 1), got {prefill_fraction}",
            )
            if n > 1:
                k = min(n - 1, max(1, round(n * prefill_fraction)))
                prefill_g, decode_g = g.incl(range(k)), g.excl(range(k))
            else:
                k, prefill_g, decode_g = 1, g, g  # degenerate single-device set
        sess.register_pset(f"{pset}/prefill", prefill_g)
        sess.register_pset(f"{pset}/decode", decode_g)
        self.prefill = Server(
            cfg, pcfg, scfg,
            Communicator.from_group(
                prefill_g, tag=f"{pset}/prefill",
                shape=(prefill_g.size(), 1), axis_names=("data", "model"),
            ),
        )
        self.decode = Server(
            cfg, pcfg, scfg,
            Communicator.from_group(
                decode_g, tag=f"{pset}/decode",
                shape=(decode_g.size(), 1), axis_names=("data", "model"),
            ),
        )
        self.bridge = Communicator.from_group(
            prefill_g | decode_g, tag=f"{pset}/bridge"
        )
        # bridge ranks: prefill devices first, then decode's (group union
        # order); pair prefill i -> decode i (distinct targets: ERR_RANK
        # guards duplicates)
        if fanout is not None:
            # the routing IS the graph: every dist-graph edge becomes a
            # window rput pair, so decode rank P+j pulls from prefill j % P
            self.graph = topology.serving_fanout_graph(self.bridge, pf, df)
            self._perm = topology.fanout_routes(
                *topology.serving_fanout_adjacency(pf, df)
            )
            self._decode_root = pf
        else:
            self.graph = None
            pairs = min(prefill_g.size(), decode_g.size())
            if n > 1:
                self._perm = [(i, k + i) for i in range(pairs)]
                self._decode_root = k
            else:
                self._perm = [(0, 0)]
                self._decode_root = 0
        self.fanout = fanout
        self.kv_pages = int(kv_pages)
        self.scfg = scfg
        self._transfer_reqs: dict[tuple, PersistentRequest] = {}

    # -- the RMA transport --------------------------------------------------

    def _transfer_request(self, staged_cache) -> PersistentRequest:
        key = argument_signature(staged_cache)
        req = self._transfer_reqs.get(key)
        if req is None:
            bridge, pages, root = self.bridge, self.kv_pages, self._decode_root
            # a heterogeneous fan-out gives one prefill origin several decode
            # targets; send_recv carries at most one target per origin, so
            # each page goes out as one rput per round (targets are disjoint
            # across rounds — decode ranks have exactly one source)
            rounds = topology.fanout_rounds(self._perm)

            def move(cache):
                tool.pvar_count("trace:kv_transfer")
                win = onesided.Window(
                    bridge, jax.tree_util.tree_map(jnp.zeros_like, cache)
                )
                win.fence()

                def page_puts(p):
                    return futures.when_all(
                        [win.rput(cache, rnd, page=(p, pages)) for rnd in rounds]
                    )

                futs = [page_puts(0)]
                for p in range(1, pages):
                    # each page's request chains onto its predecessor: the
                    # continuation completes the previous transfer, then
                    # issues (and completes) the next page's rputs
                    futs.append(futs[-1].then(
                        lambda f, _p=p: (f.get(), page_puts(_p).get())[1]
                    ))
                futures.when_all(futs).get()   # MPI_Waitall before the close
                win.fence()                    # epoch close completes the epoch
                # replicate the decode group's window content so the output
                # is well-defined on every rank (the buffers started as
                # zeros: a value here *proved* the window carried it)
                return collectives.broadcast(bridge, win.buffer, root=root)

            req = self.bridge.persistent(move, staged_cache)
            self._transfer_reqs[key] = req
        return req

    def _transfer(self, cache) -> tuple[Any, dict]:
        """Move the prefill-side cache into the decode group via the window;
        returns (decode-side cache, transfer stats)."""

        t0 = time.perf_counter()
        staged = jax.device_put(cache, self.bridge.sharding(P()))
        moved = self._transfer_request(staged).start(staged).get()
        # land on the decode mesh under the serving cache rules: donation
        # aliases the decode step's cache output onto its input, so this
        # placement is the loop's sharding fixed point
        srv = self.decode
        specs = rules.cache_specs(moved, srv.mesh, srv.pcfg, srv.cfg)
        out = jax.device_put(moved, rules.shardings(specs, srv.mesh))
        jax.block_until_ready(out)
        leaves = jax.tree_util.tree_leaves(cache)
        kv_bytes = int(sum(l.size * jnp.dtype(l.dtype).itemsize for l in leaves))
        return out, {
            "transfer_s": time.perf_counter() - t0,
            "kv_bytes": kv_bytes,
            "kv_pages": self.kv_pages,
        }

    # -- serving ------------------------------------------------------------

    def generate(self, requests: list[Request]) -> tuple[np.ndarray, dict]:
        """Disaggregated prefill + decode; token-for-token equal to
        :meth:`Server.generate` at ``temperature=0``."""

        t0 = time.perf_counter()
        batch, _lens = self.prefill._pad_batch(requests)
        key = self.prefill._next_key()
        with self.prefill.mesh:
            logits, cache = self.prefill._prefill_request(batch)(
                self.prefill.params, batch
            )
            tok = self.prefill._sample(logits, key)
            jax.block_until_ready(tok)
        t_prefill = time.perf_counter() - t0

        cache, transfer_stats = self._transfer(cache)
        # the token lands batch-sharded like every later sampled token (the
        # decode request binds its argument shardings at init)
        b = int(tok.shape[0])
        data = int(self.decode.comm.axis_size("data"))
        tok_spec = P("data") if b % data == 0 else P()
        tok = jax.device_put(tok, self.decode.comm.sharding(tok_spec))

        t1 = time.perf_counter()
        with self.decode.mesh:
            outs = self.decode._decode_loop(cache, tok, key)
        t_decode = time.perf_counter() - t1
        tokens = np.stack([np.asarray(t) for t in outs], axis=1)
        gen_lens = generation_lengths(tokens, self.scfg.stop_token)
        stats = {
            "prefill_s": t_prefill,
            "decode_s": t_decode,
            "gen_lens": gen_lens.tolist(),
            "generated_tokens": int(gen_lens.sum()),
            "tokens_per_s": int(gen_lens.sum()) / max(t_decode, 1e-9),
            "batch": len(requests),
            "prefill_devices": self.prefill.comm.size(),
            "decode_devices": self.decode.comm.size(),
            **transfer_stats,
        }
        return tokens, stats
