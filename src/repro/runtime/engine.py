"""Continuous-batching serving engine on the paged-KV slot table.

The :class:`~repro.runtime.server.Server` decodes fixed batches: every
request in a batch prefills together, decodes together, and the batch holds
its slots until the *slowest* member finishes.  This engine removes that
head-of-line blocking while reusing the Server's substrate unchanged:

* **slot table** — one cache of ``max_batch`` rows, each
  ``prompt_bucket + max_new_tokens`` tokens deep, with a *per-row* position
  vector (the model's decode path accepts ``pos`` as ``(B,)`` — see
  :func:`repro.models.attention.cache_layer_update`).  Rows decode at ragged
  depths inside one persistent decode request;
* **paged block pool** — the slot table is carved into fixed KV blocks
  (:class:`~repro.runtime.kvpool.KVBlockPool`); requests allocate blocks as
  they deepen and a budget cap forces *preemption* (``ERR_NO_MEM`` answered
  by evicting the latest-admitted row) under memory pressure;
* **in-flight admission** — new requests prefill in a side batch (the
  Server's persistent prefill request, bucketed by padded length) and are
  spliced into free slots of the *running* cache by a compiled insert-row
  request, joining the next decode iteration;
* **retirement** — a row leaves its slot the moment it emits the stop token
  or exhausts its own ``max_new`` budget; the freed blocks are reused
  verbatim by the next admission.

**Parity contract**: at ``temperature=0`` every request's generated tokens
are identical, token for token, to what :meth:`Server.generate` produces for
the same prompt left-padded to ``prompt_bucket`` — including requests
admitted mid-flight and requests preempted and resumed (resume re-prefills
``prompt + generated[:-1]`` at the same cache positions, so the recomputed
KV is bit-identical to the evicted KV).  The fixed-batch Server is therefore
the engine's oracle, and the tests pin it.
"""

from __future__ import annotations

import collections
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import errors, tool
from repro.core.futures import PersistentRequest, argument_signature
from repro.runtime.kvpool import KVBlockPool
from repro.runtime.server import Request, Server

tool.pvar_register("engine:admit", "requests admitted into a running decode batch")
tool.pvar_register("engine:retire", "requests retired from the continuous batch")
tool.pvar_register("engine:preempt", "requests preempted under block-pool pressure")
tool.pvar_register("trace:insert_row", "decode-row insert kernels traced (want 1 per shape)")
tool.span_register("repro.engine.step",
                   "one Engine.step call; stats step, running, waiting (at entry)")
tool.span_register("repro.engine.admit",
                   "one admission side batch: prefill, first-token sample, inserts; "
                   "stats rows, padded_rows, length, real_tokens")
tool.span_register("repro.engine.admit_row",
                   "one admitted request placed in its slot (or finished at once); "
                   "stats rid, slot")
tool.span_register("repro.engine.first_token",
                   "the host's wait for a side batch's sampled first tokens; stat rows")
tool.span_register("repro.engine.grow",
                   "block growth before the decode step; stat preempted")
tool.span_register("repro.engine.sample",
                   "the fold_in and sample dispatches after the decode step")
tool.span_register("repro.engine.wait",
                   "the host's wait for the decode step's sampled tokens")
tool.span_register("repro.engine.retire",
                   "append each row's token, retire finished rows; stat retired")
tool.span_register("repro.engine.finish",
                   "one request retired; stats rid, tokens")


@dataclasses.dataclass
class EngineConfig:
    """Engine knobs on top of the Server's :class:`ServerConfig` (which
    contributes ``max_batch`` slots, the ``max_new_tokens`` ceiling,
    ``temperature``, ``seed`` and ``stop_token``)."""

    prompt_bucket: int = 8        # every prompt is left-padded to this length
    block_tokens: int = 4         # KV block (page) granularity in tokens
    pool_blocks: int | None = None  # live-block budget; None = uncapped pool


#: request lifecycle states (the admission/preemption state machine)
WAITING, RUNNING, PREEMPTED, FINISHED = "waiting", "running", "preempted", "finished"


@dataclasses.dataclass
class ServingRequest:
    """One request's ticket through the engine."""

    tokens: np.ndarray                 # (prompt_len,) int32, prompt_len <= bucket
    max_new: int                       # this request's own generation budget
    rid: int = -1
    state: str = WAITING
    slot: int | None = None
    generated: list = dataclasses.field(default_factory=list)
    cached_tokens: int = 0             # tokens currently materialised in KV
    admit_seq: int = -1                # admission order (preemption victims
                                       # are picked newest-first)
    preemptions: int = 0
    block_ids: list = dataclasses.field(default_factory=list)


class Engine:
    """Continuous-batching scheduler over a Server's persistent requests."""

    def __init__(self, server: Server, ecfg: EngineConfig):
        cfg, scfg = server.cfg, server.scfg
        errors.check(
            cfg.family in ("dense", "moe"),
            errors.ErrorClass.ERR_UNSUPPORTED_OPERATION,
            f"the continuous-batching engine serves dense/moe LMs; "
            f"family {cfg.family!r} keeps the fixed-batch Server",
        )
        errors.check(
            cfg.sliding_window is None and cfg.layer_pattern == "uniform",
            errors.ErrorClass.ERR_UNSUPPORTED_OPERATION,
            "sliding-window / local_global caches are ring buffers; the "
            "paged slot table requires linear (uniform) cache layout",
        )
        errors.check(
            ecfg.prompt_bucket >= 1 and scfg.max_new_tokens >= 1,
            errors.ErrorClass.ERR_ARG,
            f"need prompt_bucket >= 1 and max_new_tokens >= 1, got "
            f"{ecfg.prompt_bucket}/{scfg.max_new_tokens}",
        )
        self.server = server
        self.ecfg = ecfg
        self.scfg = scfg
        self.num_slots = scfg.max_batch
        self.capacity = ecfg.prompt_bucket + scfg.max_new_tokens
        self.pool = KVBlockPool(
            num_slots=self.num_slots,
            slot_capacity=self.capacity,
            block_tokens=ecfg.block_tokens,
            budget_blocks=ecfg.pool_blocks,
        )
        self.waiting: collections.deque[ServingRequest] = collections.deque()
        self.active: list[ServingRequest | None] = [None] * self.num_slots
        self.finished: list[ServingRequest] = []
        # insert-row compiles are keyed by signature and shared across engine
        # instances over the same server (same params/mesh), like the
        # server's own prefill/decode request caches
        self._insert_reqs: dict[tuple, PersistentRequest] = server.__dict__.setdefault(
            "_engine_insert_reqs", {}
        )
        self._decode_req: PersistentRequest | None = None
        # the last decode step's logits, one row per slot (None before the
        # first step) — what a caller reads to check the cached decode path
        self.logits: jax.Array | None = None
        self._rid = 0
        self._admit_seq = 0
        self._key0 = jax.random.PRNGKey(scfg.seed)   # argmax path ignores it
        self._steps = 0
        self._preempt_count = 0
        self._generated_total = 0

        # the slot-table cache: a throwaway prefill at the bucket shape gives
        # the exact tree/dtypes/shardings the decode loop will carry, then the
        # scalar position becomes the per-row (all-empty) position vector
        toks = jnp.zeros((self.num_slots, ecfg.prompt_bucket), jnp.int32)
        batch = {"tokens": toks}
        with server.mesh:
            _, cache = server._prefill_request(batch)(server.params, batch)
            self.cache = {
                k: dataclasses.replace(
                    v, pos=jnp.zeros((self.num_slots,), jnp.int32)
                )
                for k, v in cache.items()
            }
            self.tok = jnp.zeros((self.num_slots, 1), jnp.int32)

    # -- submission -----------------------------------------------------------

    def submit(self, request, max_new: int | None = None) -> ServingRequest:
        """Queue a request (a server :class:`Request` or a raw token array).
        ``max_new`` caps this request's generation below the engine ceiling."""

        if isinstance(request, Request):
            errors.check(
                not request.extra,
                errors.ErrorClass.ERR_UNSUPPORTED_OPERATION,
                "the engine buckets prompts by length; per-request extras "
                "are a fixed-batch Server feature",
            )
            tokens = np.asarray(request.tokens, np.int32)
        else:
            tokens = np.asarray(request, np.int32)
        errors.check(
            1 <= len(tokens) <= self.ecfg.prompt_bucket,
            errors.ErrorClass.ERR_TRUNCATE,
            f"prompt of {len(tokens)} tokens does not fit the "
            f"{self.ecfg.prompt_bucket}-token bucket",
        )
        budget = self.scfg.max_new_tokens if max_new is None else int(max_new)
        errors.check(
            1 <= budget <= self.scfg.max_new_tokens,
            errors.ErrorClass.ERR_ARG,
            f"max_new={budget} outside [1, {self.scfg.max_new_tokens}]",
        )
        r = ServingRequest(tokens=tokens, max_new=budget, rid=self._rid)
        self._rid += 1
        self.waiting.append(r)
        return r

    # -- admission ------------------------------------------------------------

    def _padded_content(self, r: ServingRequest) -> np.ndarray:
        """What a (re-)prefill must materialise: the prompt left-padded to
        the bucket, plus all generated tokens *except* the pending one (the
        last sampled token's KV is written by its own decode step)."""

        bucket = self.ecfg.prompt_bucket
        out = np.zeros((bucket + max(0, len(r.generated) - 1),), np.int32)
        out[bucket - len(r.tokens):bucket] = r.tokens
        if len(r.generated) > 1:
            out[bucket:] = np.asarray(r.generated[:-1], np.int32)
        return out

    def _insert_request(self, pcache) -> PersistentRequest:
        key = (
            argument_signature((self.cache, self.tok)),
            argument_signature(pcache),
        )
        req = self._insert_reqs.get(key)
        if req is None:
            def insert_step(c, t_table, pc, dst, src, t):
                tool.pvar_count("trace:insert_row")

                def leaf(cd, cs):
                    if cd.ndim == 1:   # the position vector vs scalar pos
                        return cd.at[dst].set(cs.astype(cd.dtype))
                    row = jax.lax.dynamic_slice_in_dim(cs, src, 1, axis=1)
                    return jax.lax.dynamic_update_slice_in_dim(
                        cd, row.astype(cd.dtype), dst, axis=1
                    )

                new_c = jax.tree_util.tree_map(leaf, c, pc)
                return new_c, t_table.at[dst, 0].set(t)

            zero = jnp.zeros((), jnp.int32)
            req = PersistentRequest(
                jax.jit(insert_step, donate_argnums=(0, 1)),
                (self.cache, self.tok, pcache, zero, zero, zero),
                donate_argnums=(0, 1),
            )
            self._insert_reqs[key] = req
        return req

    def _admit(self) -> None:
        free = [s for s in range(self.num_slots) if self.active[s] is None]
        admitted: list[tuple[ServingRequest, int, int]] = []
        while free and self.waiting:
            r = self.waiting[0]
            plen = self.ecfg.prompt_bucket + max(0, len(r.generated) - 1)
            slot = free[0]
            if not self.pool.fits(slot, plen):
                break   # head-of-line under memory pressure: no skip-ahead
            self.waiting.popleft()
            free.pop(0)
            self.pool.ensure(slot, plen)
            admitted.append((r, slot, plen))
        if not admitted:
            return

        # prefill one side batch per padded length (resumed requests carry
        # their regenerated prefix, so their bucket is deeper); rows are
        # padded to the next power of two — a handful of compile buckets,
        # without paying a full max_batch prefill for a single admission
        by_len: dict[int, list[tuple[ServingRequest, int]]] = {}
        for r, slot, plen in admitted:
            by_len.setdefault(plen, []).append((r, slot))
        for plen, group in sorted(by_len.items()):
            nrows = min(self.num_slots, 1 << (len(group) - 1).bit_length())
            real = sum(len(r.tokens) + max(0, len(r.generated) - 1) for r, _ in group)
            with tool.span("repro.engine.admit", rows=len(group), padded_rows=nrows,
                           length=plen, real_tokens=real):
                self._admit_batch(plen, nrows, group)

    def _admit_batch(self, plen: int, nrows: int,
                     group: list[tuple[ServingRequest, int]]) -> None:
        toks = np.zeros((nrows, plen), np.int32)
        for row, (r, _slot) in enumerate(group):
            toks[row] = self._padded_content(r)
        batch = {"tokens": jnp.asarray(toks)}
        extra = self.capacity - plen
        with self.server.mesh:
            logits, pcache = self.server._prefill_request(
                batch, extra_capacity=extra
            )(self.server.params, batch)
            first = self.server._sample(logits, self.server._next_key())
            insert = self._insert_request(pcache)
            with tool.span("repro.engine.first_token", rows=len(group)):
                first_host = np.asarray(first)
            for row, (r, slot) in enumerate(group):
                with tool.span("repro.engine.admit_row", rid=r.rid, slot=slot):
                    if r.generated:
                        t = int(r.generated[-1])   # resumed: pending token
                    else:
                        t = int(first_host[row])   # fresh: sample prefill logits
                        r.generated.append(t)
                        self._generated_total += 1
                        stopped = (
                            self.scfg.stop_token is not None
                            and t == self.scfg.stop_token
                        )
                        if stopped or r.max_new <= 1:
                            # done before ever occupying a decode slot
                            with tool.span("repro.engine.finish", rid=r.rid,
                                           tokens=len(r.generated)):
                                self.pool.release(slot)
                                r.state = FINISHED
                                self.finished.append(r)
                                tool.pvar_count("engine:retire")
                            continue
                    self.cache, self.tok = insert(
                        self.cache, self.tok, pcache,
                        jnp.asarray(slot, jnp.int32),
                        jnp.asarray(row, jnp.int32),
                        jnp.asarray(t, jnp.int32),
                    )
                    r.state, r.slot = RUNNING, slot
                    r.cached_tokens = plen
                    r.admit_seq = self._admit_seq
                    self._admit_seq += 1
                    r.block_ids = self.pool.block_ids(slot)
                    self.active[slot] = r
                    tool.pvar_count("engine:admit")

    # -- preemption -----------------------------------------------------------

    def _preempt(self, slot: int) -> None:
        r = self.active[slot]
        self.pool.release(slot)
        r.state, r.slot = PREEMPTED, None
        r.preemptions += 1
        self.active[slot] = None
        # front of the queue: a preempted request outranks fresh arrivals,
        # so eviction cannot starve it
        self.waiting.appendleft(r)
        self._preempt_count += 1
        tool.pvar_count("engine:preempt")

    def _grow_or_preempt(self) -> None:
        """Before firing the decode step, every running row must own a block
        for the token it is about to write; ``ERR_NO_MEM`` on growth evicts
        the latest-admitted row (possibly the grower itself)."""

        bt = self.ecfg.block_tokens
        if not any(
            r is not None and r.cached_tokens % bt == 0 for r in self.active
        ):
            return   # nobody crosses a block boundary this step
        order = sorted(
            (s for s in range(self.num_slots) if self.active[s] is not None),
            key=lambda s: self.active[s].admit_seq,
        )
        for s in order:
            r = self.active[s]
            if r is None:
                continue   # evicted earlier in this pass
            if r.cached_tokens % bt != 0:
                continue   # current block still has room for the next token
            while True:
                try:
                    self.pool.ensure(s, r.cached_tokens + 1)
                    r.block_ids = self.pool.block_ids(s)
                    break
                except errors.NoMemError:
                    victim = max(
                        (v for v in range(self.num_slots) if self.active[v] is not None),
                        key=lambda v: self.active[v].admit_seq,
                    )
                    self._preempt(victim)
                    if victim == s:
                        break   # the grower lost its own slot

    # -- the scheduler loop ---------------------------------------------------

    def step(self) -> list[ServingRequest]:
        """One scheduler iteration: admit, grow (preempting under pressure),
        fire the persistent decode step, append/retire.  Returns the
        requests that finished this step."""

        with tool.span("repro.engine.step", step=self._steps,
                       running=self.num_slots - self.active.count(None),
                       waiting=len(self.waiting)):
            self._admit()
            with tool.span("repro.engine.grow") as sp:
                before = self._preempt_count
                self._grow_or_preempt()
                sp.set_metadata(preempted=self._preempt_count - before)
            if not any(r is not None for r in self.active):
                return []

            with self.server.mesh:
                # the slot table's signature never changes, so the persistent
                # request is resolved once and re-fired ever after (the
                # per-step signature hash would otherwise be the scheduler's
                # biggest tax)
                if self._decode_req is None:
                    self._decode_req = self.server._decode_request(self.cache, self.tok)
                logits, self.cache = self._decode_req(
                    self.server.params, self.cache, self.tok
                )
                self.logits = logits
                with tool.span("repro.engine.sample"):
                    key = (
                        jax.random.fold_in(self._key0, self._steps)
                        if self.scfg.temperature > 0 else self._key0
                    )
                    tok = self.server._sample(logits, key)
                    self.tok = tok[:, None]
            with tool.span("repro.engine.wait"):
                tok_host = np.asarray(tok)
            self._steps += 1

            with tool.span("repro.engine.retire") as sp:
                done = self._retire(tok_host)
                sp.set_metadata(retired=len(done))
            return done

    def _retire(self, tok_host: np.ndarray) -> list[ServingRequest]:
        """Append each running row's sampled token; retire the rows that
        stopped or spent their budget."""

        done: list[ServingRequest] = []
        for s in range(self.num_slots):
            r = self.active[s]
            if r is None:
                continue
            t = int(tok_host[s])
            r.generated.append(t)
            r.cached_tokens += 1
            self._generated_total += 1
            stopped = self.scfg.stop_token is not None and t == self.scfg.stop_token
            if stopped or len(r.generated) >= r.max_new:
                with tool.span("repro.engine.finish", rid=r.rid, tokens=len(r.generated)):
                    self.pool.release(s)
                    r.state, r.slot = FINISHED, None
                    self.active[s] = None
                    self.finished.append(r)
                    done.append(r)
                    tool.pvar_count("engine:retire")
        return done

    def run(self) -> list[ServingRequest]:
        """Drain the queue: step until nothing is waiting or running."""

        while self.waiting or any(r is not None for r in self.active):
            self.step()
        return self.finished

    # -- bookkeeping ----------------------------------------------------------

    def stats(self) -> dict:
        # generated_tokens counts every sampled token exactly once: the
        # prefill-sampled first token at admission, one per row per decode step
        return {
            "steps": self._steps,
            "preemptions": self._preempt_count,
            "generated_tokens": self._generated_total,
            "finished": len(self.finished),
            "waiting": len(self.waiting),
            "running": sum(1 for r in self.active if r is not None),
            "pool_live_blocks": self.pool.live_blocks,
            "pool_budget_blocks": self.pool.budget_blocks,
        }


def make_engine(server: Server, ecfg: EngineConfig | None = None) -> Engine:
    """Factory: a continuous-batching engine over an existing Server."""

    return Engine(server, ecfg if ecfg is not None else EngineConfig())
