"""The request subsystem (paper §II, C3 — Listing 2; MPI 4.0 persistent and
partitioned operations).

Requests exist in three forms, mirroring how MPI operations exist both in
host code and inside the parallel program, and how MPI 4.0 extends them:

* :class:`Future` — **host level**.  JAX dispatch is asynchronous: a jitted
  SPMD program returns immediately with unmaterialised arrays, exactly like
  an ``MPI_I*`` call returns a request.  ``get()`` = ``MPI_Wait`` =
  ``block_until_ready``; ``test()`` = ``MPI_Test``; :func:`when_all` /
  :func:`when_any` = ``MPI_Waitall`` / ``MPI_Waitany``; ``then()`` chains a
  continuation (the continuation may dispatch more work — the chain builds a
  dataflow task graph exactly as in Listing 2).  Like ``MPI_Wait``, both
  ``get()`` *and* ``then()`` consume the request: a chained-then-read double
  use raises ``ERR_REQUEST``, consistent with :func:`when_all`.

* :class:`TraceFuture` — **trace level** (inside ``comm.spmd`` regions).  An
  ``immediate_*`` collective returns a lazily-forced future; ``then()``
  chains continuations *into the traced program*, and decomposed collectives
  (:mod:`repro.core.overlap`) override forcing so a continuation can be fused
  chunk-wise with the communication schedule — the TPU-native meaning of
  "overlap nonblocking communication with computation".

* :class:`DeferredFuture` — **host level, off the dispatch path**.  Some
  completions are not XLA values: background file I/O
  (:class:`repro.core.io.IORequest`), joins over such requests.  A deferred
  future resolves at wait time, ``then()`` chains lazily (the continuation
  runs when the chain is waited), and resolver errors propagate through
  ``get()``/``wait()`` — the error-forwarding thin wrappers lose.

* :class:`PersistentRequest` — persistent operations (``MPI_Send_init`` /
  ``MPI_Allreduce_init`` + ``MPI_Start``): the argument/plan setup is
  amortised by AOT lowering and compilation; ``start()`` re-fires the
  compiled executable with **zero re-tracing**.  The fixed argument list is
  enforced: starting with mismatched shapes, dtypes, tree structure or
  shardings raises ``ERR_REQUEST``.  Buffer donation (``donate_argnums``)
  aliases inputs into outputs; ``warm_start`` prefetches the executable with
  throwaway inputs so the first real ``start()`` pays no allocator cost;
  ``then()`` registers continuations applied to every start's host future.

* :class:`PartitionedRequest` — partitioned communication
  (``MPI_Psend_init`` / ``MPI_Pready``): one logical operation over a pytree
  is split into K partitions, each marked ready independently with
  :meth:`~PartitionedRequest.pready` and forced as a lazy
  :class:`TraceFuture` — so communication for ready partitions interleaves
  with the compute producing later ones.  Results are independent of the
  ``pready`` order; :meth:`~PartitionedRequest.wait` completes the operation.

:class:`PersistentCollective` combines the two MPI 4.0 additions with the C2
datatype layer: ``comm.allreduce_init(example)`` AOT-lowers **one collective
per dtype bucket** of the example aggregate, and every ``start()`` re-fires
the compiled executables on a new aggregate of the same datatype.

Neighborhood collectives (chapter 8, :mod:`repro.core.topology`) ride the
same engine: ``neighbor_allgather``/``neighbor_alltoall(v)`` return
:class:`TraceFuture`\\ s whose forcing points place the sparse exchanges in
the trace, and ``neighbor_alltoall_init`` reuses
:class:`PersistentCollective` for the ``MPI_Neighbor_alltoall_init`` form.

Request-based RMA (``MPI_Rput``/``MPI_Rget``/``MPI_Raccumulate``, chapter
12) rides the same engine: :class:`repro.core.onesided.Window` returns
:class:`TraceFuture`\\ s from ``rput``/``rget``/``raccumulate``, so one-sided
traffic chains with ``then()`` and joins with :func:`when_all` exactly like
nonblocking collectives; ``fence`` completes any outstanding RMA requests
(``MPI_Win_fence`` closes the epoch on unwaited requests).
"""

from __future__ import annotations

import time
import weakref
from typing import Any, Callable, Sequence

import jax
import jax.numpy as jnp

from repro.analysis import events as analysis_events
from repro.core import errors


def _is_ready(tree: Any) -> bool:
    for leaf in jax.tree_util.tree_leaves(tree):
        probe = getattr(leaf, "is_ready", None)
        if callable(probe) and not probe():
            return False
    return True


class Future:
    """Host-level future over dispatched (asynchronous) results."""

    def __init__(self, value: Any):
        self._value = value
        self._valid = True

    def valid(self) -> bool:
        return self._valid

    def get(self) -> Any:
        """``MPI_Wait`` + value retrieval (consumes the future)."""

        errors.check(self._valid, errors.ErrorClass.ERR_REQUEST, "future already consumed")
        self._valid = False
        return self._wait_value()

    def _wait_value(self) -> Any:
        """Block until the value is materialised and return it (no validity
        bookkeeping — ``get``/``wait`` own that)."""

        jax.block_until_ready(self._value)
        return self._value

    def wait(self) -> "Future":
        """Block until complete (does not consume; ``get()`` does)."""

        errors.check(self._valid, errors.ErrorClass.ERR_REQUEST, "future already consumed")
        self._wait_value()
        return self

    def test(self) -> bool:
        """Non-blocking completion probe (``MPI_Test``)."""

        return _is_ready(self._value)

    def then(self, fn: Callable[["Future"], Any]) -> "Future":
        """Chain a continuation.  ``fn`` receives *this* future (paper
        Listing 2) and returns a value or another future; dispatch remains
        asynchronous throughout.

        Chaining **consumes** the parent (``ERR_REQUEST`` on reuse): the
        continuation owns the request now, exactly as :func:`when_all`
        invalidates its joined inputs.
        """

        errors.check(
            self._valid, errors.ErrorClass.ERR_REQUEST, "then() on a consumed future"
        )
        result = fn(self)
        self._valid = False
        if result is self:
            # pass-through continuation: hand the value on in a fresh request
            return Future(self._value)
        if isinstance(result, Future):
            return result
        return Future(result)


class DeferredFuture(Future):
    """Host future whose value is produced by a *resolver* at completion
    time — the host-level request behind operations that finish off the XLA
    dispatch path (background file I/O, joins over such requests).

    ``get()``/``wait()`` run the resolver exactly once; an error raised
    there (e.g. ``ERR_IO`` from a failed background write) propagates to the
    caller — a failed operation can never read as success.  ``test()`` uses
    the optional ``probe`` (e.g. a thread-completion event); without one it
    reports completion only after resolution, like :class:`TraceFuture`.

    ``then()`` on a deferred request is itself deferred: the continuation
    runs when the *chained* request is waited, not at chain time, so a chain
    built over in-flight I/O does not block the issuing thread (the host
    analogue of :meth:`TraceFuture.then`).
    """

    def __init__(self, resolver: Callable[[], Any], probe: Callable[[], bool] | None = None):
        super().__init__(None)
        self._resolver = resolver
        self._probe = probe
        self._resolved = False

    def _wait_value(self) -> Any:
        if not self._resolved:
            self._value = self._resolver()
            self._resolved = True
        jax.block_until_ready(self._value)
        return self._value

    def test(self) -> bool:
        if self._resolved:
            return True
        if self._probe is not None:
            return bool(self._probe())
        return False

    def then(self, fn: Callable[["Future"], Any]) -> "DeferredFuture":
        errors.check(
            self._valid, errors.ErrorClass.ERR_REQUEST, "then() on a consumed future"
        )
        self._valid = False
        parent = self

        def resolver():
            # the chain owns the parent request now: re-validate it for the
            # continuation's own get()/wait(), exactly as the eager form
            # hands fn a still-valid future
            parent._valid = True
            try:
                result = fn(parent)
            finally:
                parent._valid = False
            if result is parent:
                return parent._wait_value()
            if isinstance(result, Future):
                return result._wait_value()
            return result

        # no probe: the continuation only runs at wait, so completion is not
        # observable earlier (same semantics as TraceFuture.then)
        return DeferredFuture(resolver)


def when_all(futures: Sequence[Future]) -> "Future | TraceFuture":
    """``MPI_Waitall`` join: a future over the tuple of results.

    Like ``MPI_Waitall``, the joined requests are consumed: each input must
    still be valid (``ERR_REQUEST`` otherwise, exactly as a double ``get()``
    would raise) and is invalidated by the join.

    A sequence of :class:`TraceFuture`\\ s (nonblocking collectives or RMA
    requests inside an SPMD region) dispatches to :func:`trace_when_all` —
    the join stays lazy and forces its inputs in issue order.
    """

    if len(futures) > 0 and all(isinstance(f, TraceFuture) for f in futures):
        return trace_when_all(futures)
    errors.check(
        not any(isinstance(f, TraceFuture) for f in futures),
        errors.ErrorClass.ERR_REQUEST,
        "when_all over mixed host and trace futures: a trace-level request "
        "cannot be joined outside its SPMD region (join each level separately)",
    )
    seen: set[int] = set()
    for i, f in enumerate(futures):
        errors.check(
            f.valid() and id(f) not in seen,
            errors.ErrorClass.ERR_REQUEST,
            f"when_all: future {i} already consumed",
        )
        seen.add(id(f))
    for f in futures:
        f._valid = False
    if any(isinstance(f, DeferredFuture) for f in futures):
        # a join over in-flight host I/O stays lazy: waiting the join waits
        # every input (in order) and surfaces the first failure (ERR_IO from
        # a background write propagates, MPI_Waitall-style)
        inputs = tuple(futures)
        return DeferredFuture(
            lambda: tuple(f._wait_value() for f in inputs),
            probe=lambda: all(f.test() for f in inputs),
        )
    values = tuple(f._value for f in futures)
    return Future(values)


def when_any(
    futures: Sequence[Future],
    poll_interval_s: float = 1e-4,
    timeout_s: float | None = None,
) -> tuple[Future, int]:
    """``MPI_Waitany`` join: first completed future and its index.

    Inputs must be valid (unconsumed); the winner is returned still valid so
    the caller retrieves its value with ``get()``.  With ``timeout_s`` set,
    ``ERR_PENDING`` is raised if no input completes in time (instead of
    busy-waiting forever on a never-ready future).
    """

    errors.check(len(futures) > 0, errors.ErrorClass.ERR_REQUEST, "when_any of no futures")
    for i, f in enumerate(futures):
        errors.check(
            f.valid(),
            errors.ErrorClass.ERR_REQUEST,
            f"when_any: future {i} already consumed",
        )
    deadline = None if timeout_s is None else time.monotonic() + timeout_s
    while True:
        for i, f in enumerate(futures):
            if f.test():
                return f, i
        if deadline is not None and time.monotonic() >= deadline:
            errors.fail(
                errors.ErrorClass.ERR_PENDING,
                f"when_any: none of {len(futures)} futures completed "
                f"within {timeout_s}s",
            )
        time.sleep(poll_interval_s)


class TraceFuture:
    """Trace-level future: a lazily forced value inside an SPMD region."""

    def __init__(
        self,
        thunk: Callable[[], Any] | None = None,
        value: Any = None,
        label: str = "",
    ):
        self._thunk = thunk
        self._value = value
        self._forced = thunk is None
        # under analysis recording, lazy futures carry a ledger token so the
        # lifecycle checker can see which were never consumed at trace exit
        # (already-forced ready() values hold no pending communication)
        self._token = 0
        if thunk is not None and analysis_events.RECORDING:
            self._token = analysis_events.next_token()
            analysis_events.record_future_create(self._token, label)

    @classmethod
    def ready(cls, value: Any) -> "TraceFuture":
        return cls(thunk=None, value=value)

    def valid(self) -> bool:
        return True

    def _consume(self, how: str) -> None:
        if self._token:
            analysis_events.record_future_consume(self._token, how)
            self._token = 0

    def get(self) -> Any:
        """Force the communication into the trace and return its value."""

        if not self._forced:
            self._consume("get")
            self._value = self._thunk()
            self._thunk = None
            self._forced = True
        return self._value

    def test(self) -> bool:
        return self._forced

    def then(self, fn: Callable[["TraceFuture"], Any]) -> "TraceFuture":
        """Sequential-asynchronous chaining (Listing 2).  Lazy: nothing is
        traced until the chain end is forced, letting decomposed collectives
        fuse continuations."""

        self._consume("then")

        def thunk():
            result = fn(self)
            if isinstance(result, TraceFuture):
                return result.get()
            return result

        return TraceFuture(thunk, label="then")


def trace_when_all(futures: Sequence[TraceFuture]) -> TraceFuture:
    """``MPI_Waitall`` at trace level: forces all, yields the tuple."""

    for f in futures:
        f._consume("when_all")
    return TraceFuture(lambda: tuple(f.get() for f in futures), label="when_all")


def trace_when_any(futures: Sequence[TraceFuture]) -> tuple[TraceFuture, int]:
    """``MPI_Waitany`` at trace level.  XLA programs are statically
    scheduled, so "whichever completes first" is not observable; the
    documented SPMD semantics is deterministic selection of the first
    pending future (their side effects all occur at their forcing points)."""

    errors.check(len(futures) > 0, errors.ErrorClass.ERR_REQUEST, "when_any of no futures")
    for i, f in enumerate(futures):
        if not f.test():
            return f, i
    return futures[0], 0


# ---------------------------------------------------------------------------
# persistent operations (MPI_*_init / MPI_Start)
# ---------------------------------------------------------------------------


def _leaf_signature(leaf: Any) -> tuple:
    shape = tuple(getattr(leaf, "shape", ()))
    dtype = getattr(leaf, "dtype", None)
    return (shape, None if dtype is None else jnp.dtype(dtype))


def _leaf_sharding(leaf: Any):
    # only committed jax.Arrays carry a checkable sharding; ShapeDtypeStructs
    # used as AOT stand-ins leave sharding to the executable
    if isinstance(leaf, jax.Array):
        return getattr(leaf, "sharding", None)
    return None


def argument_signature(tree: Any) -> tuple:
    """Hashable (treedef, per-leaf shape/dtype) key for one argument list —
    the signature a :class:`PersistentRequest` is bound to; also usable as a
    cache key for per-shape-bucket requests."""

    leaves, treedef = jax.tree_util.tree_flatten(tree)
    return treedef, tuple(_leaf_signature(l) for l in leaves)


class PersistentRequest:
    """Persistent operation: AOT-compiled executable + ``start()``.

    ``MPI_Send_init`` fixes the argument list so repeated ``MPI_Start`` calls
    skip setup; the XLA analogue fixes shapes/shardings so repeated calls
    skip tracing, lowering and compilation — the hot path dispatches the
    compiled executable directly and can never re-trace.

    * **validation** — ``start()`` checks tree structure, leaf shapes/dtypes
      and (for committed arrays) shardings against the init-time argument
      list; any mismatch raises ``ERR_REQUEST`` (a persistent request is
      *bound* to its arguments in MPI).
    * **donation** — pass ``donate_argnums`` to the jitted function (and
      mirror it here for bookkeeping): donated inputs are aliased into
      outputs by XLA, so steady-state steps allocate nothing new.
    * **warm start** — ``warm_start=True`` fires the executable once at init
      on throwaway zero inputs (safe under donation — the zeros are owned
      here), prefetching executable load and allocator state so the first
      real ``start()`` runs at steady-state cost.
    * **continuations** — ``then(fn)`` registers a continuation applied to
      every start's host future (the persistent analogue of Listing 2).
    """

    def __init__(
        self,
        jitted: Any,
        example_args: tuple,
        example_kwargs: dict | None = None,
        *,
        donate_argnums: tuple[int, ...] = (),
        warm_start: bool = False,
    ):
        from repro.core import tool

        tool.pvar_count("persistent_init")
        #: the jitted function's name: the ``name`` stat of its dispatches
        self.name = getattr(jitted, "__name__", type(jitted).__name__)
        self._lowered = jitted.lower(*example_args, **(example_kwargs or {}))
        self._compiled = self._lowered.compile()
        self.donate_argnums = tuple(donate_argnums)
        self._continuations: list[Callable[[Future], Any]] = []
        # the bound argument list: treedef + per-leaf (shape, dtype, sharding)
        leaves, self._treedef = jax.tree_util.tree_flatten(example_args)
        self._leaf_sigs = [_leaf_signature(l) for l in leaves]
        self._leaf_shardings = [_leaf_sharding(l) for l in leaves]
        self._started = 0
        # analysis bookkeeping: the last start()'s chained future, held
        # weakly so the analyzer never extends buffer lifetimes
        self._token = 0
        self._last_future: weakref.ref | None = None
        if analysis_events.RECORDING:
            self._token = analysis_events.next_token()
            analysis_events.record_persistent_init(
                self._token, donated=bool(self.donate_argnums))
        if warm_start:
            self._warm_start(leaves)

    def _warm_start(self, example_leaves: list) -> None:
        """Prefetch: fire once on owned zero buffers (donation-safe)."""

        zeros = []
        for (shape, dtype), shard in zip(self._leaf_sigs, self._leaf_shardings):
            z = jnp.zeros(shape, dtype)
            if shard is not None:
                z = jax.device_put(z, shard)
            zeros.append(z)
        out = self._compiled(*jax.tree_util.tree_unflatten(self._treedef, zeros))
        jax.block_until_ready(out)

    @property
    def compiled(self):
        return self._compiled

    @property
    def starts(self) -> int:
        return self._started

    def _validate(self, args: tuple) -> None:
        leaves, treedef = jax.tree_util.tree_flatten(args)
        errors.check(
            treedef == self._treedef,
            errors.ErrorClass.ERR_REQUEST,
            f"persistent start: argument structure {treedef} does not match "
            f"the init-time structure {self._treedef}",
        )
        for i, (leaf, sig, shard) in enumerate(
            zip(leaves, self._leaf_sigs, self._leaf_shardings)
        ):
            errors.check(
                _leaf_signature(leaf) == sig,
                errors.ErrorClass.ERR_REQUEST,
                f"persistent start: argument leaf {i} is "
                f"{_leaf_signature(leaf)}, request was initialised with {sig}",
            )
            cur = _leaf_sharding(leaf)
            if shard is not None and cur is not None:
                errors.check(
                    cur.is_equivalent_to(shard, max(len(sig[0]), 1)),
                    errors.ErrorClass.ERR_REQUEST,
                    f"persistent start: argument leaf {i} sharding {cur} is "
                    f"not equivalent to the init-time sharding {shard}",
                )

    def __call__(self, *args: Any) -> Any:
        """Fire the persistent operation, returning the raw (asynchronously
        dispatched) outputs — the drop-in replacement for a jitted step.

        The hot path dispatches straight into the compiled executable (whose
        own C++-level signature check is free); only when that rejects the
        arguments does the Python validation run, to convert the drift into
        a precise ``ERR_REQUEST``.  Unrelated runtime failures re-raise
        unchanged."""

        from repro.core import tool

        with tool.span("repro.request.start", name=self.name):
            try:
                out = self._compiled(*args)
            except (TypeError, ValueError):
                # the compiled executable rejects drifted argument lists
                # with TypeError (shape/dtype/pytree mismatch) or ValueError
                # (sharding mismatch) — the expected failures; anything else
                # propagates untouched
                if errors.error_checking_enabled():
                    self._validate(args)     # raises ERR_REQUEST if args drifted
                raise
            # only successful dispatches count as MPI_Start events
            tool.pvar_count("persistent_start")
        self._started += 1
        return out

    def start(self, *args: Any) -> Future:
        """``MPI_Start``: fire the persistent operation; returns a host
        future, chained through any registered ``then()`` continuations."""

        if analysis_events.RECORDING and self._token:
            prev = self._last_future() if self._last_future else None
            analysis_events.record_persistent_start(
                self._token,
                donated=bool(self.donate_argnums),
                prev_outstanding=prev is not None and prev.valid(),
                has_continuations=bool(self._continuations),
            )
        fut = Future(self(*args))
        for fn in self._continuations:
            fut = fut.then(fn)
        if analysis_events.RECORDING and self._token:
            self._last_future = weakref.ref(fut)
        return fut

    def then(self, fn: Callable[[Future], Any]) -> "PersistentRequest":
        """Register a continuation applied to every start's future."""

        self._continuations.append(fn)
        return self

    def cost_analysis(self):
        return self._compiled.cost_analysis()

    def as_text(self) -> str:
        return self._compiled.as_text()


class PersistentCollective:
    """A persistent collective over a *datatype* (``MPI_Allreduce_init``).

    Built by ``comm.<op>_init(example)``: the example aggregate's datatype is
    derived (C2), and one :class:`PersistentRequest` is AOT-compiled per
    dtype bucket — single-array examples skip packing entirely and compile
    one request on the array's own shape.  ``start(value)`` packs the new
    value (same datatype enforced), fires every bucket's executable, and
    returns a host :class:`Future` over the reassembled aggregate (or the
    raw bucket list for shape-changing collectives, mirroring the blocking
    forms).
    """

    def __init__(self, name: str, datatype, requests: list[PersistentRequest],
                 *, unpackable: bool = True, signature: tuple | None = None):
        self.name = name
        self.datatype = datatype          # None => single-array fast path
        self._requests = requests
        self._unpackable = unpackable
        self._signature = signature       # init-time aggregate signature

    @property
    def requests(self) -> list[PersistentRequest]:
        return self._requests

    @property
    def starts(self) -> int:
        """``MPI_Start`` events fired so far (max over the dtype-bucket
        requests — one logical start fires every bucket once)."""

        return max((r.starts for r in self._requests), default=0)

    def as_text(self) -> str:
        return "\n".join(r.as_text() for r in self._requests)

    def start(self, value: Any) -> Future:
        if self.datatype is None:
            return Future(self._requests[0](value))
        if self._signature is not None and errors.error_checking_enabled():
            # bind the aggregate too: pack() would silently cast drifted leaf
            # dtypes to the init-time layout, so check the signature first
            errors.check(
                argument_signature(value) == self._signature,
                errors.ErrorClass.ERR_REQUEST,
                f"persistent {self.name} start: aggregate does not match the "
                f"init-time datatype (shape/dtype/structure drift)",
            )
        bufs = self.datatype.pack(value)
        outs = [req(b) for req, b in zip(self._requests, bufs)]
        if self._unpackable:
            return Future(self.datatype.unpack(outs))
        return Future(outs)


# ---------------------------------------------------------------------------
# partitioned communication (MPI_Psend_init / MPI_Pready)
# ---------------------------------------------------------------------------


class PartitionedRequest:
    """Partitioned operation at trace level (``MPI_Psend_init`` family).

    One logical operation is split into ``num_partitions`` independent
    partitions.  ``pready(i, payload)`` marks partition ``i`` ready and
    returns a lazy :class:`TraceFuture` over ``fn(i, payload)`` — nothing is
    traced until that future (or :meth:`wait`) forces it, so the schedule
    interleaves each partition's communication with the compute producing
    later partitions.  :meth:`wait` forces every partition **in index
    order**, making the result independent of the ``pready`` order.

    The request is persistent in the MPI sense: :meth:`start` re-activates
    it for another round (``ERR_REQUEST`` on double start / pready without
    start / duplicate pready; ``ERR_PENDING`` on wait with missing
    partitions).
    """

    def __init__(self, fn: Callable[[int, Any], Any], num_partitions: int):
        errors.check(
            num_partitions > 0,
            errors.ErrorClass.ERR_COUNT,
            f"partitioned request needs >= 1 partition, got {num_partitions}",
        )
        from repro.core import tool

        tool.pvar_count("partitioned_init")
        self._fn = fn
        self._n = num_partitions
        self._futures: list[TraceFuture | None] = [None] * num_partitions
        self._active = False

    @property
    def num_partitions(self) -> int:
        return self._n

    def start(self) -> "PartitionedRequest":
        """``MPI_Start``: activate the request for one round of pready/wait."""

        from repro.core import tool

        errors.check(
            not self._active,
            errors.ErrorClass.ERR_REQUEST,
            "partitioned start: request already active (wait() first)",
        )
        tool.pvar_count("partitioned_start")
        self._futures = [None] * self._n
        self._active = True
        return self

    def pready(self, index: int, payload: Any) -> TraceFuture:
        """``MPI_Pready``: partition ``index``'s payload is produced; returns
        the lazy future over its share of the operation."""

        from repro.core import tool

        errors.check(
            self._active,
            errors.ErrorClass.ERR_REQUEST,
            "pready before start() on a partitioned request",
        )
        errors.check(
            0 <= index < self._n,
            errors.ErrorClass.ERR_REQUEST,
            f"pready partition {index} out of range [0, {self._n})",
        )
        errors.check(
            self._futures[index] is None,
            errors.ErrorClass.ERR_REQUEST,
            f"pready: partition {index} already marked ready",
        )
        tool.pvar_count("partition_ready")
        fut = TraceFuture(lambda: self._fn(index, payload))
        self._futures[index] = fut
        return fut

    def parrived(self, index: int) -> bool:
        """``MPI_Parrived``: has partition ``index`` been forced yet?"""

        errors.check(
            0 <= index < self._n,
            errors.ErrorClass.ERR_REQUEST,
            f"parrived partition {index} out of range [0, {self._n})",
        )
        f = self._futures[index]
        return f is not None and f.test()

    def wait(self) -> list:
        """Complete the operation: force every partition in index order and
        return their results.  ``ERR_PENDING`` if some partition was never
        marked ready (the MPI program would deadlock)."""

        missing = [i for i, f in enumerate(self._futures) if f is None]
        errors.check(
            not missing,
            errors.ErrorClass.ERR_PENDING,
            f"partitioned wait: partitions {missing} never marked ready",
        )
        results = [f.get() for f in self._futures]
        self._active = False
        return results
