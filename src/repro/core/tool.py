"""Tool interface (paper §II — MPI 4.0 chapter 15, ``MPI_T_``).

MPI_T exposes *performance variables* (pvars) and *control variables*
(cvars).  The XLA adaptation:

* **pvars** are extracted from compiled artifacts: per-collective operand
  bytes, ring-adjusted wire bytes, FLOPs and bytes accessed — the exact
  counters the roofline analysis consumes (``collective_bytes`` is not in
  ``cost_analysis()``; it is parsed from the HLO text here).
* **cvars** are a typed runtime configuration registry (error checking,
  default algorithms, compression) — the scoped, validated analogue of MPI's
  stringly-typed control variables.
* call-site counters (``pvar_counters``) count issued operations per kind,
  maintained by the interface layer.
* **spans** are the events analogue: :func:`span` marks where the host
  spends its time (a persistent dispatch, an engine or trainer phase) in
  the profiler's own trace, on the clock of the device's operations.

Hardware model constants for the roofline (TPU v5e) also live here so every
consumer agrees on them.
"""

from __future__ import annotations

import dataclasses
import re
import threading
from collections import defaultdict
from typing import Any, Callable

from jax.profiler import TraceAnnotation

from repro.analysis import events as analysis_events
from repro.core import errors

# --------------------------------------------------------------------------
# hardware model: published per-chip peaks, keyed by jax device_kind
# --------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class ChipPeaks:
    flops_bf16: float        # FLOP/s per chip
    hbm_bandwidth: float     # bytes/s per chip
    hbm_bytes: int           # HBM capacity per chip
    ici_bandwidth: float     # bytes/s per ICI link


#: Source: Google Cloud documentation, "TPU v5e" (per chip: 197 TFLOP/s
#: bf16, 16 GB HBM at 819 GB/s, 1,600 Gbit/s ICI over four links).
CHIP_PEAKS: dict[str, ChipPeaks] = {
    "TPU v5 lite": ChipPeaks(
        flops_bf16=197e12,
        hbm_bandwidth=819e9,
        hbm_bytes=16 * 1024**3,
        ici_bandwidth=50e9,
    ),
}


def chip_peaks(device_kind: str) -> ChipPeaks:
    """The peaks of a chip by its ``jax.Device.device_kind``; a kind with no
    published entry is an error, never a default."""

    errors.check(
        device_kind in CHIP_PEAKS,
        errors.ErrorClass.ERR_ARG,
        f"no published peaks for device kind {device_kind!r} "
        f"(known: {sorted(CHIP_PEAKS)})",
    )
    return CHIP_PEAKS[device_kind]


# the roofline model and the autotuner target a TPU v5e deployment
_TARGET = chip_peaks("TPU v5 lite")
PEAK_FLOPS_BF16 = _TARGET.flops_bf16
HBM_BANDWIDTH = _TARGET.hbm_bandwidth
ICI_BANDWIDTH = _TARGET.ici_bandwidth
HBM_BYTES = _TARGET.hbm_bytes
DCN_BANDWIDTH = 12.5e9       # bytes/s per host NIC (inter-slice collectives)
COLLECTIVE_LAUNCH_S = 3e-6   # fixed per-collective launch/latency cost

# --------------------------------------------------------------------------
# HLO parsing: collective bytes (pvars from compiled artifacts)
# --------------------------------------------------------------------------

_DTYPE_BYTES = {
    "pred": 1, "s4": 0.5, "u4": 0.5, "s8": 1, "u8": 1, "s16": 2, "u16": 2,
    "s32": 4, "u32": 4, "s64": 8, "u64": 8,
    "f8e4m3fn": 1, "f8e5m2": 1, "f8e4m3b11fnuz": 1, "f8e4m3fnuz": 1, "f8e5m2fnuz": 1,
    "bf16": 2, "f16": 2, "f32": 4, "f64": 8, "c64": 8, "c128": 16,
}

COLLECTIVE_KINDS = (
    "all-gather",
    "all-reduce",
    "reduce-scatter",
    "all-to-all",
    "collective-permute",
    "collective-broadcast",
)

_SHAPE_RE = re.compile(r"\b([a-z][a-z0-9]*)\[([0-9,]*)\]")
_DEF_RE = re.compile(r"^\s*(?:ROOT\s+)?%?([\w.\-]+)\s*=\s*(.*)$")
_GROUPS_BRACE_RE = re.compile(r"replica_groups=\{\{([^}]*)\}")
_GROUPS_IOTA_RE = re.compile(r"replica_groups=\[([0-9,]+)\]<=\[")


def _shape_bytes(dtype: str, dims: str) -> float:
    size = _DTYPE_BYTES.get(dtype)
    if size is None:
        return 0.0
    n = 1
    if dims:
        for d in dims.split(","):
            n *= int(d)
    return n * size


def _line_shapes(segment: str) -> list[float]:
    return [_shape_bytes(d, dims) for d, dims in _SHAPE_RE.findall(segment)]


@dataclasses.dataclass
class CollectiveStats:
    """Aggregated collective pvars for one compiled module (per device)."""

    count: dict[str, int] = dataclasses.field(default_factory=lambda: defaultdict(int))
    operand_bytes: dict[str, float] = dataclasses.field(
        default_factory=lambda: defaultdict(float)
    )
    result_bytes: dict[str, float] = dataclasses.field(
        default_factory=lambda: defaultdict(float)
    )
    wire_bytes: dict[str, float] = dataclasses.field(
        default_factory=lambda: defaultdict(float)
    )

    @property
    def total_operand_bytes(self) -> float:
        return float(sum(self.operand_bytes.values()))

    @property
    def total_wire_bytes(self) -> float:
        return float(sum(self.wire_bytes.values()))

    @property
    def total_count(self) -> int:
        return int(sum(self.count.values()))

    def as_dict(self) -> dict[str, Any]:
        return {
            "count": dict(self.count),
            "operand_bytes": dict(self.operand_bytes),
            "result_bytes": dict(self.result_bytes),
            "wire_bytes": dict(self.wire_bytes),
            "total_operand_bytes": self.total_operand_bytes,
            "total_wire_bytes": self.total_wire_bytes,
        }


def _group_size(line: str, default: int) -> int:
    m = _GROUPS_BRACE_RE.search(line)
    if m:
        first = m.group(1)
        return max(1, len([t for t in first.split(",") if t.strip() != ""]))
    m = _GROUPS_IOTA_RE.search(line)
    if m:
        dims = [int(t) for t in m.group(1).split(",")]
        return max(1, dims[-1]) if dims else default
    return default


def _wire_factor(kind: str, n: int) -> float:
    """Ring-algorithm bytes crossing one device's link, as a multiple of the
    payload (operand bytes for reductions, result bytes for gathers)."""

    if kind in ("collective-permute", "collective-broadcast"):
        # permutes/broadcasts move the payload once regardless of group
        # size; they carry source-target pairs, not replica_groups, so the
        # parsed group size (default 1) must not zero them out — ring
        # schedules and ch. 8 neighbor exchanges are all permutes, and
        # their wire bytes used to read as 0 here
        return 1.0
    if n <= 1:
        return 0.0
    frac = (n - 1) / n
    if kind == "all-reduce":
        return 2.0 * frac
    if kind in ("all-gather", "reduce-scatter", "all-to-all"):
        return frac
    return 1.0


def parse_hlo_collectives(hlo_text: str, default_group: int = 1) -> CollectiveStats:
    """Parse HLO text; sum operand sizes of every collective op (the roofline
    ``collective_bytes`` source mandated by the methodology), plus result and
    ring-adjusted wire bytes.

    ``-start`` variants are counted; their matching ``-done`` is skipped, as
    are dead "parameter"-only mentions.
    """

    shapes_by_name: dict[str, float] = {}
    stats = CollectiveStats()
    for raw in hlo_text.splitlines():
        m = _DEF_RE.match(raw)
        if not m:
            continue
        name, rhs = m.group(1), m.group(2)
        paren = rhs.find("(")
        head = rhs[:paren] if paren >= 0 else rhs
        result_bytes = sum(_line_shapes(head))
        shapes_by_name[name] = result_bytes

        opm = re.search(r"\b([a-z][a-z0-9\-]*)\(", rhs)
        if not opm:
            continue
        op = opm.group(1)
        kind = None
        for k in COLLECTIVE_KINDS:
            if op == k or op == k + "-start":
                kind = k
                break
        if kind is None:
            continue
        # operand bytes: inline shapes if present, else resolve operand names.
        # Scan to the matching close-paren of the op's argument list.
        depth = 1
        top: list[str] = []
        cur = ""
        for ch in rhs[opm.end() :]:
            if ch == "(":
                depth += 1
            elif ch == ")":
                depth -= 1
                if depth == 0:
                    break
            if ch == "," and depth == 1:
                top.append(cur)
                cur = ""
            else:
                cur += ch
        if cur.strip():
            top.append(cur)
        operand_bytes = 0.0
        for arg in top:
            arg = arg.strip()
            inline = _line_shapes(arg)
            if inline:
                operand_bytes += sum(inline)
            else:
                ref = arg.lstrip("%").strip()
                operand_bytes += shapes_by_name.get(ref, 0.0)
        if op.endswith("-start") and kind in ("all-gather", "all-reduce"):
            # start result is (operand, result) tuples; fine — we use operands
            pass
        n = _group_size(raw, default_group)
        payload = operand_bytes if kind in ("all-reduce", "reduce-scatter", "all-to-all",
                                            "collective-permute") else result_bytes
        stats.count[kind] += 1
        stats.operand_bytes[kind] += operand_bytes
        stats.result_bytes[kind] += result_bytes
        stats.wire_bytes[kind] += payload * _wire_factor(kind, n)
    return stats


def flops_and_bytes(compiled) -> tuple[float, float]:
    """(HLO flops, HLO bytes accessed) from ``cost_analysis`` (per device)."""

    ca = compiled.cost_analysis()
    if isinstance(ca, (list, tuple)):
        ca = ca[0]
    return float(ca.get("flops", 0.0)), float(ca.get("bytes accessed", 0.0))


def roofline_terms(
    compiled,
    *,
    hlo_text: str | None = None,
    chips: int = 1,
    peak_flops: float = PEAK_FLOPS_BF16,
    hbm_bw: float = HBM_BANDWIDTH,
    link_bw: float = ICI_BANDWIDTH,
) -> dict[str, Any]:
    """The three roofline terms (seconds) for one compiled step.

    ``cost_analysis`` on an SPMD module reports *per device* numbers, so the
    ``chips`` division is already implicit; it is kept as a parameter for
    whole-model (unpartitioned) analyses.
    """

    flops, bytes_accessed = flops_and_bytes(compiled)
    text = hlo_text if hlo_text is not None else compiled.as_text()
    colls = parse_hlo_collectives(text)
    compute_t = flops / (chips * peak_flops)
    memory_t = bytes_accessed / (chips * hbm_bw)
    collective_t = colls.total_operand_bytes / (chips * link_bw)
    wire_t = colls.total_wire_bytes / (chips * link_bw)
    terms = {
        "compute_s": compute_t,
        "memory_s": memory_t,
        "collective_s": collective_t,
        "collective_wire_s": wire_t,
        "hlo_flops": flops,
        "hlo_bytes": bytes_accessed,
        "collectives": colls.as_dict(),
    }
    dominant = max(
        ("compute_s", "memory_s", "collective_s"), key=lambda k: terms[k]
    )
    terms["dominant"] = dominant
    return terms


# --------------------------------------------------------------------------
# control variables (cvars)
# --------------------------------------------------------------------------


@dataclasses.dataclass
class _Cvar:
    name: str
    type: type
    value: Any
    doc: str
    on_set: Callable[[Any], None] | None = None


_CVARS: dict[str, _Cvar] = {}


def cvar_register(
    name: str, type_: type, default: Any, doc: str, on_set: Callable[[Any], None] | None = None
) -> None:
    _CVARS[name] = _Cvar(name, type_, default, doc, on_set)
    if on_set:
        on_set(default)


def cvar_set(name: str, value: Any) -> None:
    v = _CVARS.get(name)
    if v is None:
        errors.fail(errors.ErrorClass.ERR_ARG, f"unknown control variable {name!r}")
    if not isinstance(value, v.type):
        errors.fail(
            errors.ErrorClass.ERR_TYPE,
            f"cvar {name!r} expects {v.type.__name__}, got {type(value).__name__}",
        )
    v.value = value
    if v.on_set:
        v.on_set(value)


def cvar_get(name: str) -> Any:
    v = _CVARS.get(name)
    if v is None:
        errors.fail(errors.ErrorClass.ERR_ARG, f"unknown control variable {name!r}")
    return v.value


def cvar_list() -> dict[str, str]:
    return {v.name: v.doc for v in _CVARS.values()}


# default cvars
cvar_register(
    "error_checking",
    bool,
    True,
    "trace-time argument validation (the paper's compile-time macro)",
    on_set=errors.set_error_checking,
)

cvar_register(
    "analysis_recording",
    bool,
    False,
    "record communication events into the repro.analysis ledger "
    "(MUST-style event-graph lint; off by default — disabled cost is one "
    "module-attribute read per call site)",
    on_set=analysis_events.set_recording,
)


# --------------------------------------------------------------------------
# pvar call-site counters
# --------------------------------------------------------------------------

pvar_counters: dict[str, int] = defaultdict(int)

# counters are bumped from I/O request threads too (io_bytes_*, commits) —
# `+=` on a dict entry is not atomic, so updates take this lock
_PVAR_LOCK = threading.Lock()

#: Documented performance variables (``MPI_T_pvar_get_info`` analogue).
#: Collective call-site counters are registered implicitly by the method
#: facade; the request-layer counters are registered here so tooling can
#: enumerate them before the first event fires.
PVARS: dict[str, str] = {}


def pvar_register(name: str, doc: str) -> None:
    """Describe a pvar (idempotent).  Counting does not require prior
    registration — unknown counters still count — but registered pvars are
    enumerable via :func:`pvar_info` with a zero initial value."""

    PVARS.setdefault(name, doc)


#: When True, counting an unregistered pvar is an ``ERR_ARG`` instead of a
#: silent new counter — the runtime half of the registry audit (the static
#: half lives in :mod:`repro.analysis.static`; dynamically-formatted names
#: can only be caught here).
PVAR_STRICT = False


def pvar_strict(enabled: bool) -> bool:
    """Toggle fail-fast on unregistered pvar writes; returns the previous
    value."""

    global PVAR_STRICT
    prev = PVAR_STRICT
    PVAR_STRICT = bool(enabled)
    return prev


def _pvar_check(op: str) -> None:
    if op not in PVARS:
        errors.fail(
            errors.ErrorClass.ERR_ARG,
            f"pvar {op!r} written but never registered — add a "
            f"pvar_register({op!r}, ...) where the counter is defined",
        )


def pvar_count(op: str) -> None:
    if PVAR_STRICT:
        _pvar_check(op)
    with _PVAR_LOCK:
        pvar_counters[op] += 1


def pvar_add(op: str, amount: int) -> None:
    """Add to an accumulating pvar (byte counters and the like)."""

    if PVAR_STRICT:
        _pvar_check(op)
    with _PVAR_LOCK:
        pvar_counters[op] += int(amount)


def pvar_reset() -> None:
    with _PVAR_LOCK:
        pvar_counters.clear()


def pvar_read() -> dict[str, int]:
    counts = {name: 0 for name in PVARS}
    with _PVAR_LOCK:
        counts.update(pvar_counters)
    return counts


def pvar_info() -> dict[str, str]:
    return dict(PVARS)


# request-layer pvars (persistent / partitioned operations, C3)
pvar_register("persistent_init", "persistent requests initialised (AOT lower+compile)")
pvar_register("persistent_start", "MPI_Start analogues fired on persistent requests")
pvar_register("partitioned_init", "partitioned requests constructed (Psend_init)")
pvar_register("partitioned_start", "partitioned request activations (MPI_Start)")
pvar_register("partition_ready", "partitions marked ready (MPI_Pready)")
pvar_register("cart_create", "Cartesian topologies constructed (MPI_Cart_create)")
pvar_register("dist_graph_create",
              "distributed graph topologies constructed (MPI_Dist_graph_create_adjacent)")
pvar_register("neighbor_allgather", "neighborhood allgathers issued (MPI_Neighbor_allgather)")
pvar_register("neighbor_alltoall", "neighborhood alltoalls issued (MPI_Neighbor_alltoall)")
pvar_register("neighbor_alltoallv", "vector neighborhood alltoalls issued (MPI_Neighbor_alltoallv)")
pvar_register("neighbor_alltoall_init",
              "persistent neighborhood alltoalls initialised (MPI_Neighbor_alltoall_init)")
pvar_register("rma_fence", "window fence epochs opened/closed (MPI_Win_fence)")
pvar_register("rma_put", "blocking window puts (MPI_Put)")
pvar_register("rma_rput", "request-based window puts (MPI_Rput)")
pvar_register("rma_get", "blocking window gets (MPI_Get)")
pvar_register("rma_rget", "request-based window gets (MPI_Rget)")
pvar_register("rma_accumulate", "window accumulates (MPI_Accumulate/Raccumulate)")
pvar_register("rma_attach", "pages attached to dynamic windows (MPI_Win_attach)")
pvar_register("rma_detach", "pages detached from dynamic windows (MPI_Win_detach)")

# file-I/O pvars (chapter 14) and the checkpoint subsystem built on it
pvar_register("io_write", "blocking collective file writes (MPI_File_write_at_all)")
pvar_register("io_read", "blocking collective file reads (MPI_File_read_at_all)")
pvar_register("io_iwrite", "nonblocking collective writes issued (MPI_File_iwrite_at_all)")
pvar_register("io_iread", "nonblocking collective reads issued (MPI_File_iread_at_all)")
pvar_register("io_split_begin", "split collectives begun (MPI_File_*_at_all_begin)")
pvar_register("io_set_view", "file views installed (MPI_File_set_view)")
pvar_register("io_manifest_commit", "manifest sync points written (MPI_File_sync)")
pvar_register("io_bytes_written", "fragment bytes written (accumulating)")
pvar_register("io_bytes_read", "fragment bytes read (accumulating)")
pvar_register("ckpt_save", "checkpoint saves issued (async or sync)")
pvar_register("ckpt_save_failed", "checkpoint saves that surfaced an I/O error")
pvar_register("ckpt_restore", "checkpoint restores")
pvar_register("ckpt_wait", "checkpoint completions joined (wait)")


# --------------------------------------------------------------------------
# spans (the MPI_T events analogue)
# --------------------------------------------------------------------------

#: Documented spans, ``name -> doc`` (the :data:`PVARS` of events).
SPANS: dict[str, str] = {}


def span_register(name: str, doc: str) -> None:
    """Describe a span (idempotent); the static audit holds every literal
    :func:`span` name to this registry, as it holds pvars to theirs."""

    SPANS.setdefault(name, doc)


def span_info() -> dict[str, str]:
    return dict(SPANS)


def span(name: str, /, **stats: int | str):
    """A host span in the profiler's trace: a context manager over
    ``jax.profiler.TraceAnnotation``, so it lands on the host plane of the
    ``.xplane.pb`` beside the device's operations.  ``stats`` (small ints,
    or a short name) become the event's stats; what is known only at the
    end goes in with ``set_metadata`` on the entered span.  A span's parent
    is the enclosing span of the same thread.  With no profiler session it
    records nothing."""

    return TraceAnnotation(name, **stats)


span_register("repro.request.start",
              "one persistent dispatch (MPI_Start): the compiled executable's "
              "call and its pvar; stat name = the jitted function")
