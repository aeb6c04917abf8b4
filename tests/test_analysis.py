"""repro.analysis — the MUST-style communication-correctness analyzer.

Seeded-defect suite: every checker must fire on its defect with the correct
:class:`~repro.core.errors.ErrorClass`, and must stay silent on the clean
variant of the same program.  Defects that cannot be produced through the
normal API (the runtime forbids them — e.g. cross-epoch puts, which
``Window.fence`` drains before the epoch increments) are seeded through the
events API directly: the ledger IS the interposition surface, exactly as
MUST consumes PMPI event streams rather than the application source.

Also here: the pvar-registry meta-check (every counter written anywhere in
the tree is registered in ``tool.PVARS`` — static half over literal names,
runtime half via ``pvar_strict``), the repo-wide swallowed-failure check,
and the deadlock-detector property test (flags all and only the cyclic
sync schedules; hypothesis when available, exhaustive fallback otherwise —
same precedent as the cart slot-pairing property in test_topology.py).
"""

from __future__ import annotations

import itertools

import numpy as np
import pytest

from repro.analysis import checkers, events, static
from repro.core import errors, tool
from repro.core.errors import ErrorClass

ROUND = [(0, 1), (1, 2), (2, 0)]          # 3-cycle permutation


@pytest.fixture()
def recording():
    """Fresh ledger with recording on (via the cvar, so the MPI_T path is
    exercised); everything restored afterwards."""

    events.reset()
    tool.cvar_set("analysis_recording", True)
    try:
        yield events.ledger()
    finally:
        tool.cvar_set("analysis_recording", False)
        events.reset()


def codes(findings, check=None):
    return [f.code for f in findings if check is None or f.check == check]


# ---------------------------------------------------------------------------
# recording toggle
# ---------------------------------------------------------------------------


def test_recording_off_by_default():
    assert tool.cvar_get("analysis_recording") is False
    assert events.RECORDING is False
    before = len(events.ledger())
    events.record_collective("c", "allreduce", rank=0)
    assert len(events.ledger()) == before, "recorded while disabled"


def test_cvar_toggles_recording(recording):
    assert events.RECORDING is True
    events.record_collective("c", "allreduce", rank=0)
    assert len(events.ledger()) == 1
    tool.cvar_set("analysis_recording", False)
    events.record_collective("c", "allreduce", rank=0)
    assert len(events.ledger()) == 1
    tool.cvar_set("analysis_recording", True)   # fixture teardown expects on/off pairs to be safe


# ---------------------------------------------------------------------------
# (a) collective order / signature
# ---------------------------------------------------------------------------


def test_clean_collective_order(recording):
    for r in range(4):
        events.record_collective("c", "allreduce", np.zeros(3, np.float32), rank=r)
        events.record_collective("c", "allgather", np.zeros(3, np.float32), rank=r)
    assert checkers.check_collective_order() == []


def test_mismatched_collective_order(recording):
    events.record_collective("c", "allreduce", rank=0)
    events.record_collective("c", "allgather", rank=0)
    events.record_collective("c", "allgather", rank=1)   # swapped on rank 1
    events.record_collective("c", "allreduce", rank=1)
    f = checkers.check_collective_order()
    assert codes(f, "collective-order") == [ErrorClass.ERR_NOT_SAME]


def test_mismatched_collective_signature(recording):
    events.record_collective("c", "allreduce", np.zeros(3, np.float32), rank=0)
    events.record_collective("c", "allreduce", np.zeros(3, np.int32), rank=1)
    f = checkers.check_collective_order()
    assert codes(f, "collective-signature") == [ErrorClass.ERR_NOT_SAME]


def test_collective_count_mismatch(recording):
    events.record_collective("c", "allreduce", rank=0)
    events.record_collective("c", "allreduce", rank=1)
    events.record_collective("c", "allreduce", rank=0)   # rank 1 never re-enters
    f = checkers.check_collective_order()
    assert codes(f, "collective-order") == [ErrorClass.ERR_NOT_SAME]


# ---------------------------------------------------------------------------
# (b) deadlock
# ---------------------------------------------------------------------------


def test_sendrecv_ring_is_clean(recording):
    # the combined MPI_Sendrecv form completes round-atomically: every ring
    # schedule is a legal cycle
    events.record_p2p_round("c", ROUND, mode="sendrecv", size=3)
    assert checkers.check_deadlock() == []


def test_sync_cycle_deadlocks(recording):
    events.record_p2p_round("c", ROUND, mode="sync", size=3)
    f = checkers.check_deadlock()
    assert codes(f, "deadlock") == [ErrorClass.ERR_PENDING]
    assert "wait-for cycle" in f[0].message


def test_unmatched_send(recording):
    events.record_p2p("send", 0, 1, comm="c")
    f = checkers.check_deadlock()
    assert codes(f, "unmatched-p2p") == [ErrorClass.ERR_PENDING]


def test_matched_send_recv_stream(recording):
    events.record_p2p("send", 0, 1, comm="c")
    events.record_p2p("recv", 1, 0, comm="c")
    assert checkers.check_deadlock() == []


def test_illegal_matching_round(recording):
    events.record_p2p_round("c", [(0, 1), (0, 2)], mode="sendrecv", size=3)
    f = checkers.check_deadlock()
    assert codes(f, "matching-round") == [ErrorClass.ERR_RANK]


# ---------------------------------------------------------------------------
# (b') deadlock property: all and only the cyclic sync schedules
# ---------------------------------------------------------------------------


def _partial_perms(n):
    """Every injective partial map on {0..n-1} as an edge list."""

    ranks = range(n)
    for k in range(n + 1):
        for srcs in itertools.combinations(ranks, k):
            for dsts in itertools.permutations(ranks, k):
                yield tuple(zip(srcs, dsts))


def _has_cycle(perm):
    nxt = dict(perm)
    for start in nxt:
        seen = set()
        r = start
        while r in nxt:
            if r in seen:
                return True
            seen.add(r)
            r = nxt[r]
    return False


def _check_deadlock_property(schedule):
    """The detector flags ERR_PENDING/deadlock iff some sync round of the
    schedule is cyclic — and stays silent otherwise (no false positives on
    acyclic sync rounds or any sendrecv round)."""

    events.reset()
    prev = events.set_recording(True)
    try:
        for mode, perm in schedule:
            events.record_p2p_round("c", perm, mode=mode, size=4)
    finally:
        events.set_recording(prev)
    f = checkers.check_deadlock()
    events.reset()
    expected = any(m == "sync" and _has_cycle(p) for m, p in schedule)
    flagged = any(x.check == "deadlock" for x in f)
    assert flagged == expected, (schedule, [str(x) for x in f])
    if expected:
        assert ErrorClass.ERR_PENDING in codes(f, "deadlock")
    else:
        assert f == [], (schedule, [str(x) for x in f])


try:
    from hypothesis import given, settings
    from hypothesis import strategies as st

    HAVE_HYPOTHESIS = True
except ImportError:  # container without hypothesis: exhaustive fallback below
    HAVE_HYPOTHESIS = False


if HAVE_HYPOTHESIS:

    _perm_st = st.builds(
        lambda pairs: tuple(zip([s for s, _ in pairs], [d for _, d in pairs])),
        st.lists(st.tuples(st.integers(0, 3), st.integers(0, 3)), max_size=4),
    ).filter(
        lambda p: len({s for s, _ in p}) == len(p)
        and len({d for _, d in p}) == len(p)
    )

    @settings(max_examples=300, deadline=None)
    @given(st.lists(
        st.tuples(st.sampled_from(["sync", "sendrecv"]), _perm_st),
        min_size=1, max_size=3,
    ))
    def test_deadlock_detector_property(schedule):
        _check_deadlock_property(schedule)

else:

    @pytest.mark.parametrize("perm", list(_partial_perms(3)))
    @pytest.mark.parametrize("mode", ["sync", "sendrecv"])
    def test_deadlock_detector_exhaustive_single_round(mode, perm):
        _check_deadlock_property([(mode, perm)])

    @pytest.mark.parametrize("schedule", [
        # acyclic sync chain after a legal sendrecv ring
        [("sendrecv", ((0, 1), (1, 2), (2, 0))), ("sync", ((0, 1), (1, 2)))],
        # cycle buried in the second round
        [("sync", ((0, 1),)), ("sync", ((1, 2), (2, 1)))],
        # self-loop is a 1-cycle
        [("sync", ((2, 2),))],
        # reversal across rounds is fine: round 1 completes before round 2
        [("sync", ((0, 1),)), ("sync", ((1, 0),))],
        # the same ring is legal combined, fatal unbuffered
        [("sendrecv", ((0, 1), (1, 0))), ("sync", ((0, 1), (1, 0)))],
    ])
    def test_deadlock_detector_exhaustive_multi_round(schedule):
        _check_deadlock_property(schedule)


# ---------------------------------------------------------------------------
# (c) future / request lifecycle
# ---------------------------------------------------------------------------


def test_dangling_future(recording):
    t = events.next_token()
    events.record_future_create(t, "immediate_allreduce")
    f = checkers.check_future_lifecycle()
    assert codes(f, "dangling-future") == [ErrorClass.ERR_REQUEST]
    assert "immediate_allreduce" in f[0].message


def test_consumed_future_clean(recording):
    t = events.next_token()
    events.record_future_create(t, "immediate_allreduce")
    events.record_future_consume(t, "get")
    assert checkers.check_future_lifecycle() == []


def test_donated_start_race(recording):
    t = events.next_token()
    events.record_persistent_init(t, donated=True)
    events.record_persistent_start(
        t, donated=True, prev_outstanding=True, has_continuations=True)
    f = checkers.check_future_lifecycle()
    assert codes(f, "donated-start-race") == [ErrorClass.ERR_BUFFER]


def test_donated_start_sequential_clean(recording):
    t = events.next_token()
    events.record_persistent_init(t, donated=True)
    for _ in range(3):
        events.record_persistent_start(
            t, donated=True, prev_outstanding=False, has_continuations=False)
    assert checkers.check_future_lifecycle() == []


# ---------------------------------------------------------------------------
# (d) RMA epochs
# ---------------------------------------------------------------------------


def test_cross_epoch_put(recording):
    # unreachable through the public API (fence drains pending puts before
    # the epoch increments) — seeded at the ledger layer, the MUST idiom
    events.record_rma_apply(1, issue_epoch=0, apply_epoch=2)
    f = checkers.check_rma_epochs()
    assert codes(f, "cross-epoch-put") == [ErrorClass.ERR_WIN]


def test_same_epoch_put_clean(recording):
    events.record_rma_apply(1, issue_epoch=1, apply_epoch=1)
    assert checkers.check_rma_epochs() == []


def test_attach_detach_imbalance(recording):
    events.record_rma_pages("rma_attach", 7, 3)
    f = checkers.check_rma_epochs()
    assert codes(f, "attach-detach-imbalance") == [ErrorClass.ERR_RMA_ATTACH]
    events.record_rma_pages("rma_detach", 7, 3)
    assert checkers.check_rma_epochs() == []


# ---------------------------------------------------------------------------
# (e) I/O and checkpoint joins
# ---------------------------------------------------------------------------


def test_open_split_collective(recording):
    events.record_io_split("io_split_begin", "/tmp/f.bin", "write_at_all")
    f = checkers.check_io_joins()
    assert codes(f, "split-collective-open") == [ErrorClass.ERR_IO]
    events.record_io_split("io_split_end", "/tmp/f.bin", "write_at_all")
    assert checkers.check_io_joins() == []


def test_unjoined_checkpoint_save(recording):
    events.record_ckpt("ckpt_save", 1, 0)
    f = checkers.check_io_joins()
    assert codes(f, "unjoined-save") == [ErrorClass.ERR_IO]
    events.record_ckpt("ckpt_join", 1)
    assert checkers.check_io_joins() == []


# ---------------------------------------------------------------------------
# integration: recording through the real interface (8 virtual devices)
# ---------------------------------------------------------------------------


def test_interface_recording_end_to_end(subproc):
    out = subproc("""
import jax.numpy as jnp
from repro import core as mpx
from repro.analysis import checkers, events
from repro.core import tool

tool.cvar_set("analysis_recording", True)
comm = mpx.world()
perm = [(i, (i + 1) % comm.size()) for i in range(comm.size())]

def prog(x):
    y = comm.allreduce(x)
    y = comm.send_recv(y, perm)
    return y + comm.immediate_allreduce(x).get()

comm.spmd(prog)(jnp.ones(8))
assert len(events.ledger()) > 0, "interface recorded nothing"
findings = checkers.run_all()
assert findings == [], [str(f) for f in findings]
print("CLEAN_OK", len(events.ledger()))

def leak(x):
    comm.immediate_allreduce(x)      # never consumed
    return x

comm.spmd(leak)(jnp.ones(8))
f = [x for x in checkers.run_all() if x.check == "dangling-future"]
assert len(f) == 1 and f[0].code.name == "ERR_REQUEST", [str(x) for x in f]
assert "immediate_allreduce" in f[0].message
print("DANGLING_OK")
""")
    assert "CLEAN_OK" in out and "DANGLING_OK" in out


# ---------------------------------------------------------------------------
# meta-checks: pvar registry and swallowed failures, repo-wide
# ---------------------------------------------------------------------------


def test_every_written_pvar_is_registered():
    f = static.unregistered_pvars(["src", "benchmarks"])
    assert f == [], [str(x) for x in f]


def test_no_swallowed_failures_repo_wide():
    f = static.swallowed_failures(["src", "benchmarks"])
    assert f == [], [str(x) for x in f]


def test_every_opened_span_is_registered():
    f = static.unregistered_spans(["src", "benchmarks"])
    assert f == [], [str(x) for x in f]


def test_static_scan_flags_an_unregistered_span(tmp_path):
    bad = tmp_path / "spans.py"
    bad.write_text(
        "from repro.core import tool\n"
        "with tool.span('repro.engine.step', step=0):\n"
        "    with tool.span('repro.never.registered_xyz'):\n"
        "        pass\n"
    )
    f = static.run_static([str(tmp_path)])
    spans = [x for x in f if x.check == "unregistered-span"]
    assert [x.code for x in spans] == [ErrorClass.ERR_ARG]
    assert "repro.never.registered_xyz" in spans[0].message
    assert spans[0].subject.endswith("spans.py:3")


@pytest.mark.parametrize("imported, call", [
    ("from repro.core.tool import span", "span({!r})"),
    ("from repro.core.tool import span as sp", "sp({!r})"),
    ("from repro.core import tool as t", "t.span({!r})"),
    ("import repro.core.tool", "repro.core.tool.span({!r})"),
    ("import repro.core.tool as tl", "with tl.span({!r}, rid=1):\n    pass"),
])
def test_static_scan_follows_the_names_span_is_imported_as(tmp_path, imported, call):
    (tmp_path / "spans.py").write_text("\n".join(
        [imported, call.format("repro.never.registered_xyz"),
         call.format("repro.engine.step"), ""]))
    f = static.unregistered_spans([str(tmp_path)])
    assert [x.check for x in f] == ["unregistered-span"]
    assert "repro.never.registered_xyz" in f[0].message
    assert f[0].subject.endswith("spans.py:2")


def test_static_scan_ignores_other_functions_named_span(tmp_path):
    (tmp_path / "other.py").write_text(
        "from harness import span\n"
        "import mylib\n"
        "span('bench.window', True)\n"
        "mylib.span('anything')\n"
    )
    assert static.unregistered_spans([str(tmp_path)]) == []


def test_pvar_strict_rejects_unregistered():
    prev = tool.pvar_strict(True)
    try:
        with pytest.raises(errors.Error) as ei:
            tool.pvar_count("definitely_not_a_registered_pvar")
        assert ei.value.klass == ErrorClass.ERR_ARG
        tool.pvar_count("persistent_start")     # registered: still fine
    finally:
        tool.pvar_strict(prev)


def test_static_scan_flags_seeded_defects(tmp_path):
    bad = tmp_path / "bad.py"
    bad.write_text(
        "from repro.core import tool\n"
        "try:\n    pass\nexcept Exception:\n    pass\n"
        "tool.pvar_count('never_registered_xyz')\n"
    )
    f = static.run_static([str(tmp_path)])
    assert ErrorClass.ERR_OTHER in codes(f, "swallowed-failure")
    assert ErrorClass.ERR_ARG in codes(f, "unregistered-pvar")
    ok = tmp_path / "ok.py"
    ok.write_text(
        "try:\n    pass\n"
        "except Exception:  # lint: allow-broad-except — reraised below\n"
        "    raise\n"
    )
    assert static.swallowed_failures([str(ok)]) == []
