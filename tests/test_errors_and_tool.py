"""Error handling (paper C5: opt-in trace-time checking, typed exceptions
with error classes) and the tool interface (cvars/pvars)."""

from __future__ import annotations

import jax.numpy as jnp
import pytest

from repro import core as mpx
from repro.core import errors, tool


def test_error_classes_and_codes():
    exc = None
    try:
        errors.fail(errors.ErrorClass.ERR_RANK, "bad rank")
    except errors.RankError as e:
        exc = e
    assert exc is not None
    assert exc.klass is errors.ErrorClass.ERR_RANK
    assert "bad rank" in str(exc)


def test_error_checking_toggle():
    comm = mpx.world()
    mpx.set_error_checking(False)
    try:
        # out-of-range root passes unchecked (the compile-time macro off)
        fn = comm.spmd(lambda: mpx.broadcast(comm, jnp.float32(1.0), root=0))
        fn()
    finally:
        mpx.set_error_checking(True)
    with pytest.raises(errors.RootError):
        comm.spmd(lambda: mpx.broadcast(comm, jnp.float32(1.0), root=99))()


def test_invalid_root_raises():
    comm = mpx.world()
    with pytest.raises(errors.RootError):
        comm.run(lambda: mpx.broadcast(comm, jnp.float32(0.0), root=-1))


def test_copy_is_deleted():
    import copy

    comm = mpx.world()
    with pytest.raises(errors.CommError):
        copy.copy(comm)
    dup = comm.dup()
    assert dup.size() == comm.size()


def test_cvars_registry():
    assert "error_checking" in tool.cvar_list()
    tool.cvar_set("error_checking", False)
    assert tool.cvar_get("error_checking") is False
    tool.cvar_set("error_checking", True)
    with pytest.raises(errors.TypeError_):
        tool.cvar_set("error_checking", "yes")
    with pytest.raises(errors.ArgError):
        tool.cvar_set("nonexistent", 1)


def test_pvar_counters():
    tool.pvar_reset()
    comm = mpx.world()
    comm.run(lambda: comm.allreduce(jnp.float32(1.0)))  # method facade counts
    counts = tool.pvar_read()
    assert counts.get("allreduce", 0) >= 1


def test_hlo_collective_parse_smoke():
    stats = tool.parse_hlo_collectives(
        '%ag = f32[16,32]{1,0} all-gather(%p0), dimensions={0}, '
        'replica_groups={{0,1,2,3}}\n'
        '%p0 = f32[4,32]{1,0} parameter(0)\n'
    )
    assert stats.count["all-gather"] == 1
    assert stats.result_bytes["all-gather"] == 16 * 32 * 4


def test_chip_peaks_keyed_by_device_kind():
    v5e = tool.chip_peaks("TPU v5 lite")
    assert v5e.flops_bf16 == tool.PEAK_FLOPS_BF16 == 197e12
    assert v5e.hbm_bandwidth == tool.HBM_BANDWIDTH
    # a device with no published entry is an error, not the v5e default
    with pytest.raises(errors.ArgError, match="cpu"):
        tool.chip_peaks("cpu")
