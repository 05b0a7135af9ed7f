"""Ahead-of-time compiles of the decision megakernel for a TPU v5e chip.

Interpret mode (``tests/test_kernels.py``) runs the kernel's program on the
CPU and cannot see what Mosaic refuses: block shapes off the (8, 128)
tiling, primitives with no TPU lowering, more scoped VMEM or SMEM than a
kernel may hold.  These tests compile each variant the engine launches —
scan (the fleet pass and the per-tenant StateMatrix pass), scan with cost,
and the planner's freq — at the widths ``chip_smoke.py`` serves (4 tenants,
1,024 partitions, 12 columns) for a described ``v5e:2x2`` topology.  No
chip is needed and nothing runs; a compile here is not a chip run.

The topology is described inside a module-scoped fixture, so only the
worker that runs this file loads the TPU compiler, and it skips where the
compiler is not installed.
"""
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels.decision_fused import decision_fused as df

C = 12                      # lineitem columns
T = 4                       # tenants in the smoke deployment
P = 1024                    # partitions per layout
W = 64                      # the planner's recent-query window
V5E_HBM_BYTES = 16 * 2**30


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 - any failure means "cannot describe"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def no_persistent_cache():
    """A described-chip compile cannot be read back without the chip, so
    keep these compiles out of any persistent cache."""
    from jax.experimental.compilation_cache import compilation_cache as cc
    before = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", before)
    cc.reset_cache()


def _compile(sharding, *, B, T, S, P, emit_scan=True, cost=False, W=0):
    def sds(*shape):
        return jax.ShapeDtypeStruct(shape, jnp.float32, sharding=sharding)

    return df._fused_call.lower(
        sds(B, T, C), sds(B, T, C), sds(C, T, S, P), sds(C, T, S, P),
        sds(T, S, P) if cost else None, sds(T, S) if cost else None,
        sds(W, C) if W else None, sds(W, C) if W else None,
        emit_scan=emit_scan, emit_cost=cost, emit_freq=bool(W),
        bb=df.DEFAULT_BB, bp=df.DEFAULT_BP, interpret=False).compile()


def _assert_fits_chip(compiled):
    assert "tpu_custom_call" in compiled.as_text()
    mem = compiled.memory_analysis()
    used = (mem.argument_size_in_bytes + mem.output_size_in_bytes
            + mem.temp_size_in_bytes)
    assert 0 < used < V5E_HBM_BYTES


@pytest.mark.parametrize("B,T_cap,S_cap,P_cap", [
    (8, 2 * T, 8, P),        # a served pump: 32 events = 8 frames
    (256, 2 * T, 8, P),      # run_batched's bulk pass: 1,024 events
    (8, 2 * T, 8, 2 * P),    # mid-migration: hybrid layouts double P_cap
    (8, T, 21, 2 * P),       # more candidate states than the smoke holds
])
def test_fleet_scan_compiles_for_v5e(one_chip, no_persistent_cache, B, T_cap,
                                     S_cap, P_cap):
    """The FleetMatrix pass over the packed plane; the smoke's plane has
    capacity for 2T tenant rows and 8 states."""
    _assert_fits_chip(_compile(one_chip, B=B, T=T_cap, S=S_cap, P=P_cap))


@pytest.mark.parametrize("S_cap,P_cap", [(8, P), (16, P), (8, 1158)])
def test_state_matrix_scan_compiles_for_v5e(one_chip, no_persistent_cache,
                                            S_cap, P_cap):
    """StateMatrix scores one query against its whole (C, S_cap, P_cap)
    twin: B=1 frame, one tenant.  Hybrid layouts mid-migration give a
    P_cap off the 128-lane tiling, padded up inside the wrapper."""
    _assert_fits_chip(_compile(one_chip, B=1, T=1, S=S_cap, P=P_cap))


@pytest.mark.parametrize("B,S,W_", [
    (8, 14, 0),         # scan with cost
    (40, 8, W),         # all three outputs, as chip_smoke.py's parity check
])
def test_scan_cost_compiles_for_v5e(one_chip, no_persistent_cache, B, S,
                                    W_):
    _assert_fits_chip(_compile(one_chip, B=B, T=T, S=S, P=P, cost=True,
                               W=W_))


@pytest.mark.parametrize("P_max", [P, 2 * P, 193])
def test_planner_freq_compiles_for_v5e(one_chip, no_persistent_cache,
                                       P_max):
    """The micro-move planner's freq pass: source and target layouts as
    S=2 states of one tenant against the recent-query window.  A plane of
    at most one partition block (193) is one block equal to the full
    axis."""
    _assert_fits_chip(_compile(one_chip, B=1, T=1, S=2, P=P_max,
                               emit_scan=False, W=W))
