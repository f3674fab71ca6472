"""Memory-model sanity (BASELINE.md "HBM budget")."""

from sheep_tpu.ops.elim import EXACT_TABLE_BYTES
from sheep_tpu.utils.membudget import build_phase_bytes, max_vertices_for

GIB = 1 << 30


def test_descent_auto_selection_matches_elim():
    small = build_phase_bytes(1 << 14, 1 << 12)
    assert small["descent"] == "exact"
    big = build_phase_bytes(1 << 28, 1 << 24)
    assert big["descent"] == "stream"
    assert big["lift_bytes"] == 4 * ((1 << 28) + 1)  # one table live


def test_exact_stack_is_capped():
    b = build_phase_bytes(1 << 26, 1 << 20, descent="exact")
    assert b["lift_bytes"] <= EXACT_TABLE_BYTES


def test_single_chip_ceiling_is_2_29():
    """16 GiB v5e chip: V=2^29 fits, V=2^30 does not (the documented
    single-chip ceiling with the O(C)-transient displacement fixpoint)."""
    assert max_vertices_for(16 * GIB, 1 << 24) == 1 << 29
    assert build_phase_bytes(1 << 30, 1 << 24)["total_bytes"] > 16 * GIB


def test_model_monotone_in_v_and_chunk():
    f = lambda v, c: build_phase_bytes(v, c)["total_bytes"]
    assert f(1 << 20, 1 << 16) < f(1 << 24, 1 << 16) < f(1 << 24, 1 << 20)


def test_inflight_multiplies_staging_and_donation_credits_state():
    """ISSUE 4 sizing: D in-flight executions hold D staging blocks;
    donation aliases one minp table and one oriented block pair back."""
    n, cs = 1 << 20, 1 << 16
    one = build_phase_bytes(n, cs, dispatch_batch=4)
    two = build_phase_bytes(n, cs, dispatch_batch=4, inflight=2)
    three = build_phase_bytes(n, cs, dispatch_batch=4, inflight=3)
    assert two["staging_bytes"] == 2 * one["staging_bytes"]
    assert three["staging_bytes"] == 3 * one["staging_bytes"]
    # the pipelined driver stages its blocks even at N == 1 (inflight
    # alone selects it); only the fully synchronous path is staging-free
    assert build_phase_bytes(n, cs, inflight=3)["staging_bytes"] == \
        3 * 4 * 4 * cs
    assert build_phase_bytes(n, cs)["staging_bytes"] == 0

    table = 4 * (n + 1)
    unit = one["staging_bytes"]
    don = build_phase_bytes(n, cs, dispatch_batch=4, inflight=2,
                            donate=True)
    assert don["persistent_bytes"] == two["persistent_bytes"] - table
    assert don["staging_bytes"] == two["staging_bytes"] - unit // 2
    assert don["total_bytes"] < two["total_bytes"]
