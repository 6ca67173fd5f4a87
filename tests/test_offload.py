"""Eq. 3 selective-offload solver properties."""
import math

import pytest
from hypothesis import given, settings, strategies as st

from repro.configs.registry import get_config
from repro.core import offload as OF

CFG = get_config("llama-7b")
COEFFS = OF.analytic_coeffs(CFG)


@settings(max_examples=40, deadline=None)
@given(s=st.integers(1, 4_000_000), cap=st.sampled_from([4096, 8192, 16384]))
def test_solver_bounds(s, cap):
    r, d = OF.solve_eq3(COEFFS, s, cap, CFG.num_layers)
    assert 0.0 <= r <= 1.0
    assert 1 <= d <= max(1, math.ceil(s / cap))           # never worse than
    if s <= cap:                                          # naive sharding
        assert d == 1 and r == 0.0


def test_eq3_saturation_cap_binds_without_changing_d():
    """Regression for the formerly-dead r upper bound: when overlap allows
    full offload but D(s) already saturates at a smaller ratio, solve_eq3
    must return the smallest ratio reaching that D — not r = 1."""
    c = OF.CostCoeffs(a1=1.0, b1=0.0, g=0.0, a2=1.0, b2=0.0)
    ell, cap, s = 10, 1000, 1500
    # quadratic compute dwarfs the transfer: overlap does NOT bind here
    assert OF.max_overlap_ratio(c, s, OF.OffloadHW()) == 1.0
    r, d = OF.solve_eq3(c, s, cap, ell)
    assert d == 1
    # D(s)=1 is reached at r = 1 - (l·Act(C) - 2·Act(s))/((l-2)·Act(s))
    assert r == pytest.approx(1.0 - (10 * 1000 - 2 * 1500) / (8 * 1500))
    # the cap is free: full offload reaches the same D
    d_full = math.ceil(2 * OF.act_bytes(c, s)
                       / (ell * OF.act_bytes(c, cap)))
    assert max(1, d_full) == d


@settings(max_examples=40, deadline=None)
@given(s=st.integers(1, 4_000_000), cap=st.sampled_from([4096, 8192, 16384]))
def test_eq3_saturation_cap_never_changes_d(s, cap):
    """The applied bound only trims wasted transfer: D(s) must equal what
    the uncapped (overlap-only) ratio would have produced."""
    r, d = OF.solve_eq3(COEFFS, s, cap, CFG.num_layers)
    if s <= cap:
        assert (r, d) == (0.0, 1)
        return
    ell = max(CFG.num_layers, 3)
    act_s, act_c = OF.act_bytes(COEFFS, s), OF.act_bytes(COEFFS, cap)
    r_un = min(1.0, OF.max_overlap_ratio(COEFFS, s, OF.OffloadHW()))
    d_un = math.ceil((2 * act_s + (1 - r_un) * (ell - 2) * act_s)
                     / (ell * act_c))
    d_naive = math.ceil(act_s / act_c)
    d_best = math.ceil(2 * act_s / (ell * act_c))     # D at full offload
    # never worse than the uncapped solve, never better than full offload
    assert max(1, min(d_best, d_naive)) <= d <= max(1, min(d_un, d_naive))
    assert r <= r_un + 1e-12


def test_offload_shrinks_ranks_for_long_sequences():
    _, d_no = OF.solve_eq3(COEFFS, 2_000_000, 8192, CFG.num_layers)
    d_naive = math.ceil(2_000_000 / 8192)
    assert d_no < d_naive                                  # paper Fig. 11(a)


def test_overlap_constraint_binds_for_linear_compute():
    """Attention-free (quadratic=False): linear compute can't hide linear
    transfers as well — the feasible ratio drops (DESIGN.md §5)."""
    r_quad = OF.max_overlap_ratio(COEFFS, 500_000, OF.OffloadHW())
    c_lin = OF.CostCoeffs(a1=0.0, b1=COEFFS.b1, g=COEFFS.g,
                          a2=COEFFS.a2, b2=COEFFS.b2)
    r_lin = OF.max_overlap_ratio(c_lin, 500_000, OF.OffloadHW())
    assert r_lin <= r_quad


@settings(max_examples=30, deadline=None)
@given(s=st.integers(20_000, 3_000_000), d=st.integers(1, 64))
def test_ratio_for_d_consistency(s, d):
    """If ratio_for_d returns r, plugging r back into the D formula must
    need <= d ranks."""
    cap, ell = 8192, CFG.num_layers
    r = OF.ratio_for_d(COEFFS, s, cap, ell, d)
    if r is None:
        return
    act_s = OF.act_bytes(COEFFS, s)
    need = math.ceil((2 * act_s + (1 - r) * (ell - 2) * act_s)
                     / (ell * OF.act_bytes(COEFFS, cap)))
    assert need <= max(d, 1) + 1


@pytest.mark.jax_feature("host_offload")
def test_offload_remat_executes_on_host_memory():
    """Execution side of Eq. 3: a forward under remat="offload" must
    compile and run when the backend exposes a pinned_host memory space
    (skips with a reason elsewhere — the CPU backend has none)."""
    import dataclasses as dc

    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro import compat
    from repro.models.transformer import forward_hidden, init_params
    from repro.parallel.sharding import single_device_runtime

    rt = dc.replace(single_device_runtime(), remat="offload",
                    offload_periods=1)
    with compat.use_mesh(rt.mesh):
        cfg = get_config("llama3.2-3b").reduced()
        params = init_params(jax.random.PRNGKey(0), cfg, rt)
        t = 64
        batch = {"tokens": jnp.zeros((t,), jnp.int32),
                 "seg": jnp.ones((t,), jnp.int32),
                 "pos": jnp.arange(t, dtype=jnp.int32)}
        out = jax.jit(lambda p, b: forward_hidden(p, cfg, rt, b))(params,
                                                                  batch)
        assert np.isfinite(np.asarray(out, np.float32)).all()


# ---------------------------------------------------------------------------
# Eq. 3 transfer-byte pricing (shared with the bytes ledger, ISSUE 9)
# ---------------------------------------------------------------------------

def test_eq3_bytes_matches_solver_arithmetic():
    """d2h == h2d == r·(l-2)·Act(s): exactly the transfer term solve_eq3's
    D(s) numerator subtracts — the two must never drift apart."""
    ell = max(CFG.num_layers, 3)
    for s in (20_000, 262_144, 1_048_576):
        r, _ = OF.solve_eq3(COEFFS, s, 8192, CFG.num_layers)
        d2h, h2d = OF.eq3_bytes(COEFFS, s, r, CFG.num_layers)
        want = r * (ell - 2) * OF.act_bytes(COEFFS, s)
        assert d2h == pytest.approx(want)
        assert h2d == pytest.approx(want)


def test_eq3_bytes_zero_for_nonpositive_ratio():
    assert OF.eq3_bytes(COEFFS, 100_000, 0.0, CFG.num_layers) == (0.0, 0.0)
    assert OF.eq3_bytes(COEFFS, 100_000, -0.5, CFG.num_layers) == (0.0, 0.0)


def test_eq3_bytes_config_passthrough_matches_coeffs():
    d2h_cfg, h2d_cfg = OF.eq3_bytes(CFG, 262_144, 0.5, CFG.num_layers)
    d2h, h2d = OF.eq3_bytes(OF.analytic_coeffs(CFG), 262_144, 0.5,
                            CFG.num_layers)
    assert d2h_cfg == pytest.approx(d2h) and h2d_cfg == pytest.approx(h2d)
    assert d2h_cfg > 0


@settings(max_examples=30, deadline=None)
@given(s=st.integers(1, 2_000_000),
       r=st.floats(min_value=0.0, max_value=1.0))
def test_eq3_bytes_symmetric_and_monotone(s, r):
    d2h, h2d = OF.eq3_bytes(COEFFS, s, r, CFG.num_layers)
    assert d2h == h2d >= 0.0
    d2h2, _ = OF.eq3_bytes(COEFFS, s, min(1.0, r + 0.1), CFG.num_layers)
    assert d2h2 >= d2h


# ---------------------------------------------------------------------------
# stage-aware offload windows (PP x offload, ISSUE 4 satellite)
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("num_stages", [1, 2, 4])
@pytest.mark.parametrize("r", [0.0, 0.2, 0.37, 0.5, 0.8, 1.0])
def test_stage_offload_windows_tile_global_window(num_stages, r):
    """The per-stage windows are the global leading window [0, round(r*n))
    cut at stage boundaries: disjoint, contiguous, and tiling it exactly."""
    cfg = get_config("llama3.2-3b")
    n = OF.scan_periods(cfg)
    if n % num_stages:
        pytest.skip(f"{n} periods don't split into {num_stages} stages")
    k = int(round(r * n))
    windows = OF.stage_offload_windows(cfg, r, num_stages)
    assert len(windows) == num_stages
    n_local = n // num_stages
    cursor = 0
    total = 0
    for s, (lo, hi) in enumerate(windows):
        assert lo == s * n_local                  # anchored at stage start
        assert lo <= hi <= (s + 1) * n_local      # inside the stage span
        if hi > lo:
            assert lo == cursor                   # contiguous with previous
            cursor = hi
        total += hi - lo
    assert total == k                             # tiles [0, k) exactly


@pytest.mark.parametrize("num_stages", [2, 4])
@pytest.mark.parametrize("r", [0.1, 0.33, 0.62, 0.99])
def test_quantized_ratio_makes_uniform_stage_counts_exact(num_stages, r):
    """PP co-plan: after quantize_stage_ratio the SPMD-uniform per-stage
    count (offload_periods with num_stages) sums to the global count with
    zero drift — and never *undershoots* the requested ratio."""
    cfg = get_config("llama3.2-3b")
    n = OF.scan_periods(cfg)
    if n % num_stages:
        pytest.skip(f"{n} periods don't split into {num_stages} stages")
    rq = OF.quantize_stage_ratio(r, n, num_stages)
    assert rq >= min(r, 1.0) - 1e-9
    per_stage = OF.offload_periods(cfg, rq, num_stages)
    assert num_stages * per_stage == int(round(rq * n))


def test_stage_aware_count_fixes_overshoot():
    """Regression: the old global count applied per stage offloaded up to
    num_stages x the planned fraction; the stage-aware count matches it."""
    cfg = get_config("llama3.2-3b")
    n = OF.scan_periods(cfg)
    num_stages = 2
    if n % num_stages:
        pytest.skip(f"{n} periods don't split into {num_stages} stages")
    r = 0.5
    global_count = OF.offload_periods(cfg, r)            # = round(r * n)
    per_stage = OF.offload_periods(cfg, r, num_stages)
    # per-stage x stages stays at the planned global fraction...
    assert num_stages * per_stage == pytest.approx(global_count, abs=1)
    # ...whereas applying the global count per stage overshoots
    old_effective = num_stages * min(global_count, n // num_stages)
    assert old_effective > global_count
