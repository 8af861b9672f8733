"""P2 resource-allocation solver: exactness vs brute force + constraints
(paper eq. 22 / §IV-D), property-based via hypothesis; and bitwise parity
of the batched solve with a frozen copy of the scalar one it replaced."""
import numpy as np
import pytest

pytest.importorskip("hypothesis")  # property tests need the dev extra
from hypothesis import given, settings, strategies as st

from repro.core import allocation
from repro.core.allocation import solve_bandwidth, solve_bandwidths, solve_p2
from repro.core.cost import (SystemParams, k_eps, objective, round_cost,
                             total_time, uplink_time)


def _sp(seed=0, M=8):
    sp = SystemParams(M=M, seed=seed, b_min=1.0 / 50)
    sp.S_m = np.random.default_rng(seed).uniform(5e5, 2e6, M)
    sp.d_model_bits = 6e6
    return sp


@settings(max_examples=25, deadline=None)
@given(seed=st.integers(0, 10_000), E=st.integers(1, 20),
       nsel=st.integers(1, 8))
def test_bandwidth_constraints(seed, E, nsel):
    sp = _sp(seed)
    a = np.zeros(sp.M)
    a[np.random.default_rng(seed).choice(sp.M, nsel, replace=False)] = 1
    b = solve_bandwidth(a, E, sp)
    # (22b): full budget allocated among selected
    assert abs(b.sum() - 1.0) < 1e-6
    # (22c): minimum bandwidth for every selected client
    assert (b[a > 0] >= sp.b_min - 1e-9).all()
    # no bandwidth for unselected clients
    assert (b[a == 0] == 0).all()


@settings(max_examples=15, deadline=None)
@given(seed=st.integers(0, 10_000), E=st.integers(1, 10))
def test_bandwidth_beats_random_feasible(seed, E):
    """The min-max solution's latency must be <= any random feasible split."""
    sp = _sp(seed, M=6)
    a = np.ones(sp.M)
    b_opt = solve_bandwidth(a, E, sp)
    t_opt = total_time(a, b_opt, E, sp)
    rng = np.random.default_rng(seed)
    for _ in range(20):
        raw = rng.uniform(sp.b_min, 1.0, sp.M)
        b = raw / raw.sum()
        if (b < sp.b_min).any():
            continue
        assert t_opt <= total_time(a, b, E, sp) + 1e-9


def test_bandwidth_equalizes_finish_times():
    """Unconstrained optimum: every selected client finishes uplink at τ."""
    sp = _sp(3, M=5)
    sp.b_min = 1e-6
    a = np.ones(sp.M)
    E = 4
    b = solve_bandwidth(a, E, sp)
    finish = E * sp.Q_C + uplink_time(a, b, sp)
    assert np.ptp(finish) < 1e-6 * finish.mean()


def test_p2_guard_never_increases_E():
    sp = _sp(1)
    a = np.ones(sp.M)
    _, e_new, _ = solve_p2(a, E_last=3, sp=sp)
    assert e_new <= 3


def test_p2_beats_uniform_allocation():
    sp = _sp(7)
    a = np.ones(sp.M)
    b, E, val = solve_p2(a, E_last=sp.E_max, sp=sp)
    uni = a / a.sum()
    for E_u in range(1, sp.E_max + 1):
        assert val <= objective(a, uni, E_u, sp) + 1e-9


def test_k_eps_monotone_decreasing_in_E():
    ks = [k_eps(E, 0.1) for E in range(1, 21)]
    assert all(a >= b for a, b in zip(ks, ks[1:]))
    # Corollary 4 floor: K_eps -> 1/eps^2 as E -> inf
    assert ks[-1] >= 1.0 / 0.1 ** 2


def test_round_cost_increases_with_E():
    sp = _sp(2)
    a = np.ones(sp.M)
    b = solve_bandwidth(a, 1, sp)
    costs = [round_cost(a, b, E, sp) for E in (1, 5, 10, 20)]
    assert all(c1 <= c2 + 1e-12 for c1, c2 in zip(costs, costs[1:]))


# ---------------------------------------------------------------------------
# Bitwise parity with the scalar solver the batched one replaced
# ---------------------------------------------------------------------------

def frozen_solve_bandwidth(a, E, sp):
    """The scalar per-E solver, frozen as it was before the batched solve:
    200 bisection steps of a Python closure, whatever the bracket does."""
    sel = np.where(a > 0)[0]
    b = np.zeros(sp.M)
    if len(sel) == 0:
        return b
    size = (sp.S_m[sel] + sp.omega * sp.d_model_bits) / sp.G_m[sel]  # bits
    offs = E * sp.Q_C[sel]                                # s

    def excess(tau: float) -> float:
        denom = np.maximum(tau - offs, 1e-12)
        return float(np.sum(size / (sp.B * denom)) - 1.0)

    lo = float(np.max(offs)) + 1e-9
    hi = lo + float(np.sum(size)) / sp.B + 1.0
    while excess(hi) > 0:
        hi *= 2.0
    for _ in range(200):
        mid = 0.5 * (lo + hi)
        if excess(mid) > 0:
            lo = mid
        else:
            hi = mid
    tau = hi
    bs = size / (sp.B * np.maximum(tau - offs, 1e-12))
    # enforce b_min by clip + renormalise the rest (waterfilling step)
    for _ in range(len(sel)):
        low = bs < sp.b_min
        if not low.any():
            break
        fixed = np.sum(np.where(low, sp.b_min, 0.0))
        free = ~low
        if fixed >= 1.0 or not free.any():
            bs = np.full(len(sel), 1.0 / len(sel))
            break
        bs = np.where(low, sp.b_min, bs * (1.0 - fixed) / np.sum(bs[free]))
    bs = bs / bs.sum()
    b[sel] = bs
    return b


def frozen_solve_p2(a, E_last, sp):
    """The scalar E sweep, frozen as it was before the batched solve."""
    best = None
    for E in range(1, sp.E_max + 1):
        b = frozen_solve_bandwidth(a, E, sp)
        val = objective(a, b, E, sp)
        if best is None or val < best[2]:
            best = (b, E, val)
    b_hat, e_hat, val = best
    if e_hat > E_last:           # guard (paper §IV-D): never increase E
        e_hat = E_last
        b_hat = frozen_solve_bandwidth(a, e_hat, sp)
        val = objective(a, b_hat, e_hat, sp)
    return b_hat, e_hat, val


def _parity_sp(M, seed, clip, fade, nsel):
    """A heterogeneous fleet; ``clip`` sets b_min so the waterfilling
    clips (halfway between E=5's smallest unclipped share and a fair
    share) or never does (1e-6); ``fade`` draws non-unit channel gains."""
    rng = np.random.default_rng([M, seed])
    sp = SystemParams(M=M, seed=seed, b_min=1e-6)
    sp.S_m = rng.uniform(5e5, 2e6, M)
    sp.d_model_bits = 6e6
    if fade:
        sp.G_m = rng.uniform(0.05, 1.5, M)
    a = np.zeros(M)
    a[rng.choice(M, nsel, replace=False)] = 1.0
    if clip:
        shares = frozen_solve_bandwidth(a, 5, sp)[a > 0]
        sp.b_min = 0.5 * (shares.min() + 1.0 / nsel)
    return sp, a


def _cohorts(M):
    return sorted({1, 2, M // 4, M // 2, M - 1, M})


@pytest.mark.parametrize("E", range(1, 21))
@pytest.mark.parametrize("M", [8, 48, 50])
def test_solve_bandwidth_bitwise_equals_scalar(M, E):
    """Every cohort size, with and without b_min clipping and fading: the
    one-row batch and row E of the full batch are bitwise the scalar."""
    Es = np.arange(1, 21)
    for nsel in _cohorts(M):
        for clip in (False, True):
            for fade in (False, True):
                sp, a = _parity_sp(M, E, clip, fade, nsel)
                want = frozen_solve_bandwidth(a, E, sp)
                assert np.array_equal(solve_bandwidth(a, E, sp), want)
                assert np.array_equal(solve_bandwidths(a, Es, sp)[E - 1],
                                      want)


@pytest.mark.parametrize("fade", [False, True], ids=["unit_gain", "faded"])
@pytest.mark.parametrize("clip", [False, True], ids=["no_clip", "clip"])
@pytest.mark.parametrize("M", [8, 48, 50])
def test_solve_p2_bitwise_equals_scalar(M, clip, fade):
    """b, E and the objective, for cohorts of 1 to M and a guard that
    binds (small E_last) or not."""
    for nsel in _cohorts(M):
        sp, a = _parity_sp(M, nsel, clip, fade, nsel)
        if clip and nsel > 1:    # the clip engages: it changes the split
            free = sp.copy()
            free.b_min = 1e-6
            assert not np.array_equal(frozen_solve_bandwidth(a, 5, sp),
                                      frozen_solve_bandwidth(a, 5, free))
        for E_last in (1, 3, 6, 20):
            b, E, val = solve_p2(a, E_last, sp)
            b0, E0, val0 = frozen_solve_p2(a, E_last, sp)
            assert np.array_equal(b, b0) and E == E0 and val == val0


def test_solve_p2_bitwise_on_the_paper_fleet():
    """Table III's fleet (b_min = 1/50) over random cohorts."""
    sp = SystemParams(seed=0)
    rng = np.random.default_rng(0)
    for _ in range(20):
        a = np.zeros(sp.M)
        a[rng.choice(sp.M, rng.integers(1, sp.M + 1), replace=False)] = 1.0
        b, E, val = solve_p2(a, sp.E_max, sp)
        b0, E0, val0 = frozen_solve_p2(a, sp.E_max, sp)
        assert np.array_equal(b, b0) and E == E0 and val == val0


def test_bisection_stops_at_the_brackets_fixed_point():
    """The batched solve stops well inside its 200-step cap, and counts
    the steps it took; an empty cohort takes none."""
    sp, a = _parity_sp(50, 0, False, False, 25)
    before = allocation.bisect_steps
    solve_bandwidths(a, np.arange(1, 21), sp)
    steps = allocation.bisect_steps - before
    assert 0 < steps < allocation.MAX_BISECT_STEPS
    before = allocation.bisect_steps
    assert not solve_bandwidths(np.zeros(50), [1, 2], sp).any()
    assert allocation.bisect_steps == before
