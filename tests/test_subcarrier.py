"""Subcarrier allocation (P3): Hungarian optimality, fast path, C3."""

import importlib.util
import itertools
from pathlib import Path

import numpy as np
import pytest
from _hyp_compat import given, settings, st

from repro.core import channel as channel_lib
from repro.core import subcarrier as sc_lib


def _schedule_ref():
    """The benchmark's plain schedule checks (they import nothing of the
    program), for their independent Hungarian."""
    path = (Path(__file__).resolve().parents[1] / "bench" / "reference"
            / "schedule_ref.py")
    spec = importlib.util.spec_from_file_location("schedule_ref", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _brute_force_assignment(cost):
    n, m = cost.shape
    best = np.inf
    best_cols = None
    for cols in itertools.permutations(range(m), n):
        v = cost[np.arange(n), list(cols)].sum()
        if v < best:
            best = v
            best_cols = cols
    return best, best_cols


@pytest.mark.parametrize("seed", range(15))
def test_hungarian_matches_brute_force(seed):
    rng = np.random.default_rng(seed)
    n, m = rng.integers(2, 6), rng.integers(6, 9)
    cost = rng.uniform(0, 10, size=(n, m))
    rows, cols = sc_lib.linear_sum_assignment(cost)
    got = cost[rows, cols].sum()
    want, _ = _brute_force_assignment(cost)
    assert got == pytest.approx(want, rel=1e-12)
    assert len(set(cols.tolist())) == n  # exclusivity


@settings(max_examples=40, deadline=None)
@given(seed=st.integers(0, 2**31 - 1), n=st.integers(1, 5), extra=st.integers(0, 4))
def test_property_hungarian_optimal(seed, n, extra):
    rng = np.random.default_rng(seed)
    m = n + extra
    cost = rng.uniform(0, 100, size=(n, m))
    rows, cols = sc_lib.linear_sum_assignment(cost)
    got = cost[rows, cols].sum()
    want, _ = _brute_force_assignment(cost)
    np.testing.assert_allclose(got, want, rtol=1e-12)


def test_allocate_respects_c3_and_active_links():
    cfg = channel_lib.ChannelConfig(num_experts=4, num_subcarriers=16)
    rng = np.random.default_rng(0)
    gains = channel_lib.sample_channel_gains(cfg, rng)
    rates = channel_lib.subcarrier_rates(cfg, gains)
    s = np.zeros((4, 4))
    s[0, 1] = 8192.0
    s[2, 3] = 4096.0
    s[1, 1] = 8192.0  # diagonal: must be ignored
    beta = sc_lib.allocate_subcarriers(s, rates, cfg.tx_power_w)
    channel_lib.validate_beta(beta)
    assert beta[0, 1].sum() == 1
    assert beta[2, 3].sum() == 1
    assert beta.sum() == 2


def test_fast_path_matches_hungarian_when_distinct():
    cfg = channel_lib.ChannelConfig(num_experts=3, num_subcarriers=64)
    rng = np.random.default_rng(1)
    gains = channel_lib.sample_channel_gains(cfg, rng)
    rates = channel_lib.subcarrier_rates(cfg, gains)
    s = np.full((3, 3), 8192.0)
    np.fill_diagonal(s, 0.0)
    links = np.argwhere(~np.eye(3, dtype=bool) & (s > 0))
    fast = sc_lib.max_rate_assignment(rates, links)
    if fast is None:
        pytest.skip("collision in this draw")
    b_auto = sc_lib.allocate_subcarriers(s, rates, cfg.tx_power_w, method="auto")
    b_hung = sc_lib.allocate_subcarriers(s, rates, cfg.tx_power_w, method="hungarian")
    e_auto = sc_lib.assignment_energy(s, rates, b_auto, cfg.tx_power_w)
    e_hung = sc_lib.assignment_energy(s, rates, b_hung, cfg.tx_power_w)
    assert e_auto == pytest.approx(e_hung, rel=1e-9)


def test_too_many_links_strict_raises():
    rates = np.ones((4, 4, 3))
    s = np.full((4, 4), 1.0)
    np.fill_diagonal(s, 0.0)
    with pytest.raises(ValueError, match="C3 infeasible"):
        sc_lib.allocate_subcarriers(s, rates, 1e-2, strict=True)


def test_too_many_links_serves_top_m_by_bytes():
    """C3-infeasible traffic (12 links, M=3) is served greedily: the
    three heaviest links each get one subcarrier, the rest none, and the
    round is priced at +inf by the energy accountant — no exception."""
    cfg = channel_lib.ChannelConfig(num_experts=4, num_subcarriers=3)
    rng = np.random.default_rng(2)
    gains = channel_lib.sample_channel_gains(cfg, rng)
    rates = channel_lib.subcarrier_rates(cfg, gains)
    s = rng.uniform(1.0, 10.0, size=(4, 4)) * 8192.0
    np.fill_diagonal(s, 0.0)

    beta = sc_lib.allocate_subcarriers(s, rates, cfg.tx_power_w)
    channel_lib.validate_beta(beta)
    assert beta.sum() == 3  # exactly M links served
    served = set(map(tuple, np.argwhere(beta.sum(axis=-1) > 0)))
    links = np.argwhere(~np.eye(4, dtype=bool) & (s > 0))
    order = np.argsort(-s[links[:, 0], links[:, 1]], kind="stable")[:3]
    assert served == set(map(tuple, links[order]))
    # unserved traffic -> +inf objective, never an exception
    assert sc_lib.assignment_energy(s, rates, beta, cfg.tx_power_w) == np.inf


@pytest.mark.parametrize("k, m, seed, fast", [
    (4, 64, 0, True),      # Theorem-1 fast path applies
    (4, 64, 4, False),
    (8, 64, 0, False),
    (8, 64, 1, False),
    (8, 32, 0, False),     # 45 links > M: the top-M cut
    (16, 240, 0, False),
    (16, 240, 1, False),
    (16, 160, 0, False),   # 188 links > M: the top-M cut
], ids=lambda v: str(v))
def test_assignment_matches_plain_hungarian(k, m, seed, fast):
    """`method="auto"` and `method="hungarian"` give the same beta, and
    the served links' energy is the optimum of the plain Hungarian in
    `bench/reference/schedule_ref.py` over the same weights (the top-M
    links by bytes where more links are active than subcarriers)."""
    cfg = channel_lib.ChannelConfig(num_experts=k, num_subcarriers=m)
    rng = np.random.default_rng(seed)
    rates = channel_lib.subcarrier_rates(
        cfg, channel_lib.sample_channel_gains(cfg, rng))
    s = rng.integers(1, 65, size=(k, k)) * 8192.0
    s[rng.random((k, k)) < 0.25] = 0.0
    np.fill_diagonal(s, 0.0)
    p0 = cfg.tx_power_w

    links = np.argwhere(~np.eye(k, dtype=bool) & (s > 0))
    if len(links) > m:
        top = np.argsort(-s[links[:, 0], links[:, 1]], kind="stable")[:m]
        links = links[np.sort(top)]
    assert (sc_lib.max_rate_assignment(rates, links) is not None) == fast

    auto = sc_lib.assign_subcarriers(s, rates, p0, method="auto")
    hung = sc_lib.assign_subcarriers(s, rates, p0, method="hungarian")
    np.testing.assert_array_equal(auto.beta, hung.beta)
    assert (auto.links, auto.solver) == (len(links), not fast)
    assert (hung.links, hung.solver) == (len(links), True)
    channel_lib.validate_beta(hung.beta)
    served = np.argwhere(hung.beta.sum(axis=-1) > 0)
    np.testing.assert_array_equal(served, links)

    w = np.empty((len(links), m))
    for li, (i, j) in enumerate(links):
        w[li] = p0 * s[i, j] / rates[i, j]
    got = sum(w[li, np.argmax(hung.beta[i, j])]
              for li, (i, j) in enumerate(links))
    assert got == pytest.approx(_schedule_ref().hungarian(w), rel=1e-12)
