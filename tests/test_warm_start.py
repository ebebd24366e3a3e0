"""Cross-round B&B warm starts (`repro.core.des.WarmStartCache` +
`upper_bound=` incumbent injection): the bit-identity property gate.

The contract under test — the repo's core guarantee extended across
rounds: a warm start may only SHRINK node counts, never change an
answer.  Fuzzed over random (scores, costs, qos, force_include)
instances:

  * ANY valid injected upper bound (+inf, a loose bound, the exact
    optimum) leaves selections / energies / feasibility bit-identical
    to the cold `des_select` / `des_select_batch`;
  * a STALE too-tight bound (below the optimum) is detected and treated
    as invalid — the solver transparently re-solves cold, so the answer
    is still bit-identical;
  * `nodes_explored` is monotonically non-increasing as the bound
    tightens from +inf to the exact optimum;
  * cache-carry across identical consecutive rounds resolves with ZERO
    B&B levels (`nodes_explored == 0`), and annealed-QoS structure
    repeats inject valid incumbents;
  * the sharded `resolve_prework` warm tiers (exact hits, reclassify-
    easy, bound pass-through) keep the drop-in parity contract;
  * the serving frontend invalidates the cache on channel redraw and on
    churn alive-mask changes (round-trip test: warm serve ≡ cold serve
    bit for bit, and redraws force zero carried hits).
"""

import numpy as np
import pytest
from _hyp_compat import given, settings, st

from repro.core import des as des_lib
from repro.core.des import (WarmStartCache, des_select, des_select_batch,
                            des_select_brute_force)


def _instance(seed, k, *, with_inf=True, with_forced=True):
    rng = np.random.default_rng(seed)
    t = rng.dirichlet(np.ones(k))
    e = rng.uniform(0.01, 5.0, size=k)
    if with_inf and rng.random() < 0.4:
        e[rng.random(k) < 0.3] = np.inf
    qos = float(rng.uniform(0.05, 0.95))
    forced = (rng.random(k) < 0.2) if with_forced and rng.random() < 0.4 \
        else None
    d = int(rng.integers(1, k + 1))
    return t, e, qos, d, forced


def _batch(seed, b, k, *, with_inf=True):
    rng = np.random.default_rng(seed)
    t = rng.dirichlet(np.ones(k), size=b)
    e = rng.uniform(0.01, 5.0, size=(b, k))
    if with_inf:
        e[rng.random((b, k)) < 0.15] = np.inf
    return t, e, rng.uniform(0.05, 0.95, size=b)


def _assert_same_answer(res, ref):
    np.testing.assert_array_equal(res.selected, ref.selected)
    np.testing.assert_array_equal(res.energy, ref.energy)
    np.testing.assert_array_equal(res.feasible, ref.feasible)


# ----------------------------------------------------------------------
# sequential solver: upper_bound injection
# ----------------------------------------------------------------------

@settings(max_examples=15, deadline=None)
@given(seed=st.integers(0, 2**31 - 1), k=st.integers(2, 9))
def test_property_any_valid_bound_is_bit_identical(seed, k):
    """For every valid ub in {+inf, loose, exact optimum}: identical
    selection/energy/feasibility, non-increasing nodes as ub tightens,
    and the exact answer still matches the brute-force oracle."""
    t, e, qos, d, forced = _instance(seed, k)
    cold = des_select(t, e, qos, d, force_include=forced)
    if forced is None and cold.feasible and np.isfinite(e).all():
        # sanity-anchor the cold reference itself on the oracle (finite
        # costs only — the oracle contract in tests/test_des.py)
        oracle = des_select_brute_force(t, e, qos, d)
        assert cold.energy == pytest.approx(oracle.energy, abs=1e-9)
    bounds = [np.inf]
    if np.isfinite(cold.energy):
        bounds += [cold.energy * 2.0 + 1.0, cold.energy]  # loose, exact
    prev_nodes = None
    for ub in bounds:  # tightening order
        warm = des_select(t, e, qos, d, force_include=forced,
                          upper_bound=ub)
        np.testing.assert_array_equal(warm.selected, cold.selected)
        assert warm.energy == cold.energy
        assert warm.feasible == cold.feasible
        assert warm.nodes_explored <= cold.nodes_explored
        if prev_nodes is not None:
            assert warm.nodes_explored <= prev_nodes
        prev_nodes = warm.nodes_explored
    # +inf is literally the cold path, node counts included
    inf_res = des_select(t, e, qos, d, force_include=forced,
                         upper_bound=np.inf)
    assert inf_res.nodes_explored == cold.nodes_explored
    assert inf_res.nodes_pruned == cold.nodes_pruned


@settings(max_examples=15, deadline=None)
@given(seed=st.integers(0, 2**31 - 1), k=st.integers(2, 9),
       eps=st.floats(1e-6, 0.5))
def test_property_stale_bound_treated_invalid(seed, k, eps):
    """A bound BELOW the optimum (stale by eps, or wildly so) must be
    detected and the instance re-solved cold — same answer, always."""
    t, e, qos, d, forced = _instance(seed, k)
    cold = des_select(t, e, qos, d, force_include=forced)
    if not np.isfinite(cold.energy):
        return
    for stale in (cold.energy - eps * max(cold.energy, 1.0),
                  cold.energy * 0.25 - 1.0, 0.0, -5.0):
        warm = des_select(t, e, qos, d, force_include=forced,
                          upper_bound=stale)
        np.testing.assert_array_equal(warm.selected, cold.selected)
        assert warm.energy == cold.energy
        assert warm.feasible == cold.feasible


@settings(max_examples=15, deadline=None)
@given(seed=st.integers(0, 2**31 - 1), k=st.integers(2, 8),
       b=st.integers(1, 24))
def test_property_batch_bound_bit_identical(seed, k, b):
    """Batched twin: per-row bounds (valid mixed with stale and +inf)
    leave the whole DESBatchResult answer-identical, nodes <= cold."""
    t, e, qos = _batch(seed, b, k)
    d = min(2, k)
    cold = des_select_batch(t, e, qos, d)
    rng = np.random.default_rng(seed)
    kind = rng.integers(0, 3, size=b)  # 0: +inf, 1: exact, 2: stale
    ub = np.where(kind == 0, np.inf,
                  np.where(np.isfinite(cold.energy),
                           np.where(kind == 1, cold.energy,
                                    cold.energy * 0.5 - 1.0),
                           np.inf))
    warm = des_select_batch(t, e, qos, d, upper_bound=ub)
    _assert_same_answer(warm, cold)
    valid = ~np.isfinite(ub) | (kind == 1)
    assert (warm.nodes_explored[valid]
            <= cold.nodes_explored[valid]).all()
    # scalar broadcast + row-level parity with the sequential solver
    warm1 = des_select_batch(t, e, qos, d, upper_bound=np.inf)
    np.testing.assert_array_equal(warm1.nodes_explored,
                                  cold.nodes_explored)
    row = int(rng.integers(b))
    seq = des_select(t[row], e[row], float(np.broadcast_to(qos, (b,))[row]),
                     d, upper_bound=float(ub[row]))
    np.testing.assert_array_equal(seq.selected, cold.selected[row])


# ----------------------------------------------------------------------
# WarmStartCache
# ----------------------------------------------------------------------

def test_cache_exact_carry_zero_bnb_levels():
    """Identical consecutive rounds resolve entirely from the cache:
    zero nodes explored, zero pruned, answers bit-identical."""
    t, e, qos = _batch(11, 40, 8)
    cache = WarmStartCache()
    cold = des_select_batch(t, e, qos, 2)
    first = des_select_batch(t, e, qos, 2, warm_cache=cache)
    _assert_same_answer(first, cold)
    np.testing.assert_array_equal(first.nodes_explored, cold.nodes_explored)
    second = des_select_batch(t, e, qos, 2, warm_cache=cache)
    _assert_same_answer(second, cold)
    assert (second.nodes_explored == 0).all()
    assert (second.nodes_pruned == 0).all()
    assert cache.stats["exact_hits"] == 40
    assert len(cache) > 0
    cache.invalidate()
    assert len(cache) == 0
    third = des_select_batch(t, e, qos, 2, warm_cache=cache)
    _assert_same_answer(third, cold)
    assert third.nodes_explored.sum() == cold.nodes_explored.sum()


def test_cache_annealed_qos_structure_bounds():
    """Same instances swept along a tightening-to-loosening QoS schedule
    (the z*gamma^(l) annealing): structure-tier incumbents may only
    shrink node counts, never change an answer."""
    t, e, _ = _batch(13, 32, 8)
    cache = WarmStartCache()
    for gamma_l in (0.9, 0.63, 0.44, 0.31):
        cold = des_select_batch(t, e, gamma_l, 2)
        warm = des_select_batch(t, e, gamma_l, 2, warm_cache=cache)
        _assert_same_answer(warm, cold)
        assert (warm.nodes_explored <= cold.nodes_explored).all(), gamma_l
    assert cache.stats["bound_hits"] > 0


def test_cache_differentiates_max_experts():
    """The cache key includes D: the same (scores, costs, qos) at a
    different expert budget must MISS, not replay the wrong answer."""
    t, e, qos = _batch(17, 12, 6, with_inf=False)
    cache = WarmStartCache()
    des_select_batch(t, e, qos, 2, warm_cache=cache)
    cold3 = des_select_batch(t, e, qos, 3)
    warm3 = des_select_batch(t, e, qos, 3, warm_cache=cache)
    _assert_same_answer(warm3, cold3)


def test_cache_hits_rows_stored_from_a_larger_batch():
    """A row stored from one batch hits when looked up alone: its hash
    does not depend on the other rows it was stored with (a matrix
    product's rounding can depend on the number of rows)."""
    t, e, qos = _batch(29, 64, 16, with_inf=False)
    cache = WarmStartCache()
    des_select_batch(t, e, qos, 2, warm_cache=cache)
    for i in range(64):
        one = des_select_batch(t[i:i + 1], e[i:i + 1], qos[i:i + 1], 2,
                               warm_cache=cache)
        assert one.nodes_explored[0] == 0, i
    assert cache.stats["exact_hits"] == 64


def test_cache_eviction_keeps_answers():
    """Overflowing max_entries evicts wholesale but never corrupts: the
    steady-state footprint is bounded by one call's working set (at most
    two entries per row), not by the unbounded call history."""
    t, e, qos = _batch(19, 30, 6)
    cache = WarmStartCache(max_entries=16)
    cold = des_select_batch(t, e, qos, 2)
    for _ in range(3):
        warm = des_select_batch(t, e, qos, 2, warm_cache=cache)
        _assert_same_answer(warm, cold)
    assert len(cache) <= 2 * 30


# ----------------------------------------------------------------------
# sharded warm tiers (resolve_prework) + sweep carry
# ----------------------------------------------------------------------

def test_sharded_resolve_prework_warm_parity():
    """`sharded_des_select_batch(warm_cache=...)` keeps the drop-in
    answer contract across repeated and annealed rounds, and reports the
    {warm_hits, hard_before, hard_after} split."""
    from repro.schedulers.sharded import sharded_des_select_batch

    t, e, qos = _batch(23, 48, 8)
    cache = WarmStartCache()
    cold = sharded_des_select_batch(t, e, qos, 2)
    stats: dict = {}
    first = sharded_des_select_batch(t, e, qos, 2, stats=stats,
                                     warm_cache=cache)
    _assert_same_answer(first, cold)
    np.testing.assert_array_equal(first.nodes_explored,
                                  cold.nodes_explored)
    assert stats["warm_hits"] == 0
    assert stats["hard_before"] == stats["hard"]
    second = sharded_des_select_batch(t, e, qos, 2, stats=stats,
                                      warm_cache=cache)
    _assert_same_answer(second, cold)
    assert stats["warm_hits"] == stats["hard_before"] > 0
    assert stats["hard_after"] == 0
    # annealed follow-up round: bounds flow through, answers identical
    cold2 = sharded_des_select_batch(t, e, np.asarray(qos) * 0.7, 2)
    warm2 = sharded_des_select_batch(t, e, np.asarray(qos) * 0.7, 2,
                                     stats=stats, warm_cache=cache)
    _assert_same_answer(warm2, cold2)
    assert (warm2.nodes_explored <= cold2.nodes_explored).all()


def test_jesa_policy_warm_cache_schedule_parity():
    """A warm-cached jesa policy produces the exact schedule of the cold
    reference across repeated rounds on a fixed channel — alpha, beta,
    and energy bit-identical; only des_nodes may shrink."""
    from repro.core import channel as channel_lib
    from repro.schedulers import ScheduleContext, get_policy

    k, n_tok = 4, 6
    rng = np.random.default_rng(29)
    gates = rng.dirichlet(np.ones(k), size=(k, n_tok))
    ccfg = channel_lib.ChannelConfig(num_experts=k, num_subcarriers=16)
    rates = channel_lib.subcarrier_rates(
        ccfg, channel_lib.sample_channel_gains(ccfg, rng))

    def ctx():
        return ScheduleContext(gate_scores=gates, rates=rates, qos=0.4,
                               max_experts=2,
                               rng=np.random.default_rng(0))

    cold = get_policy("jesa")
    warm = get_policy("jesa", warm_cache=WarmStartCache())
    ref = cold.schedule(ctx())
    nodes = []
    for _ in range(3):
        rs = warm.schedule(ctx())
        np.testing.assert_array_equal(rs.alpha, ref.alpha)
        np.testing.assert_array_equal(rs.beta, ref.beta)
        assert rs.energy == ref.energy
        assert rs.des_nodes <= ref.des_nodes
        nodes.append(rs.des_nodes)
    # consecutive identical rounds ride the exact tier
    assert nodes[-1] <= nodes[0]
    assert warm.warm_cache.stats["exact_hits"] > 0


# ----------------------------------------------------------------------
# frontend invalidation round-trip
# ----------------------------------------------------------------------

def _serve(warm_start, redraw, churn=None, seed=3, num_requests=3):
    from repro.data.tasks import mixed_cost_pool
    from repro.serving.frontend import FrontendConfig, ServingFrontend
    from repro.serving.workload import (QoSClass, WorkloadConfig,
                                        generate_workload)
    pool = mixed_cost_pool(k=8, num_domains=3)
    reqs = generate_workload(WorkloadConfig(
        num_requests=num_requests, rate_hz=4.0, seed=seed,
        classes=(QoSClass("t", 4.0, 3.0, 2, 3, 1.0),)))
    cfg = FrontendConfig(num_layers=2, seed=seed, record_trace=True,
                         redraw_channel=redraw, warm_start=warm_start,
                         churn=churn)
    front = ServingFrontend(policy="jesa", pool=pool, cfg=cfg)
    return front, front.serve(reqs)


def test_frontend_warm_start_round_trip_bit_identical():
    """Pool-mode round trip: warm_start=True serves the EXACT trace of
    the cold run (alpha per round, energies, makespan), with the cache
    carrying across decode rounds on a coherent channel.  seed=2 with 6
    requests makes JESA's BCD run past two iterations in several rounds,
    so the converged re-sweep replays instances already in the cache and
    exact hits genuinely occur."""
    _, cold_rep = _serve(False, redraw=False, seed=2, num_requests=6)
    front, warm_rep = _serve(True, redraw=False, seed=2, num_requests=6)
    assert front.warm_cache is not None
    assert front.warm_cache is front.policy.warm_cache
    assert len(cold_rep.trace) == len(warm_rep.trace) > 0
    for rc, rw in zip(cold_rep.trace, warm_rep.trace):
        np.testing.assert_array_equal(rc.alpha, rw.alpha)
        assert rc.energy_j == rw.energy_j
    assert warm_rep.comm_energy_j == cold_rep.comm_energy_j
    assert warm_rep.makespan_s == cold_rep.makespan_s
    assert warm_rep.des_nodes <= cold_rep.des_nodes
    stats = warm_rep.scheduler_stats
    assert stats["warm_cache_exact_hits"] > 0
    # only the serve-start invalidation fired on the coherent channel
    assert stats["warm_cache_invalidations"] == 1


def test_frontend_invalidates_on_channel_redraw():
    """Per-round fading redraws void the cache every round: answers
    still bit-identical to cold, but no exact hit can survive a redraw
    (every hit the cache reports happened within one coherence window)."""
    _, cold_rep = _serve(False, redraw=True)
    front, warm_rep = _serve(True, redraw=True)
    for rc, rw in zip(cold_rep.trace, warm_rep.trace):
        np.testing.assert_array_equal(rc.alpha, rw.alpha)
    stats = warm_rep.scheduler_stats
    # one invalidation at serve start + one per scheduled round
    assert stats["warm_cache_invalidations"] == 1 + warm_rep.rounds
    assert front.warm_cache.stats["invalidations"] \
        == stats["warm_cache_invalidations"]


def test_frontend_invalidates_on_churn_mask_change():
    """An expert-churn alive-mask flip invalidates carried incumbents
    (the masked costs changed under the cache keys)."""
    from repro.serving.churn import ChurnConfig
    churn = ChurnConfig(p_leave=0.4, min_alive=2, seed=5)
    _, cold_rep = _serve(False, redraw=False, churn=churn)
    front, warm_rep = _serve(True, redraw=False, churn=churn)
    for rc, rw in zip(cold_rep.trace, warm_rep.trace):
        np.testing.assert_array_equal(rc.alive, rw.alive)
        np.testing.assert_array_equal(rc.alpha, rw.alpha)
    # the alive trace flipped at least once -> extra invalidations
    flips = sum(
        not np.array_equal(a.alive, b.alive)
        for a, b in zip(warm_rep.trace[:-1], warm_rep.trace[1:]))
    assert warm_rep.scheduler_stats["warm_cache_invalidations"] >= 1
    if flips:
        assert warm_rep.scheduler_stats["warm_cache_invalidations"] > 1
