"""Batched exact DES (`des_select_batch`): bit-for-bit equivalence with
the per-instance solver and the brute-force oracle, including +inf costs,
all-unreachable rows, padding (all-zero-score) tokens, `force_include`,
duplicated rows (the dedup path), and per-row QoS."""

import numpy as np
import pytest
from _hyp_compat import given, settings, st

from repro.core import des as des_lib


def _assert_batch_matches_reference(t, e, qos, d, forced=None):
    batch = des_lib.des_select_batch(t, e, qos, d, force_include=forced)
    assert len(batch) == t.shape[0]
    for i in range(t.shape[0]):
        fi = None if forced is None else forced[i]
        ref = des_lib.des_select(t[i], e[i], float(qos[i]), d,
                                 force_include=fi)
        np.testing.assert_array_equal(
            batch.selected[i], ref.selected,
            err_msg=f"row {i}: selection mismatch")
        if np.isinf(ref.energy):
            assert np.isinf(batch.energy[i])
        else:
            assert batch.energy[i] == ref.energy, f"row {i}"
        assert batch.feasible[i] == ref.feasible, f"row {i}"
        assert batch.nodes_explored[i] == ref.nodes_explored, f"row {i}"
        assert batch.nodes_pruned[i] == ref.nodes_pruned, f"row {i}"
        # __getitem__ round-trips to a per-instance DESResult
        assert isinstance(batch[i], des_lib.DESResult)


@settings(max_examples=40, deadline=None)
@given(
    seed=st.integers(0, 2**31 - 1),
    k=st.integers(2, 8),
    b=st.integers(1, 16),
    d=st.integers(1, 8),
    uniform_qos=st.booleans(),
    with_forced=st.booleans(),
)
def test_property_batch_equals_per_instance(seed, k, b, d, uniform_qos,
                                            with_forced):
    rng = np.random.default_rng(seed)
    d = min(d, k)
    t = rng.dirichlet(np.ones(k), size=b)
    e = rng.uniform(0.01, 5.0, size=(b, k))
    e[rng.random((b, k)) < 0.15] = np.inf          # unreachable experts
    if b >= 2:
        e[0] = np.inf                              # all-unreachable row
        t[1] = 0.0                                 # padding-style row
    if b >= 4:
        t[3], e[3] = t[2], e[2]                    # duplicate (dedup path)
    qos = rng.uniform(0.05, 0.95, size=b)
    if uniform_qos:
        qos[:] = qos[0]
    if b >= 4:
        qos[3] = qos[2]
    forced = (rng.random((b, k)) < 0.15) if with_forced else None
    _assert_batch_matches_reference(t, e, qos, d, forced)


@settings(max_examples=25, deadline=None)
@given(seed=st.integers(0, 2**31 - 1), k=st.integers(2, 7),
       b=st.integers(1, 8))
def test_property_batch_equals_brute_force(seed, k, b):
    rng = np.random.default_rng(seed)
    t = rng.dirichlet(np.ones(k), size=b)
    e = rng.uniform(0.01, 5.0, size=(b, k))
    qos = rng.uniform(0.05, 0.95, size=b)
    d = int(rng.integers(1, k + 1))
    batch = des_lib.des_select_batch(t, e, qos, d)
    for i in range(b):
        brute = des_lib.des_select_brute_force(t[i], e[i], float(qos[i]), d)
        assert batch.feasible[i] == brute.feasible
        if brute.feasible:
            np.testing.assert_allclose(batch.energy[i], brute.energy,
                                       rtol=1e-9)
            assert t[i][batch.selected[i]].sum() >= qos[i] - 1e-12
            assert batch.selected[i].sum() <= d


def test_batch_scalar_qos_broadcasts():
    rng = np.random.default_rng(0)
    t = rng.dirichlet(np.ones(5), size=6)
    e = rng.uniform(0.1, 2.0, size=(6, 5))
    batch = des_lib.des_select_batch(t, e, 0.4, 2)
    _assert_batch_matches_reference(t, e, np.full(6, 0.4), 2)
    assert batch.selected.shape == (6, 5)


def test_batch_empty():
    res = des_lib.des_select_batch(
        np.zeros((0, 4)), np.zeros((0, 4)), 0.5, 2)
    assert len(res) == 0
    assert res.selected.shape == (0, 4)


def test_batch_dedup_disabled_matches():
    rng = np.random.default_rng(1)
    t = np.repeat(rng.dirichlet(np.ones(4), size=2), 3, axis=0)
    e = np.repeat(rng.uniform(0.1, 2.0, size=(2, 4)), 3, axis=0)
    a = des_lib.des_select_batch(t, e, 0.5, 2, deduplicate=True)
    b = des_lib.des_select_batch(t, e, 0.5, 2, deduplicate=False)
    np.testing.assert_array_equal(a.selected, b.selected)
    np.testing.assert_array_equal(a.energy, b.energy)
    np.testing.assert_array_equal(a.nodes_explored, b.nodes_explored)


def test_batch_all_unreachable_rows_priced_inf():
    t = np.array([[0.4, 0.3, 0.2, 0.1]] * 2)
    e = np.array([[np.inf] * 4, [0.5, np.inf, 0.25, 1.0]])
    res = des_lib.des_select_batch(t, e, np.array([0.5, 0.5]), 2)
    assert not res.feasible[0] and res.energy[0] == np.inf
    assert set(np.nonzero(res.selected[0])[0]) == {0, 1}  # Top-D by score
    assert res.feasible[1] and np.isfinite(res.energy[1])
    assert not res.selected[1][1]  # unreachable expert avoided


def test_batch_shape_mismatch_raises():
    with pytest.raises(ValueError, match="costs shape"):
        des_lib.des_select_batch(np.ones((2, 3)), np.ones((2, 4)), 0.5, 2)


def test_host_sweep_matches_per_token_loop():
    """`_des_sweep` (now batched) must reproduce the per-(i, n) loop it
    replaced, padding tokens included."""
    from repro.schedulers.host import _des_sweep

    k, n_tok = 5, 12
    rng = np.random.default_rng(3)
    gates = rng.dirichlet(np.ones(k), size=(k, n_tok))
    gates[0, -1] = 0.0   # padding token
    gates[2, 0] = 0.0
    costs = rng.uniform(0.1, 3.0, size=(k, k))
    costs[1, 3] = np.inf
    qos, d = 0.45, 2

    alpha, nodes = _des_sweep(gates, costs, qos, d)
    ref_alpha = np.zeros_like(alpha)
    ref_nodes = 0
    for i in range(k):
        for n in range(n_tok):
            if gates[i, n].sum() <= 0:
                continue
            r = des_lib.des_select(gates[i, n], costs[i], qos, d)
            ref_nodes += r.nodes_explored
            ref_alpha[i, n] = r.selected.astype(np.int8)
    np.testing.assert_array_equal(alpha, ref_alpha)
    assert nodes == ref_nodes
    assert (alpha[0, -1] == 0).all() and (alpha[2, 0] == 0).all()


# ----------------------------------------------------------------------
# Unreachable experts (+inf costs): exact, not `_BIG`-clamped
# ----------------------------------------------------------------------

#: Pass 4, source 3, token 30 (second round) of the Phi-3.5-MoE protocol
#: pass at the benchmark's CPU widths, seed 11, under its JESA beta:
#: with unreachable costs clamped to 1e15 the bound lost the 1e-3 J
#: costs to rounding and DES chose {5, 8} at 0.015244 J; the optimum is
#: {4, 8} at 0.014282 J.
PHI_SEED11 = (
    [0.006727094296365976, 0.004536804277449846, 0.0020536005031317472,
     0.018505461513996124, 0.06061439588665962, 0.15187892317771912,
     0.014945043250918388, 0.05448504909873009, 0.43193313479423523,
     0.016963671892881393, 0.0757904201745987, 0.024346502497792244,
     0.02446860633790493, 0.02785804122686386, 0.036252301186323166,
     0.04864099994301796],
    [0.0011319644157344474, 0.0021506947884747133, 0.0031495159314274667,
     0.004, 0.005141353913104522, 0.0061034932010992455,
     0.007099689924031978, 0.008152453923684846, 0.009140627624994787,
     0.010158256116531561, 0.011110458689762712, 0.012110790966404515,
     0.013123607361896997, np.inf, 0.015096608394645038,
     0.016149719372006682],
    0.48999999999999994)

#: A K=8 row whose only QoS-meeting subsets hold an unreachable expert:
#: no pair of reachable experts reaches 0.48, {0, 2} and {0, 1} do.  The
#: `_BIG` clamp priced {0, 2} at 1e15 + 2e-3 J and chose it as feasible;
#: the row is now Remark 2's Top-D, {0, 1}, priced +inf.
K8_REMARK2 = (
    [0.40, 0.35, 0.10, 0.05, 0.04, 0.03, 0.02, 0.01],
    [np.inf, np.inf, 2e-3, 3e-3, 4e-3, 5e-3, 6e-3, 7e-3],
    0.48)


def _unreachable_case(case):
    """(scores (B, K), costs (B, K), qos (B,)) of one case."""
    if case == "phi-seed11":
        t, e, qos = PHI_SEED11
        return np.array([t]), np.array([e]), np.array([qos])
    seed = int(case.rsplit("seed", 1)[1])
    rng = np.random.default_rng(seed)
    b, k = 6, int(case[1:].split("-")[0])
    t = rng.dirichlet(np.full(k, 0.3), size=b)
    e = rng.uniform(1e-3, 2e-2, size=(b, k))
    e[rng.random((b, k)) < 0.25] = np.inf
    qos = rng.uniform(0.3, 0.6, size=b)
    # a row whose reachable experts cannot meet the QoS, though the two
    # unreachable ones of highest score can: Remark 2's Top-D, priced +inf
    top = np.argsort(-t[0])[:2]
    e[0, top] = np.inf
    qos[0] = t[0, top].sum() - 1e-9
    if k == 8:
        t[0], e[0], qos[0] = K8_REMARK2
    return t, e, qos


def _clamped_optimum(t, e, qos, d):
    """The cheapest subset of at most `d` experts meeting `qos` with
    every +inf cost clamped to `_BIG`: what DES chose before it priced
    unreachable experts."""
    import itertools

    e = np.minimum(np.where(np.isfinite(e), e, des_lib._BIG), des_lib._BIG)
    subsets = [s for r in range(1, d + 1)
               for s in itertools.combinations(range(len(t)), r)
               if sum(t[list(s)]) >= qos]
    return set(min(subsets, key=lambda s: e[list(s)].sum()))


@pytest.mark.parametrize("case", ["phi-seed11", "k16-seed0", "k16-seed1",
                                  "k16-seed2", "k8-seed3"])
def test_unreachable_costs_match_enumeration(case):
    """With some experts unreachable, the batched and the per-instance
    DES equal the enumeration of every subset: an unreachable expert is
    chosen only where no reachable subset meets the QoS, and there the
    row is Remark 2's Top-D priced +inf.  At K=8 that changes the
    selection of such a row from what the `_BIG` clamp chose."""
    t, e, qos = _unreachable_case(case)
    batch = des_lib.des_select_batch(t, e, qos, 2)
    for i in range(t.shape[0]):
        brute = des_lib.des_select_brute_force(t[i], e[i], float(qos[i]), 2)
        one = des_lib.des_select(t[i], e[i], float(qos[i]), 2)
        for res in (batch[i], one):
            np.testing.assert_array_equal(res.selected, brute.selected)
            assert res.feasible == brute.feasible
            assert res.energy == brute.energy
    _assert_batch_matches_reference(t, e, qos, 2)
    if case == "phi-seed11":
        assert set(np.flatnonzero(batch.selected[0])) == {4, 8}
        assert batch.energy[0] == 0.014281981538099309
    else:
        assert not batch.feasible[0] and batch.energy[0] == np.inf
    if case == "k8-seed3":
        assert set(np.flatnonzero(batch.selected[0])) == {0, 1}
        assert _clamped_optimum(t[0], e[0], qos[0], 2) == {0, 2}


def test_k8_jesa_sweep_bit_identical():
    """A fixed K=8 JESA sweep (4 channel draws x 2 layers, N=64, the
    Mixtral cell's QoS) keeps the selections, subcarriers, energies, B&B
    node counts and Remark-2 rows it had with `_BIG`-clamped costs.  It
    holds no unreachable expert.  Where one occurs, B&B node counts fall
    (the bound keeps the costs' last bits), and a row whose only
    QoS-meeting subsets need one changes selection (`K8_REMARK2`)."""
    import hashlib

    from repro.core import channel as channel_lib
    from repro.core import energy as energy_lib
    from repro.core.gating import QoSSchedule
    from repro.schedulers import ScheduleContext, get_policy

    solved = des_lib.des_select_batch
    fallback = []

    def counting(*args, **kwargs):
        res = solved(*args, **kwargs)
        fallback.append(int((~res.feasible).sum()))
        return res

    digest, nodes = hashlib.sha256(), 0
    k, n = 8, 64
    rng = np.random.default_rng(20150)
    ch = channel_lib.ChannelConfig(num_experts=k, num_subcarriers=64)
    qos = QoSSchedule(z=1.0, gamma0=0.7)
    des_lib.des_select_batch = counting
    try:
        for _ in range(4):
            rates = channel_lib.subcarrier_rates(
                ch, channel_lib.sample_channel_gains(ch, rng))
            for layer in (1, 2):
                logits = rng.normal(size=(k, n, k)) * 1.5
                gates = np.exp(logits) / np.exp(logits).sum(-1, keepdims=True)
                ctx = ScheduleContext(
                    gate_scores=gates, rates=rates, layer=layer,
                    qos=qos.qos(layer), qos_schedule=qos, max_experts=2,
                    top_k=2, comp_coeff=energy_lib.make_comp_coeffs(k),
                    rng=rng)
                rs = get_policy("jesa").schedule(ctx)
                for a in (rs.alpha, rs.beta, np.asarray(rs.energy_trace)):
                    digest.update(np.ascontiguousarray(a).tobytes())
                nodes += rs.des_nodes
    finally:
        des_lib.des_select_batch = solved
    assert digest.hexdigest() == (
        "0bc6e720283a793356d87f8380466d711305a2aad99cf21d51b4f09daf73dfb6")
    assert nodes == 184825
    assert (sum(fallback), len(fallback)) == (3831, 24)


def test_k16_jesa_sweep_bit_identical():
    """A fixed K=16 JESA sweep at the Jamba cell's shape (M=240
    subcarriers, 16 tokens a source, 2 channel draws x 2 layers, the
    cell's QoS) keeps its selections, subcarriers, energies and B&B node
    count.  The digest and counts were recorded with the shortest-
    augmenting-path Hungarian written in numpy that `linear_sum_assignment`
    used before it called scipy's solver, so they pin the compiled
    solver to the same assignments over 200-206 active links."""
    import hashlib

    from repro.core import channel as channel_lib
    from repro.core import energy as energy_lib
    from repro.core.gating import QoSSchedule
    from repro.schedulers import ScheduleContext, get_policy

    solved = des_lib.des_select_batch
    fallback = []

    def counting(*args, **kwargs):
        res = solved(*args, **kwargs)
        fallback.append(int((~res.feasible).sum()))
        return res

    digest, nodes = hashlib.sha256(), 0
    k, n = 16, 16
    rng = np.random.default_rng(20160)
    ch = channel_lib.ChannelConfig(num_experts=k, num_subcarriers=240)
    qos = QoSSchedule(z=1.0, gamma0=0.7)
    des_lib.des_select_batch = counting
    try:
        for _ in range(2):
            rates = channel_lib.subcarrier_rates(
                ch, channel_lib.sample_channel_gains(ch, rng))
            for layer in (1, 2):
                logits = rng.normal(size=(k, n, k)) * 1.5
                gates = np.exp(logits) / np.exp(logits).sum(-1, keepdims=True)
                ctx = ScheduleContext(
                    gate_scores=gates, rates=rates, layer=layer,
                    qos=qos.qos(layer), qos_schedule=qos, max_experts=2,
                    top_k=2, comp_coeff=energy_lib.make_comp_coeffs(k),
                    rng=rng)
                rs = get_policy("jesa").schedule(ctx)
                for a in (rs.alpha, rs.beta, np.asarray(rs.energy_trace)):
                    digest.update(np.ascontiguousarray(a).tobytes())
                nodes += rs.des_nodes
    finally:
        des_lib.des_select_batch = solved
    assert digest.hexdigest() == (
        "3469b8564a680d14ced74ad9f810e72d4b0e8d304e0ac57ad7568ad4551561c5")
    assert nodes == 84849
    assert (sum(fallback), len(fallback)) == (1708, 11)
