"""The plain schedule checks: the Hungarian method against enumeration,
and a round scheduled by the program's JESA against the checks, sound
and with one decision changed."""

from __future__ import annotations

import itertools

import numpy as np
import pytest

import smoke
from reference import schedule_ref


@pytest.mark.parametrize("n,m,seed", [(3, 3, 0), (3, 5, 1), (4, 6, 2),
                                      (5, 5, 3)])
def test_hungarian_matches_enumeration(n, m, seed):
    cost = np.random.default_rng(seed).random((n, m))
    best = min(sum(cost[i, c[i]] for i in range(n))
               for c in itertools.permutations(range(m), n))
    assert schedule_ref.hungarian(cost) == pytest.approx(best, rel=1e-12)


def _jesa_round(k=8, n=16, seed=0, layer=1):
    smoke.common.use_program()
    from repro.core import channel as channel_lib
    from repro.core import energy as energy_lib
    from repro.core.gating import QoSSchedule
    from repro.schedulers import ScheduleContext, get_policy

    rng = np.random.default_rng(seed)
    logits = rng.normal(size=(k, n, k)) * 1.5
    gates = np.exp(logits) / np.exp(logits).sum(-1, keepdims=True)
    ch = channel_lib.ChannelConfig(num_experts=k, num_subcarriers=64)
    rates = channel_lib.subcarrier_rates(
        ch, channel_lib.sample_channel_gains(ch, rng))
    qos = QoSSchedule(z=1.0, gamma0=0.7)
    ctx = ScheduleContext(gate_scores=gates, rates=rates, layer=layer,
                          qos=qos.qos(layer), qos_schedule=qos,
                          max_experts=2, top_k=2,
                          comp_coeff=energy_lib.make_comp_coeffs(k),
                          rng=rng)
    return ctx, get_policy("jesa").schedule(ctx)


def _check(ctx, alpha, beta, energy):
    return schedule_ref.check_round(
        ctx.gate_scores, ctx.rates, ctx.qos, ctx.max_experts,
        np.asarray(ctx.comp_coeff), ctx.s0, ctx.p0, alpha, beta, energy)


@pytest.mark.parametrize("seed,layer", [(0, 1), (1, 2), (2, 2)])
def test_jesa_round_has_no_fault(seed, layer):
    ctx, rs = _jesa_round(seed=seed, layer=layer)
    faults = _check(ctx, rs.alpha, rs.beta, rs.energy)
    assert faults == {"subcarrier": 0, "assignment": 0, "selection": 0,
                      "energy": 0}


def test_changed_decisions_are_faults():
    ctx, rs = _jesa_round(seed=1, layer=2)
    # one token moved to its two experts of lowest score
    alpha = rs.alpha.copy()
    alpha[0, 0] = 0
    alpha[0, 0, np.argsort(ctx.gate_scores[0, 0])[:2]] = 1
    assert _check(ctx, alpha, rs.beta, rs.energy)["selection"] >= 1
    # one token moved to a costlier subset that still meets the QoS, with
    # its energy reported as Eq. 3 + 4 give it
    alpha = smoke.costlier_selection(ctx, rs.alpha, rs.beta)
    energy = schedule_ref.round_energy(alpha, rs.beta, ctx.rates,
                                       np.asarray(ctx.comp_coeff), ctx.s0,
                                       ctx.p0)
    assert _check(ctx, alpha, rs.beta, energy)["selection"] == 1
    # two links swap their subcarriers' owners away: one loses its own
    beta = rs.beta.copy()
    i, j = np.argwhere(beta.sum(-1) == 1)[0]
    beta[i, j] = 0
    assert _check(ctx, rs.alpha, beta, rs.energy)["subcarrier"] >= 1
    # a reported energy that is not the schedule's
    assert _check(ctx, rs.alpha, rs.beta, rs.energy * 1.01)["energy"] == 1
