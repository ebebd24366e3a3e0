"""A run of a protocol cell, past the harness's look for a chip, with the
timed path broken underneath: `correct` has to come out false for every
fault the cell can have, and true with nothing broken.

Each fault is planted in the program for the length of one run:

- state_unchanged: every round hands its hidden states on without the
  experts' update;
- half_the_batch: half of the wave's queries are not served: their
  answers are copies of the other half's;
- exchange_left_out: no hidden state reaches a remote expert; each token
  gets only its own node's expert;
- answer_altered: one position's logits are altered where they are made;
- selection_altered: one token's selection is changed where the
  scheduler makes it;
- unconverged_costlier: one token's selection is moved to a costlier one
  that still meets the QoS, the energy reported for it, and the round
  says that its descent did not converge.
"""

from __future__ import annotations

import numpy as np
import pytest

import smoke
from reference import schedule_ref

CELLS = [w["name"] for w in smoke.spec()["workloads"]]


def _program():
    smoke.common.use_program()
    from repro.schedulers.host import JESAPolicy
    from repro.serving.dmoe_sim import DMoESimulator

    return DMoESimulator, JESAPolicy


def plant(fault: str, monkeypatch) -> None:
    sim_cls, jesa = _program()
    ffn, serve, schedule = (sim_cls._expert_ffn, sim_cls.serve,
                            jesa.schedule)
    if fault == "state_unchanged":
        monkeypatch.setattr(sim_cls, "_expert_ffn",
                            lambda self, h, p: 0 * ffn(self, h, p))
    elif fault == "exchange_left_out":
        def own_expert_only(self, h, p):
            ye = ffn(self, h, p)                        # (K, N, E, d)
            own = np.eye(ye.shape[0], ye.shape[2])[:, None, :, None]
            return ye * own.astype(ye.dtype)
        monkeypatch.setattr(sim_cls, "_expert_ffn", own_expert_only)
    elif fault == "half_the_batch":
        def half(self, tokens):
            res = serve(self, tokens)
            k = res.logits.shape[0]
            res.logits = res.logits.copy()
            res.logits[k // 2:] = res.logits[: k - k // 2]
            return res
        monkeypatch.setattr(sim_cls, "serve", half)
    elif fault == "answer_altered":
        def altered(self, tokens):
            res = serve(self, tokens)
            res.logits = res.logits.copy()
            res.logits[0, 0] = res.logits[0, 0][::-1]
            return res
        monkeypatch.setattr(sim_cls, "serve", altered)
    elif fault == "selection_altered":
        def changed(self, ctx):
            rs = schedule(self, ctx)
            rs.alpha[0, 0] = 0
            rs.alpha[0, 0, np.argsort(ctx.gate_scores[0, 0])[:2]] = 1
            return rs
        monkeypatch.setattr(jesa, "schedule", changed)
    elif fault == "unconverged_costlier":
        def costlier(self, ctx):
            rs = schedule(self, ctx)
            alpha = smoke.costlier_selection(ctx, rs.alpha, rs.beta)
            if alpha is None:
                return rs
            rs.alpha = alpha
            rs.energy = schedule_ref.round_energy(
                rs.alpha, rs.beta, ctx.rates, np.asarray(ctx.comp_coeff),
                ctx.s0, ctx.p0)
            rs.converged = False
            return rs
        monkeypatch.setattr(jesa, "schedule", costlier)
    else:
        raise ValueError(fault)


@pytest.mark.parametrize("cell", CELLS)
def test_sound_run_is_correct(cell):
    result = smoke.run(cell, seed=2**31 + 17, seconds=0.5)
    assert result["correct"], result["checks"]


@pytest.mark.parametrize("fault", ["state_unchanged", "half_the_batch",
                                   "exchange_left_out", "answer_altered",
                                   "selection_altered",
                                   "unconverged_costlier"])
@pytest.mark.parametrize("cell", CELLS)
def test_fault_is_not_correct(cell, fault, monkeypatch):
    plant(fault, monkeypatch)
    result = smoke.run(cell, seed=2**31 + 29, seconds=0.5)
    assert result["correct"] is False, result["checks"]
