"""Host-exact (numpy) scheduler policies — Algorithms 1/2 + paper baselines.

These are the algorithm bodies formerly exposed as free functions in
`repro.core.jesa` (`jesa_allocate`, `topk_allocate`,
`lower_bound_allocate`); those remain as thin deprecation shims.  Each
policy consumes a `ScheduleContext` and returns the canonical
`RoundSchedule` — bit-for-bit identical decisions to the legacy entry
points (asserted by tests/test_schedulers.py).
"""

from __future__ import annotations

from typing import List, Optional

import numpy as np
from jax.profiler import TraceAnnotation

from repro.core import channel as channel_lib
from repro.core import des as des_lib
from repro.core import energy as energy_lib
from repro.core import subcarrier as sc_lib
from repro.schedulers.base import (
    RoundSchedule,
    ScheduleContext,
    SchedulerPolicy,
    register_policy,
)


def _round_energy(alpha: np.ndarray, beta: np.ndarray, ctx: ScheduleContext
                  ) -> float:
    """P2 objective for a completed (alpha, beta) decision."""
    rates_kk = channel_lib.link_rates(ctx.rates, beta)
    return energy_lib.total_energy(
        alpha, beta, rates_kk, ctx.comp_coeff, ctx.s0, ctx.p0,
        comp_static=ctx.comp_static)


def _allocate_beta(alpha: np.ndarray, ctx: ScheduleContext,
                   beta_method: str) -> np.ndarray:
    """Optimal subcarrier assignment for the traffic implied by alpha.

    The `dmoe.assign` profiler span, whose metadata `links` (active
    links solved) and `solver` (1 if `linear_sum_assignment` solved it,
    0 for the Theorem-1 fast path, greedy, or no links) it sets when it
    ends."""
    with TraceAnnotation("dmoe.assign") as span:
        s_bytes = ctx.s0 * alpha.sum(axis=1).astype(np.float64)
        np.fill_diagonal(s_bytes, 0.0)  # in-situ: no transmission
        res = sc_lib.assign_subcarriers(s_bytes, ctx.rates, ctx.p0,
                                        method=beta_method)
        span.set_metadata(links=res.links, solver=int(res.solver))
    return res.beta


def _des_sweep(gate_scores: np.ndarray, costs: np.ndarray, qos: float,
               max_experts: int, *, solver=None,
               warm_cache=None) -> tuple[np.ndarray, int]:
    """Exact DES for every (source i, token n) at once; returns
    (alpha, nodes).  All K*N instances go through one batched-solver call
    (default `des_lib.des_select_batch`: dedup + frontier-parallel B&B) —
    bit-identical to the per-(i, n) `des_select` loop it replaced.

    `solver` swaps in a drop-in batched front-end with the same signature
    and `DESBatchResult` contract (the device-sharded
    `repro.schedulers.sharded.sharded_des_select_batch` is one).

    `warm_cache` (a `repro.core.des.WarmStartCache`) is forwarded to the
    solver so incumbents carry across sweeps — along the per-layer
    z*gamma^(l) annealing schedule, across BCD iterations, and across
    protocol rounds.  Cached answers stay bit-identical to the cold
    sweep; only node counts shrink.  Passed as a kwarg only when set, so
    drop-in solvers without the parameter keep working cold.

    The solver call is the `dmoe.des` profiler span, whose metadata
    `nodes` (the count returned) and `fallback` (rows the solver marked
    infeasible: Remark-2 Top-D rows) it sets when it ends."""
    if solver is None:
        solver = des_lib.des_select_batch
    kwargs = {} if warm_cache is None else {"warm_cache": warm_cache}
    k, n_tok, n_exp = gate_scores.shape
    flat = np.asarray(gate_scores, dtype=np.float64).reshape(k * n_tok, n_exp)
    active = flat.sum(axis=1) > 0  # padding tokens are never scheduled
    if not active.any():
        return np.zeros_like(gate_scores, dtype=np.int8), 0
    cost_rows = np.repeat(np.asarray(costs, dtype=np.float64), n_tok, axis=0)
    with TraceAnnotation("dmoe.des") as span:
        if active.all():
            res = solver(flat, cost_rows, qos, max_experts, **kwargs)
            alpha = res.selected.astype(np.int8)
        else:
            res = solver(flat[active], cost_rows[active], qos, max_experts,
                         **kwargs)
            alpha = np.zeros((k * n_tok, n_exp), dtype=np.int8)
            alpha[active] = res.selected.astype(np.int8)
        nodes = int(res.nodes_explored.sum())
        span.set_metadata(nodes=nodes,
                          fallback=res.feasible.size
                          - int(np.count_nonzero(res.feasible)))
    return alpha.reshape(gate_scores.shape), nodes


def best_subcarrier_beta(rates: np.ndarray) -> np.ndarray:
    """Every link concurrently on its single best subcarrier (drops C3)."""
    k, _, m = rates.shape
    beta = np.zeros((k, k, m), dtype=np.int8)
    for i in range(k):
        for j in range(k):
            if i != j:
                beta[i, j, int(np.argmax(rates[i, j]))] = 1
    return beta


# ----------------------------------------------------------------------
# JESA — Algorithm 2 (block-coordinate descent on P2)
# ----------------------------------------------------------------------

@register_policy("jesa")
class JESAPolicy(SchedulerPolicy):
    """Joint Expert and Subcarrier Allocation (paper §VI).

    alpha-step: with beta fixed, P2 reduces to P1 -> exact DES per
                (source i, hidden-state n)  (Algorithm 1);
    beta-step:  with alpha fixed, P2 reduces to P3 -> optimal assignment.

    Prop. 2 guarantees monotone descent; Theorem 1 / Corollary 1 give
    asymptotic global optimality as M grows.
    """

    def __init__(self, *, max_iters: int = 20, beta_method: str = "auto",
                 qos: Optional[float] = None,
                 warm_cache: Optional[des_lib.WarmStartCache] = None):
        self.max_iters = max_iters
        self.beta_method = beta_method
        self.qos = qos  # None -> use ctx.qos (the layer schedule)
        # Optional cross-round B&B amortization (off by default so the
        # registry-constructed policy stays the reference cold solver):
        # the cache carries incumbents across BCD iterations, layers of
        # the z*gamma^(l) schedule, and protocol rounds.  The OWNER of
        # the cache is responsible for `invalidate()` on channel redraw /
        # churn (the serving frontend does both).
        self.warm_cache = warm_cache

    def effective_qos(self, ctx: ScheduleContext) -> float:
        return ctx.qos if self.qos is None else self.qos

    def _alpha_sweep(self, gate_scores: np.ndarray, costs: np.ndarray,
                     qos: float, max_experts: int) -> tuple[np.ndarray, int]:
        """The alpha-step solver — subclass hook so drop-in batched
        front-ends (e.g. `ShardedDESPolicy`) can reroute the sweep
        without touching the BCD loop."""
        return _des_sweep(gate_scores, costs, qos, max_experts,
                          warm_cache=self.warm_cache)

    def schedule(self, ctx: ScheduleContext) -> RoundSchedule:
        k, n_tok, _ = ctx.gate_scores.shape
        m = ctx.num_subcarriers
        qos = self.effective_qos(ctx)

        # --- Initialization (Algorithm 2): alpha <- 1, beta <- random.
        alpha = np.ones((k, n_tok, k), dtype=np.int8)
        cfg = channel_lib.ChannelConfig(num_experts=k, num_subcarriers=m)
        beta = channel_lib.random_subcarrier_assignment(cfg, ctx.rng)

        energy_trace: List[float] = []
        total_nodes = 0
        converged = False
        it = 0

        for it in range(1, self.max_iters + 1):
            # ---- alpha-step: DES per (i, n) under current link rates.
            rates_kk = channel_lib.link_rates(ctx.rates, beta)
            costs = energy_lib.selection_costs(
                rates_kk, beta, ctx.comp_coeff, ctx.s0, ctx.p0)
            new_alpha, nodes = self._alpha_sweep(
                ctx.gate_scores, costs, qos, ctx.max_experts)
            total_nodes += nodes

            # ---- beta-step: optimal assignment for the new traffic.
            new_beta = _allocate_beta(new_alpha, ctx, self.beta_method)
            energy_trace.append(_round_energy(new_alpha, new_beta, ctx))

            if np.array_equal(new_alpha, alpha) and np.array_equal(
                    new_beta, beta):
                converged = True
                alpha, beta = new_alpha, new_beta
                break
            alpha, beta = new_alpha, new_beta

        return RoundSchedule(
            layer=ctx.layer,
            alpha=alpha,
            beta=beta,
            qos=qos,
            policy=self.name,
            energy=energy_trace[-1] if energy_trace else float("inf"),
            energy_trace=energy_trace,
            iterations=it,
            converged=converged,
            des_nodes=total_nodes,
        )


@register_policy("homogeneous")
class HomogeneousPolicy(JESAPolicy):
    """H(z, D) benchmark: JESA with a layer-independent QoS threshold z
    (paper §VII-A3, gamma^(l) = 1)."""

    def __init__(self, *, z: Optional[float] = None, max_iters: int = 20,
                 beta_method: str = "auto"):
        super().__init__(max_iters=max_iters, beta_method=beta_method)
        self.z = z

    def effective_qos(self, ctx: ScheduleContext) -> float:
        if self.z is not None:
            return self.z
        if ctx.qos_schedule is not None:
            return ctx.qos_schedule.homogeneous_z
        return ctx.qos


# ----------------------------------------------------------------------
# Baselines
# ----------------------------------------------------------------------

@register_policy("topk")
class TopKPolicy(SchedulerPolicy):
    """Top-k selection + optimal subcarrier allocation (benchmark), and
    the standard centralized-MoE router on the in-graph path."""

    def __init__(self, *, top_k: Optional[int] = None,
                 beta_method: str = "auto"):
        self.top_k = top_k  # None -> ctx.top_k
        self.beta_method = beta_method

    def effective_qos(self, ctx: ScheduleContext) -> float:
        return 0.0  # Top-k ignores C1; its selection IS the Top-D fallback

    def schedule(self, ctx: ScheduleContext) -> RoundSchedule:
        k, n_tok, _ = ctx.gate_scores.shape
        top_k = self.top_k if self.top_k is not None else ctx.top_k
        # One argsort over all (source, token) rows (same stable order as
        # the former per-token loop); padding rows are masked afterwards.
        alpha = np.zeros((k, n_tok, k), dtype=np.int8)
        sel = np.argsort(-ctx.gate_scores, axis=-1,
                         kind="stable")[..., :top_k]
        np.put_along_axis(alpha, sel, 1, axis=-1)
        alpha *= ctx.active_tokens()[..., None].astype(np.int8)
        beta = _allocate_beta(alpha, ctx, self.beta_method)
        obj = _round_energy(alpha, beta, ctx)
        return RoundSchedule(
            layer=ctx.layer, alpha=alpha, beta=beta, qos=0.0,
            policy=self.name, energy=obj, energy_trace=[obj],
            iterations=1, converged=True, des_nodes=0)

    def route_mask(self, gates, *, qos=0.0, costs=None, top_k: int = 2,
                   max_experts: int = 0):
        from repro.core import selection as sel_lib
        return sel_lib.topk_mask(
            gates, self.top_k if self.top_k is not None else top_k)


@register_policy("lb")
class LowerBoundPolicy(SchedulerPolicy):
    """LB(gamma0, D) benchmark: DES with the C3 constraint dropped —
    every link concurrently uses its single best subcarrier (§VII-A3)."""

    def __init__(self, *, qos: Optional[float] = None):
        self.qos = qos

    def effective_qos(self, ctx: ScheduleContext) -> float:
        return ctx.qos if self.qos is None else self.qos

    def schedule(self, ctx: ScheduleContext) -> RoundSchedule:
        qos = self.effective_qos(ctx)
        beta = best_subcarrier_beta(ctx.rates)
        rates_kk = channel_lib.link_rates(ctx.rates, beta)
        costs = energy_lib.selection_costs(
            rates_kk, beta, ctx.comp_coeff, ctx.s0, ctx.p0)
        alpha, nodes = _des_sweep(ctx.gate_scores, costs, qos,
                                  ctx.max_experts)
        obj = _round_energy(alpha, beta, ctx)
        return RoundSchedule(
            layer=ctx.layer, alpha=alpha, beta=beta, qos=qos,
            policy=self.name, energy=obj, energy_trace=[obj],
            iterations=1, converged=True, des_nodes=nodes)
