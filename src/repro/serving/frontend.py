"""Unified serving front-end: traffic-driven continuous batching with
per-round expert scheduling.

This is the tier that joins the repo's two previously-separate worlds:
the continuous-batching slot model (`repro.serving.continuous`) and the
scheduler registry (`repro.schedulers`).  A `ServingFrontend` consumes a
workload trace (`repro.serving.workload.generate_workload`), admits
requests into the K decode slots of a DMoE deployment (§III-C step 1:
one query per expert node), and runs ANY registered scheduler policy —
``jesa``, ``async-des``, ``channel-aware``, ``siftmoe``, ... — INSIDE
the decode loop: every protocol round (one model layer of one decode
iteration) is one `SchedulerPolicy.schedule` call over the live batch,
with per-round channel redraws and live expert churn
(`repro.serving.churn.ChurnProcess`).

Two gate backends share the admission/metrics machinery:

  * **pool mode** (`ExpertPool` gates) — the production-scale tier.
    Gate scores are drawn from the calibrated synthetic expertise model
    (`repro.data.tasks`), so thousands of simulated users are feasible;
    slot admission is continuous (a freed slot immediately takes the
    next queued request, newly admitted requests prefill alongside the
    others' decode rows via the zero-padded-gate-row convention).  The
    clock is the wireless time model below.
  * **sim mode** (`DMoESimulator` forward passes) — the exactness tier.
    Admission is batch-synchronous (waves), every round's schedule comes
    from the real model's gates, and the per-round schedules are
    BIT-IDENTICAL to an offline `repro.serving.dmoe_sim.DMoESimulator`
    run on the same token trace (the parity gate in
    tests/test_serving_tier.py): the front-end adds arrival timing and
    metrics around the simulator without perturbing a single decision.

Simulated clock (pool mode): one round costs

    t_round = min(max_link s_ij*8 / R_ij  +  comp_s_per_kb * max_j s_j/1024,
                  max_round_s) + round_overhead_s

i.e. the slowest scheduled wireless transfer (Eq. 2 link rates under the
round's beta) plus the busiest expert's FFN time, clamped so dead links
cannot stall the clock forever.  QoS deadlines resolve against the ideal
(unloaded) service time — see `repro.serving.workload.QoSClass`.

Wall-clock is tracked separately: ``sched_wall_s`` is the real host time
spent inside `SchedulerPolicy.schedule` calls, the quantity the
scheduler-side optimizations (sharded/async DES) are scored against.
"""

from __future__ import annotations

import dataclasses
import time
from typing import Any, Dict, List, Optional, Tuple

import numpy as np

from repro.core import channel as channel_lib
from repro.core import energy as energy_lib
from repro.core import protocol as proto
from repro.core.gating import QoSSchedule
from repro.data.tasks import ExpertPool
from repro.schedulers import (
    RoundSchedule,
    ScheduleContext,
    SchedulerPolicy,
    get_policy,
)
from repro.serving.churn import ChurnConfig, ChurnProcess
from repro.serving.workload import ServeRequest


def _fallback_beta(rates: np.ndarray) -> np.ndarray:
    """Canonical accounting beta for schedules without an OFDMA
    assignment (pure in-graph routing records): every link on its single
    best subcarrier (`repro.schedulers.host.best_subcarrier_beta`)."""
    from repro.schedulers.host import best_subcarrier_beta
    return best_subcarrier_beta(rates)


# ----------------------------------------------------------------------
# Metrics
# ----------------------------------------------------------------------

def latency_percentiles(values, qs=(50, 90, 99)) -> Dict[str, float]:
    """{"p50": ..., "p90": ..., "p99": ...} via linear interpolation;
    empty input yields 0.0 everywhere (metrics must never be NaN)."""
    xs = np.asarray([v for v in values if np.isfinite(v)], dtype=np.float64)
    if xs.size == 0:
        return {f"p{q}": 0.0 for q in qs}
    return {f"p{q}": float(np.percentile(xs, q)) for q in qs}


@dataclasses.dataclass
class RoundRecord:
    """One protocol round of the serving loop (kept when
    ``record_trace=True``; the deterministic-replay and parity tests
    compare these across runs)."""

    iteration: int
    layer: int
    qos: float
    alive: np.ndarray             # (K,) expert availability this round
    alpha: np.ndarray             # (K, N, E) selection
    beta: Optional[np.ndarray]    # (K, K, M) subcarrier assignment
    energy_j: float
    round_s: float                # simulated duration
    live_slots: int


@dataclasses.dataclass
class ServingReport:
    """End-to-end serving metrics for one workload trace."""

    policy: str
    mode: str                             # "pool" | "sim"
    num_requests: int = 0
    completed: int = 0
    tokens_out: int = 0
    rounds: int = 0
    iterations: int = 0
    makespan_s: float = 0.0               # simulated clock at last finish
    wall_s: float = 0.0                   # real host wall time, total
    sched_wall_s: float = 0.0             # real host time in schedule()
    latency: Dict[str, float] = dataclasses.field(default_factory=dict)
    ttft: Dict[str, float] = dataclasses.field(default_factory=dict)
    queue_wait_mean_s: float = 0.0
    qos_violations: int = 0
    qos_violation_rate: float = 0.0
    qos_violations_by_class: Dict[str, float] = dataclasses.field(
        default_factory=dict)
    comm_energy_j: float = 0.0
    comp_energy_j: float = 0.0
    des_nodes: int = 0
    mean_occupancy: float = 0.0
    mean_alive: float = 0.0               # churn: mean live experts/round
    churn_masked_selections: int = 0      # selections removed post-schedule
    churn_qos_misses: int = 0             # token rows under-covered by churn
    scheduler_stats: Dict[str, Any] = dataclasses.field(default_factory=dict)
    requests: List[ServeRequest] = dataclasses.field(default_factory=list)
    trace: List[RoundRecord] = dataclasses.field(default_factory=list)

    @property
    def total_energy_j(self) -> float:
        return self.comm_energy_j + self.comp_energy_j

    @property
    def throughput_tok_s(self) -> float:
        """Simulated-clock decode throughput."""
        return self.tokens_out / self.makespan_s if self.makespan_s > 0 \
            else 0.0

    @property
    def sched_tok_s(self) -> float:
        """Tokens per real second of scheduler host work — the axis the
        sharded/async solver tiers move."""
        return self.tokens_out / self.sched_wall_s if self.sched_wall_s > 0 \
            else 0.0

    def to_json(self) -> Dict[str, Any]:
        """JSON-friendly summary (no arrays, no per-request objects)."""
        return {
            "policy": self.policy,
            "mode": self.mode,
            "num_requests": self.num_requests,
            "completed": self.completed,
            "tokens_out": self.tokens_out,
            "rounds": self.rounds,
            "iterations": self.iterations,
            "makespan_s": round(self.makespan_s, 6),
            "wall_s": round(self.wall_s, 4),
            "sched_wall_s": round(self.sched_wall_s, 4),
            "throughput_tok_s": round(self.throughput_tok_s, 4),
            "sched_tok_s": round(self.sched_tok_s, 4),
            "latency_s": {k: round(v, 6) for k, v in self.latency.items()},
            "ttft_s": {k: round(v, 6) for k, v in self.ttft.items()},
            "queue_wait_mean_s": round(self.queue_wait_mean_s, 6),
            "qos_violation_rate": round(self.qos_violation_rate, 6),
            "qos_violations_by_class": {
                k: round(v, 6)
                for k, v in self.qos_violations_by_class.items()},
            "comm_energy_j": round(self.comm_energy_j, 6),
            "comp_energy_j": round(self.comp_energy_j, 6),
            "total_energy_j": round(self.total_energy_j, 6),
            "des_nodes": self.des_nodes,
            "mean_occupancy": round(self.mean_occupancy, 4),
            "mean_alive": round(self.mean_alive, 4),
            "churn_masked_selections": self.churn_masked_selections,
            "churn_qos_misses": self.churn_qos_misses,
            "scheduler_stats": {k: int(v) if isinstance(v, (int, np.integer))
                                else v
                                for k, v in self.scheduler_stats.items()},
        }


# ----------------------------------------------------------------------
# Config
# ----------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class FrontendConfig:
    """Scenario + time-model knobs of the serving front-end."""

    num_layers: int = 8               # L protocol rounds per decode pass
    qos_z: float = 1.0                # QoS schedule z * gamma0^l
    gamma0: float = 0.7
    max_experts: int = 2              # D (C2 budget)
    top_k: int = 2
    num_subcarriers: int = 64         # lifted to >= K(K-1) like dmoe_sim
    redraw_channel: bool = True       # fresh fading draw every round
    n_prefill_rows: Optional[int] = None  # cap on scheduled prompt rows
    # --- wireless time model (pool mode) --------------------------
    comp_s_per_kb: float = 2e-3       # busiest expert's FFN s/KiB
    round_overhead_s: float = 2e-3    # gate upload + aggregation per round
    max_round_s: float = 1.0          # clamp (dead links cannot stall)
    nominal_round_s: float = 0.1      # ideal unloaded decode round
    #                                   (QoS deadline reference; roughly
    #                                   the K=8 per-round time under the
    #                                   §VII-A2 channel constants)
    # --- churn ----------------------------------------------------
    churn: Optional[ChurnConfig] = None
    renormalize_qos: bool = True      # scale C1 by live gate mass
    seed: int = 0
    record_trace: bool = False
    debug_checks: bool = False        # ScheduleContext numeric sanitizers
    # --- cross-round B&B amortization (pool mode) -----------------
    # Attach a `repro.core.des.WarmStartCache` to the policy so exact
    # DES instances repeat across decode rounds / layers / BCD
    # iterations resolve from the cache (bit-identical answers, zero
    # B&B nodes) and structure-repeats inject warm incumbents.  The
    # frontend owns the invalidation rules: the cache is dropped on
    # every channel redraw and on any expert-churn alive-mask change
    # (entries keyed on stale costs would never hit, and a fixed-point
    # selection under the old channel is not a valid incumbent
    # certificate under the new one).  Off by default — the cold path
    # stays the reference.  Only DES-family policies with a
    # `warm_cache` attribute participate; others serve unchanged.
    warm_start: bool = False


# ----------------------------------------------------------------------
# The front-end
# ----------------------------------------------------------------------

class ServingFrontend:
    """Traffic-driven continuous batching × per-round expert scheduling.

    Exactly one of ``pool`` / ``sim`` selects the gate backend:

      * ``pool=ExpertPool(...)`` — scheduling-level serving (the
        benchmark tier).  ``slots`` defaults to the pool's expert count
        K; admission is slot-level continuous batching.
      * ``sim=DMoESimulator(...)`` — model-exact serving.  ``slots`` is
        the simulator's K; admission is batch-synchronous waves so every
        forward pass is a well-formed (K, N) token batch, and the
        recorded schedules are bit-identical to offline
        `repro.serving.dmoe_sim.DMoESimulator.serve` calls on the same
        batches.

    ``policy`` is a registry name or a constructed `SchedulerPolicy`
    (pool mode only — in sim mode the simulator owns its policy).
    """

    def __init__(self, *, policy: Optional[Any] = None,
                 pool: Optional[ExpertPool] = None,
                 sim: Optional[Any] = None,
                 cfg: FrontendConfig = FrontendConfig(),
                 channel_process: Optional[
                     channel_lib.ChannelProcess] = None,
                 comp_coeff: Optional[np.ndarray] = None):
        if (pool is None) == (sim is None):
            raise ValueError("pass exactly one of pool= or sim=")
        self.cfg = cfg
        self.mode = "pool" if pool is not None else "sim"
        self.pool = pool
        self.sim = sim
        if self.mode == "pool":
            if policy is None:
                raise ValueError("pool mode needs a scheduler policy")
            self.policy: SchedulerPolicy = (
                policy if isinstance(policy, SchedulerPolicy)
                else get_policy(policy))
            self.k = pool.num_experts
        else:
            if policy is not None:
                raise ValueError(
                    "sim mode uses the simulator's own policy; construct "
                    "DMoESimulator(scheme=...) instead")
            self.policy = sim.policy
            self.k = sim.k
        self.slots = self.k           # §III-C step 1: one query per node
        self.qos_schedule = QoSSchedule(z=cfg.qos_z, gamma0=cfg.gamma0)
        self.channel_cfg = channel_lib.ChannelConfig(
            num_experts=self.k,
            num_subcarriers=max(cfg.num_subcarriers,
                                self.k * (self.k - 1)))
        #: Optional scenario hooks (`repro.scenarios`): a temporal
        #: channel process replacing the i.i.d. per-round redraws, and
        #: heterogeneous per-node compute coefficients replacing the
        #: default rank-cost ladder.  ``None`` keeps the historical
        #: behavior (and rng stream) bit for bit.
        self.channel_process = channel_process
        self.comp_coeff = (np.asarray(comp_coeff, dtype=np.float64)
                           if comp_coeff is not None
                           else energy_lib.make_comp_coeffs(self.k))
        if self.comp_coeff.shape != (self.k,):
            raise ValueError(
                f"comp_coeff must have shape ({self.k},), "
                f"got {self.comp_coeff.shape}")
        self.s0 = 8192.0
        #: Cross-round warm-start cache (pool mode, cfg.warm_start):
        #: created here, attached to the policy's `warm_cache` hook, and
        #: invalidated by the serve loop on channel redraw / churn.
        self.warm_cache = None
        if (cfg.warm_start and self.mode == "pool"
                and hasattr(self.policy, "warm_cache")):
            from repro.core import des as des_lib
            if self.policy.warm_cache is None:
                self.policy.warm_cache = des_lib.WarmStartCache()
            self.warm_cache = self.policy.warm_cache
        #: sim mode: the exact (K, N) token batches fed to the simulator,
        #: in order — an offline DMoESimulator replay of these batches
        #: must reproduce every schedule bit for bit (the parity gate).
        self.served_batches: List[np.ndarray] = []

    # ------------------------------------------------------------------
    # time model
    # ------------------------------------------------------------------
    def round_time_s(self, alpha: np.ndarray, beta: Optional[np.ndarray],
                     rates: np.ndarray) -> float:
        """Simulated duration of one scheduled round (module docstring)."""
        cfg = self.cfg
        s_bytes = self.s0 * alpha.sum(axis=1).astype(np.float64)  # (K, E)
        np.fill_diagonal(s_bytes, 0.0)                # in-situ: no transfer
        if beta is None:                              # in-graph-only record
            beta = _fallback_beta(rates)
        rates_kk = channel_lib.link_rates(rates, beta)
        with np.errstate(divide="ignore", invalid="ignore"):
            t_links = np.where(
                s_bytes > 0.0,
                s_bytes * 8.0 / np.maximum(rates_kk, 1e-30),
                0.0)
        t_comm = float(np.minimum(t_links, cfg.max_round_s).max(initial=0.0))
        per_expert_kb = self.s0 * alpha.sum(axis=(0, 1)) / 1024.0
        t_comp = cfg.comp_s_per_kb * float(per_expert_kb.max(initial=0.0))
        return min(t_comm + t_comp, cfg.max_round_s) + cfg.round_overhead_s

    def ideal_service_s(self, req: ServeRequest) -> Tuple[float, float]:
        """(ideal_ttft, ideal_total) — the unloaded service times the
        request's QoS slacks multiply.  One decode pass per output token;
        the prefill pass scales with the prompt because the time model's
        transfer term is linear in the scheduled rows."""
        per_round = self.cfg.nominal_round_s
        prefill_rows = max(len(req.prompt), 1)
        if self.cfg.n_prefill_rows is not None:
            prefill_rows = min(prefill_rows, self.cfg.n_prefill_rows)
        ideal_ttft = self.cfg.num_layers * per_round * prefill_rows
        ideal_total = ideal_ttft + (self.cfg.num_layers * per_round
                                    * max(req.max_new_tokens - 1, 0))
        return ideal_ttft, ideal_total

    def _violates(self, req: ServeRequest) -> bool:
        ideal_ttft, ideal_total = self.ideal_service_s(req)
        if req.first_token_s >= 0 and np.isfinite(req.ttft_slack):
            if req.ttft_sim_s > req.ttft_slack * ideal_ttft + 1e-12:
                return True
        if req.finish_s >= 0 and np.isfinite(req.deadline_slack):
            if req.latency_sim_s > req.deadline_slack * ideal_total + 1e-12:
                return True
        # requests the loop never finished (should not happen) violate
        return req.finish_s < 0

    # ------------------------------------------------------------------
    # serve
    # ------------------------------------------------------------------
    def serve(self, requests: List[ServeRequest]) -> ServingReport:
        t0 = time.perf_counter()
        report = ServingReport(policy=self.policy.name, mode=self.mode,
                               num_requests=len(requests))
        reqs = sorted(requests, key=lambda r: (r.arrive_s, r.uid))
        if self.mode == "pool":
            self._serve_pool(reqs, report)
        else:
            self._serve_sim(reqs, report)
        self._finalize(reqs, report)
        report.wall_s = time.perf_counter() - t0
        return report

    # ------------------------------------------------------------------
    # pool mode: continuous batching at the scheduling level
    # ------------------------------------------------------------------
    def _serve_pool(self, reqs: List[ServeRequest],
                    report: ServingReport) -> None:
        cfg = self.cfg
        rng = np.random.default_rng(cfg.seed)
        churn = (ChurnProcess(self.k, cfg.churn)
                 if cfg.churn is not None else None)
        proc = self.channel_process
        if proc is not None:
            proc.reset()                   # new serve, fresh trajectory
            gains = proc.step(rng)
        else:
            gains = channel_lib.sample_channel_gains(self.channel_cfg, rng)
        rates0 = channel_lib.subcarrier_rates(self.channel_cfg, gains)

        queue = list(reqs)                 # not yet arrived (sorted)
        waiting: List[ServeRequest] = []   # arrived, not admitted
        live: List[Optional[ServeRequest]] = [None] * self.slots
        prefilled = [False] * self.slots
        now = 0.0
        occupancy_sum = 0
        prev_alive = np.ones(self.k, dtype=bool)
        if self.warm_cache is not None:
            self.warm_cache.invalidate()   # fresh serve, fresh channel

        def admit_arrivals() -> None:
            while queue and queue[0].arrive_s <= now + 1e-12:
                waiting.append(queue.pop(0))

        while queue or waiting or any(l is not None for l in live):
            admit_arrivals()
            for s in range(self.slots):
                if live[s] is None and waiting:
                    req = waiting.pop(0)
                    if req.max_new_tokens <= 0:    # zero-budget: done now
                        req.admit_s = req.first_token_s = req.finish_s = now
                        req.output = np.zeros(0, dtype=np.int32)
                        continue
                    live[s] = req
                    prefilled[s] = False
                    req.admit_s = now
            n_live = sum(l is not None for l in live)
            if n_live == 0:
                if queue:                  # idle: jump to the next arrival
                    now = max(now, queue[0].arrive_s)
                continue

            # ---- one decode iteration: L protocol rounds -------------
            n_rows = [1] * self.slots
            for s, req in enumerate(live):
                if req is not None and not prefilled[s]:
                    rows = len(req.prompt)
                    if cfg.n_prefill_rows is not None:
                        rows = min(rows, cfg.n_prefill_rows)
                    n_rows[s] = max(rows, 1)
            n_max = max(n_rows[s] for s in range(self.slots)
                        if live[s] is not None)
            for layer in range(1, cfg.num_layers + 1):
                rates = rates0
                if cfg.redraw_channel:
                    gains = (proc.step(rng) if proc is not None else
                             channel_lib.sample_channel_gains(
                                 self.channel_cfg, rng))
                    rates = channel_lib.subcarrier_rates(
                        self.channel_cfg, gains)
                alive = churn.step() if churn is not None \
                    else np.ones(self.k, dtype=bool)
                if self.warm_cache is not None:
                    # invalidation rules (see FrontendConfig.warm_start):
                    # fresh fading draw or a flipped alive mask voids
                    # every cached incumbent
                    if cfg.redraw_channel or not np.array_equal(
                            alive, prev_alive):
                        self.warm_cache.invalidate()
                    prev_alive = alive

                gates = np.zeros((self.k, n_max, self.k))
                for s, req in enumerate(live):
                    if req is None:
                        continue          # free slot: zero rows, never
                    g = self.pool.gate_scores(   # scheduled (padding)
                        req.domain, n_rows[s], rng)
                    gates[s, : n_rows[s]] = g
                report.rounds += 1
                now += self._schedule_round(
                    gates, rates, alive, layer, rng, now, n_live, report)

            report.iterations += 1
            occupancy_sum += n_live
            for s, req in enumerate(live):
                if req is None:
                    continue
                req.tokens_done += 1
                prefilled[s] = True
                if req.first_token_s < 0:
                    req.first_token_s = now
                if req.tokens_done >= req.max_new_tokens:
                    req.finish_s = now
                    req.output = np.zeros(req.tokens_done, dtype=np.int32)
                    live[s] = None
        report.makespan_s = now
        report.mean_occupancy = occupancy_sum / max(report.iterations, 1)
        report.mean_alive = (churn.mean_alive if churn is not None
                             else float(self.k))

    def _schedule_round(self, gates: np.ndarray, rates: np.ndarray,
                        alive: np.ndarray, layer: int,
                        rng: np.random.Generator, now: float, n_live: int,
                        report: ServingReport) -> float:
        """One policy call under churn masking; returns the simulated
        round duration."""
        cfg = self.cfg
        qos = self.qos_schedule.qos(layer)
        masked_gates, masked_rates, q_eff = gates, rates, qos
        if not alive.all():
            # dead experts: zero gate mass + zero link rate (+inf cost),
            # C1 renormalized over the live mass (masked_des_select's
            # convention lifted to the batch)
            masked_gates = np.where(alive[None, None, :], gates, 0.0)
            masked_rates = np.where(alive[None, :, None], rates, 0.0)
            if cfg.renormalize_qos:
                act = gates.sum(axis=-1) > 0
                if act.any():
                    live_mass = masked_gates.sum(axis=-1)[act]
                    q_eff = qos * float(live_mass.mean())

        ctx = ScheduleContext(
            gate_scores=masked_gates, rates=masked_rates, layer=layer,
            qos=q_eff, qos_schedule=self.qos_schedule,
            max_experts=cfg.max_experts, top_k=cfg.top_k,
            comp_coeff=self.comp_coeff, s0=self.s0,
            p0=self.channel_cfg.tx_power_w, rng=rng,
            debug_checks=cfg.debug_checks)
        t_sched = time.perf_counter()
        rs = self.policy.schedule(ctx)
        report.sched_wall_s += time.perf_counter() - t_sched

        alpha = rs.alpha
        if not alive.all():
            # hard guarantee: a dead expert serves nothing, whatever the
            # policy decided (Remark-2 fallbacks may ignore gate mass)
            masked = alpha * alive[None, None, :].astype(alpha.dtype)
            report.churn_masked_selections += int(alpha.sum()
                                                  - masked.sum())
            alpha = masked
            covered = (alpha * gates).sum(axis=-1)
            act = gates.sum(axis=-1) > 0
            report.churn_qos_misses += int(
                (covered[act] < qos - 1e-12).sum())

        beta = rs.beta if rs.beta is not None else _fallback_beta(
            masked_rates)
        acct = proto.account_round(
            layer, alpha, beta, masked_rates, self.comp_coeff, self.s0,
            self.channel_cfg.tx_power_w)
        report.comm_energy_j += acct.comm_energy_j
        report.comp_energy_j += acct.comp_energy_j
        report.des_nodes += rs.des_nodes
        # reuse the fallback beta computed above instead of letting
        # round_time_s re-derive it (identical: both come from
        # _fallback_beta(masked_rates) when the policy returned none)
        dt = self.round_time_s(alpha, beta, masked_rates)
        if cfg.record_trace:
            report.trace.append(RoundRecord(
                iteration=report.iterations, layer=layer, qos=q_eff,
                alive=alive.copy(), alpha=alpha.copy(),
                beta=None if rs.beta is None else rs.beta.copy(),
                energy_j=acct.total_energy_j, round_s=dt,
                live_slots=n_live))
        return dt

    # ------------------------------------------------------------------
    # sim mode: batch-synchronous waves through the real simulator
    # ------------------------------------------------------------------
    def _serve_sim(self, reqs: List[ServeRequest],
                   report: ServingReport) -> None:
        cfg = self.cfg
        queue = list(reqs)
        now = 0.0
        occupancy_sum = 0
        self.served_batches = []

        while queue:
            # wave admission: the next <= K requests in FIFO order; the
            # server gathers the full wave before the first round, so the
            # clock jumps to the wave's last arrival (batch-synchronous
            # static batching — the exactness tier trades continuous
            # admission for bit-identical offline replays)
            wave = [queue.pop(0)
                    for _ in range(min(self.slots, len(queue)))]
            plens = {len(r.prompt) for r in wave}
            if len(plens) != 1:
                raise ValueError(
                    "sim mode needs equal prompt lengths within a wave "
                    f"(got {sorted(plens)}); generate the workload with "
                    "a fixed prompt_tokens range")
            now = max(now, max(r.arrive_s for r in wave))
            for r in wave:
                r.admit_s = now

            seqs = [np.asarray(r.prompt, dtype=np.int64) for r in wave]
            budget = max(r.max_new_tokens for r in wave)
            for it in range(budget):
                batch = np.zeros((self.slots, len(seqs[0])), dtype=np.int64)
                for s, seq in enumerate(seqs):
                    batch[s] = seq
                self.served_batches.append(batch.copy())
                t_sched = time.perf_counter()
                res = self.sim.serve(batch)
                report.sched_wall_s += time.perf_counter() - t_sched
                report.iterations += 1
                occupancy_sum += len(wave)
                for rs, acct in zip(res.schedules, res.rounds):
                    report.rounds += 1
                    report.comm_energy_j += acct.comm_energy_j
                    report.comp_energy_j += acct.comp_energy_j
                    report.des_nodes += rs.des_nodes
                    now += cfg.nominal_round_s
                    if cfg.record_trace:
                        report.trace.append(RoundRecord(
                            iteration=report.iterations, layer=rs.layer,
                            qos=rs.qos,
                            alive=np.ones(self.k, dtype=bool),
                            alpha=rs.alpha.copy(),
                            beta=None if rs.beta is None
                            else rs.beta.copy(),
                            energy_j=acct.total_energy_j,
                            round_s=cfg.nominal_round_s,
                            live_slots=len(wave)))
                nxt = np.argmax(res.logits[:, -1, :], axis=-1)
                new_seqs = []
                for s, seq in enumerate(seqs):
                    new_seqs.append(np.concatenate([seq, [int(nxt[s])]]))
                seqs = new_seqs
                for s, r in enumerate(wave):
                    if r.tokens_done < r.max_new_tokens:
                        r.tokens_done += 1
                        if r.first_token_s < 0:
                            r.first_token_s = now
                        if r.tokens_done >= r.max_new_tokens:
                            r.finish_s = now
                            r.output = np.asarray(
                                seqs[s][len(r.prompt):
                                        len(r.prompt) + r.tokens_done],
                                dtype=np.int32)
        report.makespan_s = now
        report.mean_occupancy = occupancy_sum / max(report.iterations, 1)
        report.mean_alive = float(self.k)

    # ------------------------------------------------------------------
    def _finalize(self, reqs: List[ServeRequest],
                  report: ServingReport) -> None:
        done = [r for r in reqs if r.finish_s >= 0]
        report.completed = len(done)
        report.tokens_out = sum(r.tokens_done for r in reqs)
        report.latency = latency_percentiles(
            [r.latency_sim_s for r in done])
        report.ttft = latency_percentiles([r.ttft_sim_s for r in done])
        waits = [max(r.admit_s - r.arrive_s, 0.0) for r in reqs
                 if r.admit_s >= 0]
        report.queue_wait_mean_s = float(np.mean(waits)) if waits else 0.0
        by_class: Dict[str, List[int]] = {}
        for r in reqs:
            bad = self._violates(r)
            report.qos_violations += bad
            by_class.setdefault(r.qos_class, []).append(int(bad))
        report.qos_violation_rate = (
            report.qos_violations / max(report.num_requests, 1))
        report.qos_violations_by_class = {
            name: float(np.mean(v)) for name, v in sorted(by_class.items())}
        report.requests = reqs
        last = getattr(self.policy, "last_stats", None)
        if last:
            report.scheduler_stats = dict(last)
        if self.warm_cache is not None:
            report.scheduler_stats.update(
                {f"warm_cache_{k}": v
                 for k, v in self.warm_cache.stats.items()})


def serve_workload(policy: str, pool: ExpertPool,
                   requests: List[ServeRequest], *,
                   cfg: FrontendConfig = FrontendConfig(),
                   policy_kwargs: Optional[Dict[str, Any]] = None,
                   channel_process: Optional[
                       channel_lib.ChannelProcess] = None,
                   comp_coeff: Optional[np.ndarray] = None,
                   ) -> ServingReport:
    """One-call convenience: construct the policy by registry name and
    serve `requests` through a pool-mode `ServingFrontend`."""
    front = ServingFrontend(
        policy=get_policy(policy, **(policy_kwargs or {})),
        pool=pool, cfg=cfg, channel_process=channel_process,
        comp_coeff=comp_coeff)
    return front.serve(requests)
