"""Driver for the DMoE protocol pass: `DMoESimulator.serve`.

The server serves one full wave at a time: K queries of N tokens, one
query per edge node (§III-C step 1), in a closed loop, the next wave sent
when the last one's logits are on the host.  Each pass runs one protocol
round per layer: attention and the gate on the device, a host sync,
the registry's scheduler (exact JESA) on the host, then the experts'
FFNs and the Eq.-8 combine on the device.  Each pass draws a fresh
i.i.d. Rayleigh channel from the simulator's own seeded generator.

Traffic parameters (`bench/traffic/<mix>.json`):
  tokens_per_query  N
  scheduler         registry name of the policy the server runs
  qos_z, qos_gamma0 the QoS schedule z * gamma0^l
  max_experts       D, the most experts a token may use
  token_batches     how many distinct (K, N) batches the seed draws; the
                    window cycles through them
  warmup_passes     passes served before the window (set-up)
  check_passes      passes of the window, drawn from the seed, that the
                    reference checks after the window
"""

from __future__ import annotations

import gc
import time

import numpy as np

from common import percentile
from flops.protocol import pass_flops
from reference import moe_ref, schedule_ref
from weights import make_params, program_weights


class _Round:
    __slots__ = ("ctx", "rs", "seconds")

    def __init__(self, ctx, rs, seconds):
        self.ctx, self.rs, self.seconds = ctx, rs, seconds


def _span_policy(inner):
    """The registry policy wrapped in the benchmark's own span: host time
    of each `schedule()` call, kept with the round's inputs and answer."""
    import jax
    from repro.schedulers import SchedulerPolicy

    class SpanPolicy(SchedulerPolicy):
        def __init__(self):
            self.inner = inner
            self.name = inner.name
            self.rounds = []

        def schedule(self, ctx):
            with jax.profiler.TraceAnnotation("bench.schedule"):
                t0 = time.perf_counter()
                rs = self.inner.schedule(ctx)
                dt = time.perf_counter() - t0
            self.rounds.append(_Round(ctx, rs, dt))
            return rs

    return SpanPolicy()


class Driver:
    def __init__(self, cell: dict, config: dict, traffic: dict, seed: int):
        self.cell, self.config, self.traffic = cell, config, traffic
        self.seed = int(seed)
        streams = np.random.SeedSequence(self.seed).spawn(3)
        self.rng_tokens = np.random.default_rng(streams[0])
        self.rng_check = np.random.default_rng(streams[1])
        self.sim_seed = int(streams[2].generate_state(1)[0])

    # -- set-up --------------------------------------------------------
    def program_config(self):
        from repro.configs.base import get_config

        t = self.traffic
        return get_config(self.config["repo_config"]).with_overrides(
            **self.config["overrides"], moe_max_experts=t["max_experts"])

    def setup(self) -> None:
        from repro.core.gating import QoSSchedule
        from repro.schedulers import get_policy
        from repro.serving import DMoESimulator

        t = self.traffic
        self.cfg = cfg = self.program_config()
        self.k, self.n = cfg.moe.num_experts, t["tokens_per_query"]
        self.params = make_params(cfg, self.seed)
        self.policy = _span_policy(get_policy(t["scheduler"]))
        with program_weights(self.params):
            self.sim = DMoESimulator(
                cfg, policy=self.policy, seed=self.sim_seed,
                qos=QoSSchedule(z=t["qos_z"], gamma0=t["qos_gamma0"]))
        self.pool = [self.rng_tokens.integers(
            0, cfg.vocab_size, (self.k, self.n), dtype=np.int32)
            for _ in range(t["token_batches"])]
        for i in range(t["warmup_passes"]):
            self._serve(self.pool[i % len(self.pool)])

    def _serve(self, tokens):
        import jax

        self.policy.rounds = []
        with jax.profiler.TraceAnnotation("bench.pass"):
            res = self.sim.serve(tokens)       # logits arrive as host numpy
        return res, self.policy.rounds

    # -- the measured window -------------------------------------------
    def window(self, seconds: float) -> dict:
        import jax

        keep_n = self.traffic["check_passes"]
        self.kept, lat, sched, nodes, selected = [], [], [], [], []
        t_start = time.perf_counter()
        with jax.profiler.TraceAnnotation("bench.window"):
            i = 0
            while True:
                tokens = self.pool[i % len(self.pool)]
                t0 = time.perf_counter()
                res, rounds = self._serve(tokens)
                t1 = time.perf_counter()
                lat.append(t1 - t0)
                sched.append(sum(r.seconds for r in rounds))
                nodes.append(sum(r.rs.des_nodes for r in rounds))
                selected.append([int(r.rs.alpha.sum()) for r in rounds])
                # A uniform sample of the window's passes (reservoir).
                item = (tokens, res.logits, rounds)
                if i < keep_n:
                    self.kept.append(item)
                else:
                    j = int(self.rng_check.integers(0, i + 1))
                    if j < keep_n:
                        self.kept[j] = item
                i += 1
                if t1 - t_start >= seconds:
                    break
        window_s = t1 - t_start
        passes = len(lat)
        tokens_done = passes * self.k * self.n
        per_request = np.repeat(np.asarray(lat), self.k)
        flops = sum(pass_flops(self.config, self.k, self.n, s)
                    for s in selected)
        return {
            "attempted": passes * self.k,
            "failed": 0,
            "metrics": {"tok_s": tokens_done / window_s,
                        "lat_p95_ms": 1e3 * percentile(per_request, 95)},
            "obs": {"window_s": window_s, "passes": passes,
                    "sched_s": sched, "des_nodes": nodes,
                    "required_flops": flops},
        }

    def release(self) -> None:
        """Free the program's state before the reference runs."""
        del self.sim
        self.policy = None
        gc.collect()

    # -- correctness ---------------------------------------------------
    def readings(self, control: bool = False) -> dict:
        """Compare the sampled passes with the float32 reference.  With
        `control`, the reference in float8 stands in the program's place:
        its logits and gates are compared in place of the program's."""
        dims = moe_ref.Dims.from_config(self.config)
        out = {"logit_err": 0.0, "gate_err": 0.0, "sched_faults": 0}
        for tokens, logits, rounds in self.kept:
            alphas = [r.rs.alpha for r in rounds]
            want, want_gates = moe_ref.forward(self.params, tokens, alphas,
                                               dims, "f32")
            got_gates = [r.ctx.gate_scores for r in rounds]
            if control:
                logits, got_gates = moe_ref.forward(self.params, tokens,
                                                    alphas, dims, "fp8")
            out["logit_err"] = max(out["logit_err"], float(
                moe_ref.position_errors(logits, want).max()))
            for got, g in zip(got_gates, want_gates):
                out["gate_err"] = max(out["gate_err"], float(np.abs(
                    np.asarray(got, np.float64)
                    - np.asarray(g, np.float64)).max()))
            for r in rounds:
                c = r.ctx
                faults = schedule_ref.check_round(
                    c.gate_scores, c.rates, c.qos, c.max_experts,
                    np.asarray(c.comp_coeff), c.s0, c.p0, r.rs.alpha,
                    r.rs.beta, r.rs.energy)
                out["sched_faults"] += sum(faults.values())
        return out
