"""Driver for the DMoE protocol pass of a configuration whose edge nodes
hold groups of experts (DeepSeek-V2's device-limited routing), as one
chip's share of the deployment: `DMoESimulator.serve` holding the
routed experts of the configuration's `held_nodes`.

The traffic and the loop are `protocol.py`'s: one full wave of K
queries of N tokens at a time (K = n_group nodes), a fresh i.i.d.
Rayleigh channel a pass, the registry's scheduler on the host over the
nodes' scores.  What differs: the weights are the share's
(`bench/weights_dsv2.py`), the operations are counted by
`bench/flops/protocol_group.py` from the experts the combines weighed,
and the sampled passes are checked against
`bench/reference/deepseek_ref.py` (teacher-forced on the program's node
selection and its router scores) and `bench/reference/schedule_ref.py`.

The check's numbers: `logit_err` as `protocol.py`'s; `gate_err`, the
largest L1 distance, over tokens, between the router's distributions
over the 160 experts (a largest score of about 0.05 leaves `protocol.py`'s
largest single difference too small to fail a wrong router: a flat
1/160 answer reads about 0.7 here); `sched_faults`, the schedule's
faults, with each node score that is not its group's share of the
router's mass; `route_faults`, the chosen experts that are not the
top_k of the router's scores within the selected nodes.

Traffic parameters (`bench/traffic/<mix>.json`): as `protocol.py`'s.
"""

from __future__ import annotations

import time

import numpy as np

from common import percentile
from drivers import protocol
from flops.protocol_group import ffn_step_cost, pass_flops
from reference import deepseek_ref, schedule_ref
from weights import program_weights
from weights_dsv2 import make_params


class Driver(protocol.Driver):
    def setup(self) -> None:
        import jax
        from repro.core.gating import QoSSchedule
        from repro.schedulers import get_policy
        from repro.serving import DMoESimulator

        t = self.traffic
        self.cfg = cfg = self.program_config()
        held = self.config["held_nodes"]
        self.k, self.n = cfg.moe.n_group, t["tokens_per_query"]
        self.params = make_params(cfg, self.seed, held)
        self.policy = protocol._span_policy(get_policy(t["scheduler"]))
        with program_weights(self.params):
            self.sim = DMoESimulator(
                cfg, policy=self.policy, seed=self.sim_seed, held_nodes=held,
                qos=QoSSchedule(z=t["qos_z"], gamma0=t["qos_gamma0"]))
        self.device_kind = jax.devices()[0].device_kind
        self.pool = [self.rng_tokens.integers(
            0, cfg.vocab_size, (self.k, self.n), dtype=np.int32)
            for _ in range(t["token_batches"])]
        for i in range(t["warmup_passes"]):
            self._serve(self.pool[i % len(self.pool)])

    def window(self, seconds: float) -> dict:
        import jax

        keep_n = self.traffic["check_passes"]
        self.kept, lat, sched, nodes, pairs = [], [], [], [], []
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
                pairs.append(res.routed_pairs)
                item = (tokens, res.logits, rounds, res.expert_scores,
                        res.routed)
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
        per_request = np.repeat(np.asarray(lat), self.k)
        step_flops, step_bytes = ffn_step_cost(self.config, self.k, self.n)
        return {
            "attempted": passes * self.k,
            "failed": 0,
            "metrics": {"tok_s": passes * self.k * self.n / window_s,
                        "lat_p95_ms": 1e3 * percentile(per_request, 95)},
            "obs": {"window_s": window_s, "passes": passes,
                    "sched_s": sched, "des_nodes": nodes,
                    "required_flops": sum(pass_flops(self.config, self.k,
                                                     self.n, p)
                                          for p in pairs),
                    "tokens_per_pass": self.k * self.n,
                    "ffn_step": {"flops": step_flops, "bytes": step_bytes,
                                 "per_pass": self.sim.rounds_per_pass,
                                 "device_kind": self.device_kind}},
        }

    def readings(self, control: bool = False) -> dict:
        """Compare the sampled passes with the float32 reference.  With
        `control`, the reference in float8 stands in the program's place:
        its logits and scores are compared in place of the program's."""
        dims = deepseek_ref.Dims.from_config(self.config)
        out = {"logit_err": 0.0, "gate_err": 0.0, "sched_faults": 0,
               "route_faults": 0}
        for tokens, logits, rounds, scores, routed in self.kept:
            alphas = [r.rs.alpha for r in rounds]
            want, want_gates = deepseek_ref.forward(
                self.params, tokens, alphas, scores, dims, "f32")
            got_gates = scores
            if control:
                logits, got_gates = deepseek_ref.forward(
                    self.params, tokens, alphas, scores, dims, "fp8")
            out["logit_err"] = max(out["logit_err"], float(
                deepseek_ref.position_errors(logits, want).max()))
            for got, g in zip(got_gates, want_gates, strict=True):
                out["gate_err"] = max(out["gate_err"], float(
                    deepseek_ref.score_errors(got, g).max()))
            for r, s, chosen in zip(rounds, scores, routed, strict=True):
                c = r.ctx
                faults = schedule_ref.check_round(
                    c.gate_scores, c.rates, c.qos, c.max_experts,
                    np.asarray(c.comp_coeff), c.s0, c.p0, r.rs.alpha,
                    r.rs.beta, r.rs.energy)
                out["sched_faults"] += sum(faults.values())
                mass = np.asarray(s, np.float64).reshape(
                    *s.shape[:-1], dims.n_group, dims.group).sum(-1)
                out["sched_faults"] += int((~np.isclose(
                    c.gate_scores, mass, rtol=1e-12, atol=0.0)).any(-1).sum())
                top = deepseek_ref.top_experts(s, r.rs.alpha, dims)
                out["route_faults"] += int((~(chosen[..., :, None]
                                              == top[..., None, :]).any(-1)
                                            ).sum())
        return out
