"""Driver for the DMoE protocol pass of a hybrid (Jamba) configuration
over a mesh of chips: `DMoESimulator.serve` with its K edge nodes spread
over the cell's `chips`, K / chips nodes to a chip.

The traffic and the loop are `protocol.py`'s: one full wave of K
queries of N tokens at a time, a fresh i.i.d. Rayleigh channel a pass,
the registry's scheduler on the host.  What differs: the simulator gets
a 1-D node mesh over the first `chips` devices, the weights are made
straight into the program's node shardings (`bench/weights_hybrid.py`),
the operations are counted by `bench/flops/protocol_mesh.py`, and the
sampled passes are checked against `bench/reference/jamba_ref.py` and
`bench/reference/schedule_ref.py`.

Traffic parameters (`bench/traffic/<mix>.json`): as `protocol.py`'s.
"""

from __future__ import annotations

import time

import numpy as np

from common import percentile
from drivers import protocol
from flops.protocol_mesh import pass_flops
from reference import jamba_ref, schedule_ref
from weights import program_weights
from weights_hybrid import make_params


class Driver(protocol.Driver):
    def setup(self) -> None:
        import jax
        from repro.core.gating import QoSSchedule
        from repro.schedulers import get_policy
        from repro.serving import DMoESimulator
        from repro.serving.dmoe_sim import node_mesh, node_shardings

        t = self.traffic
        self.cfg = cfg = self.program_config()
        self.k, self.n = cfg.moe.num_experts, t["tokens_per_query"]
        mesh = node_mesh(jax.devices()[: self.cell["chips"]])
        self.params = make_params(
            cfg, self.seed, shardings=lambda s: node_shardings(s, mesh))
        self.policy = protocol._span_policy(get_policy(t["scheduler"]))
        with program_weights(self.params):
            self.sim = DMoESimulator(
                cfg, policy=self.policy, seed=self.sim_seed, mesh=mesh,
                qos=QoSSchedule(z=t["qos_z"], gamma0=t["qos_gamma0"]))
        self.pool = [self.rng_tokens.integers(
            0, cfg.vocab_size, (self.k, self.n), dtype=np.int32)
            for _ in range(t["token_batches"])]
        for i in range(t["warmup_passes"]):
            self._serve(self.pool[i % len(self.pool)])

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
        per_request = np.repeat(np.asarray(lat), self.k)
        flops = sum(pass_flops(self.config, self.k, self.n, s)
                    for s in selected)
        return {
            "attempted": passes * self.k,
            "failed": 0,
            "metrics": {"tok_s": passes * self.k * self.n / window_s,
                        "lat_p95_ms": 1e3 * percentile(per_request, 95)},
            "obs": {"window_s": window_s, "passes": passes,
                    "sched_s": sched, "des_nodes": nodes,
                    "required_flops": flops},
        }

    def readings(self, control: bool = False) -> dict:
        """`protocol.Driver.readings` against the Jamba reference."""
        dims = jamba_ref.Dims.from_config(self.config)
        out = {"logit_err": 0.0, "gate_err": 0.0, "sched_faults": 0}
        for tokens, logits, rounds in self.kept:
            alphas = [r.rs.alpha for r in rounds]
            want, want_gates = jamba_ref.forward(self.params, tokens, alphas,
                                                 dims, "f32")
            got_gates = [r.ctx.gate_scores for r in rounds]
            if control:
                logits, got_gates = jamba_ref.forward(self.params, tokens,
                                                      alphas, dims, "fp8")
            out["logit_err"] = max(out["logit_err"], float(
                jamba_ref.position_errors(logits, want).max()))
            for got, g in zip(got_gates, want_gates, strict=True):
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
