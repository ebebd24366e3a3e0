"""Batched serving engine: request queue -> continuous batched prefill +
decode with KV caches, greedy sampling, and (for MoE models) DES routing
with per-expert cost vectors.

This is the generic engine (single host, jit'd steps); the wireless-edge
protocol variant with per-round JESA scheduling is `dmoe_sim.py`.
"""

from __future__ import annotations

import dataclasses
import time
from typing import Dict, List, Optional, Union

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import ModelConfig, resolve_routing_policy
from repro.models import model as model_lib


@dataclasses.dataclass
class Request:
    uid: int
    prompt: np.ndarray               # (S,) int32
    max_new_tokens: int = 16
    output: Optional[np.ndarray] = None
    latency_s: float = 0.0


@dataclasses.dataclass
class EngineStats:
    prefill_tokens: int = 0
    decode_tokens: int = 0
    batches: int = 0
    wall_s: float = 0.0

    @property
    def decode_tok_per_s(self) -> float:
        return self.decode_tokens / self.wall_s if self.wall_s else 0.0


class ServingEngine:
    def __init__(self, cfg: ModelConfig, *, max_batch: int = 8,
                 max_len: int = 256, seed: int = 0,
                 use_des_routing: Optional[Union[bool, str]] = None,
                 routing_impl: Optional[str] = None):
        # Routing policy comes from the registry: cfg.moe.routing names
        # it; `use_des_routing=True` forces the paper's greedy DES policy
        # by overriding the routing name the jitted model resolves, and a
        # string forces any registered in-graph-capable policy by name
        # (e.g. "sharded-des" or "async-des" route through the same
        # greedy mask while their host `schedule()` paths run the
        # device-sharded / pipelined exact solvers).  The policy supplies
        # its own in-graph cost vector (None for policies that route on
        # gate scores alone).
        if cfg.moe.num_experts and use_des_routing:
            from repro.schedulers import canonical_policy_name

            routing = (use_des_routing if isinstance(use_des_routing, str)
                       else "des-greedy")
            overrides = {"moe_routing": routing}
            # routing_kwargs are constructor kwargs for the CONFIG's named
            # policy — they don't transfer to a DIFFERENT policy, but an
            # alias of the same one (e.g. "des" -> use_des_routing=True's
            # "des-greedy") must keep its tuning.  An unregistered config
            # name is simply being replaced: drop its kwargs too.
            try:
                same = (canonical_policy_name(routing)
                        == canonical_policy_name(cfg.moe.routing))
            except KeyError:
                same = False
            if not same:
                overrides["moe_routing_kwargs"] = ()
            cfg = cfg.with_overrides(**overrides)
        # Token-dispatch implementation for the jitted MoE FFN: override
        # cfg.moe.routing_impl ("xla" one-hot einsums, "fused"/"grouped"
        # Pallas — see repro.kernels.moe_route).  None keeps the config's
        # own setting.
        if routing_impl is not None:
            from repro.kernels.moe_route import check_routing_impl

            cfg = cfg.with_overrides(
                moe_routing_impl=check_routing_impl(routing_impl))
        self.cfg = cfg
        self.max_batch = max_batch
        self.max_len = max_len
        self.params = model_lib.init_params(jax.random.PRNGKey(seed), cfg)
        self.policy = None
        self.expert_costs = None
        if cfg.moe.num_experts:
            self.policy = resolve_routing_policy(cfg)
            self.expert_costs = self.policy.in_graph_costs(
                cfg.moe.num_experts)

        self._prefill = jax.jit(
            lambda p, b, c: model_lib.prefill(
                p, b, cfg, c, expert_costs=self.expert_costs))
        self._decode = jax.jit(
            lambda p, t, c: model_lib.decode_step(
                p, t, c, cfg, expert_costs=self.expert_costs))

    def serve(self, requests: List[Request]) -> EngineStats:
        """Process requests in fixed-size batches (prefill + decode loop)."""
        stats = EngineStats()
        t0 = time.time()
        for i in range(0, len(requests), self.max_batch):
            batch_reqs = requests[i: i + self.max_batch]
            self._serve_batch(batch_reqs, stats)
            stats.batches += 1
        stats.wall_s = time.time() - t0
        return stats

    def prefill(self, reqs: List[Request]):
        """Run the jitted prefill on `reqs`.  Returns (last-position
        logits (B, V), caches)."""
        return self._prefill(self.params, *self.prefill_inputs(reqs))

    def prefill_inputs(self, reqs: List[Request]):
        """The (batch, caches) the jitted prefill takes for `reqs`: the
        prompts left-padded into one batch, and fresh caches."""
        b = len(reqs)
        plen = max(len(r.prompt) for r in reqs)
        toks = np.zeros((b, plen), dtype=np.int32)
        for j, r in enumerate(reqs):
            toks[j, -len(r.prompt):] = r.prompt  # left-pad
        caches = model_lib.init_caches(self.cfg, b, self.max_len)
        batch = {"tokens": jnp.asarray(toks)}
        if self.cfg.enc_dec:
            batch["frames"] = jnp.zeros(
                (b, self.cfg.encoder_max_len, self.cfg.d_model))
        return batch, caches

    def _serve_batch(self, reqs: List[Request], stats: EngineStats):
        b = len(reqs)
        t_start = time.time()
        logits, caches = self.prefill(reqs)
        stats.prefill_tokens += b * max(len(r.prompt) for r in reqs)

        n_steps = max(r.max_new_tokens for r in reqs)
        out = np.zeros((b, n_steps), dtype=np.int32)
        tok = jnp.argmax(logits, -1).astype(jnp.int32)
        for s in range(n_steps):
            # Overlap-aware decode: dispatch the next step (which only
            # needs the on-device token) BEFORE the host copy of the
            # sampled token — jax's async dispatch overlaps the device
            # step with the transfer.  Same tokens, reordered wall-clock.
            logits, caches = self._decode(self.params, tok, caches)
            out[:, s] = np.asarray(tok)
            tok = jnp.argmax(logits, -1).astype(jnp.int32)
            stats.decode_tokens += b
        dt = time.time() - t_start
        for j, r in enumerate(reqs):
            r.output = out[j, : r.max_new_tokens]
            r.latency_s = dt
