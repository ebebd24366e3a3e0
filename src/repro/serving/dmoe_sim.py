"""DMoE edge-deployment simulator — the paper's protocol end-to-end
(§III-C, Fig. 1b).

K expert nodes hold a vertically-partitioned MoE model (node j = the
shared Attn blocks + FFN_j of every layer, Eq. 6).  Each node is assigned
at most one query (§III-C step 1).  Per layer l (one protocol round):

  1. attention + gate at each source node (in-situ, real JAX compute);
  2. gate scores + CSI -> the scheduler ("server");
  3. scheduler runs any registered policy -> (alpha, beta): JESA /
     sharded-des / Top-k / homogeneous / LB / ... (`repro.schedulers`);
  4-5. hidden states "transmitted" i->j, FFN_j applied for selected j,
       results aggregated with Eq.-8 weights — computed exactly, with
       the energy meter charging Eq. (3)-(4) for the traffic;
  6. next layer.

The model math is exact (the simulator produces the same logits a
centralized run with the same per-token expert masks would); what is
simulated is the wireless channel + energy, not the transformer.

One layer plan: the simulator walks `plan`, the (mixer kind, protocol
round?) of each sublayer of a period, over every period of the stack.
A plain MoE block (`arch_type="moe"`) is a period of one sublayer,
attention with a protocol round; a hybrid (Jamba, `arch_type="hybrid"`)
takes its period from `models.transformer.jamba_sublayers`.  Every
sublayer runs one jitted mixer step -- RMSNorm, Mamba or attention, the
residual -- and at a MoE sublayer the FFN's norm and the gate with it.
A MoE sublayer is then one protocol round as above (the QoS l counts
rounds); a dense sublayer's SwiGLU runs in situ at the query's node in
its own jitted step, with no scheduler call and no transmission.  Every
node holds the shared blocks (Eq. 6): mixers and dense FFNs included.

Compiled steps: embed, the mixer step, every expert's FFN, the Eq.-8
combine, the dense FFN and unembed are each jitted once per simulator.
Each step slices its period's weights from the stacked params by a
traced period index, so one executable serves every period and every
sublayer of one shape, and jit's own cache keys them on the (K, N)
shape.  The gated mixer step and the FFN step are separate executables
on purpose: an executable's outputs are ready only when all of it ends,
so a fused step would hold the gate scores back until the FFN were done.

Nodes over chips: with a 1-D device `mesh` (its one axis is the edge
node), each chip holds K / chips nodes -- their queries' hidden states
(the K axis) and their experts (the E axis of the expert weights) --
and a replica of the shared blocks.  XLA's partitioner makes the
exchange: every token's FFN input reaches every chip that holds experts
(the protocol's step-4 transmission) and each chip's weighted expert
outputs return to the tokens' chips (step 5).  The logits are gathered
on the device after the unembed, so the host copies one buffer.  The
scheduler stays one global host policy.  With no mesh, or a mesh of
one device, nothing is placed or constrained.

Overlap-aware round: the expert FFN einsums are dense in the expert
axis and independent of the selection alpha (alpha only weights the
Eq.-8 combine), so they are dispatched *before* the round waits for its
gate scores and runs the host scheduler -- jax's asynchronous dispatch
overlaps the device FFN work of round l with the host branch-and-bound
of round l (and, under the "async-des" policy, with its pipelined
pre-work rounds).
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.profiler import TraceAnnotation

from repro.configs.base import ModelConfig
from repro.core import channel as channel_lib
from repro.core import energy as energy_lib
from repro.core import protocol as proto
from repro.core.gating import QoSSchedule
from repro.models import attention as A
from repro.models import layers as L
from repro.models import model as model_lib
from repro.models import ssm as S
from repro.models import transformer as T
from repro.schedulers import RoundSchedule, ScheduleContext, SchedulerPolicy
from repro.schedulers import get_policy


def _layer_slice(stack, layer):
    """One layer's weights from the stacked stage params; `layer` is a
    traced index, so the slice is made inside the step that reads it."""
    return jax.tree.map(
        lambda a: jax.lax.dynamic_index_in_dim(a, layer, keepdims=False),
        stack)


def node_mesh(devices=None) -> jax.sharding.Mesh:
    """A 1-D mesh over `devices` (default: all) whose one axis, "node",
    is the edge node."""
    return jax.sharding.Mesh(jax.devices() if devices is None else devices,
                             ("node",))


def node_shardings(params, mesh: jax.sharding.Mesh):
    """Where each weight lives on a node mesh: the stacked expert weights
    (`ffn/w1|wu|w2`, (layers, E, ...)) split on E, one chip's share of
    the nodes; everything else replicated."""
    axis = mesh.axis_names[0]

    def spec(path, leaf):
        names = [str(getattr(k, "key", k)) for k in path]
        expert = (names[-1] in ("w1", "wu", "w2") and "ffn" in names
                  and leaf.ndim == 4)
        return jax.sharding.NamedSharding(
            mesh, jax.sharding.PartitionSpec(None, axis) if expert
            else jax.sharding.PartitionSpec())

    return jax.tree_util.tree_map_with_path(spec, params)


@dataclasses.dataclass
class SimResult:
    logits: np.ndarray                 # (K, N, V)
    rounds: List[proto.RoundAccounting]
    summary: Dict
    selection_hist: np.ndarray         # (rounds, K) expert selection frequency
    #: the per-round policy decisions (one `RoundSchedule` per layer) —
    #: recorded so serving front-ends can prove their per-round schedules
    #: bit-identical to an offline simulator run on the same trace.
    schedules: List[RoundSchedule] = dataclasses.field(default_factory=list)


class DMoESimulator:
    """Serve queries through the DMoE protocol with a real (small) MoE
    model supplying gates and FFN compute.

    cfg must be an arch_type="moe" config, or a "hybrid" (Jamba) one
    with Mamba mixers, whose num_experts == K nodes.  `mesh`: an optional
    1-D device mesh over which the K nodes are spread (module docstring).
    """

    def __init__(self, cfg: ModelConfig, *, scheme: str = "jesa",
                 policy: Optional[SchedulerPolicy] = None,
                 qos: Optional[QoSSchedule] = None,
                 channel_cfg: Optional[channel_lib.ChannelConfig] = None,
                 channel_process: Optional[
                     channel_lib.ChannelProcess] = None,
                 seed: int = 0, top_k: Optional[int] = None,
                 count_backward: bool = True,
                 mesh: Optional[jax.sharding.Mesh] = None):
        assert cfg.moe.num_experts >= 1
        assert cfg.arch_type == "moe" or (
            cfg.arch_type == "hybrid" and cfg.ssm.kind == "mamba"), \
            "simulator serves the GQA MoE block or the Jamba hybrid"
        assert not cfg.mla, "simulator uses the plain GQA MoE block"
        self.cfg = cfg
        self.k = cfg.moe.num_experts
        #: (mixer kind, protocol round?) per sublayer of a period; a plain
        #: MoE block is a period of one gated attention sublayer
        self.plan = (T.jamba_sublayers(cfg) if cfg.arch_type == "hybrid"
                     else [("attention", True)])
        self.mesh = mesh if mesh is not None and mesh.size > 1 else None
        if self.mesh is not None:
            assert len(self.mesh.axis_names) == 1, "a 1-D node mesh"
            assert self.k % self.mesh.size == 0, "whole nodes per chip"
        #: traces of the jitted steps so far: a Python side effect of
        #: each step's body, so it counts once per new shape (or new
        #: backend), never per call
        self.compiles = 0
        self._embed = self._jit(self._embed_step)
        self._ffn = self._jit(self._ffn_step)
        self._combine = self._jit(self._combine_step)
        self._unembed = self._jit(self._unembed_step)
        self._mixer = self._jit(self._mixer_step,
                                static_argnames=("mixer", "gate"))
        self._dense_ffn = self._jit(self._dense_ffn_step)
        # `scheme` is any registry name; a pre-constructed policy instance
        # (with custom kwargs) may be passed directly instead.
        self.policy = policy if policy is not None else get_policy(scheme)
        self.scheme = self.policy.name
        self.qos = qos or QoSSchedule(z=cfg.moe.qos_z,
                                      gamma0=cfg.moe.qos_gamma0)
        self.channel_cfg = channel_cfg or channel_lib.ChannelConfig(
            num_experts=self.k,
            num_subcarriers=max(64, self.k * (self.k - 1)))
        # Optional temporal fading process (`repro.scenarios`): gains
        # evolve across serve() calls instead of being redrawn i.i.d.;
        # None keeps the historical draw (and rng stream) bit for bit.
        self.channel_process = channel_process
        self.rng = np.random.default_rng(seed)
        self.params = model_lib.init_params(jax.random.PRNGKey(seed), cfg)
        if self.mesh is not None:
            self.params = jax.device_put(
                self.params, node_shardings(self.params, self.mesh))
        self.comp_coeff = energy_lib.make_comp_coeffs(self.k)
        self.s0 = 8192.0
        self.top_k = top_k or cfg.moe.top_k
        self.count_backward = count_backward
        #: served passes so far; the `pass` id of each pass's spans
        self.passes = 0

    # ------------------------------------------------------------------
    def _jit(self, step, static_argnames=()):
        """`jax.jit` of a step that counts its own traces in `compiles`."""
        @functools.wraps(step)
        def traced(*args, **kwargs):
            self.compiles += 1          # runs only while jax traces
            return step(*args, **kwargs)
        return jax.jit(traced, static_argnames=static_argnames)

    @property
    def chips(self) -> int:
        return 1 if self.mesh is None else self.mesh.size

    def _placed(self, a, axis: Optional[int]):
        """`a` split on `axis` over the node mesh (None: replicated); `a`
        unchanged with no mesh."""
        if self.mesh is None:
            return a
        spec = [None] * a.ndim
        if axis is not None:
            spec[axis] = self.mesh.axis_names[0]
        return jax.lax.with_sharding_constraint(a, jax.sharding.NamedSharding(
            self.mesh, jax.sharding.PartitionSpec(*spec)))

    def xchip_bytes(self, k: int, n: int) -> int:
        """The protocol's step-4 and step-5 payload between chips for a
        pass of (k, n) tokens, from the shapes and the mesh: each round's
        FFN input (activation dtype) goes from its token's chip to every
        other chip, and each chip's float32 sum of its experts' weighted
        outputs goes back to every other chip's tokens.  A nominal
        count: the collectives XLA compiles for it may move more (an
        all-reduce for the return, for one)."""
        if self.mesh is None:
            return 0
        act = 4 if self.cfg.dtype == "float32" else 2
        per_round = (self.chips - 1) * k * n * self.cfg.d_model * (act + 4)
        return per_round * self.rounds_per_pass

    @property
    def rounds_per_pass(self) -> int:
        return (self.cfg.num_layers // len(self.plan)) * sum(
            moe for _, moe in self.plan)

    # -- the jitted steps ------------------------------------------------
    def _embed_step(self, table, tokens):
        x = jnp.take(table, tokens, axis=0)
        return self._placed(x.astype(jnp.float32 if self.cfg.dtype ==
                                     "float32" else jnp.bfloat16), 0)

    def _mixer_step(self, sub, period, x, *, mixer: str, gate: bool):
        """A sublayer's mixer, in situ: RMSNorm, the mixer (attention,
        with no rotary positions where the config has none, or Mamba),
        the residual.  With `gate` (step 2 of its protocol round), also
        the FFN's norm and the router's softmax: (x, h, gates (K, N, E)).
        A hybrid sublayer holds its mixer's weights under `mixer`, a plain
        MoE stack under `attn`."""
        p = _layer_slice(sub, period)
        weights = p["mixer"] if "mixer" in p else p["attn"]
        h = L.rmsnorm(x, p["norm1"], self.cfg.norm_eps)
        if mixer == "attention":
            a, _ = A.gqa_prefill(weights, h, self.cfg, causal=True)
        else:
            a, _ = S.mamba_mix(weights, h, self.cfg)
        x = self._placed(x + a, 0)
        if not gate:
            return x
        h = L.rmsnorm(x, p["norm2"], self.cfg.norm_eps)
        logits = jnp.einsum("bsd,de->bse", h.astype(jnp.float32),
                            p["ffn"]["w_gate_router"])
        return x, h, jax.nn.softmax(logits, axis=-1)

    def _dense_ffn_step(self, sub, period, x):
        """A dense sublayer's SwiGLU, in situ at the query's node."""
        p = _layer_slice(sub, period)
        h = L.rmsnorm(x, p["norm2"], self.cfg.norm_eps)
        return self._placed(x + L.swiglu(p["ffn"], h), 0)

    def _ffn_step(self, sub, period, h):
        # On a node mesh every token's input reaches every chip, and
        # each chip computes its own experts' outputs.
        ye = self._expert_ffn(self._placed(h, None),
                              _layer_slice(sub, period))
        return self._placed(ye, 2)

    def _combine_step(self, x, ye, alpha, gates):
        """Steps 4-5: the Eq.-8 weights of the selected experts and the
        weighted sum of their outputs, added to x."""
        w = alpha * gates
        w = w / jnp.maximum(w.sum(-1, keepdims=True), 1e-9)   # Eq. 8
        # On a node mesh each chip weighs its own experts' outputs; the
        # sums return to the tokens' chips.
        y = jnp.einsum("bsed,bse->bsd", ye.astype(jnp.float32),
                       self._placed(w, 2))
        return x + self._placed(y, 0).astype(x.dtype)

    def _unembed_step(self, norm, table, x):
        """The final norm and the float32 logits.  On a node mesh each
        chip computes its own queries' logits, which are then gathered
        on the device: the host lands one replicated buffer, where a
        split array would land shard by shard into a fresh host array.
        The K split is constrained first: with the replicated one alone
        XLA gathers the hidden state and every chip computes every
        query's logits."""
        logits = L.unembed(L.rmsnorm(x, norm, self.cfg.norm_eps), table)
        return self._placed(self._placed(logits, 0), None)

    def _expert_ffn(self, h, p):
        """Every expert's FFN output for every token: (K, N, E, d).
        Dense in the expert axis and independent of alpha, so it is
        dispatched before the scheduler decides the selection."""
        g1 = jnp.einsum("bsd,edf->bsef", h, p["ffn"]["w1"])
        u1 = jnp.einsum("bsd,edf->bsef", h, p["ffn"]["wu"])
        hh = jax.nn.silu(g1.astype(jnp.float32)).astype(h.dtype) * u1
        return jnp.einsum("bsef,efd->bsed", hh, p["ffn"]["w2"])

    def _schedule(self, gates: np.ndarray, rates: np.ndarray, layer: int,
                  ) -> RoundSchedule:
        """gates: (K, N, E=K). One policy call per protocol round."""
        ctx = ScheduleContext(
            gate_scores=gates,
            rates=rates,
            layer=layer + 1,
            qos=self.qos.qos(layer + 1),
            qos_schedule=self.qos,
            max_experts=self.cfg.moe.max_experts or self.cfg.moe.top_k,
            top_k=self.top_k,
            comp_coeff=self.comp_coeff,
            s0=self.s0,
            p0=self.channel_cfg.tx_power_w,
            rng=self.rng,
        )
        return self.policy.schedule(ctx)

    # ------------------------------------------------------------------
    def serve(self, tokens: np.ndarray) -> SimResult:
        """tokens: (K, N) — one query of N tokens per expert node.

        Each pass is one `dmoe.pass` profiler span, with `dmoe.*` spans
        nested at every layer boundary (docs/serving.md, "Tracing a
        served pass"); they record only while a profiler session runs.
        The device spans time the dispatch of the jitted steps, and
        `dmoe.pass` carries `compiles`: the steps' traces in the pass."""
        cfg = self.cfg
        k, n = tokens.shape
        assert k == self.k, "one query per expert node (§III-C step 1)"
        self.passes += 1
        compiles = self.compiles
        with TraceAnnotation("dmoe.pass", **{"pass": self.passes}) as span:
            gains = (self.channel_process.step(self.rng)
                     if self.channel_process is not None else
                     channel_lib.sample_channel_gains(self.channel_cfg,
                                                      self.rng))
            rates = channel_lib.subcarrier_rates(self.channel_cfg, gains)

            with TraceAnnotation("dmoe.embed"):
                x = self._embed(self.params["embed"], tokens)

            rounds: List[proto.RoundAccounting] = []
            schedules: List[RoundSchedule] = []
            hist = np.zeros((self.rounds_per_pass, self.k))
            # Each plan entry's stacked weights: a hybrid period's
            # `sub{i}`; a plain MoE stack is its own one sublayer.
            stack = self.params["stages"]["stage0"]
            subs = ([stack[f"sub{i}"] for i in range(len(self.plan))]
                    if cfg.arch_type == "hybrid" else [stack])
            for period in range(cfg.num_layers // len(self.plan)):
                for (mixer, moe), sub in zip(self.plan, subs, strict=True):
                    if not moe:
                        p = np.int32(period)
                        with TraceAnnotation("dmoe.mixer", kind=mixer):
                            x = self._mixer(sub, p, x, mixer=mixer,
                                            gate=False)
                        with TraceAnnotation("dmoe.dense_ffn"):
                            x = self._dense_ffn(sub, p, x)
                        continue
                    layer = len(rounds)
                    with TraceAnnotation("dmoe.round", layer=layer + 1,
                                         **{"pass": self.passes}):
                        x, rs, acct = self._protocol_round(
                            x, sub, period, mixer, rates, layer)
                    schedules.append(rs)
                    rounds.append(acct)
                    hist[layer] = rs.alpha.sum(axis=(0, 1)) / max(
                        rs.alpha.sum(), 1)

            with TraceAnnotation("dmoe.unembed"):
                table = (self.params["embed"] if cfg.tie_embeddings
                         else self.params["unembed"])
                logits = self._unembed(self.params["final_norm"], table, x)
            span.set_metadata(compiles=self.compiles - compiles,
                              chips=self.chips,
                              xchip_bytes=self.xchip_bytes(k, n))
            summary = proto.summarize(rounds)
            with TraceAnnotation(
                    "dmoe.logits_d2h", bytes=logits.nbytes,
                    buffers=(1 if logits.is_fully_replicated
                             else len(logits.addressable_shards))):
                return SimResult(
                    logits=np.asarray(logits, dtype=np.float32),
                    rounds=rounds,
                    summary=summary,
                    selection_hist=hist,
                    schedules=schedules,
                )

    def _protocol_round(self, x, sub, period: int, mixer: str,
                        rates: np.ndarray, layer: int):
        """One protocol round (steps 2-5, `layer` 0-based) at a MoE
        sublayer of `period`: returns (x after the Eq.-8 combine, the
        round's schedule, its energy accounting)."""
        # -- step 2: the mixer and the gate (in situ) ------------------
        # A gated attention step keeps the plain MoE block's spans, any
        # other mixer step is `dmoe.mixer` (docs/serving.md).
        if mixer == "attention":
            with TraceAnnotation("dmoe.params"):
                p = np.int32(period)
            span = TraceAnnotation("dmoe.attn_gate")
        else:
            p = np.int32(period)
            span = TraceAnnotation("dmoe.mixer", kind=mixer)
        with span:
            x, h, gates_dev = self._mixer(sub, p, x, mixer=mixer, gate=True)
        # -- step 3: joint expert & subcarrier allocation --------------
        # The per-expert FFN outputs don't depend on alpha (selection
        # only weights the Eq.-8 combine), so they are dispatched BEFORE
        # blocking on the host scheduler: the device einsums run
        # concurrently with the host B&B.
        with TraceAnnotation("dmoe.expert_ffn"):
            ye = self._ffn(sub, p, h)
        with TraceAnnotation("dmoe.gate_d2h"):
            gates = np.asarray(gates_dev, dtype=np.float64)
        with TraceAnnotation("dmoe.schedule"):
            rs = self._schedule(gates, rates, layer)
        alpha, beta = rs.alpha, rs.beta

        # -- steps 4-5: forward tx + FFN + backward tx + aggregate -----
        # The gates the scheduler saw, as the device holds them: float32
        # -> float64 -> float32 is exact, so no copy goes back up.
        with TraceAnnotation("dmoe.combine"):
            x = self._combine(x, ye, np.asarray(alpha, dtype=np.float32),
                              gates_dev)

        with TraceAnnotation("dmoe.account"):
            acct = proto.account_round(
                layer + 1, alpha, beta, rates, self.comp_coeff, self.s0,
                self.channel_cfg.tx_power_w,
                count_backward=self.count_backward)
        return x, rs, acct
