"""DMoE edge-deployment simulator — the paper's protocol end-to-end
(§III-C, Fig. 1b).

K expert nodes hold a vertically-partitioned MoE model (node j = the
shared blocks + FFN_j of every layer, Eq. 6).  A node is one routed
expert, or, under device-limited routing (DeepSeek-V2's
`group_limited_greedy`, `MoEConfig.n_group` > 1), one group of
num_experts / n_group experts.  Each node is assigned at most one query
(§III-C step 1).  Per MoE layer l (one protocol round):

  1. the mixer + gate at each source node (in-situ, real JAX compute);
  2. gate scores + CSI -> the scheduler ("server"); with node groups a
     node's score is its group's share of the router's mass;
  3. scheduler runs any registered policy -> (alpha, beta): JESA /
     sharded-des / Top-k / homogeneous / LB / ... (`repro.schedulers`);
  4-5. hidden states "transmitted" i->j, FFN_j applied for selected j,
       results aggregated — with one expert a node by the Eq.-8
       weights, with node groups by the router's own weights of its
       top_k experts within the selected nodes — computed exactly, with
       the energy meter charging Eq. (3)-(4) for the traffic;
  6. next layer.

The model math is exact (the simulator produces the same logits a
centralized run with the same per-token expert masks would); what is
simulated is the wireless channel + energy, not the transformer.

One layer plan: the simulator walks `plan`, one entry per stage of
`models.transformer.stage_plan` -- the stage's params, the (mixer kind,
protocol round?) of each sublayer of its period, and its periods.  A
plain MoE block (`arch_type="moe"`) is a stage whose period is one
sublayer, attention (GQA, or MLA with YaRN positions) with a protocol
round, after a stage of dense layers where the config has leading ones
(`first_dense_layers`); a hybrid (Jamba, `arch_type="hybrid"`) takes its
period from `models.transformer.jamba_sublayers`.  Every sublayer runs
one jitted mixer step -- RMSNorm, Mamba, GQA or MLA, the residual -- and
at a MoE sublayer the FFN's norm and the gate with it.  A MoE sublayer
is then one protocol round as above (the QoS l counts rounds); a dense
sublayer's SwiGLU runs in situ at the query's node in its own jitted
step, with no scheduler call and no transmission.  Every node holds the
shared blocks (Eq. 6): mixers, dense FFNs and shared experts included;
shared experts run in situ in the expert FFN step.

Compiled steps: embed, the mixer step, every held expert's FFN (with
the shared experts), the combine, the dense FFN and unembed are each
jitted once per simulator.  Each step slices its period's weights from
the stacked params by a traced period index, so one executable serves
every period and every sublayer of one shape, and jit's own cache keys
them on the (K, N) shape.  The gated mixer step and the FFN step are
separate executables on purpose: an executable's outputs are ready only
when all of it ends, so a fused step would hold the gate scores back
until the FFN were done.

A chip's share of the experts: `held_nodes` names the nodes whose
routed experts this simulator holds (default: all).  It routes over
every expert and computes only the part of each layer that its held
experts give, as one chip of an expert-parallel deployment does; the
parts the other nodes' experts would add are left out.

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
combine), so they are dispatched *before* the round waits for its
gate scores and runs the host scheduler -- jax's asynchronous dispatch
overlaps the device FFN work of round l with the host branch-and-bound
of round l (and, under the "async-des" policy, with its pipelined
pre-work rounds).
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Dict, List, Optional, Sequence, Tuple

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


def _sublayers(kind: str, cfg: ModelConfig) -> List[Tuple[str, bool]]:
    """(mixer kind, protocol round?) of each sublayer of one period of a
    `models.transformer.stage_plan` block kind."""
    if kind == "jamba":
        return T.jamba_sublayers(cfg)
    assert kind in ("dense", "moe", "mla_dense", "mla_moe"), kind
    return [("mla" if kind.startswith("mla") else "attention",
             kind.endswith("moe"))]


def _is_routed_expert(path, leaf) -> bool:
    """A stacked routed-expert weight: `ffn/w1|wu|w2`, (layers, E, ...)."""
    names = [str(getattr(k, "key", k)) for k in path]
    return names[-1] in ("w1", "wu", "w2") and "ffn" in names and leaf.ndim == 4


def expert_share(params, experts: slice):
    """`params` holding only the routed experts `experts` of every MoE
    layer; every other weight (router, shared experts, mixers) whole.  A
    tree that holds just that many experts already is that share."""
    held = experts.stop - experts.start
    return jax.tree_util.tree_map_with_path(
        lambda path, a: (a[:, experts] if _is_routed_expert(path, a)
                         and a.shape[1] != held else a), params)


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
    #: each round's router softmax over every routed expert, (K, N, E)
    #: float32, as the host landed it
    expert_scores: List[np.ndarray] = dataclasses.field(default_factory=list)
    #: with node groups, each round's top_k experts per token, (K, N,
    #: top_k) int32, chosen within the selected nodes
    routed: List[np.ndarray] = dataclasses.field(default_factory=list)
    #: (token, expert) pairs the combine weighed from the held experts
    routed_pairs: int = 0


class DMoESimulator:
    """Serve queries through the DMoE protocol with a real (small) MoE
    model supplying gates and FFN compute.

    cfg must be an arch_type="moe" config (GQA or MLA attention), or a
    "hybrid" (Jamba) one with Mamba mixers.  K nodes: num_experts, or
    `moe.n_group` groups of experts.  `held_nodes`: the nodes whose
    routed experts this simulator holds, a contiguous run (default:
    all).  `mesh`: an optional 1-D device mesh over which the K nodes
    are spread (module docstring).
    """

    def __init__(self, cfg: ModelConfig, *, scheme: str = "jesa",
                 policy: Optional[SchedulerPolicy] = None,
                 qos: Optional[QoSSchedule] = None,
                 channel_cfg: Optional[channel_lib.ChannelConfig] = None,
                 channel_process: Optional[
                     channel_lib.ChannelProcess] = None,
                 seed: int = 0, top_k: Optional[int] = None,
                 count_backward: bool = True,
                 mesh: Optional[jax.sharding.Mesh] = None,
                 held_nodes: Optional[Sequence[int]] = None):
        m = cfg.moe
        assert m.num_experts >= 1
        assert cfg.arch_type == "moe" or (
            cfg.arch_type == "hybrid" and cfg.ssm.kind == "mamba"), \
            "simulator serves the MoE block or the Jamba hybrid"
        self.cfg = cfg
        #: edge nodes, and the routed experts each holds
        self.k = m.n_group if m.n_group > 1 else m.num_experts
        self.group = m.num_experts // self.k
        assert self.group * self.k == m.num_experts, "whole expert groups"
        assert self.group == 1 or not m.norm_topk_prob, \
            "node groups weigh the routed scores unnormalised"
        nodes = list(range(self.k) if held_nodes is None else held_nodes)
        assert nodes == list(range(nodes[0], nodes[-1] + 1)), \
            "held nodes are a contiguous run"
        #: the routed experts held here, by id
        self.held = slice(nodes[0] * self.group, (nodes[-1] + 1) * self.group)
        #: one entry per stage of the stack: (its key in
        #: params["stages"], the (mixer kind, protocol round?) of each
        #: sublayer of its period, its periods)
        self.plan = [(f"stage{i}", _sublayers(kind, cfg), n)
                     for i, (kind, n) in enumerate(T.stage_plan(cfg))]
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
        self.qos = qos or QoSSchedule(z=m.qos_z, gamma0=m.qos_gamma0)
        self.channel_cfg = channel_cfg or channel_lib.ChannelConfig(
            num_experts=self.k,
            num_subcarriers=max(64, self.k * (self.k - 1)))
        # Optional temporal fading process (`repro.scenarios`): gains
        # evolve across serve() calls instead of being redrawn i.i.d.;
        # None keeps the historical draw (and rng stream) bit for bit.
        self.channel_process = channel_process
        self.rng = np.random.default_rng(seed)
        self.params = expert_share(
            model_lib.init_params(jax.random.PRNGKey(seed), cfg), self.held)
        if self.mesh is not None:
            self.params = jax.device_put(
                self.params, node_shardings(self.params, self.mesh))
        self.comp_coeff = energy_lib.make_comp_coeffs(self.k)
        self.s0 = 8192.0
        # Node-level budgets: a token reaches at most this many nodes.
        per_token = m.topk_group if self.group > 1 else m.top_k
        self.top_k = top_k or per_token
        self.max_experts = m.max_experts or per_token
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
        return sum(periods * sum(moe for _, moe in subs)
                   for _, subs, periods in self.plan)

    @property
    def held_experts(self) -> int:
        return self.held.stop - self.held.start

    def node_scores(self, scores: np.ndarray) -> np.ndarray:
        """The scheduler's gate scores (K, N, K) float64 from the
        router's softmax (K, N, E): each node's share of the mass, the
        sum over the experts it holds (one expert a node: the scores)."""
        s = np.asarray(scores, dtype=np.float64)
        if self.group == 1:
            return s
        return s.reshape(*s.shape[:-1], self.k, self.group).sum(-1)

    # -- the jitted steps ------------------------------------------------
    def _embed_step(self, table, tokens):
        x = jnp.take(table, tokens, axis=0)
        return self._placed(x.astype(jnp.float32 if self.cfg.dtype ==
                                     "float32" else jnp.bfloat16), 0)

    def _mixer_step(self, sub, period, x, *, mixer: str, gate: bool):
        """A sublayer's mixer, in situ: RMSNorm, the mixer (GQA, with no
        rotary positions where the config has none; MLA; or Mamba), the
        residual.  With `gate` (step 2 of its protocol round), also the
        FFN's norm and the router's float32 softmax over every routed
        expert: (x, h, gates (K, N, E)).  A hybrid sublayer holds its
        mixer's weights under `mixer`, a plain MoE stack under `attn`."""
        p = _layer_slice(sub, period)
        weights = p["mixer"] if "mixer" in p else p["attn"]
        h = L.rmsnorm(x, p["norm1"], self.cfg.norm_eps)
        if mixer == "attention":
            a, _ = A.gqa_prefill(weights, h, self.cfg, causal=True)
        elif mixer == "mla":
            a, _ = A.mla_prefill(weights, h, self.cfg)
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
        """(every held expert's output for every token (K, N, E_held, d),
        the shared experts' SwiGLU, None where the layer has none).
        Neither depends on alpha."""
        p = _layer_slice(sub, period)
        # On a node mesh every token's input reaches every chip, and
        # each chip computes its own experts' outputs.
        ye = self._placed(self._expert_ffn(self._placed(h, None), p), 2)
        shared = p["ffn"].get("shared")
        return ye, None if shared is None else L.swiglu(shared, h)

    def _combine_step(self, x, ffn, alpha, gates):
        """Steps 4-5: the weights of the selected experts and the
        weighted sum of the held experts' outputs (and the shared
        experts'), added to x: (x, the chosen experts, the pairs weighed
        here).  One expert a node: the Eq.-8 weights, and no chosen
        experts or pairs (None).  Node groups: the router's own
        (`_routed_weights`)."""
        ye, shared = ffn
        chosen = pairs = None
        if self.group == 1:
            w = alpha * gates
            w = w / jnp.maximum(w.sum(-1, keepdims=True), 1e-9)   # Eq. 8
        else:
            w, chosen, pairs = self._routed_weights(alpha, gates)
        # On a node mesh each chip weighs its own experts' outputs; the
        # sums return to the tokens' chips.
        y = jnp.einsum("bsed,bse->bsd", ye.astype(jnp.float32),
                       self._placed(w, 2))
        y = self._placed(y, 0)
        if shared is not None:
            y = y + shared.astype(jnp.float32)
        return x + y.astype(x.dtype), chosen, pairs

    def _routed_weights(self, alpha, gates):
        """Device-limited routing over the nodes alpha selected: each
        token's top_k experts by the router's score among the selected
        nodes' experts, weighted by their score x routed_scaling_factor,
        unnormalised.  Returns the weights of the held experts (K, N,
        E_held), the chosen experts (K, N, top_k) and how many chosen
        pairs are held here."""
        m = self.cfg.moe
        selected = jnp.repeat(alpha > 0, self.group, axis=-1)     # (K, N, E)
        top, chosen = jax.lax.top_k(jnp.where(selected, gates, -1.0),
                                    m.top_k)
        ok = top >= 0           # fewer than top_k experts selected: unused
        w = jnp.where(ok, top * m.routed_scaling_factor, 0.0)
        local = chosen - self.held.start
        held = ok & (local >= 0) & (local < self.held_experts)
        w_held = (jax.nn.one_hot(local, self.held_experts, dtype=jnp.float32)
                  * jnp.where(held, w, 0.0)[..., None]).sum(-2)
        return w_held, chosen, held.sum(dtype=jnp.int32)

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
        """Every held expert's FFN output for every token: (K, N, E, d).
        Dense in the expert axis and independent of alpha, so it is
        dispatched before the scheduler decides the selection."""
        g1 = jnp.einsum("bsd,edf->bsef", h, p["ffn"]["w1"])
        u1 = jnp.einsum("bsd,edf->bsef", h, p["ffn"]["wu"])
        hh = jax.nn.silu(g1.astype(jnp.float32)).astype(h.dtype) * u1
        return jnp.einsum("bsef,efd->bsed", hh, p["ffn"]["w2"])

    def _schedule(self, gates: np.ndarray, rates: np.ndarray, layer: int,
                  ) -> RoundSchedule:
        """gates: the node scores (K, N, K). One policy call per protocol
        round."""
        ctx = ScheduleContext(
            gate_scores=gates,
            rates=rates,
            layer=layer + 1,
            qos=self.qos.qos(layer + 1),
            qos_schedule=self.qos,
            max_experts=self.max_experts,
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
        `dmoe.pass` carries `compiles` (the steps' traces in the pass)
        and `routed_pairs` (the (token, expert) pairs the combines
        weighed from the held experts)."""
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
            scores: List[np.ndarray] = []
            routed, pairs = [], []
            hist = np.zeros((self.rounds_per_pass, self.k))
            for key, subplan, periods in self.plan:
                # Each sublayer's stacked weights: a hybrid period's
                # `sub{i}`; any other stage is its own one sublayer.
                stack = self.params["stages"][key]
                subs = ([stack[f"sub{i}"] for i in range(len(subplan))]
                        if "sub0" in stack else [stack])
                for period in range(periods):
                    for (mixer, moe), sub in zip(subplan, subs, strict=True):
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
                            (x, chosen, held_pairs), rs, acct, g = \
                                self._protocol_round(x, sub, period, mixer,
                                                     rates, layer)
                        if chosen is not None:
                            routed.append(chosen)
                        pairs.append(int(rs.alpha.sum())
                                     if held_pairs is None else held_pairs)
                        schedules.append(rs)
                        rounds.append(acct)
                        scores.append(g)
                        hist[layer] = rs.alpha.sum(axis=(0, 1)) / max(
                            rs.alpha.sum(), 1)

            with TraceAnnotation("dmoe.unembed"):
                table = (self.params["embed"] if cfg.tie_embeddings
                         else self.params["unembed"])
                logits = self._unembed(self.params["final_norm"], table, x)
            summary = proto.summarize(rounds)
            with TraceAnnotation(
                    "dmoe.logits_d2h", bytes=logits.nbytes,
                    buffers=(1 if logits.is_fully_replicated
                             else len(logits.addressable_shards))):
                host_logits = np.asarray(logits, dtype=np.float32)
                # the rounds' choices and counts are ready with the logits
                routed, pairs = jax.device_get((routed, pairs))
            routed_pairs = int(sum(int(n) for n in pairs))
            span.set_metadata(compiles=self.compiles - compiles,
                              chips=self.chips,
                              xchip_bytes=self.xchip_bytes(k, n),
                              routed_pairs=routed_pairs)
            return SimResult(
                logits=host_logits,
                rounds=rounds,
                summary=summary,
                selection_hist=hist,
                schedules=schedules,
                expert_scores=scores,
                routed=[np.asarray(r) for r in routed],
                routed_pairs=routed_pairs,
            )

    def _protocol_round(self, x, sub, period: int, mixer: str,
                        rates: np.ndarray, layer: int):
        """One protocol round (steps 2-5, `layer` 0-based) at a MoE
        sublayer of `period`: returns (the combine step's (x, chosen
        experts, pairs held), the round's schedule, its energy
        accounting, the router's scores (K, N, E) float32 as landed)."""
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
        # The held experts' FFN outputs (and the shared experts') don't
        # depend on alpha (selection only weights the combine), so they
        # are dispatched BEFORE blocking on the host scheduler: the
        # device einsums run concurrently with the host B&B.
        with TraceAnnotation("dmoe.expert_ffn", held=self.held_experts,
                             shared=self.cfg.moe.num_shared_experts):
            ffn = self._ffn(sub, p, h)
        with TraceAnnotation("dmoe.gate_d2h"):
            scores = np.asarray(gates_dev)
        with TraceAnnotation("dmoe.schedule"):
            rs = self._schedule(self.node_scores(scores), rates, layer)
        alpha, beta = rs.alpha, rs.beta

        # -- steps 4-5: forward tx + FFN + backward tx + aggregate -----
        # The gates as the device holds them: the scheduler's node
        # scores came from these float32 scores, so no copy goes back up.
        with TraceAnnotation("dmoe.combine"):
            out = self._combine(x, ffn, np.asarray(alpha, dtype=np.float32),
                                gates_dev)

        with TraceAnnotation("dmoe.account"):
            acct = proto.account_round(
                layer + 1, alpha, beta, rates, self.comp_coeff, self.s0,
                self.channel_cfg.tx_power_w,
                count_backward=self.count_backward)
        return out, rs, acct, scores
