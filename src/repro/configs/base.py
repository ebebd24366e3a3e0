"""Config system: architecture dataclass + registry + CLI overrides.

Every assigned architecture gets a module `repro/configs/<id>.py` exporting
`CONFIG` (full-scale, dry-run only) and `smoke()` (reduced variant for CPU
tests).  `get_config(name)` resolves either by registry id.
"""

from __future__ import annotations

import dataclasses
import importlib
from typing import Any, Dict, Optional, Tuple


@dataclasses.dataclass(frozen=True)
class MoEConfig:
    num_experts: int = 0              # routed experts (0 = dense FFN)
    top_k: int = 2
    num_shared_experts: int = 0       # always-on shared experts (DeepSeek)
    d_ff_expert: int = 0              # per-expert hidden dim
    first_dense_layers: int = 0       # leading layers with dense FFN (dsv3: 3)
    every: int = 1                    # MoE layer period (jamba: 2)
    capacity_factor: float = 1.25
    # --- paper technique: routing policy + QoS schedule -----------------
    # `routing` is a repro.schedulers registry name ("topk", "des",
    # "dense", "jesa", ...); `routing_kwargs` are constructor kwargs for
    # the policy, stored as a tuple of (key, value) pairs so the config
    # stays hashable.  Resolve with `resolve_routing_policy(cfg)`.
    routing: str = "topk"
    routing_kwargs: Tuple[Tuple[str, Any], ...] = ()
    # token-dispatch implementation for the MoE FFN hot path:
    #   "xla"     — one-hot dispatch/combine einsums (default; the
    #               historical path, SPMD lowers them to all-to-alls);
    #   "fused"   — Pallas fused route + gather-dispatch + weighted
    #               combine over the capacity layout (no one-hot);
    #   "grouped" — Pallas ragged layout (tokens sorted by expert id,
    #               per-expert offsets) with the scalar-prefetch FFN.
    # Vocabulary lives in `repro.kernels.moe_route.ROUTING_IMPLS`.
    routing_impl: str = "xla"
    qos_z: float = 1.0
    qos_gamma0: float = 0.7           # gamma^(l) = gamma0^l
    max_experts: int = 0              # D (0 -> top_k)
    # --- device-limited routing (DeepSeek-V2 `group_limited_greedy`) ----
    # `n_group` groups of num_experts / n_group experts, a token's top_k
    # taken within its best `topk_group` groups.  Only the protocol
    # simulator serves groups, each as one edge node; `models.moe`
    # rejects them.  1 = no groups.
    n_group: int = 1
    topk_group: int = 1
    routed_scaling_factor: float = 1.0  # routed weights x this
    norm_topk_prob: bool = True       # renormalise the top_k weights;
                                      # groups take only False
    aux_loss_weight: float = 0.01
    router_z_weight: float = 1e-3


@dataclasses.dataclass(frozen=True)
class SSMConfig:
    kind: str = "none"                # "rwkv6" | "mamba"
    d_state: int = 16                 # mamba state dim
    d_conv: int = 4                   # mamba conv kernel
    expand: int = 2                   # mamba d_inner = expand * d_model
    head_dim: int = 64                # rwkv6 head size
    attn_every: int = 0               # hybrid: attention layer period (jamba: 8)
    scan_chunk: int = 1024            # mamba: SSM recurrence chunk length
    inner_norms: bool = False         # mamba: RMSNorm on dt, B and C (Jamba)


@dataclasses.dataclass(frozen=True)
class ModelConfig:
    name: str = "model"
    arch_type: str = "dense"          # dense | moe | ssm | hybrid | audio | vlm
    source: str = ""                  # citation [hf:... / arXiv:...]

    num_layers: int = 12
    d_model: int = 768
    num_heads: int = 12
    num_kv_heads: int = 12
    head_dim: int = 0                 # 0 -> d_model // num_heads
    d_ff: int = 3072
    vocab_size: int = 32000

    rope_theta: float = 1e6
    rope: bool = True                 # rotary positions in GQA (Jamba: none)
    max_seq_len: int = 131072
    sliding_window: int = 0           # 0 = full attention
    norm_eps: float = 1e-5
    tie_embeddings: bool = False

    moe: MoEConfig = dataclasses.field(default_factory=MoEConfig)
    ssm: SSMConfig = dataclasses.field(default_factory=SSMConfig)

    # YaRN rotary scaling (DeepSeek-V2/V3 `rope_scaling`); a factor of 1
    # keeps plain rotary positions
    yarn_factor: float = 1.0
    yarn_original_max_pos: int = 4096
    yarn_beta_fast: float = 32.0
    yarn_beta_slow: float = 1.0
    yarn_mscale: float = 1.0
    yarn_mscale_all_dim: float = 0.0

    # MLA (DeepSeek-V2/V3)
    mla: bool = False
    q_lora_rank: int = 1536
    kv_lora_rank: int = 512
    rope_head_dim: int = 64
    nope_head_dim: int = 128
    v_head_dim: int = 128

    # multi-token prediction (DeepSeek-V3 training objective)
    mtp: bool = False
    mtp_weight: float = 0.3

    # encoder-decoder (whisper)
    enc_dec: bool = False
    encoder_layers: int = 0
    encoder_max_len: int = 1500      # whisper: 30 s audio -> 1500 frames
    decoder_max_len: int = 448

    # modality frontend stubs
    input_kind: str = "tokens"        # tokens | frames (audio) — vlm uses tokens (VQ)

    # numerics
    dtype: str = "bfloat16"           # activations/compute
    param_dtype: str = "bfloat16"

    # moe dispatch group size (tokens per dispatch group along seq)
    dispatch_group: int = 512

    # attention chunking (flash-style jnp path): use the chunked online-
    # softmax implementation when S_kv exceeds the threshold
    attn_chunk_threshold: int = 4096
    attn_q_chunk: int = 2048
    attn_kv_chunk: int = 2048

    def resolved_head_dim(self) -> int:
        return self.head_dim or (self.d_model // self.num_heads)

    def is_moe_layer(self, layer_idx: int) -> bool:
        """layer_idx is 0-based."""
        if self.moe.num_experts == 0:
            return False
        if layer_idx < self.moe.first_dense_layers:
            return False
        return (layer_idx - self.moe.first_dense_layers) % self.moe.every == 0

    def is_attn_layer(self, layer_idx: int) -> bool:
        """For hybrid (jamba): attention every `attn_every` layers."""
        if self.ssm.attn_every <= 0:
            return self.ssm.kind == "none"
        return layer_idx % self.ssm.attn_every == 0

    def with_overrides(self, **kw) -> "ModelConfig":
        moe_kw = {k[4:]: v for k, v in kw.items() if k.startswith("moe_")}
        ssm_kw = {k[4:]: v for k, v in kw.items() if k.startswith("ssm_")}
        top = {k: v for k, v in kw.items()
               if not k.startswith(("moe_", "ssm_"))}
        cfg = self
        if moe_kw:
            cfg = dataclasses.replace(cfg, moe=dataclasses.replace(cfg.moe, **moe_kw))
        if ssm_kw:
            cfg = dataclasses.replace(cfg, ssm=dataclasses.replace(cfg.ssm, **ssm_kw))
        if top:
            cfg = dataclasses.replace(cfg, **top)
        return cfg


def resolve_routing_policy(cfg: "ModelConfig"):
    """Construct the scheduler policy named by `cfg.moe.routing` via the
    repro.schedulers registry (the single construction path)."""
    from repro.schedulers import get_policy  # lazy: configs stay light

    return get_policy(cfg.moe.routing, **dict(cfg.moe.routing_kwargs))


# ----------------------------------------------------------------------
# input shapes (assignment)
# ----------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class InputShape:
    name: str
    seq_len: int
    global_batch: int
    kind: str          # "train" | "prefill" | "decode"


INPUT_SHAPES: Dict[str, InputShape] = {
    "train_4k": InputShape("train_4k", 4096, 256, "train"),
    "prefill_32k": InputShape("prefill_32k", 32768, 32, "prefill"),
    "decode_32k": InputShape("decode_32k", 32768, 128, "decode"),
    "long_500k": InputShape("long_500k", 524288, 1, "decode"),
}


ARCH_IDS = [
    "glm4_9b",
    "phi35_moe",
    "whisper_base",
    "mistral_nemo_12b",
    "llama32_1b",
    "chameleon_34b",
    "rwkv6_7b",
    "jamba_15_large",
    "stablelm_16b",
    "deepseek_v3",
]

# external ids (--arch flag) -> module names
ARCH_ALIASES = {
    "glm4-9b": "glm4_9b",
    "phi3.5-moe-42b-a6.6b": "phi35_moe",
    "whisper-base": "whisper_base",
    "mistral-nemo-12b": "mistral_nemo_12b",
    "llama3.2-1b": "llama32_1b",
    "chameleon-34b": "chameleon_34b",
    "rwkv6-7b": "rwkv6_7b",
    "jamba-1.5-large-398b": "jamba_15_large",
    "jamba2-mini": "jamba2_mini",
    "stablelm-1.6b": "stablelm_16b",
    "deepseek-v3-671b": "deepseek_v3",
    # paper's own model
    "mixtral-8x7b": "mixtral_8x7b",
    "dmoe-paper": "mixtral_8x7b",
    # ported external-baseline routing variants (routing_kwargs-tuned)
    "mixtral-channel-aware": "mixtral_channel_aware",
    "mixtral-siftmoe": "mixtral_siftmoe",
}


def get_config(name: str) -> ModelConfig:
    mod_name = ARCH_ALIASES.get(name, name.replace("-", "_").replace(".", ""))
    mod = importlib.import_module(f"repro.configs.{mod_name}")
    return mod.CONFIG


def get_smoke_config(name: str) -> ModelConfig:
    mod_name = ARCH_ALIASES.get(name, name.replace("-", "_").replace(".", ""))
    mod = importlib.import_module(f"repro.configs.{mod_name}")
    return mod.smoke()


def all_arch_names() -> Tuple[str, ...]:
    return tuple(a for a in ARCH_ALIASES if a not in ("dmoe-paper",))
