"""Basic layers: init helpers, norms, RoPE, SwiGLU — functional, pytree params.

Convention: every `init_*` returns a (nested) dict of jnp arrays; every
`apply`-style function is pure.  Param dict keys are stable strings — the
sharding rules in `repro.distributed.sharding` match on key paths.
"""

from __future__ import annotations

import math
from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np


def _dtype(name: str):
    return {"bfloat16": jnp.bfloat16, "float32": jnp.float32,
            "float16": jnp.float16}[name]


def dense_init(key, in_dim: int, out_shape, dtype) -> jnp.ndarray:
    """Fan-in scaled normal init; out_shape may be a tuple (e.g. heads)."""
    if isinstance(out_shape, int):
        out_shape = (out_shape,)
    scale = 1.0 / np.sqrt(in_dim)
    return (jax.random.normal(key, (in_dim, *out_shape), dtype=jnp.float32)
            * scale).astype(dtype)


def embed_init(key, vocab: int, d: int, dtype) -> jnp.ndarray:
    return (jax.random.normal(key, (vocab, d), dtype=jnp.float32)
            * 0.02).astype(dtype)


def rmsnorm_init(d: int, dtype) -> jnp.ndarray:
    return jnp.ones((d,), dtype=dtype)


def rmsnorm(x: jnp.ndarray, w: jnp.ndarray, eps: float = 1e-5) -> jnp.ndarray:
    """RMSNorm with fp32 accumulation."""
    xf = x.astype(jnp.float32)
    var = jnp.mean(xf * xf, axis=-1, keepdims=True)
    out = xf * jax.lax.rsqrt(var + eps)
    return (out * w.astype(jnp.float32)).astype(x.dtype)


# ----------------------------------------------------------------------
# RoPE
# ----------------------------------------------------------------------

def yarn_mscale(factor: float, mscale: float) -> float:
    """YaRN's magnitude scaling 0.1 * mscale * ln(factor) + 1; 1 where
    the factor does not stretch the positions."""
    return 1.0 if factor <= 1 else 0.1 * mscale * math.log(factor) + 1.0


def rope_frequencies(head_dim: int, theta: float,
                     yarn: Optional[Tuple[float, int, float, float]] = None,
                     ) -> jnp.ndarray:
    """Inverse frequencies of the rotary pairs.  With `yarn` = (factor,
    original positions, beta_fast, beta_slow), YaRN's blend (DeepSeek-V2's
    `DeepseekV2YarnRotaryEmbedding`): pairs that turn more than beta_fast
    times over the original positions keep their frequency, those that
    turn fewer than beta_slow times take it / factor, and a linear ramp
    between the two correction dims blends the rest."""
    freqs = 1.0 / (theta ** (jnp.arange(0, head_dim, 2, dtype=jnp.float32)
                             / head_dim))
    if yarn is None:
        return freqs
    factor, original, beta_fast, beta_slow = yarn

    def correction_dim(rotations):
        return (head_dim * math.log(original / (rotations * 2 * math.pi))
                / (2 * math.log(theta)))

    low = max(math.floor(correction_dim(beta_fast)), 0)
    high = min(math.ceil(correction_dim(beta_slow)), head_dim - 1)
    if low == high:
        high += 0.001
    ramp = jnp.clip((jnp.arange(head_dim // 2, dtype=jnp.float32) - low)
                    / (high - low), 0.0, 1.0)
    inter = 1.0 / (factor * theta ** (jnp.arange(0, head_dim, 2,
                                                  dtype=jnp.float32)
                                      / head_dim))
    return inter * ramp + freqs * (1.0 - ramp)


def apply_rope(x: jnp.ndarray, positions: jnp.ndarray, theta: float,
               yarn: Optional[Tuple[float, int, float, float]] = None,
               magnitude: float = 1.0) -> jnp.ndarray:
    """Rotary embedding. x: (..., S, H, Dh); positions: broadcastable (..., S).
    `yarn` as `rope_frequencies` takes it; cos and sin are x `magnitude`."""
    dh = x.shape[-1]
    freqs = rope_frequencies(dh, theta, yarn)                # (Dh/2,)
    angles = positions[..., None].astype(jnp.float32) * freqs  # (..., S, Dh/2)
    cos = jnp.cos(angles)[..., None, :]                      # (..., S, 1, Dh/2)
    sin = jnp.sin(angles)[..., None, :]
    if magnitude != 1.0:
        cos, sin = cos * magnitude, sin * magnitude
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], axis=-1)
    return out.astype(x.dtype)


# ----------------------------------------------------------------------
# MLPs
# ----------------------------------------------------------------------

def swiglu_init(key, d: int, f: int, dtype) -> Dict[str, jnp.ndarray]:
    k1, k2, k3 = jax.random.split(key, 3)
    return {
        "w_gate": dense_init(k1, d, f, dtype),
        "w_up": dense_init(k2, d, f, dtype),
        "w_down": dense_init(k3, f, d, dtype),
    }


def swiglu(params: Dict[str, jnp.ndarray], x: jnp.ndarray) -> jnp.ndarray:
    g = jnp.einsum("...d,df->...f", x, params["w_gate"])
    u = jnp.einsum("...d,df->...f", x, params["w_up"])
    h = jax.nn.silu(g.astype(jnp.float32)).astype(x.dtype) * u
    return jnp.einsum("...f,fd->...d", h, params["w_down"])


def relu_sq_mlp_init(key, d: int, f: int, dtype) -> Dict[str, jnp.ndarray]:
    k1, k2 = jax.random.split(key)
    return {"w_in": dense_init(k1, d, f, dtype),
            "w_out": dense_init(k2, f, d, dtype)}


def relu_sq_mlp(params, x):
    h = jnp.einsum("...d,df->...f", x, params["w_in"])
    h = jnp.square(jax.nn.relu(h))
    return jnp.einsum("...f,fd->...d", h, params["w_out"])


def unembed(x: jnp.ndarray, table: jnp.ndarray) -> jnp.ndarray:
    """Logits via (tied or untied) output table (V, d). fp32 out."""
    return jnp.einsum("...d,vd->...v", x.astype(jnp.float32),
                      table.astype(jnp.float32))
