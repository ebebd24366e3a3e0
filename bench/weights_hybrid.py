"""Weights of the hybrid (Mamba/attention) configurations, made as
`weights.py` makes the MoE block's: on the device, in one jitted call
from the seed, in the layout and types the program serves, handed to
the program and to the plain reference alike.

The distribution is `weights.py`'s (fan-in scaled normals for the
projections, N(0, 0.02) for the embedding tables, ones for the norms),
extended to the leaves the Mamba mixer and the dense SwiGLU add.  Those
with a fixed initial value in the program (the convolution's bias, dt's
bias, A and D) take that value.  Each leaf is made straight into the
sharding the caller gives (`shardings(shapes)` -> a tree like it), so a
weight split over chips is never whole on one.
"""

from __future__ import annotations

import numpy as np

import weights

#: Fan-in axes of the leaves `weights.FAN_IN_AXES` does not know, for
#: stacked (period, ...) weights: Mamba's projections and depthwise
#: convolution (period, d_conv, d_inner), the dense SwiGLU.
FAN_IN_AXES = dict(weights.FAN_IN_AXES, **{
    "w_in": (1,), "w_bcdt": (1,), "w_dt": (1,), "w_out": (1,),
    "conv_w": (1,),
    "w_gate": (1,), "w_up": (1,), "w_down": (1,),
})
ONES = weights.NORMS + ("dt_norm", "b_norm", "c_norm", "d_skip")
ZEROS = ("conv_b",)
DT_BIAS = -4.0          # softplus(-4) ~ 0.018, the program's dt bias


def make_params(cfg, seed: int, shardings=None):
    """The program's parameter tree for `cfg`, filled from `seed`, each
    leaf placed as `shardings(shapes)` says (default device if None).
    An unknown leaf is an error, as in `weights.make_params`."""
    import jax
    import jax.numpy as jnp
    from repro.models import model as model_lib

    shapes = jax.eval_shape(
        lambda: model_lib.init_params(jax.random.PRNGKey(0), cfg))
    leaves, treedef = jax.tree_util.tree_flatten_with_path(shapes)
    names = [weights._leaf_name(p) for p, _ in leaves]
    special = ONES + ZEROS + weights.TABLES + ("dt_bias", "a_log")
    for name in names:
        if name not in FAN_IN_AXES and name not in special:
            raise KeyError(f"parameter {name!r} has no rule in "
                           "bench/weights_hybrid")

    def fill(key):
        keys = jax.random.split(key, len(leaves))
        out = []
        for k, name, (_, sd) in zip(keys, names, leaves):
            if name in ONES:
                x = jnp.ones(sd.shape, jnp.float32)
            elif name in ZEROS:
                x = jnp.zeros(sd.shape, jnp.float32)
            elif name == "dt_bias":
                x = jnp.full(sd.shape, DT_BIAS, jnp.float32)
            elif name == "a_log":             # (..., d_inner, d_state)
                n = sd.shape[-1]
                x = jnp.broadcast_to(
                    jnp.log(jnp.arange(1, n + 1, dtype=jnp.float32)),
                    sd.shape)
            else:
                if name in weights.TABLES:
                    scale = 0.02
                else:
                    fan_in = int(np.prod([sd.shape[a]
                                          for a in FAN_IN_AXES[name]]))
                    scale = 1.0 / np.sqrt(fan_in)
                x = jax.random.normal(k, sd.shape, dtype=jnp.float32) * scale
            out.append(x.astype(sd.dtype))
        return jax.tree_util.tree_unflatten(treedef, out)

    out_shardings = None if shardings is None else shardings(shapes)
    params = jax.jit(fill, out_shardings=out_shardings)(
        jax.random.PRNGKey(seed))
    return jax.block_until_ready(params)
