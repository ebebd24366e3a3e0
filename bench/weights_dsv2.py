"""Weights of the DeepSeek-V2 configurations, made from the seed on the
device in the layout and types the program serves, and handed to the
program and to the plain reference alike (as `weights.py` does for the
MoE block).

The tree is the program's own for the chip's share of the deployment:
`DMoESimulator` holds only the routed experts of its `held_nodes`
(`serving/dmoe_sim.py::expert_share`), and every other weight whole.
The distribution is `weights.py`'s: fan-in scaled normals for the
projections, the router and the experts, N(0, 0.02) for the embedding
tables, ones for the norms.  A leaf is filled by its own jitted call,
one layer (or one block of table rows) at a time, so that no float32
copy of a whole stacked leaf is ever made: the share fills most of the
chip.
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

import weights

#: Fan-in axes of the leaves `weights.FAN_IN_AXES` does not know, for
#: stacked (layers, ...) weights: MLA's projections (the low-rank q and
#: kv, their up-projections), the dense and shared SwiGLUs.
FAN_IN_AXES = dict(weights.FAN_IN_AXES, **{
    "wq_a": (1,), "wq_b": (1,), "wkv_a": (1,), "wkv_b": (1,),
    "w_gate": (1,), "w_up": (1,), "w_down": (1,),
})
ONES = weights.NORMS + ("q_norm", "kv_norm")
TABLE_BLOCKS = 8            # a table is filled in this many row blocks


@partial(jax.jit, static_argnames=("shape", "dtype", "scale"))
def _normal(key, *, shape, dtype, scale):
    """N(0, scale^2) of `shape` in `dtype`, made a leading slice at a
    time (a layer of a stacked leaf, a block of a table's rows)."""
    blocks = shape[0] if len(shape) >= 3 else (
        TABLE_BLOCKS if shape[0] % TABLE_BLOCKS == 0 else 1)
    inner = (shape[0] // blocks,) * (len(shape) < 3) + tuple(shape[1:])

    def one(k):
        x = jax.random.normal(k, inner, dtype=jnp.float32) * scale
        return x.astype(dtype)

    out = jax.lax.map(one, jax.random.split(key, blocks))
    return out.reshape(shape)


def make_params(cfg, seed: int, held_nodes):
    """The program's parameter tree for `cfg`, holding the routed
    experts of `held_nodes`, filled from `seed` on the default device.
    An unknown leaf is an error, as in `weights.make_params`."""
    from repro.models import model as model_lib
    from repro.serving.dmoe_sim import expert_share

    group = cfg.moe.num_experts // cfg.moe.n_group
    held = slice(held_nodes[0] * group, (held_nodes[-1] + 1) * group)
    shapes = jax.eval_shape(lambda: expert_share(
        model_lib.init_params(jax.random.PRNGKey(0), cfg), held))
    leaves, treedef = jax.tree_util.tree_flatten_with_path(shapes)
    names = [weights._leaf_name(p) for p, _ in leaves]
    for name in names:
        if name not in FAN_IN_AXES and name not in ONES + weights.TABLES:
            raise KeyError(f"parameter {name!r} has no rule in "
                           "bench/weights_dsv2")
    keys = jax.random.split(jax.random.PRNGKey(seed), len(leaves))
    out = []
    for k, name, (_, sd) in zip(keys, names, leaves, strict=True):
        if name in ONES:
            out.append(jnp.ones(sd.shape, sd.dtype))
            continue
        if name in weights.TABLES:
            scale = 0.02
        else:
            scale = 1.0 / np.sqrt(int(np.prod(
                [sd.shape[a] for a in FAN_IN_AXES[name]])))
        out.append(_normal(k, shape=tuple(sd.shape), dtype=sd.dtype,
                           scale=float(scale)))
    return jax.block_until_ready(jax.tree_util.tree_unflatten(treedef, out))
