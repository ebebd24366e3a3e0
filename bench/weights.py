"""Weights made by the benchmark, on the device, in one jitted call from
the seed, in the layout and types the program serves.

The program would make its own weights eagerly, leaf by leaf, in float32
before casting; the benchmark makes them here instead and hands the same
arrays to the program and to the plain reference, so the reference takes
nothing the program made.  The distribution follows the program's own
initialisation: fan-in scaled normals for projections, N(0, 0.02) for the
embedding tables, ones for the norms.
"""

from __future__ import annotations

import contextlib

import numpy as np

#: For each projection, the axes of its stacked weight (the leading axis
#: is the layer) that are summed over in the forward pass: its fan-in.
FAN_IN_AXES = {
    "wq": (1,), "wk": (1,), "wv": (1,),   # (L, d, heads, head_dim)
    "wo": (1, 2),                          # (L, heads, head_dim, d)
    "w1": (2,), "wu": (2,),                # (L, E, d, f)
    "w2": (2,),                            # (L, E, f, d)
    "w_gate_router": (1,),                 # (L, d, E)
}
TABLES = ("embed", "unembed")
NORMS = ("norm1", "norm2", "final_norm")


def _leaf_name(path) -> str:
    return str(getattr(path[-1], "key", path[-1]))


def make_params(cfg, seed: int):
    """The program's parameter tree for `cfg`, filled from `seed` on the
    default device.  An unknown leaf is an error: the layout changed and
    the reference would no longer read it right."""
    import jax
    import jax.numpy as jnp
    from repro.models import model as model_lib

    shapes = jax.eval_shape(
        lambda: model_lib.init_params(jax.random.PRNGKey(0), cfg))
    leaves, treedef = jax.tree_util.tree_flatten_with_path(shapes)
    names = [_leaf_name(p) for p, _ in leaves]
    for name in names:
        if name not in FAN_IN_AXES and name not in TABLES + NORMS:
            raise KeyError(f"parameter {name!r} has no rule in bench/weights")

    def fill(key):
        keys = jax.random.split(key, len(leaves))
        out = []
        for k, name, (_, sd) in zip(keys, names, leaves):
            if name in NORMS:
                out.append(jnp.ones(sd.shape, sd.dtype))
                continue
            if name in TABLES:
                scale = 0.02
            else:
                fan_in = int(np.prod([sd.shape[a] for a in FAN_IN_AXES[name]]))
                scale = 1.0 / np.sqrt(fan_in)
            x = jax.random.normal(k, sd.shape, dtype=jnp.float32) * scale
            out.append(x.astype(sd.dtype))
        return jax.tree_util.tree_unflatten(treedef, out)

    params = jax.jit(fill)(jax.random.PRNGKey(seed))
    return jax.block_until_ready(params)


@contextlib.contextmanager
def program_weights(params):
    """While open, the program's `init_params` returns `params` instead
    of making its own, so a constructor that initialises its model
    serves the benchmark's weights."""
    from repro.models import model as model_lib

    made = model_lib.init_params

    def provided(key, cfg):
        return params

    model_lib.init_params = provided
    try:
        yield
    finally:
        model_lib.init_params = made
