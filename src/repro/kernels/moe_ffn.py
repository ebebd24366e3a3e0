"""Pallas-TPU fused MoE expert FFN kernel (capacity layout).

Computes, per expert e and capacity-row block c:

    y[e, c, :] = (silu(x[e, c, :] @ w1[e]) * (x[e, c, :] @ w_up[e])) @ w2[e]

Grid (E, NC, NF) with the FFN-hidden axis innermost: each step loads one
(d, Bf) slice of w1/w_up and one (Bf, d) slice of w2 into VMEM, computes
the partial SwiGLU activation for the current token block, and
accumulates the down-projection into an fp32 VMEM scratch — the fused
three-matmul pattern keeps the (C, f) activation entirely out of HBM.
The matmuls run in the weights' dtype with fp32 accumulation.
VMEM per step, double-buffered: 2*(Bc*d (x) + 3*d*Bf (w1/w_up/w2) +
Bc*d (out)) + Bc*d fp32 (acc).  At Mixtral widths in bf16 (d=4096,
Bc=Bf=128) that is 12 MiB, inside the default 16 MiB scoped VMEM of a
TPU v5e; Bf=512 with fp32 upcasts needed about 30 MiB.

This is the compute hot-spot of the DMoE protocol's step 4 (expert FFN
inference); the dispatch/combine einsums stay in XLA where SPMD lowers
them to all-to-alls.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def row_block(n: int, target: int = 128) -> int:
    """Token-row block for the expert FFN kernels: ``target`` rows, or n
    rounded up to a multiple of 16 (the bf16 sublane tile) when n is
    smaller, so that a few decode tokens still form a legal TPU block.
    The capacity and ragged kernels both size their blocks here, which
    keeps their matmul shapes, and so their rows, identical."""
    return min(target, -(-n // 16) * 16)


def _mm(a, b):
    """(m, k) @ (k, n) on the MXU in the operands' own dtype (bf16 stays
    bf16), accumulated in fp32."""
    dt = jnp.promote_types(a.dtype, b.dtype)
    return jax.lax.dot_general(a.astype(dt), b.astype(dt),
                               (((1,), (0,)), ((), ())),
                               preferred_element_type=jnp.float32)


def swiglu_block(x, w1, wu, w2):
    """One (Bc, d) token block through one Bf-slice of the SwiGLU FFN,
    fp32 partial output.  Gate and up projections, the activation and
    their product are rounded to the weight dtype where the XLA einsum
    path (`models.moe._dispatch_ffn_xla`) rounds them, so that in bf16
    the two paths differ only in fp32 summation order."""
    dt = w1.dtype
    g = _mm(x, w1).astype(dt)                       # (Bc, Bf)
    u = _mm(x, wu).astype(dt)
    h = jax.nn.silu(g.astype(jnp.float32)).astype(dt) * u
    return _mm(h, w2)                               # (Bc, d) fp32


def _moe_ffn_kernel(x_ref, w1_ref, wu_ref, w2_ref, o_ref, acc_scr, *,
                    num_f_blocks: int):
    fi = pl.program_id(2)

    @pl.when(fi == 0)
    def _init():
        acc_scr[...] = jnp.zeros_like(acc_scr)

    acc_scr[...] += swiglu_block(x_ref[0], w1_ref[0], wu_ref[0], w2_ref[0])

    @pl.when(fi == num_f_blocks - 1)
    def _finalize():
        o_ref[0] = acc_scr[...].astype(o_ref.dtype)


def moe_expert_ffn(x, w1, w_up, w2, *, block_c: int = 128,
                   block_f: int = 128,
                   interpret: bool | None = None) -> jnp.ndarray:
    """x: (E, C, d); w1/w_up: (E, d, f); w2: (E, f, d) -> (E, C, d).

    ``interpret=None`` auto-detects the backend (interpret mode
    everywhere except a real TPU); pass an explicit bool to override.
    """
    from repro.kernels.moe_route import resolve_interpret
    interpret = resolve_interpret(interpret)
    e, c, d = x.shape
    f = w1.shape[-1]
    block_c = row_block(c, block_c)
    block_f = min(block_f, f)
    pc = (-c) % block_c
    pf = (-f) % block_f
    if pc:
        x = jnp.pad(x, ((0, 0), (0, pc), (0, 0)))
    if pf:
        w1 = jnp.pad(w1, ((0, 0), (0, 0), (0, pf)))
        w_up = jnp.pad(w_up, ((0, 0), (0, 0), (0, pf)))
        w2 = jnp.pad(w2, ((0, 0), (0, pf), (0, 0)))
    nc = (c + pc) // block_c
    nf = (f + pf) // block_f

    kernel = functools.partial(_moe_ffn_kernel, num_f_blocks=nf)
    out = pl.pallas_call(
        kernel,
        grid=(e, nc, nf),
        in_specs=[
            pl.BlockSpec((1, block_c, d), lambda ei, ci, fi: (ei, ci, 0)),
            pl.BlockSpec((1, d, block_f), lambda ei, ci, fi: (ei, 0, fi)),
            pl.BlockSpec((1, d, block_f), lambda ei, ci, fi: (ei, 0, fi)),
            pl.BlockSpec((1, block_f, d), lambda ei, ci, fi: (ei, fi, 0)),
        ],
        out_specs=pl.BlockSpec((1, block_c, d),
                               lambda ei, ci, fi: (ei, ci, 0)),
        out_shape=jax.ShapeDtypeStruct((e, c + pc, d), x.dtype),
        scratch_shapes=[pltpu.VMEM((block_c, d), jnp.float32)],
        interpret=interpret,
    )(x, w1, w_up, w2)
    return out[:, :c]
