"""jit'd public wrappers for the Pallas kernels.

`interpret` semantics: every wrapper defaults to `interpret=None`, which
resolves through `repro.kernels.moe_route.default_interpret` — interpret
mode (the kernel body executed op by op, for correctness checks)
everywhere except a TPU backend, where the same pallas_call lowers
through Mosaic.  An explicit bool overrides it per call.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.kernels.flash_attention import flash_attention as _flash
from repro.kernels.flash_decode import flash_decode as _flash_decode
from repro.kernels.moe_ffn import moe_expert_ffn as _moe_ffn
from repro.kernels.moe_route import fused_route as _fused_route
from repro.kernels.moe_route import resolve_interpret
from repro.kernels.rwkv_scan import wkv_chunked as _wkv


@functools.partial(jax.jit, static_argnames=("causal", "window", "block_q",
                                             "block_k", "interpret"))
def flash_attention(q, k, v, *, causal=True, window=0, block_q=128,
                    block_k=128, interpret=None):
    return _flash(q, k, v, causal=causal, window=window, block_q=block_q,
                  block_k=block_k, interpret=resolve_interpret(interpret))


@functools.partial(jax.jit, static_argnames=("block_c", "block_f",
                                             "interpret"))
def moe_expert_ffn(x, w1, w_up, w2, *, block_c=128, block_f=128,
                   interpret=None):
    return _moe_ffn(x, w1, w_up, w2, block_c=block_c, block_f=block_f,
                    interpret=interpret)


@functools.partial(jax.jit, static_argnames=("top_k", "block_t",
                                             "interpret"))
def fused_route(gate_logits, policy_mask=None, *, top_k=2, block_t=128,
                interpret=None):
    return _fused_route(gate_logits, policy_mask, top_k=top_k,
                        block_t=block_t, interpret=interpret)


@functools.partial(jax.jit, static_argnames=("chunk", "interpret"))
def wkv_chunked(r, k, v, w, u, *, chunk=32, interpret=None):
    return _wkv(r, k, v, w, u, chunk=chunk,
                interpret=resolve_interpret(interpret))


@functools.partial(jax.jit, static_argnames=("window", "block_k",
                                             "interpret"))
def flash_decode(q, k, v, lengths, *, window=0, block_k=512,
                 interpret=None):
    return _flash_decode(q, k, v, lengths, window=window, block_k=block_k,
                         interpret=resolve_interpret(interpret))
