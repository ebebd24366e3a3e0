"""Pallas fused routing kernel family — gate → mask → top-k → dispatch.

The protocol's step-4 hot path (`repro.models.moe.moe_ffn`) historically
composed routing out of plain XLA ops: the policy ``route_mask`` feeds a
softmax + renormalize, then one-hot (G, gsz, E, cap) dispatch/combine
einsums round-trip the activations through HBM.  This module fuses that
pipeline into three Pallas kernels plus an alternative token layout:

  ``fused_route``           softmax + policy-mask + (optional) top-k +
                            Eq.-8 renormalized combine weights in one
                            VMEM pass over token blocks.  Any in-graph
                            policy mask (des-greedy, dense,
                            channel-aware, siftmoe) feeds in as the
                            ``policy_mask`` input, so the whole registry
                            composes; with ``policy_mask=None`` the
                            plain top-k mask (stable-tie semantics of
                            `repro.core.selection.topk_mask`) is
                            computed in-kernel from the gates.
  ``capacity_dispatch``     gather tokens straight into the per-expert
                            capacity layout (E, G, cap, d) — the
                            (G, gsz, E, cap) one-hot tensor is never
                            materialized.
  ``capacity_combine``      weighted scatter-back (E, G, cap, d) →
                            (G, gsz, d), accumulating expert
                            contributions ascending-e in an fp32
                            scratch.
  ``grouped_layout`` +      the grouped/ragged alternative: tokens
  ``moe_expert_ffn_ragged``  sorted by expert id into block-aligned
  + ``grouped_scatter``      per-expert segments, FFN'd by a
                            scalar-prefetch Pallas kernel whose
                            block→expert ``index_map`` walks the ragged
                            offsets, and scattered back bit-identically
                            to the capacity path.

Bit-contract: for the same (mask, pos, keep, combine) inputs the grouped
pipeline's scatter-back output is BIT-EQUAL to the capacity pipeline's
``capacity_combine`` output — both accumulate per-token expert
contributions in fp32, ascending expert id, and both run the SwiGLU
block matmuls at identical (block_c, d) × (d, block_f) shapes (the
per-row results of a fixed-shape matmul depend only on the row).  The
differential harness in `tests/test_moe_route.py` enforces this.

``interpret`` resolution: every public entry point takes
``interpret=None`` and resolves it via `default_interpret()` — interpret
mode everywhere except a real TPU backend, overridable per call.
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.moe_ffn import row_block, swiglu_block

#: The `MoEConfig.routing_impl` vocabulary: "xla" is the historical
#: einsum path (byte-for-byte unchanged default), "fused" the capacity-
#: layout Pallas pipeline, "grouped" the ragged-layout pipeline.
ROUTING_IMPLS = ("xla", "fused", "grouped")


def available_routing_impls() -> Tuple[str, ...]:
    return ROUTING_IMPLS


def check_routing_impl(name: str) -> str:
    if name not in ROUTING_IMPLS:
        raise ValueError(
            f"unknown routing_impl {name!r}; expected one of "
            f"{ROUTING_IMPLS}")
    return name


def default_interpret() -> bool:
    """Pallas backend auto-detection: interpret mode everywhere except a
    real TPU (Mosaic) backend.  CPU CI therefore always interprets; a
    TPU host lowers for real.  Every kernel entry point accepts an
    explicit ``interpret=`` override."""
    return jax.default_backend() != "tpu"


def resolve_interpret(interpret: Optional[bool]) -> bool:
    """A kernel entry point's ``interpret=``: None means
    `default_interpret()`, a bool is taken as given."""
    return default_interpret() if interpret is None else bool(interpret)


# ----------------------------------------------------------------------
# (a) fused gate → mask → top-k → combine weights
# ----------------------------------------------------------------------

def _rank_lt_k(gates_masked: jnp.ndarray, k: int) -> jnp.ndarray:
    """Stable-tie top-k membership, replicating
    `repro.core.selection.topk_mask` semantics in-kernel: expert j is
    kept iff fewer than k experts strictly beat it (ties broken by
    lower index)."""
    e = gates_masked.shape[-1]
    gi = gates_masked[:, :, None]          # candidate i
    gj = gates_masked[:, None, :]          # slot j
    idx = jax.lax.broadcasted_iota(jnp.int32, (e, e), 0)
    jdx = jax.lax.broadcasted_iota(jnp.int32, (e, e), 1)
    beats = (gi > gj) | ((gi == gj) & (idx[None] < jdx[None]))
    ranks = jnp.sum(beats.astype(jnp.int32), axis=1)
    return ranks < k


def _fused_route_kernel(lg_ref, pm_ref, cb_ref, mk_ref, *, top_k: int,
                        use_policy_mask: bool):
    lg = lg_ref[...].astype(jnp.float32)                    # (Bt, E)
    mx = jnp.max(lg, axis=-1, keepdims=True)
    ex = jnp.exp(lg - mx)
    gates = ex / jnp.sum(ex, axis=-1, keepdims=True)        # softmax
    if use_policy_mask:
        mk = (pm_ref[...].astype(jnp.float32) > 0).astype(jnp.float32)
    else:
        mk = _rank_lt_k(gates, top_k).astype(jnp.float32)
    cb = mk * gates
    cb = cb / (jnp.sum(cb, axis=-1, keepdims=True) + 1e-9)
    cb_ref[...] = cb
    mk_ref[...] = mk


def fused_route(gate_logits: jnp.ndarray,
                policy_mask: Optional[jnp.ndarray] = None, *,
                top_k: int = 2, block_t: int = 128,
                interpret: Optional[bool] = None
                ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Fused softmax + mask + top-k + Eq.-8 renormalize.

    gate_logits: (T, E); policy_mask: (T, E) {0,1} from any registry
    policy's ``route_mask`` (None → in-kernel top-k over the gates).
    Returns (combine (T, E) f32, mask (T, E) f32) matching
    `repro.core.selection.route` on the same mask.
    """
    t, e = gate_logits.shape
    interpret = resolve_interpret(interpret)
    block_t = min(block_t, t)
    pt = (-t) % block_t
    lg = gate_logits
    pm = policy_mask if policy_mask is not None else jnp.zeros_like(
        gate_logits)
    if pt:
        lg = jnp.pad(lg, ((0, pt), (0, 0)))
        pm = jnp.pad(pm, ((0, pt), (0, 0)))
    nt = (t + pt) // block_t
    kernel = functools.partial(
        _fused_route_kernel, top_k=top_k,
        use_policy_mask=policy_mask is not None)
    cb, mk = pl.pallas_call(
        kernel,
        grid=(nt,),
        in_specs=[
            pl.BlockSpec((block_t, e), lambda ti: (ti, 0)),
            pl.BlockSpec((block_t, e), lambda ti: (ti, 0)),
        ],
        out_specs=[
            pl.BlockSpec((block_t, e), lambda ti: (ti, 0)),
            pl.BlockSpec((block_t, e), lambda ti: (ti, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((t + pt, e), jnp.float32),
            jax.ShapeDtypeStruct((t + pt, e), jnp.float32),
        ],
        interpret=interpret,
    )(lg, pm)
    return cb[:t], mk[:t]


# ----------------------------------------------------------------------
# capacity positions (shared by both layouts)
# ----------------------------------------------------------------------

def capacity_positions(mask: jnp.ndarray, cap: int
                       ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Per-(group, expert) capacity slots for each selected token.

    mask: (G, gsz, E) {0,1}.  Returns (pos int32 (G, gsz, E) clipped to
    [0, cap), keep f32 (G, gsz, E)) where ``keep`` zeroes overflow
    tokens — the token-drop rule both layouts share.
    """
    mk = mask.astype(jnp.float32)
    pos = jnp.cumsum(mk, axis=1) * mk - 1.0
    keep = ((pos >= 0) & (pos < cap)).astype(jnp.float32) * mk
    pos = jnp.clip(pos, 0, cap - 1).astype(jnp.int32)
    return pos, keep


# ----------------------------------------------------------------------
# (b) capacity layout: fused gather-dispatch + weighted combine
# ----------------------------------------------------------------------

def _lane_block(d: int, target: int = 1024) -> int:
    """Hidden-axis block for the dispatch/combine kernels: ``target``
    lanes where it divides d, else the whole axis (legal either way
    under the TPU (8, 128) tiling rule)."""
    return target if d % target == 0 else d


def _rows32(a: jnp.ndarray) -> jnp.ndarray:
    """Widen a sub-32-bit float array to fp32 (exact for bf16/f16).
    Mosaic indexes single rows dynamically only in 32-bit layouts; a
    packed bf16 row at a runtime offset is refused."""
    return a.astype(jnp.float32) if a.dtype.itemsize < 4 else a


def _group_table(a: jnp.ndarray, dtype) -> jnp.ndarray:
    """(G, gsz, E) slot bookkeeping → a (G, 1, gsz*E) table whose
    per-group block the kernels read one scalar at a time from SMEM;
    entry (g, s, e) sits at [g, 0, s*E + e].  Vector blocks over an
    E-wide last axis are not legal TPU tiles, and one group's block
    keeps SMEM use independent of G."""
    g, gsz, e = a.shape
    return a.reshape(g, 1, gsz * e).astype(dtype)


def _table_spec(gsz: int, e: int, group_axis: int) -> pl.BlockSpec:
    return pl.BlockSpec((1, 1, gsz * e),
                        lambda *ids: (ids[group_axis], 0, 0),
                        memory_space=pltpu.SMEM)


def _dispatch_kernel(pos_ref, keep_ref, x_ref, o_ref, *, num_e: int):
    ei = pl.program_id(0)
    gsz = x_ref.shape[1]
    o_ref[...] = jnp.zeros_like(o_ref)

    def body(s, carry):
        idx = s * num_e + ei

        @pl.when(keep_ref[0, 0, idx] > 0)
        def _():
            o_ref[0, 0, pl.ds(pos_ref[0, 0, idx], 1), :] = (
                x_ref[0, pl.ds(s, 1), :])
        return carry

    jax.lax.fori_loop(0, gsz, body, 0)


def capacity_dispatch(x: jnp.ndarray, pos: jnp.ndarray, keep: jnp.ndarray,
                      cap: int, *, interpret: Optional[bool] = None
                      ) -> jnp.ndarray:
    """Gather-dispatch (G, gsz, d) → (E, G, cap, d) without the one-hot.

    Each (expert, group, hidden-block) program walks its group's tokens
    once, writing kept rows straight into their capacity slot — HBM
    traffic is O(E·G·cap·d) instead of the einsum's O(G·gsz·E·cap)
    one-hot.  The slot tables ride in SMEM; rows move as 32-bit words
    (`_rows32`), so the copy stays bit-exact.
    """
    g, gsz, d = x.shape
    e = pos.shape[-1]
    bd = _lane_block(d)
    interpret = resolve_interpret(interpret)
    x32 = _rows32(x)
    out = pl.pallas_call(
        functools.partial(_dispatch_kernel, num_e=e),
        grid=(e, g, d // bd),
        in_specs=[
            _table_spec(gsz, e, 1),
            _table_spec(gsz, e, 1),
            pl.BlockSpec((1, gsz, bd), lambda ei, gi, di: (gi, 0, di)),
        ],
        out_specs=pl.BlockSpec((1, 1, cap, bd),
                               lambda ei, gi, di: (ei, gi, 0, di)),
        out_shape=jax.ShapeDtypeStruct((e, g, cap, d), x32.dtype),
        interpret=interpret,
    )(_group_table(pos, jnp.int32), _group_table(keep > 0, jnp.int32), x32)
    return out.astype(x.dtype)


def _combine_kernel(pos_ref, keep_ref, cw_ref, ye_ref, o_ref, acc_scr, *,
                    num_e: int):
    ei = pl.program_id(2)

    @pl.when(ei == 0)
    def _init():
        acc_scr[...] = jnp.zeros_like(acc_scr)

    gsz = acc_scr.shape[0]

    def body(s, carry):
        idx = s * num_e + ei

        @pl.when(keep_ref[0, 0, idx] > 0)
        def _():
            # bare multiply feeding the accumulate: XLA contracts the
            # pair into an FMA; `grouped_scatter` goes through this same
            # kernel, so both layouts contract the same way
            # (bit-equality contract — a `where`/barrier between the
            # two ops would block contraction on one side only).
            acc_scr[pl.ds(s, 1), :] += (
                cw_ref[0, 0, idx]
                * ye_ref[0, 0, pl.ds(pos_ref[0, 0, idx], 1), :])
        return carry

    jax.lax.fori_loop(0, gsz, body, 0)

    @pl.when(ei == num_e - 1)
    def _finalize():
        o_ref[0] = acc_scr[...].astype(o_ref.dtype)


def capacity_combine(ye: jnp.ndarray, cw: jnp.ndarray, pos: jnp.ndarray,
                     keep: jnp.ndarray, *, out_dtype=None,
                     interpret: Optional[bool] = None) -> jnp.ndarray:
    """Weighted combine (E, G, cap, d) → (G, gsz, d).

    Accumulates each token's selected-expert contributions in an fp32
    scratch, expert ids ascending (the grid's inner axis) — the
    accumulation order the grouped layout's scatter-back replays for
    bit-equality.  Slot tables and combine weights ride in SMEM.
    """
    e, g, cap, d = ye.shape
    gsz = cw.shape[1]
    bd = _lane_block(d)
    interpret = resolve_interpret(interpret)
    out_dtype = out_dtype or ye.dtype
    return pl.pallas_call(
        functools.partial(_combine_kernel, num_e=e),
        grid=(g, d // bd, e),
        in_specs=[
            _table_spec(gsz, e, 0),
            _table_spec(gsz, e, 0),
            _table_spec(gsz, e, 0),
            pl.BlockSpec((1, 1, cap, bd), lambda gi, di, ei: (ei, gi, 0, di)),
        ],
        out_specs=pl.BlockSpec((1, gsz, bd), lambda gi, di, ei: (gi, 0, di)),
        out_shape=jax.ShapeDtypeStruct((g, gsz, d), out_dtype),
        scratch_shapes=[pltpu.VMEM((gsz, bd), jnp.float32)],
        interpret=interpret,
    )(_group_table(pos, jnp.int32), _group_table(keep > 0, jnp.int32),
      _group_table(cw, jnp.float32), _rows32(ye))


# ----------------------------------------------------------------------
# (c) grouped / ragged layout
# ----------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class GroupedLayout:
    """Static-shape ragged token layout: tokens sorted by expert id.

    ``dest`` (G, gsz, E) int32 — row of each kept (token, expert) pair
    inside the flat ``(total, d)`` buffer; overflow/unselected pairs
    point at the buffer's trailing scratch row.  ``block_expert`` /
    ``block_active`` ((num_blocks,) int32) drive the ragged FFN kernel's
    block→expert ``index_map`` via scalar prefetch; ``offsets`` ((E,)
    int32) are the block-aligned per-expert segment starts and
    ``counts`` ((E,) int32) the live rows per expert.
    """

    dest: jnp.ndarray
    offsets: jnp.ndarray
    counts: jnp.ndarray
    block_expert: jnp.ndarray
    block_active: jnp.ndarray
    total: int
    block_c: int
    seg_pad: int
    cap: int


def grouped_layout(pos: jnp.ndarray, keep: jnp.ndarray, cap: int,
                   *, block_c: int = 128) -> GroupedLayout:
    """Build the ragged layout from the shared capacity bookkeeping.

    Per-expert segments start at static worst-case, block-aligned
    offsets (an expert can receive at most G·cap kept rows), so every
    FFN block belongs to exactly one expert while row indices stay
    static-shaped under jit.  Row order within an expert is (group,
    slot) — exactly the capacity layout flattened — which is what makes
    the two layouts' FFN inputs row-for-row identical.
    """
    g, gsz, e = pos.shape
    seg = g * cap                      # worst-case kept rows per expert
    block_c = row_block(seg, block_c)
    seg_pad = seg + ((-seg) % block_c)
    total = e * seg_pad + block_c      # + trailing scratch block
    # kept (token, expert) pair → expert-major row: e·seg_pad + g·cap + slot
    gi = jnp.arange(g, dtype=jnp.int32)[:, None, None]
    ei = jnp.arange(e, dtype=jnp.int32)[None, None, :]
    dest = ei * seg_pad + gi * cap + pos
    dest = jnp.where(keep > 0, dest, total - block_c)   # parked in scratch
    counts = jnp.sum(keep > 0, axis=(0, 1)).astype(jnp.int32)
    offsets = (jnp.arange(e, dtype=jnp.int32) * seg_pad)
    nb = total // block_c
    block_start = jnp.arange(nb, dtype=jnp.int32) * block_c
    block_expert = jnp.minimum(block_start // seg_pad, e - 1)
    # a block is live iff any of its rows can hold a kept token: row
    # (g·cap + slot) < g·cap ⇒ the block must start below its expert's
    # used span (G·cap rows); the scratch tail block is always dead.
    block_active = ((block_start - block_expert * seg_pad < seg)
                    & (block_start < e * seg_pad)).astype(jnp.int32)
    return GroupedLayout(dest=dest, offsets=offsets, counts=counts,
                         block_expert=block_expert,
                         block_active=block_active, total=total,
                         block_c=block_c, seg_pad=seg_pad, cap=cap)


def grouped_dispatch(x: jnp.ndarray, layout: GroupedLayout) -> jnp.ndarray:
    """Scatter (G, gsz, d) tokens into the flat grouped buffer
    (total, d).  A plain XLA scatter — the data volume equals the kept
    rows, no one-hot blowup — feeding `moe_expert_ffn_ragged`."""
    g, gsz, d = x.shape
    e = layout.dest.shape[-1]
    flat_dest = layout.dest.reshape(-1)                    # (G·gsz·E,)
    rows = jnp.broadcast_to(x[:, :, None, :], (g, gsz, e, d)).reshape(
        -1, d)
    buf = jnp.zeros((layout.total, d), dtype=x.dtype)
    return buf.at[flat_dest].set(rows, mode="drop")


def _ragged_ffn_kernel(be_ref, act_ref, x_ref, w1_ref, wu_ref, w2_ref,
                       o_ref, acc_scr, *, num_f_blocks: int):
    bi = pl.program_id(0)
    fi = pl.program_id(1)

    @pl.when(fi == 0)
    def _init():
        acc_scr[...] = jnp.zeros_like(acc_scr)

    @pl.when(act_ref[bi] > 0)
    def _compute():
        acc_scr[...] += swiglu_block(x_ref[...], w1_ref[0], wu_ref[0],
                                     w2_ref[0])

    @pl.when(fi == num_f_blocks - 1)
    def _finalize():
        o_ref[...] = acc_scr[...].astype(o_ref.dtype)


def moe_expert_ffn_ragged(xs: jnp.ndarray, layout: GroupedLayout,
                          w1: jnp.ndarray, w_up: jnp.ndarray,
                          w2: jnp.ndarray, *, block_f: int = 128,
                          interpret: Optional[bool] = None) -> jnp.ndarray:
    """Ragged SwiGLU expert FFN over the grouped layout.

    xs: (total, d) grouped buffer; w1/w_up: (E, d, f); w2: (E, f, d).
    The block→expert mapping rides in as a scalar-prefetch operand so
    each (block, f-block) program pulls exactly its expert's weight
    slice; dead blocks (`block_active == 0`, i.e. segment padding and
    the scratch tail) skip the matmuls entirely — the ragged win over
    the dense capacity grid when loads are skewed.  Matmul block shapes
    match `repro.kernels.moe_ffn.moe_expert_ffn` at equal
    block_c/block_f, which is what makes the two layouts bit-comparable.
    """
    total, d = xs.shape
    f = w1.shape[-1]
    block_c = layout.block_c
    interpret = resolve_interpret(interpret)
    block_f = min(block_f, f)
    pf = (-f) % block_f
    if pf:
        w1 = jnp.pad(w1, ((0, 0), (0, 0), (0, pf)))
        w_up = jnp.pad(w_up, ((0, 0), (0, 0), (0, pf)))
        w2 = jnp.pad(w2, ((0, 0), (0, pf), (0, 0)))
    nb = total // block_c
    nf = (f + pf) // block_f
    kernel = functools.partial(_ragged_ffn_kernel, num_f_blocks=nf)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(nb, nf),
        in_specs=[
            pl.BlockSpec((block_c, d), lambda bi, fi, be, act: (bi, 0)),
            pl.BlockSpec((1, d, block_f),
                         lambda bi, fi, be, act: (be[bi], 0, fi)),
            pl.BlockSpec((1, d, block_f),
                         lambda bi, fi, be, act: (be[bi], 0, fi)),
            pl.BlockSpec((1, block_f, d),
                         lambda bi, fi, be, act: (be[bi], fi, 0)),
        ],
        out_specs=pl.BlockSpec((block_c, d),
                               lambda bi, fi, be, act: (bi, 0)),
        scratch_shapes=[pltpu.VMEM((block_c, d), jnp.float32)],
    )
    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((total, d), xs.dtype),
        interpret=interpret,
    )(layout.block_expert, layout.block_active, xs, w1, w_up, w2)


def grouped_scatter(ys: jnp.ndarray, layout: GroupedLayout,
                    cw: jnp.ndarray, pos: jnp.ndarray, keep: jnp.ndarray,
                    *, out_dtype=None,
                    interpret: Optional[bool] = None) -> jnp.ndarray:
    """Scatter-back (total, d) → (G, gsz, d), BIT-EQUAL to the capacity
    path by construction: within an expert's segment, rows sit at
    ``g·cap + slot`` — the capacity layout flattened — so the grouped
    buffer is view-reshaped back to (E, G, cap, d) (pure data movement,
    no arithmetic) and the weighted accumulate runs through the SAME
    `capacity_combine` kernel.  Any float-contraction choice XLA makes
    is therefore shared between layouts instead of merely mirrored."""
    g, gsz, e = cw.shape
    d = ys.shape[-1]
    ye = ys[:e * layout.seg_pad].reshape(e, layout.seg_pad, d)
    ye = ye[:, :g * layout.cap].reshape(e, g, layout.cap, d)
    return capacity_combine(ye, cw, pos, keep, out_dtype=out_dtype,
                            interpret=interpret)
