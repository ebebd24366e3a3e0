"""Jax-traceable DES pre-work — the vectorized front half of Algorithm 1.

`repro.core.des.des_select_batch` runs four pure-numpy passes before its
frontier-parallel branch-and-bound ever dequeues a node:

  1. sanitize      — clamp +inf selection costs to the `_BIG` sentinel;
  2. screen        — the Remark-2 feasibility test (can the Top-D experts
                     by score cover the QoS threshold?) plus the Top-D
                     fallback selection for rows that fail it;
  3. ratio sort    — order experts by energy-to-score ratio e_j/t_j
                     descending (the paper's branch order);
  4. greedy seed   — the integral incumbent: exclude greedily while C1
                     holds, keep the rest (Algorithm 1's warm start).

This module re-implements those passes as a single jit-able jax function
(`prework`) so they can run device-sharded (`repro.schedulers.sharded`
wraps it in `shard_map` over the batch axis) — and it goes one step
further: it also evaluates the root Eq. 11-12 LP bound in-graph, so
instances whose greedy seed already matches the LP bound ("easy"
instances — the bound proves the seed optimal, the sequential solver
prunes its root node immediately) are *resolved entirely in-graph*.
Only the hard residual ever reaches the host B&B.

Bit-identity contract
---------------------
Every decision this module makes (feasibility comparisons, sort order,
greedy exclusions, the root-prune test) must equal `des_select_batch`'s
numpy decisions bit-for-bit, because the sharded front-end's results are
asserted identical to the host solver (tests/test_sharded.py).  Floating-
point addition is not associative, so equality of the comparisons demands
equality of the *accumulation order*:

  * `np_pairwise_sum` reproduces numpy's pairwise summation (the exact
    8-accumulator/128-block association of `np.add.reduce`) as an
    unrolled jax expression tree — XLA does not reassociate floats, so
    the jitted sums are bit-identical to `np.sum`;
  * the greedy-seed scan and the Eq. 11-12 bound pass are unrolled
    per-expert-position loops matching the numpy column scans of
    `des_select_batch` operation for operation;
  * the seed energy uses the same add-0.0 column scan as
    `des._masked_row_sums`'s small-count path; seeds with >= 8 selected
    experts (only possible when D >= 8) are conservatively classified
    hard rather than replicating numpy's data-dependent compressed sum.

Everything runs in float64 — callers must invoke the jitted function
under `jax.enable_x64(True)` (see `repro.schedulers.sharded`).
"""

from __future__ import annotations

import functools
from typing import Dict, Sequence

import jax
import jax.numpy as jnp

from repro.core.des import _BIG

# `_masked_row_sums` switches from the exact column scan to numpy's
# data-dependent compressed sum at this count; seeds at or past it are
# classified hard (host-solved) instead of risking a divergent energy.
_SMALL_SUM = 8


def np_pairwise_sum(cols: Sequence[jnp.ndarray]) -> jnp.ndarray:
    """Sum a sequence of same-shape arrays in numpy's `np.sum` order.

    Replicates numpy's pairwise summation (numpy/core/src/umath/loops:
    `pairwise_sum_@TYPE@`): sequential below 8 terms, the 8-accumulator
    unrolled block up to 128 terms, recursive halving (rounded down to a
    multiple of 8) above.  Emitted as an unrolled expression tree, which
    XLA will not reassociate — so the jitted result is bit-identical to
    `np.sum` / `ndarray.sum(axis=-1)` over the same values.
    """
    n = len(cols)
    if n == 0:
        raise ValueError("np_pairwise_sum needs at least one column")
    if n < 8:
        acc = jnp.zeros_like(cols[0])
        for c in cols:
            acc = acc + c
        return acc
    if n <= 128:
        r = list(cols[:8])
        i = 8
        while i < n - (n % 8):
            for j in range(8):
                r[j] = r[j] + cols[i + j]
            i += 8
        res = ((r[0] + r[1]) + (r[2] + r[3])) + ((r[4] + r[5]) + (r[6] + r[7]))
        for idx in range(i, n):
            res = res + cols[idx]
        return res
    n2 = n // 2
    n2 -= n2 % 8
    return np_pairwise_sum(cols[:n2]) + np_pairwise_sum(cols[n2:])


def np_row_sum(x: jnp.ndarray) -> jnp.ndarray:
    """(B, K) -> (B,) row sums in numpy's accumulation order."""
    k = x.shape[1]
    if k == 0:
        return jnp.zeros(x.shape[0], dtype=x.dtype)
    return np_pairwise_sum([x[:, i] for i in range(k)])


def sanitize_costs(e_raw: jnp.ndarray) -> jnp.ndarray:
    """`des._sanitize`, batched: clamp non-finite costs to `_BIG`."""
    return jnp.minimum(jnp.where(jnp.isfinite(e_raw), e_raw, _BIG), _BIG)


def partly_reachable(e_raw: jnp.ndarray, forced: jnp.ndarray) -> jnp.ndarray:
    """`des._partly_reachable`: rows with finite and non-finite costs and
    no forced non-finite one.  The host prices their unreachable experts
    (`des._price_unreachable`), so they are never resolved in-graph."""
    fin = jnp.isfinite(e_raw)
    return fin.any(axis=1) & ~fin.all(axis=1) & ~(forced & ~fin).any(axis=1)


def _top_d_score(t: jnp.ndarray, d: int) -> jnp.ndarray:
    """Remark-2 screen statistic: sum of the D highest scores per row,
    accumulated exactly as `np.sort(t, axis=1)[:, ::-1][:, :d].sum(axis=1)`."""
    dd = min(d, t.shape[1])
    if dd <= 0:
        return jnp.zeros(t.shape[0], dtype=t.dtype)
    desc = jnp.sort(t, axis=1)[:, ::-1]
    return np_pairwise_sum([desc[:, i] for i in range(dd)])


def _root_bound(ts: jnp.ndarray, es: jnp.ndarray, z: jnp.ndarray,
                tt0: jnp.ndarray, ee0: jnp.ndarray) -> jnp.ndarray:
    """Eq. 11-12 LP bound at the root node, for all rows at once.

    Mirrors `des._node_bound_batch(0, ...)` operation for operation:
    greedily exclude ratio-sorted experts while C1 holds, then remove
    the critical expert fractionally.  `ts`/`es` are the sorted tables,
    `tt0`/`ee0` the all-included totals (numpy-order row sums).
    """
    k = ts.shape[1]
    score, energy = tt0, ee0
    live = jnp.ones(ts.shape[0], dtype=bool)
    for idx in range(k):
        tj, ej = ts[:, idx], es[:, idx]
        rem = score - tj
        exc = live & (rem >= z)
        crit = live & ~exc
        score = jnp.where(exc, rem, score)
        energy = jnp.where(exc, energy - ej, energy)
        frac = (score - z) * ej / jnp.where(tj > 0, tj, 1.0)
        energy = jnp.where(crit & (tj > 0), energy - frac, energy)
        live = exc
    return energy


def prework(scores: jnp.ndarray, costs: jnp.ndarray, qos: jnp.ndarray,
            forced: jnp.ndarray, *, max_experts: int
            ) -> Dict[str, jnp.ndarray]:
    """The full pre-work pipeline for a (B, K) instance batch.

    Args:
      scores: (B, K) float64 gate scores t_j.
      costs:  (B, K) float64 raw selection costs (inf = unreachable).
      qos:    (B,)  float64 per-instance threshold z * gamma^(l).
      forced: (B, K) bool must-select mask.
      max_experts: D (static).

    Returns a dict of per-row arrays (all in ORIGINAL expert order):
      infeasible      (B,)  bool — Remark-2 screen failed;
      all_unreachable (B,)  bool — every raw cost was non-finite;
      partial         (B,)  bool — `partly_reachable`: left to the host;
      fallback_sel    (B, K) bool — Top-D-by-score fallback selection
                      (valid for infeasible rows without forced experts);
      easy            (B,)  bool — feasible, not `partial`, greedy seed
                      integral within budget, and the root LP bound
                      proves it optimal (the B&B would prune its root
                      node immediately);
      easy_sel        (B, K) bool — the seed selection for easy rows;
      seed_energy     (B,)  float64 — incumbent energy (diagnostics);
      root_bound      (B,)  float64 — root LP bound (diagnostics).
    """
    t = scores.astype(jnp.float64)
    e_raw = costs.astype(jnp.float64)
    z = qos.astype(jnp.float64)
    b, k = t.shape
    d = int(max_experts)

    e = sanitize_costs(e_raw)
    all_unreachable = ~jnp.isfinite(e_raw).any(axis=1)
    partial = partly_reachable(e_raw, forced)

    # ---- Remark-2 feasibility screen + Top-D fallback ------------------
    top_d_score = _top_d_score(t, d)
    forced_count = forced.sum(axis=1)
    infeasible = (top_d_score < z) | (d < forced_count) | all_unreachable
    order_by_score = jnp.argsort(-t, axis=1, stable=True)
    rank = jnp.argsort(order_by_score, axis=1, stable=True)
    fallback_sel = rank < min(d, k)

    # ---- ratio sort (paper's branch order) -----------------------------
    ratio = jnp.where(t > 0, e / jnp.maximum(t, 1e-300), jnp.inf)
    order = jnp.argsort(-ratio, axis=1, stable=True)
    ts = jnp.take_along_axis(t, order, axis=1)
    es = jnp.take_along_axis(e, order, axis=1)
    forced_s = jnp.take_along_axis(forced, order, axis=1)

    # ---- greedy integral incumbent seed (unrolled exact scan) ----------
    tt0 = np_row_sum(ts)
    ee0 = np_row_sum(es)
    g_score = tt0
    g_cols = []
    for idx in range(k):
        can = ~forced_s[:, idx] & (g_score - ts[:, idx] >= z)
        g_cols.append(~can)
        g_score = jnp.where(can, g_score - ts[:, idx], g_score)
    g_sel = (jnp.stack(g_cols, axis=1) if g_cols
             else jnp.zeros((b, 0), dtype=bool))
    seed_count = g_sel.sum(axis=1)
    seeded = seed_count <= d

    # seed energy: `_masked_row_sums` small-count column scan (exact for
    # seed_count < 8; wider seeds are classified hard below).
    seed_energy = jnp.zeros(b, dtype=jnp.float64)
    for idx in range(k):
        seed_energy = seed_energy + jnp.where(g_sel[:, idx], es[:, idx], 0.0)

    # ---- root LP bound + easy classification ---------------------------
    root_bound = _root_bound(ts, es, z, tt0, ee0)
    # The sequential solver prunes its root iff bound >= e_min - 1e-12
    # with e_min the seed energy; identical expression, identical floats.
    root_prunes = root_bound >= seed_energy - 1e-12
    easy = (~infeasible & ~partial & seeded & (seed_count < _SMALL_SUM)
            & (tt0 >= z) & root_prunes)

    # scatter the seed back to original expert order via the inverse perm
    inv = jnp.argsort(order, axis=1, stable=True)
    easy_sel = jnp.take_along_axis(g_sel, inv, axis=1) & easy[:, None]

    return {
        "infeasible": infeasible,
        "all_unreachable": all_unreachable,
        "partial": partial,
        "fallback_sel": fallback_sel,
        "easy": easy,
        "easy_sel": easy_sel,
        "seed_energy": seed_energy,
        "root_bound": root_bound,
    }


@functools.lru_cache(maxsize=None)
def jitted_prework(max_experts: int):
    """Single-device jitted `prework` (sharded variant lives in
    `repro.schedulers.sharded`, wrapped in `shard_map` over the mesh)."""
    return jax.jit(functools.partial(prework, max_experts=max_experts))
