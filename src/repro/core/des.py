"""Dynamic Expert Selection — Algorithm 1 (paper §V), exact host-side
solvers: per-instance (`des_select`), batched (`des_select_batch`: dedup +
vectorized pre-work + frontier-parallel B&B), plus the brute-force test
oracle.  The pre-work also exists as a jax-traceable pipeline in
`repro.core.des_prework` (device-sharded by `repro.schedulers.sharded`);
both front-ends are bit-identical to the solvers here.

Solves P1(a) for one (source-expert i, hidden-state n):

    min_alpha  sum_j e_j * alpha_j
    s.t. C1:   sum_j t_j * alpha_j >= z * gamma^(l)   (QoS / task relevance)
         C2:   sum_j alpha_j <= D                     (max #experts)
         alpha_j in {0, 1}

via branch-and-bound over *exclude/include* decisions (the paper's search
tree: the root implicitly includes all K experts; the left child excludes
the next expert, the right child keeps it), BFS traversal, and the
LP-relaxation lower bound of P1(b)/P1(c): sort experts by energy-to-score
ratio e_j/t_j descending, greedily exclude while QoS is preserved, then
exclude the *critical expert* fractionally (Eq. 11-12).

Note on Eq. (12)/Algorithm-1 pseudocode: the paper's bound line reads
``e <- e - (z - t) e_j / t_j`` which is a sign typo; the fractional
exclusion of the critical expert removes (t - z)/t_j of it, i.e.
``e <- e - (t - z) * e_j / t_j``.  We implement the corrected form (it is
the unique value consistent with Eq. (11)).

Unreachable experts (cost +inf: a link with no subcarrier) are exact.  In
an instance with some finite costs each unreachable expert is priced one
joule above the sum of the finite costs (`_price_unreachable`): dearer
than any reachable selection, yet small enough that the B&B's sums keep
the 1e-3 J costs to the last bits, where `_BIG` = 1e15 would round them
away.  So an unreachable expert is chosen only where no reachable subset
meets the QoS; there every subset that does costs +inf, and Remark 2's
Top-D by score is taken, priced +inf.  Instances that are wholly
unreachable, infeasible anyway, or force an unreachable expert in keep
the `_BIG` clamp below.

The problem is NP-hard (Prop. 1, knapsack reduction) so worst-case cost is
exponential, but the bound prunes aggressively (see
benchmarks/des_complexity.py).  A brute-force oracle is provided for tests.
"""

from __future__ import annotations

import dataclasses
import heapq
from collections import deque
from typing import Optional

import numpy as np

# Stand-in for +inf costs where an instance is not priced by
# `_price_unreachable`; keeps LP math finite.
# Small enough that even K * _BIG sums and the fractional-exclusion terms
# of Eq. (11)-(12) stay far from float64 overflow (and survive a float32
# downcast in consumers), large enough to dominate any physical energy.
_BIG = 1e15


@dataclasses.dataclass
class DESResult:
    selected: np.ndarray          # (K,) bool mask in ORIGINAL expert order
    energy: float                 # objective value sum_j e_j alpha_j
    feasible: bool                # False => Remark-2 fallback (top-D) applied
    nodes_explored: int           # B&B nodes dequeued (complexity metric)
    nodes_pruned: int             # nodes cut by the LP bound


def _sanitize(e: np.ndarray) -> np.ndarray:
    e = np.asarray(e, dtype=np.float64).copy()
    e[~np.isfinite(e)] = _BIG
    return np.minimum(e, _BIG)


def _sanitize_batch(e_raw: np.ndarray) -> np.ndarray:
    """Batched `_sanitize`: clamp non-finite costs to the `_BIG` sentinel.
    Single source for the host batch solver AND the sharded front-end
    (`repro.schedulers.sharded`); the jax replica is
    `repro.core.des_prework.sanitize_costs`."""
    return np.minimum(np.where(np.isfinite(e_raw), e_raw, _BIG), _BIG)


def _partly_reachable(e_raw: np.ndarray, forced: np.ndarray) -> np.ndarray:
    """Rows (B,) with some finite and some non-finite costs, and no forced
    expert among the non-finite ones: the rows `_price_unreachable`
    prices.  The jax replica is `repro.core.des_prework.partly_reachable`
    (the device pre-work leaves these rows to the host)."""
    fin = np.isfinite(e_raw)
    return fin.any(axis=1) & ~fin.all(axis=1) & ~(forced & ~fin).any(axis=1)


def _price_unreachable(e_raw: np.ndarray) -> np.ndarray:
    """(B, K) costs with each non-finite one replaced by 1 + the row's sum
    of finite costs: above any selection of reachable experts, and of
    the order of the costs, so the B&B's sums stay exact."""
    fin = np.isfinite(e_raw)
    big = 1.0 + np.where(fin, e_raw, 0.0).sum(axis=1, keepdims=True)
    return np.where(fin, e_raw, big)


def _unreachable_chosen(selected: np.ndarray, e_raw: np.ndarray) -> np.ndarray:
    """Rows (B,) whose selection holds an unreachable expert: no reachable
    subset met the QoS."""
    return (selected & ~np.isfinite(e_raw)).any(axis=1)


def _batch_inputs(scores, costs, qos, force_include):
    """Shared validation/broadcast prologue of `des_select_batch` and
    `sharded_des_select_batch`: returns (t, e_raw, z, forced) with
    t/e_raw (B, K) float64, z (B,) float64, forced (B, K) bool."""
    t = np.atleast_2d(np.asarray(scores, dtype=np.float64))
    e_raw = np.atleast_2d(np.asarray(costs, dtype=np.float64))
    b, k = t.shape
    if e_raw.shape != (b, k):
        raise ValueError(f"costs shape {e_raw.shape} != scores {t.shape}")
    z = np.broadcast_to(np.asarray(qos, dtype=np.float64), (b,)).copy()
    forced = (np.zeros((b, k), dtype=bool) if force_include is None
              else np.atleast_2d(np.asarray(force_include, dtype=bool)))
    if forced.shape != (b, k):
        raise ValueError(
            f"force_include shape {forced.shape} != scores {t.shape}")
    return t, e_raw, z, forced


def lp_lower_bound(t: np.ndarray, e: np.ndarray, z: float) -> float:
    """LP relaxation value of P1(b) over experts (t, e) with QoS z.

    Experts must be pre-sorted by e/t descending.  This is exactly the
    root-node bound of the B&B tree: start from all-included (score
    sum(t), energy sum(e)) and greedily exclude, finishing with the
    fractional exclusion of the critical expert (Eq. 11-12) — the single
    implementation lives in `_node_bound`.

    If even all-included misses z the relaxation is infeasible; callers
    gate on feasibility before bounding (a node is only bounded while
    still feasible), so we return the all-included energy rather than
    +inf in that degenerate case.
    """
    score = float(t.sum())
    energy = float(e.sum())
    if score < z:
        return energy
    return _node_bound(0, score, energy, z, t, e)


def top_d_fallback(t: np.ndarray, e: np.ndarray, d: int) -> np.ndarray:
    """Remark 2: when C1+C2 are jointly infeasible, select the Top-D by score."""
    k = t.shape[0]
    sel = np.zeros(k, dtype=bool)
    sel[np.argsort(-t, kind="stable")[: min(d, k)]] = True
    return sel


def des_select(
    scores: np.ndarray,
    costs: np.ndarray,
    qos: float,
    max_experts: int,
    *,
    force_include: Optional[np.ndarray] = None,
    upper_bound: float = np.inf,
) -> DESResult:
    """Exact Algorithm 1 (DES) for one hidden state.

    Args:
      scores: (K,) gate scores t_j >= 0 (need not sum to 1).
      costs:  (K,) selection costs e_j >= 0 (inf allowed = unreachable).
      qos:    z * gamma^(l).
      max_experts: D.
      force_include: optional (K,) bool — experts that must be selected
        (e.g. a shared expert / in-situ expert); they consume D slots.
      upper_bound: optional warm-start incumbent energy carried from a
        near-identical instance (a previous protocol round / BCD
        iteration / QoS-annealing layer).  A *valid* bound — one at or
        above this instance's true optimum — only tightens pruning:
        selections, energies, and feasibility stay bit-identical to the
        cold solve and ``nodes_explored`` can only decrease.  The bound
        prunes on the safe side (``bound >= upper_bound + 1e-12``), so
        even ``upper_bound == optimum`` cannot clip the optimal path;
        a stale too-tight bound (below the optimum) is detected after
        the search — no solution within the bound was found — and the
        instance is transparently re-solved cold.
    """
    t = np.asarray(scores, dtype=np.float64)
    e = _sanitize(costs)
    k = t.shape[0]
    d = int(max_experts)
    ub = float(upper_bound)
    if np.isnan(ub):
        ub = np.inf

    forced = (
        np.zeros(k, dtype=bool)
        if force_include is None
        else np.asarray(force_include, dtype=bool)
    )

    # All-unreachable edge case: every cost was +inf, so every selection
    # has (sanitized) energy ~K*_BIG — a garbage bound that used to leak
    # out of the LP math.  Treat it like Remark-2 infeasibility: Top-D-by-
    # score fallback, honestly priced at +inf.
    all_unreachable = not np.isfinite(
        np.asarray(costs, dtype=np.float64)).any()

    # Feasibility (Remark 2): can the best-score D experts cover qos?
    top_d_score = float(np.sort(t)[::-1][:d].sum())
    if top_d_score < qos or d < int(forced.sum()) or all_unreachable:
        sel = top_d_fallback(t, e, d)
        sel |= forced
        # trim to D keeping highest scores if forced pushed us over
        if sel.sum() > d:
            order = np.argsort(-t, kind="stable")
            keep = np.zeros(k, dtype=bool)
            budget = d
            for j in order:
                if forced[j] and budget > 0:
                    keep[j] = True
                    budget -= 1
            for j in order:
                if sel[j] and not keep[j] and budget > 0:
                    keep[j] = True
                    budget -= 1
            sel = keep
        energy = float("inf") if all_unreachable else float(e[sel].sum())
        return DESResult(sel, energy, False, 0, 0)

    e_raw = np.asarray(costs, dtype=np.float64)
    partial = bool(_partly_reachable(e_raw[None], forced[None])[0])
    if partial:
        e = _price_unreachable(e_raw[None])[0]

    # Sort by energy-to-score ratio descending (paper's branch order).
    with np.errstate(divide="ignore"):
        ratio = np.where(t > 0, e / np.maximum(t, 1e-300), np.inf)
    order = np.argsort(-ratio, kind="stable")
    ts, es = t[order], e[order]
    forced_s = forced[order]

    # B&B state: (next_idx j, score t, energy e, n_excluded, n_included,
    #             excluded_mask_bits, included_mask_bits)
    total_t, total_e = float(ts.sum()), float(es.sum())
    e_min, sel_min = np.inf, None

    # Seed the incumbent with a greedy integral solution so pruning bites
    # from the start: exclude greedily (integral only) while feasible.
    g_sel = np.ones(k, dtype=bool)
    g_score = total_t
    for idx in range(k):
        if forced_s[idx]:
            continue
        if g_score - ts[idx] >= qos:
            g_sel[idx] = False
            g_score -= ts[idx]
    if g_sel.sum() <= d:
        e_min = float(es[g_sel].sum())
        sel_min = g_sel.copy()

    queue = deque()
    queue.append((0, total_t, total_e, 0, 0, 0, 0))
    explored = pruned = 0

    while queue:
        j, tt, ee, n_exc, n_inc, exc_bits, inc_bits = queue.popleft()
        explored += 1

        # Incumbent update: feasible leaf-equivalent state (C2 satisfiable
        # only once enough exclusions are committed: |P_exc| >= K - D).
        if tt >= qos and n_exc >= k - d and ee < e_min:
            e_min = ee
            sel = np.ones(k, dtype=bool)
            for b in range(j):
                if exc_bits >> b & 1:
                    sel[b] = False
            sel_min = sel

        if j >= k or tt < qos:
            continue

        # LP bound over undecided experts [j, K) given committed state.
        # The warm bound prunes on the SAFE side (>= ub + 1e-12): every
        # ancestor of the optimal leaf has bound <= E* <= ub for a valid
        # ub, so the optimal path is never cut — only provably-worse
        # subtrees are.  The incumbent e_min stays selection-backed (it
        # is never seeded from ub), so the returned solution is always a
        # real selection found by this search.
        bound = _node_bound(j, tt, ee, qos, ts, es)
        if bound >= e_min - 1e-12 or bound >= ub + 1e-12:
            pruned += 1
            continue

        # Left child: exclude expert j (unless forced-in).
        if not forced_s[j] and tt - ts[j] >= qos:
            queue.append(
                (j + 1, tt - ts[j], ee - es[j], n_exc + 1, n_inc,
                 exc_bits | (1 << j), inc_bits)
            )
        # Right child: include expert j.
        if n_inc + 1 <= d:
            queue.append(
                (j + 1, tt, ee, n_exc, n_inc + 1, exc_bits, inc_bits | (1 << j))
            )

    # Stale-bound detection: a valid ub (>= this instance's optimum E*)
    # guarantees the search finds an incumbent with e_min = E* <= ub.
    # Ending with no incumbent, or one above the bound, certifies the
    # injected ub was BELOW the optimum (stale — e.g. carried across a
    # channel redraw) and the pruned search is unreliable: re-solve cold.
    if np.isfinite(ub) and (sel_min is None or e_min > ub + 1e-12):
        return des_select(scores, costs, qos, max_experts,
                          force_include=force_include)

    if sel_min is None:  # should not happen (feasibility pre-checked)
        sel_min = top_d_fallback(t, e, d)
        return DESResult(sel_min, float(e[sel_min].sum()), False, explored, pruned)

    # Map back to original order.
    selected = np.zeros(k, dtype=bool)
    selected[order[sel_min]] = True
    if partial and _unreachable_chosen(selected[None], e_raw[None])[0]:
        return DESResult(top_d_fallback(t, e, d), float("inf"), False,
                         explored, pruned)
    return DESResult(selected, float(e[selected].sum()), True, explored, pruned)


def _node_bound(j, tt, ee, qos, ts, es) -> float:
    """LP bound for the subtree at node (j, tt, ee): greedily exclude the
    undecided experts [j, K) (already ratio-sorted) while QoS is kept,
    then exclude the critical expert fractionally (Eq. 11-12).  The root
    call (j=0, all-included totals) IS `lp_lower_bound`."""
    score, energy = tt, ee
    for idx in range(j, len(ts)):
        # committed decisions all live at indices < j, so [j, K) is
        # entirely undecided and every expert may be excluded.
        tj, ej = ts[idx], es[idx]
        if score - tj >= qos:
            score -= tj
            energy -= ej
        else:
            if tj > 0:
                energy -= (score - qos) * ej / tj
            break
    return energy


# ----------------------------------------------------------------------
# Batched exact solver
# ----------------------------------------------------------------------

@dataclasses.dataclass
class DESBatchResult:
    """Row-wise results of `des_select_batch` (row b solves instance b)."""

    selected: np.ndarray          # (B, K) bool masks in ORIGINAL expert order
    energy: np.ndarray            # (B,) objective values
    feasible: np.ndarray          # (B,) bool; False => Remark-2 fallback
    nodes_explored: np.ndarray    # (B,) B&B nodes dequeued per instance
    nodes_pruned: np.ndarray      # (B,) nodes cut by the LP bound

    def __getitem__(self, b: int) -> DESResult:
        return DESResult(
            self.selected[b], float(self.energy[b]), bool(self.feasible[b]),
            int(self.nodes_explored[b]), int(self.nodes_pruned[b]))

    def __len__(self) -> int:
        return self.selected.shape[0]


def des_select_batch(
    scores: np.ndarray,
    costs: np.ndarray,
    qos: np.ndarray | float,
    max_experts: int,
    *,
    force_include: Optional[np.ndarray] = None,
    deduplicate: bool = True,
    upper_bound: Optional[np.ndarray | float] = None,
    warm_cache: Optional["WarmStartCache"] = None,
) -> DESBatchResult:
    """Exact Algorithm 1 (DES) for a batch of B independent instances.

    Equivalent to ``[des_select(scores[b], costs[b], qos[b], max_experts,
    force_include=force_include[b]) for b in range(B)]`` — bit-identical
    selections, energies, and node counts — but solved batch-wide:

      1. identical (scores-row, costs-row, qos, force-row) instances are
         deduplicated (gate tensors repeat heavily across tokens, and the
         JESA sweep re-solves the same rows every BCD iteration);
      2. the per-instance pre-work (sanitize / feasibility / ratio sort /
         greedy-incumbent seed) runs as vectorized numpy over all unique
         instances at once;
      3. the branch-and-bound is *frontier-parallel*: all still-open
         instances advance level-by-level through the (shared-depth)
         search tree, and the Eq. 11-12 LP bound is evaluated as one
         vectorized pass per level.  Within a level the per-instance
         incumbent updates are replayed in exact BFS order via a
         segmented running minimum, so pruning — and therefore node
         counts and tie-breaking — match the sequential solver exactly.

    Args:
      scores: (B, K) gate scores t_j >= 0.
      costs:  (B, K) selection costs e_j >= 0 (inf allowed = unreachable).
      qos:    scalar or (B,) — z * gamma^(l) per instance.
      max_experts: D (shared across the batch).
      force_include: optional (B, K) bool — per-instance must-select mask.
      deduplicate: solve only unique instances and scatter (default).
      upper_bound: optional scalar or (B,) warm-start incumbent energies
        (see `des_select`): a valid per-row bound only tightens pruning
        — results stay bit-identical, node counts may only decrease —
        and a stale bound is detected and re-solved cold.
      warm_cache: optional `WarmStartCache` extending dedup ACROSS calls
        (protocol rounds / layers / BCD iterations): exact repeats are
        answered from the cache with zero B&B nodes, and structurally
        identical rows at a different QoS contribute warm incumbents.
    """
    t, e_raw, z, forced = _batch_inputs(scores, costs, qos, force_include)
    b, k = t.shape
    d = int(max_experts)

    if b == 0:
        zero = np.zeros(0, dtype=np.int64)
        return DESBatchResult(np.zeros((0, k), dtype=bool),
                              np.zeros(0), np.zeros(0, dtype=bool), zero, zero)

    ub = (None if upper_bound is None else
          np.broadcast_to(np.asarray(upper_bound, dtype=np.float64),
                          (b,)).copy())
    if ub is not None:
        ub[np.isnan(ub)] = np.inf
        if not np.isfinite(ub).any():
            ub = None

    if warm_cache is not None:
        return _warm_cached_solve(warm_cache, t, e_raw, z, forced, d,
                                  ub, deduplicate)

    if deduplicate:
        # Sanitized costs + the finite-mask fully determine the solver's
        # behaviour (+inf and a literal _BIG cost row must NOT collapse:
        # all-unreachable rows take the Remark-2 path with energy=+inf).
        e_san = _sanitize_batch(e_raw)
        key = np.hstack([t, e_san, np.isfinite(e_raw).astype(np.float64),
                         z[:, None], forced.astype(np.float64)])
        uniq_idx, inverse = _dedup_rows(key)
        if uniq_idx is not None and len(uniq_idx) < b:
            ub_u = None
            if ub is not None:
                # duplicate rows are identical instances, so any row's
                # valid bound is valid for the whole group: take the min.
                ub_u = np.full(len(uniq_idx), np.inf)
                np.minimum.at(ub_u, inverse, ub)
            sub = des_select_batch(
                t[uniq_idx], e_raw[uniq_idx], z[uniq_idx], d,
                force_include=forced[uniq_idx], deduplicate=False,
                upper_bound=ub_u)
            return DESBatchResult(
                sub.selected[inverse], sub.energy[inverse],
                sub.feasible[inverse], sub.nodes_explored[inverse],
                sub.nodes_pruned[inverse])

    e = _sanitize_batch(e_raw)

    selected = np.zeros((b, k), dtype=bool)
    energy = np.zeros(b, dtype=np.float64)
    feasible = np.zeros(b, dtype=bool)
    explored = np.zeros(b, dtype=np.int64)
    pruned = np.zeros(b, dtype=np.int64)

    # ---- vectorized Remark-2 feasibility screen (mirrors des_select) ----
    all_unreachable = ~np.isfinite(e_raw).any(axis=1)
    top_d_score = np.sort(t, axis=1)[:, ::-1][:, :d].sum(axis=1)
    infeasible = (top_d_score < z) | (d < forced.sum(axis=1)) | all_unreachable
    has_forced = forced.any(axis=1)
    for row in np.flatnonzero(infeasible & has_forced):
        # des_select returns immediately on this path (no B&B); the rare
        # forced-trim logic stays single-source via a thin per-row call.
        res = des_select(t[row], e_raw[row], float(z[row]), d,
                         force_include=forced[row])
        selected[row], energy[row] = res.selected, res.energy
    plain = infeasible & ~has_forced
    if plain.any():
        rows = np.flatnonzero(plain)
        # top_d_fallback, batched: same stable top-D-by-score mask.
        top = np.argsort(-t[rows], axis=1, kind="stable")[:, : min(d, k)]
        sel = np.zeros((rows.size, k), dtype=bool)
        np.put_along_axis(sel, top, True, axis=1)
        selected[rows] = sel
        energy[rows] = np.where(all_unreachable[rows], np.inf,
                                _masked_row_sums(e[rows], sel))

    live = np.flatnonzero(~infeasible)
    if live.size == 0:
        return DESBatchResult(selected, energy, feasible, explored, pruned)
    partial = live[_partly_reachable(e_raw[live], forced[live])]
    e[partial] = _price_unreachable(e_raw[partial])

    # ---- ratio sort (paper's branch order), batched ----------------------
    tl, el, zl, fl = t[live], e[live], z[live], forced[live]
    with np.errstate(divide="ignore"):
        ratio = np.where(tl > 0, el / np.maximum(tl, 1e-300), np.inf)
    order = np.argsort(-ratio, axis=1, kind="stable")
    ts = np.take_along_axis(tl, order, axis=1)
    es = np.take_along_axis(el, order, axis=1)
    forced_s = np.take_along_axis(fl, order, axis=1)

    ub_l = None if ub is None else ub[live]
    sel_sorted, has_inc, exp_l, prn_l = _branch_and_bound_batch(
        ts, es, zl, d, forced_s, upper_bound=ub_l)

    # Map back to original expert order + recompute energies exactly as
    # the sequential solver does (masked gather-sum semantics).
    for i in np.flatnonzero(~has_inc):  # should not happen (pre-checked)
        row = live[i]
        sel = top_d_fallback(t[row], e[row], d)
        selected[row] = sel
        energy[row] = float(e[row][sel].sum())
    hits = np.flatnonzero(has_inc)
    if hits.size:
        rows = live[hits]
        orig_sel = np.zeros((hits.size, k), dtype=bool)
        np.put_along_axis(orig_sel, order[hits], sel_sorted[hits], axis=1)
        selected[rows] = orig_sel
        energy[rows] = _masked_row_sums(e[rows], orig_sel)
        feasible[rows] = True
    explored[live], pruned[live] = exp_l, prn_l

    # Stale-bound detection (batched twin of des_select): rows whose warm
    # bound admitted no incumbent at or below it were given a bound BELOW
    # their optimum — re-solve those rows cold.
    if ub_l is not None:
        bad = np.isfinite(ub_l) & (~has_inc | (energy[live] > ub_l + 1e-12))
        if bad.any():
            rows = live[np.flatnonzero(bad)]
            sub = des_select_batch(t[rows], e_raw[rows], z[rows], d,
                                   force_include=forced[rows],
                                   deduplicate=False)
            selected[rows] = sub.selected
            energy[rows] = sub.energy
            feasible[rows] = sub.feasible
            explored[rows] = sub.nodes_explored
            pruned[rows] = sub.nodes_pruned
            partial = np.setdiff1d(partial, rows)
    # Partly reachable rows that had to take an unreachable expert: no
    # reachable subset meets the QoS.  Remark 2, priced +inf.
    bad = partial[feasible[partial]
                  & _unreachable_chosen(selected[partial], e_raw[partial])]
    if bad.size:
        top = np.argsort(-t[bad], axis=1, kind="stable")[:, : min(d, k)]
        sel = np.zeros((bad.size, k), dtype=bool)
        np.put_along_axis(sel, top, True, axis=1)
        selected[bad] = sel
        energy[bad] = np.inf
        feasible[bad] = False
    return DESBatchResult(selected, energy, feasible, explored, pruned)


def _dedup_rows(key: np.ndarray) -> tuple[Optional[np.ndarray], np.ndarray]:
    """Group identical rows of `key`: returns (representative row indices,
    inverse map) like np.unique(axis=0), or (None, _) when all rows are
    distinct.  Hash-first (one float dot + scalar sort) instead of
    lexicographic row sorting; equal-hash neighbours are verified
    element-wise, falling back to np.unique on a genuine hash collision."""
    b, w = key.shape
    weights = np.random.default_rng(0xDE5).standard_normal(w)
    h = key @ weights
    sort_idx = np.argsort(h, kind="stable")
    hs = h[sort_idx]
    same_hash = hs[1:] == hs[:-1]
    if not same_hash.any():
        return None, np.arange(b)
    ks = key[sort_idx]
    same_row = (ks[1:] == ks[:-1]).all(axis=1)
    if (same_hash & ~same_row).any():  # hash collision (vanishing prob.)
        _, uniq_idx, inverse = np.unique(
            key, axis=0, return_index=True, return_inverse=True)
        return uniq_idx, inverse.reshape(-1)  # numpy 2.x returns (B, 1)
    new_group = np.r_[True, ~same_row]
    group_of_sorted = np.cumsum(new_group) - 1
    inverse = np.empty(b, dtype=np.int64)
    inverse[sort_idx] = group_of_sorted
    return sort_idx[new_group], inverse


def _warm_keys(t, e_raw, z, forced, d):
    """Cache keys for a batch of instances.  `full` is the `_dedup_rows`
    dedup key extended with a max_experts column (D is constant within
    one call but the cache spans calls); `struct` additionally drops the
    QoS column — rows identical up to z share cached selections as warm
    incumbents across the z*gamma^(l) annealing schedule."""
    e_san = _sanitize_batch(e_raw)
    fin = np.isfinite(e_raw).astype(np.float64)
    fcol = forced.astype(np.float64)
    dcol = np.full((t.shape[0], 1), float(d))
    full = np.hstack([t, e_san, fin, z[:, None], fcol, dcol])
    struct = np.hstack([t, e_san, fin, fcol, dcol])
    return full, struct


class WarmStartCache:
    """Cross-call amortization for `des_select_batch`: extends the
    within-call `_dedup_rows` dedup ACROSS protocol rounds, layers, and
    BCD iterations.

    Two tiers, both keyed by the `_dedup_rows` hashing scheme (float dot
    against fixed Gaussian weights, every hash hit verified element-wise
    so a collision can only cost a miss, never a wrong answer):

      * exact tier — the full instance key (scores, sanitized costs,
        finite-mask, qos, forced, D).  A hit replays the stored
        selection/energy/feasibility bit-identically with ZERO B&B nodes
        (``nodes_explored == nodes_pruned == 0``).
      * structure tier — the same key minus qos.  A feasible cached
        selection whose coverage still meets the new qos is a valid warm
        incumbent (same costs => bit-equal energy), injected as
        `upper_bound=` into the cold solve of the missing rows; the
        solver's stale-bound detection makes an invalidated-by-channel
        bound safe (it falls back to the cold solve automatically).

    The cache holds plain host numpy and is NOT thread-safe; schedulers
    use it from the single resolver thread.  `invalidate()` must be
    called whenever the cost model changes out from under the keys —
    e.g. a channel redraw or an expert-churn mask flip (the serving
    frontend does this automatically).
    """

    def __init__(self, max_entries: int = 65536):
        self.max_entries = int(max_entries)
        self._exact: dict = {}    # hash -> [(key_row, sel, energy, feas)]
        self._struct: dict = {}   # hash -> [(key_row, energy, coverage)]
        self._n = 0
        self._weights: dict = {}  # key width -> Gaussian hash weights
        self.stats = {"lookups": 0, "exact_hits": 0, "bound_hits": 0,
                      "stores": 0, "invalidations": 0}

    def __len__(self) -> int:
        return self._n

    def invalidate(self) -> None:
        """Drop every entry (channel redraw / churn / new cost model)."""
        self._exact.clear()
        self._struct.clear()
        self._n = 0
        self.stats["invalidations"] += 1

    def _hash(self, key: np.ndarray) -> np.ndarray:
        # `_dedup_rows`' weights, summed row by row: a matrix product's
        # rounding may depend on how many rows it is given, and a row
        # stored from one batch must hash alike when looked up in another
        w = key.shape[1]
        if w not in self._weights:
            weights = np.random.default_rng(0xDE5).standard_normal(w)
            self._weights[w] = weights
        return (key * self._weights[w]).sum(axis=1)

    def match(self, full_key: np.ndarray):
        """Exact-tier lookup: (hit (B,) bool, sel (B, K'), energy (B,),
        feasible (B,)) — sel columns sized from the stored rows."""
        b = full_key.shape[0]
        h = self._hash(full_key)
        k = (full_key.shape[1] - 2) // 4
        hit = np.zeros(b, dtype=bool)
        sel = np.zeros((b, k), dtype=bool)
        energy = np.zeros(b, dtype=np.float64)
        feasible = np.zeros(b, dtype=bool)
        self.stats["lookups"] += b
        for i in range(b):
            for krow, srow, en, fe in self._exact.get(h[i], ()):
                if np.array_equal(krow, full_key[i]):
                    hit[i], sel[i], energy[i], feasible[i] = (
                        True, srow, en, fe)
                    break
        self.stats["exact_hits"] += int(hit.sum())
        return hit, sel, energy, feasible

    def bounds(self, struct_key: np.ndarray, z: np.ndarray) -> np.ndarray:
        """Structure-tier lookup: per-row warm upper bounds (B,), +inf
        where no cached selection of the same structure still covers the
        row's qos `z`."""
        b = struct_key.shape[0]
        h = self._hash(struct_key)
        ub = np.full(b, np.inf)
        for i in range(b):
            for krow, en, cov in self._struct.get(h[i], ()):
                if cov >= z[i] and en < ub[i] and np.array_equal(
                        krow, struct_key[i]):
                    ub[i] = en
        self.stats["bound_hits"] += int(np.isfinite(ub).sum())
        return ub

    def store(self, full_key, struct_key, scores, selected, energy,
              feasible) -> None:
        """Insert solved rows (deduplicated first — callers pass raw
        batches).  Infeasible Remark-2 rows enter the exact tier only:
        their fallback selection is not a valid incumbent."""
        uniq_idx, _ = _dedup_rows(full_key)
        rows = np.arange(full_key.shape[0]) if uniq_idx is None else uniq_idx
        if self._n + 2 * rows.size > self.max_entries:
            # Simple wholesale eviction: the working set of one serving
            # round is far below max_entries, so this only fires under
            # pathological churn where stale entries would never hit.
            self._exact.clear()
            self._struct.clear()
            self._n = 0
        hf = self._hash(full_key[rows])
        hs = self._hash(struct_key[rows])
        coverage = (scores[rows] * selected[rows]).sum(axis=1)
        for i, r in enumerate(rows):
            self._exact.setdefault(hf[i], []).append(
                (full_key[r].copy(), selected[r].copy(),
                 float(energy[r]), bool(feasible[r])))
            self._n += 1
            if feasible[r]:
                self._struct.setdefault(hs[i], []).append(
                    (struct_key[r].copy(), float(energy[r]),
                     float(coverage[i])))
                self._n += 1
        self.stats["stores"] += int(rows.size)


def _warm_cached_solve(cache, t, e_raw, z, forced, d, ub, deduplicate):
    """`des_select_batch` body when a `WarmStartCache` is attached: serve
    exact repeats from the cache (zero B&B nodes), solve the misses cold
    with cache-derived warm upper bounds, then store the fresh rows."""
    b, k = t.shape
    full_key, struct_key = _warm_keys(t, e_raw, z, forced, d)
    hit, sel_c, en_c, fe_c = cache.match(full_key)
    selected = np.zeros((b, k), dtype=bool)
    energy = np.zeros(b, dtype=np.float64)
    feasible = np.zeros(b, dtype=bool)
    explored = np.zeros(b, dtype=np.int64)
    pruned = np.zeros(b, dtype=np.int64)
    selected[hit] = sel_c[hit]
    energy[hit] = en_c[hit]
    feasible[hit] = fe_c[hit]
    miss = np.flatnonzero(~hit)
    if miss.size:
        ub_c = cache.bounds(struct_key[miss], z[miss])
        ub_m = ub_c if ub is None else np.minimum(ub[miss], ub_c)
        sub = des_select_batch(
            t[miss], e_raw[miss], z[miss], d, force_include=forced[miss],
            deduplicate=deduplicate, upper_bound=ub_m)
        selected[miss] = sub.selected
        energy[miss] = sub.energy
        feasible[miss] = sub.feasible
        explored[miss] = sub.nodes_explored
        pruned[miss] = sub.nodes_pruned
        cache.store(full_key[miss], struct_key[miss], t[miss],
                    sub.selected, sub.energy, sub.feasible)
    return DESBatchResult(selected, energy, feasible, explored, pruned)


def _masked_row_sums(values: np.ndarray, mask: np.ndarray) -> np.ndarray:
    """Row-wise ``float(values[row][mask[row]].sum())``, vectorized.

    Bit-identical to the masked gather-sum of the sequential solver: for
    fewer than 8 selected elements numpy's reduction is a plain
    left-to-right accumulation, which the column scan reproduces exactly
    (adding 0.0 for unselected columns is exact); wider selections fall
    back to the literal per-row sum (numpy switches to an unrolled
    pairwise scheme there, so the grouping must be numpy's own)."""
    counts = mask.sum(axis=1)
    out = np.empty(mask.shape[0], dtype=np.float64)
    small = counts < 8
    if small.any():
        vs, ms = values[small], mask[small]
        acc = np.zeros(vs.shape[0], dtype=np.float64)
        for idx in range(values.shape[1]):
            acc = acc + np.where(ms[:, idx], vs[:, idx], 0.0)
        out[small] = acc
    for row in np.flatnonzero(~small):
        out[row] = values[row][mask[row]].sum()
    return out


def _segmented_running_min(vals: np.ndarray, seg_start: np.ndarray,
                           init: np.ndarray) -> tuple[np.ndarray, np.ndarray]:
    """Per-segment running minima of `vals` (contiguous segments flagged by
    `seg_start`), seeded with `init` (one seed per element, constant within
    a segment).  Returns (exclusive, inclusive) running mins — the value a
    sequential scan would hold *before* / *after* visiting each element."""
    n = vals.shape[0]
    shifted = np.empty(n, dtype=np.float64)
    shifted[0] = np.inf
    shifted[1:] = vals[:-1]
    shifted[seg_start] = np.inf
    # position within segment (for the boundary guard of the doubling scan)
    starts = np.flatnonzero(seg_start)
    seg_id = np.cumsum(seg_start) - 1
    pos = np.arange(n) - starts[seg_id]
    res = shifted
    shift = 1
    longest = int(pos.max()) + 1  # doubling only needs the longest segment
    while shift < longest:
        idx = np.flatnonzero(pos >= shift)
        res[idx] = np.minimum(res[idx], res[idx - shift])
        shift *= 2
    exclusive = np.minimum(res, init)
    inclusive = np.minimum(exclusive, vals)
    return exclusive, inclusive


def _node_bound_batch(j: int, tt: np.ndarray, ee: np.ndarray,
                      qos, ts: np.ndarray, es: np.ndarray,
                      rows: np.ndarray) -> np.ndarray:
    """Vectorized `_node_bound` for a frontier of same-depth nodes: one
    Eq. 11-12 greedy/fractional-exclusion pass over positions [j, K) for
    all nodes at once.  `ts`/`es` are the full sorted (F, K) instance
    tables, `rows` maps each node to its instance, and `qos` is a python
    float for uniform-QoS batches (the common case) or a (F,) array."""
    k = ts.shape[1]
    tsg, esg = ts[rows], es[rows]
    q = qos if isinstance(qos, float) else qos[rows]
    energy = ee.copy()
    score = tt.copy()
    live = None  # level j: every node still excludes greedily
    for idx in range(j, k):
        tj, ej = tsg[:, idx], esg[:, idx]
        rem = score - tj
        exc = (rem >= q) if live is None else live & (rem >= q)
        crit = ~exc if live is None else live & ~exc
        score = np.where(exc, rem, score)
        if crit.any():
            # fractional exclusion of the critical expert (where t_j > 0)
            ci = np.flatnonzero(crit & (tj > 0))
            qq = q if isinstance(q, float) else q[ci]
            energy = np.where(exc, energy - ej, energy)
            energy[ci] -= (score[ci] - qq) * ej[ci] / tj[ci]
        else:
            energy = np.where(exc, energy - ej, energy)
        live = exc  # critical expert (fractional or t_j=0) ends the pass
        if not live.any():
            break
    return energy


def _branch_and_bound_batch(ts, es, qos, d, forced_s, upper_bound=None):
    """Frontier-parallel B&B over F pre-screened-feasible instances.

    All instances share depth: level j holds every live node whose next
    undecided expert is j, so the per-level work (incumbent replay, LP
    bound, child expansion) is plain vectorized numpy over one frontier.
    Node visit order within an instance is exactly the sequential BFS
    order, so incumbents, pruning, and node counts match `des_select`.
    Returns (sel_sorted (F, K), has_incumbent (F,), explored, pruned).

    `upper_bound` is an optional (F,) array of warm-start incumbent
    energies: nodes whose LP bound reaches ``ub + 1e-12`` are cut in
    addition to the incumbent rule (Scheme of `des_select`); the
    incumbent state itself is never seeded from it, and the caller
    performs stale-bound detection on the returned energies.
    """
    f, k = ts.shape
    ubv = (np.full(f, np.inf) if upper_bound is None
           else np.asarray(upper_bound, dtype=np.float64))
    # Uniform QoS (one sweep = one threshold) skips all per-node gathers.
    qu: Optional[float] = float(qos[0]) if (qos == qos[0]).all() else None
    qv = qu if qu is not None else qos

    # Greedy integral incumbent seed (same scan as des_select, batched).
    g_sel = np.ones((f, k), dtype=bool)
    g_score = ts.sum(axis=1)
    for idx in range(k):
        can = ~forced_s[:, idx] & (g_score - ts[:, idx] >= qv)
        g_sel[can, idx] = False
        g_score = np.where(can, g_score - ts[:, idx], g_score)
    seeded = g_sel.sum(axis=1) <= d
    e_min = np.full(f, np.inf)
    e_min[seeded] = _masked_row_sums(es[seeded], g_sel[seeded])
    sel_min = np.zeros((f, k), dtype=bool)
    sel_min[seeded] = g_sel[seeded]
    has_inc = seeded.copy()

    # explored/pruned accounting is deferred: every created node is
    # dequeued exactly once, so one bincount over the per-level frontier
    # snapshots at the end replaces two bincounts per level.
    explored_lists: list = []
    pruned_lists: list = []

    # Root frontier: one all-included node per instance.  `bnd` caches a
    # node's LP bound: a left child (exclude j) inherits its parent's
    # bound bit-for-bit — the parent's greedy pass starts with exactly
    # that exclusion — so only right children and roots evaluate fresh
    # bounds (NaN = not yet evaluated).  A node at level j has decided j
    # experts, so n_exc == j - n_inc and only n_inc is carried.
    inst = np.arange(f)
    tt = ts.sum(axis=1)
    ee = es.sum(axis=1)
    n_inc = np.zeros(f, dtype=np.int64)
    exc_mask = np.zeros((f, k), dtype=bool)
    bnd = np.full(f, np.nan)

    for j in range(k + 1):
        if inst.size == 0:
            break
        explored_lists.append(inst)
        meets_qos = tt >= (qu if qu is not None else qos[inst])

        # --- incumbent replay in BFS order (segmented running min) ------
        # A node can only improve the incumbent once |P_exc| >= K - D, and
        # n_exc <= j, so early levels (j < K - D) skip the scan entirely.
        if j >= k - d:
            cand = meets_qos & (j - n_inc >= k - d)
            vals = np.where(cand, ee, np.inf)
            seg_start = np.empty(inst.size, dtype=bool)
            seg_start[0] = True
            seg_start[1:] = inst[1:] != inst[:-1]
            run_excl, run_incl = _segmented_running_min(
                vals, seg_start, e_min[inst])
            improve = cand & (ee < run_excl)
            if improve.any():
                imp = np.flatnonzero(improve)
                # improvements strictly decrease, so the LAST improving
                # node per instance holds that instance's new incumbent.
                last = imp[np.flatnonzero(
                    np.r_[inst[imp][1:] != inst[imp][:-1], True])]
                rows = inst[last]
                e_min[rows] = ee[last]
                sel_min[rows] = ~exc_mask[last]
                has_inc[rows] = True
        else:
            run_incl = e_min[inst]

        # --- terminal / bound / prune -----------------------------------
        if j >= k:
            break
        if meets_qos.all():  # common: both child rules preserve C1
            keep_base = None
            btt, bee, bi, binc, bval = tt, ee, inst, run_incl, bnd
        else:
            keep_base = np.flatnonzero(meets_qos)
            if keep_base.size == 0:
                break
            btt, bee, bi = tt[keep_base], ee[keep_base], inst[keep_base]
            binc = run_incl[keep_base]
            bval = bnd[keep_base]
        fresh = np.flatnonzero(np.isnan(bval))
        if fresh.size:
            bval[fresh] = _node_bound_batch(
                j, btt[fresh], bee[fresh], qu if qu is not None else qos,
                ts, es, bi[fresh])
        cut = (bval >= binc - 1e-12) | (bval >= ubv[bi] + 1e-12)
        if cut.any():
            pruned_lists.append(bi[cut])
            keep_local = np.flatnonzero(~cut)
            if keep_local.size == 0:
                break
            keep = (keep_local if keep_base is None
                    else keep_base[keep_local])
            ki, ktt, kee = inst[keep], tt[keep], ee[keep]
            kinc = n_inc[keep]
            kmask, kbnd = exc_mask[keep], bval[keep_local]
        elif keep_base is None:
            ki, ktt, kee, kinc, kmask, kbnd = (
                inst, tt, ee, n_inc, exc_mask, bval)
        else:
            ki, ktt, kee, kbnd = bi, btt, bee, bval
            kinc = n_inc[keep_base]
            kmask = exc_mask[keep_base]

        # --- expand: left (exclude j) then right (include j) ------------
        tsj, esj = ts[ki, j], es[ki, j]
        left_ok = ~forced_s[ki, j] & (
            ktt - tsj >= (qu if qu is not None else qos[ki]))
        right_ok = kinc + 1 <= d
        nk = ki.size
        child_ok = np.empty(2 * nk, dtype=bool)
        child_ok[0::2], child_ok[1::2] = left_ok, right_ok

        inst2 = np.repeat(ki, 2)
        tt2 = np.repeat(ktt, 2)
        ee2 = np.repeat(kee, 2)
        tt2[0::2] -= tsj
        ee2[0::2] -= esj
        n_inc2 = np.repeat(kinc, 2)
        n_inc2[1::2] += 1
        exc2 = np.repeat(kmask, 2, axis=0)
        exc2[0::2, j] = True
        bnd2 = np.repeat(kbnd, 2)
        bnd2[1::2] = np.nan  # right children re-evaluate at their level

        inst = inst2[child_ok]
        tt, ee = tt2[child_ok], ee2[child_ok]
        n_inc = n_inc2[child_ok]
        exc_mask = exc2[child_ok]
        bnd = bnd2[child_ok]

    explored = np.bincount(
        np.concatenate(explored_lists) if explored_lists
        else np.zeros(0, dtype=np.int64), minlength=f)
    pruned = np.bincount(
        np.concatenate(pruned_lists) if pruned_lists
        else np.zeros(0, dtype=np.int64), minlength=f)
    return sel_min, has_inc, explored, pruned


def des_select_brute_force(
    scores: np.ndarray, costs: np.ndarray, qos: float, max_experts: int
) -> DESResult:
    """O(2^K) oracle for tests (K <= ~16): the cheapest subset of at most
    D experts that meets the QoS, over the finite-energy subsets; where a
    subset meets it but none of finite energy does, Remark 2's Top-D
    priced +inf (the solvers' rule for unreachable experts)."""
    t = np.asarray(scores, dtype=np.float64)
    e_raw = np.asarray(costs, dtype=np.float64)
    e = _sanitize(costs)
    k = t.shape[0]
    if not np.isfinite(e_raw).any():
        sel = top_d_fallback(t, e, max_experts)
        return DESResult(sel, float("inf"), False, 0, 0)
    best_e, best_sel, meets = np.inf, None, False
    for bits in range(1 << k):
        sel = np.array([(bits >> b) & 1 for b in range(k)], dtype=bool)
        if sel.sum() > max_experts:
            continue
        if t[sel].sum() < qos:
            continue
        meets = True
        ee = e_raw[sel].sum()
        if ee < best_e:
            best_e, best_sel = ee, sel
    if best_sel is None:
        sel = top_d_fallback(t, e, max_experts)
        energy = float("inf") if meets else float(e[sel].sum())
        return DESResult(sel, energy, False, 1 << k, 0)
    return DESResult(best_sel, float(best_e), True, 1 << k, 0)
