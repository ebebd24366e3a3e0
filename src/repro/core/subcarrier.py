"""Optimal subcarrier allocation — P3 / P3(a) (paper §VI-A, Appendix B).

For a fixed expert selection (=> scheduled bytes s_ij), communication
energy is minimized by giving each active directed link exactly ONE
subcarrier (Eq. 16), turning P3 into a weighted bipartite assignment:

    links (i, j) with s_ij > 0   x   subcarriers m
    edge weight w_ij^(m) = P0 * s_ij / r_ij^(m)

solved optimally in polynomial time (Kuhn-Munkres / Hungarian), here by
scipy.optimize.linear_sum_assignment (shortest augmenting paths,
Jonker-Volgenant style, compiled).

Fast path (Theorem 1's event A): if every active link's best subcarrier
(argmax_m r_ij^(m)) is distinct, assigning each link its own best
subcarrier is optimal regardless of s_ij — no Hungarian needed.
"""

from __future__ import annotations

from typing import NamedTuple, Tuple

import numpy as np
from scipy import optimize

_INF = 1e30


def linear_sum_assignment(cost: np.ndarray) -> Tuple[np.ndarray, np.ndarray]:
    """Minimum-cost rectangular assignment (rows <= cols).

    Returns (row_idx, col_idx), rows ascending, from
    scipy.optimize.linear_sum_assignment.
    """
    cost = np.asarray(cost, dtype=np.float64)
    n, m = cost.shape
    if n > m:
        raise ValueError(f"need rows <= cols, got {cost.shape}")
    return optimize.linear_sum_assignment(cost)


def max_rate_assignment(rates: np.ndarray, links: np.ndarray) -> np.ndarray | None:
    """Theorem-1 fast path: each link takes argmax_m r; valid iff all distinct.

    Args:
      rates: (K, K, M) subcarrier rates.
      links: (L, 2) int array of active (i, j) links.
    Returns (L,) chosen subcarriers or None if a collision exists.
    """
    best = np.argmax(rates[links[:, 0], links[:, 1]], axis=-1)
    if len(np.unique(best)) != len(best):
        return None
    return best


class Assignment(NamedTuple):
    """A solved P3(a): beta, the links it serves, and whether the
    assignment solver ran (False: the fast path, greedy, or no links)."""

    beta: np.ndarray
    links: int
    solver: bool


def allocate_subcarriers(
    s_bytes: np.ndarray,
    rates: np.ndarray,
    p0: float,
    *,
    method: str = "auto",
    strict: bool = False,
) -> np.ndarray:
    """Solve P3(a): returns beta (K, K, M) with C3 + one-subcarrier-per-link.

    When the traffic is C3-infeasible (more active links than subcarriers)
    the top-M links by scheduled bytes are served and the rest get no
    subcarrier — their links stay at zero rate, so the energy accountants
    (`energy.comm_energy`, `assignment_energy`) price the round at +inf
    rather than crashing a scheduler policy mid-layer.  Pass strict=True
    to raise instead (validation / direct API use).

    Args:
      s_bytes: (K, K) scheduled bytes s_ij (diagonal ignored).
      rates: (K, K, M) per-subcarrier rates r_ij^(m).
      p0: per-subcarrier transmit power (scales weights; argmin-invariant
        per link but kept for objective fidelity).
      method: "auto" (fast path then Hungarian), "hungarian", "greedy".
      strict: raise ValueError on C3-infeasible traffic instead of
        serving the top-M links.
    """
    return assign_subcarriers(s_bytes, rates, p0, method=method,
                              strict=strict).beta


def assign_subcarriers(
    s_bytes: np.ndarray,
    rates: np.ndarray,
    p0: float,
    *,
    method: str = "auto",
    strict: bool = False,
) -> Assignment:
    """`allocate_subcarriers`, with the number of links served and
    whether `linear_sum_assignment` solved it."""
    k, _, m = rates.shape
    beta = np.zeros((k, k, m), dtype=np.int8)
    off_diag = ~np.eye(k, dtype=bool)
    links = np.argwhere(off_diag & (s_bytes > 0))
    n_links = len(links)
    if n_links == 0:
        return Assignment(beta, 0, False)
    if n_links > m:
        if strict:
            raise ValueError(
                f"{n_links} active links exceed M={m} subcarriers "
                f"(C3 infeasible)"
            )
        heaviest = np.argsort(-s_bytes[links[:, 0], links[:, 1]],
                              kind="stable")[:m]
        links = links[np.sort(heaviest)]
        n_links = m

    if method == "auto":
        fast = max_rate_assignment(rates, links)
        if fast is not None:
            beta[links[:, 0], links[:, 1], fast] = 1
            return Assignment(beta, n_links, False)
        method = "hungarian"

    if method == "greedy":
        # sort links by bytes desc; each takes its best free subcarrier
        order = np.argsort(-s_bytes[links[:, 0], links[:, 1]], kind="stable")
        free = np.ones(m, dtype=bool)
        for li in order:
            i, j = links[li]
            r = np.where(free, rates[i, j], -np.inf)
            sc = int(np.argmax(r))
            beta[i, j, sc] = 1
            free[sc] = False
        return Assignment(beta, n_links, False)

    if method != "hungarian":
        raise ValueError(f"unknown method {method!r}")

    r = rates[links[:, 0], links[:, 1]]
    s = s_bytes[links[:, 0], links[:, 1], None]
    with np.errstate(divide="ignore"):
        w = np.where(r > 0, p0 * s / r, _INF)
    rows, cols = linear_sum_assignment(w)
    beta[links[rows, 0], links[rows, 1], cols] = 1
    return Assignment(beta, n_links, True)


def assignment_energy(
    s_bytes: np.ndarray, rates: np.ndarray, beta: np.ndarray, p0: float
) -> float:
    """Objective of P3(a) under a one-subcarrier-per-link beta."""
    total = 0.0
    k = s_bytes.shape[0]
    for i in range(k):
        for j in range(k):
            if i == j or s_bytes[i, j] <= 0:
                continue
            sc = np.nonzero(beta[i, j])[0]
            if len(sc) == 0:
                return float("inf")
            r = float((rates[i, j, sc]).sum())
            if r <= 0:
                return float("inf")
            total += p0 * s_bytes[i, j] * float(len(sc)) / r
    return total
