"""Plain checks of one protocol round's schedule against the paper's
definitions (arXiv 2503.13421, §II-B, §V, §VI), in float64 numpy,
importing nothing of the program.

Given the round's inputs (gate scores, per-subcarrier rates, the QoS
threshold z*gamma^(l), the expert budget D and the energy constants) and
the program's decision (alpha, beta, energy), it counts the faults:

- subcarriers: every directed link i != j that carries hidden states has
  exactly one subcarrier, no other link has one, and no subcarrier serves
  two links (C3);
- assignment: the links' communication energy equals the optimum of the
  assignment problem P3(a) for this alpha (solved here by a plain
  Hungarian method);
- selection: every token's selection solves P1 under the costs that
  beta prices: the cheapest subset of at most D experts whose gate mass
  meets the threshold, found by enumerating every subset; a token for
  which no such subset exists takes the D experts of highest score
  (Remark 2).  A block-coordinate descent ends on such a fixed point;
  what the program says of its own convergence is not consulted;
- energy: the reported P2 objective equals Eq. (3) plus Eq. (4).
"""

from __future__ import annotations

import itertools

import numpy as np

REL = 1e-9        # relative slack for float64 sums taken in another order
MASS = 1e-12      # gate-mass slack at the QoS threshold


def link_rates(rates, beta):
    """Eq. 2: R_ij = sum_m beta_ij^(m) r_ij^(m); in-situ links are free."""
    r = np.where(np.isfinite(rates), rates, 0.0)
    out = (beta * r).sum(-1)
    np.fill_diagonal(out, np.inf)
    return out


def selection_costs(rates_kk, beta, comp, s0, p0):
    """Cost of source i selecting expert j: s0 (a_j + P0 n_sc / R_ij);
    s0 a_j in situ; +inf over a link with no rate."""
    n_sc = beta.sum(-1).astype(np.float64)
    with np.errstate(divide="ignore", invalid="ignore"):
        comm = np.where(rates_kk > 0, p0 * n_sc / rates_kk, np.inf)
    e = s0 * (comp[None, :] + comm)
    np.fill_diagonal(e, s0 * comp)
    return e


def round_energy(alpha, beta, rates, comp, s0, p0):
    """Eq. 3 summed over links with traffic, plus Eq. 4 (b_j = 0)."""
    s = s0 * alpha.sum(axis=1).astype(np.float64)          # s_ij bytes
    rk = link_rates(rates, beta)
    off = ~np.eye(s.shape[0], dtype=bool) & (s > 0)
    n_sc = beta.sum(-1)
    if (rk[off] <= 0).any():
        comm = np.inf
    else:
        comm = float((s[off] / rk[off] * p0 * n_sc[off]).sum())
    return comm + float((comp * s.sum(axis=0)).sum())


def hungarian(cost):
    """Minimum total of a rows <= cols assignment; the textbook
    shortest-augmenting-path method with potentials."""
    n, m = cost.shape
    u, v = np.zeros(n + 1), np.zeros(m + 1)
    p = np.zeros(m + 1, dtype=np.int64)
    way = np.zeros(m + 1, dtype=np.int64)
    for i in range(1, n + 1):
        p[0], j0 = i, 0
        minv = np.full(m + 1, np.inf)
        used = np.zeros(m + 1, dtype=bool)
        while True:
            used[j0] = True
            i0 = p[j0]
            free = ~used
            free[0] = False
            cur = np.full(m + 1, np.inf)
            cur[free] = cost[i0 - 1, np.flatnonzero(free) - 1] - u[i0] - v[free]
            better = free & (cur < minv)
            minv[better] = cur[better]
            way[better] = j0
            cand = np.where(free, minv, np.inf)
            j1 = int(np.argmin(cand))
            delta = cand[j1]
            u[p[used]] += delta
            v[used] -= delta
            minv[free] -= delta
            j0 = j1
            if p[j0] == 0:
                break
        while j0:
            j1 = way[j0]
            p[j0] = p[j1]
            j0 = j1
    rows = p[1:]
    cols = np.flatnonzero(rows > 0)
    return float(cost[rows[cols] - 1, cols].sum())


def _subsets(e: int, d: int) -> np.ndarray:
    masks = [np.isin(np.arange(e), c)
             for r in range(1, d + 1) for c in itertools.combinations(range(e), r)]
    return np.array(masks, dtype=np.float64)                # (S, E)


def check_round(gates, rates, qos, d, comp, s0, p0, alpha, beta,
                energy) -> dict:
    """Faults of one round, by kind; every count is 0 for a sound one."""
    k, n, e = gates.shape
    alpha = np.asarray(alpha, dtype=np.int64)
    beta = np.asarray(beta, dtype=np.int64)
    s = alpha.sum(axis=1)                                      # (K, K) tokens
    off = ~np.eye(k, dtype=bool)
    active = off & (s > 0)
    n_sc = beta.sum(-1)
    faults = {"subcarrier": int((active & (n_sc != 1)).sum()
                                + (~active & (n_sc != 0)).sum()
                                + (beta.sum(axis=(0, 1)) > 1).sum())}

    # P3(a): the links' energy against the optimal assignment.
    links = np.argwhere(active)
    if len(links):
        w = np.empty((len(links), rates.shape[-1]))
        for li, (i, j) in enumerate(links):
            r = rates[i, j]
            with np.errstate(divide="ignore"):
                w[li] = np.where(r > 0, p0 * s0 * s[i, j] / r, 1e30)
        got = float(sum(w[li, np.argmax(beta[i, j])]
                        for li, (i, j) in enumerate(links)))
        best = hungarian(w)
        faults["assignment"] = int(got > best * (1 + REL) + 1e-300)
    else:
        faults["assignment"] = 0

    # P1 per token, by enumeration; Remark 2 where nothing meets qos.
    sub = _subsets(e, d)                                       # (S, E)
    flat = gates.reshape(k * n, e)
    mass = flat @ sub.T                                        # (T, S)
    costs = selection_costs(link_rates(rates, beta), beta, comp, s0, p0)
    src = np.repeat(np.arange(k), n)
    with np.errstate(invalid="ignore"):
        sub_cost = np.where(sub[None] > 0, costs[:, None, :], 0.0).sum(-1)
    sub_cost = sub_cost[src]                                   # (T, S)
    sel = alpha.reshape(k * n, e).astype(np.float64)
    sel_mass = (flat * sel).sum(-1)
    with np.errstate(invalid="ignore"):
        sel_cost = np.where(sel > 0, costs[src], 0.0).sum(-1)
    top = mass.max(axis=1)
    feasible = top >= qos + MASS
    infeasible = top < qos - MASS
    bad = sel.sum(-1) > d                                      # C2
    best = np.where(mass >= qos + MASS, sub_cost, np.inf).min(axis=1)
    bad |= feasible & ((sel_mass < qos - MASS)
                       | (sel_cost > best * (1 + REL)))
    bad |= infeasible & ((sel.sum(-1) != min(d, e))
                         | (np.abs(sel_mass - top) > MASS))
    faults["selection"] = int(bad.sum())

    want = round_energy(alpha, beta, rates, comp, s0, p0)
    faults["energy"] = int(not np.isclose(energy, want, rtol=REL, atol=0.0))
    return faults
