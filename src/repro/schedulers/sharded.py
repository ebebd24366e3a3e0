"""Device-sharded batched policy evaluation — the multi-device front-end
for the exact DES solver.

`des_select_batch` (PR 2) batched the Algorithm-1 sweep on one process;
this module shards that batch across devices.  The vectorized pre-work
(sanitize -> Remark-2 feasibility screen -> ratio sort -> greedy incumbent
seed -> root Eq. 11-12 LP bound, see `repro.core.des_prework`) runs as a
single jitted `shard_map` over a 1-D "batch" mesh
(`repro.distributed.sharding.make_batch_mesh`), with the (B, K) instance
batch partitioned over devices:

  * instances the root LP bound already proves solved by the greedy seed
    ("easy") and Remark-2-infeasible instances are resolved entirely
    in-graph — no per-instance numpy ever touches them;
  * only the hard residual is gathered back to the host frontier-parallel
    branch-and-bound (`des_select_batch`), which typically sees a small
    fraction of the batch.

`sharded_des_select_batch` is a drop-in for `des_select_batch` — same
signature, same `DESBatchResult`, and *bit-identical* selections,
energies, feasibility flags, and B&B node counts (the pre-work replicates
numpy's float accumulation order exactly; asserted by
tests/test_sharded.py on 1-device and forced multi-device meshes).

`ShardedDESPolicy` ("sharded-des") exposes it through the policy
registry: the JESA block-coordinate loop with its alpha-step routed
through the sharded solver, usable by name from the simulator, the
serving engine (in-graph greedy path), and the benchmarks
(`python -m benchmarks.des_complexity --quick --sharded`).

The solve is split into three phases so callers can overlap them:

  * `submit_prework`  — dispatch the jitted device pre-work WITHOUT
    blocking (jax's async dispatch returns device futures) and get a
    `PreworkHandle` back;
  * `collect_prework` — block on the device arrays and trim the padding;
  * `resolve_prework` — the host-side finish: forced/fallback/easy rows
    resolved from the pre-work outputs, hard residual through the host
    branch-and-bound.

`sharded_des_select_batch` is submit -> collect -> resolve in one call;
the async pipeline (`repro.schedulers.async_des.AsyncDESPipeline`)
dispatches submit on the caller thread and runs collect+resolve on a
worker so round r+1's device pre-work overlaps round r's host B&B.
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Any, Dict, Optional

import numpy as np

from repro.core import des as des_lib
from repro.schedulers.base import ScheduleContext, register_policy
from repro.schedulers.graph import GreedyDESPolicy
from repro.schedulers.host import JESAPolicy, _des_sweep

_DEFAULT_MESH = None  # lazily built over all local devices


def _default_mesh():
    global _DEFAULT_MESH
    if _DEFAULT_MESH is None:
        from repro.distributed import sharding
        _DEFAULT_MESH = sharding.make_batch_mesh()
    return _DEFAULT_MESH


@functools.lru_cache(maxsize=None)
def _sharded_prework_fn(mesh, max_experts: int):
    """Jitted shard_map'd pre-work for one (mesh, D) pair.

    Traced under x64 so every comparison happens in float64, matching the
    numpy solver bit-for-bit.  Callers must invoke the returned function
    under `jax.enable_x64(True)` as well (same trace avals)."""
    import jax
    from jax.sharding import PartitionSpec as P

    from repro.core import des_prework as des_prework_lib
    from repro.distributed.sharding import BATCH_AXIS

    row = P(BATCH_AXIS)
    mat = P(BATCH_AXIS, None)
    out_specs = {
        "infeasible": row, "all_unreachable": row, "partial": row,
        "fallback_sel": mat,
        "easy": row, "easy_sel": mat, "seed_energy": row, "root_bound": row,
    }
    # named wrapper (not a bare functools.partial) so the compilation
    # shows up as `des_prework` in jax_log_compiles output — the
    # recompile gate in tests/test_recompile_gate.py counts it by name
    def des_prework(scores, costs, qos, forced):
        return des_prework_lib.prework(scores, costs, qos, forced,
                                       max_experts=max_experts)

    fn = jax.shard_map(des_prework, mesh=mesh,
                       in_specs=(mat, mat, row, mat), out_specs=out_specs)
    return jax.jit(fn)


@dataclasses.dataclass
class PreworkHandle:
    """One submitted (B, K) instance batch: the normalized host inputs
    plus the in-flight device pre-work outputs (`out` holds jax arrays
    that may still be computing — jax dispatch is asynchronous; `out` is
    None for the empty batch)."""

    t: np.ndarray                 # (B, K) float64 gate scores
    e_raw: np.ndarray             # (B, K) float64 raw costs (inf allowed)
    z: np.ndarray                 # (B,)  float64 QoS thresholds
    forced: np.ndarray            # (B, K) bool must-select mask
    max_experts: int
    mesh: Any
    out: Optional[Dict[str, Any]]  # device arrays, padded to the mesh

    @property
    def batch(self) -> int:
        return self.t.shape[0]


def submit_prework(
    scores: np.ndarray,
    costs: np.ndarray,
    qos: np.ndarray | float,
    max_experts: int,
    *,
    force_include: Optional[np.ndarray] = None,
    mesh=None,
) -> PreworkHandle:
    """Dispatch the sharded device pre-work for a batch without blocking.

    Pads the batch to the mesh size and invokes the jitted `shard_map`
    pipeline; jax returns device futures immediately, so the caller can
    keep doing host work (e.g. the previous round's branch-and-bound)
    while the devices compute.  Pair with `collect_prework` +
    `resolve_prework` (or let `sharded_des_select_batch` do all three).
    """
    t, e_raw, z, forced = des_lib._batch_inputs(
        scores, costs, qos, force_include)
    b, k = t.shape
    d = int(max_experts)
    if mesh is None:
        mesh = _default_mesh()
    out = None
    if b:
        import jax

        from repro.distributed.sharding import pad_to_devices

        n_dev = int(np.prod(tuple(mesh.shape.values())))
        pad = pad_to_devices(b, n_dev)
        tp, ep, zp, fp = t, e_raw, z, forced
        if pad:
            tp = np.vstack([t, np.zeros((pad, k))])
            ep = np.vstack([e_raw, np.ones((pad, k))])
            zp = np.concatenate([z, np.zeros(pad)])
            fp = np.vstack([forced, np.zeros((pad, k), dtype=bool)])
        fn = _sharded_prework_fn(mesh, d)
        with jax.enable_x64(True):
            out = fn(tp, ep, zp, fp)
    return PreworkHandle(t, e_raw, z, forced, d, mesh, out)


def collect_prework(handle: PreworkHandle) -> Dict[str, np.ndarray]:
    """Block on a `submit_prework` dispatch and return host numpy arrays
    trimmed back to the unpadded batch."""
    if handle.out is None:
        return {}
    b = handle.batch
    return {key: np.asarray(val)[:b] for key, val in handle.out.items()}


def resolve_prework(
    handle: PreworkHandle,
    pw: Dict[str, np.ndarray],
    *,
    deduplicate: bool = True,
    stats: Optional[dict] = None,
    warm_cache: Optional[des_lib.WarmStartCache] = None,
) -> des_lib.DESBatchResult:
    """Host-side finish of a collected pre-work round.

    Resolves the Remark-2-infeasible and easy rows from the in-graph
    outputs and sends only the hard residual through the host
    frontier-parallel branch-and-bound — bit-identical to
    `repro.core.des.des_select_batch` on the whole batch.

    With a `WarmStartCache` attached the hard residual shrinks three
    ways, none of which may change an answer: exact cross-round repeats
    replay from the cache with zero B&B nodes; a warm incumbent that
    already meets the in-graph root Eq. 11-12 LP bound (and is met by
    the greedy seed) reclassifies the row as easy — resolved from the
    device pre-work outputs, mirroring the host solver's immediate
    root prune bit-for-bit; the remaining rows run the host B&B with the
    warm incumbent injected as `upper_bound=`.  `stats` gains
    {warm_hits, warm_easy, hard_before, hard_after}.
    """
    t, e_raw, z, forced = handle.t, handle.e_raw, handle.z, handle.forced
    b, k = t.shape
    d = handle.max_experts

    if b == 0:
        if stats is not None:
            stats.update(
                n_devices=int(np.prod(tuple(handle.mesh.shape.values()))),
                batch=0, easy=0, hard=0, infeasible=0, forced_rows=0,
                warm_hits=0, warm_easy=0, hard_before=0, hard_after=0)
        zero = np.zeros(0, dtype=np.int64)
        return des_lib.DESBatchResult(
            np.zeros((0, k), dtype=bool), np.zeros(0),
            np.zeros(0, dtype=bool), zero, zero)

    e = des_lib._sanitize_batch(e_raw)
    selected = np.zeros((b, k), dtype=bool)
    energy = np.zeros(b, dtype=np.float64)
    feasible = np.zeros(b, dtype=bool)
    explored = np.zeros(b, dtype=np.int64)
    pruned = np.zeros(b, dtype=np.int64)

    infeasible = pw["infeasible"]
    easy = pw["easy"]
    has_forced = forced.any(axis=1)

    # Remark-2-infeasible rows with forced experts: the rare forced-trim
    # logic stays single-source via per-row `des_select` (exactly what
    # `des_select_batch` does on this path).
    forced_rows = np.flatnonzero(infeasible & has_forced)
    for row in forced_rows:
        res = des_lib.des_select(t[row], e_raw[row], float(z[row]), d,
                                 force_include=forced[row])
        selected[row], energy[row] = res.selected, res.energy

    # Remark-2-infeasible, no forced experts: in-graph Top-D fallback.
    rows = np.flatnonzero(infeasible & ~has_forced)
    if rows.size:
        sel = pw["fallback_sel"][rows]
        selected[rows] = sel
        energy[rows] = np.where(pw["all_unreachable"][rows], np.inf,
                                des_lib._masked_row_sums(e[rows], sel))

    # Easy rows: the greedy seed is optimal (root LP bound prunes the
    # sequential solver's root node: 1 explored, 1 pruned) — resolved
    # entirely in-graph, only the energy gather-sum runs on host.
    rows = np.flatnonzero(easy)
    if rows.size:
        sel = pw["easy_sel"][rows]
        selected[rows] = sel
        energy[rows] = des_lib._masked_row_sums(e[rows], sel)
        feasible[rows] = True
        explored[rows] = 1
        pruned[rows] = 1

    # Hard residual: gather back to the host frontier-parallel B&B —
    # after the warm-start tiers have taken their cut.
    hard = ~infeasible & ~easy
    hard_rows = np.flatnonzero(hard)
    warm_hits = warm_easy = 0
    bnb_rows = hard_rows
    ub_b = None
    if warm_cache is not None and hard_rows.size:
        full_key, struct_key = des_lib._warm_keys(
            t[hard_rows], e_raw[hard_rows], z[hard_rows],
            forced[hard_rows], d)
        hit, sel_c, en_c, fe_c = warm_cache.match(full_key)
        if hit.any():
            rows = hard_rows[hit]
            selected[rows] = sel_c[hit]
            energy[rows] = en_c[hit]
            feasible[rows] = fe_c[hit]
            warm_hits = int(hit.sum())
        miss = np.flatnonzero(~hit)
        bnb_rows = hard_rows[miss]
        if miss.size:
            ub = warm_cache.bounds(struct_key[miss], z[bnb_rows])
            # Reclassify-easy: `root_bound >= ub + 1e-12` makes the host
            # warm solver prune the root immediately and keep the greedy
            # seed, provided the seed passes the stale-bound check — the
            # exact semantics replayed here from the in-graph outputs.
            rb = pw["root_bound"][bnb_rows]
            se = pw["seed_energy"][bnb_rows]
            easy_w = (np.isfinite(ub) & (rb >= ub + 1e-12)
                      & (se <= ub + 1e-12) & ~pw["partial"][bnb_rows])
            if easy_w.any():
                rows = bnb_rows[easy_w]
                sel = pw["easy_sel"][rows]
                selected[rows] = sel
                energy[rows] = des_lib._masked_row_sums(e[rows], sel)
                feasible[rows] = True
                explored[rows] = 1
                pruned[rows] = 1
                warm_cache.store(full_key[miss][easy_w],
                                 struct_key[miss][easy_w], t[rows],
                                 selected[rows], energy[rows],
                                 feasible[rows])
                miss = miss[~easy_w]
                ub_b = ub[~easy_w]
                bnb_rows = hard_rows[miss]
                warm_easy = int(easy_w.sum())
            else:
                ub_b = ub
    if bnb_rows.size:
        sub = des_lib.des_select_batch(
            t[bnb_rows], e_raw[bnb_rows], z[bnb_rows], d,
            force_include=forced[bnb_rows], deduplicate=deduplicate,
            upper_bound=ub_b)
        selected[bnb_rows] = sub.selected
        energy[bnb_rows] = sub.energy
        feasible[bnb_rows] = sub.feasible
        explored[bnb_rows] = sub.nodes_explored
        pruned[bnb_rows] = sub.nodes_pruned
        if warm_cache is not None:
            fk, sk = des_lib._warm_keys(
                t[bnb_rows], e_raw[bnb_rows], z[bnb_rows],
                forced[bnb_rows], d)
            warm_cache.store(fk, sk, t[bnb_rows], sub.selected,
                             sub.energy, sub.feasible)

    if stats is not None:
        stats.update(
            n_devices=int(np.prod(tuple(handle.mesh.shape.values()))),
            batch=int(b),
            easy=int(easy.sum()),
            hard=int(hard_rows.size),
            infeasible=int(infeasible.sum()),
            forced_rows=int(forced_rows.size),
            warm_hits=warm_hits,
            warm_easy=warm_easy,
            hard_before=int(hard_rows.size),
            hard_after=int(bnb_rows.size),
        )
    return des_lib.DESBatchResult(selected, energy, feasible,
                                  explored, pruned)


def sharded_des_select_batch(
    scores: np.ndarray,
    costs: np.ndarray,
    qos: np.ndarray | float,
    max_experts: int,
    *,
    force_include: Optional[np.ndarray] = None,
    deduplicate: bool = True,
    mesh=None,
    stats: Optional[dict] = None,
    warm_cache: Optional[des_lib.WarmStartCache] = None,
) -> des_lib.DESBatchResult:
    """Drop-in `des_select_batch` with device-sharded jitted pre-work.

    Same contract as `repro.core.des.des_select_batch` (bit-identical
    selections / energies / feasibility / node counts), plus:

      mesh:  a 1-D ("batch",) `jax.sharding.Mesh` to shard over
             (default: all local devices via `make_batch_mesh`).
      stats: optional dict, filled with the resolution split
             {n_devices, batch, easy, hard, infeasible, forced_rows,
             warm_hits, warm_easy, hard_before, hard_after} — `easy`
             instances never touch host numpy per-instance code.
      warm_cache: optional cross-round `WarmStartCache` (see
             `resolve_prework`) — answers stay bit-identical.

    Equivalent to `submit_prework` -> `collect_prework` ->
    `resolve_prework` back to back; use those directly (or
    `repro.schedulers.async_des.AsyncDESPipeline`) to overlap the device
    pre-work with host work.
    """
    handle = submit_prework(scores, costs, qos, max_experts,
                            force_include=force_include, mesh=mesh)
    return resolve_prework(handle, collect_prework(handle),
                           deduplicate=deduplicate, stats=stats,
                           warm_cache=warm_cache)


@register_policy("sharded-des", aliases=("des-sharded",))
class ShardedDESPolicy(JESAPolicy):
    """JESA with the alpha-step routed through the device-sharded exact
    solver — bit-identical schedules to `JESAPolicy`, pre-work sharded
    over the mesh.

    Host path (`schedule`): the Algorithm-2 BCD loop, every DES sweep a
    `sharded_des_select_batch` call.  In-graph path (`route_mask`): the
    greedy P1(b) relaxation (same mask as `GreedyDESPolicy`) — exact
    precisely on the instances the sharded pipeline classifies easy.

    `last_stats` accumulates the easy/hard resolution split across the
    BCD iterations of the most recent `schedule` call.
    """

    def __init__(self, *, mesh=None, max_iters: int = 20,
                 beta_method: str = "auto", qos: Optional[float] = None,
                 warm_cache: Optional[des_lib.WarmStartCache] = None):
        super().__init__(max_iters=max_iters, beta_method=beta_method,
                         qos=qos, warm_cache=warm_cache)
        self.mesh = mesh
        self.last_stats: Dict[str, int] = {}

    def _batch_solver(self, stats: Dict[str, int]):
        """The drop-in `des_select_batch` front-end the sweep routes
        through — subclass hook for the pipelined / multi-process tiers
        (`repro.schedulers.async_des`)."""
        return functools.partial(
            sharded_des_select_batch, mesh=self.mesh, stats=stats,
            warm_cache=self.warm_cache)

    def _alpha_sweep(self, gate_scores, costs, qos, max_experts):
        stats: Dict[str, int] = {}
        alpha, nodes = _des_sweep(gate_scores, costs, qos, max_experts,
                                  solver=self._batch_solver(stats))
        for key, val in stats.items():
            if key in ("n_devices", "n_processes"):
                self.last_stats[key] = val
            else:
                self.last_stats[key] = self.last_stats.get(key, 0) + val
        return alpha, nodes

    def schedule(self, ctx: ScheduleContext):
        self.last_stats = {}
        return super().schedule(ctx)

    # In-graph surface: delegate to the greedy P1(b) policy so the two
    # DES routing paths can never diverge (single source of the mask).
    _greedy = GreedyDESPolicy()

    def route_mask(self, gates, *, qos=0.0, costs=None, top_k: int = 2,
                   max_experts: int = 0):
        return self._greedy.route_mask(gates, qos=qos, costs=costs,
                                       top_k=top_k, max_experts=max_experts)

    def in_graph_costs(self, num_experts: int):
        return self._greedy.in_graph_costs(num_experts)
