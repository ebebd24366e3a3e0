"""Multi-process scheduler tier: `jax.distributed` bootstrap, the
process-spanning batch mesh, and host-level batch spreading for the DES
front-end.

`repro.schedulers.sharded` spreads one process's (B, K) DES instance
batch over the *local* devices.  This module spans processes, so a
serving deployment can spread scheduler load across hosts:

  * `initialize` — idempotent wrapper around `jax.distributed.initialize`
    (coordinator address / process count / process id, or the env-var &
    cluster autodetection jax ships);
  * `make_global_batch_mesh` — `make_batch_mesh` generalized to every
    device of every process (the 1-D "batch" axis spans the cluster);
  * `process_slice` — the contiguous partition of a length-B batch this
    process owns;
  * `kv_allgather` — host-level allgather of opaque bytes through the
    jax coordination-service KV store;
  * `multihost_des_select_batch` — drop-in `des_select_batch`: each
    process solves its slice with the local device-sharded pipeline and
    the per-row results are allgathered, bit-identical to the
    single-process solver.

Why host-level spreading instead of a cross-process `shard_map`?  The
scheduler batch is *host* data (numpy gate scores + CSI) and the hard
residual ends on the host B&B anyway — and the CPU backend, which runs
the CI parity tests, cannot execute multiprocess XLA computations at all
("Multiprocess computations aren't implemented on the CPU backend").
Slicing at the host boundary keeps every byte of device work inside a
process (where `repro.schedulers.sharded` already shards it) and uses
the coordination service — which works on every backend — only for the
tiny result exchange.  `make_global_batch_mesh` still exposes the
process-spanning mesh for accelerator deployments that want a global
`shard_map` (see docs/scaling.md).

All processes must call the collective helpers in the same order with
the same shapes (SPMD-style), exactly like any `jax.distributed`
program.
"""

from __future__ import annotations

import io
import itertools
from typing import List, Optional

import numpy as np

_TAGS = itertools.count()


# ----------------------------------------------------------------------
# runtime bootstrap + topology
# ----------------------------------------------------------------------

def initialize(coordinator_address: Optional[str] = None,
               num_processes: Optional[int] = None,
               process_id: Optional[int] = None,
               **kwargs) -> bool:
    """Idempotent `jax.distributed.initialize`.

    Returns True when a multi-process runtime is active.  A no-op
    (returning whether one was already active) when the runtime is up.
    Called with no arguments, jax's own cluster autodetection (SLURM,
    TPU pod, GKE, `JAX_COORDINATOR_ADDRESS`, ...) gets a shot; in a
    plain single-process environment that detection fails fast and this
    returns False without raising — the same call site runs unmodified
    on a laptop and on a fleet.  Explicit arguments pass through
    verbatim and *their* failures do raise (the caller asked for a
    specific topology).

    Must run before any other jax API touches the backend (device
    queries freeze the topology).  Extra kwargs (`local_device_ids`,
    `cluster_detection_method`, `initialization_timeout`, ...) pass
    through to `jax.distributed.initialize`.
    """
    import jax

    if is_initialized():
        return process_count() > 1
    explicit = (coordinator_address is not None
                or num_processes is not None or process_id is not None
                or kwargs)
    if explicit:
        jax.distributed.initialize(coordinator_address=coordinator_address,
                                   num_processes=num_processes,
                                   process_id=process_id, **kwargs)
        return process_count() > 1
    try:
        jax.distributed.initialize()
    except (RuntimeError, ValueError):
        # No coordinator anywhere (args, env vars, detectable cluster):
        # jax raises immediately — the single-process no-op path.
        return False
    return process_count() > 1


def is_initialized() -> bool:
    """True iff the `jax.distributed` runtime is up in this process."""
    import jax
    return jax.distributed.is_initialized()


def coordination_client():
    """The coordination-service client (KV store + barriers), or None in
    single-process mode.  jax exposes no public handle to it; the
    runtime's state object lives in `jax._src.distributed`."""
    from jax._src import distributed as _dist
    return _dist.global_state.client


def process_count() -> int:
    import jax
    return jax.process_count() if is_initialized() else 1


def process_index() -> int:
    import jax
    return jax.process_index() if is_initialized() else 0


# ----------------------------------------------------------------------
# meshes + batch partitioning
# ----------------------------------------------------------------------

def make_global_batch_mesh(devices=None):
    """`make_batch_mesh` generalized across processes: a 1-D ("batch",)
    mesh over every device of every process (`jax.devices()` is the
    global view once `initialize` ran).  Identical to the local mesh in
    single-process runs.

    Note: computations over a process-spanning mesh need a backend with
    multiprocess execution (GPU/TPU); the CPU backend only supports the
    host-level spreading of `multihost_des_select_batch`.
    """
    import jax

    from repro.distributed.sharding import make_batch_mesh

    return make_batch_mesh(jax.devices() if devices is None else devices)


def local_batch_mesh():
    """The 1-D ("batch",) mesh over this process's own devices — what
    `multihost_des_select_batch` hands to the sharded solver."""
    import jax

    from repro.distributed.sharding import make_batch_mesh

    return make_batch_mesh(jax.local_devices())


def process_slice(n: int, *, count: Optional[int] = None,
                  index: Optional[int] = None) -> slice:
    """The contiguous rows of a length-n batch owned by one process.

    Balanced to within one row (`np.array_split` boundaries): the first
    ``n % count`` processes take one extra row.  Defaults to this
    process's position in the live runtime.
    """
    count = process_count() if count is None else count
    index = process_index() if index is None else index
    if not 0 <= index < count:
        raise ValueError(f"process index {index} not in [0, {count})")
    base, extra = divmod(n, count)
    lo = index * base + min(index, extra)
    return slice(lo, lo + base + (1 if index < extra else 0))


# ----------------------------------------------------------------------
# host-level collectives (coordination-service KV store)
# ----------------------------------------------------------------------

def kv_allgather(payload: bytes, *, tag: Optional[str] = None,
                 timeout_ms: int = 60_000) -> List[bytes]:
    """Allgather opaque bytes across processes, in process order.

    Publishes this process's payload under a per-round key in the
    coordination-service KV store, fetches every process's payload, and
    deletes the own key after a barrier.  Works on every backend (no XLA
    collectives involved).  `tag` must be identical across processes for
    one logical round; by default a module-level counter supplies it,
    which is correct precisely when all processes call in the same order
    (the SPMD contract stated in the module docstring).

    Single-process: returns ``[payload]`` without touching any service.
    """
    if process_count() == 1:
        return [payload]
    client = coordination_client()
    tag = f"repro/allgather/{next(_TAGS)}" if tag is None else tag
    me = process_index()
    client.key_value_set_bytes(f"{tag}/{me}", payload)
    out = [client.blocking_key_value_get_bytes(f"{tag}/{p}", timeout_ms)
           for p in range(process_count())]
    client.wait_at_barrier(f"{tag}/done", timeout_ms)
    client.key_value_delete(f"{tag}/{me}")
    return out


def _pack_result(res) -> bytes:
    buf = io.BytesIO()
    np.savez(buf, selected=res.selected, energy=res.energy,
             feasible=res.feasible, nodes_explored=res.nodes_explored,
             nodes_pruned=res.nodes_pruned)
    return buf.getvalue()


def _unpack_result(raw: bytes):
    with np.load(io.BytesIO(raw), allow_pickle=False) as z:
        return {key: z[key] for key in z.files}


# ----------------------------------------------------------------------
# the multi-process DES front-end
# ----------------------------------------------------------------------

def multihost_des_select_batch(
    scores: np.ndarray,
    costs: np.ndarray,
    qos: np.ndarray | float,
    max_experts: int,
    *,
    force_include: Optional[np.ndarray] = None,
    deduplicate: bool = True,
    mesh=None,
    stats: Optional[dict] = None,
):
    """Drop-in `des_select_batch` spread over every process.

    Each process solves its `process_slice` of the batch with
    `repro.schedulers.sharded.sharded_des_select_batch` on its local
    device mesh (easy rows in-graph, hard residual on the local host
    B&B), then the per-row results are `kv_allgather`'d so every process
    returns the identical, complete `repro.core.des.DESBatchResult` —
    bit-identical to the single-process solver, since slicing a batch
    never changes per-row results.

    All processes must call with identical arguments (each holds the
    full gate/CSI state; only the solve is spread).  `mesh` overrides
    the *local* mesh; `stats` gains ``n_processes`` plus this process's
    local resolution split.
    """
    from repro.core import des as des_lib
    from repro.schedulers.sharded import sharded_des_select_batch

    n_proc = process_count()
    if n_proc == 1:
        res = sharded_des_select_batch(
            scores, costs, qos, max_experts, force_include=force_include,
            deduplicate=deduplicate, mesh=mesh, stats=stats)
        if stats is not None:
            stats["n_processes"] = 1
        return res

    t, e_raw, z, forced = des_lib._batch_inputs(
        scores, costs, qos, force_include)
    sl = process_slice(t.shape[0])
    local = sharded_des_select_batch(
        t[sl], e_raw[sl], z[sl], max_experts, force_include=forced[sl],
        deduplicate=deduplicate, mesh=mesh or local_batch_mesh(),
        stats=stats)
    if stats is not None:
        stats["n_processes"] = n_proc
    parts = [_unpack_result(raw) for raw in kv_allgather(
        _pack_result(local))]
    return des_lib.DESBatchResult(
        np.concatenate([p["selected"] for p in parts]),
        np.concatenate([p["energy"] for p in parts]),
        np.concatenate([p["feasible"] for p in parts]),
        np.concatenate([p["nodes_explored"] for p in parts]),
        np.concatenate([p["nodes_pruned"] for p in parts]))
