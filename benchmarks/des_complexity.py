"""§V-B/V-C: DES branch-and-bound search complexity — nodes explored vs
the 2^K exhaustive tree, exactness vs brute force — plus the batched
JESA alpha-step sweep benchmark (des_select_batch vs the per-(i, n)
Python loop it replaced).

CLI::

    PYTHONPATH=src python -m benchmarks.des_complexity [--quick]
        [--out BENCH_des_sweep.json] [--k 8] [--n-tokens 256]
    PYTHONPATH=src python -m benchmarks.des_complexity --quick --sharded
        [--out BENCH_des_sharded.json]
    PYTHONPATH=src python -m benchmarks.des_complexity --quick --async
        [--multihost] [--out BENCH_des_async.json]

writes a ``BENCH_des_sweep.json`` artifact recording per-layer and
overall loop-vs-batch wall-clock so the perf trajectory of the batched
solver is tracked over time.  ``--sharded`` instead benchmarks the
device-sharded front-end (`repro.schedulers.sharded`) against the host
batch solver on a multi-device mesh (forcing a 4-device host platform
when no accelerators are present), recording the in-graph easy/hard
resolution split — the easy path never runs per-instance numpy.
``--async`` benchmarks the pipelined tier
(`repro.schedulers.async_des.AsyncDESPipeline`): all rounds of the
hard-residual sweep are submitted up front so round r+1's jitted
pre-work overlaps round r's host branch-and-bound; ``--multihost``
additionally runs the sweep spread over two `jax.distributed` processes
(`repro.distributed.multihost.multihost_des_select_batch`).  Both write
into ``BENCH_des_async.json``; parity with the host solver hard-gates
every mode, wall-clock is recorded but never asserted.
"""

from __future__ import annotations

import argparse
import json
import os
import time

import numpy as np

from benchmarks.common import Timer
from repro.core import channel as channel_lib
from repro.core import des as des_lib
from repro.core import energy as energy_lib


def _loop_sweep(gates: np.ndarray, costs: np.ndarray, qos: float, d: int):
    """The pre-batching host sweep: one `des_select` per (source, token)."""
    k, n_tok, _ = gates.shape
    alpha = np.zeros((k, n_tok, k), dtype=np.int8)
    nodes = 0
    for i in range(k):
        for n in range(n_tok):
            g = gates[i, n]
            if g.sum() <= 0:
                continue
            res = des_lib.des_select(g, costs[i], qos, d)
            nodes += res.nodes_explored
            alpha[i, n] = res.selected.astype(np.int8)
    return alpha, nodes


def _alpha_step_instances(k: int, n_tokens: int, seed: int
                          ) -> tuple[np.ndarray, np.ndarray]:
    """The instances JESA solves per BCD iteration: a (K, N, K) gate
    tensor + per-source selection-cost rows under a random OFDMA
    assignment (shared by the batched and the sharded sweeps)."""
    rng = np.random.default_rng(seed)
    gates = rng.dirichlet(np.ones(k), size=(k, n_tokens))
    ccfg = channel_lib.ChannelConfig(
        num_experts=k, num_subcarriers=max(64, k * (k - 1)))
    gains = channel_lib.sample_channel_gains(ccfg, rng)
    rates = channel_lib.subcarrier_rates(ccfg, gains)
    beta = channel_lib.random_subcarrier_assignment(ccfg, rng)
    rates_kk = channel_lib.link_rates(rates, beta)
    costs = energy_lib.selection_costs(
        rates_kk, beta, energy_lib.make_comp_coeffs(k), 8192.0,
        ccfg.tx_power_w)
    return gates, costs


def run_sweep(k: int = 8, n_tokens: int = 256, d: int = 2,
              qos_z: float = 1.0, gamma0: float = 0.7, num_layers: int = 3,
              reps: int = 3, seed: int = 7, out_path: str | None = None,
              verbose: bool = True) -> dict:
    """Benchmark the JESA alpha-step sweep: batched vs per-(i, n) loop.

    Reproduces exactly the instances JESA solves per BCD iteration for
    each layer of the paper's default QoS schedule z * gamma0^l, and
    checks the selections are bit-identical.
    """
    from repro.schedulers.host import _des_sweep

    gates, costs = _alpha_step_instances(k, n_tokens, seed)

    layers = []
    identical = True
    loop_total = batch_total = 0.0
    for layer in range(1, num_layers + 1):
        qos = qos_z * gamma0 ** layer
        # warm both paths, then take the best of `reps` timings each.
        a_loop, n_loop = _loop_sweep(gates, costs, qos, d)
        a_batch, n_batch = _des_sweep(gates, costs, qos, d)
        t_loop, t_batch = [], []
        for _ in range(reps):
            t0 = time.perf_counter()
            _loop_sweep(gates, costs, qos, d)
            t_loop.append(time.perf_counter() - t0)
            t0 = time.perf_counter()
            _des_sweep(gates, costs, qos, d)
            t_batch.append(time.perf_counter() - t0)
        same = bool(np.array_equal(a_loop, a_batch) and n_loop == n_batch)
        identical &= same
        loop_total += min(t_loop)
        batch_total += min(t_batch)
        layers.append({
            "layer": layer,
            "qos": round(qos, 6),
            "loop_ms": round(min(t_loop) * 1e3, 3),
            "batch_ms": round(min(t_batch) * 1e3, 3),
            "speedup": round(min(t_loop) / min(t_batch), 2),
            "nodes": int(n_loop),
            "bit_identical": same,
        })

    summary = {
        "bench": "des_sweep",
        "k": k,
        "n_tokens": n_tokens,
        "max_experts": d,
        "qos_schedule": {"z": qos_z, "gamma0": gamma0},
        "reps": reps,
        "layers": layers,
        "loop_ms_total": round(loop_total * 1e3, 3),
        "batch_ms_total": round(batch_total * 1e3, 3),
        "speedup_overall": round(loop_total / batch_total, 2),
        "bit_identical": identical,
    }
    if verbose:
        print(f"{'layer':>6}{'qos':>8}{'loop ms':>10}{'batch ms':>10}"
              f"{'speedup':>9}{'identical':>10}")
        for row in layers:
            print(f"{row['layer']:>6}{row['qos']:>8.3f}{row['loop_ms']:>10.1f}"
                  f"{row['batch_ms']:>10.1f}{row['speedup']:>8.1f}x"
                  f"{str(row['bit_identical']):>10}")
        print(f"overall: {summary['speedup_overall']}x "
              f"({summary['loop_ms_total']:.0f} ms -> "
              f"{summary['batch_ms_total']:.0f} ms)")
    if out_path:
        with open(out_path, "w") as fh:
            json.dump(summary, fh, indent=2)
        if verbose:
            print(f"wrote {out_path}")
    return summary


def run_sharded_sweep(k: int = 8, n_tokens: int = 256, d: int = 2,
                      qos_z: float = 1.0, gamma0: float = 0.7,
                      num_layers: int = 3, reps: int = 3, seed: int = 7,
                      out_path: str | None = None,
                      verbose: bool = True) -> dict:
    """Benchmark the device-sharded DES front-end against the host batch
    solver on the JESA alpha-step instances.

    `sharded_des_select_batch` jit-compiles the pre-work (sanitize /
    feasibility screen / ratio sort / greedy seed / root LP bound) under
    `shard_map` over the batch mesh; instances the root bound resolves
    ("easy") never touch per-instance numpy — only the hard residual
    reaches the host B&B.  Results are asserted bit-identical
    (selections, energies, feasibility, node counts).
    """
    import jax

    from repro.distributed.sharding import make_batch_mesh
    from repro.schedulers.sharded import sharded_des_select_batch

    gates, costs = _alpha_step_instances(k, n_tokens, seed)
    flat = gates.reshape(k * n_tokens, k)
    cost_rows = np.repeat(costs, n_tokens, axis=0)
    mesh = make_batch_mesh()
    n_dev = len(jax.devices())

    layers = []
    identical = True
    batch_total = sharded_total = 0.0
    for layer in range(1, num_layers + 1):
        qos = qos_z * gamma0 ** layer
        stats: dict = {}
        res_batch = des_lib.des_select_batch(flat, cost_rows, qos, d)
        res_shard = sharded_des_select_batch(
            flat, cost_rows, qos, d, mesh=mesh, stats=stats)
        same = bool(
            np.array_equal(res_batch.selected, res_shard.selected)
            and np.array_equal(res_batch.energy, res_shard.energy)
            and np.array_equal(res_batch.feasible, res_shard.feasible)
            and np.array_equal(res_batch.nodes_explored,
                               res_shard.nodes_explored)
            and np.array_equal(res_batch.nodes_pruned,
                               res_shard.nodes_pruned))
        identical &= same
        t_batch, t_shard = [], []
        for _ in range(reps):  # both paths warm (jit cache hit for shard)
            t0 = time.perf_counter()
            des_lib.des_select_batch(flat, cost_rows, qos, d)
            t_batch.append(time.perf_counter() - t0)
            t0 = time.perf_counter()
            sharded_des_select_batch(flat, cost_rows, qos, d, mesh=mesh)
            t_shard.append(time.perf_counter() - t0)
        batch_total += min(t_batch)
        sharded_total += min(t_shard)
        layers.append({
            "layer": layer,
            "qos": round(qos, 6),
            "batch_ms": round(min(t_batch) * 1e3, 3),
            "sharded_ms": round(min(t_shard) * 1e3, 3),
            "easy_in_graph": stats.get("easy", 0),
            "hard_host_residual": stats.get("hard", 0),
            "infeasible_in_graph": stats.get("infeasible", 0),
            "bit_identical": same,
        })

    summary = {
        "bench": "des_sharded",
        "k": k,
        "n_tokens": n_tokens,
        "max_experts": d,
        "qos_schedule": {"z": qos_z, "gamma0": gamma0},
        "reps": reps,
        "n_devices": n_dev,
        "prework_jitted": True,  # shard_map'd jax pipeline, no numpy
        "layers": layers,
        "batch_ms_total": round(batch_total * 1e3, 3),
        "sharded_ms_total": round(sharded_total * 1e3, 3),
        "easy_in_graph_total": int(sum(r["easy_in_graph"] for r in layers)),
        "hard_host_residual_total": int(
            sum(r["hard_host_residual"] for r in layers)),
        "bit_identical": identical,
    }
    if verbose:
        print(f"devices: {n_dev} (mesh axes {dict(mesh.shape)})")
        print(f"{'layer':>6}{'qos':>8}{'batch ms':>10}{'sharded ms':>12}"
              f"{'easy':>7}{'hard':>7}{'identical':>10}")
        for row in layers:
            print(f"{row['layer']:>6}{row['qos']:>8.3f}"
                  f"{row['batch_ms']:>10.1f}{row['sharded_ms']:>12.1f}"
                  f"{row['easy_in_graph']:>7}{row['hard_host_residual']:>7}"
                  f"{str(row['bit_identical']):>10}")
        print(f"overall: {summary['easy_in_graph_total']} easy in-graph, "
              f"{summary['hard_host_residual_total']} hard -> host B&B")
    if out_path:
        with open(out_path, "w") as fh:
            json.dump(summary, fh, indent=2)
        if verbose:
            print(f"wrote {out_path}")
    return summary


def run_async_sweep(k: int = 8, n_tokens: int = 256, d: int = 2,
                    qos_z: float = 1.0, gamma0: float = 0.7,
                    num_layers: int = 3, reps: int = 3, seed: int = 7,
                    depth: int = 2, verbose: bool = True) -> dict:
    """Benchmark the async pipeline against the blocking sharded solver
    on the hard-residual sweep.

    The sync path solves the layers' rounds back to back through
    `sharded_des_select_batch`; the async path submits every round to an
    `AsyncDESPipeline` up front, so while the worker's branch-and-bound
    chews on round r's hard residual, round r+1's jitted pre-work is
    already running in-graph.  Per-round results are asserted
    bit-identical to `des_select_batch`; the wall-clock delta is the
    overlap won back.
    """
    from repro.distributed.sharding import make_batch_mesh
    from repro.schedulers.async_des import AsyncDESPipeline
    from repro.schedulers.sharded import sharded_des_select_batch

    gates, costs = _alpha_step_instances(k, n_tokens, seed)
    flat = gates.reshape(k * n_tokens, k)
    cost_rows = np.repeat(costs, n_tokens, axis=0)
    mesh = make_batch_mesh()
    qoses = [qos_z * gamma0 ** layer for layer in range(1, num_layers + 1)]

    # Warm the jit caches + assert parity for every round.
    layers = []
    identical = True
    with AsyncDESPipeline(mesh=mesh, depth=depth) as pipe:
        stats_list = [dict() for _ in qoses]
        pending = [pipe.submit(flat, cost_rows, qos, d, stats=st)
                   for qos, st in zip(qoses, stats_list)]
        for i, (qos, p) in enumerate(zip(qoses, pending)):
            res = p.result()
            ref = des_lib.des_select_batch(flat, cost_rows, qos, d)
            same = bool(
                np.array_equal(res.selected, ref.selected)
                and np.array_equal(res.energy, ref.energy)
                and np.array_equal(res.feasible, ref.feasible)
                and np.array_equal(res.nodes_explored, ref.nodes_explored)
                and np.array_equal(res.nodes_pruned, ref.nodes_pruned))
            identical &= same
            layers.append({
                "layer": i + 1,
                "qos": round(qos, 6),
                "easy_in_graph": stats_list[i].get("easy", 0),
                "hard_host_residual": stats_list[i].get("hard", 0),
                "bit_identical": same,
            })

        # Timed passes: sync sharded rounds vs pipelined rounds.
        t_sync, t_async = [], []
        for _ in range(reps):
            t0 = time.perf_counter()
            for qos in qoses:
                sharded_des_select_batch(flat, cost_rows, qos, d, mesh=mesh)
            t_sync.append(time.perf_counter() - t0)
            t0 = time.perf_counter()
            pending = [pipe.submit(flat, cost_rows, qos, d) for qos in qoses]
            for p in pending:
                p.result()
            t_async.append(time.perf_counter() - t0)

    hard_total = int(sum(r["hard_host_residual"] for r in layers))
    summary = {
        "k": k,
        "n_tokens": n_tokens,
        "max_experts": d,
        "qos_schedule": {"z": qos_z, "gamma0": gamma0},
        "reps": reps,
        "depth": depth,
        "n_devices": int(np.prod(tuple(mesh.shape.values()))),
        "layers": layers,
        "sharded_ms_total": round(min(t_sync) * 1e3, 3),
        "async_ms_total": round(min(t_async) * 1e3, 3),
        "speedup_vs_sharded": round(min(t_sync) / min(t_async), 3),
        "overlap_active": bool(depth > 1 and hard_total > 0),
        "hard_host_residual_total": hard_total,
        "easy_in_graph_total": int(sum(r["easy_in_graph"] for r in layers)),
        "bit_identical": identical,
    }
    if verbose:
        print(f"{'layer':>6}{'qos':>8}{'easy':>7}{'hard':>7}{'identical':>10}")
        for row in layers:
            print(f"{row['layer']:>6}{row['qos']:>8.3f}"
                  f"{row['easy_in_graph']:>7}{row['hard_host_residual']:>7}"
                  f"{str(row['bit_identical']):>10}")
        print(f"sync sharded rounds: {summary['sharded_ms_total']:.1f} ms, "
              f"pipelined: {summary['async_ms_total']:.1f} ms "
              f"({summary['speedup_vs_sharded']}x, overlap_active="
              f"{summary['overlap_active']})")
    return summary


def run_warm_start_sweep(k: int = 8, n_tokens: int = 256, d: int = 2,
                         qos_z: float = 1.0, gamma0: float = 0.7,
                         num_layers: int = 3, rounds: int = 3, seed: int = 7,
                         verbose: bool = True) -> dict:
    """Cross-round warm starts on the gamma-annealed alpha-step sweep.

    Serves `rounds` consecutive protocol rounds of the full 3-layer
    z * gamma0^l schedule on a COHERENT channel (no redraw between
    rounds, so each round re-solves the identical K*N instance batch —
    the regime the `WarmStartCache` exists for).  The cold tier solves
    every round from scratch; the warm tier carries one cache across
    rounds, so round 1 populates it and every later round's hard
    residual resolves from the exact tier without entering the B&B.

    Parity is asserted BEFORE any timing: warm selections / energies /
    feasibility must be bit-identical to the cold solver for every
    (round, layer), and warm node counts can only shrink.  The artifact
    records the measured split (`warm_hits`, `warm_easy`,
    `hard_before`, `hard_after`) and the cold-vs-warm round-time delta;
    the ≥50% hard-residual reduction is a hard claim gated in `main`.
    """
    from repro.distributed.sharding import make_batch_mesh
    from repro.schedulers.sharded import sharded_des_select_batch

    gates, costs = _alpha_step_instances(k, n_tokens, seed)
    flat = gates.reshape(k * n_tokens, k)
    cost_rows = np.repeat(costs, n_tokens, axis=0)
    mesh = make_batch_mesh()
    qoses = [qos_z * gamma0 ** layer for layer in range(1, num_layers + 1)]

    # ---- parity pass (untimed): warm ≡ cold for every (round, layer).
    refs = {qos: des_lib.des_select_batch(flat, cost_rows, qos, d)
            for qos in qoses}
    cache = des_lib.WarmStartCache()
    identical = True
    rows = []
    for rnd in range(1, rounds + 1):
        for layer, qos in enumerate(qoses, start=1):
            ws: dict = {}
            res = sharded_des_select_batch(flat, cost_rows, qos, d,
                                           mesh=mesh, stats=ws,
                                           warm_cache=cache)
            ref = refs[qos]
            same = bool(
                np.array_equal(res.selected, ref.selected)
                and np.array_equal(res.energy, ref.energy)
                and np.array_equal(res.feasible, ref.feasible)
                and np.all(res.nodes_explored <= ref.nodes_explored))
            identical &= same
            rows.append({
                "round": rnd,
                "layer": layer,
                "qos": round(qos, 6),
                "warm_hits": ws.get("warm_hits", 0),
                "warm_easy": ws.get("warm_easy", 0),
                "hard_before": ws.get("hard_before", 0),
                "hard_after": ws.get("hard_after", 0),
                "bit_identical": same,
            })

    hard_before = int(sum(r["hard_before"] for r in rows))
    hard_after = int(sum(r["hard_after"] for r in rows))

    # ---- timed passes (parity already proven): cold rounds vs warm
    # rounds through a fresh cache.
    t0 = time.perf_counter()
    for _ in range(rounds):
        for qos in qoses:
            sharded_des_select_batch(flat, cost_rows, qos, d, mesh=mesh)
    t_cold = time.perf_counter() - t0
    timed_cache = des_lib.WarmStartCache()
    t0 = time.perf_counter()
    for _ in range(rounds):
        for qos in qoses:
            sharded_des_select_batch(flat, cost_rows, qos, d, mesh=mesh,
                                     warm_cache=timed_cache)
    t_warm = time.perf_counter() - t0

    summary = {
        "k": k,
        "n_tokens": n_tokens,
        "max_experts": d,
        "qos_schedule": {"z": qos_z, "gamma0": gamma0},
        "rounds": rounds,
        "coherent_channel": True,
        "layers": rows,
        "warm_hits_total": int(sum(r["warm_hits"] for r in rows)),
        "warm_easy_total": int(sum(r["warm_easy"] for r in rows)),
        "hard_before": hard_before,
        "hard_after": hard_after,
        "hard_residual_ratio": round(hard_after / max(hard_before, 1), 4),
        "cold_ms_total": round(t_cold * 1e3, 3),
        "warm_ms_total": round(t_warm * 1e3, 3),
        "round_time_delta_ms": round((t_cold - t_warm) * 1e3 / rounds, 3),
        "bit_identical": identical,
    }
    if verbose:
        print(f"{'round':>6}{'layer':>6}{'qos':>8}{'hits':>7}{'before':>8}"
              f"{'after':>7}{'identical':>10}")
        for r in rows:
            print(f"{r['round']:>6}{r['layer']:>6}{r['qos']:>8.3f}"
                  f"{r['warm_hits']:>7}{r['hard_before']:>8}"
                  f"{r['hard_after']:>7}{str(r['bit_identical']):>10}")
        print(f"hard residual {hard_before} -> {hard_after} "
              f"({summary['hard_residual_ratio']:.0%}), "
              f"round time {t_cold * 1e3 / rounds:.1f} ms -> "
              f"{t_warm * 1e3 / rounds:.1f} ms")
    return summary


_MULTIHOST_WORKER = r"""
import json, sys
proc_id, port, k, n_tokens, d, num_layers, reps, seed = (
    int(v) for v in sys.argv[1:9])
qos_z, gamma0 = float(sys.argv[9]), float(sys.argv[10])
import os
os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "")
                           + " --xla_force_host_platform_device_count=2"
                           ).strip()
import time
import numpy as np
import jax
from repro.distributed import multihost
assert multihost.initialize(f"127.0.0.1:{port}", num_processes=2,
                            process_id=proc_id)
platform = jax.devices()[0].platform
print(f"multihost worker {proc_id}: platform {platform}", flush=True)
from benchmarks.des_complexity import _alpha_step_instances
from repro.core import des as des_lib

gates, costs = _alpha_step_instances(k, n_tokens, seed)
flat = gates.reshape(k * n_tokens, k)
cost_rows = np.repeat(costs, n_tokens, axis=0)
layers, identical, totals = [], True, []
for layer in range(1, num_layers + 1):
    qos = qos_z * gamma0 ** layer
    stats = {}
    res = multihost.multihost_des_select_batch(
        flat, cost_rows, qos, d, stats=stats)
    ref = des_lib.des_select_batch(flat, cost_rows, qos, d)
    same = bool(np.array_equal(res.selected, ref.selected)
                and np.array_equal(res.energy, ref.energy)
                and np.array_equal(res.feasible, ref.feasible)
                and np.array_equal(res.nodes_explored, ref.nodes_explored)
                and np.array_equal(res.nodes_pruned, ref.nodes_pruned))
    identical &= same
    t = []
    for _ in range(reps):
        t0 = time.perf_counter()
        multihost.multihost_des_select_batch(flat, cost_rows, qos, d)
        t.append(time.perf_counter() - t0)
    totals.append(min(t))
    layers.append({"layer": layer, "qos": round(qos, 6),
                   "multihost_ms": round(min(t) * 1e3, 3),
                   "local_rows": stats["batch"],
                   "hard_host_residual": stats.get("hard", 0),
                   "n_processes": stats["n_processes"],
                   "bit_identical": same})
if proc_id == 0:
    print("MULTIHOST_RESULT " + json.dumps({
        "layers": layers,
        "multihost_ms_total": round(sum(totals) * 1e3, 3),
        "bit_identical": identical,
        "platform": platform,
    }), flush=True)
"""


def run_multihost_sweep(k: int = 8, n_tokens: int = 256, d: int = 2,
                        qos_z: float = 1.0, gamma0: float = 0.7,
                        num_layers: int = 3, reps: int = 1, seed: int = 7,
                        verbose: bool = True) -> dict:
    """Run the alpha-step sweep spread over two `jax.distributed`
    processes (each with a forced 2-device host mesh) and report the
    per-process split + parity.

    Every process solves its contiguous half of the (K*N) instance batch
    on its local device mesh; results are exchanged through the
    coordination-service KV store — no cross-process XLA computations.
    The workers are put on the CPU backend explicitly (and report it):
    a parent that already ran on an accelerator holds it, and a worker
    reaching for the same chip would fail or hang.
    """
    import socket
    import subprocess
    import sys

    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        port = s.getsockname()[1]
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env["PYTHONPATH"] = os.pathsep.join(
        p for p in (os.path.join(repo, "src"), repo,
                    env.get("PYTHONPATH", "")) if p)
    argv = [str(v) for v in (k, n_tokens, d, num_layers, reps, seed,
                             qos_z, gamma0)]
    procs = [subprocess.Popen(
        [sys.executable, "-c", _MULTIHOST_WORKER, str(pid), str(port)] + argv,
        stdout=subprocess.PIPE, stderr=subprocess.PIPE, text=True,
        env=env, cwd=repo) for pid in (0, 1)]
    try:
        outs = [p.communicate(timeout=600) for p in procs]
    finally:
        # One worker dying before the KV barrier deadlocks its peer —
        # never leave live processes behind on timeout/failure.
        for p in procs:
            if p.poll() is None:
                p.kill()
                p.communicate()
    for p, (out, err) in zip(procs, outs):
        if p.returncode != 0:
            raise RuntimeError(f"multihost worker failed:\n{out}\n{err}")
    marker = next(line for line in outs[0][0].splitlines()
                  if line.startswith("MULTIHOST_RESULT "))
    result = json.loads(marker[len("MULTIHOST_RESULT "):])
    result.update(k=k, n_tokens=n_tokens, max_experts=d, reps=reps,
                  n_processes=2, local_devices_per_process=2)
    if verbose:
        for row in result["layers"]:
            print(f"layer {row['layer']} qos {row['qos']:.3f}: "
                  f"{row['multihost_ms']:.1f} ms across "
                  f"{row['n_processes']} processes "
                  f"({row['local_rows']} rows/process, "
                  f"identical={row['bit_identical']})")
        print(f"multihost total: {result['multihost_ms_total']:.1f} ms "
              f"(workers on {result['platform']})")
    return result


def run(verbose: bool = True, sweep: dict | None = None, seed: int = 3):
    rows = []
    rng = np.random.default_rng(seed)
    with Timer() as t:
        for k in (8, 12, 16, 20):
            explored, pruned, exact_hits, trials = 0, 0, 0, 10
            for i in range(trials):
                tt = rng.dirichlet(np.ones(k))
                e = rng.uniform(0.05, 2.0, size=k)
                qos = rng.uniform(0.3, 0.7)
                res = des_lib.des_select(tt, e, qos, max(2, k // 4))
                explored += res.nodes_explored
                pruned += res.nodes_pruned
                if k <= 16:
                    brute = des_lib.des_select_brute_force(
                        tt, e, qos, max(2, k // 4))
                    exact_hits += (abs(res.energy - brute.energy) < 1e-9
                                   or res.feasible != brute.feasible)
            rows.append({
                "K": k,
                "mean_nodes": explored / trials,
                "exhaustive": 2 ** k,
                "reduction_x": round(2 ** k / max(explored / trials, 1), 1),
                "exact": (exact_hits == trials) if k <= 16 else None,
            })
    if verbose:
        print(f"{'K':>4}{'nodes':>12}{'2^K':>12}{'reduction':>11}{'exact':>7}")
        for r in rows:
            print(f"{r['K']:>4}{r['mean_nodes']:>12.0f}{r['exhaustive']:>12}"
                  f"{r['reduction_x']:>10.0f}x{str(r['exact']):>7}")
    if sweep is None:
        sweep = run_sweep(reps=1, verbose=verbose)
    claims = {
        "all_exact": all(r["exact"] for r in rows if r["exact"] is not None),
        "superlinear_reduction": rows[-1]["reduction_x"]
        > rows[0]["reduction_x"],
        # Exactness is the hard gate; wall-clock is recorded (JSON + the
        # CSV derived column), never asserted, so loaded CI runners can't
        # fail the harness on a timing fluke.
        "sweep_bit_identical": sweep["bit_identical"],
    }
    csv = [("des_complexity", t.us / len(rows),
            ";".join(f"{k}={v}" for k, v in list(claims.items())[:2])),
           ("des_sweep_batched", sweep["batch_ms_total"] * 1e3,
            f"speedup={sweep['speedup_overall']}x")]
    return csv, {"complexity": rows, "sweep": sweep}, claims


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--quick", action="store_true",
                    help="single timing rep per layer (CI artifact mode)")
    ap.add_argument("--sharded", action="store_true",
                    help="benchmark the device-sharded front-end instead "
                         "(forces a 4-device host mesh if XLA_FLAGS is "
                         "not already forcing one)")
    ap.add_argument("--async", dest="async_", action="store_true",
                    help="benchmark the pipelined async tier (submit all "
                         "rounds up front; host B&B overlaps the next "
                         "round's jitted pre-work)")
    ap.add_argument("--multihost", action="store_true",
                    help="also run the sweep spread over two "
                         "jax.distributed processes (subprocess workers)")
    ap.add_argument("--depth", type=int, default=2,
                    help="async pipeline depth (in-flight rounds)")
    ap.add_argument("--out", default=None,
                    help="BENCH json path (default BENCH_des_sweep.json; "
                         "BENCH_des_sharded.json with --sharded; "
                         "BENCH_des_async.json with --async/--multihost)")
    ap.add_argument("--k", type=int, default=8)
    ap.add_argument("--n-tokens", type=int, default=256)
    ap.add_argument("--max-experts", type=int, default=2)
    args = ap.parse_args()
    if args.async_ or args.multihost:
        # One combined "des_async" artifact covering the pipelined and
        # the multi-process tier; the mesh choice must precede backend
        # init, so force a 4-device host platform like --sharded does.
        flags = os.environ.get("XLA_FLAGS", "")
        if "--xla_force_host_platform_device_count" not in flags:
            os.environ["XLA_FLAGS"] = (
                flags + " --xla_force_host_platform_device_count=4").strip()
        reps = 1 if args.quick else 3
        summary: dict = {"bench": "des_async"}
        if args.async_:
            summary["async"] = run_async_sweep(
                k=args.k, n_tokens=args.n_tokens, d=args.max_experts,
                reps=reps, depth=args.depth)
            summary["warm_start"] = run_warm_start_sweep(
                k=args.k, n_tokens=args.n_tokens, d=args.max_experts)
            summary["claims"] = {
                # ≥50% of the gamma-annealed hard residual resolved by the
                # carried cache on the coherent-channel round sequence.
                "warm_start_resolves_hard_residual":
                    summary["warm_start"]["hard_after"]
                    <= 0.5 * summary["warm_start"]["hard_before"],
            }
        if args.multihost:
            summary["multihost"] = run_multihost_sweep(
                k=args.k, n_tokens=args.n_tokens, d=args.max_experts,
                reps=reps)
        out = args.out or "BENCH_des_async.json"
        with open(out, "w") as fh:
            json.dump(summary, fh, indent=2)
        print(f"wrote {out}")
        for key in ("async", "warm_start", "multihost"):
            if key in summary and not summary[key]["bit_identical"]:
                raise SystemExit(
                    f"{key} sweep diverged from des_select_batch")
        for claim, ok in summary.get("claims", {}).items():
            if not ok:
                raise SystemExit(f"claim failed: {claim}")
        return
    if args.sharded:
        # Must be decided before jax initializes its backend: give the
        # host platform 4 devices so the mesh genuinely shards.
        flags = os.environ.get("XLA_FLAGS", "")
        if "--xla_force_host_platform_device_count" not in flags:
            os.environ["XLA_FLAGS"] = (
                flags + " --xla_force_host_platform_device_count=4").strip()
        sweep = run_sharded_sweep(
            k=args.k, n_tokens=args.n_tokens, d=args.max_experts,
            reps=1 if args.quick else 3,
            out_path=args.out or "BENCH_des_sharded.json")
        if not sweep["bit_identical"]:
            raise SystemExit("sharded sweep diverged from des_select_batch")
        return
    sweep = run_sweep(k=args.k, n_tokens=args.n_tokens, d=args.max_experts,
                      reps=1 if args.quick else 3,
                      out_path=args.out or "BENCH_des_sweep.json")
    if not args.quick:
        run(sweep=sweep)  # node-count study reuses the sweep measurement
    if not sweep["bit_identical"]:  # exactness gates even --quick CI runs
        raise SystemExit("batched sweep diverged from the per-(i,n) loop")


if __name__ == "__main__":
    main()
