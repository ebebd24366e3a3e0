"""Driver for the serving engine: `ServingEngine.serve`, jitted prefill
then cached greedy decode.

Closed loop of whole batches: `batch` requests are sent together, and the
next batch when every token of the last one is on the host.  Prompts are
drawn from the seed with lengths uniform over [prompt_min, prompt_max],
and every batch holds one prompt of prompt_max, so the padded prefill
shape never changes; each request asks for `new_tokens` tokens.

Traffic parameters (`bench/traffic/<mix>.json`):
  routing           the in-graph router the engine runs ("topk": the
                    published top-k of the softmax gate)
  capacity_factor   expert capacity; E / top_k makes the dispatch
                    dropless, as the published model is
  routing_impl      the engine's token dispatch ("xla" or a kernel)
  batch, prompt_min, prompt_max, new_tokens, max_len
  request_batches   distinct batches the seed draws; the window cycles
  warmup_batches    batches served before the window (set-up)
  check_batches     batches of the window, drawn from the seed, whose
                    served tokens the reference checks
"""

from __future__ import annotations

import gc
import time

import numpy as np

from common import percentile
from flops.engine import batch_flops
from reference import moe_ref
from weights import make_params, program_weights


class Driver:
    def __init__(self, cell: dict, config: dict, traffic: dict, seed: int):
        self.cell, self.config, self.traffic = cell, config, traffic
        self.seed = int(seed)
        streams = np.random.SeedSequence(self.seed).spawn(2)
        self.rng_requests = np.random.default_rng(streams[0])
        self.rng_check = np.random.default_rng(streams[1])

    def program_config(self):
        from repro.configs.base import get_config

        t = self.traffic
        return get_config(self.config["repo_config"]).with_overrides(
            **self.config["overrides"], moe_routing=t["routing"],
            moe_routing_kwargs=(), moe_capacity_factor=t["capacity_factor"])

    def _prompts(self):
        t, v = self.traffic, self.config["vocab_size"]
        lens = [t["prompt_max"], *self.rng_requests.integers(
            t["prompt_min"], t["prompt_max"] + 1, t["batch"] - 1)]
        return [self.rng_requests.integers(0, v, n, dtype=np.int32)
                for n in lens]

    def setup(self) -> None:
        from repro.serving import ServingEngine

        t = self.traffic
        self.cfg = cfg = self.program_config()
        self.params = make_params(cfg, self.seed)
        with program_weights(self.params):
            self.engine = ServingEngine(
                cfg, max_batch=t["batch"], max_len=t["max_len"],
                seed=self.seed, routing_impl=t["routing_impl"])
        self.pool = [self._prompts() for _ in range(t["request_batches"])]
        for i in range(t["warmup_batches"]):
            self._serve(self.pool[i % len(self.pool)])

    def _serve(self, prompts):
        import jax
        from repro.serving import Request

        reqs = [Request(uid=i, prompt=p,
                        max_new_tokens=self.traffic["new_tokens"])
                for i, p in enumerate(prompts)]
        with jax.profiler.TraceAnnotation("bench.batch"):
            self.engine.serve(reqs)            # ends on host token copies
        return np.stack([r.output for r in reqs])

    def window(self, seconds: float) -> dict:
        import jax

        t = self.traffic
        keep_n = t["check_batches"]
        self.kept, lat, flops = [], [], 0.0
        t_start = time.perf_counter()
        with jax.profiler.TraceAnnotation("bench.window"):
            i = 0
            while True:
                prompts = self.pool[i % len(self.pool)]
                t0 = time.perf_counter()
                served = self._serve(prompts)
                t1 = time.perf_counter()
                lat.append(t1 - t0)
                flops += batch_flops(self.config, t["batch"],
                                     t["prompt_max"], t["new_tokens"])
                item = (prompts, served)
                if i < keep_n:
                    self.kept.append(item)
                else:
                    j = int(self.rng_check.integers(0, i + 1))
                    if j < keep_n:
                        self.kept[j] = item
                i += 1
                if t1 - t_start >= seconds:
                    break
        window_s = t1 - t_start
        batches = len(lat)
        per_request = np.repeat(np.asarray(lat), t["batch"])
        return {
            "attempted": batches * t["batch"],
            "failed": 0,
            "metrics": {
                "tok_s": batches * t["batch"] * t["new_tokens"] / window_s,
                "lat_p95_ms": 1e3 * percentile(per_request, 95)},
            "obs": {"window_s": window_s, "required_flops": flops},
        }

    def release(self) -> None:
        del self.engine
        gc.collect()

    def readings(self, control: bool = False) -> dict:
        """The widest gap by which a served token's reference logit lies
        below the reference's best at its position.  The reference reads
        each prompt as the engine framed it: left-padded with token 0 to
        the batch's longest prompt.  With `control`, the float8 reference
        stands in the program's place: the gap of the token that it puts
        first at each of those positions."""
        dims = moe_ref.Dims.from_config(self.config)
        top_k = self.config["num_experts_per_tok"]
        plen = self.traffic["prompt_max"]
        out = {"served_gap": 0.0}
        for prompts, served in self.kept:
            rows = np.zeros((len(prompts), plen), dtype=np.int32)
            for r, p in enumerate(prompts):
                rows[r, plen - len(p):] = p
            served = served.astype(np.int32)
            seq = np.concatenate([rows, served], axis=1)
            want = moe_ref.routed_forward(self.params, seq, dims, top_k)
            if control:
                low = moe_ref.routed_forward(self.params, seq, dims, top_k,
                                             mode="fp8")
                served = np.asarray(
                    low[:, -served.shape[1] - 1:-1].argmax(-1), np.int32)
            gaps = moe_ref.served_gaps(want, served)
            out["served_gap"] = max(out["served_gap"], float(gaps.max()))
        return out
