"""One-launch fused engine step benchmark.

Measures what fusing the whole engine step into ONE jitted call buys over
the per-request path (one call per admitted request's chunk + one decode
call), at two scales:

  * engine     — REAL numerics (smoke model, fused runtime): fused calls
                 issued (``EngineMetrics.fused_calls``, one per step with
                 work), step-time p50/p99 on the analytic clock,
                 speculative chunk-ahead counters, and the fused entry
                 point's jit trace count across two waves (flat in request
                 count).
  * simulator  — paper scale (CodeLlama-34B on A100): step-time p50/p99 and
                 decode-lane throughput at 1-64 concurrent requests, fused
                 vs per-request launch pricing (``ModelCost.launch_time``).

The headline claims: one fused call per step whatever the number of
admitted requests, and step-time p99 no worse than the per-request
baseline at 16+ concurrent requests.

Writes ``BENCH_fused_step.json`` next to the repo root so the perf
trajectory is tracked across PRs.

    PYTHONPATH=src python -m benchmarks.fused_step
"""
from __future__ import annotations

import json
import os
from typing import Dict

import numpy as np

from benchmarks.common import pct as _pct

STEP_TOKENS = 16
SIM_CONCURRENCY = (1, 2, 4, 8, 16, 32, 64)


def measure_engine(arch: str = "qwen1.5-0.5b", n_requests: int = 12,
                   max_seq: int = 96) -> Dict[str, Dict]:
    import jax
    from repro.configs import get_config, smoke_config
    from repro.core.aqua_tensor import REMOTE
    from repro.models import api, lm
    from repro.serving.engine import ServingEngine

    cfg = smoke_config(get_config(arch))
    params = api.init_params(jax.random.PRNGKey(0), cfg)
    rng = np.random.default_rng(11)
    lengths = [int(rng.integers(5, 40)) for _ in range(n_requests)]

    def serve(lens, spec):
        eng = ServingEngine(cfg, params, max_running=4, max_seq=max_seq,
                            scheduler="cfs", slice_tokens=3,
                            offload_tier=REMOTE, step_tokens=STEP_TOKENS,
                            spec_chunk_ahead=spec)
        eng.pager.add_remote_lease("donor0", 2 ** 24)
        for n in lens:
            eng.submit(list(map(int, rng.integers(0, cfg.vocab_size, n))),
                       4, arrival=0.0)
        m = eng.run(2000)
        assert len(eng.finished) == len(lens)
        return eng, m

    jax.clear_caches()
    lm.reset_trace_counts()
    _, m = serve(lengths, True)
    traces_w1 = lm.trace_counts().get("serve_step", 0)
    # wave 2: 2x the requests, all-new lengths — zero new traces
    serve([int(rng.integers(5, 40)) for _ in range(2 * n_requests)], True)
    traces_w2 = lm.trace_counts().get("serve_step", 0)
    _, m_nospec = serve(lengths, False)

    return {
        "fused": {
            # one serve_step_paged dispatch per step with work
            "fused_calls": m.fused_calls,
            "step_time_p50_s": _pct(m.step_times, 0.50),
            "step_time_p99_s": _pct(m.step_times, 0.99),
            "sim_time_s": float(m.sim_time),
            "steps": m.steps,
            "prefill_chunk_rows": m.prefills,
            "spec_chunks": m.spec_chunks,
            "spec_tokens": m.spec_tokens,
            "jit_traces_wave1": traces_w1,
            "jit_traces_wave2": traces_w2,
        },
        "no_speculation": {
            "sim_time_s": float(m_nospec.sim_time),
            "spec_chunks": m_nospec.spec_chunks,
        },
    }


def measure_simulator(prompt_len: int = 800, gen_len: int = 40
                      ) -> Dict[str, Dict]:
    from repro.configs import get_config
    from repro.core.perfmodel import A100_NVLINK, ModelCost
    from repro.core.simulator import Request, ServingSimulator

    cfg = get_config("aqua-codellama-34b")
    mc = ModelCost.from_config(cfg)
    wb = cfg.param_count() * 2

    def run(fused, n):
        sim = ServingSimulator(A100_NVLINK, mc, weight_bytes=wb,
                               kv_capacity_bytes=80e9 - wb - 2e9,
                               scheduler="cfs", offload_tier="fabric",
                               max_running=n, step_tokens=256,
                               fused_step=fused)
        reqs = [Request(i, 0.0005 * i, prompt_len, gen_len)
                for i in range(n)]
        res = sim.run(reqs)
        steps = np.diff([0.0] + [e["t"] for e in res.timeline])
        makespan = max(r.finish for r in res.requests)
        return {
            "step_time_p50_s": _pct(list(steps), 0.50),
            "step_time_p99_s": _pct(list(steps), 0.99),
            "decode_tokens_per_s": float(n * gen_len / makespan),
            "makespan_s": float(makespan),
            # launches per engine STEP: fused = n_layers; baseline adds one
            # call per granted chunk of the step's run set
            "launches_per_step": mc.n_layers if fused else None,
        }

    out: Dict[str, Dict] = {}
    for n in SIM_CONCURRENCY:
        out[f"c{n:02d}"] = {
            "concurrent": n,
            "fused": run(True, n),
            "per_request": run(False, n),
        }
    return out


def measure() -> Dict:
    eng = measure_engine()
    sim = measure_simulator()
    at16 = sim["c16"]
    at64 = sim["c64"]
    return {
        "engine": {"step_tokens": STEP_TOKENS, **eng},
        "simulator_34b": {"step_tokens": 256, **sim},
        "derived": {
            "engine/one_call_per_step":
                eng["fused"]["fused_calls"] <= eng["fused"]["steps"],
            "engine/jit_traces_flat_across_request_counts":
                eng["fused"]["jit_traces_wave2"]
                == eng["fused"]["jit_traces_wave1"],
            "sim/p99_no_worse_at_16":
                at16["fused"]["step_time_p99_s"]
                <= at16["per_request"]["step_time_p99_s"],
            "sim/p99_improvement_x_at_64":
                at64["per_request"]["step_time_p99_s"]
                / at64["fused"]["step_time_p99_s"],
            "sim/decode_throughput_gain_at_64":
                at64["fused"]["decode_tokens_per_s"]
                / at64["per_request"]["decode_tokens_per_s"],
        },
    }


def run(m: Dict | None = None):
    m = m or measure()
    rows = []
    for key, cell in m["simulator_34b"].items():
        if not isinstance(cell, dict):
            continue
        for variant in ("fused", "per_request"):
            for k, v in cell[variant].items():
                if v is not None:
                    rows.append((f"fused_step/{key}/{variant}/{k}", v, ""))
    for k, v in m["derived"].items():
        rows.append((f"fused_step/{k}", float(v),
                     "fused vs per-request step"))
    return rows


def main():
    m = measure()
    out = os.path.join(os.path.dirname(__file__), "..",
                       "BENCH_fused_step.json")
    with open(out, "w") as f:
        json.dump(m, f, indent=2, sort_keys=True)
    print(f"# wrote {os.path.normpath(out)}")
    print("name,value,derived")
    for name, val, derived in run(m):
        print(f"{name},{val:.6g},{derived}")


if __name__ == "__main__":
    main()
