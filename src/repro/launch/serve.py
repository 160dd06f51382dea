"""Serving driver: host a model with FCFS or CFS+AQUA scheduling.

    PYTHONPATH=src python -m repro.launch.serve --arch qwen1.5-0.5b --smoke \
        --scheduler cfs --offload fabric --requests 8

``build_engine`` is the one way this repository builds a served model: the
CLI below and ``chip_smoke.py`` at the repository root both call it.
"""
from __future__ import annotations

import argparse
import os
from pathlib import Path

import jax
import numpy as np

# the persistent compile cache sits at a FIXED path: the directory is part
# of the cache key, so a moving path would never hit
COMPILE_CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn on JAX's persistent compilation cache; call before the first
    compile. Where ``JAX_COMPILATION_CACHE_DIR`` is set JAX reads it itself
    and nothing is set here; otherwise the cache lives in ``.jax_cache/`` at
    the repository root. Returns the directory in use."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(COMPILE_CACHE_DIR))
    return str(COMPILE_CACHE_DIR)


def build_engine(arch: str, *, smoke: bool = False, seed: int = 0,
                 scheduler: str = "cfs", offload: str = "fabric",
                 max_running: int = 2, max_seq: int = 96,
                 slice_tokens: int = 3, step_tokens=None, mesh=None,
                 lease_contexts: int = 8):
    """Build a ``ServingEngine`` for ``arch`` with random weights from
    ``seed``.

    ``offload="fabric"`` parks to the REMOTE tier: the donors together lease
    room for ``lease_contexts`` full-length (``max_seq``) request contexts,
    counted in whole pages of each plane, split evenly across one donor per
    peer device of ``mesh`` (a ``MeshTierDomain``), or one same-device donor
    without a mesh. ``offload="host"`` leases nothing and parks to host
    memory.

    Raises:
        ValueError: the serving device cannot hold a second copy of the
            LOCAL pools next to what is already resident (the fused step
            returns new pools without donating the old ones).
    """
    from repro.configs import get_config, smoke_config
    from repro.core.aqua_tensor import HOST, REMOTE
    from repro.models import api
    from repro.serving.engine import ServingEngine

    cfg = get_config(arch)
    if smoke:
        cfg = smoke_config(cfg)
    params = jax.jit(api.init_params, static_argnums=1)(
        jax.random.PRNGKey(seed), cfg)
    eng = ServingEngine(cfg, params, max_running=max_running,
                        max_seq=max_seq, scheduler=scheduler,
                        slice_tokens=slice_tokens, step_tokens=step_tokens,
                        offload_tier=REMOTE if offload == "fabric" else HOST,
                        mesh=mesh)
    planes = list(eng.kv.planes.values())
    if offload == "fabric":
        donors = ([f"donor{i}" for i in range(1, mesh.n_dev)]
                  if mesh is not None else ["donor0"])
        context_bytes = sum(int(n) * p.aqua.page_bytes for n, p in
                            zip(eng.kv.pages_per_request(max_seq), planes))
        for donor in donors:
            eng.pager.add_remote_lease(
                donor, lease_contexts * context_bytes / len(donors))
    pool_bytes = sum(p.aqua.local_pool.nbytes for p in planes)
    stats = jax.devices()[0].memory_stats() or {}
    if "bytes_limit" in stats and (stats["bytes_in_use"] + pool_bytes
                                   > stats["bytes_limit"]):
        raise ValueError(
            f"{cfg.name}: the fused step's second copy of the LOCAL pools "
            f"({pool_bytes} B) does not fit next to the "
            f"{stats['bytes_in_use']} B resident on a {stats['bytes_limit']}"
            " B device; lower max_running or max_seq")
    return eng


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--scheduler", choices=["fcfs", "cfs"], default="cfs")
    ap.add_argument("--offload", choices=["fabric", "host"], default="fabric")
    ap.add_argument("--requests", type=int, default=8)
    ap.add_argument("--max-running", type=int, default=2)
    ap.add_argument("--max-seq", type=int, default=96)
    ap.add_argument("--max-new-tokens", type=int, default=8)
    ap.add_argument("--slice-tokens", type=int, default=3)
    args = ap.parse_args()

    enable_compile_cache()
    eng = build_engine(args.arch, smoke=args.smoke, scheduler=args.scheduler,
                       offload=args.offload, max_running=args.max_running,
                       max_seq=args.max_seq, slice_tokens=args.slice_tokens,
                       lease_contexts=args.requests)
    cfg = eng.cfg
    print(f"runtime: unified paged state "
          f"(planes: {', '.join(eng.kv.planes)})")
    rng = np.random.default_rng(0)
    for i in range(args.requests):
        eng.submit(list(map(int, rng.integers(0, cfg.vocab_size, 8))),
                   args.max_new_tokens, arrival=0.1 * i)
    m = eng.run(2000)
    print(f"served {len(eng.finished)} requests in {m.steps} engine steps "
          f"({m.sim_time:.2f} simulated s)")
    print(f"prefills={m.prefills} preemptions={m.preemptions} "
          f"restores={m.restores}")
    print(f"max fairness spread: {max(m.fairness_trace)} tokens "
          f"(CFS bounds this; FCFS does not)")
    print("AQUA pager:", eng.pager.stats())


if __name__ == "__main__":
    main()
