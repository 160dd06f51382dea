"""Token-choice top-k MoE with capacity-based dispatch (GShard-style).

Dispatch materializes (E, C, d) expert inputs so the expert matmuls run as
grouped einsums with the expert axis shardable over the "model" mesh axis
(expert parallelism); XLA SPMD inserts the all-to-alls at the scatter/gather.
Shared experts (DeepSeek) are always-on dense FFNs added to the routed output.
"""
from __future__ import annotations

import math
from typing import Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.layers.core import _act, init_linear, trunc_normal


def expert_ff(cfg: ModelConfig) -> int:
    return (cfg.moe.d_ff_expert or cfg.d_ff)


def init_moe(key, cfg: ModelConfig) -> dict:
    m = cfg.moe
    d, fe = cfg.d_model, expert_ff(cfg)
    dt = cfg.dtype()
    ks = jax.random.split(key, 5)
    glu = cfg.activation in ("swiglu", "geglu")
    std_in, std_out = 1.0 / math.sqrt(d), 1.0 / math.sqrt(fe)
    p = {
        "router": init_linear(ks[0], d, m.n_experts, dt),
        "up": trunc_normal(ks[1], (m.n_experts, d, fe), std_in, dt),
        "down": trunc_normal(ks[2], (m.n_experts, fe, d), std_out, dt),
    }
    if glu:
        p["gate"] = trunc_normal(ks[3], (m.n_experts, d, fe), std_in, dt)
    if m.n_shared_experts:
        fs = fe * m.n_shared_experts
        p["shared"] = {
            "up": init_linear(ks[4], d, fs, dt),
            "down": init_linear(jax.random.fold_in(ks[4], 1), fs, d, dt, std=1.0 / math.sqrt(fs)),
        }
        if glu:
            p["shared"]["gate"] = init_linear(jax.random.fold_in(ks[4], 2), d, fs, dt)
    return p


def moe_capacity(cfg: ModelConfig, n_tokens: int) -> int:
    m = cfg.moe
    c = int(math.ceil(n_tokens * m.top_k * m.capacity_factor / m.n_experts))
    return max(8, -(-c // 8) * 8)   # round up to 8 for TPU lane alignment


def _route(params, cfg: ModelConfig, xt):
    """Router: probs, normalized top-k gates, and the Switch aux loss."""
    m = cfg.moe
    N = xt.shape[0]
    E, K = m.n_experts, m.top_k
    logits = xt @ params["router"]["w"].astype(jnp.float32)
    probs = jax.nn.softmax(logits, axis=-1)                       # (N,E) f32
    gate_vals, expert_idx = jax.lax.top_k(probs, K)               # (N,K)
    gate_vals = gate_vals / jnp.clip(gate_vals.sum(-1, keepdims=True), 1e-9)
    me = probs.mean(axis=0)
    onehot = jax.nn.one_hot(expert_idx, E, dtype=jnp.float32)     # (N,K,E)
    ce = onehot.sum(axis=(0, 1)) / (N * K)
    aux = E * jnp.sum(me * ce)
    return gate_vals, expert_idx, onehot, aux


def _dispatch_ffn(cfg: ModelConfig, xt, gate_vals, expert_idx, onehot,
                  up_w, gate_w, down_w, C: int, e_lo):
    """Capacity dispatch + expert FFN + combine for experts [e_lo, e_lo+El).

    e_lo is a traced scalar under expert parallelism (shard-local expert
    offset) and 0 in the single-shard path. Tokens routed outside the local
    range are masked out of the dispatch; the caller psums partial outputs.
    """
    N, d = xt.shape
    K = gate_vals.shape[1]
    El = up_w.shape[0]
    local_slot = expert_idx - e_lo                                # (N,K)
    is_local = (local_slot >= 0) & (local_slot < El)
    oh_local = jnp.where(is_local[..., None],
                         jax.nn.one_hot(local_slot, El, dtype=jnp.float32), 0.0)
    flat = oh_local.reshape(N * K, El)
    pos = jnp.sum((jnp.cumsum(flat, axis=0) - flat) * flat, -1).astype(jnp.int32)
    keep = (pos < C) & is_local.reshape(N * K)
    eidx = jnp.clip(local_slot.reshape(N * K), 0, El - 1)
    dest = jnp.where(keep, eidx * C + pos, El * C)                # overflow slot

    xr = jnp.broadcast_to(xt[:, None, :], (N, K, d)).reshape(N * K, d)
    buf = jnp.zeros((El * C + 1, d), xt.dtype).at[dest].add(
        jnp.where(keep[:, None], xr, 0.0))
    xd = buf[: El * C].reshape(El, C, d)

    up = jnp.einsum("ecd,edf->ecf", xd, up_w.astype(xt.dtype))
    if gate_w is not None:
        g = jnp.einsum("ecd,edf->ecf", xd, gate_w.astype(xt.dtype))
        h = _act(cfg.activation, g) * up
    else:
        h = _act(cfg.activation, up)
    y_exp = jnp.einsum("ecf,efd->ecd", h, down_w.astype(xt.dtype))

    y_flat = y_exp.reshape(El * C, d)
    y_asn = jnp.where(keep[:, None], y_flat[jnp.clip(dest, 0, El * C - 1)], 0.0)
    w = (gate_vals.reshape(N * K) * keep).astype(xt.dtype)
    return (y_asn * w[:, None]).reshape(N, K, d).sum(axis=1)


def _shared_experts(params, cfg: ModelConfig, xt):
    sp = params["shared"]
    su = xt @ sp["up"]["w"].astype(xt.dtype)
    if "gate" in sp:
        sh = _act(cfg.activation, xt @ sp["gate"]["w"].astype(xt.dtype)) * su
    else:
        sh = _act(cfg.activation, su)
    return sh @ sp["down"]["w"].astype(xt.dtype)


def moe_apply(params, cfg: ModelConfig, x: jnp.ndarray, *,
              dropless: bool = False,
              shard_axes=None) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """x: (B, T, d) -> (out, aux_loss).

    ``dropless=True`` sets capacity C = N (an expert can receive at most one
    assignment per token), making routing exact and batch-composition
    independent — used on the decode path where N = B is small. Training and
    prefill use capacity-factor dispatch (GShard semantics; capacity drops are
    batch-dependent, as in any capacity-routed system — see DESIGN.md).

    With ``shard_axes`` (distributed lowering) the routed experts run under
    **expert parallelism**: a shard_map over the "model" axis gives each shard
    its E/TP slice of expert weights; tokens are batch-sharded and
    model-replicated already, so each shard dispatches only to local experts
    and one psum combines the partial outputs. This keeps every dispatch
    buffer (the data-dependent scatter XLA cannot shard on its own) at 1/TP
    size — the fix for the 86 GB/device MoE temp (EXPERIMENTS.md §Perf).
    """
    m = cfg.moe
    B, T, d = x.shape
    N = B * T
    E, K = m.n_experts, m.top_k

    if shard_axes is None:
        xt = x.reshape(N, d)
        gate_vals, expert_idx, onehot, aux = _route(params, cfg, xt)
        C = N if dropless else moe_capacity(cfg, N)
        y = _dispatch_ffn(cfg, xt, gate_vals, expert_idx, onehot,
                          params["up"], params.get("gate"), params["down"],
                          C, 0)
        if m.n_shared_experts:
            y = y + _shared_experts(params, cfg, xt)
        return y.reshape(B, T, d), aux.astype(jnp.float32)

    # ---- expert-parallel path (shard_map over the model axis) ----
    from jax.sharding import PartitionSpec as P
    mesh = shard_axes["mesh"]
    tp = shard_axes["tp"]
    dp = shard_axes["dp"]
    sizes = dict(zip(mesh.axis_names, mesh.devices.shape))
    tp_n = sizes[tp]
    assert E % tp_n == 0, (cfg.name, E, tp_n)
    El = E // tp_n
    dp_axes = dp if isinstance(dp, tuple) else (dp,)
    dp_n = 1
    for a in dp_axes:
        dp_n *= sizes.get(a, 1)
    if B % dp_n != 0:
        dp = None              # tiny global batch (long_500k): replicate B

    has_gate = "gate" in params

    def ep(x, router_w, up_w, down_w, *maybe_gate):
        Bl = x.shape[0]
        xt = x.reshape(Bl * T, d)
        n_local = xt.shape[0]                 # capacity is per-shard
        C = n_local if dropless else moe_capacity(cfg, n_local)
        gate_vals, expert_idx, onehot, aux = _route(
            {"router": {"w": router_w}}, cfg, xt)
        e_lo = jax.lax.axis_index(tp) * El
        gw = maybe_gate[0] if maybe_gate else None
        y = _dispatch_ffn(cfg, xt, gate_vals, expert_idx, onehot,
                          up_w, gw, down_w, C, e_lo)
        y = jax.lax.psum(y, tp)
        return y.reshape(Bl, T, d), aux

    args = [x, params["router"]["w"], params["up"], params["down"]]
    in_specs = [P(dp, None, None), P(None, None), P(tp, None, None),
                P(tp, None, None)]
    if has_gate:
        args.append(params["gate"])
        in_specs.append(P(tp, None, None))
    y, aux = jax.shard_map(
        ep, mesh=mesh, in_specs=tuple(in_specs),
        out_specs=(P(dp, None, None), P()), check_vma=False)(*args)
    if m.n_shared_experts:
        y = y + _shared_experts(params, cfg, x.reshape(N, d)).reshape(B, T, d)
    return y, aux.astype(jnp.float32)
