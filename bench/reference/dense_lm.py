"""Plain reference of a dense decoder-only LM: the qwen2 / llama / MiniCPM
block, as the configuration file states it.

    x_0     = embed[tokens] * scale_emb
    per layer l:
      h     = rmsnorm(x) * w_attn_norm
      q,k,v = h Wq + bq, h Wk + bk, h Wv + bv        (biases where qkv_bias)
      q,k   = rope(q), rope(k)                       (rotate-half, theta)
      a     = softmax(q k^T / sqrt(head_dim) + causal) v      (GQA groups)
      x     = x + (a Wo) * depth_scale
      h     = rmsnorm(x) * w_mlp_norm
      x     = x + ((silu(h Wgate) * (h Wup)) Wdown) * depth_scale
    logits  = rmsnorm(x) * w_final_norm  @ embed^T / width_scale   (tied)

with ``depth_scale = scale_depth / sqrt(num_hidden_layers)`` and
``width_scale = hidden_size / dim_model_base`` (MiniCPM's muP factors; a
configuration without them, or whose ``bench.mup`` is false, uses 1).
Everything is float32 with ``Precision.HIGHEST`` matmuls, one layer at a
time under ``lax.scan``, no cache and no batching.

``precision="fp8"`` is the control: every matmul operand (weights per output
channel, activations per row, attention operands per row) is rounded to
float8_e4m3fn with an absmax scale and the product accumulated in float32 —
the serving precision one step below the configuration's bfloat16.

This module imports nothing of the program under test. ``init_weights``
makes the weights the benchmark serves, from a seed, in the served dtype.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

HIGHEST = jax.lax.Precision.HIGHEST
F8 = jnp.float8_e4m3fn
F8_MAX = 448.0


def dims(cfg: dict) -> dict:
    d = int(cfg["hidden_size"])
    H = int(cfg["num_attention_heads"])
    return {"d": d, "H": H, "K": int(cfg.get("num_key_value_heads", H)),
            "hd": int(cfg.get("head_dim") or d // H),
            "f": int(cfg["intermediate_size"]),
            "L": int(cfg["num_hidden_layers"]), "V": int(cfg["vocab_size"]),
            "bias": bool(cfg.get("qkv_bias", False))}


def weight_shapes(cfg: dict) -> dict:
    """Leaf shapes; per-layer leaves carry a leading layer axis."""
    n = dims(cfg)
    d, H, K, hd, f, L = n["d"], n["H"], n["K"], n["hd"], n["f"], n["L"]
    s = {"embed": (n["V"], d), "final_norm": (d,),
         "attn_norm": (L, d), "wq": (L, d, H * hd), "wk": (L, d, K * hd),
         "wv": (L, d, K * hd), "wo": (L, H * hd, d), "mlp_norm": (L, d),
         "w_gate": (L, d, f), "w_up": (L, d, f), "w_down": (L, f, d)}
    if n["bias"]:
        s.update(bq=(L, H * hd), bk=(L, K * hd), bv=(L, K * hd))
    return s


def _std(name: str, shape) -> float:
    if name == "embed":
        return 0.02
    if name.startswith("b"):
        return 0.02
    return 1.0 / math.sqrt(shape[-2])          # fan-in of a (.., in, out)


@functools.partial(jax.jit, static_argnums=(0, 2))
def _init(cfg_items, key, dtype):
    cfg = dict(cfg_items)
    out = {}
    for i, (name, shape) in enumerate(sorted(weight_shapes(cfg).items())):
        k = jax.random.fold_in(key, i)
        if name.endswith("norm"):
            w = 1.0 + 0.1 * jax.random.normal(k, shape, jnp.float32)
        else:
            w = _std(name, shape) * jax.random.truncated_normal(
                k, -2.0, 2.0, shape, jnp.float32)
        out[name] = w.astype(dtype)
    return out


def init_weights(cfg: dict, seed: int, dtype=jnp.bfloat16) -> dict:
    """Every weight, made on the default device in one jitted call from
    ``seed`` (any non-negative integer, folded to 32 bits)."""
    key = jax.random.fold_in(jax.random.PRNGKey(seed & 0xFFFFFFFF),
                             seed >> 32)
    return _init(_freeze(cfg), key, jnp.dtype(dtype))


def _freeze(cfg: dict):
    """The scalar keys, plus whether MiniCPM's muP factors are applied
    (``bench.mup``, default yes; the configuration file says so where the
    program does not apply them)."""
    items = {k: v for k, v in cfg.items()
             if isinstance(v, (int, float, bool, str))}
    items["_mup"] = bool(cfg.get("bench", {}).get("mup", True))
    return tuple(sorted(items.items()))


def _q8(x, axis):
    """Round to float8_e4m3fn with an absmax scale along ``axis``."""
    amax = jnp.max(jnp.abs(x), axis=axis, keepdims=True)
    scale = jnp.where(amax > 0, amax / F8_MAX, 1.0)
    return (x / scale).astype(F8).astype(jnp.float32) * scale


def _mm(x, w, fp8):
    """x (..., in) @ w (in, out) in float32, operands rounded to fp8 in
    the control."""
    if fp8:
        x, w = _q8(x, -1), _q8(w, 0)
    return jnp.einsum("...i,io->...o", x, w, precision=HIGHEST)


def _rmsnorm(x, w, eps):
    x = x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps)
    return x * w


def _rope(x, pos, theta):
    """Rotate-half rotary embedding. x (T, h, hd), pos (T,)."""
    hd = x.shape[-1]
    inv = 1.0 / (theta ** (jnp.arange(0, hd, 2, dtype=jnp.float32) / hd))
    ang = pos[:, None].astype(jnp.float32) * inv[None, :]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., :hd // 2], x[..., hd // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)


def _layer(cfg, fp8, x, w):
    n = dims(cfg)
    T = x.shape[0]
    H, K, hd = n["H"], n["K"], n["hd"]
    eps = float(cfg.get("rms_norm_eps", 1e-6))
    theta = float(cfg.get("rope_theta", 10000.0))
    depth = (float(cfg["scale_depth"]) / math.sqrt(n["L"])
             if cfg["_mup"] and "scale_depth" in cfg else 1.0)
    w = jax.tree.map(lambda a: a.astype(jnp.float32), w)
    pos = jnp.arange(T)
    h = _rmsnorm(x, w["attn_norm"], eps)
    q, k, v = _mm(h, w["wq"], fp8), _mm(h, w["wk"], fp8), _mm(h, w["wv"], fp8)
    if n["bias"]:
        q, k, v = q + w["bq"], k + w["bk"], v + w["bv"]
    q = _rope(q.reshape(T, H, hd), pos, theta)
    k = _rope(k.reshape(T, K, hd), pos, theta)
    v = v.reshape(T, K, hd)
    G = H // K
    q = q.reshape(T, K, G, hd)
    if fp8:
        q, k, v = _q8(q, -1), _q8(k, -1), _q8(v, 0)
    s = jnp.einsum("tkgd,skd->kgts", q, k, precision=HIGHEST) / math.sqrt(hd)
    causal = pos[None, :] <= pos[:, None]
    s = jnp.where(causal, s, -jnp.inf)
    p = jax.nn.softmax(s, axis=-1)
    if fp8:
        p = _q8(p, -1)
    a = jnp.einsum("kgts,skd->tkgd", p, v, precision=HIGHEST)
    x = x + _mm(a.reshape(T, H * hd), w["wo"], fp8) * depth
    h = _rmsnorm(x, w["mlp_norm"], eps)
    g = _mm(h, w["w_gate"], fp8)
    u = _mm(h, w["w_up"], fp8)
    x = x + _mm(jax.nn.silu(g) * u, w["w_down"], fp8) * depth
    return x


_LAYER_KEYS = ("attn_norm", "wq", "wk", "wv", "wo", "mlp_norm", "w_gate",
               "w_up", "w_down", "bq", "bk", "bv")


@functools.partial(jax.jit, static_argnums=(0, 1))
def _logits_stats(cfg_items, fp8, weights, tokens, targets):
    """-> (max logit (T,), argmax (T,), logits at ``targets`` (T, n))."""
    cfg = dict(cfg_items)
    n = dims(cfg)
    emb = weights["embed"].astype(jnp.float32)
    x = emb[tokens] * (float(cfg.get("scale_emb", 1.0)) if cfg["_mup"]
                       else 1.0)
    layers = {k: weights[k] for k in _LAYER_KEYS if k in weights}

    def body(x, w):
        return _layer(cfg, fp8, x, w), None

    x, _ = jax.lax.scan(body, x, layers)
    eps = float(cfg.get("rms_norm_eps", 1e-6))
    x = _rmsnorm(x, weights["final_norm"].astype(jnp.float32), eps)
    width = (n["d"] / float(cfg["dim_model_base"])
             if cfg["_mup"] and "dim_model_base" in cfg else 1.0)
    logits = _mm(x, emb.T, fp8) / width
    at = jnp.take_along_axis(logits, targets, axis=1)
    return jnp.max(logits, -1), jnp.argmax(logits, -1), at


def logits_stats(cfg: dict, weights: dict, tokens, targets, *,
                 precision: str = "f32"):
    """Teacher-forced pass over ``tokens`` (T,) int32.

    Returns ``(max_logit, argmax, logit_at)``: per position the largest
    logit, its token, and the logits of ``targets`` (T, n) int32 — the
    tokens whose standing against the best the caller compares.
    ``precision`` is ``"f32"`` (the reference) or ``"fp8"`` (the control).
    """
    if precision not in ("f32", "fp8"):
        raise ValueError(precision)
    return _logits_stats(_freeze(cfg), precision == "fp8", weights,
                         jnp.asarray(tokens, jnp.int32),
                         jnp.asarray(targets, jnp.int32))
