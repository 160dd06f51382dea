"""Hand the benchmark's dense-LM weights to the program under test.

``model_config`` turns the configuration file's keys into the program's
``ModelConfig``; ``program_params`` re-nests the weights that
``reference/dense_lm.init_weights`` made into the program's parameter tree.
The big matrices are handed over as they are (no copy): only the norm
weights change form, because the program stores an RMS norm's weight ``w``
as ``w - 1`` (exact in bfloat16 for the weights made here).
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp


def model_config(name: str, cfg: dict):
    """The program's ``ModelConfig`` for the file's keys.

    Raises:
        ValueError: the file asks for something the program's dense path
            does not compute (an activation other than gated SiLU, or
            MiniCPM's muP factors other than 1 where the file does not
            set ``bench.mup`` to false).
    """
    from repro.configs.base import DENSE, ModelConfig
    if cfg.get("hidden_act", "silu") != "silu":
        raise ValueError(f"{name}: hidden_act {cfg['hidden_act']!r}")
    L, d = int(cfg["num_hidden_layers"]), int(cfg["hidden_size"])
    mup = {}
    if cfg.get("bench", {}).get("mup", True):
        mup = {"scale_emb": float(cfg.get("scale_emb", 1.0)),
               "depth": (float(cfg["scale_depth"]) / math.sqrt(L)
                         if "scale_depth" in cfg else 1.0),
               "width": (d / float(cfg["dim_model_base"])
                         if "dim_model_base" in cfg else 1.0)}
    off = {k: v for k, v in mup.items() if abs(v - 1.0) > 1e-12}
    if off:
        raise ValueError(f"{name}: the program applies no muP factor; the "
                         f"file asks for {off}")
    H = int(cfg["num_attention_heads"])
    return ModelConfig(
        name=name, family=DENSE, n_layers=L, d_model=d, n_heads=H,
        n_kv_heads=int(cfg.get("num_key_value_heads", H)),
        head_dim=int(cfg.get("head_dim") or d // H),
        d_ff=int(cfg["intermediate_size"]),
        vocab_size=int(cfg["vocab_size"]), activation="swiglu",
        qkv_bias=bool(cfg.get("qkv_bias", False)),
        tie_embeddings=bool(cfg.get("tie_word_embeddings", False)),
        rope_theta=float(cfg.get("rope_theta", 10000.0)),
        rmsnorm_eps=float(cfg.get("rms_norm_eps", 1e-6)),
        max_seq_len=int(cfg.get("max_position_embeddings", 8192)),
        param_dtype=cfg.get("torch_dtype", "bfloat16"),
        compute_dtype=cfg.get("torch_dtype", "bfloat16"))


@jax.jit
def _shift(norms):
    return jax.tree.map(lambda w: w - jnp.ones((), w.dtype), norms)


def program_params(w: dict, cfg: dict) -> dict:
    """The program's parameter tree over the same weight buffers."""
    if not cfg.get("tie_word_embeddings", False):
        raise ValueError("an untied output head has no weight here")
    n = _shift({k: w[k] for k in ("attn_norm", "mlp_norm", "final_norm")})

    def lin(k, b=None):
        out = {"w": w[k]}
        if b is not None and b in w:
            out["b"] = w[b]
        return out

    return {
        "embed": {"tok": w["embed"]},
        "blocks": {"sub0": {
            "n1": {"scale": n["attn_norm"]},
            "mix": {"wq": lin("wq", "bq"), "wk": lin("wk", "bk"),
                    "wv": lin("wv", "bv"), "wo": lin("wo")},
            "n2": {"scale": n["mlp_norm"]},
            "ffn": {"up": lin("w_up"), "down": lin("w_down"),
                    "gate": lin("w_gate")}}},
        "final_norm": {"scale": n["final_norm"]},
    }
