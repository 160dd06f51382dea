"""Work the served steps needed, counted from what they served: the table
of peaks, model FLOPs per real token, and the paged-attention kernel's
bytes and FLOPs per row.

Only real work counts. A row that pads a chunk to its bucket, a decode lane
with no request in it, and the pages past a row's context are not work, so
a program that stops computing them loses nothing here.
"""
from __future__ import annotations

import json
from dataclasses import dataclass
from pathlib import Path
from typing import Iterable, Tuple

HERE = Path(__file__).resolve().parent


def peaks(device_kind: str, path: Path = HERE / "peaks.json") -> dict:
    """Published peaks of one chip of ``device_kind``.

    Raises:
        KeyError: the kind is not in the table (no default is assumed).
    """
    table = json.loads(Path(path).read_text())["kinds"]
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r} in "
                       f"{path.name}; known: {sorted(table)}")
    return table[device_kind]


@dataclass(frozen=True)
class Dims:
    """The sizes that set a dense decoder's work per token."""
    d: int          # hidden size
    H: int          # query heads
    K: int          # key/value heads
    hd: int         # head size
    f: int          # feed-forward width (gated: three matrices)
    L: int          # layers
    V: int          # vocabulary
    kv_bytes: int   # bytes of one cached K or V element

    @classmethod
    def of(cls, cfg: dict, kv_bytes: int = 2) -> "Dims":
        d, H = int(cfg["hidden_size"]), int(cfg["num_attention_heads"])
        return cls(d, H, int(cfg.get("num_key_value_heads", H)),
                   int(cfg.get("head_dim") or d // H),
                   int(cfg["intermediate_size"]),
                   int(cfg["num_hidden_layers"]), int(cfg["vocab_size"]),
                   kv_bytes)


def layer_matmul_flops(n: Dims) -> int:
    """FLOPs of one token through one layer's projections and MLP."""
    proj = n.d * (n.H + 2 * n.K) * n.hd + n.H * n.hd * n.d
    return 2 * (proj + 3 * n.d * n.f)


def attention_flops(n: Dims, q_start: int, n_real: int) -> int:
    """Causal attention FLOPs (scores and weighted values) of ``n_real``
    query tokens at positions ``q_start ..``, summed over layers: token t
    attends to ``q_start + t + 1`` keys."""
    keys = n_real * q_start + n_real * (n_real + 1) // 2
    return 4 * n.H * n.hd * keys * n.L


def token_flops(n: Dims, q_start: int, n_real: int, logit_rows: int) -> int:
    """Model FLOPs of one served row: ``n_real`` tokens from ``q_start``
    through every layer, plus the output projection for ``logit_rows``
    rows (1 when the row produced a token, else 0)."""
    return (n_real * n.L * layer_matmul_flops(n)
            + attention_flops(n, q_start, n_real)
            + logit_rows * 2 * n.d * n.V)


def attention_need(n: Dims, q_start: int, n_real: int,
                   act_bytes: int = 2) -> Tuple[int, int]:
    """(bytes, FLOPs) the paged-attention kernel needs for one row, over
    all layers: each K/V head's cached keys and values up to the row's
    last position read once, the queries read and the outputs written
    once."""
    ctx = q_start + n_real
    kv = 2 * n.K * n.hd * ctx * n.kv_bytes
    qo = 2 * n_real * n.H * n.hd * act_bytes
    return (kv + qo) * n.L, attention_flops(n, q_start, n_real)


def roofline_seconds(steps: Iterable[Iterable[Tuple[int, int]]], n: Dims,
                     peak: dict) -> float:
    """Least time the chip needs for the kernel calls of ``steps``: each
    step is the (q_start, n_real) pairs of its real rows, served by one
    call per layer, and each call is bounded by the larger of its FLOPs
    over the bf16 peak and its bytes over HBM bandwidth."""
    total = 0.0
    for rows in steps:
        need = [attention_need(n, q, k) for q, k in rows]
        total += max(sum(f for _, f in need) / peak["bf16_flops_per_s"],
                     sum(b for b, _ in need) / peak["hbm_bytes_per_s"])
    return total
