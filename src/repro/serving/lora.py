"""LoRA adapters: per-request fine-tuning deltas + an AQUA-offloaded adapter
cache (paper §6.1, Figs. 8/12).

The paper's vLLM integration loads/stores whole adapters as ONE tensor (their
fix for the many-small-copies problem) — mirrored here: an adapter is packed
into a single contiguous blob in the AquaTensor, so fetching a cold adapter is
one large fabric message instead of per-layer fragments.

``apply_lora`` patches q/v projections (the classic LoRA placement):
    W' = W + (alpha/r) * A @ B
used by the single-adapter serving example; the cache layer below is what the
multi-tenant benchmarks exercise.
"""
from __future__ import annotations

import math
from dataclasses import dataclass
from typing import Dict, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import ModelConfig
from repro.core.aqua_tensor import REMOTE, AquaTensor, TransferMeter


def init_adapter(key, cfg: ModelConfig, rank: int = 16, alpha: float = 32.0):
    """One (A, B) pair per layer for wq and wv."""
    hd = cfg.resolved_head_dim
    d = cfg.d_model
    ks = jax.random.split(key, 4)
    L = cfg.n_layers
    dt = cfg.dtype()
    def tn(k, shape, std):
        return (std * jax.random.truncated_normal(k, -2, 2, shape)).astype(dt)
    return {
        "alpha": alpha, "rank": rank,
        "q_a": tn(ks[0], (L, d, rank), 1.0 / math.sqrt(d)),
        "q_b": jnp.zeros((L, rank, cfg.n_heads * hd), dt),
        "v_a": tn(ks[1], (L, d, rank), 1.0 / math.sqrt(d)),
        "v_b": jnp.zeros((L, rank, cfg.n_kv_heads * hd), dt),
    }


def adapter_bytes(adapter: dict) -> int:
    return sum(v.nbytes for k, v in adapter.items() if hasattr(v, "nbytes"))


def apply_lora(params: dict, cfg: ModelConfig, adapter: dict) -> dict:
    """Merge the adapter into stacked block params (single-adapter serving)."""
    from repro.models.lm import group_size
    gs = group_size(cfg)
    if gs != 1:
        raise ValueError(
            f"{cfg.name}: adapter merge supported for homogeneous stacks "
            f"only (group size {gs})")
    scale = adapter["alpha"] / adapter["rank"]

    def patch(blocks):
        mix = blocks["sub0"]["mix"]
        dq = jnp.einsum("ldr,lrh->ldh", adapter["q_a"], adapter["q_b"]) * scale
        dv = jnp.einsum("ldr,lrh->ldh", adapter["v_a"], adapter["v_b"]) * scale
        mix = dict(mix, wq=dict(mix["wq"], w=mix["wq"]["w"] + dq.astype(mix["wq"]["w"].dtype)),
                   wv=dict(mix["wv"], w=mix["wv"]["w"] + dv.astype(mix["wv"]["w"].dtype)))
        return dict(blocks, sub0=dict(blocks["sub0"], mix=mix))

    return dict(params, blocks=patch(params["blocks"]))


class AdapterCache:
    """LRU adapter cache over an AquaTensor: hot adapters LOCAL, cold ones on
    the donor GPU (fabric) or host. Fetch = one coalesced blob transfer.

    Adapters page in their NATIVE dtype: every array leaf is raveled into one
    contiguous vector of ``dtype`` (pass the model's param dtype) — the
    paper's "load the adapter as one tensor" fix with no f32 blowup, on the
    same page machinery every other state tier now uses.
    """

    def __init__(self, *, capacity_local: int, page_elems: int = 65536,
                 dtype=jnp.float32, meter: Optional[TransferMeter] = None):
        self.capacity = capacity_local
        self.page_elems = page_elems
        self.aqua = AquaTensor(
            page_shape=(page_elems,),
            local_slots=max(capacity_local * 2, 4), host_slots=4096,
            dtype=dtype, meter=meter, name="lora")
        self._parked: Dict[int, tuple] = {}
        self._lru: list = []

    def put(self, aid: int, adapter: dict):
        leaves = jax.tree.leaves(adapter_arrays(adapter))
        flat = jnp.concatenate(
            [l.reshape(-1).astype(self.aqua.dtype) for l in leaves])
        n_pages = -(-flat.size // self.page_elems)
        flat = jnp.pad(flat, (0, n_pages * self.page_elems - flat.size))
        lps = self.aqua.allocate(n_pages, prefer=REMOTE)
        self.aqua.write(lps, flat.reshape(n_pages, self.page_elems))
        # the python dict is retained alongside the paged blob: fetch()
        # meters the coalesced page-in and returns the retained object
        self._parked[aid] = (lps, adapter)

    def fetch(self, aid: int) -> dict:
        """Bring an adapter into the local tier (metered if cold)."""
        lps, adapter = self._parked[aid]
        hit = aid in self._lru
        if not hit:
            self.aqua.read(lps, meter=True)   # the coalesced fabric fetch
            self._lru.append(aid)
            if len(self._lru) > self.capacity:
                self._lru.pop(0)              # evictions are free (read-only copy)
        else:
            self._lru.remove(aid)
            self._lru.append(aid)
        return adapter

    @property
    def hits_resident(self):
        return list(self._lru)


def adapter_arrays(adapter: dict) -> dict:
    return {k: v for k, v in adapter.items() if hasattr(v, "nbytes")}
