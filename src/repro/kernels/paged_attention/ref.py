"""Pure-jnp oracles for paged attention.

These are the correctness anchors for BOTH kernel passes. The
interpret-mode path the CPU tests run follows the same f32 online softmax
page loop as the compiled TPU pass; the compiled pass (Mosaic's own matmul
and exp lowering, ``kernel._POOL_SEMANTICS`` grid) is held to these oracles
under tolerances, not bit for bit.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp


def paged_attention_ref(q, k_pages, v_pages, block_tables, lengths,
                        scale: float | None = None):
    """Decode attention over a paged KV pool.

    q:            (B, H, hd)            one query token per sequence
    k_pages/v_pages: (K, P, page, hd)   global page pool per kv head
    block_tables: (B, pages_per_seq) int32  page ids per sequence
    lengths:      (B,) int32            tokens present per sequence
    -> (B, H, hd)
    """
    B, H, hd = q.shape
    K, P, page, _ = k_pages.shape
    G = H // K
    pps = block_tables.shape[1]
    scale = scale if scale is not None else 1.0 / math.sqrt(hd)

    # gather per-sequence K/V: (B, K, pps*page, hd)
    kg = k_pages[:, block_tables]            # (K, B, pps, page, hd)
    vg = v_pages[:, block_tables]
    kg = jnp.moveaxis(kg, 1, 0).reshape(B, K, pps * page, hd)
    vg = jnp.moveaxis(vg, 1, 0).reshape(B, K, pps * page, hd)

    qg = q.reshape(B, K, G, hd)
    scores = jnp.einsum("bkgd,bksd->bkgs", qg, kg).astype(jnp.float32) * scale
    pos = jnp.arange(pps * page)[None, None, None, :]
    mask = pos < lengths[:, None, None, None]
    scores = jnp.where(mask, scores, -1e30)
    probs = jax.nn.softmax(scores, axis=-1).astype(q.dtype)
    out = jnp.einsum("bkgs,bksd->bkgd", probs, vg)
    return out.reshape(B, H, hd)


def paged_attention_pool_ref(q, kv_pool, block_tables, lengths,
                             scale: float | None = None):
    """Oracle for the fused page-major pool layout.

    q: (B,H,hd); kv_pool: (P,2,K,page,hd); block_tables: (B,pps); lengths (B,).
    """
    k_pages = jnp.moveaxis(kv_pool[:, 0], 1, 0)       # (K, P, page, hd)
    v_pages = jnp.moveaxis(kv_pool[:, 1], 1, 0)
    out = paged_attention_ref(q, k_pages, v_pages, block_tables, lengths,
                              scale=scale)
    # an empty sequence sees no key and reads zeros
    return jnp.where((lengths > 0)[:, None, None], out, 0).astype(out.dtype)


def paged_prefill_attention_pool_ref(q, kv_pool, block_tables, q_starts,
                                     scale: float | None = None):
    """Oracle for the query-block (chunked prefill) fused-pool variant.

    q: (B,Tc,H,hd); kv_pool: (P,2,K,page,hd); block_tables: (B,pps);
    q_starts: (B,) absolute position of each chunk's first token.
    """
    B, Tc, H, hd = q.shape
    _, _, K, page, _ = kv_pool.shape
    G = H // K
    pps = block_tables.shape[1]
    scale = scale if scale is not None else 1.0 / math.sqrt(hd)

    k_pages = jnp.moveaxis(kv_pool[:, 0], 1, 0)       # (K, P, page, hd)
    v_pages = jnp.moveaxis(kv_pool[:, 1], 1, 0)
    kg = jnp.moveaxis(k_pages[:, block_tables], 1, 0).reshape(B, K, pps * page, hd)
    vg = jnp.moveaxis(v_pages[:, block_tables], 1, 0).reshape(B, K, pps * page, hd)

    qg = q.reshape(B, Tc, K, G, hd)
    scores = jnp.einsum("btkgd,bksd->bkgts", qg, kg).astype(jnp.float32) * scale
    k_pos = jnp.arange(pps * page)[None, None, None, None, :]
    q_pos = (q_starts[:, None] + jnp.arange(Tc)[None, :])[:, None, None, :, None]
    scores = jnp.where(k_pos <= q_pos, scores, -1e30)
    probs = jax.nn.softmax(scores, axis=-1).astype(q.dtype)
    out = jnp.einsum("bkgts,bksd->bkgtd", probs, vg)
    return out.transpose(0, 3, 1, 2, 4).reshape(B, Tc, H, hd)


def paged_mixed_attention_pool_ref(q, kv_pool, block_tables, q_starts,
                                   n_reals, is_decode,
                                   scale: float | None = None):
    """Oracle for the mixed-mode (decode lanes + prefill chunk rows) variant.

    q: (R,Tc,H,hd); kv_pool: (P,2,K,page,hd); block_tables: (R,pps);
    q_starts/n_reals/is_decode: (R,) per-row metadata — a decode lane is a
    one-token row (n_real 1) at absolute position q_start whose tail rows
    see no key and read zeros (never read by the caller); a chunk
    row attends causally at every row INCLUDING bucket padding, matching
    the per-request chunk kernel bit-exactly (garbage rows' K/V sits in
    the page window until later chunks overwrite it).
    """
    R, Tc, H, hd = q.shape
    _, _, K, page, _ = kv_pool.shape
    G = H // K
    pps = block_tables.shape[1]
    scale = scale if scale is not None else 1.0 / math.sqrt(hd)

    k_pages = jnp.moveaxis(kv_pool[:, 0], 1, 0)       # (K, P, page, hd)
    v_pages = jnp.moveaxis(kv_pool[:, 1], 1, 0)
    kg = jnp.moveaxis(k_pages[:, block_tables], 1, 0).reshape(R, K, pps * page, hd)
    vg = jnp.moveaxis(v_pages[:, block_tables], 1, 0).reshape(R, K, pps * page, hd)

    qg = q.reshape(R, Tc, K, G, hd)
    scores = jnp.einsum("btkgd,bksd->bkgts", qg, kg).astype(jnp.float32) * scale
    k_pos = jnp.arange(pps * page)[None, None, None, None, :]
    t = jnp.arange(Tc)[None, :]
    dec = is_decode[:, None] != 0
    q_pos = (q_starts[:, None]
             + jnp.where(dec, 0, t))[:, None, None, :, None]
    valid = (k_pos <= q_pos) \
        & (~dec | (t < n_reals[:, None]))[:, None, None, :, None]
    scores = jnp.where(valid, scores, -1e30)
    probs = jax.nn.softmax(scores, axis=-1)
    # a row that sees no key (a decode lane's tail) reads zeros
    probs = jnp.where(valid.any(-1, keepdims=True), probs, 0).astype(q.dtype)
    out = jnp.einsum("bkgts,bksd->bkgtd", probs, vg)
    return out.transpose(0, 3, 1, 2, 4).reshape(R, Tc, H, hd)


def append_kv_ref(kv_pool, k_new, v_new, slots, offsets):
    """Oracle for the page-append writer.

    kv_pool: (P,2,K,page,hd); k_new/v_new: (B,K,hd); slots/offsets: (B,).
    """
    B, K, hd = k_new.shape
    heads = jnp.arange(K)[None, :]                    # broadcast to (B, K)
    kv_pool = kv_pool.at[slots[:, None], 0, heads,
                         offsets[:, None]].set(k_new.astype(kv_pool.dtype))
    kv_pool = kv_pool.at[slots[:, None], 1, heads,
                         offsets[:, None]].set(v_new.astype(kv_pool.dtype))
    return kv_pool
