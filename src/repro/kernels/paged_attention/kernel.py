"""Paged attention Pallas TPU kernels (serving hot spot).

Five entry points:
  * ``paged_attention``       — split K/V pools ``(K, P, page, hd)``
  * ``paged_attention_pool``  — fused page-major pool ``(P, 2, K, page, hd)``:
    the AquaTensor LOCAL pool IS the operand (batched block tables; the
    serving runtime's layout — tier migration moves whole slots, no repack)
  * ``paged_prefill_attention_pool`` — query-BLOCK variant of the fused-pool
    kernel: a chunk of ``Tc`` query tokens per sequence attends causally to
    every page written so far (chunked continuous-batching prefill). The
    page-iteration axis and online-softmax accumulators are identical to the
    decode variant, so a token's softmax reduction order is the same for any
    chunk split — chunked prefill is bit-identical across chunk sizes.
  * ``paged_mixed_attention_pool`` — MIXED-MODE variant: one launch serves a
    packed batch of decode lanes AND prefill chunk rows against the same
    pool. Each row carries ``(q_start, n_real, is_decode)`` metadata: a
    decode lane is a one-token row (``n_real = 1``) whose single query sits
    at absolute position ``q_start``; a chunk row is ``n_real`` real tokens
    at ``q_start + t``. The page loop and accumulators are the decode/chunk
    kernels', so a row's reduction order does not depend on what else rides
    the launch — while issuing ONE launch per layer instead of one per
    admitted request.
  * ``append_kv``             — page-append writer: one decode token's K/V
    into each sequence's current page, in place via input-output aliasing

The block table is passed as a *scalar-prefetch* operand
(``pltpu.PrefetchScalarGridSpec``) so the K/V BlockSpec index maps can resolve
``block_tables[b, i]`` **before** the DMA is issued — each grid step streams
exactly one page per kv head from the HBM pool into VMEM, which is precisely
the access pattern the paged pool is laid out for. Online softmax accumulators
live in VMEM scratch and persist across the page-iteration (minor-most) grid
axis. Pages past ``lengths[b]`` are masked (their DMA still targets page id 0,
a resident dummy, so no out-of-bounds access happens).

VMEM working set per step: q (G, hd) + k,v (page, hd) + acc (G, hd) f32
≈ 0.3 MB at page=64, hd=256 — far below the ~16 MB VMEM budget, leaving room
for the double-buffered page DMAs Mosaic inserts automatically.

COMPILED pass: every attention entry point declares its grid semantics to
the Mosaic compiler — the batch/packed-row axis and the kv-head axis are
``parallel`` (rows are independent; the compiler may partition them across
TPU cores), while the page-iteration axis is ``arbitrary`` (the
online-softmax accumulators in VMEM scratch carry across it, a sequential
reduction). On TPU these are the kernels the fused engine step runs
(``tpu_custom_call`` in the compiled step); on the CPU backend the same
programs execute in interpret mode (``ops._on_cpu``). Both passes follow the
same per-row page loop, but the compiled pass is its own program: Mosaic
picks the matmul and exp lowering, so compiled outputs are checked against
the references and the dense model path under tolerances, not bit for bit.
On a TPU v5e at qwen1.5-0.5b widths in bf16 (``chip_smoke.py``), greedy
tokens served through these kernels equal the dense path's argmax except
at near-ties, whose logits differ by one bf16 step.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30

# grid = (rows, kv heads, pages-per-sequence): rows/heads partition across
# megacores, the page axis is the online-softmax reduction
_POOL_SEMANTICS = ("parallel", "parallel", "arbitrary")
_POOL_PARAMS = pltpu.CompilerParams(dimension_semantics=_POOL_SEMANTICS)


def _paged_kernel(block_tables_ref, lengths_ref, q_ref, k_ref, v_ref, o_ref,
                  acc_ref, m_ref, l_ref, *, page: int, scale: float):
    b = pl.program_id(0)
    i = pl.program_id(2)
    npages = pl.num_programs(2)

    @pl.when(i == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    length = lengths_ref[b]
    q = q_ref[0, 0].astype(jnp.float32)                    # (G, hd)
    k = k_ref[0, 0].astype(jnp.float32)                    # (page, hd)
    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32) * scale  # (G,page)
    pos = i * page + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
    s = jnp.where(pos < length, s, NEG_INF)

    m_prev = m_ref[...]
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
    p = jnp.exp(s - m_new)
    alpha = jnp.exp(m_prev - m_new)
    l_ref[...] = alpha * l_ref[...] + jnp.sum(p, axis=-1, keepdims=True)
    v = v_ref[0, 0].astype(jnp.float32)
    acc_ref[...] = acc_ref[...] * alpha + jax.lax.dot_general(
        p, v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)
    m_ref[...] = m_new

    @pl.when(i == npages - 1)
    def _finalize():
        l = l_ref[...]
        o_ref[0, 0] = (acc_ref[...] / jnp.where(l == 0.0, 1.0, l)).astype(o_ref.dtype)


def _paged_pool_kernel(block_tables_ref, lengths_ref, q_ref, kv_ref, o_ref,
                       acc_ref, m_ref, l_ref, *, page: int, scale: float):
    """Fused-pool variant: one (1, 2, 1, page, hd) block carries the K and V
    halves of a page, so each grid step issues a single DMA per page."""
    b = pl.program_id(0)
    i = pl.program_id(2)
    npages = pl.num_programs(2)

    @pl.when(i == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    length = lengths_ref[b]
    q = q_ref[0, 0].astype(jnp.float32)                    # (G, hd)
    k = kv_ref[0, 0, 0].astype(jnp.float32)                # (page, hd)
    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32) * scale
    pos = i * page + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
    s = jnp.where(pos < length, s, NEG_INF)

    m_prev = m_ref[...]
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
    p = jnp.exp(s - m_new)
    alpha = jnp.exp(m_prev - m_new)
    l_ref[...] = alpha * l_ref[...] + jnp.sum(p, axis=-1, keepdims=True)
    v = kv_ref[0, 1, 0].astype(jnp.float32)                # (page, hd)
    acc_ref[...] = acc_ref[...] * alpha + jax.lax.dot_general(
        p, v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)
    m_ref[...] = m_new

    @pl.when(i == npages - 1)
    def _finalize():
        l = l_ref[...]
        o_ref[0, 0] = (acc_ref[...] / jnp.where(l == 0.0, 1.0, l)).astype(o_ref.dtype)


def paged_attention_pool(q, kv_pool, block_tables, lengths, *,
                         scale: float | None = None, interpret: bool = False):
    """Batched block-table decode attention over a fused page-major pool.

    This is the serving-runtime layout: ``kv_pool`` IS the AquaTensor LOCAL
    pool, page-major so tier migration moves whole slots without repacking.

    q:            (B, H, hd)                   one query token per sequence
    kv_pool:      (P, 2, K, page, hd)          [:,0]=K, [:,1]=V
    block_tables: (B, pps) int32               physical page slots per sequence
    lengths:      (B,) int32                   tokens present per sequence
    -> (B, H, hd)
    """
    B, H, hd = q.shape
    P, _, K, page, _ = kv_pool.shape
    G = H // K
    pps = block_tables.shape[1]
    scale = scale if scale is not None else 1.0 / math.sqrt(hd)

    qg = q.reshape(B, K, G, hd)
    kernel = functools.partial(_paged_pool_kernel, page=page, scale=scale)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,                 # block_tables, lengths
        grid=(B, K, pps),
        in_specs=[
            pl.BlockSpec((1, 1, G, hd), lambda b, h, i, bt, ln: (b, h, 0, 0)),
            pl.BlockSpec((1, 2, 1, page, hd),
                         lambda b, h, i, bt, ln: (bt[b, i], 0, h, 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, G, hd), lambda b, h, i, bt, ln: (b, h, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((G, hd), jnp.float32),
            pltpu.VMEM((G, 1), jnp.float32),
            pltpu.VMEM((G, 1), jnp.float32),
        ],
    )
    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, K, G, hd), q.dtype),
        compiler_params=_POOL_PARAMS,
        interpret=interpret,
    )(block_tables, lengths, qg, kv_pool)
    return out.reshape(B, H, hd)


def _chunk_pool_kernel(block_tables_ref, starts_ref, q_ref, kv_ref, o_ref,
                       acc_ref, m_ref, l_ref, *, page: int, gsize: int,
                       scale: float):
    """Query-block fused-pool variant: rows are (token, q-head-in-group)
    pairs, so row r is chunk token r // gsize. The causal mask compares each
    page position against the row's absolute position ``q_start + t``; the
    page loop and accumulators are otherwise the decode kernel's."""
    b = pl.program_id(0)
    i = pl.program_id(2)
    npages = pl.num_programs(2)

    @pl.when(i == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    q = q_ref[0, 0].astype(jnp.float32)                    # (Tc*G, hd)
    k = kv_ref[0, 0, 0].astype(jnp.float32)                # (page, hd)
    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32) * scale
    k_pos = i * page + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
    q_pos = starts_ref[b] + jax.lax.broadcasted_iota(jnp.int32, s.shape, 0) // gsize
    s = jnp.where(k_pos <= q_pos, s, NEG_INF)

    m_prev = m_ref[...]
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
    p = jnp.exp(s - m_new)
    alpha = jnp.exp(m_prev - m_new)
    l_ref[...] = alpha * l_ref[...] + jnp.sum(p, axis=-1, keepdims=True)
    v = kv_ref[0, 1, 0].astype(jnp.float32)                # (page, hd)
    acc_ref[...] = acc_ref[...] * alpha + jax.lax.dot_general(
        p, v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)
    m_ref[...] = m_new

    @pl.when(i == npages - 1)
    def _finalize():
        l = l_ref[...]
        o_ref[0, 0] = (acc_ref[...] / jnp.where(l == 0.0, 1.0, l)).astype(o_ref.dtype)


def paged_prefill_attention_pool(q, kv_pool, block_tables, q_starts, *,
                                 scale: float | None = None,
                                 interpret: bool = False):
    """Chunked-prefill attention over the fused page-major pool.

    Each sequence contributes a CHUNK of ``Tc`` query tokens at absolute
    positions ``q_starts[b] + t`` that attend causally to every page the
    sequence has written so far (including the chunk's own K/V, which the
    caller writes into the pool first).

    q:            (B, Tc, H, hd)       one chunk of query tokens per sequence
    kv_pool:      (P, 2, K, page, hd)  [:,0]=K, [:,1]=V
    block_tables: (B, pps) int32       physical page slots per sequence
                                       (padding points at a resident dummy)
    q_starts:     (B,) int32           absolute position of each chunk's
                                       first token
    -> (B, Tc, H, hd)
    """
    B, Tc, H, hd = q.shape
    P, _, K, page, _ = kv_pool.shape
    G = H // K
    pps = block_tables.shape[1]
    scale = scale if scale is not None else 1.0 / math.sqrt(hd)

    # rows = (token, head-in-group): row r is token r // G of the chunk
    qg = (q.reshape(B, Tc, K, G, hd).transpose(0, 2, 1, 3, 4)
          .reshape(B, K, Tc * G, hd))
    kernel = functools.partial(_chunk_pool_kernel, page=page, gsize=G,
                               scale=scale)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,                 # block_tables, q_starts
        grid=(B, K, pps),
        in_specs=[
            pl.BlockSpec((1, 1, Tc * G, hd), lambda b, h, i, bt, st: (b, h, 0, 0)),
            pl.BlockSpec((1, 2, 1, page, hd),
                         lambda b, h, i, bt, st: (bt[b, i], 0, h, 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, Tc * G, hd),
                               lambda b, h, i, bt, st: (b, h, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((Tc * G, hd), jnp.float32),
            pltpu.VMEM((Tc * G, 1), jnp.float32),
            pltpu.VMEM((Tc * G, 1), jnp.float32),
        ],
    )
    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, K, Tc * G, hd), q.dtype),
        compiler_params=_POOL_PARAMS,
        interpret=interpret,
    )(block_tables, q_starts, qg, kv_pool)
    return (out.reshape(B, K, Tc, G, hd).transpose(0, 2, 1, 3, 4)
            .reshape(B, Tc, H, hd))


def _mixed_pool_kernel(block_tables_ref, starts_ref, n_reals_ref, decode_ref,
                       q_ref, kv_ref, o_ref, acc_ref, m_ref, l_ref, *,
                       page: int, gsize: int, scale: float):
    """Mixed-mode fused-pool kernel: every row of the packed batch is a
    query block of Tc tokens with per-row ``(q_start, n_real, is_decode)``
    metadata. A decode lane's single real token (row t = 0) attends to
    ``k_pos <= q_start`` — exactly the decode kernel's ``pos < length``
    mask with ``length = q_start + 1`` — and its tail rows (t >= n_real,
    which is 1) are fully masked, degenerating to a finite uniform mean the
    caller never reads. A chunk row's token t attends to
    ``k_pos <= q_start + t`` at EVERY row, bucket-pad rows included:
    garbage rows must stay bit-identical to the per-request chunk kernel's
    because their K/V was written into the page window (positions later
    chunks overwrite) and the next layer's writes are computed from their
    outputs. The page loop and the online-softmax accumulators are shared
    with the decode and chunk kernels — a row's reduction order never
    depends on what else rides the launch."""
    b = pl.program_id(0)
    i = pl.program_id(2)
    npages = pl.num_programs(2)

    @pl.when(i == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    q = q_ref[0, 0].astype(jnp.float32)                    # (Tc*G, hd)
    k = kv_ref[0, 0, 0].astype(jnp.float32)                # (page, hd)
    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32) * scale
    k_pos = i * page + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
    t = jax.lax.broadcasted_iota(jnp.int32, s.shape, 0) // gsize
    dec = decode_ref[b] != 0
    q_pos = starts_ref[b] + jnp.where(dec, 0, t)
    valid = (k_pos <= q_pos) & (~dec | (t < n_reals_ref[b]))
    s = jnp.where(valid, s, NEG_INF)

    m_prev = m_ref[...]
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
    p = jnp.exp(s - m_new)
    alpha = jnp.exp(m_prev - m_new)
    l_ref[...] = alpha * l_ref[...] + jnp.sum(p, axis=-1, keepdims=True)
    v = kv_ref[0, 1, 0].astype(jnp.float32)                # (page, hd)
    acc_ref[...] = acc_ref[...] * alpha + jax.lax.dot_general(
        p, v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)
    m_ref[...] = m_new

    @pl.when(i == npages - 1)
    def _finalize():
        l = l_ref[...]
        o_ref[0, 0] = (acc_ref[...] / jnp.where(l == 0.0, 1.0, l)).astype(o_ref.dtype)


def paged_mixed_attention_pool(q, kv_pool, block_tables, q_starts, n_reals,
                               is_decode, *, scale: float | None = None,
                               interpret: bool = False):
    """Fused mixed-mode attention: decode lanes + prefill chunk rows in ONE
    launch against the page-major pool.

    q:            (R, Tc, H, hd)       packed rows — decode lanes carry their
                                       single query token at t = 0
    kv_pool:      (P, 2, K, page, hd)  [:,0]=K, [:,1]=V
    block_tables: (R, pps) int32       physical page slots per row
                                       (padding points at a resident dummy)
    q_starts:     (R,) int32           absolute position of the row's first
                                       token (decode: the token's position)
    n_reals:      (R,) int32           real tokens in the row (decode: 1;
                                       bucket-pad rows: 0 — fully masked)
    is_decode:    (R,) int32           1 marks a decode lane
    -> (R, Tc, H, hd)
    """
    R, Tc, H, hd = q.shape
    P, _, K, page, _ = kv_pool.shape
    G = H // K
    pps = block_tables.shape[1]
    scale = scale if scale is not None else 1.0 / math.sqrt(hd)

    qg = (q.reshape(R, Tc, K, G, hd).transpose(0, 2, 1, 3, 4)
          .reshape(R, K, Tc * G, hd))
    kernel = functools.partial(_mixed_pool_kernel, page=page, gsize=G,
                               scale=scale)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=4,          # block_tables, q_starts, n_reals, dec
        grid=(R, K, pps),
        in_specs=[
            pl.BlockSpec((1, 1, Tc * G, hd),
                         lambda b, h, i, bt, st, nr, dc: (b, h, 0, 0)),
            pl.BlockSpec((1, 2, 1, page, hd),
                         lambda b, h, i, bt, st, nr, dc: (bt[b, i], 0, h, 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, Tc * G, hd),
                               lambda b, h, i, bt, st, nr, dc: (b, h, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((Tc * G, hd), jnp.float32),
            pltpu.VMEM((Tc * G, 1), jnp.float32),
            pltpu.VMEM((Tc * G, 1), jnp.float32),
        ],
    )
    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((R, K, Tc * G, hd), q.dtype),
        compiler_params=_POOL_PARAMS,
        interpret=interpret,
    )(block_tables, q_starts, n_reals, is_decode, qg, kv_pool)
    return (out.reshape(R, K, Tc, G, hd).transpose(0, 2, 1, 3, 4)
            .reshape(R, Tc, H, hd))


def _append_kernel(slots_ref, offs_ref, k_ref, v_ref, pool_ref, out_ref):
    """Rewrite the target page block with one token row of K and V replaced.

    The row is chosen by a select over the page axis rather than a dynamic
    one-row store: Mosaic refuses a store at a dynamic sublane offset into
    a packed (bf16) page, while the select lowers for every dtype."""
    off = offs_ref[pl.program_id(0)]
    for half, new_ref in ((0, k_ref), (1, v_ref)):
        block = pool_ref[0, half]                          # (K, page, hd)
        rows = jax.lax.broadcasted_iota(jnp.int32, block.shape, 1)
        out_ref[0, half] = jnp.where(rows == off, new_ref[0], block)


def append_kv(kv_pool, k_new, v_new, slots, offsets, *, interpret: bool = False):
    """Page-append writer: one decode token's K/V into its page, per sequence.

    kv_pool: (P, 2, K, page, hd); k_new/v_new: (B, K, hd);
    slots: (B,) int32 physical page slot holding the token's position;
    offsets: (B,) int32 row within the page (= pos % page).
    Returns the updated pool (in place on TPU via input-output aliasing).
    """
    P, _, K, page, hd = kv_pool.shape
    B = k_new.shape[0]
    # one (K, 1, hd) row block per lane: the select broadcasts it over the
    # page axis inside the kernel
    k_new = k_new.astype(kv_pool.dtype).reshape(B, K, 1, hd)
    v_new = v_new.astype(kv_pool.dtype).reshape(B, K, 1, hd)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,                 # slots, offsets
        grid=(B,),
        in_specs=[
            pl.BlockSpec((1, K, 1, hd), lambda b, s, o: (b, 0, 0, 0)),  # k_new
            pl.BlockSpec((1, K, 1, hd), lambda b, s, o: (b, 0, 0, 0)),  # v_new
            pl.BlockSpec((1, 2, K, page, hd),
                         lambda b, s, o: (s[b], 0, 0, 0, 0)),          # pool
        ],
        out_specs=pl.BlockSpec((1, 2, K, page, hd),
                               lambda b, s, o: (s[b], 0, 0, 0, 0)),
    )
    return pl.pallas_call(
        _append_kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct(kv_pool.shape, kv_pool.dtype),
        input_output_aliases={4: 0},           # pool (incl. scalar args) -> out
        interpret=interpret,
    )(slots, offsets, k_new, v_new, kv_pool)


def paged_attention(q, k_pages, v_pages, block_tables, lengths, *,
                    scale: float | None = None, interpret: bool = False):
    """q: (B,H,hd); k/v_pages: (K,P,page,hd); block_tables: (B,pps); lengths (B,)."""
    B, H, hd = q.shape
    K, P, page, _ = k_pages.shape
    G = H // K
    pps = block_tables.shape[1]
    scale = scale if scale is not None else 1.0 / math.sqrt(hd)

    qg = q.reshape(B, K, G, hd)
    kernel = functools.partial(_paged_kernel, page=page, scale=scale)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,                 # block_tables, lengths
        grid=(B, K, pps),
        in_specs=[
            pl.BlockSpec((1, 1, G, hd), lambda b, h, i, bt, ln: (b, h, 0, 0)),
            pl.BlockSpec((1, 1, page, hd), lambda b, h, i, bt, ln: (h, bt[b, i], 0, 0)),
            pl.BlockSpec((1, 1, page, hd), lambda b, h, i, bt, ln: (h, bt[b, i], 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, G, hd), lambda b, h, i, bt, ln: (b, h, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((G, hd), jnp.float32),
            pltpu.VMEM((G, 1), jnp.float32),
            pltpu.VMEM((G, 1), jnp.float32),
        ],
    )
    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, K, G, hd), q.dtype),
        compiler_params=_POOL_PARAMS,
        interpret=interpret,
    )(block_tables, lengths, qg, k_pages, v_pages)
    return out.reshape(B, H, hd)
