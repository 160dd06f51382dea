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
``block_tables[b, i]`` **before** the DMA is issued. The three fused-pool
kernels share one page loop (``_attend_pages``) over the grid
``(rows, K // hb, pps)``: each grid step streams one page of ``hb`` kv heads,
a ``(1, 2, hb, page, hd)`` block that is one contiguous DMA when
``hb == K``, and folds it into the online softmax of those heads with the
score and PV matmuls batched over the head axis, in f32. A row walks only
the pages it holds: its last page (``last_pages``: ``q_start // page`` for a
decode row, ``(q_start + Tc - 1) // page`` for a chunk row, pad rows
included) rides in as one more scalar-prefetch operand; past it the index
map repeats the last block, so the pipeline issues no DMA, and the body does
no work. Page 0 holds a visible key for every row that has one, so a skipped
page would only have added ``p = 0`` with ``alpha = 1``: the reduction is
unchanged. A row that sees no key at all (a decode lane's tail rows, an
empty lane) reads zeros. Online softmax accumulators live in VMEM scratch
and persist across the page-iteration (minor-most) grid axis. The split-pool
``paged_attention`` keeps the grid ``(B, K, pps)``: one head and every page
per step.

VMEM per grid step (``pool_working_set``, each buffer padded to the
(8 x 32-bit, 128) tile): the double-buffered pool block, the double-buffered
q and out blocks of ``(hb, Tc*G, hd)``, the f32 acc/m/l scratch, and the f32
scores and probabilities of ``(hb, Tc*G, page)``. ``hb`` is the largest
divisor of ``K`` whose working set fits ``VMEM_BUDGET``, from the shapes
alone: qwen1.5-0.5b (K = 16, hd = 64, page = 64) carries all 16 heads at
every step shape, 15 MiB at ``Tc = 256``; more or wider heads, or more rows
per head (GQA groups, long chunks), get fewer.

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

# grid = (rows, kv heads or head blocks, pages): rows/heads partition across
# megacores, the page axis is the online-softmax reduction
_POOL_SEMANTICS = ("parallel", "parallel", "arbitrary")
_POOL_PARAMS = pltpu.CompilerParams(dimension_semantics=_POOL_SEMANTICS)

# the fused-pool kernels' grid is (rows, kv-head blocks, pages): a step
# carries the most kv heads whose reckoned working set (``pool_working_set``)
# fits VMEM_BUDGET, one MiB under the default scoped VMEM limit (16 MiB on
# v5e), which the kernels keep: with a larger ``vmem_limit_bytes`` the TPU
# compiler crashed compiling the whole fused step for a v5e
VMEM_BUDGET = 15 * 2 ** 20


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


def _round_up(n: int, m: int) -> int:
    return -(-n // m) * m


def _vmem_bytes(shape, dtype) -> int:
    """Bytes a VMEM buffer of ``shape`` takes: its minor two axes padded to
    the (8 x 32-bit sublanes, 128 lanes) tile."""
    itemsize = jnp.dtype(dtype).itemsize
    *lead, r, c = shape
    return (math.prod(lead) * _round_up(r, 32 // itemsize)
            * _round_up(c, 128) * itemsize)


def pool_working_set(hb: int, rows: int, page: int, hd: int, kv_dtype,
                     q_dtype) -> int:
    """VMEM bytes of one pool-kernel grid step carrying ``hb`` kv heads of
    ``rows`` query rows each: the double-buffered pool block, the
    double-buffered q and out blocks, the f32 acc/m/l scratch and the f32
    scores and probabilities of ``(hb, rows, page)``."""
    f32 = jnp.float32
    return (2 * _vmem_bytes((2, hb, page, hd), kv_dtype)
            + 4 * _vmem_bytes((hb, rows, hd), q_dtype)
            + _vmem_bytes((hb, rows, hd), f32)
            + 2 * _vmem_bytes((hb, rows, 1), f32)
            + 2 * _vmem_bytes((hb, rows, page), f32))


def heads_per_step(K: int, rows: int, page: int, hd: int, kv_dtype,
                   q_dtype) -> int:
    """The largest divisor of ``K`` whose grid step fits ``VMEM_BUDGET``
    (1 when none does)."""
    for hb in range(K, 0, -1):
        if K % hb == 0 and pool_working_set(hb, rows, page, hd, kv_dtype,
                                            q_dtype) <= VMEM_BUDGET:
            return hb
    return 1


def last_pages(q_starts, n_reals, is_decode, *, tc: int, page: int,
               pps: int):
    """Index of the last page each row's attention reads, clamped to the
    table: ``q_start // page`` for a decode row (0 for an empty one),
    ``(q_start + tc - 1) // page`` for a chunk row, bucket padding included.
    Plain arithmetic, so it takes numpy (the engine's counters) and jax
    arrays (the kernels' scalar-prefetch operand) alike."""
    dec = (is_decode != 0) * 1
    last = (dec * (q_starts // page) * (n_reals > 0)
            + (1 - dec) * ((q_starts + tc - 1) // page))
    return last.clip(0, pps - 1)


def _attend_pages(b, last_ref, q_ref, kv_ref, o_ref, acc_ref, m_ref, l_ref,
                  *, visible, page: int, scale: float):
    """The page loop all three pool kernels share: grid step ``i`` folds
    page ``i`` of row ``b`` into the online softmax of ``hb`` kv heads at
    once, for pages up to ``last_ref[b]``. A later page is never DMA'd (the
    index map repeats the last block) nor computed; page 0 holds a visible
    key for every row that has one, so skipping the rest changes no bit of
    its reduction. ``visible(k_pos, row)`` masks scores of shape
    ``(hb, rows, page)``."""
    i = pl.program_id(2)

    @pl.when(i == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    @pl.when(i <= last_ref[b])
    def _update():
        q = q_ref[0].astype(jnp.float32)                   # (hb, rows, hd)
        k = kv_ref[0, 0].astype(jnp.float32)               # (hb, page, hd)
        s = jax.lax.dot_general(q, k, (((2,), (2,)), ((0,), (0,))),
                                preferred_element_type=jnp.float32) * scale
        k_pos = i * page + jax.lax.broadcasted_iota(jnp.int32, s.shape, 2)
        row = jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
        s = jnp.where(visible(k_pos, row), s, NEG_INF)

        m_prev = m_ref[...]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
        p = jnp.exp(s - m_new)
        alpha = jnp.exp(m_prev - m_new)
        l_ref[...] = alpha * l_ref[...] + jnp.sum(p, axis=-1, keepdims=True)
        v = kv_ref[0, 1].astype(jnp.float32)               # (hb, page, hd)
        acc_ref[...] = acc_ref[...] * alpha + jax.lax.dot_general(
            p, v, (((2,), (1,)), ((0,), (0,))),
            preferred_element_type=jnp.float32)
        m_ref[...] = m_new

    @pl.when(i == pl.num_programs(2) - 1)
    def _finalize():
        # a row that sees no key (a decode lane's tail, an empty lane) reads
        # zeros
        seen = m_ref[...] > NEG_INF
        o_ref[0] = jnp.where(seen, acc_ref[...] / l_ref[...],
                             0.0).astype(o_ref.dtype)


def _paged_pool_kernel(block_tables_ref, lengths_ref, last_ref, q_ref, kv_ref,
                       o_ref, acc_ref, m_ref, l_ref, *, page: int,
                       scale: float):
    """Decode variant: row ``b`` is one token; key ``k_pos`` is visible
    below ``lengths[b]``."""
    b = pl.program_id(0)
    length = lengths_ref[b]
    _attend_pages(b, last_ref, q_ref, kv_ref, o_ref, acc_ref, m_ref, l_ref,
                  visible=lambda k_pos, row: k_pos < length, page=page,
                  scale=scale)


def _pool_call(kernel, scalars, qg, kv_pool, *, interpret: bool):
    """Run ``kernel`` over the grid ``(rows, K // hb, pps)``.

    ``scalars`` are the scalar-prefetch operands: the block tables first,
    the per-row metadata next, the rows' last pages (``last_pages``) last.
    Each grid step DMAs one ``(1, 2, hb, page, hd)`` pool block — every kv
    head of the page, one contiguous copy when ``hb == K`` — and carries
    the matching ``hb`` heads of q, out and scratch."""
    R, K, rows, hd = qg.shape
    page = kv_pool.shape[3]
    pps = scalars[0].shape[1]
    hb = heads_per_step(K, rows, page, hd, kv_pool.dtype, qg.dtype)

    def head_block(b, h, i, *refs):
        return b, h, 0, 0

    def page_block(b, h, i, bt, *refs):
        return bt[b, jnp.minimum(i, refs[-1][b])], 0, h, 0, 0

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=len(scalars),
        grid=(R, K // hb, pps),
        in_specs=[pl.BlockSpec((1, hb, rows, hd), head_block),
                  pl.BlockSpec((1, 2, hb, page, hd), page_block)],
        out_specs=pl.BlockSpec((1, hb, rows, hd), head_block),
        scratch_shapes=[
            pltpu.VMEM((hb, rows, hd), jnp.float32),
            pltpu.VMEM((hb, rows, 1), jnp.float32),
            pltpu.VMEM((hb, rows, 1), jnp.float32),
        ],
    )
    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct(qg.shape, qg.dtype),
        compiler_params=_POOL_PARAMS,
        interpret=interpret,
    )(*scalars, qg, kv_pool)


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

    last = last_pages(lengths - 1, lengths, jnp.ones_like(lengths), tc=1,
                      page=page, pps=pps)
    kernel = functools.partial(_paged_pool_kernel, page=page, scale=scale)
    out = _pool_call(kernel, (block_tables, lengths, last),
                     q.reshape(B, K, G, hd), kv_pool, interpret=interpret)
    return out.reshape(B, H, hd)


def _chunk_pool_kernel(block_tables_ref, starts_ref, last_ref, q_ref, kv_ref,
                       o_ref, acc_ref, m_ref, l_ref, *, page: int, gsize: int,
                       scale: float):
    """Query-block fused-pool variant: rows are (token, q-head-in-group)
    pairs, so row r is chunk token r // gsize. The causal mask compares each
    page position against the row's absolute position ``q_start + t``; the
    page loop and accumulators are otherwise the decode kernel's."""
    b = pl.program_id(0)
    start = starts_ref[b]
    _attend_pages(b, last_ref, q_ref, kv_ref, o_ref, acc_ref, m_ref, l_ref,
                  visible=lambda k_pos, row: k_pos <= start + row // gsize,
                  page=page, scale=scale)


def _to_head_major(q, K):
    """(R, Tc, H, hd) -> (R, K, Tc*G, hd): row r of a kv head's block is
    token r // G, q head r % G of the head's group."""
    R, Tc, H, hd = q.shape
    G = H // K
    return (q.reshape(R, Tc, K, G, hd).transpose(0, 2, 1, 3, 4)
            .reshape(R, K, Tc * G, hd))


def _from_head_major(out, Tc):
    R, K, rows, hd = out.shape
    G = rows // Tc
    return (out.reshape(R, K, Tc, G, hd).transpose(0, 2, 1, 3, 4)
            .reshape(R, Tc, K * G, hd))


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

    last = last_pages(q_starts, jnp.ones_like(q_starts),
                      jnp.zeros_like(q_starts), tc=Tc,
                      page=page, pps=pps)
    kernel = functools.partial(_chunk_pool_kernel, page=page, gsize=G,
                               scale=scale)
    out = _pool_call(kernel, (block_tables, q_starts, last),
                     _to_head_major(q, K), kv_pool, interpret=interpret)
    return _from_head_major(out, Tc)


def _mixed_pool_kernel(block_tables_ref, starts_ref, n_reals_ref, decode_ref,
                       last_ref, q_ref, kv_ref, o_ref, acc_ref, m_ref, l_ref,
                       *, page: int, gsize: int, scale: float):
    """Mixed-mode fused-pool kernel: every row of the packed batch is a
    query block of Tc tokens with per-row ``(q_start, n_real, is_decode)``
    metadata. A decode lane's single real token (row t = 0) attends to
    ``k_pos <= q_start`` — exactly the decode kernel's ``pos < length``
    mask with ``length = q_start + 1`` — and its tail rows (t >= n_real,
    which is 1) see no key and read zeros, which the caller never reads. A
    chunk row's token t attends to ``k_pos <= q_start + t`` at EVERY row,
    bucket-pad rows included: garbage rows must stay bit-identical to the
    per-request chunk kernel's because their K/V was written into the page
    window (positions later chunks overwrite) and the next layer's writes
    are computed from their outputs. The page loop and the online-softmax
    accumulators are shared with the decode and chunk kernels — a row's
    reduction order never depends on what else rides the launch."""
    b = pl.program_id(0)
    start, n_real = starts_ref[b], n_reals_ref[b]
    dec = decode_ref[b] != 0

    def visible(k_pos, row):
        t = row // gsize
        q_pos = start + jnp.where(dec, 0, t)
        return (k_pos <= q_pos) & (~dec | (t < n_real))

    _attend_pages(b, last_ref, q_ref, kv_ref, o_ref, acc_ref, m_ref, l_ref,
                  visible=visible, page=page, scale=scale)


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
                                       bucket-pad rows: 0)
    is_decode:    (R,) int32           1 marks a decode lane
    -> (R, Tc, H, hd)
    """
    R, Tc, H, hd = q.shape
    P, _, K, page, _ = kv_pool.shape
    G = H // K
    pps = block_tables.shape[1]
    scale = scale if scale is not None else 1.0 / math.sqrt(hd)

    last = last_pages(q_starts, n_reals, is_decode, tc=Tc, page=page,
                      pps=pps)
    kernel = functools.partial(_mixed_pool_kernel, page=page, gsize=G,
                               scale=scale)
    out = _pool_call(kernel,
                     (block_tables, q_starts, n_reals, is_decode, last),
                     _to_head_major(q, K), kv_pool, interpret=interpret)
    return _from_head_major(out, Tc)


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
