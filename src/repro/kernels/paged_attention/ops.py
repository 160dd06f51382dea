"""Jit'd public wrappers for paged decode attention + page writers.

These are the ops the serving hot path calls. Backend policy — enforced by
a CI grep-guard (no hard-coded interpreter pin anywhere under ``src/``):

  * On TPU the kernels run COMPILED (Mosaic), with grid partitioning
    declared over the packed row and kv-head axes
    (``kernel._POOL_SEMANTICS``). Compiled outputs match the ``ref.py``
    oracles and the dense model path under tolerances; they are not
    promised bit-identical to interpret mode.
  * On the CPU backend the same programs run in interpret mode. The ONLY
    sanctioned way to request it on an engine-path call is this module's
    ``interpret=_on_cpu()`` — hard-coding the flag to ``True`` would
    silently pin the compiled pass back to the interpreter on hardware.

``impl='xla'`` callers can use the jnp oracles in ``ref.py`` instead.
"""
from __future__ import annotations

import functools

import jax

from repro.kernels.paged_attention.kernel import append_kv as _append_kv
from repro.kernels.paged_attention.kernel import paged_attention as _kernel
from repro.kernels.paged_attention.kernel import \
    paged_attention_pool as _kernel_pool
from repro.kernels.paged_attention.kernel import \
    paged_mixed_attention_pool as _kernel_mixed
from repro.kernels.paged_attention.kernel import \
    paged_prefill_attention_pool as _kernel_chunk


def _on_cpu() -> bool:
    return jax.default_backend() == "cpu"


@jax.jit
def paged_attention(q, k_pages, v_pages, block_tables, lengths):
    return _kernel(q, k_pages, v_pages, block_tables, lengths,
                   interpret=_on_cpu())


@jax.jit
def paged_attention_pool(q, kv_pool, block_tables, lengths):
    """Decode attention reading the fused page-major AquaTensor pool."""
    return _kernel_pool(q, kv_pool, block_tables, lengths,
                        interpret=_on_cpu())


@jax.jit
def paged_prefill_attention_pool(q, kv_pool, block_tables, q_starts):
    """Chunked-prefill attention: a query BLOCK per sequence attends causally
    to every page written so far (the query-block fused-pool variant)."""
    return _kernel_chunk(q, kv_pool, block_tables, q_starts,
                         interpret=_on_cpu())


@jax.jit
def paged_mixed_attention_pool(q, kv_pool, block_tables, q_starts, n_reals,
                               is_decode):
    """Mixed-mode fused-pool attention: a packed batch of decode lanes and
    prefill chunk rows — per-row (q_start, n_real, is_decode) metadata —
    served in ONE launch per layer (the fused engine step's hot kernel)."""
    return _kernel_mixed(q, kv_pool, block_tables, q_starts, n_reals,
                         is_decode, interpret=_on_cpu())


@jax.jit
def append_kv(kv_pool, k_new, v_new, slots, offsets):
    """Append one decode token's K/V into each sequence's current page."""
    return _append_kv(kv_pool, k_new, v_new, slots, offsets,
                      interpret=_on_cpu())
