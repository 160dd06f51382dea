"""Jit'd public wrappers for the AQUA coalescing gather/scatter."""
from __future__ import annotations

import jax
import numpy as np

from repro.kernels.kv_gather.kernel import gather_pages as _gather
from repro.kernels.kv_gather.kernel import scatter_pages as _scatter


def _on_cpu() -> bool:
    return jax.default_backend() == "cpu"


def _canon(pool):
    """Kernel operates on (P, page, d); fold arbitrary page payloads to 2-D."""
    P = pool.shape[0]
    if pool.ndim == 3:
        return pool, pool.shape[1:]
    payload = pool.shape[1:]
    n = int(np.prod(payload)) if payload else 1
    d = 128 if n % 128 == 0 else 1
    return pool.reshape(P, n // d, d), payload


# The programs are named (XLA modules ``jit_aqua_gather_pages`` and
# ``jit_aqua_scatter_pages``) so a profile finds every tier move by name.
def aqua_gather_pages(pool, page_ids):
    """Coalesce scattered pages into one contiguous staging buffer."""
    with jax.named_scope("aqua_gather_pages"):
        p3, payload = _canon(pool)
        out = _gather(p3, page_ids, interpret=_on_cpu())
        return out.reshape((page_ids.shape[0],) + tuple(payload))


def aqua_scatter_pages(pool, staging, page_ids):
    """Scatter a staging buffer back into the page pool. The jit does not
    donate the pool, so XLA copies it before the kernel writes in place."""
    with jax.named_scope("aqua_scatter_pages"):
        p3, payload = _canon(pool)
        s3 = staging.reshape((staging.shape[0],) + p3.shape[1:])
        out = _scatter(p3, s3, page_ids, interpret=_on_cpu())
        return out.reshape(pool.shape)


gather_pages = jax.jit(aqua_gather_pages)
scatter_pages = jax.jit(aqua_scatter_pages)
