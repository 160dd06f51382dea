"""Compile the serving main path's kernels for a TPU v5e that is described,
not attached, at qwen1.5-0.5b's published widths in bfloat16.

Interpret mode (what every other test runs) accepts programs Mosaic refuses
— a dynamic one-row store into a packed bf16 page, for one. These compiles
catch that at no chip time. The topology is described inside a fixture, so
only the worker that runs this file loads the TPU compiler; where it cannot
be described every test here skips.
"""
import math
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.configs import get_config
from repro.kernels.kv_gather import ops as kv_ops
from repro.kernels.paged_attention import kernel as pa_kernel
from repro.kernels.paged_attention import ops as pa_ops
from repro.models import api, lm
from repro.serving.scheduler import bucket_tokens

ARCH = "qwen1.5-0.5b"
PAGE = 8                   # the engine's default kv_page_tokens
HBM_BYTES = 16 * 10 ** 9   # one v5e chip


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip is written to the persistent cache but
    # cannot be read back without one: keep these out of it
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)


@pytest.fixture
def compiled_kernels(monkeypatch):
    """Steer the ops wrappers off their CPU (interpret-mode) branch: this
    process's backend is the CPU, the compile target is the chip."""
    monkeypatch.setattr(pa_ops, "_on_cpu", lambda: False)
    monkeypatch.setattr(kv_ops, "_on_cpu", lambda: False)


def _sds(sharding, shape, dtype):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _kv_page(cfg, page=PAGE):
    return (2, cfg.n_kv_heads, page, cfg.resolved_head_dim)


def _compile(fn, *args):
    return jax.jit(fn).lower(*args).compile()


def test_append_kv_compiles_in_bf16(one_chip):
    cfg = get_config(ARCH)
    K, hd, B = cfg.n_kv_heads, cfg.resolved_head_dim, 4
    c = _compile(pa_kernel.append_kv,
                 _sds(one_chip, (257,) + _kv_page(cfg), jnp.bfloat16),
                 _sds(one_chip, (B, K, hd), jnp.bfloat16),
                 _sds(one_chip, (B, K, hd), jnp.bfloat16),
                 _sds(one_chip, (B,), jnp.int32),
                 _sds(one_chip, (B,), jnp.int32))
    assert "tpu_custom_call" in c.as_text()


@pytest.mark.parametrize("arch,page", [
    pytest.param(ARCH, PAGE, id=ARCH),
    # the benchmark's pages: the largest reckoned working set, 15 MiB
    pytest.param(ARCH, 64, id=f"{ARCH}-page64"),
    # 36 kv heads: VMEM_BUDGET splits them, 12 per grid step
    pytest.param("minicpm-2b", PAGE, id="minicpm-2b"),
])
def test_mixed_attention_kernel_compiles_in_bf16(one_chip, arch, page):
    """A 256-token mixed step of 12 rows: the VMEM each grid step takes
    (``kernel.heads_per_step``) must fit the scoped limit Mosaic is given."""
    cfg = get_config(arch)
    R, Tc, pps = 12, 256, 512 // page
    c = _compile(pa_kernel.paged_mixed_attention_pool,
                 _sds(one_chip, (R, Tc, cfg.n_heads, cfg.resolved_head_dim),
                      jnp.bfloat16),
                 _sds(one_chip, (1025,) + _kv_page(cfg, page), jnp.bfloat16),
                 _sds(one_chip, (R, pps), jnp.int32),
                 _sds(one_chip, (R,), jnp.int32),
                 _sds(one_chip, (R,), jnp.int32),
                 _sds(one_chip, (R,), jnp.int32))
    assert "tpu_custom_call" in c.as_text()


@pytest.mark.parametrize("op", ["gather", "scatter"])
def test_kv_gather_compiles_at_kv_page_shape(one_chip, compiled_kernels, op):
    cfg = get_config(ARCH)
    n = 64
    pool = _sds(one_chip, (1025,) + _kv_page(cfg), jnp.bfloat16)
    ids = _sds(one_chip, (n,), jnp.int32)
    if op == "gather":
        c = kv_ops.gather_pages.lower(pool, ids).compile()
    else:
        staging = _sds(one_chip, (n,) + _kv_page(cfg), jnp.bfloat16)
        c = kv_ops.scatter_pages.lower(pool, staging, ids).compile()
    assert "tpu_custom_call" in c.as_text()


def test_fused_step_compiles_and_fits_one_chip(one_chip, compiled_kernels):
    """The whole jitted fused step at the shapes ``chip_smoke.py`` serves:
    4 decode lanes plus a chunk region of bucket(4 + 1) rows at a 256-token
    step budget, max_seq 512."""
    cfg = get_config(ARCH)
    max_running, max_seq, step_tokens = 4, 512, 256
    pps = math.ceil(max_seq / PAGE)
    slots = max_running * cfg.n_layers * pps + 1
    pps_pad = pps + math.ceil(bucket_tokens(max_seq) / PAGE) + 1
    R = max_running + bucket_tokens(max_running + 1, lo=1)
    Tc = bucket_tokens(step_tokens)
    params = jax.tree.map(
        lambda l: _sds(one_chip, l.shape, l.dtype), api.param_specs(cfg))
    pools = {"kv": _sds(one_chip, (slots,) + _kv_page(cfg), jnp.bfloat16)}
    tables = {"kv": _sds(one_chip, (cfg.n_layers, 1, R, pps_pad), jnp.int32)}
    step = lm._serve_step_jit(cfg, "pallas", pps, max_running, "mixed")
    c = step.lower(params, _sds(one_chip, (R, Tc), jnp.int32), pools, tables,
                   _sds(one_chip, (R,), jnp.int32),
                   _sds(one_chip, (R,), jnp.int32), None).compile()
    assert "tpu_custom_call" in c.as_text()
    ma = c.memory_analysis()
    live = (ma.argument_size_in_bytes + ma.output_size_in_bytes
            + ma.temp_size_in_bytes - ma.alias_size_in_bytes)
    assert live < HBM_BYTES, live
