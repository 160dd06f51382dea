"""Training substrate tests: optimizer correctness, schedules, checkpoint
atomicity + restart determinism, microbatch-accumulation equivalence,
gradient-compression error feedback, straggler/rebalance policies.
"""
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from _hypothesis_compat import given, settings, st

from repro.configs import get_config, smoke_config
from repro.models import api
from repro.training import checkpoint as ckpt
from repro.training.compression import (compress, compressed_psum, decompress,
                                        init_error_buffers)
from repro.training.data import DataConfig, make_batch
from repro.training.elastic import RebalancePolicy
from repro.training.optimizer import (AdamWConfig, adamw_init, adamw_update,
                                      cosine_schedule, global_norm, wsd_schedule)
from repro.training.train_loop import TrainConfig, make_train_step, train


def test_adamw_reduces_quadratic_loss():
    params = {"w": jnp.asarray([3.0, -2.0, 1.0])}
    ocfg = AdamWConfig(lr=0.1, weight_decay=0.0)
    st_ = adamw_init(params, ocfg)
    loss = lambda p: jnp.sum(jnp.square(p["w"]))
    for _ in range(200):
        g = jax.grad(loss)(params)
        params, st_, _ = adamw_update(g, st_, params, ocfg)
    assert float(loss(params)) < 1e-3


def test_adamw_grad_clip_bounds_update():
    params = {"w": jnp.zeros(4)}
    ocfg = AdamWConfig(lr=1.0, weight_decay=0.0, grad_clip=1.0)
    st_ = adamw_init(params, ocfg)
    g = {"w": jnp.full((4,), 1e6)}
    p2, _, stats = adamw_update(g, st_, params, ocfg)
    assert float(stats["grad_norm"]) > 1e5
    assert float(jnp.abs(p2["w"]).max()) < 1.5      # clipped step ~ lr


def test_schedules_shapes():
    cos = cosine_schedule(1e-3, warmup=10, total=100)
    wsd = wsd_schedule(1e-3, warmup=10, total=100, decay_frac=0.2)
    assert float(cos(jnp.asarray(0))) == 0.0
    assert abs(float(cos(jnp.asarray(10))) - 1e-3) < 1e-9
    assert float(cos(jnp.asarray(100))) < 2e-4
    assert abs(float(wsd(jnp.asarray(50))) - 1e-3) < 1e-9   # stable plateau
    assert float(wsd(jnp.asarray(100))) < 2e-5              # sharp decay


def test_microbatch_accumulation_matches_full_batch():
    cfg = smoke_config(get_config("qwen1.5-0.5b"))
    dcfg = DataConfig(seed=0, batch=4, seq_len=32)
    batch = make_batch(dcfg, cfg, 0)
    params = api.init_params(jax.random.PRNGKey(0), cfg)
    ocfg = AdamWConfig(lr=1e-3)
    s0 = adamw_init(params, ocfg)
    p1, _, st1 = make_train_step(cfg, ocfg, TrainConfig(micro_batches=1))(params, s0, batch)
    p4, _, st4 = make_train_step(cfg, ocfg, TrainConfig(micro_batches=4))(params, s0, batch)
    assert abs(float(st1["loss"]) - float(st4["loss"])) < 1e-5
    d = max(float(jnp.abs(a - b).max())
            for a, b in zip(jax.tree.leaves(p1), jax.tree.leaves(p4)))
    assert d < 5e-5          # f32 accumulation-order noise only


def test_remat_matches_no_remat():
    cfg = smoke_config(get_config("qwen1.5-0.5b"))
    dcfg = DataConfig(seed=0, batch=2, seq_len=32)
    batch = make_batch(dcfg, cfg, 0)
    params = api.init_params(jax.random.PRNGKey(0), cfg)
    g1 = jax.grad(lambda p: api.loss_fn(p, cfg, batch, remat=False))(params)
    g2 = jax.grad(lambda p: api.loss_fn(p, cfg, batch, remat=True))(params)
    d = max(float(jnp.abs(a - b).max())
            for a, b in zip(jax.tree.leaves(g1), jax.tree.leaves(g2)))
    assert d < 1e-5


def test_checkpoint_roundtrip_and_atomicity(tmp_path):
    cfg = smoke_config(get_config("qwen1.5-0.5b"))
    params = api.init_params(jax.random.PRNGKey(0), cfg)
    ckpt.save({"params": params}, str(tmp_path), 7)
    assert ckpt.latest_step(str(tmp_path)) == 7
    back = ckpt.restore({"params": params}, str(tmp_path), 7)
    for a, b in zip(jax.tree.leaves(params), jax.tree.leaves(back["params"])):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    # a torn checkpoint (no COMMITTED marker) is invisible to discovery
    os.makedirs(tmp_path / "step_9")
    (tmp_path / "step_9" / "manifest.json").write_text("{}")
    assert ckpt.latest_step(str(tmp_path)) == 7


def test_failure_injection_and_restart_resumes_exactly(tmp_path):
    """Train 12 steps with a crash at 8; restart resumes from the step-6
    checkpoint and converges to the same trajectory as an uninterrupted run
    (deterministic data + checkpointed optimizer state)."""
    cfg = smoke_config(get_config("qwen1.5-0.5b")).replace(n_layers=2)
    dcfg = DataConfig(seed=1, batch=2, seq_len=16)
    ocfg = AdamWConfig(lr=1e-3)

    ref = train(cfg, dcfg, ocfg, TrainConfig(steps=12), seed=0)

    tc = TrainConfig(steps=12, ckpt_dir=str(tmp_path / "ck"), ckpt_every=3)
    with pytest.raises(RuntimeError, match="injected node failure"):
        train(cfg, dcfg, ocfg, tc, seed=0, fail_at=8)
    resumed = train(cfg, dcfg, ocfg, tc, seed=0)    # restart: resumes at ckpt
    np.testing.assert_allclose(ref["losses"][-3:], resumed["losses"][-3:],
                               rtol=2e-4, atol=2e-5)


@settings(max_examples=25, deadline=None)
@given(st.integers(0, 1000))
def test_compression_error_feedback_bounded(seed):
    """EF property: accumulated quantization error stays O(scale), and the
    running sum of decompressed grads tracks the true sum."""
    rng = np.random.default_rng(seed)
    g_true = jnp.asarray(rng.standard_normal(64), jnp.float32)
    err = jnp.zeros(64)
    acc_true = np.zeros(64)
    acc_q = np.zeros(64)
    for t in range(30):
        g = g_true * (0.9 ** t)
        c, err = compress(g, err)
        acc_true += np.asarray(g)
        acc_q += np.asarray(decompress(c))
    scale = float(jnp.max(jnp.abs(g_true))) / 127.0
    assert float(jnp.abs(err).max()) <= scale * 1.01
    np.testing.assert_allclose(acc_q, acc_true, atol=2 * scale)


def test_compressed_psum_matches_mean():
    import jax
    devs = jax.devices()
    from jax.sharding import Mesh, PartitionSpec as P
    mesh = Mesh(np.array(devs[:1]), ("dp",))
    g = jnp.asarray(np.random.default_rng(0).standard_normal((1, 32)), jnp.float32)
    err = jnp.zeros((1, 32))
    f = jax.shard_map(lambda g, e: compressed_psum(g[0], e[0], "dp"),
                      mesh=mesh, in_specs=(P("dp"), P("dp")), out_specs=P(),
                      check_vma=False)
    out, _ = f(g, err)
    np.testing.assert_allclose(np.asarray(out), np.asarray(g[0]),
                               atol=float(jnp.abs(g).max()) / 100)


def test_rebalance_policy_shrinks_slow_shard():
    pol = RebalancePolicy(n_shards=4)
    sizes = pol.bucket_sizes(64, [1.0, 1.0, 1.0, 3.0])   # shard 3 is a straggler
    assert sum(sizes) == 64
    assert sizes[3] < min(sizes[:3])


def test_wsd_schedule_assigned_to_minicpm():
    """The minicpm-2b config pairs with WSD per its assignment note."""
    cfg = get_config("minicpm-2b")
    assert cfg.name == "minicpm-2b"
    lr = wsd_schedule(1e-2, 10, 1000)
    vals = [float(lr(jnp.asarray(s))) for s in (5, 500, 999)]
    assert vals[0] < vals[1] and vals[2] < vals[1] / 10
