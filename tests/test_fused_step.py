"""One-launch fused engine steps: the mixed-mode kernel vs its oracle and
the per-mode kernels, bit-identical fused vs per-request paths for every
servable family (across bucket boundaries, mid-page chunk splits, and a
park/restore mid-step round trip), the speculative chunk-ahead satellite,
cross-plane message coalescing, the launch-count model, and the fused-step
jit-retrace guard (trace count flat across request counts — wired into the
tier-1 CI workflow).
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config, list_archs, smoke_config
from repro.core.aqua_tensor import HOST, REMOTE
from repro.kernels.paged_attention import kernel
from repro.kernels.paged_attention.kernel import (
    paged_attention_pool, paged_mixed_attention_pool,
    paged_prefill_attention_pool)
from repro.kernels.paged_attention.ref import paged_mixed_attention_pool_ref
from repro.models import api, lm
from repro.serving.engine import ServingEngine
from repro.serving.kv_cache import PagedStateRuntime
from repro.serving.scheduler import bucket_tokens

ARCH = "qwen1.5-0.5b"
FAMILIES = ["qwen1.5-0.5b", "rwkv6-3b", "deepseek-v2-lite-16b",
            "jamba-v0.1-52b"]


# Two XLA programs of different shapes may sum f32 products in a different
# order (XLA promises no bit equality across shapes): a few dozen f32 ulps
# at the O(1) magnitude of the smoke model's logits and page payloads.
PROGRAM_ATOL = 1e-5


def _rand(rng, shape, dtype):
    return jnp.asarray(rng.standard_normal(shape), jnp.float32).astype(dtype)


def _assert_close(a, b, err_msg=""):
    np.testing.assert_allclose(np.asarray(a, np.float32),
                               np.asarray(b, np.float32), rtol=0,
                               atol=PROGRAM_ATOL, err_msg=err_msg)


# ---------------------------------------------------------------------------
# kernel: mixed-mode fused-pool variant
# ---------------------------------------------------------------------------
# rows of every kind, page = 8 over a table of 4 pages: decode lanes whose
# context ends in the last page, in page 0, mid-table and on the first
# token of a page, an empty decode lane (n_real 0: sees no key, reads
# zeros); chunk rows crossing a page boundary, a bucket-pad row (n_real 0),
# one ending in the last page, one whose padding runs past the table
_MIXED_ROWS = dict(starts=[31, 0, 9, 16, 4, 5, 0, 24, 28],
                   n_reals=[1, 1, 1, 1, 0, 6, 0, 8, 3],
                   is_dec=[1, 1, 1, 1, 1, 0, 0, 0, 0])
# R, Tc, H, K, hd, P, page, pps, rows
_MIXED_SHAPES = {
    "mha": (9, 8, 4, 4, 32, 12, 8, 4, _MIXED_ROWS),
    "gqa": (9, 8, 8, 2, 32, 12, 8, 4, _MIXED_ROWS),
    # G = 4 query heads per kv head at a 256-token step: 1024 rows per kv
    # head, so VMEM_BUDGET splits the 8 kv heads over several grid steps
    "split": (3, 256, 32, 8, 128, 8, 64, 5,
              dict(starts=[300, 40, 0], n_reals=[1, 200, 0],
                   is_dec=[1, 0, 0])),
}


@pytest.mark.parametrize("dtype,shape", [
    pytest.param(jnp.float32, "mha", id="float32"),
    pytest.param(jnp.bfloat16, "mha", id="bfloat16"),
    pytest.param(jnp.float32, "gqa", id="gqa-float32"),
    pytest.param(jnp.bfloat16, "gqa", id="gqa-bfloat16"),
    pytest.param(jnp.float32, "split", id="split-heads-float32"),
    pytest.param(jnp.bfloat16, "split", id="split-heads-bfloat16"),
])
def test_mixed_kernel_matches_ref(dtype, shape):
    rng = np.random.default_rng(0)
    R, Tc, H, K, hd, P, page, pps, rows = _MIXED_SHAPES[shape]
    if shape == "split":
        hb = kernel.heads_per_step(K, Tc * (H // K), page, hd, dtype, dtype)
        assert hb < K
    q = _rand(rng, (R, Tc, H, hd), dtype)
    pool = _rand(rng, (P, 2, K, page, hd), dtype)
    bt = jnp.asarray(rng.integers(0, P, (R, pps)), jnp.int32)
    starts, n_reals, is_dec = (jnp.asarray(rows[k], jnp.int32)
                               for k in ("starts", "n_reals", "is_dec"))
    out = paged_mixed_attention_pool(q, pool, bt, starts, n_reals, is_dec,
                                     interpret=True)
    ref = paged_mixed_attention_pool_ref(q, pool, bt, starts, n_reals,
                                         is_dec)
    tol = 3e-5 if dtype == jnp.float32 else 3e-2
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref, np.float32), atol=tol)


def test_mixed_kernel_rows_bit_identical_to_per_mode_kernels():
    """The fused launch's decode rows equal the decode kernel and its chunk
    rows equal the chunk kernel BIT-exactly (garbage rows included — their
    K/V lands in the page window, so the next layer's writes depend on
    them): a row's online-softmax reduction never sees its neighbors."""
    rng = np.random.default_rng(1)
    R, Tc, H, K, hd, P, page, pps = 5, 8, 4, 2, 16, 12, 8, 4
    q = _rand(rng, (R, Tc, H, hd), jnp.float32)
    pool = _rand(rng, (P, 2, K, page, hd), jnp.float32)
    bt = jnp.asarray(rng.integers(0, P, (R, pps)), jnp.int32)
    starts = jnp.asarray([5, 9, 21, 3, 11], jnp.int32)
    n_reals = jnp.asarray([1, 1, 1, 6, 8], jnp.int32)
    is_dec = jnp.asarray([1, 1, 1, 0, 0], jnp.int32)
    out = paged_mixed_attention_pool(q, pool, bt, starts, n_reals, is_dec,
                                     interpret=True)
    dec = paged_attention_pool(q[:3, 0], pool, bt[:3], starts[:3] + 1,
                               interpret=True)
    np.testing.assert_array_equal(np.asarray(out[:3, 0]), np.asarray(dec))
    ch = paged_prefill_attention_pool(q[3:], pool, bt[3:], starts[3:],
                                      interpret=True)
    np.testing.assert_array_equal(np.asarray(out[3:]), np.asarray(ch))


def _kv_archs():
    return [a for a in list_archs()
            if lm.supports_paged(get_config(a))
            and "kv" in lm.paged_layout(get_config(a))]


@pytest.mark.parametrize("arch", _kv_archs())
def test_heads_per_step_divides_k_and_fits_the_budget(arch):
    """At the engine's step shapes (all-decode Tc = 1, a 256-token step
    budget) and its page sizes (8 by default, 64 in the benchmark), the
    kv heads a grid step carries divide K and their reckoned VMEM fits the
    budget; qwen1.5-0.5b carries all 16 at every one."""
    cfg = get_config(arch)
    K, hd = cfg.n_kv_heads, cfg.resolved_head_dim
    G = cfg.n_heads // K
    dt = jnp.dtype(cfg.compute_dtype)
    for Tc in (1, 256):
        for page in (8, 64):
            hb = kernel.heads_per_step(K, Tc * G, page, hd, dt, dt)
            assert K % hb == 0
            assert (kernel.pool_working_set(hb, Tc * G, page, hd, dt, dt)
                    <= kernel.VMEM_BUDGET)
            if arch == "qwen1.5-0.5b":
                assert hb == 16


# ---------------------------------------------------------------------------
# fused step == per-request paths, bit-identical, every servable family
# ---------------------------------------------------------------------------
def _prefill_per_request(cfg, params, kv, pad, rid, toks, upto, chunk=8):
    """Drive ``prefill_chunk_paged`` to position ``upto``; returns the last
    chunk's argmax token."""
    pos = 0
    lg = None
    while pos < upto:
        c = min(chunk, upto - pos)
        kv.ensure_capacity(rid, pos + c)
        bt = kv.block_tables_prefill(rid, pad_to=pad)
        tk = np.zeros((1, bucket_tokens(c)), np.int32)
        tk[0, :c] = toks[pos:pos + c]
        lg, kv.pools = api.prefill_chunk_paged(
            params, cfg, jnp.asarray(tk), kv.pools, bt,
            jnp.int32(pos), jnp.int32(c - 1), read_pps=kv.pps)
        pos += c
    return int(np.argmax(np.asarray(lg[0])))


def _fused_vs_per_request(arch, park_mid_step=False):
    """One MIXED step — request 0 decoding, request 1 mid-prefill with a
    bucket-crossing mid-page chunk (6 tokens from position 5), request 2 on
    its first chunk — executed as three per-request calls on runtime A and
    as ONE ``serve_step_paged`` call on runtime B. Logits and every
    request-owned page must agree within ``PROGRAM_ATOL`` (the fused call is
    a program of another shape than the per-request ones)."""
    cfg = smoke_config(get_config(arch))
    params = api.init_params(jax.random.PRNGKey(0), cfg)
    rng = np.random.default_rng(7)
    p0 = list(map(int, rng.integers(0, cfg.vocab_size, 11)))
    p1 = list(map(int, rng.integers(0, cfg.vocab_size, 14)))
    p2 = list(map(int, rng.integers(0, cfg.vocab_size, 9)))

    def setup():
        kv = PagedStateRuntime(cfg, max_seq=64, page_tokens=8,
                               max_running=3, prefix_sharing=False)
        kv.add_remote_lease("d0", 1 << 24)
        pad = kv.pps + 3
        last = {rid: _prefill_per_request(cfg, params, kv, pad, rid, toks, n)
                for rid, toks, n in ((0, p0, 11), (1, p1, 5))}
        return kv, pad, last

    # --- runtime A: the per-request path (chunks, then batched decode)
    kvA, pad, lastA = setup()
    logits = {}
    for rid, toks, start, c in ((1, p1, 5, 6), (2, p2, 0, 7)):
        kvA.ensure_capacity(rid, start + c)
        bt = kvA.block_tables_prefill(rid, pad_to=pad)
        tk = np.zeros((1, bucket_tokens(c)), np.int32)
        tk[0, :c] = toks[start:start + c]
        lg, kvA.pools = api.prefill_chunk_paged(
            params, cfg, jnp.asarray(tk), kvA.pools, bt,
            jnp.int32(start), jnp.int32(c - 1), read_pps=kvA.pps)
        logits[rid] = np.asarray(lg[0])
    kvA.ensure_capacity(0, 12)
    bts = kvA.block_tables([0, None])
    lg, kvA.pools = api.decode_step_paged(
        params, cfg, kvA.pools, bts,
        jnp.asarray([lastA[0], 0], jnp.int32),
        jnp.asarray([11, 0], jnp.int32))
    logits["dec"] = np.asarray(lg[0])

    # --- runtime B: ONE fused call with the identical packed work
    kvB, pad, lastB = setup()
    assert lastA == lastB
    if park_mid_step:
        for rid, n in ((0, 11), (1, 5)):
            kvB.park(rid, n, prefer=REMOTE)
            kvB.restore(rid)
    for rid, n in ((0, 12), (1, 11), (2, 7)):
        kvB.ensure_capacity(rid, n)
    n_dec, Tc = 2, bucket_tokens(7)
    tokens = np.zeros((4, Tc), np.int32)
    q_starts = np.zeros((4,), np.int32)
    n_reals = np.zeros((4,), np.int32)
    tokens[0, 0] = lastB[0]
    q_starts[0], n_reals[0] = 11, 1                   # decode lane 0
    n_reals[1] = 1                                    # idle decode lane
    tokens[2, :6] = p1[5:11]
    q_starts[2], n_reals[2] = 5, 6                    # mid-page chunk
    tokens[3, :7] = p2[0:7]
    q_starts[3], n_reals[3] = 0, 7                    # first chunk
    bt = kvB.block_tables([0, None, 1, 2], pad_to=pad)
    lg, kvB.pools = api.serve_step_paged(
        params, cfg, jnp.asarray(tokens), kvB.pools, bt,
        jnp.asarray(q_starts), jnp.asarray(n_reals), n_decode=n_dec,
        read_pps=kvB.pps)
    lg = np.asarray(lg)
    _assert_close(lg[0], logits["dec"])
    _assert_close(lg[2], logits[1])
    _assert_close(lg[3], logits[2])
    for name in kvA.planes:
        pa, pb = kvA.planes[name], kvB.planes[name]
        for rid in (0, 1, 2):
            _assert_close(pa.aqua.read(pa.flat(rid)),
                          pb.aqua.read(pb.flat(rid)), err_msg=name)


@pytest.mark.parametrize("arch", ["qwen1.5-0.5b", "deepseek-v2-lite-16b"])
def test_fused_step_bit_identical_to_per_request(arch):
    _fused_vs_per_request(arch)


@pytest.mark.slow
@pytest.mark.parametrize("arch", ["rwkv6-3b", "jamba-v0.1-52b"])
def test_fused_step_bit_identical_to_per_request_state_families(arch):
    _fused_vs_per_request(arch)


@pytest.mark.parametrize("arch", ["qwen1.5-0.5b", "jamba-v0.1-52b"])
def test_fused_step_bit_identical_after_mid_step_park_roundtrip(arch):
    """A park/restore round trip between the per-request prefix and the
    fused step (every plane's pages flip tiers and come back, bit for bit)
    leaves the fused step's logits and written pages within
    ``PROGRAM_ATOL`` of the per-request path."""
    _fused_vs_per_request(arch, park_mid_step=True)


def test_fused_chunk_splits_bit_identical_across_bucket_boundaries():
    """Prefilling through the fused entry point with chunk splits that
    cross shape buckets and page boundaries ([17] vs [8, 9] vs [16, 1] vs
    [5, 12]) yields BIT-identical final logits — the packed rows inherit
    the chunked pipeline's split invariance."""
    cfg = smoke_config(get_config(ARCH))
    params = api.init_params(jax.random.PRNGKey(0), cfg)
    rng = np.random.default_rng(2)
    prompt = list(map(int, rng.integers(0, cfg.vocab_size, 17)))

    def last_logits(splits):
        kv = PagedStateRuntime(cfg, max_seq=64, page_tokens=8, max_running=2,
                               prefix_sharing=False)
        pad = kv.pps + 3
        pos, out = 0, None
        for c in splits:
            kv.ensure_capacity(0, pos + c)
            Tc = bucket_tokens(c)
            tokens = np.zeros((1, Tc), np.int32)
            tokens[0, :c] = prompt[pos:pos + c]
            bt = kv.block_tables([0], pad_to=pad)
            lg, kv.pools = api.serve_step_paged(
                params, cfg, jnp.asarray(tokens), kv.pools, bt,
                jnp.asarray([pos], jnp.int32), jnp.asarray([c], jnp.int32),
                n_decode=0, read_pps=kv.pps)
            pos += c
            out = np.asarray(lg[0])
        return out

    whole = last_logits([17])
    for splits in ([8, 9], [16, 1], [5, 12], [8, 4, 5]):
        np.testing.assert_array_equal(last_logits(splits), whole)


# ---------------------------------------------------------------------------
# engine: one call per step, launches O(1) in admitted requests
# ---------------------------------------------------------------------------
def test_engine_issues_one_call_per_step_and_matches_greedy(monkeypatch):
    cfg = smoke_config(get_config(ARCH))
    params = api.init_params(jax.random.PRNGKey(0), cfg)
    rng = np.random.default_rng(3)
    prompts = [list(map(int, rng.integers(0, cfg.vocab_size, n)))
               for n in (19, 11, 26)]

    def greedy(prompt, n):
        cache = api.init_decode_state(cfg, 1, 64)
        toks = jnp.asarray(prompt, jnp.int32)[None]
        logits, cache = api.prefill(params, cfg, toks, cache)
        out = [int(jnp.argmax(logits[0]))]
        for _ in range(n - 1):
            pos = jnp.asarray([len(prompt) + len(out) - 1], jnp.int32)
            logits, cache = api.decode_step(
                params, cfg, cache, jnp.asarray([out[-1]], jnp.int32), pos)
            out.append(int(jnp.argmax(logits[0])))
        return out

    truth = [greedy(p, 4) for p in prompts]
    eng = ServingEngine(cfg, params, max_running=2, max_seq=64,
                        scheduler="cfs", slice_tokens=3, offload_tier=HOST,
                        step_tokens=13)
    # every fused dispatch, stamped with the step it ran in and the calls
    # the per-request path would have made for it (one per chunk row, one
    # for all decode lanes)
    calls = []
    dispatch = api.serve_step_paged

    def counted(*a, **kw):
        n_dec = kw["n_decode"]
        chunks = int((np.asarray(a[6])[n_dec:] > 0).sum())
        calls.append((eng.metrics.steps, chunks + (n_dec > 0)))
        return dispatch(*a, **kw)

    monkeypatch.setattr(api, "serve_step_paged", counted)
    for p in prompts:
        eng.submit(p, 4)
    m = eng.run(400)
    got = {tuple(r.prompt_tokens): r.generated for r in eng.finished}
    assert all(got[tuple(p)] == t for p, t in zip(prompts, truth))
    # one fused call per step with work, however many requests' chunks and
    # decode lanes rode it (the per-request path paid one call per chunk
    # row plus one for decode)
    steps_with_work = {step for step, _ in calls}
    assert m.fused_calls == len(calls) == len(steps_with_work)
    assert max(replaced for _, replaced in calls) > 1
    assert m.prefills > len(prompts)                  # chunking really ran


def test_attention_page_counters_follow_the_rows_last_pages(monkeypatch):
    """``attn_pages_held`` / ``attn_pages_grid`` add up, per fused step and
    kv layer, the pages each packed row's attention walks (through its last
    page: a decode row's ``q_start // page``, a chunk row's
    ``(q_start + Tc - 1) // page``, clamped to the table) and those of the
    kernel's grid (every row x every page)."""
    cfg = smoke_config(get_config(ARCH))
    params = api.init_params(jax.random.PRNGKey(0), cfg)
    rng = np.random.default_rng(4)
    eng = ServingEngine(cfg, params, max_running=2, max_seq=64,
                        scheduler="cfs", slice_tokens=3, offload_tier=HOST,
                        step_tokens=13)
    steps = []
    dispatch = api.serve_step_paged

    def recorded(*a, **kw):
        steps.append((a[2].shape, np.asarray(a[5]), np.asarray(a[6]),
                      kw["n_decode"], kw["read_pps"]))
        return dispatch(*a, **kw)

    monkeypatch.setattr(api, "serve_step_paged", recorded)
    for n in (19, 11, 40):
        eng.submit(list(map(int, rng.integers(0, cfg.vocab_size, n))), 4)
    m = eng.run(400)
    layers = eng.kv.planes["kv"].n_layers
    page = eng.kv.page_tokens
    held = grid = 0
    for (R, Tc), q_starts, n_reals, n_dec, pps in steps:
        for r in range(R):
            if r < n_dec:
                last = q_starts[r] // page if n_reals[r] else 0
            else:
                last = (q_starts[r] + Tc - 1) // page
            held += layers * (min(last, pps - 1) + 1)
        grid += layers * R * pps
    assert steps and len(steps) == m.fused_calls
    assert (m.attn_pages_held, m.attn_pages_grid) == (held, grid)
    assert 0 < m.attn_pages_held < m.attn_pages_grid


# ---------------------------------------------------------------------------
# speculative chunk-ahead (satellite)
# ---------------------------------------------------------------------------
def test_speculative_chunk_ahead_uses_slack_and_stays_correct():
    """With budget slack (one decode lane, step_tokens 24), the head-of-line
    WAITING prefill is speculatively chunked ahead — its prefill_pos
    advances while it waits, its pages park right after, tokens stay
    greedy-exact, and the final position is never speculated (the first
    token belongs to admission)."""
    cfg = smoke_config(get_config(ARCH))
    params = api.init_params(jax.random.PRNGKey(0), cfg)
    rng = np.random.default_rng(4)
    p_short = list(map(int, rng.integers(0, cfg.vocab_size, 6)))
    p_long = list(map(int, rng.integers(0, cfg.vocab_size, 30)))

    def greedy(prompt, n):
        cache = api.init_decode_state(cfg, 1, 64)
        toks = jnp.asarray(prompt, jnp.int32)[None]
        logits, cache = api.prefill(params, cfg, toks, cache)
        out = [int(jnp.argmax(logits[0]))]
        for _ in range(n - 1):
            pos = jnp.asarray([len(prompt) + len(out) - 1], jnp.int32)
            logits, cache = api.decode_step(
                params, cfg, cache, jnp.asarray([out[-1]], jnp.int32), pos)
            out.append(int(jnp.argmax(logits[0])))
        return out

    truth = {tuple(p): greedy(p, 4) for p in (p_short, p_long)}

    def serve(spec):
        eng = ServingEngine(cfg, params, max_running=1, max_seq=64,
                            scheduler="fcfs", offload_tier=HOST,
                            step_tokens=24, spec_chunk_ahead=spec,
                            prefetch=False)
        eng.submit(p_short, 4, arrival=0.0)
        eng.submit(p_long, 4, arrival=0.0)
        m = eng.run(400)
        got = {tuple(r.prompt_tokens): r.generated for r in eng.finished}
        assert got == truth
        return m, {r.rid: r.ttft_step for r in eng.finished}

    m_off, steps_off = serve(False)
    m_on, steps_on = serve(True)
    assert m_off.spec_chunks == 0
    assert m_on.spec_chunks > 0 and m_on.spec_tokens > 0
    # the speculated prefix shortens the long prompt's admission prefill:
    # its first token lands in an earlier STEP (the smoke model is
    # transfer-bound, so the speculation's priced page flips can outweigh
    # its tiny prefill compute on the wall clock — the time-domain win is
    # asserted at paper scale in the simulator test below)
    assert steps_on[1] < steps_off[1]
    # the token budget still bounds every step (slack was reused, not grown)
    assert max(m_on.prefill_tokens_trace) <= 24


def test_speculative_chunk_ahead_priced_in_simulator():
    from repro.core.perfmodel import A100_NVLINK, ModelCost
    from repro.core.simulator import Request, ServingSimulator
    cfg34 = get_config("aqua-codellama-34b")
    mc = ModelCost.from_config(cfg34)
    wb = cfg34.param_count() * 2

    def run(spec):
        # FCFS admission: the long prompt sits slot-blocked behind two
        # long decodes — exactly the slack-rich regime speculation targets.
        # A ~96-token budget keeps the speculated chunks under the decode
        # rounds' memory-bound FLOPs slack, so they piggyback nearly free.
        sim = ServingSimulator(A100_NVLINK, mc, weight_bytes=wb,
                               kv_capacity_bytes=80e9 - wb - 2e9,
                               scheduler="vllm", offload_tier="fabric",
                               max_running=2, step_tokens=96,
                               spec_chunk_ahead=spec)
        reqs = [Request(0, 0.0, 96, 200), Request(1, 0.0, 96, 200),
                Request(2, 0.001, 3000, 20)]
        res = sim.run(reqs)
        return res.requests[2].ttft - res.requests[2].arrival

    # the waiting long prompt's prefill is chunked ahead on decode slack:
    # its first token arrives earlier even though every speculated chunk
    # pays its park/restore page flips
    assert run(True) < run(False) - 0.5


# ---------------------------------------------------------------------------
# cross-plane message coalescing (satellite)
# ---------------------------------------------------------------------------
def test_multi_plane_park_restore_is_one_message_per_tier_donor():
    """A hybrid request's park touches three planes (kv + ssm + conv); the
    fused staging buffer sends ONE fabric message per (tier, donor) — not
    one per plane — and the restore leg matches."""
    cfg = smoke_config(get_config("jamba-v0.1-52b"))
    kv = PagedStateRuntime(cfg, max_seq=64, page_tokens=8, max_running=2)
    kv.add_remote_lease("d0", 1 << 24)
    kv.ensure_capacity(0, 17)
    assert len(kv.planes) == 3
    before = kv.meter.messages_fabric
    kv.park(0, 17, prefer=REMOTE)
    assert kv.meter.messages_fabric - before == 1
    before = kv.meter.messages_fabric
    kv.restore(0)
    assert kv.meter.messages_fabric - before == 1
    # bytes are untouched by coalescing: the payload still moves in full
    assert kv.meter.bytes_fabric > 0


def test_plane_coalescing_priced_in_perfmodel_and_simulator():
    from repro.core.perfmodel import A100_NVLINK, ModelCost, page_flip_time
    mc = ModelCost.from_config(get_config("jamba-v0.1-52b"))
    assert mc.n_planes == 3
    assert ModelCost.from_config(get_config("rwkv6-3b")).n_planes == 2
    assert ModelCost.from_config(get_config(ARCH)).n_planes == 1
    nbytes = mc.context_bytes(4096)
    fused = page_flip_time(A100_NVLINK, nbytes, tier="fabric", n_groups=1)
    split = page_flip_time(A100_NVLINK, nbytes, tier="fabric",
                           n_groups=mc.n_planes)
    assert split - fused == pytest.approx(2 * A100_NVLINK.fabric.latency)


# ---------------------------------------------------------------------------
# launch-count model
# ---------------------------------------------------------------------------
def test_launch_overhead_model():
    from repro.core.perfmodel import (A100_NVLINK, ModelCost,
                                      launch_overhead_time)
    assert launch_overhead_time(A100_NVLINK, 0) == 0.0
    assert launch_overhead_time(A100_NVLINK, 96) == \
        pytest.approx(96 * A100_NVLINK.launch_overhead)
    mc = ModelCost.from_config(get_config("aqua-codellama-34b"))
    assert mc.launch_time(A100_NVLINK, 3) == \
        pytest.approx(3 * mc.n_layers * A100_NVLINK.launch_overhead)
    # pod slices dispatch in lockstep: the tax does not shrink with TP
    assert A100_NVLINK.pod_slice(4).launch_overhead == \
        A100_NVLINK.launch_overhead


def test_simulator_fused_step_p99_no_worse_at_scale():
    """34B/A100, 16+ concurrent requests: the fused step's O(1) dispatch
    keeps step-time p99 at or below the per-request baseline, and the gap
    grows with admitted requests (the acceptance criterion)."""
    from repro.core.perfmodel import A100_NVLINK, ModelCost
    from repro.core.simulator import Request, ServingSimulator
    cfg34 = get_config("aqua-codellama-34b")
    mc = ModelCost.from_config(cfg34)
    wb = cfg34.param_count() * 2

    def run(fused, n):
        sim = ServingSimulator(A100_NVLINK, mc, weight_bytes=wb,
                               kv_capacity_bytes=80e9 - wb - 2e9,
                               scheduler="cfs", offload_tier="fabric",
                               max_running=n, step_tokens=256,
                               fused_step=fused)
        res = sim.run([Request(i, 0.0005 * i, 800, 40) for i in range(n)])
        steps = np.diff([0.0] + [e["t"] for e in res.timeline])
        return float(np.percentile(steps, 99)), float(res.requests[-1].finish)

    for n in (16, 64):
        p99_f, fin_f = run(True, n)
        p99_b, fin_b = run(False, n)
        assert p99_f <= p99_b
        assert fin_f <= fin_b


# ---------------------------------------------------------------------------
# jit-retrace guard (run explicitly by the tier-1 CI workflow)
# ---------------------------------------------------------------------------
def test_retrace_guard_fused_trace_count_flat_across_request_counts():
    """The packed step's shapes live on the (chunk-bucket x row-bucket)
    ladder with chunk rows capped by the run-set size, so the fused entry
    point's trace count is flat in the number of admitted requests: serving
    8x more requests (with all-new prompt lengths) adds ZERO traces."""
    cfg = smoke_config(get_config(ARCH))
    params = api.init_params(jax.random.PRNGKey(0), cfg)
    rng = np.random.default_rng(6)

    def serve(n_requests):
        eng = ServingEngine(cfg, params, max_running=4, max_seq=64,
                            scheduler="cfs", slice_tokens=3,
                            offload_tier=HOST, step_tokens=16)
        for i in range(n_requests):
            n = int(rng.integers(4, 30))
            eng.submit(list(map(int, rng.integers(0, cfg.vocab_size, n))), 2)
        eng.run(1200)
        assert len(eng.finished) == n_requests

    lm.reset_trace_counts()
    serve(8)                       # saturates the slot cap + spec row
    c1 = lm.trace_counts().get("serve_step", 0)
    serve(64)                      # 8x the requests, all-new lengths
    c2 = lm.trace_counts().get("serve_step", 0)
    assert c2 == c1
    assert c1 <= 10                # the bucket ladder, not the workload
