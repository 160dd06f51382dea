"""Chunked continuous-batching prefill: query-block kernel vs oracle, the
mid-page chunk writer, bit-identical logits across chunk splits, bounded step
times + TTFT-under-burst regression, restore-prefetch overlap, the scheduling
invariant error, and the jit-retrace guard (trace count flat across a
mixed-length workload — wired into the tier-1 CI workflow).
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config, smoke_config
from repro.core.aqua_tensor import HOST, REMOTE
from repro.kernels.paged_attention.kernel import paged_prefill_attention_pool
from repro.kernels.paged_attention.ref import \
    paged_prefill_attention_pool_ref
from repro.layers.attention import write_chunk_pages
from repro.models import api, lm
from repro.serving.engine import SchedulingInvariantError, ServingEngine
from repro.serving.kv_cache import PagedStateRuntime
from repro.serving.scheduler import (Decision, bucket_tokens,
                                     split_step_budget)

ARCH = "qwen1.5-0.5b"


def _rand(rng, shape, dtype):
    return jnp.asarray(rng.standard_normal(shape), jnp.float32).astype(dtype)


def _greedy(cfg, params, prompt, n, max_seq=64):
    cache = api.init_decode_state(cfg, 1, max_seq)
    toks = jnp.asarray(prompt, jnp.int32)[None]
    logits, cache = api.prefill(params, cfg, toks, cache)
    out = [int(jnp.argmax(logits[0]))]
    for _ in range(n - 1):
        pos = jnp.asarray([len(prompt) + len(out) - 1], jnp.int32)
        logits, cache = api.decode_step(params, cfg, cache,
                                        jnp.asarray([out[-1]], jnp.int32), pos)
        out.append(int(jnp.argmax(logits[0])))
    return out


# ---------------------------------------------------------------------------
# kernel: query-block fused-pool variant
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("dtype,H", [
    pytest.param(jnp.float32, 4, id="float32"),       # GQA, 2 per kv head
    pytest.param(jnp.bfloat16, 4, id="bfloat16"),
    pytest.param(jnp.float32, 2, id="mha-float32"),
    pytest.param(jnp.bfloat16, 2, id="mha-bfloat16"),
])
def test_chunk_kernel_matches_ref(dtype, H):
    rng = np.random.default_rng(0)
    B, Tc, K, hd, P, page, pps = 5, 6, 2, 32, 16, 8, 4
    q = _rand(rng, (B, Tc, H, hd), dtype)
    pool = _rand(rng, (P, 2, K, page, hd), dtype)
    bt = jnp.asarray(rng.integers(0, P, (B, pps)), jnp.int32)
    # chunks crossing a page boundary, mid-table, in page 0, ending in the
    # last page, and padded past the table; the kernel walks each row only
    # up to the page its last (pad) token sits in
    starts = jnp.asarray([3, 10, 0, 26, 29], jnp.int32)
    out = paged_prefill_attention_pool(q, pool, bt, starts, interpret=True)
    ref = paged_prefill_attention_pool_ref(q, pool, bt, starts)
    tol = 3e-5 if dtype == jnp.float32 else 3e-2
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref, np.float32), atol=tol)


def test_write_chunk_pages_mid_page_boundary_preserves_earlier_rows():
    """Chunked writes (incl. chunk boundaries mid-page) produce the same
    pages as one whole-prompt write: the read-modify-write window must not
    clobber rows written by earlier chunks."""
    rng = np.random.default_rng(1)
    K, hd, page, pps = 2, 16, 8, 3
    S = page * pps                                    # 24 tokens
    k = _rand(rng, (1, S, K, hd), jnp.float32)
    v = _rand(rng, (1, S, K, hd), jnp.float32)
    bt = jnp.asarray([1, 2, 3], jnp.int32)            # slot 0 = scratch
    bt_pad = jnp.concatenate([bt, jnp.zeros((4,), jnp.int32)])

    def write(splits):
        pool = jnp.zeros((pps + 1, 2, K, page, hd), jnp.float32)
        pos = 0
        for c in splits:
            start_page = pos // page
            w = c // page + (1 if c % page else 0) + 1
            win = jax.lax.dynamic_slice(bt_pad, (start_page,), (w,))
            pool = write_chunk_pages(pool, k[:, pos:pos + c],
                                     v[:, pos:pos + c], win,
                                     jnp.int32(pos % page), page_tokens=page)
            pos += c
        return pool

    whole = write([S])
    for splits in ([5, 7, 12], [8, 8, 8], [3, 21], [13, 11]):
        chunked = write(splits)
        np.testing.assert_array_equal(np.asarray(chunked[bt]),
                                      np.asarray(whole[bt]))


# ---------------------------------------------------------------------------
# budget splitting + shape buckets
# ---------------------------------------------------------------------------
def test_split_step_budget_fair_shares_across_pending_prefills():
    # a short prompt's chunk rides the same step as the long prefill
    assert split_step_budget(16, 0, [64, 6]) == [10, 6]
    assert split_step_budget(16, 4, [64, 6]) == [6, 6]
    assert split_step_budget(16, 0, [64]) == [16]
    # lanes ate the budget: the progress floor still grants one token, so an
    # admitted prefill can never starve behind a saturated decode batch
    assert split_step_budget(8, 8, [64]) == [1]
    assert split_step_budget(8, 8, []) == []
    assert split_step_budget(None, 2, [64, 6]) == [64, 6]   # unchunked
    assert sum(split_step_budget(16, 1, [5, 5, 5, 5])) <= 15


def test_bucket_tokens_ladder():
    assert [bucket_tokens(n) for n in (1, 8, 9, 13, 16, 17, 40)] == \
        [8, 8, 16, 16, 16, 32, 64]


# ---------------------------------------------------------------------------
# chunked prefill parity: bit-identical logits for ANY chunk split
# ---------------------------------------------------------------------------
def test_chunked_prefill_bit_identical_across_chunk_sizes():
    """Whole-prompt prefill is the single-chunk case; every split — including
    chunk boundaries mid-page — yields the same logits: each token's
    page-sequence softmax reduction order is split-invariant. Chunks of
    different lengths are programs of different shapes, whose f32 sums XLA
    may order differently, so they agree within a few dozen f32 ulps at the
    logits' O(1) magnitude rather than bit for bit."""
    cfg = smoke_config(get_config(ARCH))
    params = api.init_params(jax.random.PRNGKey(0), cfg)
    rng = np.random.default_rng(2)
    prompt = list(map(int, rng.integers(0, cfg.vocab_size, 17)))
    pad_to = 16                                       # pps(8)+spill, page=8

    def last_logits(splits):
        kv = PagedStateRuntime(cfg, max_seq=64, page_tokens=8, max_running=2)
        pos = 0
        out = None
        for c in splits:
            kv.ensure_capacity(0, pos + c)
            bt = kv.block_tables_prefill(0, pad_to=pad_to)
            toks = jnp.asarray(prompt[pos:pos + c], jnp.int32)[None]
            logits, kv.pools = lm.prefill_chunk_paged(
                params, cfg, toks, kv.pools, bt, jnp.int32(pos),
                jnp.int32(c - 1))
            pos += c
            out = logits[0]
        return np.asarray(out)

    whole = last_logits([17])
    for splits in ([5, 12], [8, 4, 5], [12, 5], [16, 1]):
        np.testing.assert_allclose(last_logits(splits), whole, rtol=0,
                                   atol=1e-5, err_msg=str(splits))


def test_engine_chunked_tokens_match_greedy_incl_mid_page_chunks():
    """End-to-end through the engine with a budget that forces multi-chunk,
    mid-page-boundary prefill (13 % 8 != 0): tokens == direct greedy."""
    cfg = smoke_config(get_config(ARCH))
    params = api.init_params(jax.random.PRNGKey(0), cfg)
    rng = np.random.default_rng(3)
    prompts = [list(map(int, rng.integers(0, cfg.vocab_size, n)))
               for n in (19, 11, 26)]
    truth = [_greedy(cfg, params, p, 4) for p in prompts]
    eng = ServingEngine(cfg, params, max_running=2, max_seq=64,
                        scheduler="cfs", slice_tokens=3, offload_tier=HOST,
                        step_tokens=13)
    for p in prompts:
        eng.submit(p, 4)
    m = eng.run(400)
    got = {tuple(r.prompt_tokens): r.generated for r in eng.finished}
    assert all(got[tuple(p)] == t for p, t in zip(prompts, truth))
    # the budget really chunked the prefills: more chunk executions than
    # requests, and no step ever prefilled more than step_tokens tokens
    assert m.prefills > len(prompts)


# ---------------------------------------------------------------------------
# bounded step times + TTFT under burst (the headline regression)
# ---------------------------------------------------------------------------
def _burst_engine(cfg, params, long_len, step_tokens, rng_seed=4):
    rng = np.random.default_rng(rng_seed)
    long_p = list(map(int, rng.integers(0, cfg.vocab_size, long_len)))
    shorts = [list(map(int, rng.integers(0, cfg.vocab_size, 6)))
              for _ in range(3)]
    eng = ServingEngine(cfg, params, max_running=2, max_seq=96,
                        scheduler="cfs", slice_tokens=3, offload_tier=HOST,
                        step_tokens=step_tokens, prefetch=False)
    eng.submit(long_p, 3, arrival=0.0)                # the head-of-line hog
    for s in shorts:
        eng.submit(s, 3, arrival=0.0)
    m = eng.run(400)
    short_ttfts = [m.ttft[r.rid] for r in eng.finished
                   if len(r.prompt_tokens) == 6]
    assert len(short_ttfts) == 3
    return m, short_ttfts


def test_engine_bounded_step_tokens_and_first_token_under_burst():
    cfg = smoke_config(get_config(ARCH))
    params = api.init_params(jax.random.PRNGKey(0), cfg)
    m_whole, ttft_whole = _burst_engine(cfg, params, 64, None)
    m_chunk, ttft_chunk = _burst_engine(cfg, params, 64, 16)
    # the first short's token no longer waits out the whole 64-token prefill
    assert min(ttft_chunk) < min(ttft_whole)
    # the per-step prefill work is bounded by the token budget; unchunked it
    # scales with the longest prompt (64-token prompt + a 6-token rider)
    assert max(m_chunk.prefill_tokens_trace) <= 16
    assert max(m_whole.prefill_tokens_trace) >= 64
    m_chunk2, _ = _burst_engine(cfg, params, 32, 16)
    assert max(m_chunk2.prefill_tokens_trace) <= 16   # invariant in long_len


def test_ttft_under_burst_improves_at_paper_scale():
    """Simulator, paper regime (34B on A100: a 6k-token prefill is ~0.7 s vs
    a ~45 ms decode step): chunking un-sticks the short prompts queued behind
    the head-of-line prefill — TTFT p50 AND p99 improve by multiples."""
    from repro.core.perfmodel import A100_NVLINK, ModelCost
    from repro.core.simulator import Request, ServingSimulator
    cfg34 = get_config("aqua-codellama-34b")
    mc = ModelCost.from_config(cfg34)
    wb = cfg34.param_count() * 2

    def run(step_tokens):
        sim = ServingSimulator(A100_NVLINK, mc, weight_bytes=wb,
                               kv_capacity_bytes=80e9 - wb - 2e9,
                               scheduler="cfs", offload_tier="fabric",
                               max_running=8, step_tokens=step_tokens)
        reqs = [Request(0, 0.0, 6000, 30)]
        reqs += [Request(i, 0.001 * i, 120, 30) for i in range(1, 13)]
        res = sim.run(reqs)
        ttfts = sorted(r.ttft - r.arrival for r in res.requests
                       if r.prompt_len == 120)
        ts = [e["t"] for e in res.timeline]
        steps = np.diff([0.0] + ts)
        return ttfts, float(max(steps))

    (whole, ms_whole), (chunked, ms_chunk) = run(None), run(256)
    assert chunked[len(chunked) // 2] < whole[len(whole) // 2] / 3.0   # p50
    assert chunked[-1] < whole[-1] / 2.0                               # p99
    # and the max scheduler-round time no longer carries the whole prefill
    assert ms_chunk < ms_whole / 2.0


# ---------------------------------------------------------------------------
# scheduling invariant: never silently skip placement
# ---------------------------------------------------------------------------
def test_place_raises_loudly_when_slots_exhausted():
    cfg = smoke_config(get_config(ARCH))
    params = api.init_params(jax.random.PRNGKey(0), cfg)
    eng = ServingEngine(cfg, params, max_running=1, max_seq=64,
                        scheduler="cfs", slice_tokens=3, offload_tier=HOST)
    r = eng.submit([1, 2, 3, 4], 2)
    eng._free_slots = []                              # simulate a plan bug
    with pytest.raises(SchedulingInvariantError, match="slot"):
        eng._place(Decision([r], [r], []))


# ---------------------------------------------------------------------------
# restore prefetch: transfers overlap compute
# ---------------------------------------------------------------------------
def test_prefetch_overlaps_restore_with_compute():
    cfg = smoke_config(get_config(ARCH))
    params = api.init_params(jax.random.PRNGKey(0), cfg)
    rng = np.random.default_rng(5)
    prompts = [list(map(int, rng.integers(0, cfg.vocab_size, 8)))
               for _ in range(4)]
    truth = [_greedy(cfg, params, p, 6) for p in prompts]

    def serve(prefetch):
        eng = ServingEngine(cfg, params, max_running=2, max_seq=64,
                            scheduler="cfs", slice_tokens=3,
                            offload_tier=REMOTE, step_tokens=16,
                            prefetch=prefetch)
        eng.pager.add_remote_lease("donor0", 2 ** 24)
        for p in prompts:
            eng.submit(p, 6)
        m = eng.run(400)
        got = {tuple(r.prompt_tokens): r.generated for r in eng.finished}
        assert all(got[tuple(p)] == t for p, t in zip(prompts, truth))
        return m

    m_sync = serve(False)
    m_pf = serve(True)
    assert m_sync.prefetched_restores == 0
    assert m_pf.prefetched_restores > 0
    assert m_pf.overlap_hidden_s > 0.0
    # prefetching hides transfer time behind compute: the clock only improves
    assert m_pf.sim_time <= m_sync.sim_time
    assert m_pf.sim_time < m_sync.sim_time - 0.5 * m_pf.overlap_hidden_s


def test_prefetch_misprediction_parks_back_on_new_arrival():
    """A submit() between steps can invalidate the peeked plan; the engine
    must re-park mispredicted prefetches so LOCAL only ever holds the
    planned run set (otherwise ensure_capacity can die mid-step later)."""
    cfg = smoke_config(get_config(ARCH))
    params = api.init_params(jax.random.PRNGKey(0), cfg)
    rng = np.random.default_rng(7)
    prompts = [list(map(int, rng.integers(0, cfg.vocab_size, 8)))
               for _ in range(3)]
    truth = [_greedy(cfg, params, p, n)
             for p, n in zip(prompts, (8, 8, 4))]
    # prefix_cache=False: the final assert counts exact LOCAL pages after
    # drain, and the global prefix cache would (correctly) retain the
    # prompts' refcount-0 pages (cache residency is covered by
    # tests/test_prefix_cache.py)
    eng = ServingEngine(cfg, params, max_running=1, max_seq=64,
                        scheduler="cfs", slice_tokens=2, offload_tier=HOST,
                        step_tokens=16, prefetch=True, prefix_cache=False)
    eng.submit(prompts[0], 8)
    eng.submit(prompts[1], 8)
    for _ in range(100):
        eng.step()
        if eng.metrics.prefetched_restores:
            break
    assert eng.metrics.prefetched_restores > 0
    # the new arrival (vruntime 0) jumps the queue at the next boundary,
    # dropping the freshly-prefetched request from the planned run set
    eng.submit(prompts[2], 4)
    m = eng.run(400)
    got = {tuple(r.prompt_tokens): r.generated for r in eng.finished}
    assert all(got[tuple(p)] == t for p, t in zip(prompts, truth))
    assert eng.kv.aqua.tier_counts()["local"] == 1    # scratch page only


# ---------------------------------------------------------------------------
# jit-retrace guard (run explicitly by the tier-1 CI workflow)
# ---------------------------------------------------------------------------
def test_retrace_guard_trace_count_flat_across_prompt_lengths():
    """Shape buckets make the jit cache size independent of the prompt-length
    mix: a second wave of NEW distinct lengths must add zero traces."""
    cfg = smoke_config(get_config(ARCH))
    params = api.init_params(jax.random.PRNGKey(0), cfg)
    rng = np.random.default_rng(6)

    def serve(lengths):
        eng = ServingEngine(cfg, params, max_running=2, max_seq=64,
                            scheduler="cfs", slice_tokens=3,
                            offload_tier=HOST, step_tokens=16)
        for n in lengths:
            eng.submit(list(map(int, rng.integers(0, cfg.vocab_size, n))), 3)
        eng.run(400)

    lm.reset_trace_counts()
    serve([5, 9, 18, 27])
    c1 = lm.trace_counts()
    serve([6, 11, 22, 31])                            # all-new lengths
    c2 = lm.trace_counts()
    # the engine's sole entry point is the fused step: its trace count must
    # stay flat across a second wave of all-new distinct prompt lengths
    assert c2.get("serve_step", 0) == c1.get("serve_step", 0)
    # packed shapes live on the (chunk-bucket x row-bucket) ladder: with a
    # 16-token budget, Tc in {1, 8, 16}, chunk rows in {1, 2}, decode region
    # present or absent — a handful of traces, independent of prompt lengths
    assert c2.get("serve_step", 0) <= 8
