"""Page-native serving runtime tests (kv plane deep coverage): fused-pool
kernels vs oracles, batched block-table queries, partial-tail metering,
tier-exhaustion errors, bit-identical decoding under CFS preemption in bf16,
unified TTFT accounting, and the context-switch microbenchmark's coalescing
invariants. The other planes (mla/ssm/conv/wkv/shift) are covered in
tests/test_state_paging.py.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config, smoke_config
from repro.core.aqua_tensor import HOST, LOCAL, REMOTE, AquaTensor
from repro.kernels.paged_attention import kernel
from repro.kernels.paged_attention.kernel import (append_kv,
                                                  paged_attention_pool)
from repro.kernels.paged_attention.ref import (append_kv_ref,
                                               paged_attention_pool_ref)
from repro.models import api
from repro.serving.engine import ServingEngine
from repro.serving.kv_cache import PagedStateRuntime

ARCH = "qwen1.5-0.5b"


def _rand(rng, shape, dtype):
    return jnp.asarray(rng.standard_normal(shape), jnp.float32).astype(dtype)


# ---------------------------------------------------------------------------
# kernels: fused page-major pool variant + page-append writer
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("B,H,K,hd,P,page,pps", [
    (2, 4, 2, 64, 16, 8, 4),
    (3, 6, 2, 32, 32, 16, 6),
    (4, 8, 1, 64, 64, 32, 4),               # MQA
    (5, 4, 4, 32, 24, 8, 6),                # MHA, every kind of row
    (5, 16, 2, 32, 24, 8, 6),               # GQA, every kind of row
    (2, 8192, 8, 128, 12, 8, 3),            # the budget splits the heads
])
def test_paged_attention_pool_matches_ref(B, H, K, hd, P, page, pps, dtype):
    """Rows, in order: a context ending in the table's last page, one in
    page 0, one mid-table, an empty row (reads zeros), one ending on the
    first token of the second page. The kernel walks each row only up to
    its last page."""
    rng = np.random.default_rng(0)
    q = _rand(rng, (B, H, hd), dtype)
    pool = _rand(rng, (P, 2, K, page, hd), dtype)
    bt = jnp.asarray(rng.integers(0, P, (B, pps)), jnp.int32)
    edges = [pps * page, 1, (pps // 2) * page + page // 2, 0, page + 1]
    ln = jnp.asarray(edges[:B], jnp.int32)
    if H == 8192:
        assert kernel.heads_per_step(K, H // K, page, hd, dtype, dtype) < K
    out = paged_attention_pool(q, pool, bt, ln, interpret=True)
    ref = paged_attention_pool_ref(q, pool, bt, ln)
    tol = 3e-5 if dtype == jnp.float32 else 3e-2
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref, np.float32), atol=tol)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_append_kv_writes_one_row_per_sequence(dtype):
    rng = np.random.default_rng(1)
    B, K, hd, P, page = 3, 2, 32, 8, 8
    pool = _rand(rng, (P, 2, K, page, hd), dtype)
    k_new = _rand(rng, (B, K, hd), dtype)
    v_new = _rand(rng, (B, K, hd), dtype)
    slots = jnp.asarray([1, 4, 6], jnp.int32)
    offs = jnp.asarray([0, 3, 7], jnp.int32)
    out = append_kv(pool, k_new, v_new, slots, offs, interpret=True)
    ref = append_kv_ref(pool, k_new, v_new, slots, offs)
    np.testing.assert_array_equal(np.asarray(out), np.asarray(ref))
    # untouched pages bit-identical
    untouched = np.setdiff1d(np.arange(P), np.asarray(slots))
    np.testing.assert_array_equal(np.asarray(out[untouched]),
                                  np.asarray(pool[untouched]))


def test_append_then_attend_equals_contiguous():
    """Pages filled token-by-token through the writer op attend identically
    to contiguous attention."""
    from repro.kernels.flash_attention.ref import flash_attention_ref
    rng = np.random.default_rng(2)
    K, hd, page, pps = 2, 32, 4, 3
    S = page * pps
    H = 4
    kc = _rand(rng, (1, S, K, hd), jnp.float32)
    vc = _rand(rng, (1, S, K, hd), jnp.float32)
    pool = jnp.zeros((pps + 1, 2, K, page, hd), jnp.float32)
    bt = jnp.asarray([[1, 2, 3]], jnp.int32)        # slot 0 = scratch
    for t in range(S):
        slot = bt[0, t // page][None]
        off = jnp.asarray([t % page], jnp.int32)
        pool = append_kv(pool, kc[:, t], vc[:, t], slot, off, interpret=True)
    q = _rand(rng, (1, 1, H, hd), jnp.float32)
    ref = flash_attention_ref(q, kc, vc, causal=True)[:, 0]
    out = paged_attention_pool(q[:, 0], pool, bt,
                               jnp.asarray([S], jnp.int32), interpret=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=3e-5)


# ---------------------------------------------------------------------------
# AquaTensor: batched block tables, partial tails, tier exhaustion
# ---------------------------------------------------------------------------
def test_block_tables_batched_query_and_padding():
    t = AquaTensor(page_shape=(4,), local_slots=16,
                   host_slots=8, dtype=jnp.float32)
    a = t.allocate(3)
    b = t.allocate(2)
    bt = t.block_tables([list(a), list(b), []], pad_to=4, pad_slot=9)
    assert bt.shape == (3, 4) and bt.dtype == np.int32
    np.testing.assert_array_equal(bt[0, :3], t.page_table[a, 1])
    assert (bt[0, 3:] == 9).all() and (bt[2] == 9).all()
    # non-LOCAL pages are rejected: the caller must ensure_local first
    t.offload(a[:1], prefer=HOST)
    with pytest.raises(ValueError, match="not LOCAL"):
        t.block_tables([list(a)], pad_to=4)


def test_partial_tail_pages_metered_at_fill():
    t = AquaTensor(page_shape=(8,), local_slots=8,
                   host_slots=16, dtype=jnp.bfloat16)
    lps = t.allocate(4)
    t.write_local(lps, jnp.ones((4, 8), jnp.bfloat16))
    t.set_page_fill(lps[-1:], 0.5)                  # half-filled tail
    t.offload(lps, prefer=HOST)
    assert t.meter.bytes_host == 3.5 * t.page_bytes
    assert t.meter.messages_host == 1               # one coalesced message


def test_move_to_full_tier_raises_memoryerror_not_indexerror():
    """Regression: host-tier exhaustion during migration used to surface as a
    bare IndexError from list.pop on the empty free list."""
    t = AquaTensor(page_shape=(4,), local_slots=8, host_slots=2,
                   dtype=jnp.float32, name="kvtest")
    lps = t.allocate(4)
    t.write_local(lps, jnp.ones((4, 4), jnp.float32))
    with pytest.raises(MemoryError, match="kvtest.*host"):
        t.offload(lps, prefer=HOST)


def test_evict_remote_onto_full_host_raises_memoryerror():
    t = AquaTensor(page_shape=(4,), local_slots=8, host_slots=1,
                   dtype=jnp.float32, name="kvtest")
    t.add_remote_lease("d0", 8)
    lps = t.allocate(3)
    t.write_local(lps, jnp.ones((3, 4), jnp.float32))
    t.offload(lps, prefer=REMOTE)
    with pytest.raises(MemoryError, match="kvtest.*host"):
        t.evict_remote("d0")


def test_logical_ids_cover_every_physical_slot_and_lease():
    """Every physical slot — LOCAL, host, and each remote lease added later
    — can hold a page: logical ids never run out first. At full width one
    1024-token qwen1.5-0.5b request takes 24 x 128 kv pages, so a fixed id
    count would run dry long before the slots do."""
    cfg = smoke_config(get_config(ARCH))
    kv = PagedStateRuntime(cfg, max_seq=64, local_pages=9, host_pages=16400,
                           max_running=2)
    a = kv.planes["kv"].aqua
    assert len(a.allocate(8 + 16400)) == 16408    # LOCAL (bar scratch), HOST
    kv.add_remote_lease("d0", 64 * a.page_bytes)
    assert a.remote_capacity["d0"] == 64
    assert (a.page_table[a.allocate(64), 0] == REMOTE).all()
    with pytest.raises(MemoryError):
        a.allocate(1)


# ---------------------------------------------------------------------------
# engine: paged runtime end-to-end
# ---------------------------------------------------------------------------
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


def test_preemption_bit_identical_bf16_no_f32_roundtrip():
    """Tentpole parity: prefill + decode with interleaved CFS preemptions
    produces bit-identical tokens vs serving each request alone (never
    preempted) — in bf16, with NO float32 roundtrip on the context switches:
    park/restore move the native-dtype page payloads untouched."""
    cfg = smoke_config(get_config(ARCH)).replace(param_dtype="bfloat16",
                                                 compute_dtype="bfloat16")
    params = api.init_params(jax.random.PRNGKey(0), cfg)
    rng = np.random.default_rng(0)
    prompts = [list(map(int, rng.integers(0, cfg.vocab_size,
                                          int(rng.integers(4, 12)))))
               for _ in range(4)]

    def serve(batch):
        eng = ServingEngine(cfg, params, max_running=2, max_seq=64,
                            scheduler="cfs", slice_tokens=3,
                            offload_tier=REMOTE)
        eng.pager.add_remote_lease("donor0", 2 ** 24)
        if batch:                              # contended: CFS preempts
            for p in prompts:
                eng.submit(p, 6)
            m = eng.run(400)
            assert m.preemptions > 0 and m.restores > 0
        else:                                  # serial: never preempted
            for p in prompts:
                eng.submit(p, 6)
                eng.run(400)
            assert eng.metrics.preemptions == 0
        return {tuple(r.prompt_tokens): r.generated for r in eng.finished}, eng

    got_preempted, eng_p = serve(True)
    got_serial, _ = serve(False)
    assert got_preempted == got_serial
    # the paged switches moved native-dtype pages over the fabric
    assert eng_p.kv.meter.bytes_fabric > 0
    assert eng_p.kv.aqua.dtype == jnp.bfloat16


def test_paged_engine_transparent_vs_direct_greedy():
    cfg = smoke_config(get_config(ARCH))
    params = api.init_params(jax.random.PRNGKey(0), cfg)
    rng = np.random.default_rng(3)
    prompts = [list(map(int, rng.integers(0, cfg.vocab_size, 8)))
               for _ in range(4)]
    truth = [_greedy(cfg, params, p, 5) for p in prompts]
    eng = ServingEngine(cfg, params, max_running=2, max_seq=64,
                        scheduler="cfs", slice_tokens=3, offload_tier=HOST)
    for p in prompts:
        eng.submit(p, 5)
    m = eng.run(300)
    got = {tuple(r.prompt_tokens): r.generated for r in eng.finished}
    assert all(got[tuple(p)] == t for p, t in zip(prompts, truth))
    assert m.preemptions > 0
    assert eng.kv.meter.bytes_host > 0


def test_paged_engine_under_local_page_pressure():
    """LOCAL pool sized for ~1 request: the scheduler must plan in pages,
    serving requests in fair rotation without corrupting any KV."""
    cfg = smoke_config(get_config(ARCH))
    params = api.init_params(jax.random.PRNGKey(0), cfg)
    rng = np.random.default_rng(4)
    prompts = [list(map(int, rng.integers(0, cfg.vocab_size, 8)))
               for _ in range(3)]
    truth = [_greedy(cfg, params, p, 5) for p in prompts]
    kv = PagedStateRuntime(cfg, max_seq=64, page_tokens=8, max_running=1)
    eng = ServingEngine(cfg, params, max_running=2, max_seq=64,
                        scheduler="cfs", slice_tokens=3, offload_tier=HOST,
                        kv=kv)
    assert (eng.sched.page_budget == kv.page_budget).all()
    for p in prompts:
        eng.submit(p, 5)
    eng.run(400)
    got = {tuple(r.prompt_tokens): r.generated for r in eng.finished}
    assert all(got[tuple(p)] == t for p, t in zip(prompts, truth))


def test_ttft_includes_full_step_time_on_both_paths():
    """Regression: the prefill path recorded TTFT without the current step's
    accrued time while the decode path included it — they now agree: TTFT of
    an arrival-0 request whose first token lands in step 0 is exactly the
    simulated duration of step 0."""
    cfg = smoke_config(get_config(ARCH))
    params = api.init_params(jax.random.PRNGKey(0), cfg)
    eng = ServingEngine(cfg, params, max_running=2, max_seq=64,
                        scheduler="cfs", slice_tokens=3, offload_tier=HOST)
    r = eng.submit([1, 2, 3, 4], 4, arrival=0.0)
    eng.step()
    m = eng.metrics
    assert r.generated, "prefill must emit the first token"
    assert m.ttft[r.rid] == pytest.approx(m.sim_time)
    assert m.ttft[r.rid] > 0.0


def test_park_meters_exactly_resident_tokens():
    """Regression: parking used to compute the tail fill from the nominal
    context length, so a request whose resident KV ended exactly on a page
    boundary metered a FULL page at 1/page fill. Park meters precisely
    n_tokens of native-dtype KV, for any alignment."""
    cfg = smoke_config(get_config(ARCH))
    kv = PagedStateRuntime(cfg, max_seq=64, page_tokens=8, max_running=1)
    kv.add_remote_lease("d0", 64 * kv.aqua.page_bytes)
    for resident in (3, 8, 9, 16):            # sub-page, boundary, +1, 2 pages
        rid = resident
        kv.ensure_capacity(rid, resident + 1)  # engine ensures ctx, parks ctx-1
        before = kv.meter.bytes_fabric
        kv.park(rid, resident, prefer=REMOTE)
        moved = kv.meter.bytes_fabric - before
        assert moved == pytest.approx(kv.footprint_bytes(resident)), resident
        kv.restore(rid)
        kv.release(rid)


def test_fcfs_paged_budgets_to_completion_under_pressure():
    """Regression: FCFS admission budgeted only one slice of growth, so
    admitted requests outgrew the LOCAL pool mid-serve and the engine died
    with MemoryError. FCFS never preempts, so it must admit only what fits
    to completion — later arrivals wait (the paper's Fig. 1a starvation),
    but every request completes correctly."""
    cfg = smoke_config(get_config(ARCH))
    params = api.init_params(jax.random.PRNGKey(0), cfg)
    rng = np.random.default_rng(5)
    prompts = [list(map(int, rng.integers(0, cfg.vocab_size, 8)))
               for _ in range(2)]
    truth = [_greedy(cfg, params, p, 20) for p in prompts]
    # pages to completion: ceil(28/8)=4 pages x 4 layers = 16 per request;
    # a 20-page budget forces strictly serial FCFS admission
    kv = PagedStateRuntime(cfg, max_seq=64, page_tokens=8, local_pages=21)
    eng = ServingEngine(cfg, params, max_running=2, max_seq=64,
                        scheduler="fcfs", offload_tier=HOST, kv=kv)
    for p in prompts:
        eng.submit(p, 20)
    eng.run(600)
    got = {tuple(r.prompt_tokens): r.generated for r in eng.finished}
    assert all(got[tuple(p)] == t for p, t in zip(prompts, truth))


# ---------------------------------------------------------------------------
# microbenchmark invariants (the acceptance numbers)
# ---------------------------------------------------------------------------
def test_context_switch_benchmark_coalescing_invariants():
    from benchmarks.context_switch import measure
    m = measure(arch=ARCH, ctx_len=52, page_tokens=8, max_seq=64)
    # paged preempt moves ONLY native-dtype payload (tail at its fill)...
    assert m["paged/preempt_bytes"] <= m["native_state_bytes"] + 1e-6
    # ...as one coalesced message per (plane, tier, donor) group
    assert m["paged/preempt_messages"] == 1
    assert m["paged/roundtrip_messages"] == 2
    # the seed blob path paid the f32 repack: ~2x for a bf16 model
    assert m["blob/preempt_bytes"] >= 1.9 * m["native_state_bytes"]
