"""Copy-on-write prefix sharing tests: adopted block tables alias physical
pages (strictly fewer physical pages than unshared, bit-identical logits),
copy-on-write isolates a sharer's writes, refcounts keep pages alive until
the last referencer frees them, and the schedulers admit strictly larger run
sets because they budget PHYSICAL pages.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config, smoke_config
from repro.core.aqua_tensor import HOST, AquaTensor
from repro.models import api
from repro.serving.engine import ServingEngine
from repro.serving.kv_cache import PagedStateRuntime
from repro.serving.scheduler import bucket_tokens

ARCH = "qwen1.5-0.5b"
PAD = 11                                  # pps(8) + chunk window spill


def _prefill(kv, cfg, params, rid, prompt, chunks, start=0):
    """Drive chunked prefill for one request directly on the runtime,
    registering completed prefix pages as the engine does. Returns the last
    chunk's logits."""
    pos = start
    for c in chunks:
        kv.ensure_capacity(rid, pos + c)
        kv.make_writable(rid, pos, pos + c)
        bt = kv.block_tables_prefill(rid, pad_to=PAD)
        toks = np.zeros((1, bucket_tokens(c)), np.int32)
        toks[0, :c] = prompt[pos:pos + c]
        lg, kv.pools = api.prefill_chunk_paged(
            params, cfg, jnp.asarray(toks), kv.pools, bt,
            jnp.int32(pos), jnp.int32(c - 1), read_pps=kv.pps)
        pos += c
        kv.register_prefix(rid, pos)
    return np.asarray(lg)


def _decode(kv, cfg, params, rid, ctx0, first_tok, steps):
    """Greedy-decode `steps` tokens for one request; returns logits arrays."""
    out, logs = first_tok, []
    for t in range(steps):
        ctx = ctx0 + t + 1
        kv.ensure_capacity(rid, ctx)
        kv.make_writable(rid, ctx - 1, ctx)
        bts = kv.block_tables([rid, None])
        lg, kv.pools = api.decode_step_paged(
            params, cfg, kv.pools, bts, jnp.asarray([out, 0], jnp.int32),
            jnp.asarray([ctx - 1, 0], jnp.int32))
        logs.append(np.asarray(lg[0]))
        out = int(np.argmax(lg[0]))
    return logs


@pytest.fixture(scope="module")
def qwen():
    cfg = smoke_config(get_config(ARCH))
    return cfg, api.init_params(jax.random.PRNGKey(0), cfg)


# ---------------------------------------------------------------------------
# the acceptance invariant: fewer physical pages, bit-identical logits
# ---------------------------------------------------------------------------
def test_shared_prefix_fewer_physical_pages_bit_identical_logits(qwen):
    """Two requests with an identical 2-page prompt prefix occupy strictly
    fewer physical pages than 2x one request, and the sharer's prefill +
    decode logits are BIT-identical to unshared execution."""
    cfg, params = qwen
    rng = np.random.default_rng(0)
    prefix = list(map(int, rng.integers(0, cfg.vocab_size, 16)))  # 2 pages
    b_prompt = prefix + list(map(int, rng.integers(0, cfg.vocab_size, 5)))

    # unshared truth: B alone on a sharing-disabled runtime
    kv0 = PagedStateRuntime(cfg, max_seq=64, page_tokens=8, max_running=2,
                            prefix_sharing=False)
    lg0 = _prefill(kv0, cfg, params, 0, b_prompt, [8, 8, 5])
    solo_pages = kv0.physical_pages()["kv"]
    dec0 = _decode(kv0, cfg, params, 0, len(b_prompt),
                   int(np.argmax(lg0[0])), 3)

    # shared: A writes the prefix, B adopts it
    kv = PagedStateRuntime(cfg, max_seq=64, page_tokens=8, max_running=2)
    assert kv.sharing
    assert kv.adopt_prefix(0, prefix) == 0        # empty index
    _prefill(kv, cfg, params, 0, prefix, [8, 8])
    matched = kv.adopt_prefix(1, b_prompt)
    assert matched == 16                          # both full prefix pages
    lg1 = _prefill(kv, cfg, params, 1, b_prompt, [5], start=matched)
    dec1 = _decode(kv, cfg, params, 1, len(b_prompt),
                   int(np.argmax(lg1[0])), 3)

    np.testing.assert_array_equal(lg0, lg1)       # first-token logits
    for a, b in zip(dec0, dec1):                  # decode logits
        np.testing.assert_array_equal(a, b)
    # A(2 pages) + B(2 shared + 1 own) per layer < A + B unshared
    both = kv.physical_pages()["kv"]
    assert both < solo_pages + kv.physical_pages()["kv"] // 1  # sanity
    assert both < 2 * solo_pages
    assert sum(kv.logical_pages().values()) > both  # tables alias pages
    assert kv.stats()["sharing"]["prefix_hits"] == 1


def test_full_match_copy_on_write_isolates_the_sharer(qwen):
    """B's prompt IS A's prompt (fully page-aligned): B adopts every page,
    recomputes only the final position — the write clones the shared tail
    page (one CoW per layer row) and A's subsequent decode is unaffected."""
    cfg, params = qwen
    rng = np.random.default_rng(1)
    prompt = list(map(int, rng.integers(0, cfg.vocab_size, 16)))

    # solo truth for both sides
    kv0 = PagedStateRuntime(cfg, max_seq=64, page_tokens=8, max_running=2,
                            prefix_sharing=False)
    lg0 = _prefill(kv0, cfg, params, 0, prompt, [8, 8])
    dec0 = _decode(kv0, cfg, params, 0, len(prompt),
                   int(np.argmax(lg0[0])), 3)

    kv = PagedStateRuntime(cfg, max_seq=64, page_tokens=8, max_running=2)
    kv.adopt_prefix(0, prompt)
    lga = _prefill(kv, cfg, params, 0, prompt, [8, 8])
    assert kv.adopt_prefix(1, prompt) == 16
    n_layers = kv.planes["kv"].n_layers
    # the recompute chunk starts at the last position and CoWs its page
    lgb = _prefill(kv, cfg, params, 1, prompt, [1], start=15)
    assert kv.cow_copies == n_layers
    np.testing.assert_array_equal(lga, lgb)
    # after CoW the tail page is exclusive again; the first page stays shared
    plane = kv.planes["kv"]
    assert int(plane.aqua.refcounts([plane.pages[1][0][1]])[0]) == 1
    assert int(plane.aqua.refcounts([plane.pages[1][0][0]])[0]) == 2
    # B's recompute/decode writes never corrupt A: A decodes bit-identically
    decb = _decode(kv, cfg, params, 1, len(prompt), int(np.argmax(lgb[0])), 3)
    deca = _decode(kv, cfg, params, 0, len(prompt), int(np.argmax(lga[0])), 3)
    for a, b in zip(dec0, deca):
        np.testing.assert_array_equal(a, b)
    for a, b in zip(dec0, decb):
        np.testing.assert_array_equal(a, b)


# ---------------------------------------------------------------------------
# refcount lifecycle
# ---------------------------------------------------------------------------
def test_refcounted_free_keeps_shared_pages_alive():
    """AquaTensor refcounts: freeing one referencer neither releases the
    physical slot nor touches the payload; the last free does both."""
    t = AquaTensor(page_shape=(4,), local_slots=8, host_slots=4,
                   dtype=jnp.float32, name="shared")
    lps = t.allocate(2)
    t.write_local(lps, jnp.arange(8, dtype=jnp.float32).reshape(2, 4))
    t.retain(lps)                                # second block table
    assert (t.refcounts(lps) == 2).all()
    assert t.free(lps) == []                     # first free: deref only
    assert (t.page_table[lps, 0] != -1).all()
    np.testing.assert_array_equal(np.asarray(t.read(lps)).ravel(),
                                  np.arange(8, dtype=np.float32))
    assert t.local_free == 8 - 2                 # slots still occupied
    assert sorted(t.free(lps)) == sorted(int(l) for l in lps)
    assert t.local_free == 8
    with pytest.raises(ValueError, match="retain"):
        t.retain(lps)                            # dead pages can't be shared


def test_release_of_one_requester_preserves_the_others_pages(qwen):
    """Runtime-level: A registers, B adopts, A releases mid-flight — B's
    shared pages survive (never zeroed/reused) and the index entries backed
    by them stay valid until B too is gone. With ``prefix_cache=False`` the
    LAST release drops the index (the pre-cache lifecycle; retention past
    refcount 0 is covered in test_prefix_cache.py)."""
    cfg, params = qwen
    rng = np.random.default_rng(2)
    prompt = list(map(int, rng.integers(0, cfg.vocab_size, 16)))
    kv = PagedStateRuntime(cfg, max_seq=64, page_tokens=8, max_running=2,
                           prefix_cache=False)
    assert kv.sharing and not kv.caching
    kv.adopt_prefix(0, prompt)
    lg = _prefill(kv, cfg, params, 0, prompt, [8, 8])
    assert kv.adopt_prefix(1, prompt + [3, 4]) == 16
    plane = kv.planes["kv"]
    shared_lps = [row[0] for row in plane.pages[1]]
    payload = np.asarray(plane.aqua.read(shared_lps))
    kv.release(0)
    # B still owns the pages: allocated, payload untouched
    assert (plane.aqua.page_table[shared_lps, 0] != -1).all()
    assert (plane.aqua.refcounts(shared_lps) == 1).all()
    np.testing.assert_array_equal(np.asarray(plane.aqua.read(shared_lps)),
                                  payload)
    # a third twin can still adopt from B's live pages
    assert kv.adopt_prefix(2, prompt) == 16
    kv.release(2)
    kv.release(1)
    # last release drops the index too: nothing left to adopt
    assert kv.adopt_prefix(3, prompt) == 0
    assert kv.physical_pages()["kv"] == 1         # only the scratch page


# ---------------------------------------------------------------------------
# schedulers budget physical pages
# ---------------------------------------------------------------------------
def test_marginal_page_cost_discounts_shared_pages(qwen):
    """The engine's CFS page cost is MARGINAL: a request whose prefix pages
    are already counted by a chosen sharer costs only its exclusive pages."""
    cfg, params = qwen
    rng = np.random.default_rng(3)
    prefix = list(map(int, rng.integers(0, cfg.vocab_size, 16)))
    eng = ServingEngine(cfg, params, max_running=2, max_seq=64,
                        scheduler="cfs", slice_tokens=3, offload_tier=HOST)
    a = eng.submit(prefix + [1, 2, 3], 4)
    while not a.prefilled:
        eng.step()
    b = eng.submit(prefix + [4, 5, 6], 4)
    assert b.shared_tokens == 16 and b.prefill_pos == 16
    alone = eng._page_cost_cfs(b, [])
    with_a = eng._page_cost_cfs(b, [a])
    n_layers = eng.kv.planes["kv"].n_layers
    assert (alone - with_a == 2 * n_layers).all()   # both prefix pages


def test_shared_prefix_raises_admission_capacity(qwen):
    """A LOCAL budget too small for two unshared requests runs both sharers
    CONCURRENTLY when they alias a prefix: physical-page budgeting admits
    the pair, and the generated tokens still match the unshared run."""
    cfg, params = qwen
    rng = np.random.default_rng(4)
    prefix = list(map(int, rng.integers(0, cfg.vocab_size, 16)))
    tails = [list(map(int, rng.integers(0, cfg.vocab_size, 4)))
             for _ in range(2)]

    def serve(sharing):
        kv = PagedStateRuntime(cfg, max_seq=64, page_tokens=8,
                               local_pages=27, prefix_sharing=sharing)
        eng = ServingEngine(cfg, params, max_running=2, max_seq=64,
                            scheduler="cfs", slice_tokens=3,
                            offload_tier=HOST, kv=kv)
        lead = eng.submit(prefix + tails[0], 6)
        while not lead.prefilled:
            eng.step()
        eng.submit(prefix + tails[1], 6)
        peak = 0
        while eng.waiting or eng.running:
            eng.step()
            peak = max(peak, sum(r.slot is not None for r in eng.running))
        toks = [r.generated for r in sorted(eng.finished,
                                            key=lambda r: r.rid)]
        return toks, peak

    toks_s, peak_s = serve(True)
    toks_u, peak_u = serve(False)
    assert toks_s == toks_u
    assert peak_s == 2, "sharers must fit the LOCAL budget together"
    assert peak_u == 1, "unshared pair must not fit (budget sized for it)"


# ---------------------------------------------------------------------------
# families / modes that must opt out
# ---------------------------------------------------------------------------
def test_recurrent_state_families_disable_sharing():
    """A recurrent state page summarizes the whole prefix and is rewritten
    every step — families owning one never share (the layout marks their
    planes non-shareable)."""
    for arch in ("rwkv6-3b", "jamba-v0.1-52b"):
        cfg = smoke_config(get_config(arch))
        layout = api.paged_layout(cfg)
        assert not all(s.get("shareable", False) for s in layout.values())
        kv = PagedStateRuntime(cfg, max_seq=64, page_tokens=8)
        assert not kv.sharing
        assert kv.adopt_prefix(0, list(range(24))) == 0


def test_forged_radix_collision_never_aliases_foreign_pages(qwen):
    """Radix children are keyed by their first token block and the walk
    compares edge blocks verbatim: a forged key collision (another prompt's
    block mapped onto this node) yields a miss, never foreign pages."""
    cfg, params = qwen
    rng = np.random.default_rng(6)
    prompt = list(map(int, rng.integers(0, cfg.vocab_size, 16)))
    kv = PagedStateRuntime(cfg, max_seq=64, page_tokens=8, max_running=2)
    kv.adopt_prefix(0, prompt)
    _prefill(kv, cfg, params, 0, prompt, [8, 8])
    other = [t + 1 for t in prompt]
    root = kv._roots[None]
    node = root.children[tuple(prompt[:8])]
    root.children[tuple(other[:8])] = node    # forged hash collision
    assert kv.adopt_prefix(1, other) == 0     # token mismatch -> miss
    assert kv.adopt_prefix(2, prompt) == 16   # honest match still works
    del root.children[tuple(other[:8])]


def test_lora_id_partitions_the_prefix_index(qwen):
    """The same tokens under a different adapter produce different K/V: the
    index never aliases across lora ids (hash seed)."""
    cfg, params = qwen
    rng = np.random.default_rng(5)
    prompt = list(map(int, rng.integers(0, cfg.vocab_size, 16)))
    kv = PagedStateRuntime(cfg, max_seq=64, page_tokens=8, max_running=2)
    kv.adopt_prefix(0, prompt, seed=7)
    _prefill(kv, cfg, params, 0, prompt, [8, 8])
    assert kv.adopt_prefix(1, prompt, seed=8) == 0      # other adapter
    assert kv.adopt_prefix(2, prompt, seed=7) == 16     # same adapter
