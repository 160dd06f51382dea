"""Generic decoder LM covering dense / moe / ssm (RWKV-6) / hybrid (Jamba) / vlm.

The layer stack is executed as a ``lax.scan`` over *layer groups* so HLO size is
O(1) in depth (critical for the 80 dry-run compiles on one CPU core):
  * homogeneous archs: group_size = 1
  * gemma3: group_size = 6 (5 local + 1 global)
  * jamba:  group_size = 8 (attention at index 4, Mamba elsewhere, MoE on odd)
Sub-layer kind depends only on the position *within* the group, so one traced
group body serves every group.
"""
from __future__ import annotations

import functools
from collections import Counter
from typing import Any, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import (DENSE, HYBRID, MOE, SSM, VLM, ModelConfig)
from repro.layers import attention as attn
from repro.layers import mamba as mamba_mod
from repro.layers import mla as mla_mod
from repro.layers import rwkv6 as rwkv_mod
from repro.layers.core import (embed, init_embedding, init_mlp, init_rmsnorm,
                               mlp, rms_norm, unembed)
from repro.layers.moe import init_moe, moe_apply


# ---------------------------------------------------------------------------
# Group structure
# ---------------------------------------------------------------------------
def group_size(cfg: ModelConfig) -> int:
    if cfg.family == HYBRID:
        return cfg.hybrid.period
    if cfg.global_layer_every > 0:
        return cfg.global_layer_every
    return 1


def n_groups(cfg: ModelConfig) -> int:
    gs = group_size(cfg)
    assert cfg.n_layers % gs == 0, (cfg.name, cfg.n_layers, gs)
    return cfg.n_layers // gs


def mixer_kind(cfg: ModelConfig, i: int) -> str:
    """Sequence mixer of sub-layer i (position within group)."""
    if cfg.family == SSM:
        return "rwkv"
    if cfg.family == HYBRID:
        return "attn" if i == cfg.hybrid.attn_index else "mamba"
    if cfg.mla is not None:
        return "mla"
    if cfg.global_layer_every > 0:
        return "attn" if (i + 1) % cfg.global_layer_every == 0 else "attn_local"
    if cfg.sliding_window > 0:
        return "attn_local"
    return "attn"


def ffn_kind(cfg: ModelConfig, i: int) -> Optional[str]:
    if cfg.family == SSM:
        return None                       # channel-mix lives inside the rwkv block
    if cfg.moe is not None and (i % cfg.moe.moe_every) == (cfg.moe.moe_every - 1):
        return "moe"
    return "mlp"


def layer_window(cfg: ModelConfig, i: int) -> int:
    return cfg.sliding_window if mixer_kind(cfg, i) == "attn_local" else 0


# ---------------------------------------------------------------------------
# Params
# ---------------------------------------------------------------------------
def _init_sublayer(key, cfg: ModelConfig, i: int) -> dict:
    kind = mixer_kind(cfg, i)
    k1, k2, k3 = jax.random.split(key, 3)
    dt = cfg.dtype()
    p: dict = {"n1": init_rmsnorm(cfg.d_model, dt)}
    if kind == "rwkv":
        p["mix"] = rwkv_mod.init_rwkv_layer(k1, cfg)
        p["n2"] = init_rmsnorm(cfg.d_model, dt)
        return p
    if kind == "mamba":
        p["mix"] = mamba_mod.init_mamba_layer(k1, cfg)
    elif kind == "mla":
        p["mix"] = mla_mod.init_mla(k1, cfg)
    else:
        p["mix"] = attn.init_attention(k1, cfg)
    fk = ffn_kind(cfg, i)
    if fk:
        p["n2"] = init_rmsnorm(cfg.d_model, dt)
        p["ffn"] = init_moe(k2, cfg) if fk == "moe" else init_mlp(k2, cfg)
    return p


def init_group(key, cfg: ModelConfig) -> dict:
    gs = group_size(cfg)
    keys = jax.random.split(key, gs)
    return {f"sub{i}": _init_sublayer(keys[i], cfg, i) for i in range(gs)}


def init_params(key, cfg: ModelConfig) -> dict:
    ke, kb, kf = jax.random.split(key, 3)
    G = n_groups(cfg)
    blocks = jax.vmap(lambda k: init_group(k, cfg))(jax.random.split(kb, G))
    return {
        "embed": init_embedding(ke, cfg),
        "blocks": blocks,
        "final_norm": init_rmsnorm(cfg.d_model, cfg.dtype()),
    }


def param_specs(cfg: ModelConfig) -> Any:
    """Abstract param shapes (no allocation) for the dry-run."""
    return jax.eval_shape(lambda k: init_params(k, cfg),
                          jax.ShapeDtypeStruct((2,), jnp.uint32))


# ---------------------------------------------------------------------------
# Decode caches
# ---------------------------------------------------------------------------
def _sublayer_cache(cfg: ModelConfig, i: int, batch: int, seq: int, dtype):
    kind = mixer_kind(cfg, i)
    if kind == "rwkv":
        return rwkv_mod.init_rwkv_state(cfg, batch, dtype)
    if kind == "mamba":
        return mamba_mod.init_mamba_state(cfg, batch, dtype)
    if kind == "mla":
        return mla_mod.make_mla_cache(cfg, batch, seq, dtype)
    return attn.make_kv_cache(cfg, batch, seq, layer_window(cfg, i), dtype)


def init_decode_state(cfg: ModelConfig, batch: int, seq: int, dtype=None):
    """Stacked (over groups) cache pytree."""
    dt = dtype or jnp.dtype(cfg.compute_dtype)
    gs = group_size(cfg)
    one = {f"sub{i}": _sublayer_cache(cfg, i, batch, seq, dt) for i in range(gs)}
    G = n_groups(cfg)
    return jax.tree.map(lambda x: jnp.broadcast_to(x[None], (G,) + x.shape), one)


def decode_state_specs(cfg: ModelConfig, batch: int, seq: int, dtype=None):
    return jax.eval_shape(
        functools.partial(init_decode_state, cfg, batch, seq, dtype))


def supports_paged(cfg: ModelConfig) -> bool:
    """True when the request's ENTIRE dynamic context can live on AquaTensor
    pages — i.e. every sub-layer's state has a page plane in
    :func:`paged_layout`: full (unwindowed, uncapped) GQA/MQA attention KV,
    Mamba ssm/conv tails, RWKV6 wkv + token-shift state, or the MLA latent
    cache. Ring-buffer windowed layers and encoder-decoder stacks are the
    only remaining exceptions (ROADMAP follow-up)."""
    if cfg.family not in (DENSE, MOE, VLM, SSM, HYBRID):
        return False
    if cfg.attn_logit_softcap > 0:
        return False
    gs = group_size(cfg)
    return all(mixer_kind(cfg, i) in ("attn", "rwkv", "mamba", "mla")
               and layer_window(cfg, i) == 0 for i in range(gs))


def paged_layout(cfg: ModelConfig) -> dict:
    """Map every dynamic-context leaf of the family onto a page PLANE.

    A plane is one AquaTensor pool; every sub-layer position (within the
    layer group) contributes its state leaves to the planes listed here, in
    group order. Two plane kinds:

      * ``tokens`` — grows with context, ``ceil(ctx/page_tokens)`` pages per
        layer. ``kv``: payload ``(2, n_kv, page, hd)`` (attention K/V);
        ``mla``: payload ``(page, kv_lora + rope_dim)`` (fused latent+rope).
      * ``state``  — fixed-size recurrent state, ONE page per layer whose
        payload is exactly the leaf. ``ssm``: ``(di, ds)`` f32; ``conv``:
        ``(d_conv-1, di)`` native; ``wkv``: ``(H, hd, hd)`` f32; ``shift``:
        ``(2, d_model)`` native (rows: time-mix / channel-mix shifts).

    Token planes are SHAREABLE (``"shareable": True``): their pages are
    position-addressed and immutable once prefill has written them, so two
    requests with a common page-aligned prompt prefix can alias the same
    physical pages and a prefill chunk may start past the shared prefix
    (``q_start > 0`` on its first chunk — the block tables carry the shared
    pages, so attention/MLA reads cover them without recomputation). State
    planes are NOT shareable: a recurrent state page is rewritten on every
    chunk/decode step and summarizes the whole prefix, so the runtime
    disables prefix sharing for any family that owns one.

    Returns ``{name: {"kind", "positions", "dtype", "shareable", ...}}``
    where token planes carry ``dims`` + ``token_bytes`` and state planes
    carry ``shape``.
    """
    assert supports_paged(cfg), f"{cfg.name}: not paged-servable"
    from repro.layers import mamba as _mam
    native = jnp.dtype(cfg.compute_dtype)
    planes: dict = {}

    def add(name, i, **kw):
        planes.setdefault(name, dict(positions=[], **kw))["positions"].append(i)

    K, hd = cfg.n_kv_heads, cfg.resolved_head_dim
    for i in range(group_size(cfg)):
        kind = mixer_kind(cfg, i)
        if kind == "attn":
            add("kv", i, kind="tokens", dtype=native, dims=(K, hd),
                token_bytes=2 * K * hd * native.itemsize, shareable=True)
        elif kind == "mla":
            C = cfg.mla.kv_lora_rank + cfg.mla.qk_rope_head_dim
            add("mla", i, kind="tokens", dtype=native, dims=(C,),
                token_bytes=C * native.itemsize, shareable=True)
        elif kind == "mamba":
            di, ds, dc, _ = _mam._dims(cfg)
            add("ssm", i, kind="state", dtype=jnp.dtype(jnp.float32),
                shape=(di, ds), shareable=False)
            add("conv", i, kind="state", dtype=native, shape=(dc - 1, di),
                shareable=False)
        elif kind == "rwkv":
            rhd = cfg.ssm.rwkv_head_dim
            H = cfg.d_model // rhd
            add("wkv", i, kind="state", dtype=jnp.dtype(jnp.float32),
                shape=(H, rhd, rhd), shareable=False)
            add("shift", i, kind="state", dtype=native,
                shape=(2, cfg.d_model), shareable=False)
        else:  # pragma: no cover — guarded by supports_paged
            raise ValueError(f"{cfg.name}: sub-layer {i} ({kind}) has no "
                             "page plane")
    return planes


# ---------------------------------------------------------------------------
# Forward (training): full sequence, no cache
# ---------------------------------------------------------------------------
def _sp_constrain(x, shard_axes):
    """Sequence parallelism (Megatron SP): the residual stream carried across
    layer groups is sequence-sharded over the 'model' axis, so the per-layer
    stack saved for the scan backward is 1/TP of the naive size (the dominant
    train-memory term — see EXPERIMENTS.md §Perf). XLA inserts the
    all-gather/reduce-scatter transitions around attention/MLP."""
    if not shard_axes or not shard_axes.get("sp"):
        return x
    from repro.models.losses import constrain
    mesh = shard_axes["mesh"]
    tp_n = dict(zip(mesh.axis_names, mesh.devices.shape))[shard_axes["tp"]]
    if x.ndim >= 3 and x.shape[1] % tp_n == 0 and x.shape[1] >= tp_n:
        return constrain(x, (shard_axes["dp"], shard_axes["tp"], None))
    return x


def _group_train(gp, cfg: ModelConfig, x, shard_axes=None):
    aux = jnp.zeros((), jnp.float32)
    x = _sp_constrain(x, shard_axes)
    for i in range(group_size(cfg)):
        p = gp[f"sub{i}"]
        kind = mixer_kind(cfg, i)
        if kind == "rwkv":
            st = rwkv_mod.init_rwkv_state(cfg, x.shape[0], x.dtype)
            x, _ = rwkv_mod.rwkv_block(p["mix"], cfg, x, st,
                                       {"n1": p["n1"], "n2": p["n2"]})
            continue
        h = rms_norm(p["n1"], x, cfg.rmsnorm_eps)
        if kind == "mamba":
            st = mamba_mod.init_mamba_state(cfg, x.shape[0], x.dtype)
            h, _ = mamba_mod.mamba_forward(p["mix"], cfg, h, st,
                                           shard_axes=shard_axes)
        elif kind == "mla":
            h = mla_mod.mla_full(p["mix"], cfg, h)
        else:
            h = attn.attention_full(p["mix"], cfg, h, window=layer_window(cfg, i))
        x = x + h
        fk = ffn_kind(cfg, i)
        if fk:
            h = rms_norm(p["n2"], x, cfg.rmsnorm_eps)
            if fk == "moe":
                h, a = moe_apply(p["ffn"], cfg, h, shard_axes=shard_axes)
                aux = aux + a
            else:
                h = mlp(p["ffn"], cfg, h)
            x = x + h
    return _sp_constrain(x, shard_axes), aux


def forward(params, cfg: ModelConfig, tokens, *, prefix_embeds=None,
            remat: bool = False, shard_axes=None):
    """tokens: (B,T) -> logits (B, T(+P), V); returns (logits, aux_loss)."""
    from repro.models.losses import constrain
    x = embed(params["embed"], cfg, tokens)
    if prefix_embeds is not None:
        x = jnp.concatenate([prefix_embeds.astype(x.dtype), x], axis=1)
    if shard_axes:
        x = constrain(x, (shard_axes["dp"], None, None))

    def body_fn(gp, cfg, x):
        return _group_train(gp, cfg, x, shard_axes)
    body = body_fn
    if remat:
        # full remat: at d_ff up to 8*d_model, saving projection outputs
        # (dots_*_saveable policies) costs ~5.6 GB/layer-stack at this scale;
        # recomputing the whole group body in the backward is the right
        # trade (see EXPERIMENTS.md §Perf iteration log)
        body = jax.checkpoint(body, static_argnums=(1,))

    def scan_body(carry, gp):
        x, aux = carry
        x, a = body(gp, cfg, x)
        return (x, aux + a), None

    (x, aux), _ = jax.lax.scan(scan_body, (x, jnp.zeros((), jnp.float32)),
                               params["blocks"])
    x = rms_norm(params["final_norm"], x, cfg.rmsnorm_eps)
    logits = unembed(params["embed"], cfg, x)
    return logits, aux


def loss_fn(params, cfg: ModelConfig, batch: dict, *, remat: bool = False,
            shard_axes=None):
    """Next-token cross-entropy (+ MoE aux). batch: tokens (B,T), prefix_embeds?"""
    from repro.models.losses import shifted_xent
    tokens = batch["tokens"]
    logits, aux = forward(params, cfg, tokens,
                          prefix_embeds=batch.get("prefix_embeds"),
                          remat=remat, shard_axes=shard_axes)
    P = logits.shape[1] - tokens.shape[1]
    loss = shifted_xent(logits[:, P:], tokens, shard_axes)
    if cfg.moe is not None:
        loss = loss + cfg.moe.aux_loss_coef * aux / max(cfg.n_layers // cfg.moe.moe_every, 1)
    return loss


# ---------------------------------------------------------------------------
# Prefill / decode
# ---------------------------------------------------------------------------
def _group_prefill(gp, cfg: ModelConfig, x, cache, pos_offset=0, shard_axes=None):
    """Run a full-sequence pass, producing filled caches."""
    new_cache = {}
    for i in range(group_size(cfg)):
        p = gp[f"sub{i}"]
        kind = mixer_kind(cfg, i)
        c = cache[f"sub{i}"]
        if kind == "rwkv":
            x, nc = rwkv_mod.rwkv_block(p["mix"], cfg, x,
                                        rwkv_mod.RWKVState(*c),
                                        {"n1": p["n1"], "n2": p["n2"]})
            new_cache[f"sub{i}"] = nc
            continue
        h = rms_norm(p["n1"], x, cfg.rmsnorm_eps)
        if kind == "mamba":
            h, nc = mamba_mod.mamba_forward(p["mix"], cfg, h,
                                            mamba_mod.MambaState(*c),
                                            shard_axes=shard_axes)
        elif kind == "mla":
            h, (c_kv, k_rope) = mla_mod.mla_full(p["mix"], cfg, h, return_cache=True)
            nc = mla_mod.fill_mla_cache(mla_mod.MLACache(*c), c_kv, k_rope)
        else:
            w = layer_window(cfg, i)
            h, (k, v) = attn.attention_full(p["mix"], cfg, h, window=w,
                                            return_kv=True)
            nc = attn.fill_kv_cache(attn.KVCache(*c), k, v, w)
        x = x + h
        fk = ffn_kind(cfg, i)
        if fk:
            h = rms_norm(p["n2"], x, cfg.rmsnorm_eps)
            h = (moe_apply(p["ffn"], cfg, h, shard_axes=shard_axes)[0]
                 if fk == "moe" else mlp(p["ffn"], cfg, h))
            x = x + h
        new_cache[f"sub{i}"] = nc
    return x, new_cache


def prefill(params, cfg: ModelConfig, tokens, cache, *, prefix_embeds=None,
            shard_axes=None):
    """tokens (B,T) + empty cache -> (last-token logits (B,V), filled cache)."""
    x = embed(params["embed"], cfg, tokens)
    if prefix_embeds is not None:
        x = jnp.concatenate([prefix_embeds.astype(x.dtype), x], axis=1)

    def scan_body(x, xs):
        gp, c = xs
        x, nc = _group_prefill(gp, cfg, x, c, shard_axes=shard_axes)
        return x, nc

    x, new_cache = jax.lax.scan(scan_body, x, (params["blocks"], cache))
    x = rms_norm(params["final_norm"], x, cfg.rmsnorm_eps)
    logits = unembed(params["embed"], cfg, x[:, -1:])[:, 0]
    return logits, new_cache


# ---------------------------------------------------------------------------
# Paged prefill / decode: KV lives on AquaTensor pages (serving runtime)
# ---------------------------------------------------------------------------
def _ffn_apply(p, cfg: ModelConfig, x, i: int, *, dropless: bool = False,
               shard_axes=None):
    fk = ffn_kind(cfg, i)
    if not fk:
        return x
    h = rms_norm(p["n2"], x, cfg.rmsnorm_eps)
    if fk == "moe":
        h = moe_apply(p["ffn"], cfg, h, dropless=dropless,
                      shard_axes=shard_axes)[0]
    else:
        h = mlp(p["ffn"], cfg, h)
    return x + h


# One representative arch per paged state family — attention (qwen), MLA
# (deepseek), hybrid attention+Mamba (jamba), RWKV6 — the cross-family axis
# the paging/mesh bit-exactness suites sweep.
PAGED_FAMILY_ARCHS = ("qwen1.5-0.5b", "deepseek-v2-lite-16b",
                      "jamba-v0.1-52b", "rwkv6-3b")

# Traces of the serving entry points, keyed by name. The counter bumps as a
# Python side effect INSIDE the traced function body, so it advances once per
# jit trace (shape bucket), not per call — the CI retrace guard asserts it
# stays flat across a mixed-length workload.
TRACE_COUNTS: Counter = Counter()


def trace_counts() -> dict:
    return dict(TRACE_COUNTS)


def reset_trace_counts():
    TRACE_COUNTS.clear()


def _plane_state_rwkv(pools, tables_g, j, b=None):
    """Assemble an RWKVState from the state pools. ``b=None``: B=1 prefill
    (scalar slots, add the batch axis); else batched decode (slots (B,))."""
    ws, ss = tables_g["wkv"][j], tables_g["shift"][j]
    if b is None:
        return rwkv_mod.RWKVState(pools["wkv"][ws][None],
                                  pools["shift"][ss][0][None],
                                  pools["shift"][ss][1][None])
    return rwkv_mod.RWKVState(pools["wkv"][ws],
                              pools["shift"][ss][:, 0],
                              pools["shift"][ss][:, 1])


def _store_state_rwkv(pools, tables_g, j, nst, b=None):
    ws, ss = tables_g["wkv"][j], tables_g["shift"][j]
    shift = jnp.stack([nst.tm_shift, nst.cm_shift],
                      axis=-2).astype(pools["shift"].dtype)
    if b is None:
        pools["wkv"] = pools["wkv"].at[ws].set(nst.wkv[0])
        pools["shift"] = pools["shift"].at[ss].set(shift[0])
    else:
        pools["wkv"] = pools["wkv"].at[ws].set(nst.wkv)
        pools["shift"] = pools["shift"].at[ss].set(shift)
    return pools


def _group_fwd_paged(gp, cfg: ModelConfig, x, pools, tables_g, *,
                     q_start=None, n_real=None, pos=None,
                     read_pps: Optional[int], impl: str):
    """One layer group against the page pools — shared by chunked prefill
    (B=1, ``q_start``/``n_real`` set) and batched decode (``pos`` set).

    Sub-layer kind is static in the position within the group, so each
    position statically dispatches to its plane(s); ``idx`` tracks each
    plane's running sub-index, matching the runtime's table row order.
    """
    prefill = pos is None
    b = None if prefill else x.shape[0]
    idx: Counter = Counter()
    for i in range(group_size(cfg)):
        p = gp[f"sub{i}"]
        kind = mixer_kind(cfg, i)
        if kind == "rwkv":
            j = idx["wkv"]
            idx["wkv"] += 1
            st = _plane_state_rwkv(pools, tables_g, j, b)
            x, nst = rwkv_mod.rwkv_block(p["mix"], cfg, x, st,
                                         {"n1": p["n1"], "n2": p["n2"]},
                                         n_real=n_real)
            pools = _store_state_rwkv(pools, tables_g, j, nst, b)
            continue
        h = rms_norm(p["n1"], x, cfg.rmsnorm_eps)
        if kind == "mamba":
            j = idx["ssm"]
            idx["ssm"] += 1
            ss, cs = tables_g["ssm"][j], tables_g["conv"][j]
            if prefill:
                st = mamba_mod.MambaState(pools["ssm"][ss][None],
                                          pools["conv"][cs][None])
                h, nst = mamba_mod.mamba_forward(p["mix"], cfg, h, st,
                                                 n_real=n_real)
                pools["ssm"] = pools["ssm"].at[ss].set(nst.ssm[0])
                pools["conv"] = pools["conv"].at[cs].set(
                    nst.conv[0].astype(pools["conv"].dtype))
            else:
                st = mamba_mod.MambaState(pools["ssm"][ss], pools["conv"][cs])
                h, nst = mamba_mod.mamba_decode(p["mix"], cfg, h, st)
                pools["ssm"] = pools["ssm"].at[ss].set(nst.ssm)
                pools["conv"] = pools["conv"].at[cs].set(
                    nst.conv.astype(pools["conv"].dtype))
        elif kind == "mla":
            j = idx["mla"]
            idx["mla"] += 1
            if prefill:
                h, pools["mla"] = mla_mod.mla_prefill_chunk(
                    p["mix"], cfg, h, pools["mla"], tables_g["mla"][j],
                    q_start, read_pps=read_pps)
            else:
                h, pools["mla"] = mla_mod.mla_decode_paged(
                    p["mix"], cfg, h, pools["mla"], tables_g["mla"][j], pos)
        else:
            j = idx["kv"]
            idx["kv"] += 1
            if prefill:
                h, pools["kv"] = attn.attention_prefill_chunk(
                    p["mix"], cfg, h, pools["kv"], tables_g["kv"][j], q_start,
                    read_pps=read_pps, impl=impl)
            else:
                h, pools["kv"] = attn.attention_decode_paged(
                    p["mix"], cfg, h, pools["kv"], tables_g["kv"][j], pos,
                    impl=impl)
        x = x + h
        x = _ffn_apply(p, cfg, x, i, dropless=True)
    return x, pools


def prefill_chunk_paged(params, cfg: ModelConfig, tokens, pools,
                        block_tables, q_start, last_index, *,
                        prefix_embeds=None,
                        read_pps: Optional[int] = None,
                        impl: str = "pallas"):
    """Prefill ONE CHUNK of one request, writing its state straight into the
    page pools — any family, one code path.

    tokens: (1,Tc) — the chunk, bucket-padded. Garbage rows past the real
    length are masked causally for attention/MLA (and overwritten by later
    chunks/decode); for recurrent planes ``n_real = last_index + 1`` zeroes
    their state updates (identity transition), so the carried Mamba/RWKV
    state is bit-exactly the state after the last real token.
    pools: {plane: pool} LOCAL pools (see ``paged_layout``);
    block_tables: {plane: (G, n_sub, ...)} — token planes ``(..., pps_pad)``
    int32 physical slots from position 0, dummy-padded; state planes bare
    physical slots. q_start / last_index: () int32 (traced) — the chunk's
    absolute start position and the row whose logits the caller wants.
    prefix_embeds: (1, P, d) VLM prefix rows — rows of the chunk at absolute
    positions < P take these embeddings instead of the token embedding (the
    engine routes the q_start == 0 chunks of a VLM prompt through here).
    -> (logits (1,V) of ``last_index``, updated pools)

    Whole-prompt prefill is the degenerate single-chunk call; any chunk
    split yields bit-identical logits (split-invariant page reduction for
    attention/MLA, exact state handoff for Mamba/RWKV). MoE FFNs run
    DROPLESS so a token's routing cannot depend on its chunk's occupancy.
    """
    assert supports_paged(cfg), f"{cfg.name}: not paged-servable"
    assert tokens.shape[0] == 1, "chunked prefill is per-request"
    TRACE_COUNTS["prefill_chunk"] += 1
    q_start = jnp.asarray(q_start, jnp.int32).reshape(())
    last_index = jnp.asarray(last_index, jnp.int32).reshape(())
    n_real = last_index + 1
    x = embed(params["embed"], cfg, tokens)
    if prefix_embeds is not None:
        P = prefix_embeds.shape[1]
        rows = q_start + jnp.arange(tokens.shape[1], dtype=jnp.int32)
        pre = jnp.take(prefix_embeds[0], jnp.clip(rows, 0, P - 1), axis=0)
        x = jnp.where((rows < P)[None, :, None], pre[None].astype(x.dtype), x)

    def scan_body(carry, xs):
        x, pools = carry
        gp, tg = xs
        x, pools = _group_fwd_paged(gp, cfg, x, dict(pools), tg,
                                    q_start=q_start, n_real=n_real,
                                    read_pps=read_pps, impl=impl)
        return (x, pools), None

    (x, pools), _ = jax.lax.scan(scan_body, (x, pools),
                                 (params["blocks"], block_tables))
    x = rms_norm(params["final_norm"], x, cfg.rmsnorm_eps)
    last = jax.lax.dynamic_slice_in_dim(x, last_index, 1, axis=1)
    logits = unembed(params["embed"], cfg, last)[:, 0]
    return logits, pools


@functools.lru_cache(maxsize=None)
def _prefill_chunk_jit(cfg: ModelConfig, impl: str, read_pps: Optional[int]):
    """One compiled program per (config, impl, shape bucket)."""
    return jax.jit(lambda params, tokens, pools, bt, q_start, last, prefix:
                   prefill_chunk_paged(params, cfg, tokens, pools, bt,
                                       q_start, last, prefix_embeds=prefix,
                                       read_pps=read_pps, impl=impl))


def prefill_chunk_paged_jit(params, cfg: ModelConfig, tokens, pools,
                            block_tables, q_start, last_index, *,
                            prefix_embeds=None,
                            read_pps: Optional[int] = None,
                            impl: str = "pallas"):
    """Jit'd chunk prefill: callers pass bucket-padded shapes, so the trace
    count is bounded by the bucket ladder, not the prompt-length set."""
    return _prefill_chunk_jit(cfg, impl, read_pps)(params, tokens, pools,
                                                   block_tables, q_start,
                                                   last_index, prefix_embeds)


def decode_step_paged(params, cfg: ModelConfig, pools, block_tables,
                      tokens, pos, *, impl: str = "pallas"):
    """One token for every sequence against the page pools — any family.

    tokens/pos: (B,); pools: {plane: pool}; block_tables: {plane:
    (G, n_sub, B[, pps])} int32 physical LOCAL slots (token planes carry the
    trailing pps axis; state planes are one slot per layer per lane; idle
    lanes point at the plane's scratch page). -> (logits (B,V), pools).
    Decode attention goes through kernels/paged_attention (interpret on CPU)
    when ``impl='pallas'``; ``impl='xla'`` uses the jnp oracle. MLA and the
    recurrent planes read/scatter the pools directly in jnp (shape-stable).
    """
    assert supports_paged(cfg), f"{cfg.name}: not paged-servable"
    TRACE_COUNTS["decode_step"] += 1
    x = embed(params["embed"], cfg, tokens[:, None])

    def scan_body(carry, xs):
        x, pools = carry
        gp, tg = xs
        x, pools = _group_fwd_paged(gp, cfg, x, dict(pools), tg, pos=pos,
                                    read_pps=None, impl=impl)
        return (x, pools), None

    (x, pools), _ = jax.lax.scan(scan_body, (x, pools),
                                 (params["blocks"], block_tables))
    x = rms_norm(params["final_norm"], x, cfg.rmsnorm_eps)
    logits = unembed(params["embed"], cfg, x)[:, 0]
    return logits, pools


@functools.lru_cache(maxsize=None)
def _decode_step_jit(cfg: ModelConfig, impl: str):
    return jax.jit(lambda params, pools, bt, tokens, pos: decode_step_paged(
        params, cfg, pools, bt, tokens, pos, impl=impl))


def decode_step_paged_jit(params, cfg: ModelConfig, pools, block_tables,
                          tokens, pos, *, impl: str = "pallas"):
    """Jit'd paged decode: batch lanes and block tables have fixed padded
    shapes, so the whole step compiles exactly once per (config, impl)."""
    return _decode_step_jit(cfg, impl)(params, pools, block_tables, tokens,
                                       pos)


def _group_fwd_mixed(gp, cfg: ModelConfig, x, pools, tables_g, *,
                     q_starts, n_reals, n_decode: int,
                     read_pps: Optional[int], impl: str):
    """One layer group of a PACKED engine step: rows ``[:n_decode]`` are
    decode lanes (single real token at column 0), the rest prefill chunk
    rows — every plane dispatches per row REGION so each mode keeps its
    per-request math bit-exactly (absorbed MLA decode, batched recurrent
    decode steps, per-lane ``n_real`` identity transitions for chunk rows),
    while the attention plane serves every row in ONE fused kernel launch.

    tables_g: token planes ``(n_sub, R, pps_pad)``, state planes
    ``(n_sub, R)`` — one row per packed lane, scratch for idle/pad rows.
    """
    R, Tc, _ = x.shape
    nd, Rp = n_decode, x.shape[0] - n_decode
    idx: Counter = Counter()

    def merge(h_dec, h_chunk, d):
        if h_dec is not None and Tc > 1:
            h_dec = jnp.concatenate(
                [h_dec, jnp.zeros((nd, Tc - 1, d), h_dec.dtype)], axis=1)
        parts = [h for h in (h_dec, h_chunk) if h is not None]
        return parts[0] if len(parts) == 1 else jnp.concatenate(parts, axis=0)

    for i in range(group_size(cfg)):
        p = gp[f"sub{i}"]
        kind = mixer_kind(cfg, i)
        if kind == "rwkv":
            j = idx["wkv"]
            idx["wkv"] += 1
            ws, ss = tables_g["wkv"][j], tables_g["shift"][j]
            norms = {"n1": p["n1"], "n2": p["n2"]}
            x_dec = x_chunk = None
            if nd:
                st = rwkv_mod.RWKVState(pools["wkv"][ws[:nd]],
                                        pools["shift"][ss[:nd]][:, 0],
                                        pools["shift"][ss[:nd]][:, 1])
                x_dec, nst = rwkv_mod.rwkv_block(p["mix"], cfg, x[:nd, :1],
                                                 st, norms)
                shift = jnp.stack([nst.tm_shift, nst.cm_shift],
                                  axis=-2).astype(pools["shift"].dtype)
                pools["wkv"] = pools["wkv"].at[ws[:nd]].set(nst.wkv)
                pools["shift"] = pools["shift"].at[ss[:nd]].set(shift)
            if Rp:
                st = rwkv_mod.RWKVState(pools["wkv"][ws[nd:]],
                                        pools["shift"][ss[nd:]][:, 0],
                                        pools["shift"][ss[nd:]][:, 1])
                x_chunk, nst = rwkv_mod.rwkv_block(p["mix"], cfg, x[nd:], st,
                                                   norms, n_real=n_reals[nd:])
                shift = jnp.stack([nst.tm_shift, nst.cm_shift],
                                  axis=-2).astype(pools["shift"].dtype)
                pools["wkv"] = pools["wkv"].at[ws[nd:]].set(nst.wkv)
                pools["shift"] = pools["shift"].at[ss[nd:]].set(shift)
            # the rwkv block carries its own residual: decode rows keep
            # their garbage tail columns unchanged
            if x_dec is not None and Tc > 1:
                x_dec = jnp.concatenate([x_dec, x[:nd, 1:]], axis=1)
            parts = [h for h in (x_dec, x_chunk) if h is not None]
            x = parts[0] if len(parts) == 1 else jnp.concatenate(parts, axis=0)
            continue
        h = rms_norm(p["n1"], x, cfg.rmsnorm_eps)
        if kind == "mamba":
            j = idx["ssm"]
            idx["ssm"] += 1
            ss, cs = tables_g["ssm"][j], tables_g["conv"][j]
            h_dec = h_chunk = None
            if nd:
                st = mamba_mod.MambaState(pools["ssm"][ss[:nd]],
                                          pools["conv"][cs[:nd]])
                h_dec, nst = mamba_mod.mamba_decode(p["mix"], cfg,
                                                    h[:nd, :1], st)
                pools["ssm"] = pools["ssm"].at[ss[:nd]].set(nst.ssm)
                pools["conv"] = pools["conv"].at[cs[:nd]].set(
                    nst.conv.astype(pools["conv"].dtype))
            if Rp:
                st = mamba_mod.MambaState(pools["ssm"][ss[nd:]],
                                          pools["conv"][cs[nd:]])
                h_chunk, nst = mamba_mod.mamba_forward(p["mix"], cfg, h[nd:],
                                                       st,
                                                       n_real=n_reals[nd:])
                pools["ssm"] = pools["ssm"].at[ss[nd:]].set(nst.ssm)
                pools["conv"] = pools["conv"].at[cs[nd:]].set(
                    nst.conv.astype(pools["conv"].dtype))
            h = merge(h_dec, h_chunk, h.shape[-1])
        elif kind == "mla":
            j = idx["mla"]
            idx["mla"] += 1
            h, pools["mla"] = mla_mod.mla_mixed_paged(
                p["mix"], cfg, h, pools["mla"], tables_g["mla"][j],
                q_starts, n_reals, n_decode=nd, read_pps=read_pps)
        else:
            j = idx["kv"]
            idx["kv"] += 1
            h, pools["kv"] = attn.attention_mixed_paged(
                p["mix"], cfg, h, pools["kv"], tables_g["kv"][j],
                q_starts, n_reals, n_decode=nd, read_pps=read_pps, impl=impl)
        x = x + h
        x = _ffn_apply(p, cfg, x, i, dropless=True)
    return x, pools


def serve_step_paged(params, cfg: ModelConfig, tokens, pools, block_tables,
                     q_starts, n_reals, *, n_decode: int, prefix_embeds=None,
                     read_pps: Optional[int] = None, impl: str = "pallas"):
    """ONE fused engine step: every scheduled decode token and every
    request's prompt chunk in a single jitted call — any family.

    tokens: (R, Tc) packed rows. Rows ``[:n_decode]`` are decode lanes
    (``Tc`` is 1 on all-decode steps): the lane's next token at column 0,
    ``q_starts[r]`` its position, ``n_reals[r] = 1``; idle lanes hold token
    0 at position 0 against the scratch page. Rows ``[n_decode:]`` are
    prefill chunk rows: ``n_reals[r]`` prompt tokens from absolute position
    ``q_starts[r]``, bucket-padded in both axes (``n_real == 0`` marks a
    pad row pointing at scratch).
    pools: {plane: pool} LOCAL pools; block_tables: token planes
    ``(G, n_sub, R, pps_pad)`` int32 physical slots from position 0, state
    planes ``(G, n_sub, R)`` bare slots — one row per packed lane.
    prefix_embeds: (R, P, d) VLM prefix rows (zeros for non-VLM rows) —
    chunk rows covering absolute positions < P take these embeddings.
    -> (logits (R, V) of each row's last real token, updated pools)

    Row r's logits match the per-request entry point that row replaces
    (``decode_step_paged`` / ``prefill_chunk_paged``) to within f32
    rounding — the fused call is an XLA program of another shape: each
    plane dispatches decode and chunk row regions through its per-request
    math, and the fused attention kernel's per-row reduction order is the
    per-request kernels'. What changes is the launch count: one jitted
    dispatch and one attention launch per layer for the WHOLE step, instead
    of one call per admitted request's chunk plus one more for decode. On
    TPU that launch is the COMPILED ``paged_mixed_attention_pool`` pass,
    partitioned across the packed row axis (whole rows, never a row's page
    loop); interpret mode is CPU-only (``ops._on_cpu``).
    """
    assert supports_paged(cfg), f"{cfg.name}: not paged-servable"
    TRACE_COUNTS["serve_step"] += 1
    R, Tc = tokens.shape
    q_starts = jnp.asarray(q_starts, jnp.int32).reshape(-1)
    n_reals = jnp.asarray(n_reals, jnp.int32).reshape(-1)
    x = embed(params["embed"], cfg, tokens)
    if prefix_embeds is not None:
        P = prefix_embeds.shape[1]
        rows = q_starts[:, None] + jnp.arange(Tc, dtype=jnp.int32)[None, :]
        pre = jnp.take_along_axis(prefix_embeds,
                                  jnp.clip(rows, 0, P - 1)[:, :, None],
                                  axis=1)
        x = jnp.where((rows < P)[:, :, None], pre.astype(x.dtype), x)

    def scan_body(carry, xs):
        x, pools = carry
        gp, tg = xs
        x, pools = _group_fwd_mixed(gp, cfg, x, dict(pools), tg,
                                    q_starts=q_starts, n_reals=n_reals,
                                    n_decode=n_decode, read_pps=read_pps,
                                    impl=impl)
        return (x, pools), None

    (x, pools), _ = jax.lax.scan(scan_body, (x, pools),
                                 (params["blocks"], block_tables))
    x = rms_norm(params["final_norm"], x, cfg.rmsnorm_eps)
    last = jnp.take_along_axis(x, jnp.clip(n_reals - 1, 0, Tc - 1)
                               [:, None, None], axis=1)
    logits = unembed(params["embed"], cfg, last)[:, 0]
    return logits, pools


def step_kind(n_decode: int, chunk_tokens: int) -> str:
    """Which fused-step program a packed ``(rows, chunk_tokens)`` batch
    runs: ``chunk`` (no decode lanes), ``decode`` (decode lanes only,
    ``Tc == 1``) or ``mixed`` (decode lanes plus a chunk region)."""
    if n_decode == 0:
        return "chunk"
    return "decode" if chunk_tokens == 1 else "mixed"


@functools.lru_cache(maxsize=None)
def _serve_step_jit(cfg: ModelConfig, impl: str, read_pps: Optional[int],
                    n_decode: int, kind: str):
    """One compiled program per (config, impl, n_decode, shape bucket),
    named ``aqua_step_<kind>`` (its XLA module is ``jit_aqua_step_<kind>``)
    so a profile tells the three step programs apart."""
    name = f"aqua_step_{kind}"

    def program(params, tokens, pools, bt, q_starts, n_reals, pre):
        with jax.named_scope(name):
            return serve_step_paged(params, cfg, tokens, pools, bt, q_starts,
                                    n_reals, n_decode=n_decode,
                                    prefix_embeds=pre, read_pps=read_pps,
                                    impl=impl)

    program.__name__ = program.__qualname__ = name
    return jax.jit(program)


def serve_step_paged_jit(params, cfg: ModelConfig, tokens, pools,
                         block_tables, q_starts, n_reals, *, n_decode: int,
                         prefix_embeds=None, read_pps: Optional[int] = None,
                         impl: str = "pallas"):
    """Jit'd fused step: callers pass bucket-padded row counts and chunk
    lengths, so the trace count is bounded by the (rows x tokens) bucket
    ladder — flat in the number of admitted requests."""
    kind = step_kind(n_decode, tokens.shape[1])
    return _serve_step_jit(cfg, impl, read_pps, n_decode, kind)(
        params, tokens, pools, block_tables, q_starts, n_reals,
        prefix_embeds)


def _group_decode(gp, cfg: ModelConfig, x, cache, pos, shard_axes=None):
    new_cache = {}
    for i in range(group_size(cfg)):
        p = gp[f"sub{i}"]
        kind = mixer_kind(cfg, i)
        c = cache[f"sub{i}"]
        if kind == "rwkv":
            x, nc = rwkv_mod.rwkv_block(p["mix"], cfg, x, rwkv_mod.RWKVState(*c),
                                        {"n1": p["n1"], "n2": p["n2"]})
            new_cache[f"sub{i}"] = nc
            continue
        h = rms_norm(p["n1"], x, cfg.rmsnorm_eps)
        if kind == "mamba":
            h, nc = mamba_mod.mamba_decode(p["mix"], cfg, h, mamba_mod.MambaState(*c))
        elif kind == "mla":
            h, nc = mla_mod.mla_decode(p["mix"], cfg, h, mla_mod.MLACache(*c), pos)
        else:
            h, nc = attn.attention_decode(p["mix"], cfg, h, attn.KVCache(*c), pos,
                                          window=layer_window(cfg, i))
        x = x + h
        fk = ffn_kind(cfg, i)
        if fk:
            h = rms_norm(p["n2"], x, cfg.rmsnorm_eps)
            h = (moe_apply(p["ffn"], cfg, h, dropless=True,
                           shard_axes=shard_axes)[0] if fk == "moe"
                 else mlp(p["ffn"], cfg, h))
            x = x + h
        new_cache[f"sub{i}"] = nc
    return x, new_cache


def decode_step(params, cfg: ModelConfig, cache, tokens, pos, shard_axes=None):
    """One token for every sequence. tokens (B,), pos (B,) -> (logits (B,V), cache)."""
    x = embed(params["embed"], cfg, tokens[:, None])

    def scan_body(x, xs):
        gp, c = xs
        x, nc = _group_decode(gp, cfg, x, c, pos, shard_axes=shard_axes)
        return x, nc

    x, new_cache = jax.lax.scan(scan_body, x, (params["blocks"], cache))
    x = rms_norm(params["final_norm"], x, cfg.rmsnorm_eps)
    logits = unembed(params["embed"], cfg, x)[:, 0]
    return logits, new_cache
