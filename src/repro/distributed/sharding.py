"""Per-tensor sharding rules with divisibility fallbacks.

The rules are name-aware where it matters (attention in/out projections, MoE
expert stacks, embeddings) and fall back to a size-greedy auto-sharder
everywhere else. Every rule checks divisibility against the mesh axis size
and degrades to replication rather than failing — a config change must never
break lowering (large-scale runnability requirement).

Conventions (see DESIGN.md §7):
  * batch-bearing inputs shard over ("pod","data")
  * weight matrices: input-features x output-features -> P(fsdp, "model") for
    in-projections, P("model", fsdp) for out-projections (keeps the TP
    all-reduce at the residual, Megatron-style)
  * MoE expert stacks (E, d, f): expert axis over "model" (EP) when divisible
  * KV caches: batch over dp; kv-head over "model" when divisible, else
    sequence over "model" (flash-decoding style), else replicate
  * scan-stacked params carry a leading group axis that is never sharded
"""
from __future__ import annotations

from typing import Any, Callable, Tuple

import jax
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.configs.base import ModelConfig, ShapeConfig


# parameter-name classes
_IN_PROJ = ("wq", "wk", "wv", "up", "gate", "mix_w1", "decay_w1", "in_proj",
            "x_proj", "wdkv", "wuk", "wuv", "q_a", "v_a")
_OUT_PROJ = ("wo", "down", "out_proj", "mix_w2", "decay_w2", "dt_proj",
             "q_b", "v_b")


def _path_names(path) -> Tuple[str, ...]:
    return tuple(str(getattr(p, "key", getattr(p, "idx", p))) for p in path)


def _div(n: int, k: int) -> bool:
    return k > 0 and n % k == 0


class ShardingRules:
    def __init__(self, mesh, cfg: ModelConfig, *, fsdp: bool = True):
        self.mesh = mesh
        self.cfg = cfg
        self.fsdp = fsdp
        self.model_n = dict(zip(mesh.axis_names, mesh.devices.shape)).get("model", 1)
        self.data_n = dict(zip(mesh.axis_names, mesh.devices.shape)).get("data", 1)
        self.dp = tuple(a for a in ("pod", "data") if a in mesh.axis_names)
        self.dp_n = int(np.prod([dict(zip(mesh.axis_names, mesh.devices.shape))[a]
                                 for a in self.dp])) if self.dp else 1

    # ------------------------------------------------------------------
    def param_spec(self, path, leaf) -> P:
        names = _path_names(path)
        shape = leaf.shape
        nd = len(shape)
        # scan-stacked params: leading group axis — shard the rest
        skip = 1 if (names and names[0] in ("blocks", "enc_blocks", "dec_blocks")
                     and nd >= 2) else 0
        dims = list(range(skip, nd))
        spec: list = [None] * nd
        if not dims:
            return P()
        leafname = names[-1] if names[-1] != "w" and names[-1] != "b" else names[-2]

        # expert stacks (G, E, d, f) / (E, d, f): expert axis -> model (EP)
        if leafname in ("up", "down", "gate") and nd - skip == 3:
            e_dim = dims[0]
            if _div(shape[e_dim], self.model_n):
                spec[e_dim] = "model"
                if self.fsdp and _div(shape[e_dim + 1], self.data_n):
                    spec[e_dim + 1] = "data"
                return P(*spec)
        # embeddings: vocab x d_model
        if leafname in ("tok", "head"):
            big = max(dims, key=lambda i: shape[i])
            if _div(shape[big], self.model_n):
                spec[big] = "model"
            other = [i for i in dims if i != big]
            if self.fsdp and other and _div(shape[other[0]], self.data_n):
                spec[other[0]] = "data"
            return P(*spec)
        if nd - skip == 2:
            i, o = dims[0], dims[1]
            if leafname in _IN_PROJ:
                tp, fs = o, i
            elif leafname in _OUT_PROJ:
                tp, fs = i, o
            else:
                tp, fs = (o, i) if shape[o] >= shape[i] else (i, o)
            if _div(shape[tp], self.model_n):
                spec[tp] = "model"
            if self.fsdp and _div(shape[fs], self.data_n):
                spec[fs] = "data"
            return P(*spec)
        # 1-D (biases, norms) and small leftovers: replicate; fsdp big vectors
        if nd - skip == 1 and self.fsdp and shape[dims[0]] >= 1 << 16 \
                and _div(shape[dims[0]], self.data_n):
            spec[dims[0]] = "data"
        return P(*spec)

    def params(self, param_tree):
        flat, treedef = jax.tree_util.tree_flatten_with_path(param_tree)
        return jax.tree_util.tree_unflatten(
            treedef, [NamedSharding(self.mesh, self.param_spec(p, l))
                      for p, l in flat])

    # ------------------------------------------------------------------
    def cache_spec(self, path, leaf, batch: int) -> P:
        shape = leaf.shape
        nd = len(shape)
        names = _path_names(path)
        leafname = names[-1] if names else ""
        spec: list = [None] * nd
        # caches are stacked (G/L, B, ...): dim1 = batch
        bdim = 1 if nd >= 2 and shape[1] == batch else None
        if bdim is not None and _div(batch, self.dp_n):
            spec[bdim] = self.dp
        # one axis over "model". For k/v caches (G,B,S,K,hd) the order is
        # kv-heads -> sequence -> NEVER head_dim (sharding the attention
        # contraction dim forces layout churn + full-cache copies per step:
        # HC3 in EXPERIMENTS.md §Perf). Latent caches (MLA c_kv, rwkv state)
        # prefer their trailing feature dim (contraction-parallel decode).
        # (NamedTuple fields flatten to index keys, so dispatch on rank:
        # rank-5 leaves are (G,B,S,K,hd) k/v caches or (G,B,H,hd,hd) rwkv
        # states — dim 3 is the kv-head / outer-product-row dim in both.)
        if nd >= 5:
            order = [3, 2]
        else:
            order = [nd - 1, 2] if nd >= 3 else list(range(2, nd))
        for d in order:
            if 2 <= d < nd and spec[d] is None and _div(shape[d], self.model_n):
                spec[d] = "model"
                break
        return P(*spec)

    def cache(self, cache_tree, batch: int):
        flat, treedef = jax.tree_util.tree_flatten_with_path(cache_tree)
        return jax.tree_util.tree_unflatten(
            treedef, [NamedSharding(self.mesh, self.cache_spec(p, l, batch))
                      for p, l in flat])

    # ------------------------------------------------------------------
    def batch_spec(self, leaf, batch: int) -> P:
        nd = len(leaf.shape)
        if nd >= 1 and leaf.shape[0] == batch and _div(batch, self.dp_n):
            return P(self.dp, *([None] * (nd - 1)))
        return P(*([None] * nd))

    def batch(self, tree, batch: int):
        return jax.tree.map(
            lambda l: NamedSharding(self.mesh, self.batch_spec(l, batch)), tree)

    # ------------------------------------------------------------------
    def opt_state(self, opt_template, param_tree):
        """Optimizer moments/master mirror the param specs; step is replicated."""
        pspecs = self.params(param_tree)

        def build(field):
            if field is None:
                return None
            return jax.tree.map(lambda l, s: s, field, pspecs)

        from repro.training.optimizer import AdamWState
        return AdamWState(
            NamedSharding(self.mesh, P()),
            build(opt_template.mu), build(opt_template.nu),
            build(opt_template.master))
