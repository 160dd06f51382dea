"""Unified paged state runtime: EVERY family's dynamic context on AquaTensor
pages, behind per-request block tables.

``PagedStateRuntime`` is the serving engine's state manager (paper §3 + §5
made structural, for the paper's whole model zoo): each family's per-request
dynamic context is decomposed by ``models/lm.py:paged_layout`` into page
PLANES — one tiered AquaTensor pool per plane, native-dtype payloads:

    kv     (2, n_kv, page, hd)   attention K/V, ceil(ctx/page) pages/layer
    mla    (page, kv_lora+rope)  fused MLA latent + roped key, token-paged
    ssm    (d_inner, d_state)    Mamba SSM state (f32), one page/layer
    conv   (d_conv-1, d_inner)   Mamba conv tail, one page/layer
    wkv    (H, hd, hd)           RWKV6 wkv state (f32), one page/layer
    shift  (2, d_model)          RWKV6 time/channel-mix shifts, one page/layer

A hybrid (Jamba) request owns kv pages for its attention sub-layers and
ssm/conv pages for the Mamba ones; an RWKV6 request owns only fixed-size
state pages (O(1) context). Decode/prefill read and write the LOCAL pools
directly inside the jit'd whole-step programs (attention through the
``kernels/paged_attention`` block-table kernels, MLA/recurrent planes via
shape-stable jnp gathers/scatters), so preemption is a *page-table tier
flip* for every family:

    park    = offload(pages)      one coalesced message per (tier, donor)
    restore = ensure_local(pages) group across ALL planes of the request

— no gather of cache leaves, no float32 blob, no repacking, for ANY family.
Partial token-plane tails are metered at their valid fraction, so a parked
request moves exactly its native-dtype context footprint. The seed-era dense
blob-store shim this replaces is deleted; there is exactly one way a
request's state moves between tiers.

PREFIX SHARING (copy-on-write): the same by-reference insight applies
*within* the resident tier. A RADIX TREE over page-aligned prompt token
blocks lets ``adopt_prefix`` map a new request's block tables onto the
physical pages another request already wrote for the longest common prefix
of its prompt — mid-prompt divergence splits a tree edge at the block
boundary, so two prompts sharing 40 of 60 blocks share 40 physical pages.
Children are keyed by their first token block verbatim (a dict lookup is a
hash PLUS an exact tuple compare), so a hash collision is a miss, never
foreign pages. Shared pages are refcounted in the AquaTensor
(``page_refs``), pinned LOCAL while any referencer is active, moved between
tiers ONCE however many block tables point at them, and copied on write
(``make_writable``) the moment a sharer must write into one. Sharing is
enabled only when every plane is ``shareable`` (token planes:
position-addressed, immutable once written); families with recurrent state
planes opt out — a state page summarizes the whole prefix and is rewritten
every step.

GLOBAL PREFIX CACHE (retain past refcount 0): with ``prefix_cache`` on,
tree-indexed pages OUTLIVE their last referencer in a CACHED state
(refcount 0, physical slot kept, payload intact, any tier) so the next
request with the same prompt prefix revives them instead of recomputing
prefill. Cached pages count against the same pools as live pages but YIELD
on demand: every plane's AquaTensor carries a ``reclaim`` hook that evicts
the coldest cached leaf blocks (LRU) with cold-first demotion
LOCAL -> REMOTE -> HOST -> free before any tier-exhausted MemoryError can
fire — a cache-on run never fails an allocation a cache-off run would have
served. Donor death drops (never leaks) cached pages on the dead slab and
prunes their radix coverage.
"""
from __future__ import annotations

import math
from contextlib import nullcontext
from dataclasses import dataclass, field
from typing import Dict, List, Optional, Sequence, Tuple

import jax.numpy as jnp
import numpy as np
from jax.profiler import TraceAnnotation

from repro.configs.base import ModelConfig
from repro.core.aqua_tensor import (AquaTensor, HOST, LOCAL, REMOTE,
                                    TIER_NAMES, TransferMeter)
from repro.core.errors import LeaseRevokedError


@dataclass
class _Plane:
    """One page plane: an AquaTensor pool + the per-request page bookkeeping."""
    name: str
    kind: str                        # "tokens" | "state"
    aqua: AquaTensor
    n_layers: int                    # plane layers across the whole stack
    n_sub: int                       # plane sub-layers per group
    token_bytes: int = 0             # per-layer bytes/token (token planes)
    scratch_lp: int = 0
    pages: Dict[int, List[List[int]]] = field(default_factory=dict)
    # LOCAL pin count per logical page: how many ACTIVE (unparked)
    # requests reference it. park() may only offload pages whose pin
    # reaches zero — a shared prefix page stays LOCAL while any sharer
    # still runs, and moves tiers exactly once when the last sharer parks.
    pin: Dict[int, int] = field(default_factory=dict)

    @property
    def scratch_slot(self) -> int:
        return int(self.aqua.page_table[self.scratch_lp, 1])

    def flat(self, rid: int) -> np.ndarray:
        return np.asarray([lp for row in self.pages.get(rid, [])
                           for lp in row], np.int64)


def _token_blocks(tokens: Sequence[int], page_tokens: int
                  ) -> List[Tuple[int, ...]]:
    """A prompt's FULL page-aligned token blocks (the partial tail block is
    never indexed — only completely written pages are shareable)."""
    return [tuple(int(t) for t in tokens[i * page_tokens:(i + 1) * page_tokens])
            for i in range(len(tokens) // page_tokens)]


class _RadixNode:
    """One edge of the prefix radix tree: a run of page-aligned token blocks
    plus the physical pages backing each block.

    ``blocks[i]`` is the i-th token block of the edge verbatim and
    ``pages[i]`` maps plane name -> (n_layers,) logical page ids holding its
    context. Children are keyed by their OWN first block, so descending is a
    dict lookup whose tuple-equality compare IS the exact-token
    verification: a hash collision falls through ``==`` and reads as a miss,
    never as foreign pages. ``last_use`` is the runtime's LRU clock tick of
    the newest adoption/registration through this node — eviction takes the
    coldest cached leaf block first. One root per index seed (lora_id):
    adapters never alias even for identical token streams."""
    __slots__ = ("blocks", "pages", "children", "parent", "last_use")

    def __init__(self, blocks: Optional[List[Tuple[int, ...]]] = None,
                 pages: Optional[List[Dict[str, np.ndarray]]] = None,
                 parent: Optional["_RadixNode"] = None):
        self.blocks: List[Tuple[int, ...]] = blocks if blocks is not None else []
        self.pages: List[Dict[str, np.ndarray]] = pages if pages is not None else []
        self.children: Dict[Tuple[int, ...], "_RadixNode"] = {}
        self.parent: Optional["_RadixNode"] = parent
        self.last_use: int = 0


class PagedStateRuntime:
    """Family-agnostic block-table state manager on tiered AquaTensor pools."""

    def __init__(self, cfg: ModelConfig, *, max_seq: int,
                 page_tokens: int = 8, local_pages: Optional[int] = None,
                 host_pages: int = 8192, max_running: int = 4,
                 meter: Optional[TransferMeter] = None,
                 prefix_sharing: bool = True, prefix_cache: bool = True,
                 mesh=None):
        """Build one AquaTensor pool per page plane of ``cfg``'s family.

        Args:
            cfg: model config; must be paged-servable (``lm.supports_paged``).
            max_seq: maximum context length a request may reach; sizes the
                per-request block tables (``pps`` pages per layer).
            page_tokens: tokens per token-plane page.
            local_pages: LOCAL slots of each token plane (the admission
                budget the schedulers plan against); default sizes for
                ``max_running`` full-length requests.
            host_pages: host-tier slots per plane (the PCIe fallback).
            max_running: used only to size default pools.
            meter: shared ``TransferMeter``; a fresh one by default.
            prefix_sharing: enable the copy-on-write prefix index. Forced
                off when any plane is not ``shareable`` (recurrent state).
            prefix_cache: retain tree-indexed pages past refcount 0 in the
                CACHED state (global prefix cache) instead of freeing them
                with their last referencer. Effective only with sharing on.
            mesh: optional ``MeshTierDomain`` — every plane's REMOTE pools
                become real peer-device slabs and remote transfer legs
                become collectives; None keeps the single-device backend.

        Raises:
            ValueError: the family has a sub-layer with no page plane
                (windowed ring buffers, logit softcap, encoder-decoder).
        """
        from repro.models import lm
        if not lm.supports_paged(cfg):
            raise ValueError(f"{cfg.name}: not paged-servable (windowed "
                             "ring-buffer / softcap / encdec layers have no "
                             "page plane yet)")
        self.cfg = cfg
        self.G = lm.n_groups(cfg)
        self.gs = lm.group_size(cfg)
        self.page_tokens = page_tokens
        self.max_seq = max_seq
        self.pps = math.ceil(max_seq / page_tokens)
        self.meter = meter or TransferMeter()
        self.mesh = mesh
        self.faults = None
        self.planes: Dict[str, _Plane] = {}
        layout = lm.paged_layout(cfg)
        # prefix sharing requires every plane to be position-addressed and
        # immutable once written (token planes); one recurrent state plane
        # disables it for the whole family — skipping a shared chunk would
        # skip its state recurrence
        self.sharing = bool(prefix_sharing) and all(
            spec.get("shareable", False) for spec in layout.values())
        # the prefix cache retains tree-indexed pages past refcount 0; it
        # only makes sense on top of the sharing index
        self.caching = self.sharing and bool(prefix_cache)
        # prefix RADIX TREE: one root per index seed (lora_id partitions the
        # key space — identical tokens under different adapters never
        # alias). Each node edge is a run of page-aligned token blocks with
        # the physical pages backing them; ``_lp_node`` is the reverse map
        # (plane, logical page) -> (node, block index) so release/eviction/
        # donor loss find a page's coverage in O(1). With caching ON the
        # tree OWNS refcount-0 pages (CACHED state); with caching OFF nodes
        # are backed purely by live requests' refcounts and pruned the
        # moment a backing page is freed.
        self._roots: Dict[object, _RadixNode] = {}
        self._lp_node: Dict[Tuple[str, int], Tuple[_RadixNode, int]] = {}
        self._req_blocks: Dict[int, List[Tuple[int, ...]]] = {}
        self._req_tokens: Dict[int, Tuple[int, ...]] = {}
        self._req_seed: Dict[int, object] = {}
        self._req_registered: Dict[int, int] = {}
        self._active: set = set()
        self.prefix_hits = 0
        self.adopted_tokens = 0
        self.cow_copies = 0
        # cache counters: a HIT is an adoption that revived at least one
        # refcount-0 block (pure sharing with a live sharer is not a cache
        # hit); evictions/demotions count whole blocks
        self.cache_hits = 0
        self.cache_hit_tokens = 0
        self.cache_evictions = 0
        self.cache_demotions = 0
        self._clock = 0
        self._evicting = False
        for name, spec in layout.items():
            n_sub = len(spec["positions"])
            n_layers = self.G * n_sub
            if spec["kind"] == "tokens":
                if name == "kv":
                    K, hd = spec["dims"]
                    page_shape: Tuple[int, ...] = (2, K, page_tokens, hd)
                else:                                   # mla latent plane
                    (C,) = spec["dims"]
                    page_shape = (page_tokens, C)
                per_req = n_layers * self.pps
                # token-plane LOCAL budget is caller-tunable (the admission
                # gate the schedulers plan against); +1 is the scratch page
                slots = (local_pages if local_pages is not None
                         else max_running * per_req + 1)
            else:
                page_shape = spec["shape"]
                per_req = n_layers
                slots = max_running * per_req + 1
            # logical ids: one per LOCAL and host slot, plus one per slot of
            # every remote lease added later — the whole parked population
            aqua = AquaTensor(page_shape=page_shape,
                              local_slots=slots, host_slots=host_pages,
                              dtype=spec["dtype"], meter=self.meter,
                              name=f"{cfg.name}/{name}", mesh=mesh)
            plane = _Plane(name, spec["kind"], aqua, n_layers, n_sub,
                           token_bytes=spec.get("token_bytes", 0))
            # pinned LOCAL dummy page: idle batch lanes and block-table
            # padding point here so masked DMAs / idle-lane state reads and
            # writes stay in-bounds
            plane.scratch_lp = int(aqua.allocate(1, prefer=LOCAL)[0])
            self.planes[name] = plane
            if self.caching:
                # cached pages yield before any allocation in this plane can
                # fail: the tensor consults this hook when a tier runs dry
                aqua.reclaim = (lambda tier, need, _n=name:
                                self._cache_reclaim(_n, tier, need))

    # -- geometry ---------------------------------------------------------
    def pages_for(self, n_tokens: int) -> int:
        """Token-plane pages per layer covering n_tokens."""
        return max(1, math.ceil(n_tokens / self.page_tokens))

    def _plane_pages(self, plane: _Plane, n_tokens: int) -> int:
        if plane.kind == "tokens":
            return plane.n_layers * self.pages_for(n_tokens)
        return plane.n_layers

    def pages_per_request(self, n_tokens: int) -> np.ndarray:
        """Per-plane page cost of a request at n_tokens of context — the
        vector the schedulers budget against (one entry per plane)."""
        return np.asarray([self._plane_pages(p, n_tokens)
                           for p in self.planes.values()], np.int64)

    def footprint_bytes(self, n_tokens: int) -> float:
        """Native-dtype whole-context bytes of a request (no page slack):
        token planes at n_tokens, recurrent state planes at their fixed
        size. This is exactly what one park/restore moves."""
        total = 0.0
        for p in self.planes.values():
            if p.kind == "tokens":
                total += p.n_layers * n_tokens * p.token_bytes
            else:
                total += p.n_layers * p.aqua.page_bytes
        return float(total)

    def footprint_elems(self, n_tokens: int) -> int:
        """Element count of the same footprint (the seed blob path moved
        4 bytes per element, whatever the native dtype)."""
        total = 0
        for p in self.planes.values():
            per_page = int(np.prod(p.aqua.page_shape))
            if p.kind == "tokens":
                total += p.n_layers * n_tokens * (p.token_bytes
                                                  // p.aqua.dtype.itemsize)
            else:
                total += p.n_layers * per_page
        return total

    @property
    def page_budget(self) -> np.ndarray:
        """Per-plane LOCAL pages available to requests (scratch excluded)."""
        return np.asarray([p.aqua.local_pool.shape[0] - 1
                           for p in self.planes.values()], np.int64)

    @property
    def aqua(self) -> AquaTensor:
        """The sole plane's tensor — attention-only (or ssm-state-only)
        convenience for tests/benchmarks; multi-plane runtimes must address
        ``planes[name].aqua`` explicitly."""
        if len(self.planes) != 1:
            raise AttributeError("runtime has multiple planes; use "
                                 f".planes[name].aqua ({list(self.planes)})")
        return next(iter(self.planes.values())).aqua

    # -- pool plumbing (the jit operands) ---------------------------------
    @property
    def pools(self) -> Dict[str, jnp.ndarray]:
        return {n: p.aqua.local_pool for n, p in self.planes.items()}

    @pools.setter
    def pools(self, value: Dict[str, jnp.ndarray]):
        for n, pool in value.items():
            self.planes[n].aqua.local_pool = pool

    # -- activation bookkeeping (LOCAL pins) -------------------------------
    def _unpin(self, plane: _Plane, lp: int):
        c = plane.pin.get(lp, 0) - 1
        if c <= 0:
            plane.pin.pop(lp, None)
        else:
            plane.pin[lp] = c

    def _activate(self, rid: int, cause: str):
        """Mark the request active: pull every page it references LOCAL
        (adopted prefix pages may sit on another tier) and pin them there —
        a pinned page is never offloaded by another sharer's park. All
        planes' page-ins ride ONE coalesced message per (tier, donor).
        A page-in that moves pages is an ``aqua.kv.restore`` span, labelled
        with the tiers it reads and the caller's ``cause``."""
        if rid in self._active:
            return
        self._active.add(rid)
        away = np.concatenate([plane.aqua.page_table[plane.flat(rid), 0]
                               for plane in self.planes.values()])
        away = away[away != LOCAL]
        span = nullcontext()
        if len(away):
            span = TraceAnnotation(
                "aqua.kv.restore", rid=rid, pages=len(away), cause=cause,
                tier="+".join(TIER_NAMES[int(t)] for t in np.unique(away)))
        with span, self.meter.coalesce():
            for plane in self.planes.values():
                lps = plane.flat(rid)
                if len(lps):
                    plane.aqua.ensure_local(lps)
                    plane.aqua.set_page_fill(lps, 1.0)
                    for lp in lps:
                        lp = int(lp)
                        plane.pin[lp] = plane.pin.get(lp, 0) + 1

    # -- allocation -------------------------------------------------------
    def ensure_capacity(self, rid: int, n_tokens: int, *,
                        cause: str = "admit"):
        """Grow the request's block tables to cover ``n_tokens`` of context.

        Token planes add pages as the context crosses page boundaries
        (adopted shared-prefix pages already in the tables count toward the
        need); state planes allocate their fixed page set on first touch
        (zeroed — a freed slot may hold a previous occupant's state, and the
        zero page IS the initial recurrent state). Implicitly activates the
        request: its existing pages are pulled LOCAL and pinned (a restore
        labelled ``cause``).

        New pages must be LOCAL (the step programs read the LOCAL pools): if
        the allocator had to spill a fresh page to another tier the LOCAL
        pool is full and no later step could pull it back either, so fail
        loudly here with the tensor/tier MemoryError. The page-budget-aware
        schedulers are designed to keep planned run sets below this point.

        The grow is ALL-OR-NOTHING across planes: if any plane's pool runs
        dry mid-way, every page this call already took — in this plane and
        the planes before it — is unpinned and released before the
        MemoryError propagates, so a failed hybrid (multi-plane) grow never
        leaks pages or refcounts.

        Raises:
            MemoryError: a fresh page cannot be placed (or kept) LOCAL.
        """
        self._activate(rid, cause)
        added: List[Tuple[_Plane, List[int], int]] = []
        fresh_rids: List[_Plane] = []     # planes whose rows this call made
        try:
            for plane in self.planes.values():
                if rid not in plane.pages:
                    fresh_rids.append(plane)
                rows = plane.pages.setdefault(
                    rid, [[] for _ in range(plane.n_layers)])
                need = (self.pages_for(n_tokens) if plane.kind == "tokens"
                        else 1)
                fresh: List[int] = []
                for row in rows:
                    while len(row) < need:
                        lp = int(plane.aqua.allocate(1, prefer=LOCAL)[0])
                        try:
                            if plane.aqua.page_table[lp, 0] != LOCAL:
                                plane.aqua.ensure_local([lp])  # LOCAL full
                        except MemoryError:
                            plane.aqua.free([lp])   # spilled page: unwind it
                            raise
                        row.append(lp)
                        added.append((plane, row, lp))
                        plane.pin[lp] = plane.pin.get(lp, 0) + 1
                        if plane.kind == "state":
                            fresh.append(lp)
                if fresh:
                    plane.aqua.write_local(
                        fresh,
                        jnp.zeros((len(fresh),) + plane.aqua.page_shape,
                                  plane.aqua.dtype))
        except MemoryError:
            for plane, row, lp in reversed(added):
                self._unpin(plane, lp)
                plane.aqua.free([lp])
                row.remove(lp)
            for plane in fresh_rids:
                if not any(plane.pages.get(rid, [])):
                    plane.pages.pop(rid, None)
            raise

    def release(self, rid: int):
        """Drop the request's references. Pages shared with a live request
        survive (the sharer keeps reading them). Tree-indexed pages whose
        LAST reference this drops enter the CACHED state when caching is on
        (refcount 0, slot kept, payload intact — the global prefix cache
        retains them for future adoption) and are freed-with-pruning when it
        is off, so a recycled logical id can never serve a stale prefix
        match. Unindexed pages (decode tails, diverged suffixes) free as
        always."""
        for plane in self.planes.values():
            if rid not in plane.pages:
                continue
            lps = plane.flat(rid)
            if rid in self._active:
                for lp in lps:
                    self._unpin(plane, int(lp))
            indexed = [int(lp) for lp in lps
                       if (plane.name, int(lp)) in self._lp_node]
            plain = [int(lp) for lp in lps
                     if (plane.name, int(lp)) not in self._lp_node]
            plane.aqua.free(plain)
            if self.caching:
                plane.aqua.free_to_cache(indexed)
                # a LOST page cannot be cached — free_to_cache freed it;
                # prune the dead coverage so no arrival adopts it
                for lp in indexed:
                    if plane.aqua.page_table[lp, 0] == -1:
                        self._drop_tree_page(plane.name, lp)
            else:
                for lp in plane.aqua.free(indexed):
                    self._drop_tree_page(plane.name, lp)
            # defensive: no pin may survive the pages it pinned. A release
            # racing a same-step prefetch restore (the engine restored and
            # pinned this rid's pages for the NEXT plan in the step it
            # finished) already unpinned through the active set above, but
            # a pin entry left on a now-freed page would corrupt every
            # later occupant of the recycled id.
            for lp in lps:
                if plane.aqua.page_table[int(lp), 0] == -1:
                    plane.pin.pop(int(lp), None)
            del plane.pages[rid]
        self._active.discard(rid)
        self._req_blocks.pop(rid, None)
        self._req_tokens.pop(rid, None)
        self._req_seed.pop(rid, None)
        self._req_registered.pop(rid, None)

    # -- radix-tree plumbing ----------------------------------------------
    def _radix_walk(self, seed: object, blocks: List[Tuple[int, ...]]
                    ) -> List[Tuple[_RadixNode, int]]:
        """Longest-common-prefix match: descend the seed's tree comparing
        token blocks verbatim; returns one (node, block index) per matched
        block. Divergence mid-edge stops at the last matched block boundary
        — the caller reuses exactly the common prefix."""
        out: List[Tuple[_RadixNode, int]] = []
        node = self._roots.get(seed)
        if node is None:
            return out
        i = 0
        while i < len(blocks):
            child = node.children.get(blocks[i])
            if child is None:
                break
            j = 0
            while (j < len(child.blocks) and i < len(blocks)
                   and child.blocks[j] == blocks[i]):
                out.append((child, j))
                i += 1
                j += 1
            if j < len(child.blocks):
                break                      # diverged mid-edge
            node = child
        return out

    def _split_node(self, node: _RadixNode, at: int):
        """Split an edge at block boundary ``at``: the node keeps blocks
        [:at], a new child carries blocks [at:] with the pages, children and
        LRU stamp of the tail — the structural move behind mid-prompt
        divergence reuse."""
        tail = _RadixNode(blocks=node.blocks[at:], pages=node.pages[at:],
                          parent=node)
        tail.children = node.children
        tail.last_use = node.last_use
        for c in tail.children.values():
            c.parent = tail
        for bi, pagedict in enumerate(tail.pages):
            for name, lps in pagedict.items():
                for lp in lps:
                    self._lp_node[(name, int(lp))] = (tail, bi)
        node.blocks = node.blocks[:at]
        node.pages = node.pages[:at]
        node.children = {tail.blocks[0]: tail}

    def _radix_insert(self, seed: object, blocks: List[Tuple[int, ...]],
                      page_dicts: List[Dict[str, np.ndarray]]):
        """Publish ``blocks`` (with their backing pages) into the seed's
        tree. Blocks already present are skipped (a concurrent twin won the
        publication race — its pages stay canonical); a mid-edge divergence
        splits the edge; the unmatched suffix lands as one new node."""
        root = self._roots.setdefault(seed, _RadixNode())
        node, i = root, 0
        while i < len(blocks):
            child = node.children.get(blocks[i])
            if child is None:
                new = _RadixNode(blocks=list(blocks[i:]),
                                 pages=list(page_dicts[i:]), parent=node)
                new.last_use = self._clock
                node.children[new.blocks[0]] = new
                for bi, pagedict in enumerate(new.pages):
                    for name, lps in pagedict.items():
                        for lp in lps:
                            self._lp_node[(name, int(lp))] = (new, bi)
                return
            j = 0
            while (j < len(child.blocks) and i < len(blocks)
                   and child.blocks[j] == blocks[i]):
                i += 1
                j += 1
            child.last_use = max(child.last_use, self._clock)
            if j == len(child.blocks):
                node = child               # whole edge matched: descend
                continue
            if i == len(blocks):
                return                     # prompt is a prefix of the edge
            self._split_node(child, j)     # diverged mid-edge
            node = child

    def _prune_from(self, node: _RadixNode, bi: int):
        """Remove blocks [bi:] of ``node`` and its ENTIRE subtree from the
        index (every deeper prefix contains the removed block). CACHED
        pages under the cut are dropped back to their free lists — never
        leaked; still-referenced pages are merely un-indexed (their owners
        free them at release). An emptied node unlinks from its parent."""
        key = node.blocks[0] if node.blocks else None
        for child in list(node.children.values()):
            self._prune_from(child, 0)
        node.children.clear()
        for idx in range(bi, len(node.pages)):
            for name, lps in node.pages[idx].items():
                plane = self.planes[name]
                drop = []
                for lp in lps:
                    lp = int(lp)
                    self._lp_node.pop((name, lp), None)
                    if (plane.aqua.page_refs[lp] == 0
                            and plane.aqua.page_table[lp, 0] != -1):
                        drop.append(lp)
                if drop:
                    plane.aqua.drop_cached(drop)
        del node.pages[bi:]
        del node.blocks[bi:]
        if not node.pages and node.parent is not None and key is not None:
            if node.parent.children.get(key) is node:
                node.parent.children.pop(key)
            node.parent = None

    def _drop_tree_page(self, plane_name: str, lp: int):
        """A tree-indexed page went away (freed, or lost with its donor):
        prune its block and everything below it from the index."""
        hit = self._lp_node.get((plane_name, int(lp)))
        if hit is not None:
            self._prune_from(hit[0], hit[1])

    def _iter_nodes(self):
        for root in self._roots.values():
            stack = list(root.children.values())
            while stack:
                n = stack.pop()
                stack.extend(n.children.values())
                yield n

    def _block_cached(self, node: _RadixNode, bi: int) -> bool:
        """True when every page of the block holds zero references — the
        block is retained purely by the cache and may be evicted."""
        for name, lps in node.pages[bi].items():
            if (self.planes[name].aqua.page_refs[np.asarray(lps, np.int64)]
                    != 0).any():
                return False
        return True

    def _cache_reclaim(self, plane_name: str, tier: int, need: int) -> int:
        """The AquaTensor reclaim hook: free ``need`` slots of ``tier`` in
        ``plane_name`` by evicting the coldest cached LEAF blocks (LRU).
        Cold-first demotion: a LOCAL victim demotes to REMOTE-else-HOST and
        a REMOTE victim to HOST when the lower tier has room (the block
        stays adoptable — only its residence degrades, priced as a normal
        coalesced migration); otherwise the block frees outright. tier -1
        requests outright frees (logical-id pressure). Reentrancy-guarded:
        a demotion's own ``_move`` never recurses into eviction."""
        if not self.caching or self._evicting:
            return 0
        self._evicting = True
        try:
            freed = 0
            while freed < need:
                victim = None              # (node, block index)
                for node in self._iter_nodes():
                    if not node.pages:
                        continue
                    # deepest cached block of this node holding pages of
                    # the pressured plane in the pressured tier. The prefix
                    # invariant (a referenced block keeps every ancestor
                    # referenced) means everything at or below a cached
                    # block is itself cached, so an interior block whose
                    # descendants were already demoted to a lower tier is
                    # a legal victim — requiring a childless node here
                    # would strand such blocks forever.
                    for bi in range(len(node.pages) - 1, -1, -1):
                        if not self._block_cached(node, bi):
                            break          # earlier blocks are referenced
                        lps = node.pages[bi].get(plane_name)
                        if lps is None:
                            continue
                        tiers = self.planes[plane_name].aqua.page_table[
                            np.asarray(lps, np.int64), 0]
                        if tier != -1 and not (tiers == tier).any():
                            continue
                        if victim is None or node.last_use < victim[0].last_use:
                            victim = (node, bi)
                        break
                if victim is None:
                    break
                freed += self._evict_block(victim[0], plane_name, tier,
                                           victim[1])
            return freed
        finally:
            self._evicting = False

    def _evict_block(self, node: _RadixNode, plane_name: str,
                     tier: int, bi: Optional[int] = None) -> int:
        """Evict cached block ``bi`` (tail by default) of ``node`` under
        ``tier`` pressure in ``plane_name``. Demotes when the next tier
        down has room (the subtree below stays intact and adoptable),
        frees the block AND its subtree otherwise — everything below a
        cached block is cached too, so nothing referenced is cut. Returns
        slots freed in the pressured tier."""
        if bi is None:
            bi = len(node.pages) - 1
        aqua = self.planes[plane_name].aqua
        lps = np.asarray(node.pages[bi][plane_name], np.int64)
        in_tier = lps[aqua.page_table[lps, 0] == tier] if tier != -1 else lps
        room = 0
        if tier == LOCAL:
            room = aqua.remote_free + len(aqua._free_host)
        elif tier == REMOTE:
            room = len(aqua._free_host)
        if 0 < len(in_tier) <= room:
            aqua._move(in_tier, REMOTE if tier == LOCAL else HOST)
            self.cache_demotions += 1
            return len(in_tier)
        freed = len(in_tier)
        self._prune_from(node, bi)         # drops the cached pages
        self.cache_evictions += 1
        return max(freed, 1)

    def cached_pages(self) -> Dict[str, int]:
        """Refcount-0-but-resident pages per plane (the CACHED state)."""
        return {n: int(((p.aqua.page_refs == 0)
                        & (p.aqua.page_table[:, 0] != -1)).sum())
                for n, p in self.planes.items()}

    # -- prefix sharing (refcounted copy-on-write pages) -------------------
    def adopt_prefix(self, rid: int, tokens: Sequence[int],
                     seed: object = None) -> int:
        """Map a new request's block tables onto already-resident pages for
        the LONGEST COMMON page-aligned prefix of ``tokens`` in the radix
        tree — mid-prompt divergence still reuses every block up to the
        divergence boundary.

        For every matched block the physical pages are taken by reference —
        RETAINED (refcount + 1) when live, REVIVED (a cache hit: refcount
        0 -> 1, the pages were retained past their last referencer) when
        cached — and appended to this request's block-table rows in every
        plane; the chunked-prefill pipeline then starts past the shared
        prefix (the engine sets ``prefill_pos`` accordingly; revived pages
        may sit on a lower tier, so the restore pays only their page-in
        bytes, never prefill FLOPs). Must be called before the request's
        first ``ensure_capacity``.

        Args:
            rid: the request id (no pages allocated yet).
            tokens: the full prompt token ids.
            seed: index partition key (e.g. lora_id) — one tree root per
                seed, so adapters never alias.

        Returns:
            Matched prefix length in TOKENS (a multiple of ``page_tokens``;
            0 when sharing is disabled or nothing matches). The caller must
            still compute at least the final prompt position for logits —
            on a full match that recompute write triggers copy-on-write of
            the tail page (``make_writable``).
        """
        if not self.sharing:
            return 0
        blocks = _token_blocks(tokens, self.page_tokens)
        self._req_blocks[rid] = blocks
        self._req_tokens[rid] = tuple(map(int, tokens))
        self._req_seed[rid] = seed
        matched = self._radix_walk(seed, blocks)
        self._req_registered[rid] = len(matched)
        if not matched:
            return 0
        if any(rid in p.pages for p in self.planes.values()):
            raise ValueError(f"adopt_prefix({rid}) after pages were "
                             "allocated — adoption must precede the first "
                             "ensure_capacity")
        self._clock += 1
        revived_blocks = 0
        for node, bi in matched:
            node.last_use = self._clock
            hit = self._block_cached(node, bi)
            for name, plane in self.planes.items():
                lps = np.asarray(node.pages[bi][name], np.int64)
                if hit:
                    plane.aqua.revive(lps)
                else:
                    refs = plane.aqua.page_refs[lps]
                    cold = lps[refs == 0]
                    if len(cold):          # mixed: revive the cold layers
                        plane.aqua.revive(cold)
                    warm = lps[refs > 0]
                    if len(warm):
                        plane.aqua.retain(warm)
                rows = plane.pages.setdefault(
                    rid, [[] for _ in range(plane.n_layers)])
                for l in range(plane.n_layers):
                    rows[l].append(int(lps[l]))
            if hit:
                revived_blocks += 1
        self.prefix_hits += 1
        self.adopted_tokens += len(matched) * self.page_tokens
        if revived_blocks:
            self.cache_hits += 1
            self.cache_hit_tokens += revived_blocks * self.page_tokens
        return len(matched) * self.page_tokens

    def register_prefix(self, rid: int, n_tokens: int):
        """Publish the request's completed full prompt pages into the radix
        tree (up to ``n_tokens`` positions written so far). Blocks already
        in the tree are skipped (adopted blocks, or a concurrent twin won
        the publication race — its pages stay canonical); a divergence
        mid-edge SPLITS the edge at the block boundary so both branches
        share the common-prefix node. Decode-written pages are never
        registered (the tree covers prompt blocks only). No-op unless
        ``adopt_prefix`` recorded the request's prompt blocks."""
        blocks = self._req_blocks.get(rid)
        if not self.sharing or blocks is None:
            return
        n_full = min(n_tokens // self.page_tokens, len(blocks))
        start = self._req_registered.get(rid, 0)
        if n_full <= start:
            return
        page_dicts: List[Dict[str, np.ndarray]] = []
        for p in range(n_full):
            entry: Dict[str, np.ndarray] = {}
            for name, plane in self.planes.items():
                rows = plane.pages.get(rid)
                if rows is None or len(rows[0]) <= p:
                    return
                entry[name] = np.asarray(
                    [rows[l][p] for l in range(plane.n_layers)], np.int64)
            page_dicts.append(entry)
        self._clock += 1
        self._radix_insert(self._req_seed.get(rid), blocks[:n_full],
                           page_dicts)
        self._req_registered[rid] = max(start, n_full)

    def make_writable(self, rid: int, start: int, end: int):
        """Copy-on-write: before the request writes token positions
        ``[start, end)``, clone any covered page it SHARES (refcount > 1,
        or refcount 1 but radix-indexed — a cache-revived sole referencer
        must not mutate the canonical cached copy) into a fresh exclusive
        LOCAL page and repoint only this request's block-table row at the
        clone. The other referencers (and the radix tree) keep the original
        — a sharer's write can never corrupt the prefix another request is
        still reading or a future arrival will adopt.

        Raises:
            MemoryError: no LOCAL slot is free for a clone.
        """
        if not self.sharing or end <= start:
            return
        p0, p1 = start // self.page_tokens, (end - 1) // self.page_tokens
        for plane in self.planes.values():
            if plane.kind != "tokens":
                continue
            rows = plane.pages.get(rid)
            if not rows:
                continue
            for row in rows:
                for p in range(p0, min(p1 + 1, len(row))):
                    lp = int(row[p])
                    if (int(plane.aqua.refcounts([lp])[0]) <= 1
                            and (plane.name, lp) not in self._lp_node):
                        continue
                    new = int(plane.aqua.allocate(1, prefer=LOCAL)[0])
                    try:
                        if plane.aqua.page_table[new, 0] != LOCAL:
                            plane.aqua.ensure_local([new])
                    except MemoryError:
                        # the clone spilled and cannot be pulled back: hand
                        # it straight back instead of leaking it (the block
                        # table still points at the shared original)
                        plane.aqua.free([new])
                        raise
                    plane.aqua.write_local([new], plane.aqua.read([lp]))
                    if rid in self._active:
                        self._unpin(plane, lp)
                        plane.pin[new] = plane.pin.get(new, 0) + 1
                    # deref the original; sharers keep it, and if this was
                    # its last reference an indexed page stays CACHED (or
                    # prunes its coverage when caching is off)
                    if (self.caching
                            and (plane.name, lp) in self._lp_node):
                        plane.aqua.free_to_cache([lp])
                    else:
                        for f in plane.aqua.free([lp]):
                            self._drop_tree_page(plane.name, f)
                    row[p] = new
                    self.cow_copies += 1

    def shared_pages_with(self, rid: int, other_rids: Sequence[int]
                          ) -> np.ndarray:
        """Per-plane count of this request's pages also referenced by any of
        ``other_rids`` — the physical-page discount the schedulers apply
        when budgeting a run set that contains both sharers."""
        out = []
        for plane in self.planes.values():
            mine = plane.pages.get(rid)
            if not mine:
                out.append(0)
                continue
            mine_set = {lp for row in mine for lp in row}
            shared = set()
            for o in other_rids:
                for row in plane.pages.get(o, []):
                    shared.update(mine_set.intersection(row))
            out.append(len(shared))
        return np.asarray(out, np.int64)

    def prefix_group_of(self, rid: int) -> Optional[object]:
        """Co-scheduling identity: the root-edge radix node of the
        request's prompt (same node <=> same seed and at least the first
        prompt block in common — every sharer of any deeper prefix shares
        that root edge too). The schedulers cluster same-group requests
        inside a fairness class so a shared prefix parks/restores once per
        plan. None when sharing is off or the prompt has no indexed
        coverage."""
        if not self.sharing:
            return None
        blocks = self._req_blocks.get(rid)
        if not blocks:
            return None
        root = self._roots.get(self._req_seed.get(rid))
        if root is None:
            return None
        return root.children.get(blocks[0])

    def cow_reserve(self) -> np.ndarray:
        """Per-plane pages a pending copy-on-write may allocate (one clone
        per layer row of each token plane): the scheduler headroom for a
        fully-matched request that must still recompute its final prompt
        position."""
        return np.asarray([p.n_layers if p.kind == "tokens" else 0
                           for p in self.planes.values()], np.int64)

    def physical_pages(self) -> Dict[str, int]:
        """Allocated PHYSICAL pages per plane (a page shared by N block
        tables counts once) — what eviction and MemoryError accounting see."""
        return {n: int((p.aqua.page_table[:, 0] != -1).sum())
                for n, p in self.planes.items()}

    def logical_pages(self) -> Dict[str, int]:
        """Block-table page references per plane (a page shared by N block
        tables counts N times) — the unshared footprint for comparison."""
        return {n: sum(len(row) for rows in p.pages.values() for row in rows)
                for n, p in self.planes.items()}

    # -- block tables (the step-program operands) --------------------------
    def block_tables_prefill(self, rid: int, pad_to: Optional[int] = None
                             ) -> Dict[str, jnp.ndarray]:
        """One request's tables from position 0: token planes as
        (G, n_sub, pad_to) physical LOCAL slots, scratch-padded; state
        planes as (G, n_sub) bare slots. Chunked prefill passes a FIXED
        ``pad_to`` (pps plus the write-window spill) so every chunk of every
        request shares one table shape — no retrace per context length."""
        out = {}
        for name, plane in self.planes.items():
            rows = plane.pages[rid]
            if plane.kind == "tokens":
                bt = plane.aqua.block_tables(rows,
                                             pad_to=pad_to or len(rows[0]),
                                             pad_slot=plane.scratch_slot)
                out[name] = jnp.asarray(bt.reshape(self.G, plane.n_sub, -1))
            else:
                bt = plane.aqua.block_tables(rows, pad_to=1,
                                             pad_slot=plane.scratch_slot)
                out[name] = jnp.asarray(bt.reshape(self.G, plane.n_sub))
        return out

    def block_tables(self, lane_rids: Sequence[Optional[int]],
                     pad_to: Optional[int] = None) -> Dict[str, jnp.ndarray]:
        """Batched row query (decode lanes, or the fused step's packed
        decode+chunk rows): token planes as (G, n_sub, B, pad_to) physical
        LOCAL slots (``pad_to`` defaults to ``pps``; the fused step passes
        ``pps`` plus the chunk write-window spill so every row shares one
        shape), state planes as (G, n_sub, B); empty lanes and padding
        point at each plane's scratch page."""
        B = len(lane_rids)
        tok_pad = pad_to or self.pps
        out = {}
        for name, plane in self.planes.items():
            rows: List[List[int]] = []
            for l in range(plane.n_layers):
                for rid in lane_rids:
                    rows.append(plane.pages[rid][l] if rid is not None else [])
            if plane.kind == "tokens":
                bt = plane.aqua.block_tables(rows, pad_to=tok_pad,
                                             pad_slot=plane.scratch_slot)
                out[name] = jnp.asarray(
                    bt.reshape(self.G, plane.n_sub, B, tok_pad))
            else:
                bt = plane.aqua.block_tables(rows, pad_to=1,
                                             pad_slot=plane.scratch_slot)
                out[name] = jnp.asarray(bt.reshape(self.G, plane.n_sub, B))
        return out

    # -- tier migration (preempt / restore as page-table flips) ------------
    def park(self, rid: int, n_tokens: int, *, prefer: int = REMOTE,
             cause: str = "preempt"):
        """Preempt: flip the request's pages out of LOCAL — ALL planes fused
        into one coalesced message per (tier, donor) group (a hybrid's kv +
        ssm + conv pages ride one staging buffer, not one message per
        plane), token pages metered at their fill, state pages whole (they
        are always fully live).

        ``n_tokens`` is the context actually RESIDENT in the pools (for an
        engine request at ctx_len that is ctx_len-1: the newest token's
        state lands at its next decode step). A token page allocated ahead
        of a boundary but not yet written moves at fill 0.

        Shared pages move ONCE: parking drops this request's LOCAL pin, and
        only pages whose pin count reaches zero (no other active sharer) are
        offloaded — a shared prefix page leaves LOCAL when its LAST active
        referencer parks, and is metered full (its payload is complete
        whatever this request's own resident prefix is).

        Every park is an ``aqua.kv.park`` span labelled with the request,
        the pages it moved off LOCAL, the tier preferred and the caller's
        ``cause`` (preempt, spec, mispredict, drain).
        """
        moved = 0
        with TraceAnnotation("aqua.kv.park", rid=rid, tier=TIER_NAMES[prefer],
                             cause=cause) as span, self.meter.coalesce():
            for plane in self.planes.values():
                if rid not in plane.pages:
                    continue
                if plane.kind == "tokens":
                    for row in plane.pages[rid]:
                        fills = np.clip(
                            n_tokens - np.arange(len(row)) * self.page_tokens,
                            0, self.page_tokens) / self.page_tokens
                        # shared prefix pages are always fully written (only
                        # full prompt pages enter the index)
                        fills = np.where(plane.aqua.refcounts(row) > 1,
                                         1.0, fills)
                        plane.aqua.set_page_fill(row, fills)
                lps = plane.flat(rid)
                if rid in self._active:
                    for lp in lps:
                        self._unpin(plane, int(lp))
                victims = np.asarray([int(lp) for lp in lps
                                      if plane.pin.get(int(lp), 0) == 0],
                                     np.int64)
                if len(victims):
                    moved += int((plane.aqua.page_table[victims, 0]
                                  == LOCAL).sum())
                    plane.aqua.offload(victims, prefer=prefer)
            span.set_metadata(pages=moved)
        self._active.discard(rid)

    def restore(self, rid: int, *, cause: str = "admit"):
        """Make every page of the request LOCAL and pin it there (no bytes
        move for pages a still-active sharer kept LOCAL); resets token-page
        fills to 1.0. No-op when the request is already active. ``cause``
        (admit, prefetch) labels the ``aqua.kv.restore`` span."""
        self._activate(rid, cause)

    def nonlocal_pages(self, rid: int) -> np.ndarray:
        """Per-plane pages of the request currently NOT in the LOCAL tier."""
        out = []
        for plane in self.planes.values():
            rows = plane.aqua.page_table[plane.flat(rid)]
            out.append(int((rows[:, 0] != LOCAL).sum()) if len(rows) else 0)
        return np.asarray(out, np.int64)

    def local_headroom(self) -> np.ndarray:
        """Per-plane LOCAL slots obtainable without touching live pages:
        free slots plus cached (refcount-0) LOCAL pages, which eviction
        demotes or drops on demand."""
        out = []
        for p in self.planes.values():
            free = p.aqua.local_free
            if self.caching:
                free += int(((p.aqua.page_refs == 0)
                             & (p.aqua.page_table[:, 0] == LOCAL)).sum())
            out.append(free)
        return np.asarray(out, np.int64)

    def can_restore(self, rid: int) -> bool:
        """True when a restore fits every plane's obtainable LOCAL slots
        right now (free plus evictable cache — cached pages yield to a real
        restore) — the prefetch guard: an early ``ensure_local`` must never
        steal pages the current run set still needs (it would raise
        mid-step)."""
        return bool(np.all(self.nonlocal_pages(rid) <= self.local_headroom()))

    # -- coordinator-driven lease plumbing --------------------------------
    def add_remote_lease(self, donor: str, nbytes: float):
        """Split a donor's byte grant across the planes in proportion to
        their share of a full-length request's footprint. Slots are floored
        per plane so the booked capacity never exceeds the grant the
        coordinator accounts (a plane whose share rounds to zero simply
        gets no pool from this donor and falls through to the host tier);
        a grant too small for any plane's page goes whole to the
        largest-weight plane, matching the old single-pool ``max(1, ...)``."""
        weights = {n: float(self._plane_pages(p, self.max_seq)
                            * p.aqua.page_bytes)
                   for n, p in self.planes.items()}
        total = sum(weights.values())
        slots = {n: int(nbytes * weights[n] / total // p.aqua.page_bytes)
                 for n, p in self.planes.items()}
        if not any(slots.values()):
            slots[max(weights, key=weights.get)] = 1
        for name, n_slots in slots.items():
            if n_slots > 0:
                self.planes[name].aqua.add_remote_lease(donor, n_slots)

    def evict_remote(self, donor: str) -> int:
        """Honor a donor reclaim: evacuate every PHYSICAL page parked on the
        donor's pools to the host tier and drop the lease (the paper's
        iteration-boundary ``aqua.respond()``). A page shared by several
        block tables moves once. Returns pages moved.

        Raises:
            MemoryError: the host tier cannot absorb the evacuation.
        """
        with self.meter.coalesce():
            return sum(p.aqua.evict_remote(donor)
                       for p in self.planes.values()
                       if donor in p.aqua.remote_pools)

    # -- fault plumbing (lease revocation, donor loss) ---------------------
    def attach_faults(self, faults) -> None:
        """Share one ``core/faults.FaultInjector`` with every plane's tensor
        (transfer-leg retry consults) and the mesh domain (lease-boundary
        guards on the collective legs)."""
        self.faults = faults
        for plane in self.planes.values():
            plane.aqua.faults = faults
        if self.mesh is not None:
            self.mesh.attach_faults(faults)

    def shrink_lease(self, donor: str, frac: float) -> int:
        """Dynamic donor-side memory pressure: the donor reclaims ``frac``
        of its leased slots in EVERY plane, NOW (unlike ``evict_remote``
        this is partial, and unlike the coordinator reclaim poll it is not
        deferred to a respond boundary — the donor's own traffic needs the
        HBM). Occupied reclaimed slots live-migrate to the remaining donors
        or the host tier, all planes fused into one coalesced message per
        (tier, donor) group. Returns pages migrated.

        Raises:
            LeaseRevokedError: no live lease from this donor in any plane.
            MemoryError: the surviving tiers cannot absorb the migration.
        """
        if not 0.0 < frac <= 1.0:
            raise ValueError(f"shrink fraction {frac} not in (0, 1]")
        holders = [p for p in self.planes.values()
                   if donor in p.aqua.remote_pools]
        if not holders:
            raise LeaseRevokedError(
                f"shrink of donor {donor} without a live lease in any plane",
                donor=donor)
        moved = 0
        with self.meter.coalesce():
            for plane in holders:
                n = math.ceil(frac * plane.aqua.remote_capacity[donor])
                moved += plane.aqua.shrink_lease(donor, n)
        return moved

    def fail_donor(self, donor: str) -> List[int]:
        """Permanent donor loss: every page resident on the donor (every
        plane) flips to the LOST tier and the leases drop. Returns the
        sorted rids of VICTIM requests — those whose block tables reference
        a lost page — for the engine's recompute-from-prompt recovery.
        Radix coverage backed by lost pages is pruned immediately — CACHED
        pages on the dead slab are DROPPED with it (their only copy died;
        leaking their logical ids would bleed the pool one donor death at a
        time) — so no later arrival can adopt a dead prefix."""
        victims: set = set()
        for plane in self.planes.values():
            if donor not in plane.aqua.remote_pools:
                continue
            lost = set(int(l) for l in plane.aqua.fail_donor(donor))
            if not lost:
                continue
            for lp in lost:
                self._drop_tree_page(plane.name, lp)
            for rid, rows in plane.pages.items():
                if any(int(lp) in lost for row in rows for lp in row):
                    victims.add(rid)
        if self.faults is not None:
            self.faults.mark_donor_lost(donor)
        return sorted(victims)

    def total_capacity(self) -> np.ndarray:
        """Per-plane PHYSICAL slots across every live tier (scratch
        excluded): what the runtime can hold AT ALL, LOCAL or parked. The
        engine re-plans the scheduler budget against this after a lease
        shrinks or a donor dies — admission must contract when the tiers
        backing preemption vanish."""
        return np.asarray(
            [p.aqua.local_pool.shape[0] - 1 + p.aqua.host_pool.shape[0]
             + sum(p.aqua.remote_capacity.values())
             for p in self.planes.values()], np.int64)

    def stats(self) -> Dict:
        """Tier occupancy per plane, transfer-meter totals, and the prefix-
        sharing counters (hits, adopted tokens, copy-on-write clones,
        physical vs logical page counts)."""
        tiers: Dict[str, int] = {}
        for p in self.planes.values():
            for k, v in p.aqua.tier_counts().items():
                tiers[k] = tiers.get(k, 0) + v
        return {"tiers": tiers,
                "planes": {n: p.aqua.tier_counts()
                           for n, p in self.planes.items()},
                "page_tokens": self.page_tokens,
                "sharing": {"enabled": self.sharing,
                            "prefix_hits": self.prefix_hits,
                            "adopted_tokens": self.adopted_tokens,
                            "cow_copies": self.cow_copies,
                            "physical_pages": self.physical_pages(),
                            "logical_pages": self.logical_pages()},
                "cache": {"enabled": self.caching,
                          "hits": self.cache_hits,
                          "hit_tokens": self.cache_hit_tokens,
                          "evictions": self.cache_evictions,
                          "demotions": self.cache_demotions,
                          "cached_pages": self.cached_pages(),
                          "nodes": sum(1 for _ in self._iter_nodes())},
                "meter": {"bytes_fabric": self.meter.bytes_fabric,
                          "bytes_host": self.meter.bytes_host,
                          "messages_fabric": self.meter.messages_fabric,
                          "messages_host": self.meter.messages_host,
                          "retries_fabric": self.meter.retries_fabric,
                          "retries_host": self.meter.retries_host,
                          "sim_time": self.meter.sim_time}}

    # -- crash-consistent snapshot / restore --------------------------------
    _SNAP_COUNTERS = ("prefix_hits", "adopted_tokens", "cow_copies",
                      "cache_hits", "cache_hit_tokens", "cache_evictions",
                      "cache_demotions")

    def snapshot_state(self) -> Dict:
        """Serialize the runtime's full serving state to a plain dict:
        per-request block tables, every referenced page's PAYLOAD (gathered
        from whatever tier it sits on, each physical page captured once
        however many block tables alias it), the radix prefix tree with its
        per-block page sets, the per-request prompt records behind
        ``register_prefix``, and the sharing/cache counters.

        Logical page ids in the snapshot are snapshot-relative:
        :meth:`restore_state` re-allocates pages on a fresh runtime and
        remaps every reference, so the snapshot survives any allocator
        history. Call between engine steps only (no step program in
        flight); LOST pages cannot be captured — recovery must re-queue
        their victims first (``read`` raises on them, loudly).
        """
        def ser_node(node: _RadixNode) -> Dict:
            return {"blocks": [list(b) for b in node.blocks],
                    "pages": [{n: [int(x) for x in lps]
                               for n, lps in pd.items()}
                              for pd in node.pages],
                    "last_use": int(node.last_use),
                    "children": [ser_node(c)
                                 for c in node.children.values()]}

        tree_lps: Dict[str, set] = {name: set() for name in self.planes}
        for node in self._iter_nodes():
            for pd in node.pages:
                for n, lps in pd.items():
                    tree_lps[n].update(int(x) for x in lps)
        planes: Dict[str, Dict] = {}
        for name, plane in self.planes.items():
            rows = {int(rid): [[int(lp) for lp in row] for row in rws]
                    for rid, rws in plane.pages.items()}
            lps = sorted({lp for rws in rows.values()
                          for row in rws for lp in row} | tree_lps[name])
            planes[name] = {
                "pages": rows, "lps": lps,
                "data": (np.asarray(plane.aqua.read(lps)) if lps
                         else None),
                "fills": (plane.aqua.page_fill[
                    np.asarray(lps, np.int64)].tolist() if lps else [])}
        return {
            "version": 1,
            "planes": planes,
            "tree": [{"seed": seed,
                      "children": [ser_node(c)
                                   for c in root.children.values()]}
                     for seed, root in self._roots.items()],
            "req_blocks": {int(r): [list(b) for b in bl]
                           for r, bl in self._req_blocks.items()},
            "req_tokens": {int(r): list(t)
                           for r, t in self._req_tokens.items()},
            "req_seed": dict(self._req_seed),
            "req_registered": dict(self._req_registered),
            "clock": int(self._clock),
            "counters": {k: getattr(self, k) for k in self._SNAP_COUNTERS}}

    def restore_state(self, snap: Dict) -> None:
        """Rebuild a :meth:`snapshot_state` dict on a FRESH runtime of the
        same configuration and geometry.

        Every snapshot page is re-allocated preferring the HOST tier (the
        crash-safe landing zone; the fallback ladder spills to surviving
        remote leases, then LOCAL) and its payload written back verbatim,
        unmetered — a restore is reconstruction, not traffic. Refcounts are
        reconstructed exactly: one reference per block table aliasing the
        page, plus the CACHED state (refcount 0, slot kept) for pages owned
        purely by the radix index. The tree, its reverse map, the prompt
        records and the counters are rebuilt with the remapped ids. NO
        request is active afterwards (pins empty): the engine re-queues
        every in-flight request as parked and the normal placement path
        pulls its pages LOCAL on its next admission.

        Raises:
            ValueError: this runtime already holds request state (restore
                targets a fresh engine, never a live one).
        """
        if (any(p.pages for p in self.planes.values()) or self._roots
                or self._active):
            raise ValueError(f"{self.cfg.name}: restore_state on a runtime "
                             "already holding request state — restore "
                             "targets a FRESH engine")
        maps: Dict[str, Dict[int, int]] = {}
        for name, ps in snap["planes"].items():
            plane = self.planes[name]
            ref_rids: Dict[int, set] = {}
            for rid, rws in ps["pages"].items():
                for row in rws:
                    for lp in row:
                        ref_rids.setdefault(int(lp), set()).add(int(rid))
            lp_map: Dict[int, int] = {}
            old_lps = [int(x) for x in ps["lps"]]
            if old_lps:
                new = plane.aqua.allocate(len(old_lps), prefer=HOST)
                plane.aqua.write(new, jnp.asarray(ps["data"]), meter=False)
                plane.aqua.set_page_fill(new, np.asarray(ps["fills"]))
                cached: List[int] = []
                for old, nlp in zip(old_lps, new):
                    nlp = int(nlp)
                    lp_map[old] = nlp
                    k = len(ref_rids.get(old, ()))
                    if k == 0:
                        cached.append(nlp)   # tree-owned: CACHED, ref 0
                    for _ in range(k - 1):   # one ref per aliasing table
                        plane.aqua.retain([nlp])
                if cached:
                    plane.aqua.free_to_cache(cached)
            for rid, rws in ps["pages"].items():
                plane.pages[int(rid)] = [[lp_map[int(lp)] for lp in row]
                                         for row in rws]
            maps[name] = lp_map

        def build(d: Dict, parent: _RadixNode) -> _RadixNode:
            node = _RadixNode(
                blocks=[tuple(int(t) for t in b) for b in d["blocks"]],
                pages=[{n: np.asarray([maps[n][int(x)] for x in lps],
                                      np.int64)
                        for n, lps in pd.items()} for pd in d["pages"]],
                parent=parent)
            node.last_use = int(d["last_use"])
            for cd in d["children"]:
                c = build(cd, node)
                node.children[c.blocks[0]] = c
            return node

        for entry in snap["tree"]:
            root = _RadixNode()
            for cd in entry["children"]:
                c = build(cd, root)
                root.children[c.blocks[0]] = c
            self._roots[entry["seed"]] = root
        for node in self._iter_nodes():
            for bi, pd in enumerate(node.pages):
                for n, lps in pd.items():
                    for lp in lps:
                        self._lp_node[(n, int(lp))] = (node, bi)
        self._req_blocks = {int(r): [tuple(int(t) for t in b) for b in bl]
                            for r, bl in snap["req_blocks"].items()}
        self._req_tokens = {int(r): tuple(int(t) for t in ts)
                            for r, ts in snap["req_tokens"].items()}
        self._req_seed = dict(snap["req_seed"])
        self._req_registered = {int(r): int(v)
                                for r, v in snap["req_registered"].items()}
        self._clock = int(snap["clock"])
        for k in self._SNAP_COUNTERS:
            setattr(self, k, snap["counters"][k])
