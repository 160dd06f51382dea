"""AQUA TENSORS — transparent, elastic, tiered paged tensors (paper §3).

A logical paged tensor whose pages physically live in one of three tiers:

  LOCAL   the serving chip's own HBM page pool (directly addressable by the
          paged_attention kernel)
  REMOTE  a *donor* chip's HBM pool, reachable over the scale-up fabric
          (NVLink in the paper; ICI here). Transfers are COALESCED: the
          kv_gather Pallas kernel packs the victim pages into one contiguous
          staging buffer, which moves as a single large message
          (distributed/collectives.paging_permute on a real mesh).
  HOST    host DRAM over PCIe — the FlexGen/vLLM-swap fallback tier the paper
          compares against.

The ML model is oblivious to placement (the paper's "transparent" property):
the serving engine only sees logical page ids; ``ensure_local`` is invoked at
inference-iteration boundaries (the paper's ``aqua.respond()`` insight — pages
are only read/written between iterations, so migration is race-free).

Serving-runtime hooks (docs/paged_runtime.md): the LOCAL pool is directly the
operand of the paged_attention kernels, ``block_tables`` answers batched
logical->physical LOCAL slot queries for whole request sets, and
``set_page_fill`` declares partial tails so a half-filled page is moved and
metered at its valid fraction only.

Elasticity: the remote tier is backed by *leases* from the coordinator; a
donor can reclaim its memory at any iteration boundary via ``evict_remote``.

Two REMOTE backends share one data path:

  single-device (mesh=None)   every tier is a real buffer on the serving
      device; transfers are gather -> staging -> scatter on one chip. Always
      available, bit-exact, and the reference the mesh backend is tested
      against.
  mesh-real (mesh=MeshTierDomain)   a donor lease is an actual slab of a
      PEER device's memory (distributed/mesh_tiers.py): the pool is sharded
      over the domain's 1-D mesh with the donor's rows resident on the donor
      device, and each (tier, donor) leg of offload/ensure_local/evict_remote
      lowers to ONE ``ppermute`` collective — physically matching the
      TransferMeter's one-message-per-leg pricing. Host staging exists only
      on the HOST leg.

Every movement is metered (bytes, messages, tier) and priced by
core/perfmodel.py — that is the simulated clock the benchmarks report; on a
mesh the clock is additionally CALIBRATED against measured collective times
(``MeshTierDomain.calibrated_profile``).
"""
from __future__ import annotations

import dataclasses
from dataclasses import dataclass, field
from typing import Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.profiler import TraceAnnotation

from repro.core.errors import (AquaError, LeaseRevokedError, PageLossError,
                               TransferFaultError)
from repro.core.perfmodel import (HardwareProfile, TPU_V5E,
                                  retry_backoff_time)
from repro.kernels.kv_gather import ops as kv_ops

LOCAL, REMOTE, HOST = 0, 1, 2
# LOST: the page's only copy was on a donor that died (``fail_donor``).
# Lost pages keep their refcounts (the auditor still sees the referencers)
# but any read/migration raises PageLossError — the engine's recovery path
# releases the victims and recomputes their context from the prompt.
LOST = 3
TIER_NAMES = {LOCAL: "local", REMOTE: "remote", HOST: "host", LOST: "lost"}


@dataclass
class TransferMeter:
    """Accounting for every page movement; priced by the perf model.

    ``coalesce()`` opens a CROSS-PLANE transaction: every ``record`` inside
    it accumulates bytes per ``(tier, group)`` key instead of emitting a
    message, and the transaction emits ONE message per key on exit — the
    multi-plane park/restore of a request (kv + ssm + conv pages, say)
    rides one staging buffer per (tier, donor) instead of one message per
    plane, which is the AQUA Fig. 3a small-message tax applied to hybrid
    and SSM flips."""
    hw: HardwareProfile = TPU_V5E
    bytes_fabric: float = 0.0
    bytes_host: float = 0.0
    messages_fabric: int = 0
    messages_host: int = 0
    # failed-then-retried leg attempts (fault injection): priced like
    # messages plus backoff, but counted apart — a retry never issued a
    # physical collective
    retries_fabric: int = 0
    retries_host: int = 0
    sim_time: float = 0.0
    coalesced: bool = True
    _txn: Optional[Dict] = field(default=None, repr=False, compare=False)

    def record(self, nbytes: float, tier: int, n_pages: int, group=None):
        if self._txn is not None:
            b, p = self._txn.get((tier, group), (0.0, 0))
            self._txn[(tier, group)] = (b + nbytes, p + n_pages)
            return
        link = self.hw.fabric if tier == REMOTE else self.hw.host_link
        msgs = 1 if self.coalesced else max(1, n_pages)
        if tier == REMOTE:
            self.bytes_fabric += nbytes
            self.messages_fabric += msgs
        else:
            self.bytes_host += nbytes
            self.messages_host += msgs
        self.sim_time += link.time(nbytes, n_messages=msgs)

    def record_retry(self, nbytes: float, tier: int, n_pages: int,
                     attempt: int):
        """Price one FAILED transfer-leg attempt: the wasted message time
        plus exponential backoff before the retry. Retries bypass any open
        ``coalesce`` transaction (their time is real whatever the batching)
        and are counted in ``retries_*``, never ``messages_*`` — a failed
        attempt never issued a physical collective, so the mesh domain's
        ``collectives`` counter and the priced message count stay in
        lockstep."""
        link = self.hw.fabric if tier == REMOTE else self.hw.host_link
        msgs = 1 if self.coalesced else max(1, n_pages)
        if tier == REMOTE:
            self.retries_fabric += msgs
        else:
            self.retries_host += msgs
        self.sim_time += (link.time(nbytes, n_messages=msgs)
                          + retry_backoff_time(self.hw, attempt))

    def coalesce(self):
        """Context manager fusing every ``record`` inside it into one
        message per ``(tier, group)`` key (reentrant: the outermost
        transaction wins)."""
        return _MeterTxn(self)


class _MeterTxn:
    def __init__(self, meter: TransferMeter):
        self.meter = meter
        self.outer = False

    def __enter__(self):
        if self.meter._txn is not None:
            self.outer = True           # nested: fold into the outer txn
            return self.meter
        self.meter._txn = {}
        return self.meter

    def __exit__(self, exc_type, exc, tb):
        if self.outer:
            return False
        txn, self.meter._txn = self.meter._txn, None
        for (tier, _group), (nbytes, n_pages) in txn.items():
            self.meter.record(nbytes, tier, n_pages)
        return False


class AquaTensor:
    """A paged tensor with tiered page placement. Page payload: (page, d)."""

    def __init__(self, *, page_shape: Tuple[int, ...], local_slots: int,
                 host_slots: int, dtype=jnp.bfloat16,
                 meter: Optional[TransferMeter] = None, name: str = "kv",
                 mesh=None, faults=None):
        self.name = name
        # optional MeshTierDomain: REMOTE pools become donor-device slabs and
        # remote legs become collectives (duck-typed; None = single-device)
        self.mesh = mesh
        # optional core/faults.FaultInjector, consulted at every transfer
        # leg (bounded retry-with-backoff on transient failures) and lease
        # boundary (lost donors are never addressed again)
        self.faults = faults
        self.page_shape = tuple(page_shape)
        self.dtype = jnp.dtype(dtype)
        self.page_bytes = int(np.prod(page_shape)) * self.dtype.itemsize
        self.local_pool = jnp.zeros((local_slots,) + self.page_shape, self.dtype)
        self.host_pool = np.zeros((host_slots,) + self.page_shape, self.dtype)
        self.remote_pools: Dict[str, jnp.ndarray] = {}
        self._remote_free: Dict[str, List[int]] = {}
        # page_table[lp] = (tier, slot, donor_idx) ; -1 = unallocated. One
        # logical id per physical slot (a page always holds a slot, so more
        # ids could never be allocated); every remote lease adds ids for its
        # slots
        n_logical = local_slots + host_slots
        self.page_table = np.full((n_logical, 3), -1, np.int64)
        # reference count per logical page: pages shared between block tables
        # (prefix sharing) are retained once per referencer and their physical
        # slot is released only when the LAST reference is freed. All physical
        # accounting (tier_counts, local_free, MemoryError on exhaustion) is
        # per logical page, so a page shared by N block tables costs one slot.
        self.page_refs = np.zeros((n_logical,), np.int64)
        # fraction of the page payload that holds live data (partial tails):
        # transfers are metered on valid bytes only, so a request's last,
        # half-filled KV page does not inflate its migration cost.
        self.page_fill = np.ones((n_logical,), np.float64)
        self._free_local = list(range(local_slots))[::-1]
        self._free_host = list(range(host_slots))[::-1]
        self._donors: List[str] = []
        # leased slots per live donor (shrinks under ``shrink_lease``) — the
        # capacity the auditor checks the free-list/occupancy partition
        # against
        self.remote_capacity: Dict[str, int] = {}
        self.meter = meter or TransferMeter()
        # CACHED pages: refcount 0 but still physically resident (a prefix
        # cache retains them for future adoption). ``reclaim`` is an optional
        # hook ``reclaim(tier, need) -> freed`` installed by the cache owner;
        # it is consulted when a tier's free list runs dry so cached pages
        # YIELD before any real allocation can fail — a cache-on run never
        # raises a MemoryError a cache-off run would not hit.
        self.reclaim = None
        self._reclaiming = False

    def _try_reclaim(self, tier: int, need: int) -> int:
        """Ask the cache owner to evict/demote cached pages out of ``tier``.
        Reentrancy-guarded: an eviction's own demotion ``_move`` must not
        recurse into another reclaim."""
        if self.reclaim is None or self._reclaiming:
            return 0
        self._reclaiming = True
        # an eviction's demotion is its own migration, not a leg of the
        # caller's park/restore: it is priced as its own message(s), one per
        # physical transfer, even inside an open coalesce() transaction
        txn, self.meter._txn = self.meter._txn, None
        try:
            return int(self.reclaim(tier, need))
        finally:
            self.meter._txn = txn
            self._reclaiming = False

    # ------------------------------------------------------------------
    # lease management (driven by the coordinator)
    # ------------------------------------------------------------------
    def add_remote_lease(self, donor: str, slots: int):
        """Donor offered `slots` pages of its HBM (coordinator /lease).

        A donor evicted earlier may re-lease: its ``_donors`` entry is
        REUSED, never duplicated — a second append would leave the old
        index resolvable to the new pool for any stale ``donor_idx`` and
        split one physical donor across two bookkeeping identities.

        Raises:
            ValueError: the donor already holds a live lease here.
            LeaseRevokedError: the donor was marked permanently lost.
        """
        if donor in self.remote_pools:
            raise ValueError(f"{self.name}: donor {donor} already holds a "
                             "live lease (evict before re-leasing)")
        if self.faults is not None and self.faults.donor_lost(donor):
            raise LeaseRevokedError(
                f"{self.name}: donor {donor} is permanently lost and cannot "
                "offer a lease", donor=donor)
        if self.mesh is not None:
            self.remote_pools[donor] = self.mesh.alloc_pool(
                donor, slots, self.page_shape, self.dtype)
        else:
            self.remote_pools[donor] = jnp.zeros(
                (slots,) + self.page_shape, self.dtype)
        self._remote_free[donor] = list(range(slots))[::-1]
        self.remote_capacity[donor] = int(slots)
        self.page_table = np.concatenate(
            [self.page_table, np.full((slots, 3), -1, np.int64)])
        self.page_refs = np.concatenate(
            [self.page_refs, np.zeros((slots,), np.int64)])
        self.page_fill = np.concatenate(
            [self.page_fill, np.ones((slots,), np.float64)])
        if donor not in self._donors:
            self._donors.append(donor)

    def evict_remote(self, donor: str) -> int:
        """Donor reclaims its lease: evacuate pages to host, drop the pool."""
        moved = 0
        victims = np.nonzero((self.page_table[:, 0] == REMOTE)
                             & (self.page_table[:, 2] == self._donors.index(donor)))[0]
        if len(victims):
            self._move(victims, HOST)
            moved = len(victims)
        del self.remote_pools[donor]
        del self._remote_free[donor]
        del self.remote_capacity[donor]
        # donor stays in _donors so indices of others remain stable
        return moved

    def shrink_lease(self, donor: str, n_slots: int) -> int:
        """Donor reclaims its TOP ``n_slots`` slots under its own memory
        pressure (the dynamic-lease gap: eviction's partial form). Occupied
        reclaimed slots LIVE-MIGRATE to the remaining remote donors or the
        HOST tier — never back onto the shrinking donor (it wants the HBM
        back, re-placing there would hand it straight out again). Reclaimed
        free slots just leave the free list. A shrink to zero drops the
        lease entirely (like ``evict_remote``). Returns pages migrated.

        Raises:
            LeaseRevokedError: no live lease from this donor.
            MemoryError: the surviving tiers cannot absorb the migration.
        """
        if donor not in self.remote_pools:
            raise LeaseRevokedError(
                f"{self.name}: shrink of donor {donor} without a live lease",
                donor=donor)
        cap = self.remote_capacity[donor]
        n = int(min(max(n_slots, 0), cap))
        if n == 0:
            return 0
        lo = cap - n
        di = self._donors.index(donor)
        victims = np.nonzero((self.page_table[:, 0] == REMOTE)
                             & (self.page_table[:, 2] == di)
                             & (self.page_table[:, 1] >= lo))[0]
        moved = 0
        if len(victims):
            self._move(victims, REMOTE, exclude_donor=donor)
            moved = len(victims)
        self._remote_free[donor] = [s for s in self._remote_free[donor]
                                    if s < lo]
        self.remote_capacity[donor] = lo
        if lo == 0:
            del self.remote_pools[donor]
            del self._remote_free[donor]
            del self.remote_capacity[donor]
        return moved

    def fail_donor(self, donor: str) -> np.ndarray:
        """Permanent donor loss: the peer died holding its slab, so every
        page resident there is gone — no evacuation leg exists to run. The
        pages flip to the LOST tier (refcounts intact: the auditor still
        sees every referencer until recovery releases them) and the lease
        is dropped. Returns the lost logical page ids; reading, migrating,
        or building block tables over them raises ``PageLossError`` — the
        engine's cue to re-queue the victims and recompute from the
        prompt."""
        if donor not in self.remote_pools:
            return np.zeros((0,), np.int64)
        di = self._donors.index(donor)
        lost = np.nonzero((self.page_table[:, 0] == REMOTE)
                          & (self.page_table[:, 2] == di))[0]
        self.page_table[lost, 0] = LOST
        del self.remote_pools[donor]
        del self._remote_free[donor]
        del self.remote_capacity[donor]
        if self.faults is not None:
            self.faults.mark_donor_lost(donor)
        return lost

    # ------------------------------------------------------------------
    # allocation
    # ------------------------------------------------------------------
    def allocate(self, n: int, prefer: int = LOCAL) -> np.ndarray:
        """Allocate n logical pages (preferred tier first, then fallbacks).

        Each page starts with refcount 1 (the allocator owns it); sharers
        call :meth:`retain` to add references.

        Raises:
            MemoryError: out of logical page ids, or every physical tier is
                full (``all tiers full``).
        """
        free_lp = np.nonzero(self.page_table[:, 0] == -1)[0]
        if len(free_lp) < n:
            # cached pages occupy logical ids too: ask them to yield
            # (tier -1 = "free outright, any tier") before failing
            self._try_reclaim(-1, n - len(free_lp))
            free_lp = np.nonzero(self.page_table[:, 0] == -1)[0]
        if len(free_lp) < n:
            raise MemoryError(f"{self.name}: out of logical pages")
        lps = free_lp[:n]
        taken: List[int] = []
        try:
            for lp in lps:
                tier, slot, donor = self._take_slot(prefer)
                self.page_table[lp] = (tier, slot, donor)
                taken.append(int(lp))
        except MemoryError:
            # all-or-nothing: hand back every slot this call already took —
            # a partial multi-page allocation must not leak pages when the
            # pool runs dry mid-way
            self._release_slots(taken)
            raise
        self.page_fill[lps] = 1.0
        self.page_refs[lps] = 1
        return lps

    def _release_slots(self, lps: Sequence[int]):
        """Return the physical slots of not-yet-reffed pages to their free
        lists (allocation-rollback helper: the pages were taken in a failing
        call and never exposed to a caller)."""
        for lp in lps:
            tier, slot, donor = self.page_table[lp]
            if tier == LOCAL:
                self._free_local.append(int(slot))
            elif tier == HOST:
                self._free_host.append(int(slot))
            elif tier == REMOTE:
                self._remote_free[self._donors[donor]].append(int(slot))
            self.page_table[lp] = (-1, -1, -1)
            self.page_fill[lp] = 1.0
            self.page_refs[lp] = 0

    def retain(self, lps: Sequence[int]):
        """Add one reference to each listed page (copy-on-write sharing): the
        physical slot is released only when every reference is freed."""
        lps = np.asarray(lps, np.int64)
        if (self.page_refs[lps] < 1).any():
            bad = [int(l) for l in lps if self.page_refs[l] < 1]
            raise ValueError(f"{self.name}: retain of unallocated pages {bad}")
        self.page_refs[lps] += 1

    def refcounts(self, lps: Sequence[int]) -> np.ndarray:
        """Current reference count of each listed logical page."""
        return self.page_refs[np.asarray(lps, np.int64)].copy()

    def free(self, lps: Sequence[int]) -> List[int]:
        """Drop one reference per listed page; release the physical slot of
        pages whose count reaches zero. Returns the logical ids actually
        freed — a page still referenced by another block table survives with
        its payload intact (the sharer keeps reading it)."""
        freed: List[int] = []
        for lp in lps:
            if self.page_refs[lp] > 1:
                self.page_refs[lp] -= 1
                continue
            tier, slot, donor = self.page_table[lp]
            if tier == LOCAL:
                self._free_local.append(int(slot))
            elif tier == HOST:
                self._free_host.append(int(slot))
            elif tier == REMOTE:
                self._remote_free[self._donors[donor]].append(int(slot))
            # LOST: the slot's pool is gone — nothing to hand back
            self.page_table[lp] = (-1, -1, -1)
            self.page_fill[lp] = 1.0
            self.page_refs[lp] = 0
            freed.append(int(lp))
        return freed

    # ------------------------------------------------------------------
    # CACHED state: refcount 0, still resident (global prefix cache)
    # ------------------------------------------------------------------
    def free_to_cache(self, lps: Sequence[int]) -> List[int]:
        """Drop one reference per listed page but KEEP the physical slot of
        pages whose count reaches zero — they enter the CACHED state
        (refcount 0, page_table row still valid, payload intact) so a future
        prefix adoption can ``revive`` them instead of recomputing prefill.
        Returns the logical ids that just became cached. A LOST page cannot
        be cached (its payload is gone): it is freed as usual."""
        cached: List[int] = []
        for lp in lps:
            if self.page_refs[lp] > 1:
                self.page_refs[lp] -= 1
                continue
            if self.page_table[lp, 0] == LOST:
                self.page_table[lp] = (-1, -1, -1)
                self.page_fill[lp] = 1.0
                self.page_refs[lp] = 0
                continue
            self.page_refs[lp] = 0
            cached.append(int(lp))
        return cached

    def revive(self, lps: Sequence[int]):
        """Cache hit: take the first reference on CACHED pages (refcount
        0 -> 1). Strict counterpart of :meth:`retain`, which refuses
        refcount-0 pages — revive refuses anything NOT cached."""
        lps = np.asarray(lps, np.int64)
        bad = [int(l) for l in lps
               if self.page_refs[l] != 0 or self.page_table[l, 0] == -1]
        if bad:
            raise ValueError(f"{self.name}: revive of non-cached pages {bad}")
        self.page_refs[lps] = 1

    def drop_cached(self, lps: Sequence[int]) -> List[int]:
        """Evict CACHED pages: hand their physical slots back to the free
        lists (LOST rows have no pool — just clear the row). Only legal on
        refcount-0 resident pages; returns the ids actually dropped."""
        dropped: List[int] = []
        for lp in lps:
            if self.page_refs[lp] != 0 or self.page_table[lp, 0] == -1:
                raise ValueError(
                    f"{self.name}: drop_cached of non-cached page {int(lp)}")
            tier, slot, donor = self.page_table[lp]
            if tier == LOCAL:
                self._free_local.append(int(slot))
            elif tier == HOST:
                self._free_host.append(int(slot))
            elif tier == REMOTE:
                self._remote_free[self._donors[donor]].append(int(slot))
            self.page_table[lp] = (-1, -1, -1)
            self.page_fill[lp] = 1.0
            dropped.append(int(lp))
        return dropped

    def set_page_fill(self, lps: Sequence[int], frac):
        """Declare the valid fraction of each page payload (partial tails)."""
        self.page_fill[np.asarray(lps, np.int64)] = np.clip(frac, 0.0, 1.0)

    def _take_slot(self, prefer: int = LOCAL) -> Tuple[int, int, int]:
        order = {LOCAL: [LOCAL, REMOTE, HOST], REMOTE: [REMOTE, HOST, LOCAL],
                 HOST: [HOST, REMOTE, LOCAL]}[prefer]
        for tier in order:
            if tier == LOCAL:
                if not self._free_local:
                    self._try_reclaim(LOCAL, 1)
                if self._free_local:
                    return LOCAL, self._free_local.pop(), -1
            if tier == REMOTE:
                for di, d in enumerate(self._donors):
                    if d in self._remote_free and self._remote_free[d]:
                        return REMOTE, self._remote_free[d].pop(), di
            if tier == HOST:
                if not self._free_host:
                    self._try_reclaim(HOST, 1)
                if self._free_host:
                    return HOST, self._free_host.pop(), -1
        raise MemoryError(f"{self.name}: all tiers full")

    # ------------------------------------------------------------------
    # remote-pool transfer legs (mesh-aware, fault-guarded)
    # ------------------------------------------------------------------
    def _leg_guard(self, tier: int, donor: Optional[str], n_pages: int):
        """Consult the fault injector BEFORE issuing a transfer leg.

        Transient failures retry with exponential backoff, each failed
        attempt priced as a full wasted message (``record_retry``); the
        injector's ``max_consecutive`` streak cap guarantees convergence
        below the retry budget for any seed. Because the consult precedes
        the collective, a failed attempt never reaches the wire — the mesh
        ``collectives`` counter stays in lockstep with priced messages.

        Raises:
            LeaseRevokedError: the addressed donor is permanently lost.
            TransferFaultError: the leg failed past ``max_leg_retries``
                (unreachable with a streak-capped injector).
        """
        f = self.faults
        if f is None:
            return
        if f.donor_lost(donor):
            raise LeaseRevokedError(
                f"{self.name}: transfer leg addressed lost donor {donor}",
                donor=donor)
        nbytes = float(n_pages) * self.page_bytes
        attempt = 0
        while f.leg_fails(tier, donor):
            attempt += 1
            self.meter.record_retry(nbytes, tier, n_pages, attempt)
            if attempt >= f.max_leg_retries:
                raise TransferFaultError(
                    f"{self.name}: {TIER_NAMES[tier]} leg"
                    f"{' to ' + donor if donor else ''} failed "
                    f"{attempt} consecutive attempts (retry budget "
                    f"{f.max_leg_retries})", tier=tier, donor=donor,
                    attempts=attempt)

    def _remote_gather(self, donor: str, slots) -> jnp.ndarray:
        """Pull `slots` out of a donor pool as one contiguous staging batch.
        Mesh backend: one ``ppermute`` donor -> serving device."""
        if donor not in self.remote_pools:
            raise LeaseRevokedError(
                f"{self.name}: gather from donor {donor} without a live "
                "lease", donor=donor)
        self._leg_guard(REMOTE, donor, len(slots))
        pool = self.remote_pools[donor]
        slots = np.asarray(slots, np.int32)
        if self.mesh is not None:
            return self.mesh.pull(pool, donor, slots)
        return kv_ops.gather_pages(pool, jnp.asarray(slots))

    def _remote_scatter(self, donor: str, slots, data: jnp.ndarray):
        """Push a contiguous staging batch into a donor pool at `slots`.
        Mesh backend: one ``ppermute`` serving device -> donor."""
        if donor not in self.remote_pools:
            raise LeaseRevokedError(
                f"{self.name}: scatter to donor {donor} without a live "
                "lease", donor=donor)
        self._leg_guard(REMOTE, donor, len(slots))
        pool = self.remote_pools[donor]
        slots = np.asarray(slots, np.int32)
        data = data.astype(self.dtype)
        if self.mesh is not None:
            self.remote_pools[donor] = self.mesh.push(pool, donor, slots, data)
        else:
            self.remote_pools[donor] = kv_ops.scatter_pages(
                pool, data, jnp.asarray(slots))

    # ------------------------------------------------------------------
    # data access
    # ------------------------------------------------------------------
    def write_local(self, lps: Sequence[int], data: jnp.ndarray):
        """Write page payloads for LOCAL-resident logical pages."""
        slots = self._slots_of(lps, LOCAL)
        self.local_pool = kv_ops.scatter_pages(
            self.local_pool, data.astype(self.dtype), jnp.asarray(slots, jnp.int32))

    def write(self, lps: Sequence[int], data: jnp.ndarray, *, meter: bool = True):
        """Write page payloads wherever the pages live. Non-local groups are
        one coalesced transfer each (metered): data is already contiguous, so
        this is the staging-buffer -> donor/host leg of a page-out."""
        data = data.astype(self.dtype)
        rows = self.page_table[np.asarray(lps, np.int64)]
        for tier in (LOCAL, REMOTE, HOST):
            idx = np.nonzero(rows[:, 0] == tier)[0]
            if not len(idx):
                continue
            slots = rows[idx, 1].astype(np.int32)
            part = data[idx]
            if tier == LOCAL:
                self.local_pool = kv_ops.scatter_pages(
                    self.local_pool, part, jnp.asarray(slots))
                continue
            if tier == REMOTE:
                for di in np.unique(rows[idx, 2]):
                    sub = idx[rows[idx, 2] == di]
                    d = self._donors[int(di)]
                    self._remote_scatter(d, rows[sub, 1], data[sub])
                    if meter:
                        self.meter.record(data[sub].nbytes, REMOTE, len(sub))
            else:
                self._leg_guard(HOST, None, len(idx))
                self.host_pool[slots] = np.asarray(part)
                if meter:
                    self.meter.record(part.nbytes, HOST, len(idx))

    def read(self, lps: Sequence[int], *, meter: bool = False) -> jnp.ndarray:
        """Gather page payloads regardless of tier (does not migrate).
        Batched per (tier, donor) group — one gather (one collective, on a
        mesh) per group, reassembled into request order. meter=True prices
        the non-local groups as coalesced page-in transfers (the restore leg
        of a context switch)."""
        lps = np.asarray(lps, np.int64)
        rows = self.page_table[lps]
        if len(lps) == 0:
            return jnp.zeros((0,) + self.page_shape, self.dtype)
        self._check_not_lost(lps, rows, "read")
        parts: List[jnp.ndarray] = []
        order: List[np.ndarray] = []
        for tier in (LOCAL, REMOTE, HOST):
            idx = np.nonzero(rows[:, 0] == tier)[0]
            if not len(idx):
                continue
            if tier == LOCAL:
                parts.append(self.local_pool[jnp.asarray(
                    rows[idx, 1].astype(np.int32))])
                order.append(idx)
            elif tier == HOST:
                self._leg_guard(HOST, None, len(idx))
                parts.append(jnp.asarray(
                    self.host_pool[rows[idx, 1].astype(np.int64)]))
                order.append(idx)
            else:
                for di in np.unique(rows[idx, 2]):
                    sub = idx[rows[idx, 2] == di]
                    parts.append(self._remote_gather(
                        self._donors[int(di)], rows[sub, 1]))
                    order.append(sub)
        combined = jnp.concatenate(parts, axis=0)
        positions = np.concatenate(order)
        out = combined[jnp.asarray(np.argsort(positions, kind="stable"))]
        if meter:
            fills = self.page_fill[lps]
            for tier in (REMOTE, HOST):
                idx = np.nonzero(rows[:, 0] == tier)[0]
                if len(idx):
                    self.meter.record(float(fills[idx].sum()) * self.page_bytes,
                                      tier, len(idx))
        return out

    def local_slots_of(self, lps: Sequence[int]) -> np.ndarray:
        return self._slots_of(lps, LOCAL)

    def block_tables(self, lps_rows: Sequence[Sequence[int]], pad_to: int,
                     *, pad_slot: int = 0) -> np.ndarray:
        """Batched block-table query: physical LOCAL slots of each row's
        logical pages as one padded (B, pad_to) int32 table — the operand the
        paged_attention kernel consumes. Every listed page must be LOCAL
        (call ``ensure_local`` first); padding entries point at ``pad_slot``
        (a resident dummy) so masked DMAs stay in-bounds."""
        out = np.full((len(lps_rows), pad_to), pad_slot, np.int32)
        for b, lps in enumerate(lps_rows):
            if len(lps) == 0:
                continue
            if len(lps) > pad_to:
                raise ValueError(f"{self.name}: row {b} has {len(lps)} pages"
                                 f" > pad_to={pad_to}")
            rows = self.page_table[np.asarray(lps, np.int64)]
            if not (rows[:, 0] == LOCAL).all():
                self._check_not_lost(lps, rows, "block-table build")
                bad = [int(l) for l, r in zip(lps, rows) if r[0] != LOCAL]
                raise ValueError(f"{self.name}: pages {bad} not LOCAL; "
                                 "ensure_local before building block tables")
            out[b, :len(lps)] = rows[:, 1]
        return out

    def _slots_of(self, lps, tier) -> np.ndarray:
        rows = self.page_table[np.asarray(lps, np.int64)]
        if not (rows[:, 0] == tier).all():
            bad = [int(l) for l, r in zip(lps, rows) if r[0] != tier]
            raise ValueError(f"pages {bad} not in tier {TIER_NAMES[tier]}")
        return rows[:, 1].astype(np.int32)

    # ------------------------------------------------------------------
    # migration (the AQUA mechanism)
    # ------------------------------------------------------------------
    def ensure_local(self, lps: Sequence[int]):
        """Page-in: make all listed logical pages LOCAL (coalesced per tier).

        Raises:
            PageLossError: a listed page is in the LOST tier (its donor died
                holding the only copy) — there is nothing to page in.
        """
        lps = np.asarray(lps, np.int64)
        rows = self.page_table[lps]
        self._check_not_lost(lps, rows, "ensure_local")
        for tier in (REMOTE, HOST):
            sel = lps[rows[:, 0] == tier]
            if len(sel):
                self._move(sel, LOCAL)

    def offload(self, lps: Sequence[int], *, prefer: int = REMOTE):
        """Page-out LOCAL pages to the fast remote tier (host as fallback).

        Raises:
            PageLossError: a listed page is LOST — silently skipping it
                (like the already-remote pages below) would mask a donor
                death from the park path.
        """
        lps = np.asarray(lps, np.int64)
        rows = self.page_table[lps]
        self._check_not_lost(lps, rows, "offload")
        sel = lps[rows[:, 0] == LOCAL]
        if len(sel):
            self._move(sel, prefer)

    def _check_not_lost(self, lps, rows, op: str):
        """Touching a LOST page is unrecoverable here — surface the typed
        loss so the engine's recompute-from-prompt path takes over."""
        lost = [int(l) for l, r in zip(lps, rows) if r[0] == LOST]
        if lost:
            raise PageLossError(
                f"{self.name}: {op} of page(s) {lost[:8]} whose donor died "
                "holding the only copy", plane=self.name, pages=lost)

    def _move(self, lps: np.ndarray, dst_tier: int,
              exclude_donor: Optional[str] = None):
        """Coalesced migration of a batch of pages between tiers.

        ``exclude_donor`` removes one donor from the REMOTE destination set
        (a shrinking donor must not receive the pages it is reclaiming).

        Raises:
            PageLossError: a listed page is in the LOST tier.
        """
        # group by (source tier, donor) so each group is ONE gather + transfer
        rows = self.page_table[lps]
        self._check_not_lost(lps, rows, "migration")
        groups: Dict[Tuple[int, int], List[int]] = {}
        for lp, (tier, slot, donor) in zip(lps, rows):
            groups.setdefault((int(tier), int(donor)), []).append(int(lp))
        for (src_tier, src_donor), group in groups.items():
            slots = self.page_table[group, 1].astype(np.int32)
            # 1) coalescing gather into a contiguous staging buffer. The
            # source slots are NOT freed yet: destination acquisition below
            # can fail (tier exhausted even after cache reclaim), and the
            # group's rows must still be valid then — freeing first left
            # pages mapped to free-listed slots, a double-free on their
            # eventual release.
            if src_tier == LOCAL:
                with TraceAnnotation("aqua.tier.gather", pages=len(slots)):
                    staging = kv_ops.gather_pages(self.local_pool,
                                                  jnp.asarray(slots))
            elif src_tier == REMOTE:
                donor_name = self._donors[src_donor]
                with TraceAnnotation("aqua.tier.gather", pages=len(slots)):
                    staging = self._remote_gather(donor_name, slots)
            else:
                self._leg_guard(HOST, None, len(slots))
                with TraceAnnotation("aqua.tier.h2d", pages=len(slots)):
                    staging = jnp.asarray(self.host_pool[slots])
            # valid payload only: a partial tail page moves (and is priced
            # as) its live rows, not the whole page buffer
            fills = self.page_fill[group] * self.page_bytes   # per-page bytes
            # 2) message metering rides the placement below: the txn key is
            # (src tier, src donor NAME, dst tier, dst donor NAME), so a
            # cross-plane coalesce() transaction fuses every plane's leg of
            # the same physical migration into one staging buffer per
            # (tier, donor) — donor NAMES, not per-plane indices (two
            # planes may hold different donor lists when a lease's share
            # rounds to zero), and transfers touching different physical
            # donors on EITHER end never fuse into one message. A leg is a
            # fabric message when either end is a donor, a host message
            # otherwise (a REMOTE-bound move that spills to HOST never
            # touches the fabric)
            src_name = self._donors[src_donor] if src_donor >= 0 else None

            def meter(lo, hi, dst, dst_name):
                if dst_tier == src_tier or hi <= lo:
                    return
                link = REMOTE if REMOTE in (src_tier, dst) else HOST
                self.meter.record(float(fills[lo:hi].sum()), link, hi - lo,
                                  group=(src_tier, src_name, dst, dst_name))

            # 3) acquire destination slots and scatter (metering per
            # destination donor group). A failure mid-placement (tier
            # exhausted past reclaim, or a transfer leg dying) rolls every
            # acquired slot back: the group's source rows stay
            # authoritative, so the caller sees the exception against an
            # unchanged page table and free lists.
            new_rows = []
            popped: List[Tuple[List[int], int]] = []
            try:
                if dst_tier == LOCAL:
                    dst_slots = [self._pop_free(self._free_local, LOCAL,
                                                len(group))
                                 for _ in group]
                    popped += [(self._free_local, s) for s in dst_slots]
                    with TraceAnnotation("aqua.tier.scatter",
                                         pages=len(group)):
                        self.local_pool = kv_ops.scatter_pages(
                            self.local_pool, staging,
                            jnp.asarray(dst_slots, jnp.int32))
                    new_rows = [(LOCAL, s, -1) for s in dst_slots]
                    meter(0, len(group), LOCAL, None)
                elif dst_tier == REMOTE:
                    placed = 0
                    for di, d in enumerate(self._donors):
                        if d == exclude_donor:
                            continue
                        free = self._remote_free.get(d, [])
                        take = min(len(free), len(group) - placed)
                        if take <= 0:
                            continue
                        dst_slots = [free.pop() for _ in range(take)]
                        popped += [(free, s) for s in dst_slots]
                        with TraceAnnotation("aqua.tier.scatter", pages=take):
                            self._remote_scatter(
                                d, dst_slots, staging[placed:placed + take])
                        new_rows += [(REMOTE, s, di) for s in dst_slots]
                        meter(placed, placed + take, REMOTE, d)
                        placed += take
                    if placed < len(group):      # remote full -> host fallback
                        rest = staging[placed:]
                        need = len(group) - placed
                        self._leg_guard(HOST, None, need)
                        dst_slots = [self._pop_free(self._free_host, HOST,
                                                    need)
                                     for _ in range(need)]
                        popped += [(self._free_host, s) for s in dst_slots]
                        with TraceAnnotation("aqua.tier.d2h", pages=need):
                            self.host_pool[np.asarray(dst_slots)] = \
                                np.asarray(rest)
                        new_rows += [(HOST, s, -1) for s in dst_slots]
                        meter(placed, len(group), HOST, None)
                else:
                    self._leg_guard(HOST, None, len(group))
                    dst_slots = [self._pop_free(self._free_host, HOST,
                                                len(group))
                                 for _ in group]
                    popped += [(self._free_host, s) for s in dst_slots]
                    with TraceAnnotation("aqua.tier.d2h", pages=len(group)):
                        self.host_pool[np.asarray(dst_slots)] = \
                            np.asarray(staging)
                    new_rows = [(HOST, s, -1) for s in dst_slots]
                    meter(0, len(group), HOST, None)
            except (MemoryError, AquaError):
                # every intentional failure class a placement can hit:
                # _pop_free exhaustion past reclaim (MemoryError) and
                # _leg_guard transfer faults / lease revocations (AquaError)
                for free_list, s in popped:
                    free_list.append(s)
                raise
            # 4) the whole group landed: only now do the source slots
            # return to their free lists and the rows repoint
            if src_tier == LOCAL:
                for s in slots:
                    self._free_local.append(int(s))
            elif src_tier == REMOTE:
                for s in slots:
                    self._remote_free[src_name].append(int(s))
            else:
                for s in slots:
                    self._free_host.append(int(s))
            for lp, row in zip(group, new_rows):
                self.page_table[lp] = row

    def _pop_free(self, free_list: List[int], tier: int, need: int) -> int:
        """Take one destination slot, or fail loudly: a bare IndexError from
        ``list.pop`` told the operator nothing about which tensor/tier ran dry
        (e.g. ``evict_remote`` onto an already-full host pool). Before
        failing, cached (refcount-0) pages in the tier are asked to yield."""
        if not free_list:
            self._try_reclaim(tier, need)
        if not free_list:
            raise MemoryError(
                f"{self.name}: {TIER_NAMES[tier]} tier exhausted while "
                f"migrating pages (needed {need} free slot(s))")
        return free_list.pop()

    # ------------------------------------------------------------------
    def tier_counts(self) -> Dict[str, int]:
        t = self.page_table[:, 0]
        out = {TIER_NAMES[k]: int((t == k).sum())
               for k in (LOCAL, REMOTE, HOST)}
        n_lost = int((t == LOST).sum())
        if n_lost:                    # only surfaced while a loss is live
            out["lost"] = n_lost
        return out

    @property
    def local_free(self) -> int:
        return len(self._free_local)

    @property
    def remote_free(self) -> int:
        return sum(len(v) for v in self._remote_free.values())
