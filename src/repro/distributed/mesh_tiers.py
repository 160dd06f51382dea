"""Mesh-real memory tiers: donor page pools resident on PEER mesh devices.

This is the step from *simulated* AQUA to AQUA: the REMOTE tier stops being
an analytic fiction (a device-local array priced as if it crossed a fabric)
and becomes a slab of a peer device's memory on a real ``jax`` device mesh.

``MeshTierDomain`` owns a 1-D mesh over the scale-up domain (the paper's
8-GPU NVLink clique; here every addressable jax device — on the CPU CI box a
forced host-platform device mesh, on real hardware the ICI/NVLink ring).
Device 0 is the SERVING chip; every other device is a potential donor.
A donor lease (``AquaTensor.add_remote_lease``) allocates an actual pool
sharded so the donor's slab lives on the donor device, and the two transfer
legs lower to collectives:

  push (offload / park)    stage the coalesced page batch on the serving
                           shard, ONE ``jax.lax.ppermute`` to the donor
                           shard, scatter into the donor's pool slab
  pull (ensure_local)      gather the requested slots on the donor shard,
                           ONE ``ppermute`` back to the serving shard

Both legs run inside a single ``shard_map`` program per (bucket, pool-shape)
key, named ``aqua_mesh_push`` / ``aqua_mesh_pull`` (host spans
``aqua.mesh.push`` / ``aqua.mesh.pull`` around each leg), so each (plane,
tier, donor) leg of a tier flip is exactly one collective message on the
wire — the physical counterpart of the ``TransferMeter`` coalescing
invariant (``collectives`` counts them, tests assert one per leg). Page
counts pad to power-of-two buckets so the jit cache stays flat however many
pages a request parks.

Every warm leg is wall-clocked (``block_until_ready``; the first call per
compiled key is compile time and is skipped), and the samples feed
``perfmodel.fit_link_model`` / ``calibrate_profile`` — the analytic clock
(``page_flip_time``, ``TransferMeter`` pricing) is thereby calibrated
against MEASURED mesh transfers instead of datasheet constants
(``ServingEngine.calibrate_clock``).

Host staging exists only on the HOST leg (``AquaTensor`` keeps its numpy
host pool); fabric legs never bounce through host memory.
"""
from __future__ import annotations

import time
from typing import Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.profiler import TraceAnnotation
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.core.errors import LeaseRevokedError


def _bucket(n: int) -> int:
    """Power-of-two shape bucket for a page-batch length (min 1)."""
    b = 1
    while b < n:
        b *= 2
    return b


class MeshTierDomain:
    """A scale-up domain: one serving device plus donor peers on a 1-D mesh.

    The domain is shared by every plane's :class:`~repro.core.aqua_tensor.
    AquaTensor` of a serving runtime: it owns the donor name -> device
    mapping (stable across evict/re-lease cycles), the compiled transfer
    legs, the collective counter, and the measured-transfer sample log.
    """

    def __init__(self, devices: Optional[Sequence] = None, *,
                 axis: str = "fabric"):
        """Build the domain over ``devices`` (default: every jax device).

        Raises:
            ValueError: fewer than 2 devices (no peer to donate HBM) or a
                multi-process mesh (single-controller only — the serving
                process must address every donor shard directly).
        """
        devices = list(devices) if devices is not None else list(jax.devices())
        if jax.process_count() > 1:
            raise ValueError("mesh tiers need a single-process mesh: the "
                             "serving process must address donor shards "
                             "directly")
        if len(devices) < 2:
            raise ValueError(f"mesh tiers need >= 2 devices (got "
                             f"{len(devices)}): a donor lease is a slab of a "
                             "PEER device's memory")
        self.axis = axis
        self.devices = devices
        self.n_dev = len(devices)
        self.mesh = Mesh(np.array(devices), (axis,))
        self._donor_dev: Dict[str, int] = {}
        # optional core/faults.FaultInjector shared with the AquaTensors of
        # this domain: the domain double-checks lost donors at its own
        # boundary (a collective addressed to a dead peer must never be
        # issued, whatever the caller's bookkeeping says)
        self.faults = None
        # one entry per physical collective issued (one per (plane, tier,
        # donor) leg) — the wire-message counterpart of the TransferMeter's
        # priced messages
        self.collectives = 0
        # measured (message_bytes, seconds) per warm fabric leg
        self.samples: Dict[str, List[Tuple[float, float]]] = {"fabric": []}
        self._push_cache: Dict[tuple, object] = {}
        self._pull_cache: Dict[tuple, object] = {}
        self._zero_cache: Dict[tuple, list] = {}
        self._warm: set = set()

    # ------------------------------------------------------------------
    @staticmethod
    def available(min_devices: int = 2) -> bool:
        """True when a domain can be built here — the tier-1 skip guard
        (single process, at least one peer device)."""
        try:
            return (jax.process_count() == 1
                    and len(jax.devices()) >= min_devices)
        except RuntimeError:
            return False

    def attach_faults(self, faults) -> None:
        """Share a ``FaultInjector`` with the domain (lease-boundary checks
        on every collective leg; the AquaTensors consult the same injector
        for transient-leg retries BEFORE reaching these entry points)."""
        self.faults = faults

    def _guard_donor(self, donor: str, op: str) -> None:
        if self.faults is not None and self.faults.donor_lost(donor):
            raise LeaseRevokedError(
                f"mesh {op} addressed lost donor {donor} — its device left "
                "the domain", donor=donor)

    def donor_device(self, donor: str) -> int:
        """Mesh index of the device backing ``donor``'s leases. Assigned on
        first use, cycling over the peers (device 0 serves), and STABLE for
        the donor's lifetime — an evicted donor that re-leases lands on the
        same device."""
        if donor not in self._donor_dev:
            self._donor_dev[donor] = 1 + len(self._donor_dev) % (self.n_dev - 1)
        return self._donor_dev[donor]

    # ------------------------------------------------------------------
    # pool + transfer legs (called by AquaTensor's remote helpers)
    # ------------------------------------------------------------------
    def alloc_pool(self, donor: str, slots: int, page_shape: Tuple[int, ...],
                   dtype) -> jax.Array:
        """A donor lease as a REAL slab: a zeroed ``(n_dev, slots+1, *page)``
        array sharded over the fabric axis, so row ``donor_device(donor)``
        — the only row ever read or written — is resident on the donor
        device. Slot ``slots`` is the scatter scratch row bucket padding
        targets."""
        self._guard_donor(donor, "lease")
        self.donor_device(donor)              # pin the mapping at lease time
        shape = (self.n_dev, slots + 1) + tuple(page_shape)
        sharding = NamedSharding(self.mesh, P(self.axis))
        return jax.device_put(jnp.zeros(shape, dtype), sharding)

    def push(self, pool: jax.Array, donor: str, slots: np.ndarray,
             data: jnp.ndarray) -> jax.Array:
        """Offload leg: move ``data`` (a coalesced page batch on the serving
        device) into ``pool``'s donor slab at ``slots`` — ONE ppermute.
        Returns the updated pool."""
        self._guard_donor(donor, "push")
        dst = self.donor_device(donor)
        n = len(slots)
        with TraceAnnotation("aqua.mesh.push", donor=donor, pages=n):
            S = pool.shape[1] - 1
            page_shape = tuple(pool.shape[2:])
            dtype = pool.dtype
            b = _bucket(n)
            slots = np.asarray(slots, np.int32)
            data = jnp.asarray(data, dtype)
            if b > n:                         # pad to the bucket: scratch row
                slots = np.concatenate([slots, np.full(b - n, S, np.int32)])
                data = jnp.concatenate(
                    [data, jnp.zeros((b - n,) + page_shape, dtype)], axis=0)
            fn, key = self._push_fn(dst, b, S, page_shape, str(dtype))
            stage = self._stage(data, b, page_shape, dtype)
            out, dt = self._timed(fn, pool, stage, jnp.asarray(slots))
        self._account(key, b * int(np.prod(page_shape)) * dtype.itemsize, dt)
        return out

    def pull(self, pool: jax.Array, donor: str,
             slots: np.ndarray) -> jnp.ndarray:
        """Restore leg: gather ``slots`` from the donor slab and move them to
        the serving device — ONE ppermute. Returns the ``(n, *page)`` staging
        batch committed to the serving device."""
        self._guard_donor(donor, "pull")
        src = self.donor_device(donor)
        n = len(slots)
        with TraceAnnotation("aqua.mesh.pull", donor=donor, pages=n):
            S = pool.shape[1] - 1
            page_shape = tuple(pool.shape[2:])
            b = _bucket(n)
            slots = np.asarray(slots, np.int32)
            if b > n:                         # padded gathers are discarded
                slots = np.concatenate([slots, np.zeros(b - n, np.int32)])
            fn, key = self._pull_fn(src, b, S, page_shape, str(pool.dtype))
            out, dt = self._timed(fn, pool, jnp.asarray(slots))
        self._account(key, b * int(np.prod(page_shape)) * pool.dtype.itemsize,
                      dt)
        for shard in out.addressable_shards:
            if shard.device == self.devices[0]:
                return shard.data[0, :n]
        raise RuntimeError("serving device shard missing from pull output")

    # ------------------------------------------------------------------
    def _timed(self, fn, *args):
        t0 = time.perf_counter()
        out = fn(*args)
        out.block_until_ready()
        return out, time.perf_counter() - t0

    def _account(self, key: tuple, nbytes: int, dt: float):
        self.collectives += 1
        if key in self._warm:                 # first call per key = compile
            self.samples["fabric"].append((float(nbytes), float(dt)))
        else:
            self._warm.add(key)

    def _push_fn(self, dst: int, bucket: int, S: int,
                 page_shape: Tuple[int, ...], dtype_str: str):
        key = ("push", dst, bucket, S, page_shape, dtype_str)
        fn = self._push_cache.get(key)
        if fn is None:
            axis = self.axis

            def aqua_mesh_push(pool_s, stage_s, slots):
                # pool_s (1, S+1, *page), stage_s (1, bucket, *page): this
                # device's shards; slots replicated. One collective moves the
                # staged batch serving -> donor; only the donor keeps the
                # scattered update (everyone else's shard passes through).
                moved = jax.lax.ppermute(stage_s, axis, [(0, dst)])
                upd = pool_s[0].at[slots].set(moved[0])
                keep = jax.lax.axis_index(axis) == dst
                return jnp.where(keep, upd, pool_s[0])[None]

            fn = jax.jit(jax.shard_map(
                aqua_mesh_push, mesh=self.mesh,
                in_specs=(P(axis), P(axis), P()),
                out_specs=P(axis), check_vma=False))
            self._push_cache[key] = fn
        return fn, key

    def _pull_fn(self, src: int, bucket: int, S: int,
                 page_shape: Tuple[int, ...], dtype_str: str):
        key = ("pull", src, bucket, S, page_shape, dtype_str)
        fn = self._pull_cache.get(key)
        if fn is None:
            axis = self.axis

            def aqua_mesh_pull(pool_s, slots):
                # gather is cheap on every shard; only the donor's rows are
                # real, and one collective moves them donor -> serving
                # (non-addressed shards receive zeros per ppermute semantics)
                stage = pool_s[0][slots]
                return jax.lax.ppermute(stage[None], axis, [(src, 0)])

            fn = jax.jit(jax.shard_map(
                aqua_mesh_pull, mesh=self.mesh, in_specs=(P(axis), P()),
                out_specs=P(axis), check_vma=False))
            self._pull_cache[key] = fn
        return fn, key

    def _stage(self, data: jnp.ndarray, bucket: int,
               page_shape: Tuple[int, ...], dtype) -> jax.Array:
        """Assemble the push operand: the real batch as the serving shard,
        cached zero shards for every peer (building the global array from
        per-device pieces keeps the staging traffic at ONE message — a
        replicated operand would broadcast the payload to all peers)."""
        shape = (self.n_dev, bucket) + page_shape
        sharding = NamedSharding(self.mesh, P(self.axis))
        zkey = (bucket, page_shape, str(jnp.dtype(dtype)))
        zeros = self._zero_cache.get(zkey)
        if zeros is None:
            zeros = [jax.device_put(jnp.zeros((1, bucket) + page_shape, dtype),
                                    d) for d in self.devices[1:]]
            self._zero_cache[zkey] = zeros
        first = jax.device_put(data[None], self.devices[0])
        return jax.make_array_from_single_device_arrays(
            shape, sharding, [first] + zeros)

    # ------------------------------------------------------------------
    def calibrated_profile(self, hw, *, min_samples: int = 4):
        """A copy of ``hw`` whose fabric link is least-squares fitted to the
        measured push/pull samples (``perfmodel.calibrate_profile``); ``hw``
        itself when there are not yet enough samples to fit."""
        from repro.core.perfmodel import calibrate_profile
        return calibrate_profile(hw, fabric_samples=self.samples["fabric"],
                                 min_samples=min_samples)
