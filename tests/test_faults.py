"""Fault-tolerant tier domain: transfer-leg fault injection with bounded
retry, dynamic lease shrinkage with live page migration, permanent donor
loss with degrade-to-host recompute recovery, allocation rollback, the
typed error hierarchy, and the full-state invariant auditor — deterministic
scenarios, a seeded chaos loop, and a hypothesis property test (skipped
when hypothesis is not installed).
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config, smoke_config
from repro.core import errors as errs
from repro.core.aqua_tensor import (HOST, LOCAL, LOST, REMOTE, AquaTensor,
                                    TransferMeter)
from repro.core.faults import FaultEvent, FaultInjector, InvariantAuditor
from repro.models import api
from repro.serving.engine import ServingEngine
from repro.serving.kv_cache import PagedStateRuntime

from _hypothesis_compat import given, settings, st

ARCH = "qwen1.5-0.5b"


def _tensor(**kw):
    args = dict(page_shape=(4,), local_slots=8, host_slots=8,
                dtype=jnp.float32, meter=TransferMeter())
    args.update(kw)
    return AquaTensor(**args)


# ---------------------------------------------------------------------------
# typed error hierarchy
# ---------------------------------------------------------------------------
def test_error_hierarchy():
    for sub in (errs.PageLossError, errs.LeaseRevokedError,
                errs.TransferFaultError, errs.SchedulingInvariantError,
                errs.InvariantViolation, errs.CapacityError,
                errs.CancelledError, errs.EngineCrashError):
        assert issubclass(sub, errs.AquaError)
        assert issubclass(sub, RuntimeError)
    # the engine re-exports SchedulingInvariantError (it moved to errors.py)
    from repro.serving.engine import SchedulingInvariantError
    assert SchedulingInvariantError is errs.SchedulingInvariantError
    e = errs.PageLossError("gone", plane="kv", pages=[3, 4])
    assert e.plane == "kv" and e.pages == (3, 4)
    v = errs.InvariantViolation(["a", "b"])
    assert v.violations == ("a", "b") and "a" in str(v)
    c = errs.CancelledError("gone", rid=7, reason="deadline")
    assert c.rid == 7 and c.reason == "deadline"


# ---------------------------------------------------------------------------
# allocation rollback (all-or-nothing across a failing multi-page alloc)
# ---------------------------------------------------------------------------
def test_allocate_rollback_when_tiers_exhaust_midway():
    t = _tensor(local_slots=3, host_slots=2)     # 5 physical slots total
    # a lease that shrank away leaves more logical ids than slots, so the
    # allocation runs out of slots mid-way rather than out of ids up front
    t.add_remote_lease("d0", 4)
    t.shrink_lease("d0", 4)
    before_local = len(t._free_local)
    before_host = len(t._free_host)
    with pytest.raises(MemoryError, match="all tiers full"):
        t.allocate(6)                            # fails on the 6th slot
    # every slot the failing call took is back on its free list
    assert len(t._free_local) == before_local
    assert len(t._free_host) == before_host
    assert (t.page_table[:, 0] == -1).all()
    assert (t.page_refs == 0).all()
    # the pool still works after the rollback
    lps = t.allocate(5)
    assert len(lps) == 5


@pytest.mark.parametrize("plane_idx", [0, 1, 2])
def test_ensure_capacity_rollback_at_each_plane_boundary(plane_idx):
    """Multi-plane ensure_capacity is all-or-nothing: exhaust the pool of
    plane ``plane_idx`` (kv + the two mamba state planes of a hybrid) so
    the per-step allocation fails there, and assert every page an EARLIER
    plane already took was handed back — no leak, no partial rows."""
    cfg = smoke_config(get_config("jamba-v0.1-52b"))
    kv = PagedStateRuntime(cfg, max_seq=64, page_tokens=8, max_running=2,
                           host_pages=0)
    planes = list(kv.planes.values())
    if plane_idx >= len(planes):
        pytest.skip(f"family has {len(planes)} planes")
    victim = planes[plane_idx]
    # drain the victim plane's LOCAL pool (its only tier: host_pages=0,
    # no lease), keeping one page so a 1-page request part-fits
    drained = victim.aqua.allocate(victim.aqua.local_free)
    auditor = InvariantAuditor()
    snap = {p.name: p.aqua.tier_counts() for p in planes}
    with pytest.raises(MemoryError):
        kv.ensure_capacity(7, 40)
    assert all(7 not in p.pages for p in planes), "partial rows leaked"
    assert {p.name: p.aqua.tier_counts() for p in planes} == snap
    victim.aqua.free(drained)
    # and the runtime still serves: the same request fits after the drain
    kv.ensure_capacity(7, 40)
    assert not auditor.check(kv)
    kv.release(7)


def test_make_writable_clone_rollback_frees_the_clone():
    """A CoW clone that spills off LOCAL (pool full) must be handed back
    instead of leaking on the spill tier — the block table keeps pointing
    at the shared original."""
    cfg = smoke_config(get_config(ARCH))
    kv = PagedStateRuntime(cfg, max_seq=64, page_tokens=8, max_running=2,
                           host_pages=64)
    toks = list(range(100, 109))                 # 9 tokens: one full page
    kv.adopt_prefix(1, toks)
    kv.ensure_capacity(1, 9)
    kv.register_prefix(1, 9)
    assert kv.adopt_prefix(2, toks) == 8         # page 0 now shared
    kv.ensure_capacity(2, 9)
    plane = kv.planes["kv"]
    filler = plane.aqua.allocate(plane.aqua.local_free)  # LOCAL now full
    before = plane.aqua.tier_counts()
    with pytest.raises(MemoryError):
        kv.make_writable(2, 0, 9)                # clone would spill to HOST
    assert plane.aqua.tier_counts() == before, "spilled clone leaked"
    plane.aqua.free(filler)
    kv.make_writable(2, 0, 9)                    # with room it clones fine
    assert kv.cow_copies > 0


# ---------------------------------------------------------------------------
# transient transfer-leg faults: bounded retry, backoff pricing
# ---------------------------------------------------------------------------
def test_leg_retry_converges_and_prices_backoff():
    faults = FaultInjector(seed=11, leg_fault_rate=0.8, max_consecutive=2)
    t = _tensor(faults=faults)
    lps = t.allocate(6)
    payload = jnp.arange(6 * 4, dtype=jnp.float32).reshape(6, 4)
    t.write_local(lps, payload)
    clean = _tensor()
    c = clean.allocate(6)
    clean.write_local(c, payload)
    for tensor, pages in ((t, lps), (clean, c)):
        tensor.offload(pages, prefer=HOST)
        tensor.ensure_local(pages)
    # faulted run: same data back, retries counted and priced
    np.testing.assert_array_equal(np.asarray(t.read(lps)),
                                  np.asarray(clean.read(c)))
    assert t.meter.retries_host > 0
    assert faults.leg_faults_injected == t.meter.retries_host
    assert t.meter.sim_time > clean.meter.sim_time
    # retries are priced but never counted as messages
    assert t.meter.messages_host == clean.meter.messages_host


def test_leg_guard_raises_past_retry_budget():
    # a leg that fails 10x consecutively exceeds the 2-retry budget before
    # the injector's forced success can kick in
    faults = FaultInjector(seed=0, leg_fault_rate=1.0, max_consecutive=10,
                           max_leg_retries=2)
    t = _tensor(faults=faults)
    lps = t.allocate(2)
    t.write_local(lps, jnp.zeros((2, 4), jnp.float32))
    with pytest.raises(errs.TransferFaultError) as ei:
        t.offload(lps, prefer=HOST)
    assert ei.value.attempts == 2 and ei.value.tier == HOST


def test_fault_injection_is_seed_deterministic():
    def draws(seed):
        f = FaultInjector(seed=seed, leg_fault_rate=0.5)
        return [f.leg_fails(REMOTE, "d0") for _ in range(32)]

    assert draws(7) == draws(7)
    assert draws(7) != draws(8)
    # the consecutive-failure cap guarantees convergence for ANY seed
    f = FaultInjector(seed=3, leg_fault_rate=1.0, max_consecutive=3)
    run = [f.leg_fails(HOST, None) for _ in range(20)]
    assert max(len(s) for s in
               "".join("T" if x else "F" for x in run).split("F")) <= 3


# ---------------------------------------------------------------------------
# dynamic lease shrinkage: live migration off the shrinking donor
# ---------------------------------------------------------------------------
def test_shrink_lease_migrates_excluding_the_shrinking_donor():
    t = _tensor(local_slots=4, host_slots=16)
    t.add_remote_lease("d0", 8)
    t.add_remote_lease("d1", 8)
    lps = t.allocate(8, prefer=REMOTE)           # fills d0 entirely
    assert (t.page_table[lps, 0] == REMOTE).all()
    assert (t.page_table[lps, 2] == 0).all()
    moved = t.shrink_lease("d0", 4)              # reclaim the TOP 4 slots
    assert moved == 4
    assert t.remote_capacity["d0"] == 4
    # migrated pages went to d1 (or host), never back onto d0's low slots
    relocated = lps[np.asarray(t.page_table[lps, 2] != 0)
                    | np.asarray(t.page_table[lps, 0] != REMOTE)]
    assert len(relocated) == 4
    on_d0 = [lp for lp in lps
             if t.page_table[lp, 0] == REMOTE and t.page_table[lp, 2] == 0]
    assert all(t.page_table[lp, 1] < 4 for lp in on_d0)
    # shrink to zero drops the lease entirely
    t.shrink_lease("d0", 4)
    assert "d0" not in t.remote_pools and "d0" not in t.remote_capacity
    with pytest.raises(errs.LeaseRevokedError):
        t.shrink_lease("d0", 1)


def test_shrink_preserves_payload_bits():
    t = _tensor(local_slots=8, host_slots=16)
    t.add_remote_lease("d0", 8)
    t.add_remote_lease("d1", 8)
    rng = np.random.default_rng(5)
    payload = jnp.asarray(rng.standard_normal((8, 4)), jnp.float32)
    lps = t.allocate(8)
    t.write_local(lps, payload)
    t.offload(lps, prefer=REMOTE)
    t.shrink_lease("d0", 8)
    t.ensure_local(lps)
    np.testing.assert_array_equal(np.asarray(t.read(lps)),
                                  np.asarray(payload))


# ---------------------------------------------------------------------------
# permanent donor loss: LOST tier, PageLossError surfaces
# ---------------------------------------------------------------------------
def test_fail_donor_marks_lost_and_every_touch_raises():
    faults = FaultInjector(seed=0)
    t = _tensor(faults=faults)
    t.add_remote_lease("d0", 8)
    lps = t.allocate(4)
    t.write_local(lps, jnp.ones((4, 4), jnp.float32))
    t.offload(lps, prefer=REMOTE)
    lost = t.fail_donor("d0")
    assert sorted(int(x) for x in lost) == sorted(int(x) for x in lps)
    assert (t.page_table[lps, 0] == LOST).all()
    assert t.tier_counts()["lost"] == 4
    assert faults.donor_lost("d0")
    for op in (lambda: t.read(lps), lambda: t.ensure_local(lps),
               lambda: t.block_tables([list(lps)], pad_to=8),
               lambda: t.offload(lps, prefer=HOST)):
        with pytest.raises(errs.PageLossError):
            op()
    # a lost donor can never lease again
    with pytest.raises(errs.LeaseRevokedError):
        t.add_remote_lease("d0", 8)
    # recovery path: freeing the lost pages clears them for reuse
    t.free(lps)
    assert (t.page_table[lps, 0] == -1).all()
    assert "lost" not in t.tier_counts()


# ---------------------------------------------------------------------------
# invariant auditor: green on healthy state, loud on seeded corruption
# ---------------------------------------------------------------------------
def test_auditor_green_then_detects_seeded_corruption():
    cfg = smoke_config(get_config(ARCH))
    kv = PagedStateRuntime(cfg, max_seq=64, page_tokens=8, max_running=2)
    kv.ensure_capacity(1, 20)
    kv.ensure_capacity(2, 12)
    auditor = InvariantAuditor()
    assert auditor.check(kv) == []
    auditor.audit(kv)                            # green: must not raise
    plane = kv.planes["kv"]
    lp = int(plane.pages[1][0][0])
    plane.aqua.page_refs[lp] += 1                # phantom reference
    assert auditor.check(kv)
    with pytest.raises(errs.InvariantViolation):
        auditor.audit(kv)
    plane.aqua.page_refs[lp] -= 1
    assert auditor.check(kv) == []
    # corrupt the free list: a slot both free and occupied
    plane.aqua._free_local.append(int(plane.aqua.page_table[lp, 1]))
    assert any("free" in v or "occupancy" in v for v in auditor.check(kv))


# ---------------------------------------------------------------------------
# engine end-to-end: donor loss -> recompute, shrink -> migration,
# bit-identical outputs either way, auditor green after every step
# ---------------------------------------------------------------------------
def _engine_prompts(cfg, n=3, length=8, seed=0):
    rng = np.random.default_rng(seed)
    return [list(map(int, rng.integers(0, cfg.vocab_size, length)))
            for _ in range(n)]


def _build_engine(cfg, params, prompts, faults=None, audit=False):
    kv = PagedStateRuntime(cfg, max_seq=64, page_tokens=8, max_running=1,
                           prefix_sharing=False)
    eng = ServingEngine(cfg, params, max_running=2, max_seq=64,
                        scheduler="cfs", slice_tokens=3, offload_tier=REMOTE,
                        kv=kv, faults=faults, audit=audit, prefetch=False)
    eng.pager.add_remote_lease("d0", 2 ** 24)
    for p in prompts:
        eng.submit(p, 6)
    return eng


def test_engine_recovers_from_donor_loss_and_lease_shrink_bit_identical():
    cfg = smoke_config(get_config(ARCH))
    params = api.init_params(jax.random.PRNGKey(0), cfg)
    prompts = _engine_prompts(cfg)

    eng0 = _build_engine(cfg, params, prompts)
    eng0.run(500)
    base = {tuple(r.prompt_tokens): r.generated for r in eng0.finished}
    assert len(base) == len(prompts)

    # probe: find the first step after which pages sit on the donor
    probe = _build_engine(cfg, params, prompts)
    hit = None
    for _ in range(200):
        if not (probe.waiting or probe.running):
            break
        probe.step()
        if probe.kv.stats()["tiers"].get("remote", 0) > 0:
            hit = probe.metrics.steps
            break
    assert hit is not None, "CFS under page pressure must park remotely"

    # donor loss at that step: victims recompute from the prompt
    fi = FaultInjector(seed=3, events=[
        FaultEvent(kind="donor_loss", donor="d0", at_step=hit)])
    eng = _build_engine(cfg, params, prompts, faults=fi, audit=True)
    m = eng.run(500)
    got = {tuple(r.prompt_tokens): r.generated for r in eng.finished}
    assert m.donor_losses == 1 and m.recomputes > 0 and m.recovered_rids
    assert got == base, "recomputed requests must regenerate bit-identically"
    assert eng.auditor.audits == m.steps
    # capacity re-planned: the budget contracted to the surviving tiers
    assert (np.asarray(eng.sched.page_budget)
            <= np.asarray(eng.kv.page_budget)).all()

    # lease shrink at the same step: pages live-migrate, nothing recomputes
    fi2 = FaultInjector(seed=5, events=[
        FaultEvent(kind="lease_shrink", donor="d0", frac=1.0, at_step=hit)])
    eng2 = _build_engine(cfg, params, prompts, faults=fi2, audit=True)
    m2 = eng2.run(500)
    got2 = {tuple(r.prompt_tokens): r.generated for r in eng2.finished}
    assert m2.lease_shrinks == 1 and m2.migrated_pages > 0
    assert m2.recomputes == 0
    assert got2 == base, "migrated requests must keep their exact KV"


def test_engine_transient_leg_faults_priced_not_fatal():
    cfg = smoke_config(get_config(ARCH))
    params = api.init_params(jax.random.PRNGKey(0), cfg)
    prompts = _engine_prompts(cfg, seed=1)
    eng0 = _build_engine(cfg, params, prompts)
    m0 = eng0.run(500)
    base = {tuple(r.prompt_tokens): r.generated for r in eng0.finished}
    fi = FaultInjector(seed=9, leg_fault_rate=0.3)
    eng = _build_engine(cfg, params, prompts, faults=fi, audit=True)
    m = eng.run(500)
    got = {tuple(r.prompt_tokens): r.generated for r in eng.finished}
    assert got == base
    assert m.leg_retries > 0
    assert m.sim_time > m0.sim_time              # the retries cost time


# ---------------------------------------------------------------------------
# simulator: fault schedules on the analytic clock
# ---------------------------------------------------------------------------
def _sim34(faults=None, **kw):
    from repro.core.perfmodel import A100_NVLINK, ModelCost
    from repro.core.simulator import ServingSimulator
    cfg = get_config("aqua-codellama-34b")
    wb = cfg.param_count() * 2
    args = dict(weight_bytes=wb, kv_capacity_bytes=80e9 - wb - 2e9,
                scheduler="cfs", offload_tier="fabric", max_running=4,
                step_tokens=256, faults=faults)
    args.update(kw)
    return ServingSimulator(A100_NVLINK, ModelCost.from_config(cfg), **args)


def _sim_requests(n=16, seed=2):
    from repro.core.simulator import Request
    rng = np.random.default_rng(seed)
    arr = np.cumsum(rng.exponential(1.0 / 80.0, n))
    return [Request(i, float(arr[i]), int(rng.integers(300, 800)),
                    int(rng.integers(40, 120))) for i in range(n)]


def test_simulator_capacity_error_is_typed():
    with pytest.raises(errs.CapacityError):
        _sim34(kv_capacity_bytes=0.0).run(_sim_requests(1))


def test_simulator_fault_events_and_retry_pricing():
    def run(faults):
        sim = _sim34(faults=faults)
        res = sim.run(_sim_requests())
        assert all(r.finish is not None for r in res.requests)
        return sim, res

    sim0, res0 = run(None)
    t0 = max(r.finish for r in res0.requests)

    fi = FaultInjector(seed=4, leg_fault_rate=0.2, events=[
        FaultEvent(kind="donor_loss", donor="d0", frac=1.0,
                   at_time=t0 * 0.3),
        FaultEvent(kind="lease_shrink", donor="d1", frac=0.5,
                   at_time=t0 * 0.5)])
    sim1, res1 = run(fi)
    assert sim1.leg_retries > 0
    assert sim1.donor_losses == 1 and sim1.lease_shrinks == 1
    assert len(fi.events_fired) == 2
    # every request still completes, later than the fault-free run
    t1 = max(r.finish for r in res1.requests)
    assert t1 > t0
    # at least one parked context was reset and recomputed
    assert any(r.recovered for r in res1.requests)


# ---------------------------------------------------------------------------
# chaos: random op interleavings against the auditor
# ---------------------------------------------------------------------------
def _chaos_round(seed: int, n_ops: int = 80):
    rng = np.random.default_rng(seed)
    cfg = smoke_config(get_config(ARCH))
    faults = FaultInjector(seed=seed, leg_fault_rate=0.05)
    kv = PagedStateRuntime(cfg, max_seq=64, page_tokens=8, max_running=2)
    kv.attach_faults(faults)
    page_bytes = kv.planes["kv"].aqua.page_bytes
    kv.add_remote_lease("d0", 64 * page_bytes)
    kv.add_remote_lease("d1", 64 * page_bytes)
    auditor = InvariantAuditor()
    # three prompt families drive the radix cache: new requests adopt a
    # family prefix (sometimes with a diverged tail) and register their
    # growth, so releases leave CACHED pages behind and later growth
    # triggers revival, eviction and cold-first demotion mid-chaos
    fam = [list(map(int, rng.integers(0, 50, 60))) for _ in range(3)]
    live: dict = {}                              # rid -> resident tokens
    prompts: dict = {}                           # rid -> token identity
    parked: set = set()
    next_rid = 0
    for _ in range(n_ops):
        op = rng.choice(["grow", "park", "restore", "release",
                         "shrink", "fail"],
                        p=[0.35, 0.2, 0.2, 0.15, 0.07, 0.03])
        try:
            if op == "grow":
                rid = (int(rng.choice(list(live))) if live and rng.random() < 0.5
                       else next_rid)
                if rid == next_rid:
                    next_rid += 1
                    live[rid] = 0
                    base = fam[int(rng.integers(len(fam)))]
                    if rng.random() < 0.4:       # mid-prompt divergence
                        cut = int(rng.integers(8, 60))
                        prompts[rid] = base[:cut] + [t + 1 for t in base[cut:]]
                    else:
                        prompts[rid] = list(base)
                    live[rid] = kv.adopt_prefix(rid, prompts[rid])
                if rid in parked:
                    kv.restore(rid)
                    parked.discard(rid)
                tok = min(live[rid] + int(rng.integers(1, 12)), 60)
                kv.ensure_capacity(rid, tok)
                live[rid] = tok
                kv.register_prefix(rid, tok)
            elif op == "park" and live:
                rid = int(rng.choice([r for r in live if r not in parked]
                                     or list(live)))
                if rid not in parked and live[rid] > 0:
                    kv.park(rid, live[rid],
                            prefer=REMOTE if rng.random() < 0.7 else HOST)
                    parked.add(rid)
            elif op == "restore" and parked:
                rid = int(rng.choice(sorted(parked)))
                if kv.can_restore(rid):
                    kv.restore(rid)
                    parked.discard(rid)
            elif op == "release" and live:
                rid = int(rng.choice(sorted(live)))
                kv.release(rid)
                live.pop(rid)
                prompts.pop(rid, None)
                parked.discard(rid)
            elif op == "shrink":
                donor = str(rng.choice(["d0", "d1"]))
                if any(donor in p.aqua.remote_pools
                       for p in kv.planes.values()):
                    kv.shrink_lease(donor, float(rng.uniform(0.2, 0.8)))
            elif op == "fail":
                donor = str(rng.choice(["d0", "d1"]))
                victims = kv.fail_donor(donor)
                for rid in victims:              # recovery: drop the victims
                    kv.release(rid)
                    live.pop(rid, None)
                    prompts.pop(rid, None)
                    parked.discard(rid)
        except (MemoryError, errs.LeaseRevokedError, errs.PageLossError):
            pass                                 # legal under chaos
        violations = auditor.check(kv)
        assert not violations, (seed, op, violations)
    for rid in list(live):
        kv.release(rid)
    assert auditor.check(kv) == []


def test_chaos_interleavings_keep_every_invariant():
    for seed in (0, 1, 2):
        _chaos_round(seed)


@given(seed=st.integers(min_value=0, max_value=2 ** 16))
@settings(max_examples=15, deadline=None)
def test_chaos_property_random_seeds(seed):
    _chaos_round(seed, n_ops=30)


# ---------------------------------------------------------------------------
# engine-level chaos: random lifecycle-op interleavings (step / submit /
# cancel-at-any-state / drain+resume / snapshot-restore-swap) against the
# full-state auditor after EVERY op
# ---------------------------------------------------------------------------
def _engine_chaos_round(seed: int, cfg, params, n_ops: int = 30):
    rng = np.random.default_rng(seed)
    eng = ServingEngine(cfg, params, max_running=2, max_seq=64,
                        scheduler="cfs", slice_tokens=4, offload_tier=HOST,
                        step_tokens=8, prefetch=False)
    auditor = InvariantAuditor()
    for i in range(n_ops):
        op = rng.choice(["step", "submit", "cancel", "drain", "snapshot"],
                        p=[0.45, 0.25, 0.15, 0.05, 0.10])
        if op == "submit":
            n = int(rng.integers(4, 16))
            toks = list(map(int, 1 + rng.integers(0, cfg.vocab_size - 1, n)))
            eng.submit(toks, int(rng.integers(1, 6)))
        elif op == "cancel":
            live = [r.rid for r in eng.waiting + eng.running]
            if live:
                eng.cancel(int(rng.choice(live)))
        elif op == "drain":
            eng.drain()
            eng.resume()
        elif op == "snapshot":
            eng = ServingEngine.restore(cfg, params, eng.snapshot())
            auditor = InvariantAuditor()     # the mesh check is per-engine
        else:
            eng.step()
        violations = auditor.check(eng.kv, engine=eng)
        assert not violations, (seed, i, op, violations)
    eng.run(500)
    assert not (eng.waiting or eng.running)
    assert auditor.check(eng.kv, engine=eng) == []


def test_engine_chaos_lifecycle_ops_keep_every_invariant():
    cfg = smoke_config(get_config(ARCH))
    params = api.init_params(jax.random.PRNGKey(0), cfg)
    for seed in (0, 1, 2):
        _engine_chaos_round(seed, cfg, params)


# ---------------------------------------------------------------------------
# mesh: requests surviving donor loss via migration stay bit-identical
# across the real-collective and single-device backends (slow tier)
# ---------------------------------------------------------------------------
@pytest.mark.slow
def test_mesh_shrink_migration_bit_identical_vs_single_device():
    from repro.distributed.mesh_tiers import MeshTierDomain
    if not MeshTierDomain.available():
        pytest.skip("mesh tiers need a single-process mesh with >= 2 devices")
    cfg = smoke_config(get_config(ARCH))
    params = api.init_params(jax.random.PRNGKey(0), cfg)
    prompts = _engine_prompts(cfg, seed=2)

    def serve(mesh, faults=None):
        kv = PagedStateRuntime(cfg, max_seq=64, page_tokens=8, max_running=1,
                               prefix_sharing=False, mesh=mesh)
        eng = ServingEngine(cfg, params, max_running=2, max_seq=64,
                            scheduler="cfs", slice_tokens=3,
                            offload_tier=REMOTE, kv=kv, faults=faults,
                            audit=True, prefetch=False)
        eng.pager.add_remote_lease("d0", 2 ** 24)
        eng.pager.add_remote_lease("d1", 2 ** 24)
        for p in prompts:
            eng.submit(p, 6)
        m = eng.run(500)
        return {tuple(r.prompt_tokens): r.generated
                for r in eng.finished}, m

    base, _ = serve(None)
    fi = FaultInjector(seed=1, events=[
        FaultEvent(kind="lease_shrink", donor="d0", frac=1.0, at_step=4)])
    mesh_got, m = serve(MeshTierDomain(), faults=fi)
    assert mesh_got == base
    assert m.lease_shrinks == 1
