"""Mesh-real memory tiers: donor leases as PEER-device slabs, one collective
per (tier, donor) leg, mesh-vs-single-device bit-exactness per family,
donor reclaim mid-flight, re-lease bookkeeping, and clock calibration.

The CI box forces a 4-way host-platform device mesh (conftest.py sets
``--xla_force_host_platform_device_count=4``), so every test here runs the
REAL collective path — ``shard_map`` + ``ppermute`` — just on host devices.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config, smoke_config
from repro.core.aqua_tensor import (HOST, LOCAL, REMOTE, AquaTensor,
                                    TransferMeter)
from repro.core.perfmodel import TPU_V5E, fit_link_model
from repro.distributed.mesh_tiers import MeshTierDomain
from repro.models import api, lm
from repro.serving.kv_cache import PagedStateRuntime
from repro.serving.scheduler import bucket_tokens

pytestmark = pytest.mark.skipif(
    not MeshTierDomain.available(),
    reason="mesh tiers need a single-process mesh with >= 2 devices")


def _tensor(dom, *, slots=8, page=(4, 6)):
    a = AquaTensor(page_shape=page, local_slots=slots,
                   host_slots=slots, dtype=jnp.float32,
                   meter=TransferMeter(), mesh=dom)
    a.add_remote_lease("d0", slots)
    return a


# ---------------------------------------------------------------------------
# the lease is a real peer slab; transfers are bit-exact round trips
# ---------------------------------------------------------------------------
def test_donor_pool_resident_on_peer_device():
    dom = MeshTierDomain()
    a = _tensor(dom)
    pool = a.remote_pools["d0"]
    dst = dom.donor_device("d0")
    assert dst != 0                       # device 0 serves, never donates
    by_dev = {s.device: s.index for s in pool.addressable_shards}
    donor_dev = dom.devices[dst]
    assert donor_dev in by_dev            # the slab really lives on the peer
    assert by_dev[donor_dev][0] == slice(dst, dst + 1)


def test_offload_restore_round_trip_bit_exact():
    dom = MeshTierDomain()
    a = _tensor(dom)
    rng = np.random.default_rng(0)
    lps = a.allocate(5)
    payload = jnp.asarray(rng.standard_normal((5,) + a.page_shape), jnp.float32)
    a.write_local(lps, payload)

    a.offload(lps, prefer=REMOTE)
    assert (a.page_table[lps, 0] == REMOTE).all()
    np.testing.assert_array_equal(np.asarray(a.read(lps)),
                                  np.asarray(payload))
    a.ensure_local(lps)
    assert (a.page_table[lps, 0] == LOCAL).all()
    np.testing.assert_array_equal(np.asarray(a.read(lps)),
                                  np.asarray(payload))


def test_one_collective_per_tier_donor_leg():
    """Each leg of a tier flip is exactly ONE wire message: the domain's
    collective counter and the TransferMeter's priced message counter move
    in lockstep, one per (tier, donor) leg however many pages move."""
    dom = MeshTierDomain()
    a = _tensor(dom)
    lps = a.allocate(6)
    a.write_local(lps, jnp.ones((6,) + a.page_shape, jnp.float32))

    c0, m0 = dom.collectives, a.meter.messages_fabric
    a.offload(lps, prefer=REMOTE)         # push leg: 6 pages, 1 ppermute
    assert dom.collectives - c0 == 1
    assert a.meter.messages_fabric - m0 == 1

    c0, m0 = dom.collectives, a.meter.messages_fabric
    a.ensure_local(lps)                   # pull leg: 6 pages, 1 ppermute
    assert dom.collectives - c0 == 1
    assert a.meter.messages_fabric - m0 == 1


def test_two_donors_one_collective_each():
    dom = MeshTierDomain()
    a = AquaTensor(page_shape=(4, 6), local_slots=8,
                   host_slots=8, dtype=jnp.float32, meter=TransferMeter(),
                   mesh=dom)
    a.add_remote_lease("d0", 4)
    a.add_remote_lease("d1", 4)
    lps = a.allocate(6)                   # spills across both donor pools
    a.write_local(lps, jnp.full((6,) + a.page_shape, 2.0, jnp.float32))
    c0 = dom.collectives
    a.offload(lps, prefer=REMOTE)
    donors = set(a.page_table[lps, 2].tolist())
    assert donors == {0, 1}               # really split across the peers
    assert dom.collectives - c0 == 2      # one push per donor leg
    c0 = dom.collectives
    a.ensure_local(lps)
    assert dom.collectives - c0 == 2      # one pull per donor leg
    np.testing.assert_array_equal(
        np.asarray(a.read(lps)),
        np.full((6,) + a.page_shape, 2.0, np.float32))


def test_spilled_remote_leg_is_a_host_message():
    """A REMOTE-bound park that overflows its lease spills the rest to
    HOST. The spilled leg never touches the fabric: it is priced as a host
    message and issues no collective."""
    dom = MeshTierDomain()
    a = AquaTensor(page_shape=(4, 6), local_slots=8, host_slots=8,
                   dtype=jnp.float32, meter=TransferMeter(), mesh=dom)
    a.add_remote_lease("d0", 2)
    lps = a.allocate(4)
    a.write_local(lps, jnp.ones((4,) + a.page_shape, jnp.float32))
    c0, f0, h0 = dom.collectives, a.meter.messages_fabric, a.meter.messages_host
    a.offload(lps, prefer=REMOTE)
    assert sorted(a.page_table[lps, 0].tolist()) == [REMOTE, REMOTE, HOST, HOST]
    assert dom.collectives - c0 == 1
    assert a.meter.messages_fabric - f0 == 1
    assert a.meter.messages_host - h0 == 1


def test_engine_collectives_equal_fabric_messages_every_step():
    """Serving under CFS with contexts parked in peer HBM: each step's
    collectives equal its priced fabric messages, also in the steps where a
    park's allocation demotes cached prefix pages (a demotion is its own
    migration, never folded into the park's message)."""
    from repro.launch.serve import build_engine
    mesh = MeshTierDomain()
    eng = build_engine("qwen1.5-0.5b", smoke=True, mesh=mesh, max_running=2,
                       max_seq=128, slice_tokens=3, step_tokens=32)
    rng = np.random.default_rng(0)
    for n in (23, 37, 41, 55, 60, 71, 80, 90):
        eng.submit(list(map(int, rng.integers(0, eng.cfg.vocab_size, n))), 8)
    meter = eng.pager.meter
    for _ in range(500):
        if not (eng.waiting or eng.running):
            break
        c0, m0 = mesh.collectives, meter.messages_fabric
        eng.step()
        assert (mesh.collectives - c0 == meter.messages_fabric - m0), \
            f"step {eng.metrics.steps}"
    assert len(eng.finished) == 8
    assert eng.metrics.preemptions > 0
    assert eng.kv.stats()["cache"]["demotions"] > 0


# ---------------------------------------------------------------------------
# mesh vs single-device: bit-identical logits + pool contents per family
# ---------------------------------------------------------------------------
def _roundtrip_logits(cfg, params, prompt, chunks, mesh, decode_steps=2):
    """Chunked prefill + decode, parking REMOTE at every boundary; returns
    the logits arrays and the request's final owned-page payloads."""
    kv = PagedStateRuntime(cfg, max_seq=64, page_tokens=8, max_running=2,
                           mesh=mesh)
    kv.add_remote_lease("d0", 1 << 24)
    pad = kv.pps + 3
    logs = []
    pos = 0
    for c in chunks:
        kv.ensure_capacity(0, pos + c)
        bt = kv.block_tables_prefill(0, pad_to=pad)
        toks = np.zeros((1, bucket_tokens(c)), np.int32)
        toks[0, :c] = prompt[pos:pos + c]
        lg, kv.pools = api.prefill_chunk_paged(
            params, cfg, jnp.asarray(toks), kv.pools, bt,
            jnp.int32(pos), jnp.int32(c - 1), read_pps=kv.pps)
        pos += c
        kv.park(0, pos, prefer=REMOTE)
        kv.restore(0)
    logs.append(np.asarray(lg))
    out = int(np.argmax(logs[-1][0]))
    for t in range(decode_steps):
        ctx = len(prompt) + t + 1
        kv.ensure_capacity(0, ctx)
        bts = kv.block_tables([0, None])
        lg, kv.pools = api.decode_step_paged(
            params, cfg, kv.pools, bts,
            jnp.asarray([out, 0], jnp.int32),
            jnp.asarray([ctx - 1, 0], jnp.int32))
        logs.append(np.asarray(lg[0]))
        out = int(np.argmax(lg[0]))
        kv.park(0, ctx, prefer=REMOTE)
        kv.restore(0)
    pages = {name: np.asarray(pl.aqua.read(
        [lp for row in pl.pages[0] for lp in row]))
        for name, pl in kv.planes.items()}
    return logs, pages


@pytest.mark.parametrize("arch", lm.PAGED_FAMILY_ARCHS)
def test_mesh_matches_single_device_bit_exact(arch):
    """Every family (attention, MLA, hybrid SSM, RWKV6): a run whose pages
    bounce through a REAL peer-device donor slab at every chunk and decode
    boundary produces bit-identical logits AND page payloads to the
    single-device backend."""
    cfg = smoke_config(get_config(arch))
    params = api.init_params(jax.random.PRNGKey(0), cfg)
    rng = np.random.default_rng(1)
    prompt = list(map(int, rng.integers(0, cfg.vocab_size, 17)))
    base_logs, base_pages = _roundtrip_logits(cfg, params, prompt, [7, 10],
                                              None)
    mesh_logs, mesh_pages = _roundtrip_logits(cfg, params, prompt, [7, 10],
                                              MeshTierDomain())
    for a, b in zip(base_logs, mesh_logs):
        np.testing.assert_array_equal(a, b)
    assert set(base_pages) == set(mesh_pages)
    for name in base_pages:
        np.testing.assert_array_equal(base_pages[name], mesh_pages[name],
                                      err_msg=name)


# ---------------------------------------------------------------------------
# donor reclaim mid-flight
# ---------------------------------------------------------------------------
def test_donor_reclaim_mid_flight_evacuates_to_host_bit_exact():
    """The coordinator reclaims the donor while a request is parked on its
    slab: pages evacuate donor -> serving -> host (one pull collective),
    the lease drops, and the restored run continues bit-exact."""
    cfg = smoke_config(get_config("qwen1.5-0.5b"))
    params = api.init_params(jax.random.PRNGKey(0), cfg)
    rng = np.random.default_rng(2)
    prompt = list(map(int, rng.integers(0, cfg.vocab_size, 17)))
    base_logs, _ = _roundtrip_logits(cfg, params, prompt, [7, 10], None)

    dom = MeshTierDomain()
    kv = PagedStateRuntime(cfg, max_seq=64, page_tokens=8, max_running=2,
                           mesh=dom)
    kv.add_remote_lease("d0", 1 << 24)
    pad = kv.pps + 3
    pos = 0
    for c in (7, 10):
        kv.ensure_capacity(0, pos + c)
        bt = kv.block_tables_prefill(0, pad_to=pad)
        toks = np.zeros((1, bucket_tokens(c)), np.int32)
        toks[0, :c] = prompt[pos:pos + c]
        lg, kv.pools = api.prefill_chunk_paged(
            params, cfg, jnp.asarray(toks), kv.pools, bt,
            jnp.int32(pos), jnp.int32(c - 1), read_pps=kv.pps)
        pos += c
    kv.park(0, pos, prefer=REMOTE)
    plane = kv.planes["kv"]
    assert (plane.aqua.page_table[:, 0] == REMOTE).any()
    c0 = dom.collectives
    moved = kv.evict_remote("d0")         # mid-flight reclaim
    assert moved > 0
    assert dom.collectives - c0 >= 1      # the evacuation pull really ran
    assert not plane.aqua.remote_pools    # lease dropped
    assert (plane.aqua.page_table[:, 0] != REMOTE).all()
    kv.restore(0)                         # restore now comes from HOST

    out = int(np.argmax(np.asarray(lg)[0]))
    logs = [np.asarray(lg)]
    for t in range(2):
        ctx = len(prompt) + t + 1
        kv.ensure_capacity(0, ctx)
        bts = kv.block_tables([0, None])
        lg, kv.pools = api.decode_step_paged(
            params, cfg, kv.pools, bts,
            jnp.asarray([out, 0], jnp.int32),
            jnp.asarray([ctx - 1, 0], jnp.int32))
        logs.append(np.asarray(lg[0]))
        out = int(np.argmax(lg[0]))
    for a, b in zip(base_logs, logs):
        np.testing.assert_array_equal(a, b)


# ---------------------------------------------------------------------------
# re-lease bookkeeping (regression: duplicate _donors entries) — no mesh
# ---------------------------------------------------------------------------
def test_donor_re_lease_reuses_bookkeeping_index():
    """An evicted donor that re-leases must reuse its ``_donors`` entry: a
    duplicate append would leave stale donor_idx values resolving to the
    new pool and split one physical donor across two identities."""
    a = AquaTensor(page_shape=(2, 4), local_slots=8,
                   host_slots=16, dtype=jnp.float32, meter=TransferMeter())
    a.add_remote_lease("d0", 4)
    lps = a.allocate(3)
    payload = jnp.arange(3 * 8, dtype=jnp.float32).reshape((3, 2, 4))
    a.write_local(lps, payload)
    a.offload(lps, prefer=REMOTE)
    assert a.evict_remote("d0") == 3      # all victims captured
    a.add_remote_lease("d0", 4)           # re-lease
    assert a._donors.count("d0") == 1     # no duplicate identity
    a.ensure_local(lps)                   # evacuated pages sit on HOST
    a.offload(lps, prefer=REMOTE)
    assert (a.page_table[lps, 0] == REMOTE).all()
    assert (a.page_table[lps, 2] == a._donors.index("d0")).all()
    np.testing.assert_array_equal(np.asarray(a.read(lps)),
                                  np.asarray(payload))
    # eviction after the re-lease still captures every victim
    assert a.evict_remote("d0") == 3
    np.testing.assert_array_equal(np.asarray(a.read(lps)),
                                  np.asarray(payload))


def test_re_leased_donor_keeps_its_device():
    dom = MeshTierDomain()
    a = _tensor(dom, slots=4)
    dev = dom.donor_device("d0")
    lps = a.allocate(2)
    a.write_local(lps, jnp.ones((2,) + a.page_shape, jnp.float32))
    a.offload(lps, prefer=REMOTE)
    a.evict_remote("d0")
    a.add_remote_lease("d0", 4)
    assert dom.donor_device("d0") == dev  # stable across the reclaim cycle


# ---------------------------------------------------------------------------
# clock calibration
# ---------------------------------------------------------------------------
def test_warm_legs_record_fabric_samples():
    dom = MeshTierDomain()
    a = _tensor(dom)
    lps = a.allocate(4)
    a.write_local(lps, jnp.ones((4,) + a.page_shape, jnp.float32))
    for _ in range(3):                    # same key: first is compile, skipped
        a.offload(lps, prefer=REMOTE)
        a.ensure_local(lps)
    assert len(dom.samples["fabric"]) >= 4
    assert all(b > 0 and t > 0 for b, t in dom.samples["fabric"])


def test_legs_are_named_programs_and_spans(tmp_path):
    """A profile finds each fabric leg by name: the push and pull programs
    are the XLA modules ``jit_aqua_mesh_push`` / ``jit_aqua_mesh_pull``,
    and each leg is a host span ``aqua.mesh.push`` / ``aqua.mesh.pull``
    inside the tier move's ``aqua.tier.scatter`` / ``aqua.tier.gather``."""
    from jax.profiler import ProfileData
    dom = MeshTierDomain()
    a = _tensor(dom)
    lps = a.allocate(3)
    a.write_local(lps, jnp.ones((3,) + a.page_shape, jnp.float32))
    with jax.profiler.trace(str(tmp_path)):
        a.offload(lps, prefer=REMOTE)
        a.ensure_local(lps)
    pd = ProfileData.from_file(
        str(next(tmp_path.glob("plugins/profile/*/*.xplane.pb"))))
    modules, host = set(), {}
    for plane in pd.planes:
        for line in plane.lines:
            for e in line.events:
                host.setdefault(e.name, []).append(
                    (e.start_ns, e.start_ns + e.duration_ns))
                modules |= {str(v) for k, v in e.stats if k == "hlo_module"}
    assert {"jit_aqua_mesh_push", "jit_aqua_mesh_pull"} <= modules
    for leg, move in (("push", "scatter"), ("pull", "gather")):
        [(s, e)] = host[f"aqua.mesh.{leg}"]
        assert any(ms <= s and e <= me
                   for ms, me in host[f"aqua.tier.{move}"])


def test_fit_link_model_recovers_known_link():
    alpha, bw = 5e-6, 100e9
    sizes = [1 << 16, 1 << 18, 1 << 20, 1 << 22]
    samples = [(float(s), alpha + s / bw) for s in sizes]
    link = fit_link_model(samples, "fit")
    assert link is not None
    assert link.latency == pytest.approx(alpha, rel=1e-6)
    assert link.peak_bw == pytest.approx(bw, rel=1e-6)
    assert fit_link_model(samples[:1], "fit") is None     # underdetermined
    assert fit_link_model([samples[0]] * 4, "fit") is None


def test_calibrated_profile_replaces_fabric_link():
    dom = MeshTierDomain()
    dom.samples["fabric"] = [(float(s), 1e-5 + s / 50e9)
                             for s in (1 << 14, 1 << 16, 1 << 18, 1 << 20)]
    hw = dom.calibrated_profile(TPU_V5E)
    assert hw is not TPU_V5E
    assert hw.name.endswith("-calibrated")
    assert hw.fabric.peak_bw == pytest.approx(50e9, rel=1e-3)
    # not enough samples -> identity (callers detect no-op with `is`)
    dom2 = MeshTierDomain()
    assert dom2.calibrated_profile(TPU_V5E) is TPU_V5E


def test_engine_calibrate_clock_installs_fitted_profile():
    """``ServingEngine.calibrate_clock`` swaps the measured-fit profile into
    the engine AND the meter, so every subsequent priced flip uses the
    calibrated fabric link; without samples it is a no-op."""
    cfg = smoke_config(get_config("qwen1.5-0.5b"))
    params = api.init_params(jax.random.PRNGKey(0), cfg)
    dom = MeshTierDomain()
    from repro.serving.engine import ServingEngine
    eng = ServingEngine(cfg, params, max_running=2, max_seq=64, mesh=dom)
    assert eng.calibrate_clock() is False         # no samples yet
    dom.samples["fabric"] = [(float(s), 2e-5 + s / 25e9)
                             for s in (1 << 14, 1 << 16, 1 << 18, 1 << 20)]
    assert eng.calibrate_clock() is True
    assert eng.hw.name.endswith("-calibrated")
    assert eng.pager.meter.hw is eng.hw
    assert eng.hw.fabric.peak_bw == pytest.approx(25e9, rel=1e-3)
    assert eng.calibrate_clock() is True          # refit stays installable


# ---------------------------------------------------------------------------
# guards
# ---------------------------------------------------------------------------
def test_single_device_domain_rejected():
    with pytest.raises(ValueError, match="2 devices"):
        MeshTierDomain(devices=[jax.devices()[0]])
