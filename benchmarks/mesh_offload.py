"""Mesh-tier offload benchmark: peer-HBM donor legs vs host staging.

Times the two restore paths of the paged runtime on a REAL device mesh
(on the CI box a forced 4-way host-platform mesh; on hardware the scale-up
domain itself):

  * remote  — pages parked on a donor device's slab, restored by ONE
              ``ppermute`` collective per leg (``distributed/mesh_tiers.py``)
  * host    — pages parked in host DRAM, restored over the (priced) PCIe
              host link

Reported per page-batch size:

  * the ANALYTIC clock (``TransferMeter`` pricing, what the simulator and
    every BENCH trajectory reports) — the headline remote-beats-host
    restore ratio lives here, on the paper's datasheet link constants;
  * the MEASURED wall-clock of each warm collective leg (compile call
    skipped), which feeds ``perfmodel.fit_link_model``;
  * the calibration loop closed: the relative error of the datasheet
    fabric clock vs the measured legs, against the error of the
    CALIBRATED clock (``MeshTierDomain.calibrated_profile``) on the same
    samples — calibration should collapse the error by construction.

Wall-clock keys are prefixed ``wall_`` and excluded from the CI perf gate
(host-device collectives on a shared CI box are not a perf surface); the
analytic keys are the gated trajectory.

Writes ``BENCH_mesh_offload.json`` next to the repo root.

    PYTHONPATH=src python -m benchmarks.mesh_offload
"""
from __future__ import annotations

import json
import os
import time
from typing import Dict

# the mesh needs peers: force a multi-device host platform BEFORE jax init
_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = (
        _flags + " --xla_force_host_platform_device_count=4").strip()

import numpy as np

PAGE_SHAPE = (8, 512)                     # 16 KiB f32 pages
BATCHES = (4, 8, 16, 32)
REPEATS = 5


def _median(xs):
    return float(np.median(np.asarray(xs, np.float64)))


def _tensor(mesh):
    import jax.numpy as jnp

    from repro.core.aqua_tensor import AquaTensor, TransferMeter
    a = AquaTensor(page_shape=PAGE_SHAPE, local_slots=128,
                   host_slots=128, dtype=jnp.float32, meter=TransferMeter(),
                   name="bench", mesh=mesh)
    a.add_remote_lease("donor0", 64)
    return a


def _time_leg(fn) -> float:
    t0 = time.perf_counter()
    fn()
    return time.perf_counter() - t0


def measure() -> Dict:
    import jax
    import jax.numpy as jnp

    from repro.core.aqua_tensor import HOST, REMOTE
    from repro.core.perfmodel import TPU_V5E
    from repro.distributed.mesh_tiers import MeshTierDomain

    if not MeshTierDomain.available():
        raise SystemExit("mesh_offload needs a single-process multi-device "
                         "mesh (set --xla_force_host_platform_device_count)")
    dom = MeshTierDomain()
    a = _tensor(dom)
    rng = np.random.default_rng(0)
    hw = a.meter.hw

    out: Dict = {"page_bytes": a.page_bytes, "n_devices": dom.n_dev,
                 "batches": {}}
    for n in BATCHES:
        lps = a.allocate(n)
        data = jnp.asarray(rng.standard_normal((n,) + PAGE_SHAPE),
                           jnp.float32)
        a.write_local(lps, data)
        nbytes = n * a.page_bytes

        legs = {("remote", "park"): lambda: a.offload(lps, prefer=REMOTE),
                ("remote", "restore"): lambda: a.ensure_local(lps),
                ("host", "park"): lambda: a.offload(lps, prefer=HOST),
                ("host", "restore"): lambda: a.ensure_local(lps)}
        wall = {k: [] for k in legs}
        analytic = {}
        for i in range(REPEATS + 1):
            for key, fn in legs.items():
                t_sim0 = a.meter.sim_time
                dt = _time_leg(fn)
                if i > 0:                 # iteration 0 pays compile
                    wall[key].append(dt)
                analytic[key] = a.meter.sim_time - t_sim0
        roundtrip = np.asarray(a.read(lps))
        assert np.array_equal(roundtrip, np.asarray(data)), "corrupt restore"
        cell = {"pages": n, "message_bytes": nbytes}
        for (tier, leg), ts in wall.items():
            cell[f"analytic_{tier}_{leg}_s"] = float(analytic[(tier, leg)])
            cell[f"wall_{tier}_{leg}_s"] = _median(ts)
        cell["analytic_restore_speedup_x"] = (
            analytic[("host", "restore")] / analytic[("remote", "restore")])
        out["batches"][f"p{n:03d}"] = cell
        a.free(lps)

    # ------------------------------------------------------------------
    # calibration: the measured warm legs refit the fabric link; the
    # calibrated clock should track the measurements far better than the
    # datasheet constants do
    cal = dom.calibrated_profile(hw)
    calibrated = cal is not hw
    err_data, err_cal = [], []
    for cell in out["batches"].values():
        b = cell["message_bytes"]
        meas = _median([cell["wall_remote_park_s"],
                        cell["wall_remote_restore_s"]])
        err_data.append(abs(hw.fabric.time(b, 1) - meas) / meas)
        if calibrated:
            err_cal.append(abs(cal.fabric.time(b, 1) - meas) / meas)
    out["calibration"] = {
        "n_fabric_samples": len(dom.samples["fabric"]),
        "calibrated": bool(calibrated),
        "fabric_bw_datasheet_gbps": hw.fabric.peak_bw / 1e9,
        "fabric_bw_calibrated_gbps":
            (cal.fabric.peak_bw / 1e9) if calibrated else None,
        "fabric_latency_calibrated_us":
            (cal.fabric.latency * 1e6) if calibrated else None,
        "wall_clock_rel_error_datasheet": _median(err_data),
        "wall_clock_rel_error_calibrated":
            _median(err_cal) if err_cal else None,
    }
    big = out["batches"][f"p{max(BATCHES):03d}"]
    out["derived"] = {
        "remote_beats_host_restore":
            bool(big["analytic_remote_restore_s"]
                 < big["analytic_host_restore_s"]),
        "analytic_restore_speedup_x": big["analytic_restore_speedup_x"],
        "one_collective_per_leg":
            bool(dom.collectives == 2 * (REPEATS + 1) * len(BATCHES)),
        "calibration_tracks_measurement":
            bool(calibrated
                 and out["calibration"]["wall_clock_rel_error_calibrated"]
                 < out["calibration"]["wall_clock_rel_error_datasheet"]),
    }
    return out


def run(m: Dict | None = None):
    m = m or measure()
    rows = []
    for key, cell in m["batches"].items():
        for k, v in cell.items():
            if k.startswith("analytic"):
                rows.append((f"mesh_offload/{key}/{k}", float(v), ""))
    for k, v in m["derived"].items():
        rows.append((f"mesh_offload/{k}", float(v), "peer-HBM vs host"))
    return rows


def main():
    m = measure()
    out = os.path.join(os.path.dirname(__file__), "..",
                       "BENCH_mesh_offload.json")
    with open(out, "w") as f:
        json.dump(m, f, indent=2, sort_keys=True)
    print(f"# wrote {os.path.normpath(out)}")
    print("name,value,derived")
    for name, val, derived in run(m):
        print(f"{name},{val:.6g},{derived}")


if __name__ == "__main__":
    main()
