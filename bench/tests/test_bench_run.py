"""Whole runs of the harness on the CPU: it refuses to measure without a
chip, and a tiny cell runs end to end and checks its served tokens."""
import json
import os
import shutil
import subprocess
import sys

import pytest

import harness
import spec
import tiny


def _run_cli(cwd, env_extra=None):
    env = dict(os.environ, JAX_PLATFORMS="cpu", **(env_extra or {}))
    return subprocess.run(
        [sys.executable, "bench/run.py", "--workload", "qwen05b-burst-host",
         "--seed", "2147483659", "--seconds", "1", "--trace", "0"],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=300)


def test_no_tpu_exits_nonzero_with_no_result():
    p = _run_cli(spec.ROOT)
    assert p.returncode != 0
    assert not any(line.startswith("{") for line in p.stdout.splitlines())
    assert "no TPU" in p.stderr


def test_benchmark_files_alone_do_not_run(tmp_path):
    shutil.copytree(spec.BENCH, tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copy(spec.ROOT / "BENCHMARK.json", tmp_path)
    p = _run_cli(tmp_path, {"PYTHONPATH": ""})
    assert p.returncode != 0
    assert not any(line.startswith("{") for line in p.stdout.splitlines())


def _main(root, capsys, *args, fault=None):
    rc = harness.main(["--workload", "tiny-cell", *args], root=root,
                      bench_dir=root / "bench", require_tpu=False,
                      fault=fault)
    out = capsys.readouterr().out.strip().splitlines()
    return rc, json.loads(out[-1])


@pytest.mark.parametrize("trace", [0, 1])
def test_tiny_cell_end_to_end(tmp_path, capsys, trace):
    root = tiny.make(tmp_path)
    rc, res = _main(root, capsys, "--seed", "3000000001", "--seconds", "3",
                    "--trace", str(trace))
    assert rc == 0 and res["correct"] is True
    assert list(res)[-1] == "compared"
    assert res["compared"]["worst_gap"]["value"] <= tiny.LIMITS["worst_gap"]
    # the warm-up left nothing to build inside the window
    assert res["compared"]["window_builds"]["value"] == 0
    assert res["attempted"] > 0 and res["failed"] == 0
    names = set(res["metrics"])
    if trace:
        wanted = {m["name"] for m in spec.metrics_of(
            spec.load_benchmark(root), "tiny-cell", "per_layer")}
        # counters always read; which step kinds a window holds depends
        # on how fast the host runs the tiny model, but it holds steps
        assert {"preempts_per_req", "tier_mb_per_s"} <= names <= wanted
        assert names & {"step_ms.decode", "step_ms.mixed"}
        assert "setup_s" not in names and "breakdown" in res
        assert res["device"]["window_s"] > 0
    else:
        assert names == {"tbt_p95_ms", "output_tokens_per_s", "setup_s"}
