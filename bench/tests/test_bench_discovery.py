"""Everything a cell needs is found by name, and a new configuration,
traffic mix or metric is taken up by adding its file alone."""
import json
import re
import shutil

import harness
import spec

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")


def test_every_cell_resolves():
    bench = spec.load_benchmark()
    for cell in bench["workloads"]:
        conf = spec.config(bench, cell["config"])
        assert conf["bench"]["family"]
        assert (spec.BENCH / "traffic" / f"{cell['traffic']}.json").is_file()
        lim = spec.limits(cell["name"])
        assert lim["worst_gap"] > 0 and lim["sample_tokens"] > 0
        spec.module("reference", conf["bench"]["family"])
        spec.module("adapters", conf["bench"]["family"])
        for m in spec.metrics_of(bench, cell["name"], "per_layer"):
            assert callable(spec.reader(m["name"]))


def test_benchmark_file_keeps_its_shape():
    bench = spec.load_benchmark()
    assert set(bench) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    names = [c["name"] for c in bench["configs"]]
    used = {w["config"] for w in bench["workloads"]}
    assert set(names) == used and len(set(names)) == len(names)
    cells = [w["name"] for w in bench["workloads"]]
    assert len(set(cells)) == len(cells)
    assert len({(w["config"], w["traffic"]) for w in bench["workloads"]}) \
        == len(cells)
    assert sum(w["chips"] == 4 for w in bench["workloads"]) <= max(
        1, len(cells) // 2)
    metrics = bench["end_to_end"] + bench["per_layer"]
    assert len({m["name"] for m in metrics}) == len(metrics)
    for n in names + cells + [m["name"] for m in metrics]:
        assert NAME.match(n), n
    for m in metrics:
        assert UNIT.match(m["unit"]) and m["better"] in ("lower", "higher")
        for c in m.get("workloads", []):
            assert c in cells
    for m in bench["end_to_end"]:
        assert 0.01 <= m["bound"] <= 0.25
    e2e = {m["name"] for m in bench["end_to_end"]}
    assert "setup_s" in e2e
    for m in bench["per_layer"]:
        assert m["moves"] in e2e and "\n" not in m["layer"]
        assert (spec.BENCH / "metrics" / f"{m['name']}.py").is_file()
    # a full check of 24 cells fits its time
    assert 2 + 14 * 24 * (bench["run_seconds"] + 60) + 24 * 180 + 1200 \
        <= 43200


def test_a_new_cell_is_found_without_an_edit(tmp_path):
    root = tmp_path / "checkout"
    shutil.copytree(spec.BENCH, root / "bench",
                    ignore=shutil.ignore_patterns("__pycache__", "tests"))
    shutil.copy(spec.ROOT / "BENCHMARK.json", root / "BENCHMARK.json")
    b = root / "bench"
    conf = json.loads((b / "configs" / "qwen1.5-0.5b.json").read_text())
    conf["num_hidden_layers"] = 2
    (b / "configs" / "qwen-two-layers.json").write_text(json.dumps(conf))
    (b / "traffic" / "steady.json").write_text(json.dumps({
        "loop": "open", "arrivals": {"base_rate": 1.0},
        "prompt": {"median": 64, "sigma": 0.1, "min": 8, "max": 128},
        "output": {"median": 16, "sigma": 0.1, "min": 4, "max": 32}}))
    (b / "limits" / "qwen2l-steady.json").write_text(json.dumps(
        {"worst_gap": 0.1, "sample_tokens": 64, "sample_requests": 4}))
    (b / "metrics" / "requests_due.py").write_text(
        "def read(run):\n    return float(run.due_in_window())\n")
    bench = json.loads((root / "BENCHMARK.json").read_text())
    bench["configs"].append({"name": "qwen-two-layers", "source": "x",
                             "file": "bench/configs/qwen-two-layers.json",
                             "reduced": ["num_hidden_layers"], "why": "t"})
    bench["workloads"].append({"name": "qwen2l-steady",
                               "config": "qwen-two-layers",
                               "traffic": "steady", "chips": 1, "why": "t"})
    bench["per_layer"].append({"name": "requests_due", "unit": "req",
                               "better": "higher",
                               "source": "program_counter",
                               "layer": "front end", "moves": "setup_s",
                               "workloads": ["qwen2l-steady"]})
    (root / "BENCHMARK.json").write_text(json.dumps(bench))

    cell = harness.Cell("qwen2l-steady", 3, root=root, bench_dir=b,
                        require_tpu=False)
    assert cell.dims.L == 2 and cell.traffic["name"] == "steady"
    assert cell.limits["worst_gap"] == 0.1
    wanted = [m["name"] for m in spec.metrics_of(cell.bench, "qwen2l-steady",
                                                 "per_layer")]
    assert "requests_due" in wanted and "tier_mb_per_s" not in wanted
    assert spec.reader("requests_due", b)(
        harness.Run(cell.cell, cell.conf, cell.dims, None,
                    [harness.stats.Record(0.5)], [], 0.0, 1.0)) == 1.0


def test_weights_fit_the_program_for_every_configuration():
    """The benchmark's weights, re-nested by the adapter, have exactly the
    program's parameter tree, shapes and dtypes (checked on shapes only)."""
    import jax
    import jax.numpy as jnp
    from repro.models import api
    bench = spec.load_benchmark()
    for c in bench["configs"]:
        conf = spec.config(bench, c["name"])
        ref = spec.module("reference", conf["bench"]["family"])
        ad = spec.module("adapters", conf["bench"]["family"])
        model = ad.model_config(c["name"], conf)
        w = {k: jax.ShapeDtypeStruct(s, jnp.bfloat16)
             for k, s in ref.weight_shapes(conf).items()}
        got = jax.eval_shape(lambda w: ad.program_params(w, conf), w)
        want = jax.eval_shape(lambda k: api.init_params(k, model),
                              jax.ShapeDtypeStruct((2,), jnp.uint32))
        assert jax.tree.structure(got) == jax.tree.structure(want)
        for a, b in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
            assert (a.shape, a.dtype) == (b.shape, b.dtype)
