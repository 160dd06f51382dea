"""A tiny cell for the CPU tests: a copy of the benchmark's code folders
and one small dense model, traffic mix and limit in a directory of its
own."""
import json
import shutil
from pathlib import Path

BENCH = Path(__file__).resolve().parents[1]

CONFIG = {
    "hidden_act": "silu", "hidden_size": 64, "intermediate_size": 128,
    "num_attention_heads": 4, "num_key_value_heads": 2, "head_dim": 16,
    "num_hidden_layers": 2, "vocab_size": 256, "qkv_bias": True,
    "tie_word_embeddings": True, "rope_theta": 10000.0,
    "rms_norm_eps": 1e-6, "max_position_embeddings": 512,
    "torch_dtype": "bfloat16",
    "bench": {"family": "dense_lm",
              "deployment": {"offload": "host", "lease_contexts": 4},
              "engine": {"max_running": 2, "max_seq": 128,
                         "scheduler": "cfs", "step_tokens": 32,
                         "slice_tokens": 4, "kv_page_tokens": 16,
                         "kv_host_pages": 512}}}

TRAFFIC = {"loop": "open",
           "arrivals": {"base_rate": 2.0, "period_s": 2, "spike_at_s": 0.5,
                        "spike_s": 0.5, "spike_factor": 4},
           "prompt": {"median": 24, "sigma": 0.5, "min": 4, "max": 60},
           "output": {"median": 8, "sigma": 0.5, "min": 2, "max": 16},
           "preroll_s": 1}

# the tiny model's served tokens sit within 0.002 of the reference's best;
# its fp8 control's reach 0.02 and more
LIMITS = {"worst_gap": 0.01, "sample_tokens": 32, "sample_requests": 3}


def make(dst: Path, *, chips: int = 1, offload: str = "host") -> Path:
    """Write the tiny cell ``tiny-cell`` under ``dst`` and return it."""
    dst = Path(dst)
    b = dst / "bench"
    for k in ("reference", "adapters", "metrics"):
        shutil.copytree(BENCH / k, b / k,
                        ignore=shutil.ignore_patterns("__pycache__"))
    for k in ("configs", "traffic", "limits"):
        (b / k).mkdir(parents=True, exist_ok=True)
    cfg = json.loads(json.dumps(CONFIG))
    cfg["bench"]["deployment"]["offload"] = offload
    (b / "configs" / "tiny.json").write_text(json.dumps(cfg))
    (b / "traffic" / "tiny.json").write_text(json.dumps(TRAFFIC))
    (b / "limits" / "tiny-cell.json").write_text(json.dumps(LIMITS))
    bench = json.loads((BENCH.parent / "BENCHMARK.json").read_text())
    bench["configs"] = [{"name": "tiny", "source": "tiny",
                         "file": "bench/configs/tiny.json", "reduced": [],
                         "why": "CPU test"}]
    bench["workloads"] = [{"name": "tiny-cell", "config": "tiny",
                           "traffic": "tiny", "chips": chips,
                           "why": "CPU test"}]
    for m in bench["end_to_end"] + bench["per_layer"]:
        m.pop("workloads", None)
    (dst / "BENCHMARK.json").write_text(json.dumps(bench))
    return dst
