"""Trace reduction: device busy and idle time, kernel time, top operations
and idle gaps labelled by the benchmark's host spans — on hand-made
intervals, and on a small trace recorded on one TPU v5e."""
from pathlib import Path

import pytest

import xplane

DATA = Path(__file__).resolve().parent / "data"


def test_op_names():
    assert xplane.op_name("%paged_mixed_attention_pool.9 = bf16[8] "
                          "custom-call(s32[8] %a)") == \
        "paged_mixed_attention_pool"
    assert xplane.op_name("%fusion.12 = f32[1] fusion(%x)") == "fusion"
    assert xplane.op_name("%copy-start.1 = (s32[8]) copy-start(%q)") == \
        "copy-start"
    assert xplane.op_name("while") == "while"


def test_reduce_by_hand():
    dev = xplane.Device(0, modules=[(10, 40), (30, 60), (80, 90)],
                        ops=[("while", 10, 60), ("paged_attn", 12, 30),
                             ("paged_attn", 32, 50), ("fusion", 80, 90)])
    spans = [("bench.window", 0, 100), ("bench.step", 5, 62),
             ("bench.wait", 62, 79), ("bench.step", 79, 95)]
    r = xplane.reduce({0: dev}, spans)
    assert r.window == (0, 100)
    assert r.busy_ns[0] == 50 + 10           # [10, 60) and [80, 90)
    assert r.kernel_s("paged_attn") == pytest.approx(36e-9)
    assert r.kernel_s("absent") is None
    assert [n for n, _ in r.top_ops()] == ["paged_attn", "fusion"]
    # idle: [0,10) in a step, [60,80) mostly waiting, [90,100) in a step
    assert sorted(r.gaps) == sorted([("bench.step", 10), ("bench.wait", 20),
                                     ("bench.step", 10)])
    assert r.longest_gaps(1) == [["bench.wait", 2e-8]]
    assert r.busy_ns[0] + sum(ns for _, ns in r.gaps) == 100


def test_recorded_chip_trace():
    path = next(DATA.glob("*.xplane.pb"))
    r = xplane.read(path)
    assert r.window_s > 0
    assert 0 < r.busy_s(0) <= r.window_s
    kernel = r.kernel_s("paged_mixed_attention_pool")
    assert kernel and kernel <= r.busy_s(0)
    top = r.top_ops()
    assert 0 < len(top) <= 10
    assert [s for _, s in top] == sorted((s for _, s in top), reverse=True)
    assert not {n for n, _ in top} & xplane.CONTAINERS
    gaps = r.longest_gaps(10)
    assert gaps and all(n.startswith("bench.") for n, _ in gaps)
    idle = sum(ns for _, ns in r.gaps) / 1e9
    assert idle + r.busy_s(0) == pytest.approx(r.window_s, rel=1e-6)
