"""Trace reduction by program and by span: idle time split by the innermost
span, device operations attributed to the program that ran them, and the
new per-layer metrics read from it — on hand-made intervals, on traces
recorded on one TPU v5e, and on a trace of the engine serving on the CPU."""
from pathlib import Path

import jax
import numpy as np
import pytest

import harness
import spans
import spec

DATA = Path(__file__).resolve().parent / "data"
CHIP_TRACE = DATA / "qwen05b-burst-host-2s.xplane.pb"
SPANS_TRACE = DATA / "spans" / "qwen05b-burst-host-spans-2s.xplane.pb"


def _hand_made() -> spans.Trace:
    """Device 0 runs a mixed step, a gather and a decode step; the host is
    in nested spans (bench.step > aqua.step > aqua.step.place >
    aqua.kv.park) during the idle stretches between them."""
    modules = {0: [("aqua_step_mixed", 10, 40), ("aqua_gather_pages", 50, 55),
                   ("aqua_step_decode", 70, 90)]}
    ops = {0: [("while", 10, 40), ("paged_attn", 12, 30), ("copy", 30, 38),
               ("copy", 51, 54), ("fusion", 70, 88)]}
    S = spans.Span
    host = [S("bench.window", 0, 100), S("bench.step", 5, 62),
            S("aqua.step", 6, 61, {"step_num": 0, "kind": "mixed"}),
            S("aqua.step.plan", 6, 9), S("aqua.step.place", 41, 60),
            S("aqua.kv.park", 44, 58, {"rid": 7, "pages": 3,
                                       "cause": "preempt"}),
            S("aqua.kv.park", 59, 60, {"rid": 8, "pages": 0,
                                       "cause": "preempt"}),
            S("bench.wait", 62, 69), S("bench.step", 69, 95)]
    return spans.reduce(modules, ops, host)


def test_program_names():
    assert spans.program_name("jit_aqua_step_mixed(8655483430299075520)") \
        == "aqua_step_mixed"
    assert spans.program_name("jit__argmax(45)") == "_argmax"
    assert spans.program_name("jit_aqua_gather_pages") == "aqua_gather_pages"


def test_gaps_take_the_innermost_span():
    t = _hand_made()
    assert t.window == (0, 100)
    # [0,10): 5 ns in no span beats 3 in the plan; [40,50): the park holds
    # 6 of 10; [55,70): the wait holds 7 of 15; [90,100): 5 ns each in the
    # step and in no span, and the shorter span wins the tie
    assert t.gaps == [("host", 10), ("aqua.kv.park", 10), ("bench.wait", 15),
                      ("bench.step", 10)]
    assert t.idle == {"host": 10, "aqua.step.plan": 3, "bench.step": 8,
                      "aqua.step": 3, "aqua.step.place": 4,
                      "aqua.kv.park": 10, "bench.wait": 7}
    assert t.longest_gaps(1) == [["bench.wait", 1.5e-8]]


def test_ops_belong_to_the_program_that_ran_them():
    t = _hand_made()
    assert t.op_ns[0] == {("aqua_step_mixed", "paged_attn"): 18,
                          ("aqua_step_mixed", "copy"): 8,
                          ("aqua_gather_pages", "copy"): 3,
                          ("aqua_step_decode", "fusion"): 18}
    assert t.op_in("copy", ["aqua_step_mixed", "aqua_step_decode"]) == 8
    assert t.kind_ns() == {"mixed": 30, "decode": 20}
    assert t.executions("aqua_step_mixed", "aqua_step_chunk") == [30]


def test_program_and_span_sums_add_up_to_busy_and_idle():
    t = _hand_made()
    busy = sum(t.program_ns().values())
    assert t.program_ns() == {"aqua_step_mixed": 30, "aqua_gather_pages": 5,
                              "aqua_step_decode": 20}
    assert busy == 55
    assert sum(t.idle.values()) == sum(ns for _, ns in t.gaps) == 45
    assert busy + sum(t.idle.values()) == t.window[1] - t.window[0]
    assert t.idle_inside(t.in_window("aqua.step")) == 4 + 10 + 6


def _run(trace) -> harness.Run:
    bench = spec.load_benchmark()
    cell = spec.workload(bench, "qwen05b-burst-host")
    conf = spec.config(bench, cell["config"])
    run = harness.Run(cell, conf, harness.work.Dims.of(conf), None, [], [],
                      0.0, 1.0)
    run.spans = trace
    return run


@pytest.mark.parametrize("metric,want", [
    ("step_device_ms.mixed", 30e-6),
    ("step_device_ms.decode", 20e-6),
    ("step_host_idle_ms", 20e-6),      # idle inside the one aqua.step
    ("tier_move_ms", 14e-6),           # the park that moved pages
    ("step_copy_share", 100.0 * 8 / 50),
])
def test_reader_on_a_hand_made_trace(metric, want):
    assert spec.reader(metric)(_run(_hand_made())) == pytest.approx(want)


@pytest.mark.parametrize("metric", ["step_device_ms.mixed",
                                    "step_device_ms.decode",
                                    "step_host_idle_ms", "tier_move_ms",
                                    "step_copy_share"])
def test_reader_reads_nothing_without_spans_or_names(metric):
    """A trace of a program with neither spans nor named programs (as
    before they existed) reads None, never an error; so does an untraced
    run."""
    bare = spans.reduce({0: [("_lambda", 10, 40)]},
                        {0: [("copy", 12, 20)]},
                        [spans.Span("bench.window", 0, 100),
                         spans.Span("bench.step", 5, 45)])
    assert spec.reader(metric)(_run(bare)) is None
    assert spec.reader(metric)(_run(None)) is None


def test_recorded_chip_trace_without_program_spans():
    """The trace recorded before the program had spans and names: every
    gap is the benchmark's own, and program sums are the busy time."""
    t = spans.read(CHIP_TRACE)
    busy = sum(t.program_ns().values())
    assert {"_lambda", "_argmax"} <= set(t.program_ns())
    idle = sum(t.idle.values())
    assert busy + idle == pytest.approx(t.window[1] - t.window[0], rel=1e-6)
    assert all(n.startswith("bench.") or n == "host" for n, _ in t.gaps)
    assert not t.in_window(spans.STEP)


@pytest.mark.skipif(not SPANS_TRACE.exists(),
                    reason="no chip trace with program spans recorded")
def test_recorded_chip_trace_with_program_spans():
    """A 2 s trace of the burst cell on one TPU v5e with the program's
    spans and names: its gaps are the program's, and each fused step is
    found by name."""
    t = spans.read(SPANS_TRACE)
    progs = t.program_ns()
    assert {p for p in progs if p.startswith("aqua_step_")}
    assert not {"_lambda"} & set(progs)
    assert t.in_window(spans.STEP)
    gaps = t.longest_gaps(10)
    assert gaps and all(n.startswith("aqua.") for n, _ in gaps)
    idle = sum(t.idle.values())
    assert sum(progs.values()) + idle == pytest.approx(
        t.window[1] - t.window[0], rel=1e-6)


# ---------------------------------------------------------------------------
# the engine's own spans, traced on the CPU
# ---------------------------------------------------------------------------
PHASES = ["aqua.step.plan", "aqua.step.place", "aqua.step.pack",
          "aqua.step.dispatch", "aqua.step.readback"]


def test_engine_spans_on_the_cpu(tmp_path):
    from repro.configs import get_config, smoke_config
    from repro.core.aqua_tensor import HOST
    from repro.models import api
    from repro.serving.engine import ServingEngine
    cfg = smoke_config(get_config("qwen1.5-0.5b"))
    params = api.init_params(jax.random.PRNGKey(0), cfg)
    rng = np.random.default_rng(5)
    # two lanes, three requests, short CFS slices: the scheduler preempts
    eng = ServingEngine(cfg, params, max_running=2, max_seq=64,
                        scheduler="cfs", slice_tokens=2, offload_tier=HOST,
                        step_tokens=16)
    for n in (9, 12, 20):
        eng.submit(list(map(int, rng.integers(0, cfg.vocab_size, n))), 6)
    eng.step()                                 # compile outside the trace
    with jax.profiler.trace(str(tmp_path)):
        with jax.profiler.TraceAnnotation("bench.window"):
            m = eng.run(200)
    assert m.preemptions > 0 and not (eng.waiting or eng.running)
    t = spans.read(next(tmp_path.glob("plugins/profile/*/*.xplane.pb")))

    steps = t.in_window(spans.STEP)
    assert len(steps) == m.steps - 1
    for s in steps:
        assert s.args["kind"] in ("decode", "mixed", "chunk")
        inside = [c for c in t.spans if c.name.startswith("aqua.step.")
                  and s.start <= c.start and c.end <= s.end]
        first = {}
        for c in sorted(inside, key=lambda c: c.start):
            first.setdefault(c.name, c.start)
        assert [n for n in sorted(first, key=first.get) if n in PHASES] \
            == PHASES

    parks = [s for s in t.in_window("aqua.kv.park")
             if s.args["cause"] == "preempt" and s.args["pages"] > 0]
    assert parks
    for p in parks:
        assert p.args["tier"] == "host"
        back = [r for r in t.in_window("aqua.kv.restore")
                if r.args["rid"] == p.args["rid"] and r.start > p.end]
        assert back and back[0].args["cause"] in ("admit", "prefetch")
    # each step's program is found by its kind's name
    kinds = {f"aqua_step_{s.args['kind']}" for s in steps}
    assert "aqua_step_decode" in kinds and kinds <= t.programs
