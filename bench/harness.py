"""One run of one cell: build, warm up, serve the traffic on the host
clock, reduce, check against the plain reference, print one result line.

The served path is the program's own: ``ServingEngine.submit`` ->
``ServingEngine.step`` -> ``serve_step_paged`` (Pallas paged kernels),
with CFS preemption parking contexts to host memory or, on four chips, to
peer HBM through ``MeshTierDomain``. The benchmark only submits requests
when they are due, steps the engine while it has work, waits on the
device, and watches what each step did from outside (prefill positions and
tokens of every request in flight).
"""
from __future__ import annotations

import gc
import json
import math
import os
import statistics
import sys
import time
from contextlib import nullcontext
from dataclasses import dataclass, field
from pathlib import Path
from typing import Dict, List, Optional

import numpy as np

import spec as specs
import stats
import traffic as traffic_mod
import work

BENCH = specs.BENCH
ROOT = specs.ROOT
CACHE_DIR = ROOT / ".jax_cache"


class NoAccelerator(RuntimeError):
    """JAX found no TPU, or fewer chips than the cell asks for."""


class CompileLog:
    """Programs built, from JAX's own monitoring events: every build, those
    of them loaded from the persistent compilation cache, and the names of
    those built while ``watching`` (the measured window)."""

    def __init__(self):
        import jax
        self.builds = 0
        self.seconds = 0.0
        self.cache_hits = 0
        self.watching = False
        self.watched: List[str] = []
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    @property
    def count(self) -> int:
        """Programs compiled (built and not found in the cache)."""
        return self.builds - self.cache_hits

    def _duration(self, event: str, secs: float, **kw):
        if event == "/jax/core/compile/backend_compile_duration":
            self.builds += 1
            self.seconds += secs
            if self.watching:
                self.watched.append(str(kw.get("fun_name", "?")))

    def _event(self, event: str, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self.cache_hits += 1


@dataclass
class Step:
    """One engine step as the benchmark saw it."""
    start: float
    end: float
    rows: List[tuple]           # (q_start, n_real) of every real row
    decode_rows: int            # rows that were decode lanes
    chunk_tokens: int           # prompt tokens processed
    logit_rows: int             # rows whose logits became a token

    @property
    def kind(self) -> str:
        if not self.chunk_tokens:
            return "decode"
        return "mixed" if self.decode_rows else "chunk"

    @property
    def wall(self) -> float:
        return self.end - self.start


@dataclass
class Run:
    """Everything a metric reader may read about one run."""
    cell: dict
    config: dict
    dims: work.Dims
    peak: Optional[dict]
    records: List[stats.Record]
    steps: List[Step]                      # steps inside the window
    t0: float
    t1: float
    counters: Dict[str, float] = field(default_factory=dict)
    trace: object = None                   # xplane.Reduced, traced runs

    @property
    def seconds(self) -> float:
        return self.t1 - self.t0

    def due_in_window(self) -> int:
        return sum(1 for r in self.records if self.t0 <= r.due < self.t1)


def log(*a):
    print(*a, flush=True)


def _span(name: str, on: bool):
    if not on:
        return nullcontext()
    import jax
    return jax.profiler.TraceAnnotation(name)


class Cell:
    """A cell's program, weights and traffic for one seed."""

    def __init__(self, name: str, seed: int, *, root: Path = ROOT,
                 bench_dir: Path = BENCH, require_tpu: bool = True,
                 traffic: Optional[dict] = None):
        self.bench = specs.load_benchmark(root)
        self.cell = specs.workload(self.bench, name)
        self.conf = specs.config(self.bench, self.cell["config"], root)
        self.traffic = traffic or traffic_mod.load(
            self.cell["traffic"], Path(bench_dir) / "traffic")
        self.limits = specs.limits(name, bench_dir)
        self.seed = int(seed)
        family = self.conf["bench"]["family"]
        self.ref = specs.module("reference", family, bench_dir)
        self.adapter = specs.module("adapters", family, bench_dir)
        self.require_tpu = require_tpu
        self.dims = work.Dims.of(self.conf)

    # -- devices ----------------------------------------------------------
    def devices(self):
        import jax
        devs = jax.devices()
        chips = int(self.cell["chips"])
        if self.require_tpu:
            if devs[0].platform != "tpu":
                raise NoAccelerator(f"JAX found no TPU (platform "
                                    f"{devs[0].platform})")
            if len(devs) < chips:
                raise NoAccelerator(f"{len(devs)} chips, the cell asks "
                                    f"for {chips}")
        return devs[:chips]

    # -- build ------------------------------------------------------------
    def build(self):
        """Weights from the seed (one jitted call, served dtype), the
        program's engine over them, and the tier it parks to."""
        import jax
        from repro.core.aqua_tensor import HOST, REMOTE
        from repro.serving.engine import ServingEngine
        b = self.conf["bench"]
        self.weights = self.ref.init_weights(self.conf, self.seed)
        jax.block_until_ready(self.weights)
        self.model = self.adapter.model_config(self.conf["name"], self.conf)
        params = self.adapter.program_params(self.weights, self.conf)
        dep = b["deployment"]
        mesh = None
        if dep["offload"] == "fabric":
            from repro.distributed.mesh_tiers import MeshTierDomain
            mesh = MeshTierDomain(self.devices())
        self.engine = ServingEngine(
            self.model, params,
            offload_tier=REMOTE if dep["offload"] == "fabric" else HOST,
            mesh=mesh, **b["engine"])
        self.mesh = mesh
        if mesh is not None:
            eng = self.engine
            ctx = sum(int(n) * p.aqua.page_bytes for n, p in zip(
                eng.kv.pages_per_request(eng.max_seq),
                eng.kv.planes.values()))
            donors = [f"donor{i}" for i in range(1, mesh.n_dev)]
            for d in donors:
                eng.pager.add_remote_lease(
                    d, int(dep["lease_contexts"]) * ctx / len(donors))

    # -- warm-up ----------------------------------------------------------
    def warm(self):
        """Run every program the window can call, through the program's own
        paths: two short requests served by the engine take it through a
        chunk-only, a mixed and an all-decode step (and their argmax); the
        paged runtime parks a context of every page count up to
        ``max_seq`` to the cell's offload tier and restores it, as the
        engine's preemptions and restores do."""
        import jax
        eng = self.engine
        eng.submit([1] * 16, 4)
        eng.step()                     # chunk-only: nothing decodes yet
        eng.submit([2] * 16, 2)
        while eng.waiting or eng.running:
            eng.step()                 # mixed, then all-decode
        kv = eng.kv
        rid = -1                       # no request of the engine's own
        for n in range(1, kv.pps + 1):
            kv.ensure_capacity(rid, n * kv.page_tokens)
            kv.park(rid, n * kv.page_tokens, prefer=eng.offload_tier)
            kv.restore(rid)
            kv.release(rid)
        jax.block_until_ready(kv.pools)

    # -- serve ------------------------------------------------------------
    def serve(self, seconds: float, *, trace_dir: Optional[Path] = None,
              compiles: Optional[CompileLog] = None,
              fault=None) -> Run:
        """Serve the traffic: the pre-roll, then the measured window of
        ``seconds``. ``fault`` (tests only) may break the engine first."""
        import jax
        eng = self.engine
        tr = self.traffic
        vocab = self.dims.V
        pre = float(tr.get("preroll_s", 0.0))
        T0, T1 = pre, pre + float(seconds)
        closed = tr["loop"] == "closed"
        if closed:
            queue = traffic_mod.closed_loop(tr, self.seed, vocab)
            items = queue[:int(tr["clients"])]
            nxt = len(items)
        else:
            items = traffic_mod.open_loop(tr, self.seed, T1, vocab)
        recs = [stats.Record(it.due) for it in items]
        by_rid: Dict[int, int] = {}
        reqs: Dict[int, object] = {}
        steps: List[Step] = []
        if fault is not None:
            fault(self)
        tracing = trace_dir is not None
        lateness: List[float] = []
        counters0: Dict[str, float] = {}
        fin_seen = len(eng.finished)
        submitted = 0
        opened = False
        clock = time.perf_counter
        base = clock()
        self.base = base

        def now():
            return clock() - base

        def submit(i):
            it = items[i]
            r = eng.submit(list(it.prompt), it.max_new_tokens)
            by_rid[r.rid] = i
            reqs[r.rid] = r
            recs[i].submitted = now()
            lateness.append(recs[i].submitted - it.due)

        while True:
            t = now()
            if not opened and t >= T0:
                opened = True
                counters0 = self._counters(compiles)
                if compiles is not None:
                    compiles.watching = True
                if tracing:
                    jax.profiler.start_trace(
                        str(trace_dir), profiler_options=_trace_options())
                win = _span("bench.window", tracing)
                win.__enter__()
            if t >= T1:
                break
            with _span("bench.submit", tracing):
                while submitted < len(items) and items[submitted].due <= t:
                    submit(submitted)
                    submitted += 1
            if eng.waiting or eng.running:
                with _span("bench.step", tracing):
                    before = {rid: (r.prefill_pos, len(r.generated),
                                    r.prefilled)
                              for rid, r in reqs.items()
                              if r.terminal is None}
                    ts = now()
                    eng.step()
                    jax.block_until_ready(eng.kv.pools)
                    te = now()
                with _span("bench.observe", tracing):
                    step = self._observe(before, reqs, by_rid, recs, ts, te)
                    if ts >= T0:
                        steps.append(step)
                    for r in eng.finished[fin_seen:]:
                        i = by_rid.get(r.rid)
                        if i is not None:
                            recs[i].finished = te
                        reqs.pop(r.rid, None)
                        if closed:
                            if nxt == len(queue):
                                queue += traffic_mod.closed_loop(
                                    tr, self.seed, vocab,
                                    block=nxt // int(tr["queue"]))
                            items.append(traffic_mod.Item(
                                nxt, te, queue[nxt].prompt,
                                queue[nxt].max_new_tokens))
                            recs.append(stats.Record(te))
                            nxt += 1
                    fin_seen = len(eng.finished)
            else:
                nd = (items[submitted].due if submitted < len(items)
                      else T1)
                with _span("bench.wait", tracing):
                    time.sleep(max(0.0, min(nd, T1 if opened else T0)
                                   - now()))
        if compiles is not None:
            compiles.watching = False
        if opened:
            win.__exit__(None, None, None)
        if tracing:
            jax.profiler.stop_trace()
        counters1 = self._counters(compiles)
        late = sorted(lateness) or [0.0]
        log(f"generator: {len(lateness)} submitted, late by median "
            f"{1e3 * statistics.median(late):.3f} ms, max "
            f"{1e3 * late[-1]:.3f} ms (the loop submits between steps)")
        run = Run(self.cell, self.conf, self.dims, None, recs, steps, T0, T1)
        run.counters = {k: counters1[k] - counters0.get(k, 0.0)
                        for k in counters1}
        self.finished = [(list(r.prompt_tokens), list(r.generated),
                          recs[by_rid[r.rid]].parked
                          and recs[by_rid[r.rid]].restored)
                         for r in eng.finished
                         if r.terminal == "finished" and r.rid in by_rid]
        self.in_flight = [(list(r.prompt_tokens), list(r.generated),
                           recs[by_rid[r.rid]].parked)
                          for r in reqs.values() if r.generated]
        self.failed = sum(1 for r in eng.finished
                          if r.terminal not in (None, "finished"))
        return run

    def _counters(self, compiles: Optional[CompileLog]) -> Dict[str, float]:
        eng = self.engine
        meter = eng.pager.meter
        out = {"preemptions": eng.metrics.preemptions,
               "restores": eng.metrics.restores,
               "tier_bytes": meter.bytes_host + meter.bytes_fabric,
               "messages_host": meter.messages_host,
               "messages_fabric": meter.messages_fabric}
        if self.mesh is not None:
            out["collectives"] = self.mesh.collectives
        if compiles is not None:
            out["builds"] = compiles.builds
            out["cache_loads"] = compiles.cache_hits
        return out

    def _observe(self, before, reqs, by_rid, recs, ts, te) -> Step:
        rows, dec, chunk, logit_rows = [], 0, 0, 0
        for rid, (pp, ng, was_prefilled) in before.items():
            r = reqs[rid]
            rec = recs[by_rid[rid]]
            grew = len(r.generated) - ng
            if r.prefill_pos > pp:
                n = r.prefill_pos - pp
                rows.append((pp, n))
                chunk += n
                if rec.first_chunk is None:
                    rec.first_chunk = ts
            elif grew and was_prefilled:
                rows.append((r.prompt_positions + ng - 1, 1))
                dec += 1
            logit_rows += 1 if grew else 0
            rec.tokens.extend([te] * grew)
            if r.parked:
                rec.parked = True
            elif rec.parked and r.slot is not None:
                rec.restored = True
        return Step(ts, te, rows, dec, chunk, logit_rows)

    # -- after the window ----------------------------------------------------
    def free_program(self):
        """Drop the engine, its pools and the program's own parameter
        tree; the benchmark's weights stay for the reference."""
        import jax
        self.engine = None
        self.mesh = None
        gc.collect()
        jax.clear_caches()

    def sample(self) -> List[tuple]:
        """Requests to compare, drawn from the seed: the longest finished
        one, then the finished ones that were parked and restored, then the
        rest, until ``sample_tokens`` served tokens or ``sample_requests``
        requests."""
        pool = self.finished or self.in_flight
        if not pool:
            return []
        rng = np.random.default_rng([self.seed & 0xFFFFFFFF,
                                     self.seed >> 32, 7])
        order = list(rng.permutation(len(pool)))
        longest = max(range(len(pool)),
                      key=lambda i: len(pool[i][0]) + len(pool[i][1]))
        order.remove(longest)
        order.sort(key=lambda i: not pool[i][2])
        order = [longest] + order
        out, n = [], 0
        for i in order:
            if (n >= int(self.limits["sample_tokens"])
                    or len(out) >= int(self.limits["sample_requests"])):
                break
            out.append(pool[i])
            n += len(pool[i][1])
        return out

    def gaps(self, sample, precision: str = "f32") -> List[float]:
        """Per request, the widest gap by which a served token's logit lies
        below the reference's best at that position. With ``precision``
        "fp8" the served tokens are replaced by the control's own argmax
        (the reference computed one precision below the configuration's),
        and the gap is read in the float32 reference all the same."""
        T = int(self.conf["bench"]["engine"]["max_seq"])
        out = []
        for prompt, served, _ in sample:
            seq = (prompt + served)[:T]
            x = np.zeros(T, np.int32)
            x[:len(seq)] = seq
            P = len(prompt)
            pos = np.arange(P - 1, P - 1 + len(served))
            tgt = np.zeros((T, 1), np.int32)
            tgt[pos, 0] = served
            if precision != "f32":
                _, am, _ = self.ref.logits_stats(self.conf, self.weights, x,
                                                 tgt, precision=precision)
                tgt = np.asarray(am, np.int32)[:, None]
            mx, _, at = self.ref.logits_stats(self.conf, self.weights, x, tgt)
            mx, at = np.asarray(mx), np.asarray(at)[:, 0]
            out.append(float(np.max(mx[pos] - at[pos])))
        return out


def enable_compile_cache():
    """JAX's persistent compilation cache at the fixed ``.jax_cache/`` of
    this checkout, for every program however quick to compile, with no
    eviction (eviction keeps access-time files that a failed write can
    leave missing, which then fails every later write)."""
    import jax
    CACHE_DIR.mkdir(parents=True, exist_ok=True)
    jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    jax.config.update("jax_compilation_cache_max_size", -1)


def _trace_options():
    import jax
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 2
    return opts


def end_to_end(run: Run, setup_s: float, wanted: List[dict]) -> Dict:
    e2e = stats.end_to_end(run.records, run.t0, run.t1)
    values = {"setup_s": setup_s, **e2e}
    out = {}
    for m in wanted:
        v = values.get(m["name"])
        if v is not None:
            out[m["name"]] = {"value": v, "unit": m["unit"]}
    log(f"window: {run.seconds:.1f} s, {e2e['n_ttft']} requests due "
        f"({e2e['censored_ttft']} without a first token at the close), "
        f"{e2e['n_tbt']} token gaps, {len(run.steps)} steps "
        f"({sum(s.kind == 'decode' for s in run.steps)} decode, "
        f"{sum(s.kind == 'mixed' for s in run.steps)} mixed, "
        f"{sum(s.kind == 'chunk' for s in run.steps)} chunk-only)")
    return out


def per_layer(run: Run, wanted: List[dict], bench_dir: Path) -> Dict:
    out = {}
    for m in wanted:
        v = specs.reader(m["name"], bench_dir)(run)
        if v is not None:
            out[m["name"]] = {"value": v, "unit": m["unit"]}
    return out


def main(argv=None, *, t_start: Optional[float] = None, root: Path = ROOT,
         bench_dir: Path = BENCH, require_tpu: bool = True,
         fault=None, control: Optional[str] = None) -> int:
    """Run one cell once; returns the exit code. ``require_tpu=False``,
    ``fault`` and ``control`` exist for the benchmark's own tests and
    calibration: they run a tiny cell on the CPU (without touching the
    persistent compile cache), plant a fault under the timed path, and put
    the reference at the lower precision ``control`` ("fp8") in the
    program's place for the comparison."""
    import argparse
    t_start = time.perf_counter() if t_start is None else t_start
    ap = argparse.ArgumentParser(description="Run one benchmark cell once.")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    cell = Cell(args.workload, args.seed, root=root, bench_dir=bench_dir,
                require_tpu=require_tpu)
    try:
        devs = cell.devices()
    except NoAccelerator as e:
        print(f"bench: {e}; no result", file=sys.stderr)
        return 2
    kind = devs[0].device_kind
    peak = work.peaks(kind) if require_tpu else None
    if require_tpu:
        enable_compile_cache()
    compiles = CompileLog()
    log(f"device: {kind} x{len(devs)} ({devs[0].platform}); cell "
        f"{args.workload}; seed {args.seed}; compile cache "
        f"{CACHE_DIR if require_tpu else 'off'}; JAX environment "
        f"{ {k: v for k, v in os.environ.items() if k.startswith('JAX_')} }")

    cell.build()
    cell.warm()
    log(f"built and warmed: {compiles.builds} programs in "
        f"{compiles.seconds:.1f} s, {compiles.cache_hits} of them from the "
        "persistent cache")
    trace_dir = None
    if args.trace:
        trace_dir = root / ".bench_trace" / f"{args.workload}-{args.seed}"
        if trace_dir.exists():
            import shutil
            shutil.rmtree(trace_dir)
    run = cell.serve(args.seconds, trace_dir=trace_dir, compiles=compiles,
                     fault=fault)
    run.peak = peak
    setup_s = cell.base + run.t0 - t_start
    log(f"counters over the window: {json.dumps(run.counters)}")
    if compiles.watched:
        log(f"built inside the window: {compiles.watched}")
    mem = max((d.memory_stats() or {}).get("peak_bytes_in_use", 0)
              for d in devs)
    device = {"platform": devs[0].platform, "kind": kind,
              "count": len(devs), "memory_peak_bytes": int(mem)}
    bench = cell.bench
    if args.trace:
        import xplane
        run.trace = xplane.read(xplane.find_xplane(trace_dir))
        busy = [run.trace.busy_s(i) for i in range(len(devs))]
        device["busy_s"] = sum(busy) / len(busy)
        device["window_s"] = run.trace.window_s
        metrics = per_layer(run, specs.metrics_of(bench, args.workload,
                                                  "per_layer"), bench_dir)
    else:
        metrics = end_to_end(run, setup_s, specs.metrics_of(
            bench, args.workload, "end_to_end"))

    cell.free_program()
    sample = cell.sample()
    gaps = cell.gaps(sample, precision=control or "f32")
    limit = float(cell.limits["worst_gap"])
    worst = max(gaps) if gaps else math.inf
    n_tok = sum(len(s[1]) for s in sample)
    n_parked = sum(1 for s in sample if s[2])
    built = len(compiles.watched)
    # a program built inside the window would be timed with it
    correct = bool(sample) and worst <= limit and built == 0
    attempted = sum(1 for r in run.records if r.due < run.t1
                    and not (r.finished is not None and r.finished < run.t0))
    result = {"correct": correct, "attempted": attempted,
              "failed": cell.failed, "metrics": metrics, "device": device}
    if args.trace:
        result["breakdown"] = {"device_ops": run.trace.top_ops(10),
                               "idle_gaps": run.trace.longest_gaps(10)}
    result["compared"] = {
        "worst_gap": {"value": worst, "limit": limit},
        "window_builds": {"value": built, "limit": 0},
        "requests": {"value": len(sample),
                     "limit": int(cell.limits["sample_requests"])},
        "tokens": {"value": n_tok,
                   "limit": int(cell.limits["sample_tokens"])},
        "parked_restored": {"value": n_parked, "limit": None}}
    for k, v in result["compared"].items():
        print(f"compared {k}: {v['value']} (limit {v['limit']})",
              file=sys.stderr, flush=True)
    print(json.dumps(result), flush=True)
    return 0
