"""Bring-up smoke run of the serving main path on a TPU.

    python3 chip_smoke.py                # one chip
    python3 chip_smoke.py --four-chips   # peer-HBM parking, four chips

One chip: qwen1.5-0.5b at its published widths (24 layers, d_model 1024,
vocab 151936, bfloat16, random weights from ``SEED``) is built exactly as
``repro.launch.serve`` builds it and serves ``N_REQUESTS`` prompts of a few
hundred tokens through ``ServingEngine.submit`` -> ``step`` -> the fused
``serve_step_paged`` (Pallas mixed-mode attention and page-append kernels)
under CFS with a per-step token budget. More requests than batch slots
means chunked prefill, decode, preemption (park) and restore all run. Every
served token is checked against the dense model path (``api.prefill`` /
``api.decode_step``) on the same chip, teacher-forced on the served
sequence.

``--four-chips`` runs only AQUA's cross-chip mechanism: the same serving run
with contexts parked in peer chips' HBM (``MeshTierDomain``, REMOTE tier),
compared with the same run parking to host memory on one chip.

The timings printed are host-clock walls of a smoke run, not a benchmark.
The last line of standard output is the JSON result; the script exits
non-zero without it when no TPU is found or any check fails.
"""
from __future__ import annotations

import argparse
import json
import statistics
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from repro.launch import serve  # noqa: E402

ARCH = "qwen1.5-0.5b"
SEED = 0
N_REQUESTS = 8
MAX_RUNNING = 4
MAX_SEQ = 512
STEP_TOKENS = 256
SLICE_TOKENS = 8
NEW_TOKENS = 32
# prompt lengths: none page-aligned, the longer ones wider than one step's
# token budget (several prefill chunks each)
PROMPT_LENS = (203, 229, 251, 277, 310, 343, 389, 421)
# A served token that is not the dense path's argmax is accepted only at a
# near-tie: the dense path's logit for its own top token exceeds its logit
# for the served token by at most NEAR_TIE. The two paths round
# differently in bf16 — the kernels keep attention scores, softmax and
# accumulators in f32 where the dense path rounds scores and probabilities
# to bf16 — and the difference compounds over 24 layers. Logits near the
# top lie in [2, 4), where bf16 steps by 2**-6; 0.125 is 8 such steps.
NEAR_TIE = 0.125


class SmokeFailure(RuntimeError):
    """A phase of the smoke run produced a wrong or missing result."""


def check(ok: bool, what: str) -> None:
    if not ok:
        raise SmokeFailure(what)


class CompileLog:
    """Compile seconds and persistent-cache hits, from JAX's own events."""

    def __init__(self):
        self.seconds = 0.0
        self.cache_hits = 0
        self.cache_misses = 0
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event: str, secs: float, **_):
        if event.startswith("/jax/core/compile/"):
            self.seconds += secs

    def _event(self, event: str, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self.cache_hits += 1
        elif event == "/jax/compilation_cache/cache_misses":
            self.cache_misses += 1


def make_prompts(vocab: int):
    rng = np.random.default_rng(SEED)
    return [list(map(int, rng.integers(0, vocab, n))) for n in PROMPT_LENS]


def serve_workload(eng, prompts):
    """Submit every prompt at once and step the engine until all are served.

    Returns ``(outputs, ttft, step_walls)``: the served tokens per request,
    the host-clock seconds from the first step to each request's first
    token, and each step's host-clock seconds. A step ends in a host read of
    the sampled tokens, and the pools are waited on as well, so each wall
    covers the device's work."""
    for p in prompts:
        eng.submit(p, NEW_TOKENS)
    ttft, walls = {}, []
    t0 = time.perf_counter()
    for _ in range(20 * len(prompts) * (NEW_TOKENS + 1)):
        if not (eng.waiting or eng.running):
            break
        ts = time.perf_counter()
        eng.step()
        jax.block_until_ready(eng.kv.pools)
        te = time.perf_counter()
        walls.append(te - ts)
        for r in eng.running + eng.finished:
            if r.generated and r.rid not in ttft:
                ttft[r.rid] = te - t0
    check(not (eng.waiting or eng.running), "engine did not drain")
    check(len(eng.finished) == len(prompts)
          and all(r.terminal == "finished" for r in eng.finished),
          f"served {len(eng.finished)} of {len(prompts)} requests")
    outputs = {r.rid: list(r.generated) for r in eng.finished}
    check(all(len(o) == NEW_TOKENS for o in outputs.values()),
          "a request stopped short of its token count")
    return outputs, ttft, walls


def dense_reference_check(params, cfg, prompts, outputs):
    """Teacher-force each prompt plus its served tokens through the dense
    path — ``api.prefill`` over the shortest prompt, then
    ``api.decode_step`` one position at a time for all requests at once —
    and compare every served token with that path's logits.

    Returns ``(n_tokens, n_exact, worst_margin)``; raises ``SmokeFailure``
    at a disagreement that is not a near-tie (``NEAR_TIE``)."""
    from repro.models import api
    seqs = [list(p) + list(outputs[i]) for i, p in enumerate(prompts)]
    B, L0 = len(seqs), min(len(p) for p in prompts)
    T = max(len(s) for s in seqs)

    prefill = jax.jit(lambda p, t, c: api.prefill(p, cfg, t, c))
    decode = jax.jit(lambda p, c, t, pos: api.decode_step(p, cfg, c, t, pos))

    @jax.jit
    def read(logits, nxt):
        logits = logits.astype(jnp.float32)
        return (jnp.argmax(logits, -1), jnp.max(logits, -1),
                jnp.take_along_axis(logits, nxt[:, None], 1)[:, 0])

    def targets(pos):
        return jnp.asarray([s[pos + 1] if pos + 1 < len(s) else 0
                            for s in seqs], jnp.int32)

    cache = api.init_decode_state(cfg, B, MAX_SEQ)
    logits, cache = prefill(params, jnp.asarray([s[:L0] for s in seqs]),
                            cache)
    n = exact = 0
    worst = 0.0
    for pos in range(L0 - 1, T - 1):
        if pos >= L0:
            tok = jnp.asarray([s[pos] if pos < len(s) else 0 for s in seqs],
                              jnp.int32)
            logits, cache = decode(params, cache, tok,
                                   jnp.full((B,), pos, jnp.int32))
        top, top_v, served_v = (np.asarray(x) for x in
                                read(logits, targets(pos)))
        for b, s in enumerate(seqs):
            if not len(prompts[b]) - 1 <= pos < len(s) - 1:
                continue               # position b's logits don't predict
            n += 1
            if top[b] == s[pos + 1]:
                exact += 1
                continue
            margin = float(top_v[b] - served_v[b])
            worst = max(worst, margin)
            check(margin <= NEAR_TIE,
                  f"request {b} token {pos + 1 - len(prompts[b])}: served "
                  f"{s[pos + 1]}, dense path's argmax {top[b]} leads it by "
                  f"{margin:.4f} > {NEAR_TIE}")
    return n, exact, worst


def append_kv_check(eng) -> None:
    """The page-append kernel, compiled at the served kv page shape in bf16,
    equals ``append_kv_ref`` bit for bit: it only copies, so any difference
    is a wrong row. Eight lanes at distinct pages cover every row offset."""
    from repro.kernels.paged_attention.kernel import append_kv
    from repro.kernels.paged_attention.ref import append_kv_ref
    aqua = eng.kv.planes["kv"].aqua
    _, K, page, hd = aqua.page_shape
    rng = np.random.default_rng(SEED)

    def rand(*shape):
        return jnp.asarray(rng.standard_normal(shape), aqua.dtype)

    B = 8
    pool, k_new, v_new = rand(64, 2, K, page, hd), rand(B, K, hd), rand(B, K, hd)
    slots = jnp.asarray(rng.permutation(64)[:B], jnp.int32)
    offs = jnp.asarray(np.arange(B) % page, jnp.int32)
    want = np.asarray(append_kv_ref(pool, k_new, v_new, slots, offs))
    kernel = jax.jit(append_kv).lower(pool, k_new, v_new, slots,
                                      offs).compile()
    check("tpu_custom_call" in kernel.as_text(),
          "append_kv compiled without its Pallas kernel")
    got = np.asarray(kernel(pool, k_new, v_new, slots, offs))
    check(np.array_equal(got, want), "append_kv differs from append_kv_ref")
    print(f"append_kv: {aqua.dtype} pages {aqua.page_shape} equal "
          f"append_kv_ref bit for bit ({B} lanes, every row offset)")


def fused_step_text(eng) -> str:
    """Compiled text of the engine's mixed fused step (decode lanes plus a
    chunk region) at the shapes it serves."""
    from repro.models import lm
    from repro.serving.scheduler import bucket_tokens
    R = eng.max_running + bucket_tokens(eng.max_running + 1, lo=1)
    Tc = bucket_tokens(eng.step_tokens)
    zeros = jnp.zeros((R,), jnp.int32)
    step = lm._serve_step_jit(eng.cfg, eng.paged_impl, eng.kv.pps,
                              eng.max_running, "mixed")
    return step.lower(eng.params, jnp.zeros((R, Tc), jnp.int32),
                      eng.kv.pools, eng.kv.block_tables([None] * R,
                                                        pad_to=eng._pps_pad),
                      zeros, zeros, None).compile().as_text()


def build(**kw):
    return serve.build_engine(ARCH, seed=SEED, scheduler="cfs",
                              max_running=MAX_RUNNING, max_seq=MAX_SEQ,
                              slice_tokens=SLICE_TOKENS,
                              step_tokens=STEP_TOKENS,
                              lease_contexts=N_REQUESTS, **kw)


def median_ms(xs) -> float:
    return 1e3 * statistics.median(xs)


def one_chip(log: CompileLog) -> None:
    eng = build()
    cfg = eng.cfg
    print(f"model: {cfg.name} layers={cfg.n_layers} d_model={cfg.d_model} "
          f"heads={cfg.n_heads} vocab={cfg.vocab_size} "
          f"dtype={cfg.compute_dtype} params={cfg.param_count()}")
    prompts = make_prompts(cfg.vocab_size)
    cold, _, _ = serve_workload(eng, prompts)
    print(f"compile: {log.seconds:.1f} s in the cold pass "
          f"(persistent cache hits={log.cache_hits} "
          f"misses={log.cache_misses})")

    eng = build()
    outputs, ttft, walls = serve_workload(eng, prompts)
    check(outputs == cold, "warm pass served different tokens than cold")
    m = eng.metrics
    print(f"smoke, not a benchmark: requests={len(outputs)} "
          f"steps={len(walls)} TTFT median={median_ms(ttft.values()):.1f} ms"
          f" step median={median_ms(walls):.2f} ms (host clock, "
          "block_until_ready)")
    print(f"engine: prefill chunks={m.prefills} preemptions={m.preemptions} "
          f"restores={m.restores} spec_chunks={m.spec_chunks}")
    print("pager:", json.dumps(eng.pager.stats()["meter"]),
          json.dumps(eng.pager.stats()["tiers"]))
    check(m.preemptions > 0 and m.restores > 0,
          "no preemption/restore happened")

    n, exact, worst = dense_reference_check(eng.params, cfg, prompts,
                                            outputs)
    print(f"dense-path check: {n} tokens, {exact} equal to its argmax, "
          f"{n - exact} near-ties (worst margin {worst:.4f} <= {NEAR_TIE})")
    check(n == N_REQUESTS * NEW_TOKENS, f"checked {n} tokens")
    append_kv_check(eng)
    check("tpu_custom_call" in fused_step_text(eng),
          "the compiled fused step holds no Pallas TPU kernel")
    print("fused step: compiled with tpu_custom_call (Pallas kernels)")


def four_chips(log: CompileLog) -> None:
    from repro.distributed.mesh_tiers import MeshTierDomain
    check(len(jax.devices()) == 4, f"{len(jax.devices())} devices, want 4")
    mesh = MeshTierDomain()
    remote = build(offload="fabric", mesh=mesh)
    prompts = make_prompts(remote.cfg.vocab_size)
    t0 = time.perf_counter()
    out_remote, _, walls_r = serve_workload(remote, prompts)
    wall_r = time.perf_counter() - t0
    host = build(offload="host")
    t0 = time.perf_counter()
    out_host, _, walls_h = serve_workload(host, prompts)
    wall_h = time.perf_counter() - t0

    meter = remote.pager.meter
    donors = {}
    for plane in remote.kv.planes.values():
        for donor, pool in plane.aqua.remote_pools.items():
            i = mesh.donor_device(donor)
            held = {s.device for s in pool.addressable_shards
                    if s.index[0].start == i}
            check(held == {mesh.devices[i]},
                  f"{donor}'s slab is not on mesh device {i}")
            donors[donor] = i
    print(f"remote run: wall {wall_r:.1f} s over {len(walls_r)} steps, "
          f"preemptions={remote.metrics.preemptions} "
          f"restores={remote.metrics.restores} "
          f"collectives={mesh.collectives} "
          f"fabric messages={meter.messages_fabric} "
          f"fabric bytes={meter.bytes_fabric:.0f} "
          f"host messages={meter.messages_host}")
    print(f"host run: wall {wall_h:.1f} s over {len(walls_h)} steps, "
          f"preemptions={host.metrics.preemptions} "
          f"host messages={host.pager.meter.messages_host} "
          f"host bytes={host.pager.meter.bytes_host:.0f}")
    print(f"donor slabs: {json.dumps(donors)} (mesh device of each donor); "
          f"compile {log.seconds:.1f} s; smoke, not a benchmark")
    check(sorted(donors.values()) == [1, 2, 3],
          f"donor slabs on mesh devices {sorted(donors.values())}")
    check(remote.metrics.preemptions > 0 and meter.messages_fabric > 0,
          "no context was parked in peer HBM")
    check(mesh.collectives == meter.messages_fabric,
          f"collectives {mesh.collectives} != fabric messages "
          f"{meter.messages_fabric}")
    check(host.pager.meter.messages_fabric == 0
          and host.metrics.preemptions > 0, "host run did not park to host")
    check(out_remote == out_host,
          "peer-HBM parking served different tokens than host parking")
    print(f"tokens identical across REMOTE and HOST parking: "
          f"{sum(map(len, out_remote.values()))} tokens")


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the peer-HBM parking phase on 4 chips")
    args = ap.parse_args()
    devices = jax.devices()
    if devices[0].platform != "tpu":
        print(f"chip_smoke: no TPU (JAX found {devices[0].platform}); this "
              "smoke runs on the chip only", file=sys.stderr)
        return 1
    cache_dir = serve.enable_compile_cache()
    print(f"device: {devices[0].device_kind} x{len(devices)} "
          f"(platform {devices[0].platform}); compile cache {cache_dir}")
    log = CompileLog()
    if args.four_chips:
        four_chips(log)
    else:
        one_chip(log)
    print(json.dumps({"ok": True,
                      "device": {"platform": devices[0].platform,
                                 "kind": devices[0].device_kind,
                                 "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
