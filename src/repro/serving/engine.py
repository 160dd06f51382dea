"""The serving engine: chunked continuous batching with pluggable schedulers
(FCFS / CFS) on the unified paged state runtime.

EVERY family's per-request dynamic context lives on AquaTensor pages
(``PagedStateRuntime``): attention K/V and MLA latents on token-paged
planes, Mamba ssm/conv tails and RWKV6 wkv/shift state on fixed-size state
planes. Decode and prefill read/write the LOCAL pools inside the jit'd
whole-step programs (attention through the ``kernels/paged_attention``
block-table kernels — interpret mode on CPU — MLA and recurrent planes via
shape-stable jnp gathers), and a CFS preemption is a page-table tier flip
for any family — ``offload(pages)`` out, ``ensure_local(pages)`` back, one
coalesced message per (plane, tier, donor) group, zero repacking (paper
§3+§5). There is no dense fallback runtime: the seed-era dense blob-store
shim is deleted. Families with no page plane yet (windowed ring buffers,
attention-logit softcap, encoder-decoder) are rejected at construction.

Prefill is CHUNKED: every step spends at most ``step_tokens`` tokens, split
between the decode lanes and prompt chunks of the run set's pending
prefills (several requests' chunks may ride one step), so no step scales
with the longest prompt. Recurrent planes stay exact across chunk
boundaries (masked identity transitions for the bucket padding). A VLM
prompt's ``prefix_embeds`` occupy its first ``n_prefix`` positions and are
injected into the chunks that cover them (the ``q_start == 0`` side of the
prompt).

The whole step is ONE JITTED CALL (``api.serve_step_paged``): every decode
lane and every scheduled prompt chunk is packed into a single (rows x
chunk-bucket) token batch with per-row ``(q_start, n_real, is_decode)``
metadata, and each layer serves all rows in one fused mixed-mode attention
launch. The per-request chunk loop and the separate decode call are GONE
from the engine — dispatch overhead per step is O(1) in the number of
admitted requests instead of O(requests) (the between-launch idle regime
of Kossmann et al. 2024), priced by ``perfmodel.launch_overhead_time``.
Row logits are bit-identical to the per-request entry points the packed
rows replace. With decode lanes present, the chunk budget is additionally
capped by the launch's memory-bound FLOPs slack
(``ModelCost.piggyback_tokens``) so mixed steps stay AT the roofline. When
``split_step_budget`` leaves slack (every admitted prefill fully granted),
WAITING prefills get it as speculative chunks riding the same call — in
arrival order, PAST the head-of-line waiter while page headroom allows —
each parked again right after, so admission finds their prompts partially
prefilled.

All paged entry points go through shape buckets — chunk lengths and packed
row counts pad to power-of-two ladders, block tables and decode lanes to
fixed sizes — so the jit cache holds a constant number of traces
regardless of the prompt-length mix or the number of admitted requests.
Page restores for the NEXT step's scheduled requests are prefetched during
the current step and priced with the transfer hidden up to the step's
compute time (``perfmodel.overlapped_transfer_time``).

The engine runs REAL model numerics (any paged-servable family in the zoo)
on tiny configs in CI; its per-step wall-times are additionally priced by
core/perfmodel.py so end-to-end TTFT/RCT in *simulated seconds* are reported
for the benchmark harness. The scheduler and paging logic are shared with the
discrete-event simulator — one implementation, two clocks.

Coordinator integration (consumer side): at engine construction, AQUA-LIB
requests offloaded memory (/allocate); every ``respond_every`` iterations the
engine polls pending reclaims (the paper's ``aqua.respond()``) and evacuates
donor pools at the iteration boundary.
"""
from __future__ import annotations

import math
from dataclasses import dataclass, field, fields as dataclass_fields
from typing import Dict, List, Optional, Sequence

import jax.numpy as jnp
import numpy as np
from jax.profiler import StepTraceAnnotation, TraceAnnotation

from repro.configs.base import ModelConfig
from repro.core.aqua_tensor import HOST, REMOTE
from repro.core.coordinator import Coordinator
# re-exported for backward compatibility: SchedulingInvariantError predates
# the typed hierarchy in core/errors.py and callers import it from here
from repro.core.errors import (CancelledError, EngineCrashError,
                               SchedulingInvariantError)  # noqa: F401
from repro.core.faults import InvariantAuditor
from repro.core.perfmodel import (HardwareProfile, ModelCost, TPU_V5E,
                                  overlapped_transfer_time)
from repro.kernels.paged_attention.kernel import last_pages
from repro.models import api, lm
from repro.serving.kv_cache import PagedStateRuntime
from repro.serving.scheduler import (CFSScheduler, Decision, FCFSScheduler,
                                     ReqState, bucket_tokens, fairness_spread,
                                     split_step_budget)


@dataclass
class EngineMetrics:
    sim_time: float = 0.0
    steps: int = 0
    prefills: int = 0                     # prefill chunk rows executed
    preemptions: int = 0
    restores: int = 0
    prefetched_restores: int = 0          # restores overlapped with compute
    overlap_hidden_s: float = 0.0         # transfer time hidden by overlap
    spec_chunks: int = 0                  # speculative chunk-ahead grants
    spec_tokens: int = 0                  # tokens prefilled speculatively
    # speculative tier flips ride OUTSIDE preemptions/restores: each spec
    # chunk parks once after running (spec_chunks parks) and pages its
    # prior speculated prefix back in first (spec_restores); the admission
    # restore of a spec-parked request still counts in `restores`. The
    # preemptions == restores symmetry therefore only holds when
    # speculation never fired (spec_chunks == 0).
    spec_restores: int = 0
    ttft: Dict[int, float] = field(default_factory=dict)
    rct: Dict[int, float] = field(default_factory=dict)
    fairness_trace: List[int] = field(default_factory=list)
    step_times: List[float] = field(default_factory=list)
    prefill_tokens_trace: List[int] = field(default_factory=list)
    fused_calls: int = 0                  # serve_step_paged dispatches
    # paged-attention page steps of the fused steps, summed over kv layers:
    # those the kernel walks (each row up to its last held page,
    # ``kernel.last_pages``) and those of its grid (every row x every page
    # of max_seq); 1 - held / grid is the share it skips
    attn_pages_held: int = 0
    attn_pages_grid: int = 0
    # fault-tolerance accounting (zero on a fault-free run): transfer-leg
    # retries absorbed by bounded backoff, donor losses / lease shrinks
    # applied, pages live-migrated off shrinking donors, and the requests
    # whose pages died with a donor and were recomputed from the prompt
    leg_retries: int = 0
    donor_losses: int = 0
    lease_shrinks: int = 0
    migrated_pages: int = 0
    recomputes: int = 0
    recovered_rids: List[int] = field(default_factory=list)
    # burst/admission observability: waiting-queue depth at each plan, the
    # run+waiting set's occupied fraction of the page budget (max over
    # planes, marginal under prefix sharing), and cumulative defer
    # decisions by the SLO-aware admission controller (0 with admission
    # off — arrivals go straight to the scheduler)
    queue_depth_trace: List[int] = field(default_factory=list)
    occupancy_trace: List[float] = field(default_factory=list)
    admission_deferrals: int = 0
    # request-lifecycle accounting: submissions, teardowns before
    # completion (client cancels + deadline expiries + fault cancels),
    # the deadline-expiry subset, requests parked by a graceful drain,
    # and no-progress watchdog escalations into the recovery ladder
    submitted: int = 0
    cancelled: int = 0
    deadline_missed: int = 0
    drained: int = 0
    watchdog_trips: int = 0

    def ttft_quantile(self, q: float, *, censored: int = 0) -> float:
        """TTFT quantile on the simulated clock (nan when nothing finished
        a first token yet) — p50/p99 reporting for the burst benchmarks.

        ``censored`` makes right-censoring EXPLICIT instead of silently
        excluded: that many submitted-but-never-first-token requests
        (cancelled, expired, still queued at measurement time) are counted
        as +inf observations, so a quantile landing in the censored tail
        returns ``inf`` — the honest answer when e.g. p99 asks about a
        request that never got a first token. The engine's own count is
        ``metrics.submitted - len(metrics.ttft)``. The default (0)
        preserves the historical finished-only quantile."""
        xs = sorted(self.ttft.values())
        n = len(xs) + max(int(censored), 0)
        if n == 0:
            return float("nan")
        i = min(int(q * n), n - 1)
        return float(xs[i]) if i < len(xs) else float("inf")


class ServingEngine:
    def __init__(self, cfg: ModelConfig, params, *, max_running: int = 4,
                 max_seq: int = 128, scheduler: str = "cfs",
                 slice_tokens: int = 4, offload_tier: int = REMOTE,
                 kv: Optional[PagedStateRuntime] = None,
                 kv_page_tokens: int = 8,
                 kv_local_pages: Optional[int] = None,
                 kv_host_pages: int = 8192,
                 prefix_sharing: bool = True,
                 prefix_cache: bool = True,
                 paged_impl: str = "pallas",
                 step_tokens: Optional[int] = None,
                 prefetch: bool = True,
                 spec_chunk_ahead: bool = True,
                 coordinator: Optional[Coordinator] = None,
                 name: str = "llm0", hw: HardwareProfile = TPU_V5E,
                 want_remote_bytes: float = 0.0, respond_every: int = 4,
                 mesh=None, faults=None, audit: bool = False,
                 admission: bool = False, admission_headroom: float = 0.9,
                 prefill_admit_limit: Optional[int] = 4,
                 slo_ttft_s: Optional[float] = None,
                 watchdog_steps: Optional[int] = None):
        """Build a serving engine on the unified paged state runtime.

        Args:
            cfg: model config (must be paged-servable) and ``params`` its
                weights pytree.
            max_running: batch slots (concurrent decode lanes).
            max_seq: maximum context length per request.
            scheduler: ``"cfs"`` (fair, preempting) or ``"fcfs"``.
            slice_tokens: CFS fair-pick period in generated tokens.
            offload_tier: preferred park tier (``REMOTE`` fabric / ``HOST``).
            kv: an existing :class:`PagedStateRuntime` to serve on; by
                default one is built from the ``kv_*`` sizing knobs.
            prefix_sharing: enable copy-on-write prompt-prefix sharing
                (effective only on all-token-plane families).
            prefix_cache: retain refcount-0 prefix pages in the radix
                index as a global prefix cache (evicted cold-first under
                page pressure); effective only with ``prefix_sharing``.
            paged_impl: ``"pallas"`` kernels (interpret on CPU) or the
                ``"xla"`` jnp oracles.
            step_tokens: per-step token budget for chunked prefill
                (``None`` = whole-prompt chunks); must be >= 8.
            prefetch: overlap next-step page restores with compute.
            spec_chunk_ahead: when the step's token budget has slack after
                every admitted prefill is fully granted, speculatively
                prefill WAITING requests' next chunks — arrival order,
                extending past the head-of-line waiter while page headroom
                allows (each grant page-headroom guarded, parked right
                after) — instead of idling the slack. Effective only with
                a ``step_tokens`` budget.
            coordinator/want_remote_bytes/respond_every: AQUA-LIB consumer
                wiring — lease donor HBM at construction, poll reclaims
                every ``respond_every`` steps.
            name: engine id used in coordinator bookkeeping and errors.
            hw: hardware profile pricing the simulated clock.
            mesh: optional ``MeshTierDomain`` — REMOTE parks become real
                collective page moves to peer-device donor slabs, and
                :meth:`calibrate_clock` can refit ``hw``'s fabric link to
                the measured transfer times. Ignored when ``kv`` is given
                (the runtime's own mesh wins).
            faults: optional ``core/faults.FaultInjector`` — attached to
                every plane and the mesh so transfer legs and lease
                boundaries consult it; its step-scheduled ``FaultEvent``\\s
                (donor loss, lease shrink) are applied at the top of each
                engine step, with live migration / recompute-from-prompt
                recovery and scheduler budget re-planning.
            admission: layer the SLO-aware admission controller
                (``serving/admission.py``) ahead of the scheduler — waiting
                requests enter the scheduler's view only while the
                committed set's projected KV-occupancy trajectory (each
                request priced at its marginal per-plane page cost, growing
                to its terminal context) stays inside
                ``admission_headroom`` x the page budget; everything else
                defers in the queue (never rejected). Composes with
                ``_replan_capacity``: a lease shrink or donor loss
                contracts the stability region the next step.
            admission_headroom: fraction of the page budget the projected
                trajectory may fill (the rest absorbs projection error).
            prefill_admit_limit: with admission on, max requests in their
                prefill phase at once while decode lanes are live
                (prefill/decode priority mixing; ``None`` = uncapped).
            slo_ttft_s: optional TTFT SLO in simulated seconds — admissions
                whose projected prefill completion misses it are counted
                (``admission.slo_at_risk``), observational only.
            watchdog_steps: flag any RESIDENT request whose combined
                prefill+decode progress hasn't advanced for this many
                steps (a starved prefill behind a saturated decode batch,
                a fault-wedged restore) and escalate it through the
                recovery ladder's recompute rung (``_recover_lost``:
                release, requeue, recompute) so the slot it wedged comes
                back. ``None`` (default) disables the watchdog.
            audit: run a full ``InvariantAuditor`` pass after EVERY step
                (refcounts vs block tables vs tier occupancy vs meter and
                collective counters) — a debug mode that fails loudly on
                state corruption instead of letting it surface as wrong
                logits later.

        Raises:
            ValueError: the family is not paged-servable, or
                ``step_tokens < 8``.
        """
        self.cfg = cfg
        self.params = params
        self.max_running = max_running
        self.max_seq = max_seq
        self.name = name
        self.hw = hw
        self.cost = ModelCost.from_config(cfg)
        self.weight_bytes = cfg.param_count() * cfg.dtype().itemsize
        self.offload_tier = offload_tier
        self.paged_impl = paged_impl

        if not api.supports_paged(cfg):
            raise ValueError(
                f"{cfg.name}: not paged-servable — windowed ring-buffer / "
                "softcap / encoder-decoder layers have no page plane yet "
                "(ROADMAP follow-up); the dense blob runtime is gone")

        if step_tokens is not None and step_tokens < 8:
            raise ValueError("step_tokens must be >= 8 (one chunk bucket)")
        self.step_tokens = step_tokens
        self.prefetch = prefetch
        self.spec_chunk_ahead = spec_chunk_ahead

        self.kv = kv or PagedStateRuntime(
            cfg, max_seq=max_seq, page_tokens=kv_page_tokens,
            local_pages=kv_local_pages, host_pages=kv_host_pages,
            max_running=max_running, prefix_sharing=prefix_sharing,
            prefix_cache=prefix_cache, mesh=mesh)
        self.pager = self.kv
        # the scheduler plans in PAGES (a per-plane cost vector). CFS
        # revisits the run set every slice, so it budgets one slice of
        # growth; FCFS never preempts, so an admitted request must fit the
        # LOCAL pools to COMPLETION.
        page_cost = (self._page_cost_cfs if scheduler == "cfs"
                     else self._page_cost_fcfs)
        page_budget = self.kv.page_budget
        # chunk block tables pad to the request's max pages PLUS the write
        # window of the largest chunk bucket: ONE table shape for every
        # (chunk, context-length) combination
        hi = bucket_tokens(max_seq)
        self._pps_pad = (self.kv.pps
                         + math.ceil(hi / self.kv.page_tokens) + 1)

        self.coord = coordinator
        self.respond_every = respond_every
        if coordinator is not None and want_remote_bytes > 0:
            for donor, nbytes in coordinator.allocate(name, want_remote_bytes):
                self.pager.add_remote_lease(donor, nbytes)
                self._grants = getattr(self, "_grants", []) + [(donor, nbytes)]

        self.slice_tokens = slice_tokens
        self._free_slots = list(range(max_running))[::-1]
        # prefix-aware co-scheduling: requests adopting the same root-edge
        # radix node cluster behind their group's earliest member within a
        # vruntime class, so a shared prefix parks/restores once per plan
        prefix_group = ((lambda r: self.kv.prefix_group_of(r.rid))
                        if self.kv.sharing else None)
        self.sched = (CFSScheduler(max_running, slice_tokens,
                                   page_cost=page_cost,
                                   page_budget=page_budget,
                                   prefix_group=prefix_group)
                      if scheduler == "cfs"
                      else FCFSScheduler(max_running, page_cost=page_cost,
                                         page_budget=page_budget))
        self.waiting: List[ReqState] = []
        self.running: List[ReqState] = []
        self.finished: List[ReqState] = []
        self._prefetched: List[ReqState] = []
        self.metrics = EngineMetrics()
        self._next_rid = 0
        # request-lifecycle state: drain gate, watchdog progress marks
        self.watchdog_steps = watchdog_steps
        self._draining = False
        self._watch: Dict[int, tuple] = {}
        # constructor knobs a crash-consistent snapshot must carry so
        # `restore` can rebuild an equivalently-sized engine. The
        # local-pages knob only sizes TOKEN planes (state-plane pools
        # derive from max_running), so read it back off one of those.
        tok_plane = next((p for p in self.kv.planes.values()
                          if p.kind == "tokens"), None)
        first_plane = next(iter(self.kv.planes.values()))
        self._snap_knobs = dict(
            max_running=max_running, max_seq=max_seq, scheduler=scheduler,
            slice_tokens=slice_tokens, offload_tier=offload_tier,
            kv_page_tokens=self.kv.page_tokens,
            kv_local_pages=(int(tok_plane.aqua.local_pool.shape[0])
                            if tok_plane is not None else None),
            kv_host_pages=int(first_plane.aqua.host_pool.shape[0]),
            prefix_sharing=self.kv.sharing,
            prefix_cache=bool(getattr(self.kv, "caching", False)),
            paged_impl=paged_impl, step_tokens=step_tokens,
            prefetch=prefetch, spec_chunk_ahead=spec_chunk_ahead,
            name=name, admission=admission,
            admission_headroom=admission_headroom,
            prefill_admit_limit=prefill_admit_limit,
            slo_ttft_s=slo_ttft_s, watchdog_steps=watchdog_steps)

        self.faults = faults
        if faults is not None:
            self.kv.attach_faults(faults)
        self.auditor = InvariantAuditor() if audit else None

        # SLO-aware admission: a one-way gate AHEAD of the scheduler. The
        # budget is read through the scheduler each step, so a fault
        # event's _replan_capacity contracts the stability region with no
        # extra wiring; costs are the schedulers' own marginal per-plane
        # page vectors plus the FCFS-style terminal footprint.
        self.admission = None
        self._eligible_rids: Optional[set] = None
        if admission:
            from repro.serving.admission import AdmissionController
            self.admission = AdmissionController(
                budget=lambda: np.asarray(self.sched.page_budget,
                                          np.float64),
                current_cost=self._page_cost_now,
                terminal_cost=self._page_cost_fcfs,
                remaining_tokens=lambda r: (
                    r.prompt_positions - r.prefill_pos,
                    r.max_new_tokens - len(r.generated)),
                headroom=admission_headroom,
                step_tokens=self.step_tokens,
                prefill_admit_limit=prefill_admit_limit,
                slo_ttft_s=slo_ttft_s,
                step_time=lambda: self.cost.decode_step_time(
                    self.hw, max(len(self.running), 1), self.max_seq / 2,
                    self.weight_bytes),
                # earliest-deadline-first candidate order: urgency, not
                # just age, decides who prices against the region first —
                # deadline-free requests keep their arrival order after
                # every deadline-carrying one
                order_key=lambda r: (
                    (r.arrival + r.deadline_s)
                    if getattr(r, "deadline_s", None) is not None
                    else float("inf"), r.arrival, r.rid),
                # remaining e2e slack — a candidate whose projected finish
                # exceeds it is excluded from the occupancy trajectory
                # (work that will miss anyway must not crowd out work that
                # can still make it)
                deadline_of=lambda r: (
                    None if getattr(r, "deadline_s", None) is None
                    else r.deadline_s - (self.metrics.sim_time - r.arrival)))

    def _shared_discount(self, r: ReqState,
                         chosen: Sequence[ReqState]) -> np.ndarray:
        """PHYSICAL pages this request aliases with the run set chosen so
        far (counted once by the sharer already picked), minus the headroom
        a pending copy-on-write recompute may claim back."""
        if not self.kv.sharing or not chosen:
            return np.zeros(len(self.kv.planes), np.int64)
        disc = self.kv.shared_pages_with(
            r.rid, [o.rid for o in chosen if o.rid != r.rid])
        if r.shared_tokens and r.prefill_pos < r.shared_tokens:
            # the final-position recompute of a fully-matched prompt CoWs
            # the tail shared page in every layer row of each token plane
            disc = np.maximum(disc - self.kv.cow_reserve(), 0)
        return disc

    def _page_cost_cfs(self, r: ReqState,
                       chosen: Sequence[ReqState] = ()) -> np.ndarray:
        """Per-plane PHYSICAL pages the request needs LOCAL through the next
        slice boundary: context now plus one slice of growth (CFS re-plans
        every slice), minus pages shared with the run set chosen so far —
        shared prefixes directly raise admission capacity."""
        base = self.kv.pages_per_request(
            min(r.ctx_len + self.slice_tokens, self.max_seq))
        return base - self._shared_discount(r, chosen)

    def _page_cost_now(self, r: ReqState,
                       chosen: Sequence[ReqState] = ()) -> np.ndarray:
        """Per-plane PHYSICAL pages the request occupies RIGHT NOW (no
        growth term), marginal against ``chosen`` — the admission
        controller's trajectory starting point and the occupancy metric."""
        base = self.kv.pages_per_request(min(r.ctx_len, self.max_seq))
        return base - self._shared_discount(r, chosen)

    def _occupancy_frac(self) -> float:
        """Occupied fraction of the per-plane page budget by the running
        set (max over planes, shared prefixes counted once)."""
        budget = np.maximum(np.asarray(self.sched.page_budget, np.float64),
                            1.0)
        pages = np.zeros(len(self.kv.planes), np.float64)
        chosen: List[ReqState] = []
        for r in self.running:
            pages = pages + self._page_cost_now(r, chosen)
            chosen.append(r)
        return float(np.max(pages / budget))

    def _page_cost_fcfs(self, r: ReqState,
                        chosen: Sequence[ReqState] = ()) -> np.ndarray:
        """FCFS never preempts: an admitted request holds LOCAL pages until
        it completes, so budget its full remaining generation (minus pages
        shared with already-admitted sharers, which stay allocated for as
        long as any referencer lives)."""
        remaining = r.max_new_tokens - len(r.generated)
        base = self.kv.pages_per_request(
            min(r.ctx_len + max(remaining, 0), self.max_seq))
        return base - self._shared_discount(r, chosen)

    # ------------------------------------------------------------------
    def submit(self, prompt_tokens: Sequence[int], max_new_tokens: int,
               arrival: float = 0.0, lora_id: Optional[int] = None,
               prefix_embeds=None, deadline_s: Optional[float] = None,
               ttft_deadline_s: Optional[float] = None) -> ReqState:
        """Queue a request for generation.

        If prefix sharing is enabled (the default on all-token-plane
        families) the prompt is matched against the runtime's prefix index
        here: the longest page-aligned prefix another live request already
        wrote is ADOPTED — the new request's block tables alias those
        physical pages (refcounted, copy-on-write) and its chunked prefill
        starts past the shared prefix (``ReqState.shared_tokens``,
        ``prefill_pos``). At least the final prompt position is always
        recomputed so the first-token logits exist.

        Args:
            prompt_tokens: prompt token ids (ints).
            max_new_tokens: tokens to generate before the request retires.
            arrival: arrival timestamp on the simulated clock (TTFT/RCT are
                reported relative to it).
            lora_id: adapter id; partitions the prefix index (the same
                tokens under a different adapter never alias).
            prefix_embeds: for a VLM config (``cfg.n_prefix_embeds > 0``)
                the (n_prefix, d) / (1, n_prefix, d) patch-embedding block
                occupying the prompt's first positions; omitted, it defaults
                to zeros (the stub frontend's null image). VLM requests
                never share prefixes (the image is not in the hash).
            deadline_s: end-to-end deadline in simulated seconds AFTER
                ``arrival``; once exceeded, the per-step deadline sweep
                cancels the request (terminal state ``"expired"``) and
                reclaims its pages the same step. With admission on, the
                controller also orders candidates earliest-deadline-first
                and excludes projected-to-miss work from its occupancy
                trajectory.
            ttft_deadline_s: first-token deadline on the same base —
                enforced only until the first token lands.

        Returns:
            The queued :class:`ReqState` (its ``generated`` list fills in
            as the engine steps).

        Raises:
            ValueError: ``prefix_embeds`` passed to a non-VLM config.
        """
        r = ReqState(self._next_rid, arrival, list(map(int, prompt_tokens)),
                     max_new_tokens, lora_id=lora_id,
                     deadline_s=deadline_s, ttft_deadline_s=ttft_deadline_s)
        self._next_rid += 1
        self.metrics.submitted += 1
        if self.cfg.n_prefix_embeds:
            P, d = self.cfg.n_prefix_embeds, self.cfg.d_model
            if prefix_embeds is None:
                prefix_embeds = jnp.zeros((1, P, d), self.cfg.dtype())
            prefix_embeds = jnp.asarray(prefix_embeds).reshape(1, P, d)
            r.n_prefix = P
            r.prefix_embeds = prefix_embeds
        elif prefix_embeds is not None:
            raise ValueError(f"{self.cfg.name} takes no prefix embeds")
        if self.kv.sharing and not r.n_prefix:
            shared = self.kv.adopt_prefix(r.rid, r.prompt_tokens,
                                          seed=lora_id)
            if shared:
                r.shared_tokens = shared
                # always leave >= 1 position to compute: the last chunk
                # produces the first-token logits (a full match recomputes
                # the final position, CoW-cloning the tail shared page)
                r.prefill_pos = min(shared, r.prompt_positions - 1)
        self.waiting.append(r)
        return r

    # ------------------------------------------------------------------
    def _respond(self):
        """The paper's aqua.respond(): honor donor reclaims at an iteration
        boundary — evacuate their pools and release the grants."""
        reclaimed = False
        for donor in self.coord.pending_reclaims(self.name):
            self.pager.evict_remote(donor)
            reclaimed = True
            for d, nbytes in list(getattr(self, "_grants", [])):
                if d == donor:
                    self.coord.free(self.name, donor, nbytes)
                    self._grants.remove((d, nbytes))
        if reclaimed:
            self._replan_capacity()

    # ------------------------------------------------------------------
    # lifecycle transition helpers — the ONLY places engine bookkeeping
    # state (batch slots, page ownership, the finished list) may change.
    # Every exit path (finish ladder, cancel, deadline expiry, lost-page
    # recovery, preemption) goes through these, and a CI grep-guard pins
    # each mutation pattern to exactly one occurrence in this file.
    # ------------------------------------------------------------------
    def _free_slot(self, r: ReqState) -> None:
        """Return a request's batch slot to the pool (no-op if slotless)."""
        if r.slot is not None:
            self._free_slots.append(r.slot)
            r.slot = None

    def _release_pages(self, r: ReqState) -> None:
        """Release every plane page a request holds, defensively clearing
        any prefetched restore first: ``_prefetch_restores`` may have
        restored (and pinned) this rid's pages for the NEXT plan in the
        same step it finishes or cancels — the release drops the pin via
        the active set either way, and the stale ``_prefetched`` entry
        must not re-park a retired rid at the next ``_place``."""
        self._prefetched = [p for p in self._prefetched if p.rid != r.rid]
        self.kv.release(r.rid)

    def _retire(self, r: ReqState, terminal: str,
                reason: Optional[str] = None) -> None:
        """The one lifecycle exit: free the slot, release the pages, stamp
        the terminal state (``finished`` / ``cancelled`` / ``expired``) and
        move the request to ``finished``. The caller removes it from
        ``running``/``waiting`` first."""
        m = self.metrics
        self._free_slot(r)
        self._release_pages(r)
        r.parked = None
        r.prefix_embeds = None           # don't pin VLM embeds forever
        r.terminal = terminal
        r.cancel_reason = reason
        r.finish_step = m.steps
        self.finished.append(r)
        self._watch.pop(r.rid, None)
        if self.admission is not None:
            self.admission.forget(r.rid)

    # ------------------------------------------------------------------
    # cancellation, deadlines, drain, watchdog
    # ------------------------------------------------------------------
    def cancel(self, rid: int, *, reason: str = "client") -> bool:
        """Tear a request out of ANY lifecycle state — waiting, prefilling
        mid-chunk, decoding, parked, mid-prefetch, or speculated — and
        reclaim everything it holds, within the current step.

        Mirrors the finish ladder exactly (slot back to the pool, every
        plane page released through refcounts, prefetched restores
        un-pinned, ``admission.forget``), with one addition: the completed
        page-aligned prompt prefix is PUBLISHED into the radix index
        before teardown, so with the prefix cache on the prefill work
        already done is retained for future sharers instead of freed.

        ``reason`` is recorded on the request (``"client"``, ``"deadline"``,
        ``"fault"``); a ``"deadline"`` cancel stamps the ``"expired"``
        terminal state. Idempotent: returns False when ``rid`` is unknown
        or already retired, True when the request was torn down. Callers
        that need the tokens-so-far read them off the returned
        :class:`ReqState` in ``finished``; :meth:`output` raises the typed
        :class:`~repro.core.errors.CancelledError` for them."""
        r = next((x for x in self.running + self.waiting if x.rid == rid),
                 None)
        if r is None:
            return False
        if self.kv.sharing and not r.n_prefix:
            # salvage before teardown: cache-publish the full prompt blocks
            # this request already prefilled (release then free_to_caches
            # them instead of dropping the work)
            self.kv.register_prefix(r.rid, r.prefill_pos)
        if r in self.running:
            self.running.remove(r)
        else:
            self.waiting.remove(r)
        self._retire(r, "expired" if reason == "deadline" else "cancelled",
                     reason=reason)
        self.metrics.cancelled += 1
        return True

    def output(self, rid: int) -> List[int]:
        """Generated tokens of a RETIRED request — the client result path.

        Raises:
            CancelledError: the request was cancelled or expired (the
                typed signal carries ``rid`` and the recorded reason).
            ValueError: ``rid`` is unknown or still in flight.
        """
        r = next((x for x in self.finished if x.rid == rid), None)
        if r is None:
            raise ValueError(f"request {rid} is unknown or still in flight")
        if r.terminal in ("cancelled", "expired"):
            raise CancelledError(
                f"request {rid} was {r.terminal} "
                f"({r.cancel_reason or 'no reason recorded'})",
                rid=rid, reason=r.cancel_reason)
        return list(r.generated)

    def _shed_expired(self) -> None:
        """Enforce both deadline clocks at the top of the step, BEFORE the
        admission gate sees the queue: an expired waiter is shed before it
        can be admitted, an expired runner is cancelled and its pages
        reclaimed the same step. TTFT deadlines only bind until the first
        token landed."""
        m = self.metrics
        for r in list(self.waiting) + list(self.running):
            age = m.sim_time - r.arrival
            ttft_miss = (r.ttft_deadline_s is not None
                         and r.rid not in m.ttft
                         and age > r.ttft_deadline_s)
            e2e_miss = r.deadline_s is not None and age > r.deadline_s
            if (ttft_miss or e2e_miss) \
                    and self.cancel(r.rid, reason="deadline"):
                m.deadline_missed += 1

    def _watchdog(self) -> None:
        """Flag resident requests making NO prefill+decode progress for
        ``watchdog_steps`` consecutive steps — a prefill starved to
        zero-token chunks behind a saturated decode batch holds its slot
        and pages indefinitely — and escalate through the recovery
        ladder's recompute rung (:meth:`_recover_lost`): pages released,
        request requeued, context recomputed bit-identically on its next
        admission. The lower rungs (bounded leg retry, live migration)
        already ran inside the data plane; a request still stuck after
        them has nothing left to wait for."""
        m = self.metrics
        for r in list(self.running):
            prog = r.prefill_pos + len(r.generated)
            last, since = self._watch.get(r.rid, (None, m.steps))
            if prog != last:
                self._watch[r.rid] = (prog, m.steps)
            elif m.steps - since >= self.watchdog_steps:
                m.watchdog_trips += 1
                self._watch.pop(r.rid, None)
                self._recover_lost(r.rid)

    def drain(self) -> int:
        """Graceful drain: stop admitting work and park every restorable
        request to HOST, returning (synchronously) once the engine is
        quiescent — no batch slot held, no active pins, no in-flight
        prefetch. Queued requests stay queued; in-flight ones keep their
        progress parked on the host tier and resume bit-identically after
        :meth:`resume` (park/restore round-trips are exact). While
        draining, ``step()`` admits nothing, speculates nothing and
        prefetches nothing. Returns the number of requests parked; the
        ``drained`` metric accrues it.

        A drained engine is also the cheapest snapshot point — every
        payload already sits on the slow tier — though :meth:`snapshot`
        works mid-stream too."""
        m = self.metrics
        self._draining = True
        n = 0
        for r in list(self.running):
            self.kv.park(r.rid, r.resident_tokens, prefer=HOST,
                         cause="drain")
            r.parked = True
            self._free_slot(r)
            self.running.remove(r)
            self.waiting.append(r)
            n += 1
        self._prefetched = []
        for r in self.waiting:
            # prefetched restores / speculated chunks left pages active
            if r.rid in self.kv._active:
                self.kv.park(r.rid, r.resident_tokens, prefer=HOST,
                             cause="drain")
                r.parked = True
                n += 1
        m.drained += n
        return n

    def resume(self) -> None:
        """Reopen admission after :meth:`drain`; the next plan restores
        the parked set through the normal placement path."""
        self._draining = False

    # ------------------------------------------------------------------
    # fault application and recovery
    # ------------------------------------------------------------------
    def _replan_capacity(self):
        """Contract the scheduler's admission budget after tiers shrink.

        The planning budget stays the LOCAL pool sizes (the run set must fit
        LOCAL), additionally capped by the runtime's TOTAL live capacity —
        after a lease shrink or donor loss the tiers backing preemption may
        hold fewer pages than LOCAL itself, and admitting up to the LOCAL
        budget would wedge the first park."""
        self.sched.update_budget(
            np.minimum(self.kv.page_budget, self.kv.total_capacity()))

    def _recover_lost(self, rid: int):
        """Degrade-to-host recovery for a request whose pages died with a
        donor: release every surviving page, reset the request to the start
        of prefill, and re-queue it — the greedy decode loop regenerates
        bit-identical tokens from the prompt. A still-resident shared
        prefix (other sharers' pages survived LOCAL/HOST) is re-adopted so
        the recompute starts past it, not from position zero."""
        m = self.metrics
        r = next((x for x in self.running + self.waiting if x.rid == rid),
                 None)
        if r is None or r.done:
            return
        self._free_slot(r)
        if r in self.running:
            self.running.remove(r)
        self._release_pages(r)
        r.parked = None
        r.prefill_pos = 0
        r.generated = []
        r.shared_tokens = 0
        if self.kv.sharing and not r.n_prefix:
            shared = self.kv.adopt_prefix(r.rid, r.prompt_tokens,
                                          seed=r.lora_id)
            if shared:
                r.shared_tokens = shared
                r.prefill_pos = min(shared, r.prompt_positions - 1)
        if r not in self.waiting:
            self.waiting.append(r)
        if self.admission is not None:
            # the victim resets to prefill position 0 AND the stability
            # region just contracted — it must re-price before re-entry
            self.admission.forget(rid)
        m.recomputes += 1
        m.recovered_rids.append(rid)

    def _apply_faults(self) -> float:
        """Apply the injector's scheduled fault events, then re-plan
        admission capacity. The poll is DUAL-CLOCK — ``at_step`` events
        fire on the engine's step counter, ``at_time`` events on its
        simulated clock — so one schedule (e.g. ``make_cancel_events``)
        drives the engine and the byte-clock simulator alike. A
        ``lease_shrink`` live-migrates the reclaimed slots' pages to
        surviving donors or the host tier; a ``donor_loss`` flips the
        donor's pages to LOST and sends every victim request through
        :meth:`_recover_lost`; a ``cancel`` tears the named request down
        through :meth:`cancel`; an ``engine_crash`` raises
        :class:`~repro.core.errors.EngineCrashError` — the harness
        discards this engine and rebuilds from the latest
        :meth:`snapshot` via :meth:`restore`. Returns the metered
        transfer time the recovery work cost (migration page moves)."""
        m = self.metrics
        t_before = self.pager.meter.sim_time
        fired = False
        for ev in self.faults.due_events(step=m.steps, now=m.sim_time):
            if ev.kind == "engine_crash":
                raise EngineCrashError(
                    f"{self.name}: seeded engine_crash fired at step "
                    f"{m.steps} — rebuild from the latest snapshot "
                    "(ServingEngine.restore)")
            if ev.kind == "cancel":
                if ev.rid is not None:
                    self.cancel(int(ev.rid), reason="fault")
                continue
            fired = True
            if ev.kind == "lease_shrink":
                m.lease_shrinks += 1
                m.migrated_pages += self.kv.shrink_lease(ev.donor, ev.frac)
            elif ev.kind == "donor_loss":
                m.donor_losses += 1
                for rid in self.kv.fail_donor(ev.donor):
                    self._recover_lost(rid)
        if fired:
            self._replan_capacity()
        return self.pager.meter.sim_time - t_before

    # ------------------------------------------------------------------
    def calibrate_clock(self, *, min_samples: int = 4) -> bool:
        """Refit the analytic clock against MEASURED mesh transfers.

        On a mesh-backed runtime every warm collective leg was wall-clocked
        (``MeshTierDomain.samples``); this fits the latency+bandwidth link
        model to those samples (``perfmodel.calibrate_profile``) and swaps
        the calibrated profile into both pricing paths — ``self.hw`` (step
        compute / page-flip times) and the runtime's ``TransferMeter`` — so
        every simulator and benchmark number downstream inherits real
        fabric costs. Returns True when the clock actually changed (False
        without a mesh or with too few samples to fit)."""
        dom = getattr(self.kv, "mesh", None)
        if dom is None:
            return False
        hw2 = dom.calibrated_profile(self.hw, min_samples=min_samples)
        if hw2 is self.hw:
            return False
        self.hw = hw2
        self.pager.meter.hw = hw2
        return True

    # ------------------------------------------------------------------
    def step(self):
        """Run ONE engine step: plan the run set, execute the plan as a
        single fused call.

        In order: (1) poll coordinator reclaims every ``respond_every``
        steps; (2) ``sched.plan`` picks the run set under the physical-page
        budget; (3) ``_place`` parks preempted requests (page-table tier
        flips) and slots + restores scheduled ones; (4) the WHOLE step's
        work — one decode token per resident prefilled request plus every
        pending prefill's fair-share chunk under the ``step_tokens`` budget
        (plus speculative chunks for waiting prefills when the budget has
        slack) — is packed into ONE ``api.serve_step_paged``
        call; (5) finished requests retire (pages released — shared prefix
        pages survive while any sharer lives); (6) next step's restores are
        prefetched, priced as hidden up to this step's compute time.
        Metrics (TTFT/RCT on the simulated clock, step times, fused calls,
        fairness spread) accrue on ``self.metrics``.

        Each step is an ``aqua.step`` profiler span (its ``step_num`` and
        ``kind``: decode, mixed, chunk or idle) holding ``aqua.step.plan``,
        ``.place``, ``.pack``, ``.dispatch``, ``.readback``, ``.retire`` and
        ``.prefetch`` spans; a span costs about a microsecond when no
        profiler runs.

        Raises:
            SchedulingInvariantError: the planned run set needs more batch
                slots than exist — a scheduler bug, never silent.
            MemoryError: a page allocation or tier flip found every slot of
                the target tier full (the page-budget-aware schedulers are
                designed to keep plans below this point).
        """
        with StepTraceAnnotation("aqua.step",
                                 step_num=self.metrics.steps) as span:
            self._step(span)

    def _step(self, span):
        m = self.metrics
        if self.coord is not None and m.steps % self.respond_every == 0:
            self._respond()
        fault_time = (self._apply_faults() if self.faults is not None
                      else 0.0)
        self._shed_expired()

        with TraceAnnotation("aqua.step.plan"):
            decision, lanes, pending, chunks, flops_slack = self._plan()

        with TraceAnnotation("aqua.step.place"):
            transfer_time = self._place(decision)

        self.running = [r for r in decision.run if r.slot is not None]
        self.waiting = [r for r in self.waiting + decision.preempt
                        if r.slot is None and not r.done]

        # all the step's model work — decode lanes + prompt chunks (+ a
        # speculative chunk-ahead when the budget has slack) — in ONE call
        live = [r for r in self.running if not r.done and r.prefilled]
        chunk_plan = [(r, n) for r, n in zip(pending, chunks)
                      if n > 0 and r.slot is not None]
        with TraceAnnotation("aqua.step.plan"):
            specs = self._pick_speculative(decision, len(lanes), chunks,
                                           len(chunk_plan), flops_slack)
        kind, compute_time, fused_transfer = self._fused_step(
            live, chunk_plan, specs)
        span.set_metadata(kind=kind)
        step_time = compute_time + transfer_time + fused_transfer + fault_time

        # retire bookkeeping first: freed slots/pages raise the odds the
        # prefetch below fits (times are stamped after the prefetch)
        retired = []
        with TraceAnnotation("aqua.step.retire"):
            for r in list(self.running):
                if r.done:
                    self.running.remove(r)
                    self._retire(r, "finished")
                    retired.append(r)

        if self.watchdog_steps is not None:
            self._watchdog()

        with TraceAnnotation("aqua.step.prefetch"):
            step_time += self._prefetch_restores(compute_time)

        # TTFT: one accounting for prefill- and decode-produced first tokens —
        # the time the step COMPLETES, including everything accrued in it
        # (the visible excess of a prefetched restore included)
        for r in self.running + retired:
            if r.generated and r.rid not in m.ttft:
                r.ttft_step = m.steps
                m.ttft[r.rid] = m.sim_time + step_time - r.arrival
        for r in retired:
            m.rct[r.rid] = m.sim_time + step_time - r.arrival

        m.sim_time += step_time
        m.steps += 1
        m.step_times.append(step_time)
        m.fairness_trace.append(
            fairness_spread(self.waiting + self.running))
        m.leg_retries = (self.pager.meter.retries_fabric
                         + self.pager.meter.retries_host)
        if self.auditor is not None:
            self.auditor.audit(self.kv, engine=self)

    def _plan(self) -> tuple:
        """Admission, the scheduler's plan and the step's token budget:
        ``(decision, decode lanes, pending prefills, their chunks, the
        decode launch's FLOPs slack)``."""
        m = self.metrics
        # admission gate: the scheduler only ever sees the eligible subset
        # of the queue — deferred requests stay waiting (degrade-to-queue)
        # until completions reopen the stability region. While draining,
        # NOTHING is eligible: the queue holds until resume().
        m.queue_depth_trace.append(len(self.waiting))
        if self._draining:
            eligible = []
            self._eligible_rids = set()
        elif self.admission is not None:
            eligible, deferred = self.admission.filter(self.waiting,
                                                       self.running)
            m.admission_deferrals += len(deferred)
            self._eligible_rids = {r.rid for r in eligible}
        else:
            eligible = self.waiting
            self._eligible_rids = None
        m.occupancy_trace.append(self._occupancy_frac())

        decision = self.sched.plan(m.steps, eligible, self.running)

        # the step's token budget: one token per decode lane, the remainder
        # handed out as prompt chunks (several requests' chunks per step).
        # With decode lanes present the chunk budget is additionally capped
        # by the launch's memory-bound FLOPs slack (the roofline piggyback
        # window): chunk tokens beyond it stop riding the decode stream for
        # free and extend the step linearly.
        lanes = [r for r in decision.run if r.prefilled and not r.done]
        pending = [r for r in decision.run if not r.prefilled]
        flops_slack = None
        if self.step_tokens is not None and lanes:
            ctx_mean = float(np.mean([r.ctx_len for r in lanes]))
            flops_slack = self.cost.piggyback_tokens(
                self.hw, len(lanes), ctx_mean, self.weight_bytes)
        chunks = split_step_budget(
            self.step_tokens, len(lanes),
            [r.prompt_positions - r.prefill_pos for r in pending],
            flops_slack=flops_slack)
        return decision, lanes, pending, chunks, flops_slack

    # ------------------------------------------------------------------
    # placement: park preempted requests, slot + restore the scheduled set
    # ------------------------------------------------------------------
    def _place(self, decision: Decision) -> float:
        """Execute a plan's page-table moves (park the preempted, slot and
        restore the scheduled). Returns the metered transfer time."""
        m = self.metrics
        t_before = self.pager.meter.sim_time
        if self._prefetched:
            # prefetch misprediction (a submit() between steps changed the
            # plan): re-park so LOCAL holds only the planned run set — the
            # page-budget invariant ensure_capacity relies on
            run_ids = {r.rid for r in decision.run}
            for r in self._prefetched:
                if (r.parked is None and r.slot is None and not r.done
                        and r.rid not in run_ids):
                    self.kv.park(r.rid, r.resident_tokens,
                                 prefer=self.offload_tier, cause="mispredict")
                    r.parked = True
            self._prefetched = []
        for r in decision.preempt:
            # only r.resident_tokens of context exist in the pools: the
            # newest generated token's state lands at its next decode step
            self.kv.park(r.rid, r.resident_tokens, prefer=self.offload_tier,
                         cause="preempt")
            r.parked = True
            self._free_slot(r)
            m.preemptions += 1
        for r in decision.run:
            if r.slot is not None:
                continue
            if not self._free_slots:
                raise SchedulingInvariantError(
                    f"{self.name}: planned run set needs a slot for request "
                    f"{r.rid} but none are free (max_running="
                    f"{self.max_running}) — scheduler exceeded the slot cap")
            r.slot = self._free_slots.pop()
            if r.parked:
                # ensure_local: coalesced page-in
                self.kv.restore(r.rid, cause="admit")
                r.parked = None
                m.restores += 1
        return self.pager.meter.sim_time - t_before

    # ------------------------------------------------------------------
    # prefetch: restore next step's scheduled requests DURING this step,
    # pricing the transfer as hidden up to the step's compute time
    # ------------------------------------------------------------------
    def _prefetch_restores(self, compute_time: float) -> float:
        if not self.prefetch or not (self.waiting or self.running):
            return 0.0
        m = self.metrics
        # under admission control, prefetch only what the controller would
        # let the next plan see — restoring a deferred request's pages
        # would pull unadmitted work LOCAL
        pool = (self.waiting if self._eligible_rids is None
                else [r for r in self.waiting
                      if r.rid in self._eligible_rids])
        nxt = self.sched.peek(m.steps + 1, pool, self.running)
        t_before = self.pager.meter.sim_time
        for r in nxt.run:
            if r.parked and self.kv.can_restore(r.rid):
                self.kv.restore(r.rid, cause="prefetch")
                r.parked = None
                m.restores += 1
                m.prefetched_restores += 1
                self._prefetched.append(r)
        transfer = self.pager.meter.sim_time - t_before
        if transfer <= 0.0:
            return 0.0
        visible = overlapped_transfer_time(compute_time, transfer)
        m.overlap_hidden_s += transfer - visible
        return visible

    # ------------------------------------------------------------------
    # the fused step: ALL model work in one jitted call
    # ------------------------------------------------------------------
    def _pick_speculative(self, decision: Decision, n_lanes: int,
                          chunks: List[int], n_chunk_rows: int = 0,
                          flops_slack: Optional[int] = None) -> List:
        """Speculative chunk-ahead: when ``split_step_budget`` left slack
        (every admitted prefill fully granted this step), hand it to
        WAITING prefills — arrival order, PAST the head-of-line waiter
        while slack and page headroom allow — as extra chunks riding the
        same fused call. Each grant is capped at ``remaining - 1``
        positions (the final position — and the first token — stays for
        admission), must be worth at least one page (a sub-page grant
        would pay the chunk's park/restore flips for almost no prefill
        progress), skips requests preempted THIS step (re-restoring them
        immediately would turn the optimization into pure tier-flip
        thrash), and is page-headroom guarded: the whole speculative
        context must fit the free LOCAL slots of every plane, net of
        earlier grants. The slack is also capped by the decode launch's
        FLOPs piggyback window (``flops_slack``) and the fixed packed row
        budget (specs never widen the fused call's row bucket). Returns a
        list of ``(request, n_tokens)`` grants, possibly empty.

        The headroom check is advisory — the run set's own same-step
        growth (fresh decode pages, CoW clones) allocates first, so
        ``_fused_step`` still treats every speculative allocation as
        fallible and drops the row (and the grants after it) on
        ``MemoryError``."""
        if not self.spec_chunk_ahead or self.step_tokens is None:
            return []
        slack = self.step_tokens - n_lanes - sum(chunks)
        if flops_slack is not None:
            slack = min(slack, max(int(flops_slack) - sum(chunks), 0))
        if slack < self.kv.page_tokens:
            return []
        max_rows = bucket_tokens(self.max_running + 1, lo=1) - n_chunk_rows
        skip = {r.rid for r in decision.run}
        skip.update(r.rid for r in decision.preempt)
        cands = sorted((r for r in self.waiting
                        if r.rid not in skip and not r.prefilled
                        and not r.done and r.slot is None
                        and (self._eligible_rids is None
                             or r.rid in self._eligible_rids)),
                       key=lambda r: (r.arrival, r.rid))
        free = np.asarray([p.aqua.local_free
                           for p in self.kv.planes.values()], np.int64)
        picks: List = []
        for r in cands:
            if len(picks) >= max_rows or slack < self.kv.page_tokens:
                break
            n = min(slack, r.prompt_positions - 1 - r.prefill_pos)
            if n < self.kv.page_tokens:
                continue
            need = self.kv.pages_per_request(r.prefill_pos + n)
            if np.all(need <= free):
                picks.append((r, n))
                slack -= n
                free = free - need
        return picks

    def _fused_step(self, live: List[ReqState], chunk_plan: List,
                    specs: List) -> tuple:
        """Pack the step's work into one ``api.serve_step_paged`` call.

        Rows ``[0, max_running)`` are the decode lanes (present whenever
        any resident request decodes; idle lanes point at scratch), the
        following rows one prompt chunk each — the run set's fair-share
        chunks plus the speculative chunk-ahead grants — bucket-padded in
        both axes. Returns the program's kind (``lm.step_kind``, or
        ``idle`` when nothing runs) and ``(compute_time,
        metered_transfer_time)`` on the analytic clock, including the O(1)
        per-step launch overhead (``ModelCost.launch_time``)."""
        m = self.metrics
        rows_chunk = list(chunk_plan) + list(specs)
        spec_rids = {r.rid for r, _ in specs}
        if not live and not rows_chunk:
            m.prefill_tokens_trace.append(0)
            return "idle", 0.0, 0.0
        with TraceAnnotation("aqua.step.pack"):
            t_before = self.pager.meter.sim_time
            n_dec = self.max_running if live else 0
            # packed shapes: with a step budget, the chunk region is FIXED
            # at (max_running + 1 rows) x (budget bucket) whenever any chunk
            # runs, so the jit cache is provably flat in the number of
            # admitted requests (chunk rows — run-set chunks plus
            # speculative grants — are capped at that fixed row bucket by
            # _pick_speculative); the all-decode steady state stays at
            # Tc = 1 with no chunk region. Unbudgeted (step_tokens=None)
            # chunks are whole prompts, so their shapes ride the
            # prompt-length bucket ladder instead.
            if not rows_chunk:
                Tc, Rp = 1, 0
            elif self.step_tokens is not None:
                Tc = bucket_tokens(self.step_tokens)
                Rp = bucket_tokens(self.max_running + 1, lo=1)
            else:
                Tc = bucket_tokens(max(n for _, n in rows_chunk))
                Rp = bucket_tokens(len(rows_chunk), lo=1)
            R = n_dec + Rp
            tokens = np.zeros((R, Tc), np.int32)
            q_starts = np.zeros((R,), np.int32)
            n_reals = np.zeros((R,), np.int32)
            row_rids: List[Optional[int]] = [None] * R
            prefix_rows = None
            if self.cfg.n_prefix_embeds:
                prefix_rows = [None] * R
            if live:
                n_reals[:n_dec] = 1          # idle lanes: token 0 at pos 0
                ctx_mean = float(np.mean([r.ctx_len for r in live]))
                for r in live:
                    # the new token's position may cross into a fresh page:
                    # grow the block tables (allocation guarantees LOCAL;
                    # parked requests were already restored in _place). A
                    # decode append landing in a still-shared page copies it
                    # first (CoW).
                    self.kv.ensure_capacity(r.rid, r.ctx_len)
                    self.kv.make_writable(r.rid, r.ctx_len - 1, r.ctx_len)
                    row_rids[r.slot] = r.rid
                    tokens[r.slot, 0] = (r.generated[-1] if r.generated
                                         else r.prompt_tokens[-1])
                    q_starts[r.slot] = r.ctx_len - 1
            for j, (r, n) in enumerate(rows_chunk):
                row = n_dec + j
                start = r.prefill_pos
                if r.rid in spec_rids:
                    if r.parked:
                        m.spec_restores += 1   # its prior prefix pages in
                    try:
                        self.kv.ensure_capacity(r.rid, start + n,
                                                cause="spec")
                    except MemoryError:
                        # the run set's own same-step growth (fresh decode
                        # pages, CoW clones) beat _pick_speculative's
                        # advisory headroom check — speculation is
                        # opportunistic: hand back whatever the attempt
                        # pulled LOCAL and drop this grant and every later
                        # one (specs are the trailing rows; the later grants
                        # haven't allocated yet)
                        self.kv.park(r.rid, r.prefill_pos,
                                     prefer=self.offload_tier, cause="spec")
                        r.parked = True
                        specs = specs[:j - len(chunk_plan)]
                        rows_chunk = rows_chunk[:j]
                        break
                else:
                    self.kv.ensure_capacity(r.rid, start + n)
                # copy-on-write: a fully-matched prompt recomputes its
                # final position INTO the shared tail page — clone it first
                self.kv.make_writable(r.rid, start, start + n)
                row_rids[row] = r.rid
                # a VLM request's first chunks cover its prefix-embedding
                # rows, whose token ids are dummies and whose residual rows
                # come from prefix_embeds instead
                idx = np.arange(n) + start - r.n_prefix
                text = idx >= 0
                tokens[row, :n][text] = np.asarray(r.prompt_tokens,
                                                   np.int32)[idx[text]]
                q_starts[row] = start
                n_reals[row] = n
                if prefix_rows is not None:
                    prefix_rows[row] = r.prefix_embeds
            pre = None
            if prefix_rows is not None:
                P, d = self.cfg.n_prefix_embeds, self.cfg.d_model
                zero = jnp.zeros((1, P, d), self.cfg.dtype())
                pre = jnp.concatenate([p if p is not None else zero
                                       for p in prefix_rows], axis=0)
            bt = self.kv.block_tables(row_rids, pad_to=self._pps_pad)
            kv_plane = self.kv.planes.get("kv")
            if kv_plane is not None:
                last = last_pages(q_starts, n_reals, np.arange(R) < n_dec,
                                  tc=Tc, page=self.kv.page_tokens,
                                  pps=self.kv.pps)
                layers = kv_plane.n_layers
                m.attn_pages_held += layers * int(last.sum() + R)
                m.attn_pages_grid += layers * R * self.kv.pps
            tokens, q_starts, n_reals = (jnp.asarray(tokens),
                                         jnp.asarray(q_starts),
                                         jnp.asarray(n_reals))
        kind = lm.step_kind(n_dec, Tc)
        with TraceAnnotation("aqua.step.dispatch"):
            logits, self.kv.pools = api.serve_step_paged(
                self.params, self.cfg, tokens, self.kv.pools, bt, q_starts,
                n_reals, n_decode=n_dec, prefix_embeds=pre,
                read_pps=self.kv.pps, impl=self.paged_impl)
        m.fused_calls += 1
        with TraceAnnotation("aqua.step.readback"):
            nxt = np.asarray(jnp.argmax(logits, axis=-1))

        compute = 0.0
        ptoks = 0
        with TraceAnnotation("aqua.step.retire"):
            for j, (r, n) in enumerate(rows_chunk):
                r.prefill_pos += n
                if not r.n_prefix:
                    # publish completed full prompt pages into the prefix
                    # index so later arrivals with the same prefix adopt them
                    self.kv.register_prefix(r.rid, r.prefill_pos)
                if r.prefilled:
                    r.generated.append(int(nxt[n_dec + j]))
                m.prefills += 1
                ptoks += n
            for r, n in specs:
                m.spec_chunks += 1
                m.spec_tokens += n
                # hand the pages straight back: a speculative request is
                # not in the planned run set, and LOCAL must only hold that
                # set
                self.kv.park(r.rid, r.prefill_pos, prefer=self.offload_tier,
                             cause="spec")
                r.parked = True
            if live:
                for r in live:
                    r.generated.append(int(nxt[r.slot]))
                # mixed step: the chunk rows share the decode launch's weight
                # pass, so their FLOPs hide under the memory-bound decode
                # stream (ModelCost.fused_step_time) instead of paying a
                # separate per-request launch sequence
                compute += self.cost.fused_step_time(self.hw, len(live),
                                                     ctx_mean,
                                                     self.weight_bytes, ptoks)
            elif ptoks:
                compute += self.cost.prefill_time(self.hw, ptoks)
        # ONE jitted call per step: launches stay O(1) in admitted requests
        compute += self.cost.launch_time(self.hw, 1)
        m.prefill_tokens_trace.append(ptoks)
        return kind, compute, self.pager.meter.sim_time - t_before

    # ------------------------------------------------------------------
    def run(self, max_steps: int = 1000):
        """Step until every submitted request finished (or ``max_steps``);
        honors pending coordinator reclaims before returning. Returns the
        engine's :class:`EngineMetrics`."""
        for _ in range(max_steps):
            if not (self.waiting or self.running):
                break
            self.step()
        if self.coord is not None:
            self._respond()        # don't leave leases dangling after drain
        return self.metrics

    # ------------------------------------------------------------------
    # crash-consistent snapshot / restore
    # ------------------------------------------------------------------
    def snapshot(self) -> Dict:
        """Serialize the FULL serving state to a plain dict — the journal
        record a crash-consistent restart replays.

        Carries: the constructor knobs needed to rebuild an
        equivalently-sized engine, every request's :class:`ReqState`
        (waiting, running and finished — prompts, generated tokens,
        prefill positions, deadlines, terminal stamps), the runtime's
        whole page state through :meth:`PagedStateRuntime.snapshot_state`
        (block tables, page PAYLOADS from whatever tier they sit on, the
        radix prefix tree), the admission controller's admitted set, the
        CFS slice phase, the drain gate and the metrics. Greedy decode
        has no sampler RNG, so no RNG state exists to carry — restart
        determinism is argmax + the chunk-split invariance of prefill.

        Read-only and side-effect-free; call BETWEEN steps (no step
        program in flight). Remote leases are NOT serialized — restored
        pages land on the host tier and the restored engine re-leases
        donor memory through its own constructor/coordinator path.

        Raises:
            PageLossError: a block table still references a LOST page
                (recovery must re-queue its victim before snapshotting).
        """
        def req(r: ReqState) -> Dict:
            return {"rid": r.rid, "arrival": r.arrival,
                    "prompt_tokens": list(r.prompt_tokens),
                    "max_new_tokens": r.max_new_tokens,
                    "generated": list(r.generated),
                    "prefill_pos": r.prefill_pos,
                    "n_prefix": r.n_prefix,
                    "prefix_embeds": (None if r.prefix_embeds is None
                                      else np.asarray(r.prefix_embeds)),
                    "shared_tokens": r.shared_tokens,
                    "ttft_step": r.ttft_step,
                    "finish_step": r.finish_step,
                    "lora_id": r.lora_id,
                    "deadline_s": r.deadline_s,
                    "ttft_deadline_s": r.ttft_deadline_s,
                    "terminal": r.terminal,
                    "cancel_reason": r.cancel_reason}

        metrics: Dict[str, object] = {}
        for f in dataclass_fields(EngineMetrics):
            v = getattr(self.metrics, f.name)
            metrics[f.name] = (dict(v) if isinstance(v, dict)
                               else list(v) if isinstance(v, list) else v)
        return {"version": 1,
                "config": dict(self._snap_knobs),
                "next_rid": self._next_rid,
                "running": [req(r) for r in self.running],
                "waiting": [req(r) for r in self.waiting],
                "finished": [req(r) for r in self.finished],
                "kv": self.kv.snapshot_state(),
                "admitted": (sorted(self.admission._admitted)
                             if self.admission is not None else None),
                "since_switch": getattr(self.sched, "_since_switch", None),
                "draining": self._draining,
                "metrics": metrics}

    @classmethod
    def restore(cls, cfg: ModelConfig, params, snapshot: Dict, *,
                mesh=None, faults=None,
                coordinator: Optional[Coordinator] = None,
                audit: bool = False, hw: HardwareProfile = TPU_V5E,
                **overrides) -> "ServingEngine":
        """Rebuild a serving engine from a :meth:`snapshot` dict — the
        crash-consistent restart path.

        A FRESH engine is constructed from the snapshot's carried knobs
        (``overrides`` win — e.g. attach a new fault injector), the
        runtime's page state is rebuilt payload-for-payload
        (:meth:`PagedStateRuntime.restore_state`; everything lands parked
        on the host tier), and every surviving request re-queues: former
        RUNNERS first (the next plan re-admits them ahead of the
        backlog), each marked parked exactly when it still owns pages.
        The finished list, metric counters, admitted set, CFS slice phase
        and drain gate carry over, so post-restart TTFT/RCT stamps stay
        on the same simulated clock.

        Every restored request then completes BIT-IDENTICALLY to an
        uninterrupted run: park/restore round-trips are exact, greedy
        decode is argmax, and prefill logits are chunk-split-invariant —
        the restart may schedule different chunks, never different
        tokens. Mesh collective counters start fresh, so audit restored
        engines with a NEW :class:`InvariantAuditor`.
        """
        knobs = dict(snapshot["config"])
        knobs.update(overrides)
        eng = cls(cfg, params, mesh=mesh, faults=faults,
                  coordinator=coordinator, audit=audit, hw=hw, **knobs)
        eng.kv.restore_state(snapshot["kv"])

        def req(d: Dict) -> ReqState:
            r = ReqState(d["rid"], d["arrival"], list(d["prompt_tokens"]),
                         d["max_new_tokens"], lora_id=d["lora_id"],
                         deadline_s=d["deadline_s"],
                         ttft_deadline_s=d["ttft_deadline_s"])
            r.generated = list(d["generated"])
            r.prefill_pos = d["prefill_pos"]
            r.n_prefix = d["n_prefix"]
            if d["prefix_embeds"] is not None:
                r.prefix_embeds = jnp.asarray(d["prefix_embeds"])
            r.shared_tokens = d["shared_tokens"]
            r.ttft_step = d["ttft_step"]
            r.finish_step = d["finish_step"]
            r.terminal = d["terminal"]
            r.cancel_reason = d["cancel_reason"]
            if any(r.rid in p.pages for p in eng.kv.planes.values()):
                r.parked = True      # its pages sit on the host tier
            return r

        eng.waiting = ([req(d) for d in snapshot["running"]]
                       + [req(d) for d in snapshot["waiting"]])
        eng.finished = [req(d) for d in snapshot["finished"]]
        eng._next_rid = int(snapshot["next_rid"])
        eng._draining = bool(snapshot["draining"])
        if eng.admission is not None and snapshot["admitted"] is not None:
            eng.admission._admitted = set(snapshot["admitted"])
        if (snapshot["since_switch"] is not None
                and hasattr(eng.sched, "_since_switch")):
            eng.sched._since_switch = snapshot["since_switch"]
        for k, v in snapshot["metrics"].items():
            setattr(eng.metrics, k, dict(v) if isinstance(v, dict)
                    else list(v) if isinstance(v, list) else v)
        return eng
