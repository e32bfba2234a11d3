"""Engine core: continuous-batching step loop with stall-free mixed steps.

A ``step()`` fuses decode and prefill work into ONE runner dispatch: every
running sequence contributes a 1-token decode row, and waiting/resumed
prompts are admitted as *chunks* under a per-step token budget
(``chunk_prefill_tokens``, Sarathi-style stall-free batching) so a long
prompt never stalls the decode stream — it advances a bounded chunk per
step instead. A decode row is just the degenerate final chunk (one token
that samples), so both phases share the same jitted program at different
bucket shapes (see runner.py); there is no separate prefill/decode code
path on device. ``chunk_prefill_tokens=0`` restores the legacy
phase-exclusive behavior (a step is prefill XOR decode) — kept as the
baseline the bench stall probe compares against. Policy details:
``docs/SCHEDULER.md``.

Scheduling policy (extending the engines the reference wraps, vLLM-v0-style
admission + Sarathi-Serve chunking):

- Admission: FIFO from the waiting queue under the prefill token budget and
  page availability; prefix-cache matches reduce the budget charge. Pages
  are allocated per chunk, not per prompt, so a prompt bigger than the
  current free pool admits incrementally instead of head-of-line blocking.
- Decode first: running sequences' next-token pages are reserved before any
  chunk is sized, and decode rows ride every mixed dispatch.
- Preemption: on page exhaustion during decode, the most-recently-arrived
  running sequence is evicted (pages released, tokens kept) and requeued;
  mid-prefill sequences are preferred victims over decoding ones.
  Recomputation re-matches whatever prefix survived in cache and re-chunks.
- Pages commit to the prefix cache as they fill — chunk by chunk, so a long
  prompt's early pages are shareable before its prefill finishes — emitting
  KV stored events; eviction emits removed events (allocator.py). This
  feeds the KV-aware router's global index natively, replacing the
  reference's engine->ZMQ->NATS event bridge (SURVEY.md §3 call stack D).
"""

from __future__ import annotations

import dataclasses
import logging
import os
import threading
import time
from collections import deque
from typing import Callable

import numpy as np

from dynamo_tpu.engine.allocator import OutOfPagesError, PageAllocator, SlotAllocator
from dynamo_tpu.engine.runner import SPLIT, DispatchReport, ModelRunner, StepBatch
from dynamo_tpu.engine.sequence import SeqStatus, Sequence
from dynamo_tpu.observability.flight import CRASH, STEP, FlightRecorder
from dynamo_tpu.protocols.common import EngineOutput, FinishReason, PreprocessedRequest
from dynamo_tpu.protocols.kv import ForwardPassMetrics, KvCacheEvent
from dynamo_tpu.runtime.engine import Context
from dynamo_tpu.runtime.faults import FAULTS, DropFault
from dynamo_tpu.tokens import DEFAULT_SALT
from dynamo_tpu import tracing

logger = logging.getLogger(__name__)

_NO_DISPATCH = DispatchReport()  # what the STEP record of a step that dispatched nothing holds

# Logprobs requests always compute this many alternatives on-device (one
# compiled program; per-request top_logprobs slices host-side — a static
# per-value k would recompile the step program for every distinct request
# setting). 20 = the OpenAI top_logprobs cap.
LOGPROBS_TOP_K = 20

# Every reason an overlapped step can record for barriering (first reason
# wins within a step; "idle" is the default when none was noted). This
# vocabulary is load-bearing: docs/SCHEDULER.md documents each row and
# tools/check_barrier_reasons.py pins both the _note_barrier call sites and
# the docs table against it — the two have drifted before.
BARRIER_REASONS = (
    "cancel",  # cancellation reaped mid-pipeline: in-flight writes are stale
    "runner",  # runner has no step_async (mock timing modes, embedders)
    "prefill",  # legacy XOR mode: whole-prompt prefill steps carry no decodes
    "constraint",  # constrained rows with lookahead disabled (knob = 0)
    "constraint_miss",  # lookahead mask-cache miss or candidate-cap overflow
    "spec",  # verify in flight (harvest-first) or the runner has no async verify
    "drain",  # every live row finishes inside the in-flight step
    "pages",  # sole candidate cannot extend: commit in-flight, then re-check
    "fill",  # pipeline refill: dispatched with nothing in flight
    "idle",  # barrier step with no recorded reason (nothing dispatched)
)


@dataclasses.dataclass
class EngineConfig:
    num_pages: int = 512
    page_size: int = 16
    max_batch_size: int = 64
    max_prefill_tokens: int = 2048  # token budget per prefill step (chunked-prefill cap)
    max_seq_len: int = 4096
    eos_token_ids: tuple[int, ...] = ()
    enable_prefix_caching: bool = True
    # Sliding-window models: release pages whose every token has slid out of
    # the attention window (they can never be attended again). Committed
    # pages demote to evictable prefix cache; uncommitted ones free
    # immediately. A 32k-context window-4k Mistral stream otherwise pins
    # ~28k tokens of dead KV per sequence.
    swa_free_pages: bool = True
    salt: int = DEFAULT_SALT
    worker_id: int = 0
    # The pipelined loop's count of chained pure-decode sub-dispatches a step
    # (_run_mixed_overlapped), and nothing else: >1 harvests that many tokens
    # a row per step() call, at K-token stream granularity. The synchronous
    # loop (overlap=False, the oracle) emits one token a step whatever it says.
    decode_steps: int = 1
    # Per-step prefill token budget while decodable sequences are running:
    # prompts are admitted/advanced in chunks of at most this many tokens,
    # fused with the decode rows in one dispatch, so the longest decode
    # stall is one chunk-step rather than one whole-prompt prefill.
    # Distinct from max_prefill_tokens, which still caps a step with no
    # decodes to coalesce against. 0 disables chunking (legacy
    # prefill-XOR-decode steps; the bench stall probe's baseline).
    chunk_prefill_tokens: int = 512
    # Speculative decoding (DYN_SPEC_K): max draft tokens per decode row per
    # step, verified in one multi-token dispatch. 0 = off. Lossless: output
    # streams are bit-identical to spec_k=0 (greedy and seeded) — the
    # drafter only changes how many forwards the same tokens cost. Draft
    # tokens are charged against chunk_prefill_tokens and the decode-first
    # page reserve grows to cover spec_k+1 slots, so speculation composes
    # with chunked prefill, admission, and preemption (docs/SCHEDULER.md).
    spec_k: int = 0
    # SLO-native admission control (DYN_SLO_SCHED, dynamo_tpu/sched):
    # EDF-over-predicted-TTFT ordering of the waiting queue, per-tenant
    # quotas, and an ITL-driven chunk-budget controller. Off by default —
    # FIFO intake is then bit-identical to the pre-sched scheduler.
    slo_sched: bool = False
    # The pipelined step loop, which is the serving loop: a depth-1 pipeline
    # — step N+1 is composed at the sequences' effective state and dispatched
    # with its decode rows' input tokens chained from N's device-resident
    # samples before N's tokens reach the host, so the host's work of a step
    # hides under the device's program. Mixed steps ride it too: prefill
    # chunk rows feed from host (their tokens are known), decode rows chain;
    # penalty history and the pos_limit write clamp are applied in-graph, so
    # penalized rows and budget-final tokens are not barriers. Constrained
    # (json_mode) rows chain via one-step-lookahead mask groups
    # (constraint_lookahead_tokens); multimodal/mrope rows chain with their
    # extras threaded through the explicit-args chained program; and
    # decode_steps>1 folds into the same pipeline as K chained sub-steps per
    # dispatch. Stops are evaluated one step late; a late-detected stop
    # cancels the in-flight row (its token is discarded, its pages released
    # — output streams are bit-identical to the synchronous step's). What the
    # graph cannot chain barriers to the synchronous step (_overlap_route),
    # by a reason counted in overlap_barrier_counts and the STEP flight
    # records (BARRIER_REASONS is the full vocabulary). docs/SCHEDULER.md.
    # False steps synchronously throughout: the oracle the parity tests
    # compare against, set by no entry point.
    overlap: bool = True
    # Pipelined tier onboarding (DYN_ASYNC_ONBOARD; DYN_CACHE_AWARE also
    # arms it): admission no longer blocks on G2/G3/G4 payload reads — a
    # background session fetches them and they land through the batched
    # write_pages scatter while other rows (and later the same row's own
    # chunks) compute. The scheduler treats the pending pages like an
    # in-flight chunk: num_cached advances only when the session lands; a
    # fetch shortfall degrades to recompute exactly like the synchronous
    # path. Off keeps onboarding synchronous inside _schedule_prefill.
    async_onboard: bool = False
    # Cache-aware scheduling (DYN_CACHE_AWARE): the admission plane prices a
    # request by its *residual* (uncached) prefill tokens — resident G1
    # match plus capacity-tier probe — so EDF slack ranks a mostly-cached
    # long prompt ahead of a cold short one and tenant buckets charge only
    # the tokens that will actually be computed. Policy-only: off is
    # bit-identical to full-cost pricing. (The router's residual-prefill
    # cost term is armed by the same knob via sched.configure_cache_aware.)
    cache_aware: bool = False
    # Constrained-decode lookahead (DYN_CONSTRAINT_LOOKAHEAD_TOKENS): max
    # distinct successor machine states a chained json_mode row may fan out
    # to per step. At compose time the row's input token is still in flight,
    # so the engine precomputes the constraint mask for every admissible
    # candidate (grouped by successor state — JSON masks collapse thousands
    # of candidate tokens into a handful of states) and the chained program
    # selects the right one in-graph from the gathered token. Overflow or a
    # cold mask cache barriers that step (reason "constraint_miss") and
    # self-warms. 0 disables lookahead: every constrained step barriers
    # (reason "constraint") — the pre-lookahead behavior, kept as the bench
    # baseline.
    constraint_lookahead_tokens: int = 32


@dataclasses.dataclass
class _InflightStep:
    """A dispatched-but-unharvested device step.

    kind "step" is a plain (possibly mixed prefill+decode) single step;
    "spec" is a speculative verify. ns/samples/drafts snapshot the
    composition the harvest needs to apply the results — sequence state may
    have moved on (preemption, cancellation) by the time the tokens land,
    so apply skips any row whose sequence is no longer RUNNING. ``extra``
    holds the chained pure-decode sub-step handles a decode_steps>1 burst
    dispatched behind the primary step — harvested in dispatch order, one
    more token per row each."""

    batch: list
    handle: object
    kind: str = "step"
    ns: list | None = None  # real token columns per row (step/spec)
    n_dec: int = 0  # leading decode rows (the rest are prefill chunks)
    samples: list | None = None  # per-row: does the engine accept a sample?
    drafts: list | None = None  # per-decode-row draft tokens (spec)
    extra: list = dataclasses.field(default_factory=list)  # burst sub-step handles


@dataclasses.dataclass
class _OnboardSession:
    """An admitted row's in-flight tier onboarding (config.async_onboard).

    The fetch thread fills ``payloads``/``tiers`` and sets ``done``; the
    engine thread lands the session under ``step_lock`` (device write +
    prefix-cache commit + ``num_cached`` advance) from ``_poll_onboards``.
    Cancellation (preempt/finish/abort) simply removes the session from the
    engine's list — the orphaned fetch thread finishes into this object and
    nobody reads it, so stale payloads can never land in reused pages."""

    seq: Sequence
    hashes: list  # full block-hash chain of the sequence
    start: int  # first onboard block index (== resident match length)
    pages: list  # freshly-allocated G1 pages awaiting payloads
    t0: float  # session start (perf_counter) for the wait histogram
    count_at_start: bool  # fold landed pages into num_cached_at_start
    payloads: list = dataclasses.field(default_factory=list)
    tiers: list = dataclasses.field(default_factory=list)
    done: threading.Event = dataclasses.field(default_factory=threading.Event)


class EngineCore:
    """Synchronous scheduler + executor. The async service layer drives it."""

    def __init__(
        self,
        runner: ModelRunner,
        config: EngineConfig,
        *,
        on_kv_event: Callable[[KvCacheEvent], None] | None = None,
        block_manager=None,  # dynamo_tpu.blocks.KvBlockManager (G2/G3 tiers)
        admission=None,  # sched.AdmissionController (overrides the env build)
        chunk_controller=None,  # sched.ChunkBudgetController (same)
    ) -> None:
        if runner.num_pages != config.num_pages or runner.page_size != config.page_size:
            raise ValueError("runner and engine config disagree on cache geometry")
        self.runner = runner
        self.config = config
        self.block_manager = block_manager
        self.allocator = PageAllocator(config.num_pages, config.page_size, on_event=on_kv_event)
        # A model with recurrent layers keeps a second kind of per-sequence
        # state: a slot, taken at admission and given back at finish or
        # preemption (which is by recompute: the sequence starts again from its
        # tokens, in whatever slot it is given then). Pages alone do not bring
        # a state back, so such a model never matches a prefix, whatever
        # ``enable_prefix_caching`` says; its KV events still go out.
        self.state_slots: SlotAllocator | None = None
        if getattr(runner, "recurrent", False):
            self.state_slots = SlotAllocator(runner.state_slots)
            if config.spec_k > 0 or config.decode_steps > 1:
                raise ValueError(
                    f"{runner.cfg.name}: spec_k {config.spec_k} / decode_steps {config.decode_steps} are not served "
                    "for a model with recurrent layers: a rejected draft or a burst's overshoot cannot be taken "
                    "out of a state again")
            if config.enable_prefix_caching:
                logger.info("%s has recurrent layers: prefix matching is off (a state has no pages to match; "
                            "state snapshots are not built), KV events still go out", runner.cfg.name)
        self.prefix_matching = config.enable_prefix_caching and self.state_slots is None
        # A model that mixes window and full layers keeps a page pool a layer
        # kind: ``allocator`` is the full layers', which seats the context and
        # speaks to the KV event plane; ``window_allocator`` the sliding
        # layers', whose pages a sequence gives back as they fall wholly
        # behind the window (``_release_out_of_window``). A sequence holds a
        # page of each for every block it grows by (``_grow``).
        self.window_allocator: PageAllocator | None = None
        self.window_pages_released = 0
        self._window_released_step = 0
        if getattr(runner, "two_pool", False):
            name = runner.cfg.name
            if block_manager is not None:
                raise ValueError(
                    f"{name}: offload tiers are not served for a model with a page pool per layer kind: a page "
                    "id names a page of one kind's pool (engine/runner.py refuses the page movers by name)")
            if not config.swa_free_pages and runner.window_pages < config.num_pages:
                raise ValueError(
                    f"{name}: swa_free_pages is off and the window pool holds {runner.window_pages} pages of the "
                    f"full pool's {config.num_pages}: pages that are never given back need a pool that seats the context")
            self.window_allocator = PageAllocator(runner.window_pages, config.page_size, pool="window")
        self.waiting: deque[Sequence] = deque()
        self.running: list[Sequence] = []
        # Admitted but mid-prompt: their next chunk is scheduled each step
        # (arrival order) before new admissions; they are not decodable
        # until the final chunk samples, at which point they move to
        # ``running``. Always empty when chunk_prefill_tokens == 0.
        self.prefilling: list[Sequence] = []
        # Composition of the latest dispatch + cumulative mixed-step stats —
        # the observable form of the stall-free invariant (tests, bench
        # stall probe): with chunking on, a dispatch carrying chunk rows
        # while decodable sequences exist must also carry their decode rows.
        self.last_step_info: dict = {}
        self.mixed_steps = 0
        # Dispatches that carried a chunk row, by the layout of their program:
        # tokens on one axis, a position per decode row ("split"), or the
        # rows x t rectangle that pads every row to the chunk.
        self.chunk_steps_split = 0
        self.chunk_steps_rows_x_t = 0
        self.stall_violations = 0  # prefill-only dispatches that starved decodes
        self._next_seq_id = 0
        self._eos = set(config.eos_token_ids)
        self.num_preemptions = 0
        self.admission_rejections = 0  # requests refused at add_request intake
        # SLO admission-control plane (None => legacy FIFO intake; the
        # explicit kwargs let tests/bench inject configured controllers
        # without touching the environment).
        self.admission = admission
        self.chunk_controller = chunk_controller
        if config.slo_sched:
            from dynamo_tpu.sched import build_admission_controller, build_chunk_controller

            if self.admission is None:
                self.admission = build_admission_controller()
            if self.chunk_controller is None and config.chunk_prefill_tokens > 0:
                self.chunk_controller = build_chunk_controller(config.chunk_prefill_tokens)
        if config.cache_aware and self.admission is not None:
            # Residual-cost admission (DYN_CACHE_AWARE): the EDF plane
            # prices every waiting request by its uncached prefill tokens.
            self.admission.cached_tokens_fn = self._cached_prefix_tokens
        # Last _schedule_prefill's admission outcome (flight STEP record).
        self.last_admission = {
            "admitted": 0, "deferred": 0, "deadline_slack_ms": 0.0, "cached_frac": 0.0,
        }
        # Async tier onboarding (config.async_onboard): live sessions, the
        # lazy fetch pool, and the counters the metrics/bench planes read.
        self._onboards: list[_OnboardSession] = []
        self._onboard_pool = None  # ThreadPoolExecutor, built on first use
        self.onboard_sessions = 0
        self.onboard_page_counts: dict[str, int] = {}  # tier -> pages landed
        self.onboard_shortfall_pages = 0  # probed but gone at fetch: recomputed
        self._onboard_waits: list[float] = []  # seconds; metrics plane drains
        self.onboard_wait_ms_sum = 0.0
        self.onboard_wait_count = 0
        # Overlap accounting: of the steps that had a session in flight, how
        # many still dispatched fresh device work (the pipelining win) vs
        # idled waiting on the fetch. overlap_frac = overlap / (overlap+stall).
        self.onboard_overlap_steps = 0
        self.onboard_stall_steps = 0
        self._onboard_pending_step = False
        # Speculative decoding: cumulative drafting/verify counters (metrics
        # plane syncs them; acceptance rate = accepted / proposed).
        self.spec_tokens_proposed = 0
        self.spec_tokens_accepted = 0
        self.spec_steps = 0
        # Attention dispatch-path accounting: steps by (phase, path) —
        # phase in {decode, verify, prefill}, path in {pallas, fallback,
        # ring} (runner._attn_dispatch). A serving config silently riding
        # the ~5x-slower gather formulation shows up here and at /metrics.
        self.attn_dispatch_counts: dict[tuple[str, str], int] = {}
        self._proposer = None
        if config.spec_k > 0:
            from dynamo_tpu.engine.spec import build_proposer

            self._proposer = build_proposer()
        # Flight recorder: last-N-steps ring for postmortems. The compile
        # tracker (when the runner has one — mock runners don't) sinks its
        # first-execution events into the same ring, so a flight dump shows
        # recompiles interleaved with the steps that triggered them.
        self.flight = FlightRecorder()
        self._compile_tracker = getattr(runner, "compile_tracker", None)
        if self._compile_tracker is not None:
            self._compile_tracker.bind_sink(self.flight.record)
        # Time-loss accounting (attribution plane): cumulative ms charged per
        # cause (the pinned attribution.LOSS_CAUSES vocabulary — barrier
        # reasons + queue/admission/onboard_stall/preempt/recompile/gap),
        # exported as dynamo_engine_lost_time_seconds_total{cause}. The
        # step-time totals let consumers compute non-compute wall time
        # (wall + gap - dispatch) and hence the unattributed residual.
        self.lost_time_ms: dict[str, float] = {}
        self.step_wall_ms_total = 0.0
        self.step_dispatch_ms_total = 0.0
        self._recompile_events_seen = 0  # tracker events already charged
        self.recompile_count = 0  # cumulative new_shape events (sentinel feed)
        # Anomaly sentinel: rolling-window self-diagnosis over the step
        # stream, raising ANOMALY flight records + dynamo_anomaly_active.
        from dynamo_tpu.observability.anomaly import AnomalySentinel
        from dynamo_tpu.observability.incidents import IncidentCapture

        self.sentinel = AnomalySentinel(flight=self.flight)
        # Incident plane: a sentinel rising edge (or a step crash, below)
        # snapshots a black-box bundle — flight excerpt, intersecting spans,
        # loss ledger, config — into the size-capped on-disk store, so a
        # worker that dies still leaves a postmortem artifact. The worker
        # label is refined to the lease id at telemetry bring-up (launch.py).
        self.incidents = IncidentCapture(worker=f"pid-{os.getpid()}", core=self)
        self.sentinel.on_fire = lambda kind, info: self.incidents.capture("anomaly", info)
        # Long steps (one whose period is five times its kind's; the
        # sentinel tells which) by cause: "gc" / "profiler" where a host
        # pause of tracing.HOST_PAUSES covers half of what was lost,
        # "compile", or "" for one that nothing names.
        self._host_pauses = tracing.install_host_pauses()
        self.long_steps: dict[str, int] = {}
        self.long_step_lost_ms: dict[str, float] = {}
        # Cumulative counters for the metrics plane.
        self._prompt_tokens_total = 0
        self._generated_tokens_total = 0
        # Tier write-through is collected per step and flushed as one batched
        # device->host read. The async service sets ``defer_offloads`` and
        # flushes after routing outputs, so token delivery never waits on
        # offload copies; direct drivers (tests, bench) flush at end of step.
        self.pending_offloads: list[tuple[int, int]] = []  # (block_hash, page_id)
        self.defer_offloads = False
        # Serializes step()/flush_offloads() (executor thread) against
        # abort_all() (event-loop thread, on service shutdown/failure): the
        # scheduler queues and page lists have no other cross-thread guard.
        self.step_lock = threading.RLock()
        self._head_stall_steps = 0
        # The dispatch in flight on device, not yet consumed (pipelined
        # bursts and the overlapped lookahead alike).
        self._inflight: _InflightStep | None = None
        # Effective-state advance for sequences with a dispatch in flight:
        # seq_id -> (cached_delta, emit_delta). cached_delta = new KV slots
        # the in-flight step writes for the row; emit_delta = 1 iff the row
        # samples a token the host has not seen yet. The scheduler and the
        # lookahead builder reason at num_cached + cached_delta /
        # num_generated + emit_delta so in-flight work is never
        # double-scheduled. Cleared whenever the in-flight step is consumed.
        self._inflight_adv: dict[int, tuple[int, int]] = {}
        # seq_id -> flat index into the runner's device-resident sample
        # buffer from the *latest async dispatch* (plain step: row i; spec
        # verify: row*verify_width + accepted_col, filled at harvest). A
        # chained dispatch sources these rows' input tokens in-graph.
        self._chain_map: dict[int, int] = {}
        # Constrained-row lookahead plans for the step being composed:
        # seq_id -> (successor masks, token -> group map). Built by
        # _plan_constraint_lookahead during routing, consumed by
        # _run_mixed_overlapped when it assembles the la_masks/la_groups
        # device arrays. Rebuilt whenever constrained rows route overlapped.
        self._la_plan: dict[int, tuple[list, np.ndarray]] = {}
        # Overlapped execution accounting (config.overlap): per-step mode —
        # "overlapped" when the step dispatched a chained lookahead while
        # harvesting the previous one, "barrier" otherwise — plus the host
        # gap between consecutive dispatches (device-idle observability).
        self._overlap_mode: str | None = None
        self.overlap_step_counts: dict[str, int] = {"overlapped": 0, "barrier": 0}
        # Why each barrier step barriered (first reason wins within a step):
        # cumulative reason -> count, mirrored to the metrics plane as
        # dynamo_engine_overlap_barrier_total{reason}.
        self.overlap_barrier_counts: dict[str, int] = {}
        self._overlap_barrier_reason: str | None = None
        # Rows that were in flight when a dispatch crashed (CRASH record).
        self._aborted_inflight = 0
        # Where a step's and a gap's time goes, phase by phase: the runner
        # marks dispatch -> wait, the service the five parts of the gap.
        self.clock = tracing.StepClock()
        if hasattr(runner, "clock"):
            runner.clock = self.clock
        self.step_gap_ms_sum = 0.0
        self.step_gap_ms_count = 0
        self.step_gap_ms_last = 0.0
        # Steps recorded per kind ("mixed"/"prefill"/"decode"/"drain") — the
        # step-kind histogram behind loss_snapshot() and the metrics plane.
        self.step_kind_counts: dict[str, int] = {}
        # Constrained decoding (response_format json_object): the mask cache
        # needs token TEXT, so a tokenizer (or factory) must be installed
        # before json_mode requests are admitted.
        self._constraint_tok = None
        self._constraint_tok_factory = None
        self._mask_cache = None
        import threading as _threading

        self._constraint_lock = _threading.Lock()

    # -- request intake ----------------------------------------------------

    def add_request(self, request: PreprocessedRequest, context: Context | None = None) -> Sequence:
        context = context or Context()
        # Image content is part of the prefix-cache identity: two prompts
        # with identical placeholder tokens but different images must not
        # reuse each other's KV. The router folds the same value (tokens.py).
        from dynamo_tpu.tokens import mm_salt_fold

        salt = self.config.salt ^ mm_salt_fold(request.mm_inputs)
        seq = Sequence.from_request(
            self._next_seq_id, request, context,
            page_size=self.config.page_size, salt=salt,
        )
        self._next_seq_id += 1
        if not request.token_ids:
            return self._reject(seq, FinishReason.ERROR)
        max_prompt = self.config.max_seq_len - 1
        if len(request.token_ids) > max_prompt:
            return self._reject(seq, FinishReason.LENGTH)
        if request.sampling.json_mode:
            try:
                seq.constraint = self._make_constraint()
            except ValueError as exc:
                logger.warning("rejecting json_mode request: %s", exc)
                return self._reject(seq, FinishReason.ERROR)
        if request.mm_inputs:
            try:
                seq.mm_embeds = self._decode_mm_inputs(request)
                seq.mrope = self._mrope_for(request)
            except ValueError as exc:
                logger.warning("rejecting multimodal request: %s", exc)
                return self._reject(seq, FinishReason.ERROR)
        # A prompt needing more pages than the pool holds can never be
        # scheduled; admitting it would wedge the FIFO head forever.
        usable_pages = self.config.num_pages - 1  # page 0 is the reserved null page
        pages_needed = -(-len(request.token_ids) // self.config.page_size)
        if pages_needed > usable_pages:
            logger.warning(
                "rejecting request: prompt needs %d pages, pool holds %d",
                pages_needed, usable_pages,
            )
            return self._reject(seq, FinishReason.ERROR)
        self.waiting.append(seq)
        return seq

    def _reject(self, seq: Sequence, reason: FinishReason) -> Sequence:
        self.admission_rejections += 1
        seq.status = SeqStatus.FINISHED
        seq.finish_reason = reason
        return seq

    def set_constraint_tokenizer(self, tokenizer) -> None:
        self._constraint_tok = tokenizer

    def set_constraint_tokenizer_factory(self, factory) -> None:
        """Install the tokenizer source for constrained decoding. Loaded by
        warm_constraints (launch starts it at worker bring-up unless
        DYNAMO_WARM_CONSTRAINTS=0) or, failing that, by the first json_mode
        request."""
        self._constraint_tok_factory = factory

    def _make_constraint(self):
        from dynamo_tpu.constrained import JsonConstraint, TokenMaskCache

        with self._constraint_lock:
            if self._mask_cache is None:
                tok = self._constraint_tok
                if tok is None and self._constraint_tok_factory is not None:
                    tok = self._constraint_tok = self._constraint_tok_factory()
                if tok is None:
                    raise ValueError("json_mode needs a tokenizer on the engine worker")
                self._mask_cache = TokenMaskCache(
                    tok, self.runner.cfg.vocab_size, tuple(self._eos)
                )
            return JsonConstraint(self._mask_cache)

    def warm_constraints(self) -> None:
        """Pre-build the vocab piece table and the hot mask summaries OFF
        the serving loop (a cold 128k-vocab build walks every piece through
        the machine — seconds of work that must not land inside
        add_request and stall co-resident decode). Launch calls this on a
        daemon thread at worker startup; a json_mode request racing the
        warm-up just blocks on the same lock until it finishes."""
        from dynamo_tpu.constrained import MachineState, advance_text

        try:
            c = self._make_constraint()
            for prefix in ("", "{", '{"', '{"k"', '{"k":', '{"k": 1', "["):
                c.cache.mask_for(advance_text(MachineState(), prefix))
        except Exception:
            logger.debug("constraint warm-up skipped", exc_info=True)

    @property
    def constraint_mask_cache_hits(self) -> int:
        """Cumulative TokenMaskCache hits (mask builds + lookahead plans) —
        mirrored as dynamo_engine_constraint_mask_cache_hits_total."""
        return self._mask_cache.hits if self._mask_cache is not None else 0

    @property
    def constraint_mask_cache_misses(self) -> int:
        return self._mask_cache.misses if self._mask_cache is not None else 0

    def drain_constraint_build_seconds(self) -> list[float]:
        """Cold mask-build durations since the last scrape — observed into
        the dynamo_engine_constraint_mask_build_seconds histogram."""
        if self._mask_cache is None:
            return []
        return self._mask_cache.drain_build_seconds()

    def _decode_mm_inputs(self, request: PreprocessedRequest):
        """mm_inputs wire format -> [total_image_tokens, D] embeddings.

        The placeholder count in the prompt must match the embedding rows:
        a mismatch would silently shift every image's content."""
        import base64

        mi = request.mm_inputs
        try:
            arr = np.frombuffer(
                base64.b64decode(mi["embeds_b64"]), dtype=np.dtype(mi.get("dtype", "float32"))
            ).reshape(mi["shape"])
            arr = arr.reshape(-1, arr.shape[-1])
        except Exception as exc:  # malformed wire payloads must not escape
            raise ValueError(f"malformed mm_inputs: {exc}") from exc
        img_id = getattr(self.runner.cfg, "image_token_id", None) if hasattr(self.runner, "cfg") else None
        if img_id is None:
            raise ValueError("model has no image placeholder token")
        vid_id = getattr(self.runner.cfg, "video_token_id", None)
        n_placeholders = sum(1 for t in request.token_ids if t == img_id or t == vid_id)
        if n_placeholders != arr.shape[0]:
            raise ValueError(
                f"{n_placeholders} image placeholders vs {arr.shape[0]} embedding rows"
            )
        return arr

    def _mrope_for(self, request: PreprocessedRequest):
        """(pos3, delta) for an M-RoPE model's multimodal request; None for
        standard-rope models. The encode worker ships per-image grids in
        mm_inputs — without them the 3D positions are unknowable, so their
        absence on an M-RoPE model is a rejection, not a silent 1D fallback
        (which would quietly diverge from HF on every image prompt)."""
        cfg = getattr(self.runner, "cfg", None)
        if cfg is None or not getattr(cfg, "mrope_section", None):
            return None
        from dynamo_tpu.models.qwen2_vl import mrope_position_ids

        grids = request.mm_inputs.get("grids")
        if not grids:
            raise ValueError("M-RoPE model needs per-image grids in mm_inputs")
        pos3, delta = mrope_position_ids(
            request.token_ids, [tuple(g) for g in grids],
            image_token_id=cfg.image_token_id,
            video_token_id=cfg.video_token_id,
        )
        return pos3, delta

    @property
    def has_work(self) -> bool:
        return bool(
            self.waiting or self.running or self.prefilling or self._inflight is not None
        )

    # -- stepping ----------------------------------------------------------

    def step(self) -> list[tuple[Sequence, EngineOutput]]:
        """Advance the engine by one batched forward; returns per-seq deltas.

        Every step (and any raise out of one) lands a structured record in
        ``self.flight``: the step's composition is captured per step rather
        than last-write-wins, and a crash record snapshots the failing step's
        context before the exception propagates to the service loop (which
        dumps the ring to JSONL).
        """
        with self.step_lock:
            prev_info = self.last_step_info
            tracker = self._compile_tracker
            # Host gap since the previous step returned: the window where the
            # device has nothing newly dispatched (detok/stop/route/schedule
            # time). The overlapped loop exists to hide exactly this. The
            # clock's five gap phases tile it (docs/OBSERVABILITY.md); 0 for
            # the first step and for the one after a step that raised.
            clock = self.clock
            t0_ns, gap_ns = clock.begin()
            gap_ms = gap_ns / 1e6
            self._overlap_mode = None
            self._overlap_barrier_reason = None
            self._aborted_inflight = 0
            preempt0 = self.num_preemptions
            try:
                out = self._step_locked()
            except Exception as exc:
                inflight_rows = self._aborted_inflight or (
                    len(self._inflight.batch) if self._inflight is not None else 0
                )
                self.flight.record(
                    CRASH,
                    error=type(exc).__name__,
                    detail=str(exc)[:500],
                    waiting=len(self.waiting),
                    running=len(self.running),
                    prefilling=len(self.prefilling),
                    free_pages=self.allocator.num_free(),
                    inflight_rows=inflight_rows,
                    last_step_info=dict(self.last_step_info),
                )
                # After the CRASH flight record, so the bundle's flight
                # excerpt ends on the crash itself.
                self.incidents.capture(
                    "crash",
                    {
                        "error": type(exc).__name__,
                        "detail": str(exc)[:500],
                        "where": "engine_step",
                        "waiting": len(self.waiting),
                        "running": len(self.running),
                        "inflight_rows": inflight_rows,
                    },
                )
                raise
            # ``record`` runs from here to the end of step(): it ends after
            # this step's flight record is written, so the next record holds it.
            wall_ms = (clock.mark(tracing.RECORD) - t0_ns) / 1e6
            info = self.last_step_info
            fresh = info is not prev_info  # _run_mixed built a new dict
            onboard_stalled = False
            if self._onboard_pending_step:
                # A tier fetch was in flight across this step: did the step
                # still dispatch device work (overlapped) or idle on it?
                if fresh:
                    self.onboard_overlap_steps += 1
                else:
                    self.onboard_stall_steps += 1
                    onboard_stalled = True
                self._onboard_pending_step = False
            if not fresh and not out and not self.running:
                clock.restart_gap()
                return out  # idle drain: nothing dispatched, nothing to record
            overlap_mode = ""
            barrier_reason = ""
            if self.config.overlap:
                overlap_mode = self._overlap_mode or "barrier"
                self.overlap_step_counts[overlap_mode] = (
                    self.overlap_step_counts.get(overlap_mode, 0) + 1
                )
                if overlap_mode == "barrier":
                    barrier_reason = self._overlap_barrier_reason or "idle"
                    self.overlap_barrier_counts[barrier_reason] = (
                        self.overlap_barrier_counts.get(barrier_reason, 0) + 1
                    )
            self.step_gap_ms_sum += gap_ms
            self.step_gap_ms_count += 1
            self.step_gap_ms_last = gap_ms
            if fresh:
                decode_rows = int(info.get("decode_rows", 0))
                chunk_rows = int(info.get("chunk_rows", 0))
                chunk_tokens = int(info.get("chunk_tokens", 0))
                spec_drafted = int(info.get("spec_drafted", 0))
                spec_accepted = int(info.get("spec_accepted", 0))
                kind = (
                    "mixed" if decode_rows and chunk_rows
                    else ("prefill" if chunk_rows else "decode")
                )
            else:
                decode_rows = len(self.running)
                chunk_rows = chunk_tokens = 0
                spec_drafted = spec_accepted = 0
                kind = "decode" if self.running else "drain"
            self.step_kind_counts[kind] = self.step_kind_counts.get(kind, 0) + 1
            # What the runner dispatched in this step, taken once: a step that
            # only drains in-flight results takes nothing.
            report = self.runner.take_dispatch() or _NO_DISPATCH
            dispatch_ms = report.seconds * 1e3
            if report.attn_phase:
                attn = (report.attn_phase, report.attn_path)
                self.attn_dispatch_counts[attn] = self.attn_dispatch_counts.get(attn, 0) + 1
            if chunk_rows and report.layout == SPLIT:
                self.chunk_steps_split += 1
            elif chunk_rows and report.layout:
                self.chunk_steps_rows_x_t += 1
            # Feed the chunk-budget controller only steps that carried decode
            # rows: their wall time is the ITL a running request observed.
            if self.chunk_controller is not None and decode_rows:
                self.chunk_controller.observe(wall_ms)
            record = self.flight.record(
                STEP,
                step_kind=kind,
                decode_rows=decode_rows,
                chunk_rows=chunk_rows,
                chunk_tokens=chunk_tokens,
                outputs=len(out),
                waiting=len(self.waiting),
                running=len(self.running),
                prefilling=len(self.prefilling),
                free_pages=self.allocator.num_free(),
                preemptions=self.num_preemptions,
                admission_rejections=self.admission_rejections,
                mixed_steps=self.mixed_steps,
                stall_violations=self.stall_violations,
                spec_drafted=spec_drafted,
                spec_accepted=spec_accepted,
                spec_accept_rate=(
                    round(spec_accepted / spec_drafted, 4) if spec_drafted else 0.0
                ),
                wall_ms=round(wall_ms, 3),
                dispatch_ms=round(dispatch_ms, 3),
                attn_phase=report.attn_phase,
                attn_path=report.attn_path,
                moe_path=report.moe_path,
                router_select=report.router_select,
                kv_tokens_full=report.kv_tokens_full,
                kv_tokens_window=report.kv_tokens_window,
                step_tokens=report.step_tokens,
                moe_pad_positions=report.moe_pad_positions,
                moe_choices=report.moe_counts[0],  # parallel/moe.HELD_COUNTS, in its order: plain keywords,
                moe_choices_zero=report.moe_counts[1],  # because a ** in the middle takes the whole call
                moe_choices_held=report.moe_counts[2],  # off the interpreter's fast path (0.015 ms a step
                moe_experts_touched=report.moe_counts[3],  # on a v5e's host: PERF.md, PR 34)
                moe_extra_passes=report.moe_counts[4],
                state_rows=report.state_rows,
                state_slots_live=self.state_slots.live if self.state_slots is not None else 0,
                full_pages_live=self.allocator.live,
                window_pages_live=self.window_allocator.live if self.window_allocator is not None else 0,
                window_pages_released=self._take_window_released(),
                layout=report.layout,
                admitted=int(self.last_admission.get("admitted", 0)),
                deferred=int(self.last_admission.get("deferred", 0)),
                deadline_slack_ms=self.last_admission.get("deadline_slack_ms", 0.0),
                cached_frac=self.last_admission.get("cached_frac", 0.0),
                gap_ms=round(gap_ms, 3),
                overlap_mode=overlap_mode,
                barrier_reason=barrier_reason,
                chained_rows=int(info.get("chained_rows", 0)) if fresh else 0,
                t0_ns=t0_ns,
                ann_ns=clock.ann_ns,
                traced=clock.traced,
                phases_us=clock.phases_us(),
            )
            # Time-loss accounting: every millisecond of this step's wall
            # clock that was not runner dispatch, plus the host gap before
            # it, lands under exactly one cause. Without a compile tracker
            # (mock/timing runners) the step wall IS the model-compute
            # analog, so only the gap is lost time.
            self.step_wall_ms_total += wall_ms
            self.step_dispatch_ms_total += dispatch_ms if tracker is not None else wall_ms
            host_ms = max(0.0, wall_ms - dispatch_ms) if tracker is not None else 0.0
            self._charge_loss("gap", gap_ms)
            if self.num_preemptions > preempt0:
                self._charge_loss("preempt", host_ms)
            elif onboard_stalled:
                self._charge_loss("onboard_stall", host_ms)
            elif overlap_mode == "barrier" and barrier_reason:
                self._charge_loss(barrier_reason, host_ms)
            else:
                self._charge_loss("gap", host_ms)
            recompiles0 = self.recompile_count
            if tracker is not None:
                events = tracker.events()
                for ev in events[self._recompile_events_seen:]:
                    # Only what a step paid: a warm-up drives the runner
                    # outside any step, and its first calls (``in_step``
                    # false) are no recompiles of the serving path.
                    if ev.get("reason") == "new_shape" and ev.get("in_step", True):
                        self.recompile_count += 1
                        self._charge_loss("recompile", float(ev.get("wall_ms", 0.0)))
                self._recompile_events_seen = len(events)
            self.sentinel.observe_step(
                wall_ms=wall_ms, gap_ms=gap_ms,
                barrier=overlap_mode == "barrier",
                outputs=len(out), decode_rows=decode_rows,
                recompiles=self.recompile_count,
                shortfall_pages=self.onboard_shortfall_pages,
            )
            # The step's period: the eleven phases its record holds (the tail
            # of the step before, the gap, this step up to here) less the wait
            # for a request, which is no pause.
            no_work_ns = clock.carried[tracing.NO_WORK - tracing.RECORD]
            period_ms = wall_ms + gap_ms + (clock.carried[0] - no_work_ns) / 1e6
            expected_ms = self.sentinel.observe_period(kind, decode_rows, period_ms)
            if expected_ms:
                self._record_long_step(
                    record, period_ms, expected_ms, no_work_ns=no_work_ns,
                    compiled=self.recompile_count > recompiles0,
                )
            if self._host_pauses.pending:
                self._host_pauses.flush()
            clock.end()
            return out

    def _record_long_step(
        self, record: dict, period_ms: float, expected_ms: float, *, no_work_ns: int, compiled: bool
    ) -> None:
        """One ``engine_long_step`` span and the counters by cause, for a step
        the sentinel found long: what was lost, the phase that holds most of
        it, and the host pauses (garbage collection, the profiler's start and
        stop) that lie inside the period, on ``perf_counter_ns``."""
        lost_ms = period_ms - expected_ms
        phases = record["phases_us"]
        phase = max((p for p in tracing.PHASES if p != "no_work"), key=phases.__getitem__)
        hi_ns = record["t0_ns"] + int(record["wall_ms"] * 1e6)
        lo_ns = hi_ns - int(period_ms * 1e6) - no_work_ns
        gc_ms, gc_generation, profiler_ms = self._host_pauses.overlap_ms(lo_ns, hi_ns)
        if profiler_ms >= lost_ms / 2:
            cause = "profiler"
        elif gc_ms >= lost_ms / 2:
            cause = "gc"
        else:
            cause = "compile" if compiled else ""
        self.long_steps[cause] = self.long_steps.get(cause, 0) + 1
        self.long_step_lost_ms[cause] = self.long_step_lost_ms.get(cause, 0.0) + lost_ms
        tracing.record_span(
            "engine_long_step", round(period_ms, 3), start_mono=lo_ns / 1e9, request_id="engine_long_step",
            step_kind=record["step_kind"], decode_rows=record["decode_rows"],
            expected_ms=round(expected_ms, 3), lost_ms=round(lost_ms, 3),
            phase=phase, phase_ms=round(phases[phase] / 1e3, 3),
            gc_ms=round(gc_ms, 3), gc_generation=gc_generation, profiler_ms=round(profiler_ms, 3),
            cause=cause, traced=record["traced"], t0_ns=record["t0_ns"], seq=record["seq"],
        )

    def _charge_loss(self, cause: str, ms: float) -> None:
        """Accumulate lost wall time under one attribution cause (ms)."""
        if ms > 0.0:
            self.lost_time_ms[cause] = self.lost_time_ms.get(cause, 0.0) + ms

    def loss_snapshot(self) -> dict:
        """Programmatic lost-time/step-kind snapshot (stable keys).

        The structured twin of the ``dynamo_engine_lost_time_seconds_total``
        and ``dynamo_engine_step_time_seconds_total`` exports, so the tuner
        and tests never scrape Prometheus text. All times are cumulative
        milliseconds since engine construction. Keys (pinned — extend, never
        rename):

        - ``lost_time_ms``: cumulative ms per attribution cause (the pinned
          :data:`~dynamo_tpu.observability.attribution.LOSS_CAUSES`
          vocabulary; absent cause = 0 charged so far).
        - ``step_time_ms``: ``{"wall", "dispatch", "gap"}`` cumulative totals.
        - ``step_kind_counts``: steps recorded per kind
          (``mixed``/``prefill``/``decode``/``drain``).
        - ``steps_total``: sum of ``step_kind_counts``.
        - ``overlap_step_counts`` / ``overlap_barrier_counts``: the overlap
          pipeline's mode and per-reason barrier tallies.
        - ``noncompute_wall_ms``: ``max(0, wall + gap - dispatch)`` — the
          denominator the burn-down targets divide by.
        - ``loss_coverage_frac``: fraction of non-compute wall the per-cause
          ledger accounts for (1.0 when nothing is unattributed).
        """
        wall = self.step_wall_ms_total
        dispatch = self.step_dispatch_ms_total
        gap = self.step_gap_ms_sum
        noncompute = max(0.0, wall + gap - dispatch)
        attributed = sum(
            ms for cause, ms in self.lost_time_ms.items()
            if cause not in ("queue", "admission")  # pre-step waits, not step wall
        )
        return {
            "lost_time_ms": dict(self.lost_time_ms),
            "step_time_ms": {"wall": wall, "dispatch": dispatch, "gap": gap},
            "step_kind_counts": dict(self.step_kind_counts),
            "steps_total": sum(self.step_kind_counts.values()),
            "overlap_step_counts": dict(self.overlap_step_counts),
            "overlap_barrier_counts": dict(self.overlap_barrier_counts),
            "noncompute_wall_ms": noncompute,
            "loss_coverage_frac": (
                min(1.0, attributed / noncompute) if noncompute > 0.0 else 1.0
            ),
        }

    def _step_locked(self) -> list[tuple[Sequence, EngineOutput]]:
        # Pending offloads must be read before allocate() can evict their
        # pages (deferred-mode safety; no-op when the service already flushed).
        self.flush_offloads()
        cancelled = self._reap_cancelled()
        if self._inflight is not None and (
            cancelled or (not self.config.overlap and (self.waiting or self.prefilling))
        ):
            # Composition is about to change. With overlap off an in-flight
            # step only exists defensively (config flipped mid-run) and
            # drains on any admission/chunk pressure; the chained pipeline
            # drains only on cancellation — reaping released the cancelled
            # rows' pages, so the in-flight step's writes for them are stale
            # and nothing new may be composed on top of it.
            if cancelled:
                self._note_barrier("cancel")
            out = cancelled + self._drain_inflight()
            if not self.defer_offloads:
                self.flush_offloads()
            return out
        chunks = self._schedule_prefill()
        overlap_ok, reason = self._overlap_route(chunks)
        if overlap_ok:
            # The annotation says what the step dispatches, as the synchronous
            # step's does (the trace's readers select programs by it): a chunk
            # program where a chunk of several tokens rides or a verify may,
            # else the decode program. A sequence whose last chunk is in
            # flight still counts as prefilling and already decodes here.
            chunky = any(n > 1 for _, n in chunks) or (self._spec_active() and bool(self.running))
            with self.clock.annotate("engine.mixed" if chunky else "engine.decode"):
                out = cancelled + self._run_mixed_overlapped(chunks)
            if not self.defer_offloads:
                self.flush_offloads()
            return out
        if reason is not None:
            self._note_barrier(reason)
        if self.config.overlap and self._inflight is not None:
            # Barrier with work in flight: commit it before any synchronous
            # dispatch. Chunks scheduled above keep their pages and are
            # re-scheduled (idempotently) next step.
            out = cancelled + self._drain_inflight()
            if not self.defer_offloads:
                self.flush_offloads()
            return out
        fused = self.config.chunk_prefill_tokens > 0
        if chunks or (fused and self.running and self.prefilling) or (
            self._spec_active() and self.running
        ):
            # Mixed step: decode rows + prefill-chunk rows in one dispatch.
            # Also taken with zero chunks scheduled (page-starved prefills):
            # decode must not wait on them. Legacy mode (fused=False) runs
            # the scheduled whole prompts without decode rows (XOR). With
            # speculation on, pure-decode steps route here too: the verify
            # dispatch supersedes the decode step.
            with self.clock.annotate("engine.mixed" if fused else "engine.prefill"):
                out = cancelled + self._run_mixed(chunks)
        elif self.running:
            with self.clock.annotate("engine.decode"):
                out = cancelled + self._run_decode()
        else:
            out = cancelled + self._drain_inflight()
        if not self.defer_offloads:
            self.flush_offloads()
        return out

    def _reap_cancelled(self) -> list[tuple[Sequence, EngineOutput]]:
        out: list[tuple[Sequence, EngineOutput]] = []
        for q in (self.waiting, self.prefilling, self.running):
            for seq in list(q):
                if seq.context.is_stopped and seq.status is not SeqStatus.FINISHED:
                    self._finish(seq, FinishReason.CANCELLED)
                    out.append(
                        (
                            seq,
                            EngineOutput(
                                token_ids=[],
                                finish_reason=FinishReason.CANCELLED,
                                cumulative_tokens=seq.num_generated,
                                prompt_tokens=seq.num_prompt,
                                cached_tokens=seq.num_cached_at_start,
                            ),
                        )
                    )
        return out

    # -- overlapped pipeline routing ---------------------------------------

    def _note_barrier(self, reason: str) -> None:
        """Record why this step barriered (first reason wins)."""
        if self._overlap_barrier_reason is None:
            self._overlap_barrier_reason = reason

    def _adv(self, s: Sequence) -> tuple[int, int]:
        """(cached_delta, emit_delta) the in-flight dispatch owes ``s``."""
        return self._inflight_adv.get(s.seq_id, (0, 0))

    def _eff_cached(self, s: Sequence) -> int:
        """num_cached once the in-flight step lands."""
        return s.num_cached + self._adv(s)[0]

    def _eff_remaining(self, s: Sequence) -> int:
        """remaining_tokens at effective state, WITHOUT the live-row floor:
        <= 0 means the sequence reaches its finish line inside the in-flight
        step (it is excluded from the lookahead, and the late stop check at
        harvest finishes it). Matches Sequence.remaining_tokens for rows
        with nothing in flight."""
        de = self._adv(s)[1]
        return min(
            s.request.stop.max_tokens - (s.num_generated + de),
            self.config.max_seq_len - (len(s.tokens) + de),
        )

    def _overlap_route(self, chunks) -> tuple[bool, str | None]:
        """Decide whether this step runs the chained pipeline.

        Returns (use_overlap, barrier_reason). reason is None when overlap
        is simply off/idle; otherwise it names the composition the graph
        cannot absorb. Penalties, logprobs, page-budget-final tokens,
        admission, mixed prefill+decode, multimodal/mrope rows, json_mode
        constraints, and decode_steps>1 are deliberately NOT here — they
        are all chained in-graph now."""
        cfg = self.config
        if not cfg.overlap:
            return False, None
        if not hasattr(self.runner, "step_async"):
            return False, "runner"
        if chunks and cfg.chunk_prefill_tokens <= 0:
            # Legacy XOR mode: whole-prompt prefill steps carry no decode
            # rows, so there is nothing to chain.
            return False, "prefill"
        rows = (
            self.running
            + [s for s, _ in chunks]
            + [s for s in self.prefilling if self._adv(s)[1]]
        )
        if not rows:
            # Nothing schedulable. If a step is in flight its rows are all
            # finishing — let the driver harvest it; otherwise idle.
            return (self._inflight is not None), None
        if any(s.constraint is not None for s in rows):
            if cfg.constraint_lookahead_tokens <= 0:
                return False, "constraint"
            if not self._plan_constraint_lookahead(rows):
                # Cold successor mask or candidate fan-out past the cap:
                # barrier to the sync mask path (which warms exactly the
                # states that missed) and retry the pipeline next step.
                return False, "constraint_miss"
        if self._spec_active() and not hasattr(self.runner, "spec_step_async"):
            # Speculation is on but the runner has no async verify: stand
            # down entirely — barrier to the sync verify path rather than
            # silently dropping drafts.
            return False, "spec"
        return True, None

    def _plan_constraint_lookahead(self, rows) -> bool:
        """Pre-build successor masks for constrained rows whose input token
        is still in flight. Returns False (barrier "constraint_miss") when
        any plan would need a mask the cache cannot produce warm.

        Soundness: at compose time exactly one step is unharvested, so the
        host constraint state is current through the *previous* harvested
        token — which makes ``constraint.mask(remaining_tokens)`` exactly
        the mask the in-flight step is sampling under (state unchanged
        since that compose, and remaining_tokens has not advanced for the
        in-flight emit). Every token that mask admits (minus EOS, whose
        sample the late stop check discards at harvest) is a candidate;
        candidates collapse into successor machine states and each state's
        mask at the row's post-emit remaining becomes one lookahead group."""
        cap = self.config.constraint_lookahead_tokens
        cache = self._mask_cache
        plan: dict[int, tuple[list, np.ndarray]] = {}
        self._la_plan = plan
        ok = True
        for s in rows:
            if s.constraint is None or s.seq_id not in self._chain_map:
                continue  # unchained constrained rows ship a host-built mask
            allowed = s.constraint.mask(s.remaining_tokens(self.config.max_seq_len))
            la = cache.lookahead_groups(s.constraint.state, allowed, cap)
            if la is None:
                ok = False
                continue
            states, group_of = la
            rem_next = self._eff_remaining(s)
            masks = []
            for ns in states:
                m = cache.peek_mask(ns, rem_next)
                if m is None:
                    # Cold successor summary: this step barriers to the sync
                    # mask path anyway, so spend the barrier warming the
                    # summary — otherwise a successor the stream never takes
                    # would stay cold and re-miss every step it remains a
                    # candidate.
                    cache.mask_for(ns, remaining=rem_next)
                    ok = False
                masks.append(m)
            if ok:
                plan[s.seq_id] = (masks, group_of)
        return ok

    def _attach_lookahead_masks(self, sb, batch, chain_src) -> None:
        """Ship per-row constraint masks as lookahead groups on a chained
        dispatch: ``la_masks[i, la_groups[i, tok]]`` is row i's sampling mask
        once its chained input token ``tok`` materialises in-graph. Group 0
        is the all-True identity (unconstrained rows; EOS candidates, whose
        rows finish at harvest before the sampled token is ever used)."""
        vocab = self.runner.cfg.vocab_size
        rows: dict[int, list] = {}
        groups = np.zeros((len(batch), vocab), np.int32)
        g_max = 1
        for i, s in enumerate(batch):
            if s.constraint is None:
                continue
            if chain_src[i] >= 0:
                # Routed here only after _plan_constraint_lookahead succeeded
                # for every chained constrained row: a missing plan is a bug,
                # not a fallback case.
                masks, group_of = self._la_plan[s.seq_id]
                groups[i] = np.where(group_of >= 0, group_of + 1, 0)
                rows[i] = masks
            else:
                # The host knows this row's input token (fresh chunk row or a
                # chain-lost decode row): one group holding its exact mask.
                # Non-final chunk rows' samples are discarded, so masking
                # them is harmless.
                rows[i] = [s.constraint.mask(s.remaining_tokens(self.config.max_seq_len))]
                groups[i] = 1
            g_max = max(g_max, 1 + len(rows[i]))
        la = np.zeros((len(batch), g_max, vocab), bool)
        la[:, 0] = True
        for i, masks in rows.items():
            for g, m in enumerate(masks):
                la[i, g + 1] = m
        sb.la_masks = la
        sb.la_groups = groups

    # -- prefill phase -----------------------------------------------------

    def chunk_budget_tokens(self) -> int:
        """The live per-step prefill chunk budget: the ITL-driven controller's
        current value when the SLO plane runs one, else the static config.
        Never 0 when the config is nonzero (the controller floors at
        ``chunk_floor_tokens``), so chunked-vs-legacy mode never flips."""
        if self.chunk_controller is not None:
            return self.chunk_controller.budget()
        return self.config.chunk_prefill_tokens

    def _schedule_prefill(self) -> list[tuple[Sequence, int]]:
        """Schedule this step's prefill work: ``(sequence, num_tokens)`` chunks.

        Continues mid-prompt sequences first (arrival order), then admits
        from the waiting queue FIFO, all under the per-step token budget:
        ``chunk_prefill_tokens`` while decodable sequences are running
        (decode-first — their stall is bounded by one chunk), the full
        ``max_prefill_tokens`` otherwise. Pages are allocated per chunk, so
        a prompt larger than the current free pool admits incrementally
        instead of parking at the queue head. With chunking disabled every
        scheduled chunk is a whole remaining prompt (legacy admission).

        A *resumed* (preempted) sequence already carries generated tokens;
        its "prompt" for this prefill is everything generated so far — the
        forward recomputes all uncached KV and the final chunk's sampled
        token is the legitimate next token of the continuation (no
        re-emission of old tokens).
        """
        # Land any finished onboarding sessions first: their rows' num_cached
        # advances here (engine thread, under step_lock), which both unblocks
        # their next chunk and frees this step from re-probing them.
        if self._onboards:
            self._poll_onboards(wait=False)
        ps = self.config.page_size
        chunk_budget = self.chunk_budget_tokens()
        chunked = chunk_budget > 0
        if chunked and self.running:
            budget = min(chunk_budget, self.config.max_prefill_tokens)
        else:
            budget = self.config.max_prefill_tokens
        chunks: list[tuple[Sequence, int]] = []
        # Decode first: the running sequences' next-token pages are spoken
        # for before any chunk is sized against the free pool. Speculation
        # widens the reserve to spec_k+1 slots per sequence — a chunk must
        # never get pages a verify row needs this step (draft allocation is
        # opportunistic and drops drafts rather than preempting, so without
        # the reserve a full pool would silently disable speculation).
        ahead = 1 + (self.config.spec_k if self._spec_active() else 0)
        reserve = sum(
            s.pages_needed(
                ps, self._adv(s)[0] + max(0, min(ahead, self._eff_remaining(s)))
            )
            for s in self.running
        ) if chunked else 0

        def free_pages() -> int:
            free = self.allocator.num_free()
            if self.window_allocator is not None:  # a block takes a page of each pool
                free = min(free, self.window_allocator.num_free())
            return max(0, free - reserve)

        # 1) Continue sequences already mid-prompt (arrival order).
        for seq in self.prefilling:
            if budget <= 0:
                break
            if seq.onboard_pending:
                # Tier payloads still in flight: the row's cached prefix is
                # not final, so chunking it now would recompute tokens the
                # session is about to land. Skipped exactly like a
                # page-starved row; lands via _poll_onboards.
                continue
            # A chunk already in flight counts as computed (overlap): the
            # next chunk starts where the in-flight one will leave off.
            dc = self._adv(seq)[0]
            n = min(seq.prompt_remaining - dc, budget)
            # Cap by pages: slack in already-held pages + the free pool.
            n = min(n, len(seq.pages) * ps - (seq.num_cached + dc) + free_pages() * ps)
            if n <= 0:
                continue  # page-starved this step; decode still proceeds
            need = seq.pages_needed(ps, dc + n)
            if need:
                try:
                    self._grow(seq, need)
                except OutOfPagesError:
                    continue
            budget -= n
            chunks.append((seq, n))

        # 2) Admit from the waiting queue (admission appends to
        # self.prefilling, so the live-sequence cap self-counts).
        # With the SLO plane attached, prepare() reorders the queue EDF
        # (least slack first) and returns how many head entries clear their
        # tenant quotas this step; without it the deque is untouched (FIFO,
        # bit-identical to the pre-sched scheduler).
        admissible: int | None = None
        quota_deferred = 0
        if self.admission is not None and self.waiting:
            admissible = self.admission.prepare(
                self.waiting,
                running=len(self.running) + len(self.prefilling),
                slots=self.config.max_batch_size,
            )
            # Admission-plane deferrals only: waiting entries the quota gate
            # held back at prepare time. Entries later skipped for pages /
            # prefill budget / batch slots are resource-limited, not deferred
            # by the controller, and don't belong in this count.
            quota_deferred = len(self.waiting) - admissible
        n_admitted = 0
        admit_cached = 0  # admission-time cached tokens (resident + probed)
        admit_total = 0  # total prompt tokens admitted this step
        while (
            self.waiting
            and budget > 0
            and (admissible is None or n_admitted < admissible)
            and len(self.running) + len(self.prefilling) < self.config.max_batch_size
        ):
            seq = self.waiting[0]
            if FAULTS.armed:
                try:
                    if FAULTS.fire("sched.admit") == "delay":
                        break  # deferred; retried next step
                except DropFault:
                    # Leave the seq in waiting but kill its context: next
                    # step's _reap_cancelled emits CANCELLED, so the client
                    # stream terminates instead of hanging outside all queues.
                    seq.context.kill()
                    break
            total = len(seq.tokens)  # prompt + any generated-before-preemption
            matched: list[int] = []
            onboard_n = 0  # tier blocks to onboard (payloads fetched post-alloc)
            hashes: list[int] = []
            if self.prefix_matching:
                hashes = seq.block_seq.block_hashes
                matched = self.allocator.match_prefix(hashes)
                if self.block_manager is not None:
                    # Extend the G1 match from the capacity tiers (membership
                    # probe only; payload I/O happens after allocation succeeds).
                    onboard_n = self.block_manager.probe_prefix(hashes, len(matched))
                # Must compute at least the final token's logits.
                while (len(matched) + onboard_n) * ps > total - 1:
                    if onboard_n:
                        onboard_n -= 1
                    else:
                        self.allocator.release([matched.pop()])
            window_matched = self._match_window(hashes, matched)  # may shorten ``matched``
            new_window: list[int] = []
            cached_len = (len(matched) + onboard_n) * ps
            num_new = total - cached_len
            # Pipelined onboarding (config.async_onboard): admit the row
            # with only its onboard-region pages allocated and ZERO chunk —
            # the tier payloads are fetched on a background thread and land
            # through the batched write_pages scatter while other rows (and
            # later this row's own chunks) compute. Legacy unchunked mode
            # keeps the synchronous path: its whole-prompt admission has no
            # later chunk for the session to overlap with.
            async_ob = self.config.async_onboard and chunked and onboard_n > 0
            if async_ob:
                n = 0
                try:
                    new_pages = self.allocator.allocate(onboard_n)
                except OutOfPagesError:
                    self.allocator.release(matched)
                    if not chunks and not self.running:
                        self._note_head_stall(seq, num_new)
                    break
            elif chunked:
                # First chunk: capped by the budget and by what the free
                # pool can hold. (Onboard pages hold fully *cached* tokens,
                # so any n >= 1 allocates at least the onboard_n pages.)
                n = min(num_new, budget)
                n = min(n, (len(matched) + free_pages()) * ps - cached_len)
                if n <= 0:
                    self._release_pages(matched, window_matched)
                    if not chunks and not self.running:
                        self._note_head_stall(seq, num_new)
                    break
            else:
                n = num_new
                if chunks and n > budget:
                    self._release_pages(matched, window_matched)
                    break
            if not async_ob:
                pages_goal = -(-(cached_len + n) // ps)
                try:
                    new_pages, new_window = self._allocate(pages_goal - len(matched))
                except OutOfPagesError:
                    self._release_pages(matched, window_matched)
                    if not chunks and not self.running:
                        self._note_head_stall(seq, num_new)
                    break
            self.waiting.popleft()
            seq.admitted_time = time.monotonic()
            seq.admitted_step = self.step_gap_ms_count
            n_admitted += 1
            admit_cached += cached_len
            admit_total += total
            if self.admission is not None:
                self.admission.on_admit(seq, seq.admitted_time)
            if onboard_n and not async_ob:
                # Pages exist now: fetch tier payloads, copy them in, and
                # commit — they re-enter the G1 prefix cache and re-announce
                # on the KV event plane. A fetch shortfall (evicted since the
                # probe) just means those tokens get recomputed.
                onboard, tiers = self.block_manager.fetch_prefix_tiered(
                    hashes, len(matched), onboard_n
                )
                if len(onboard) < onboard_n:
                    shortfall = onboard_n - len(onboard)
                    self.onboard_shortfall_pages += shortfall
                    onboard_n = len(onboard)
                    cached_len = (len(matched) + onboard_n) * ps
                    n += min(shortfall * ps, total - cached_len - n)
                self.block_manager.onboard(new_pages[: onboard_n], onboard)
                blocks = seq.block_seq.blocks
                for i, pid in enumerate(new_pages[:onboard_n]):
                    blk = blocks[len(matched) + i]
                    self.allocator.commit(pid, blk.block_hash, blk.parent_hash, blk.tokens)
                for tier in tiers[:onboard_n]:
                    self.onboard_page_counts[tier] = (
                        self.onboard_page_counts.get(tier, 0) + 1
                    )
            seq.pages = matched + new_pages
            seq.window_pages = window_matched + new_window
            seq.prefill_chunks = 0
            if self.state_slots is not None:  # live sequences never outnumber max_batch_size, nor the slots
                seq.state_slot = self.state_slots.allocate()
            if async_ob:
                # The onboard region is pending: cached state reflects only
                # the resident match until the session lands (shortfall
                # pages then degrade to plain compute pages).
                seq.committed_pages = len(matched)
                seq.num_cached = len(matched) * ps
                if seq.status is not SeqStatus.PREEMPTED:
                    seq.num_cached_at_start = seq.num_cached  # re-set at land
                self._start_onboard(
                    seq, hashes, len(matched), new_pages,
                    count_at_start=seq.status is not SeqStatus.PREEMPTED,
                )
            else:
                seq.committed_pages = len(matched) + onboard_n
                seq.num_cached = cached_len
                if seq.status is not SeqStatus.PREEMPTED:
                    seq.num_cached_at_start = cached_len
            seq.status = SeqStatus.RUNNING
            self.prefilling.append(seq)
            budget -= n
            if n:
                chunks.append((seq, n))
        if chunks:
            self._head_stall_steps = 0
        elif (
            chunked
            and not self.running
            and len(self.prefilling) > 1
            and self._inflight is None
            and not self._onboards
        ):
            # Nothing can move: mid-prompt sequences pin every page among
            # themselves. Preempt the most recently arrived one (its pages
            # return to the pool / prefix cache) and retry — bounded by the
            # prefilling count. A sole mid-prompt sequence always fits (its
            # whole prompt passed the pool check in add_request). With a
            # step in flight, emptiness is progress (the in-flight chunks
            # land next step), not deadlock — never preempt a row whose
            # chunk is mid-air. An onboarding session in flight is progress
            # for the same reason: its row's cached prefix lands shortly.
            self._preempt(self.prefilling[-1])
            return self._schedule_prefill()
        if self._onboards and not chunks and not self.running:
            # Nothing else to run: block briefly on the fetch instead of
            # busy-spinning the step loop. Bounded wait — a hung tier read
            # never wedges the engine; landed sessions schedule next step.
            self._poll_onboards(wait=True)
        self._onboard_pending_step = bool(self._onboards)
        self.last_admission = {
            "admitted": n_admitted,
            "deferred": quota_deferred,
            "deadline_slack_ms": (
                round(self.admission.last_slack_ms, 3) if self.admission is not None else 0.0
            ),
            "cached_frac": round(admit_cached / admit_total, 4) if admit_total else 0.0,
        }
        return chunks

    def _note_head_stall(self, seq: Sequence, num_new: int) -> None:
        self._head_stall_steps += 1
        if self._head_stall_steps % 100 == 1:
            logger.warning(
                "head-of-queue seq %d cannot allocate pages for %d tokens "
                "(free %d pages) with nothing running; stalled %d steps",
                seq.seq_id, num_new, self.allocator.num_free(), self._head_stall_steps,
            )

    # -- async tier onboarding ---------------------------------------------

    def _start_onboard(
        self, seq: Sequence, hashes: list, start: int, pages: list, *, count_at_start: bool
    ) -> None:
        from concurrent.futures import ThreadPoolExecutor

        if self._onboard_pool is None:
            # Pool width bounds how many tier fetches overlap the forward
            # pass; on hardware wider pools contend with compute for HBM
            # bandwidth, so the width is a tunable (swept by the auto-tuner).
            width = max(1, int(os.environ.get("DYN_ONBOARD_POOL_WIDTH", "2")))
            self._onboard_pool = ThreadPoolExecutor(
                max_workers=width, thread_name_prefix="kv-onboard"
            )
        sess = _OnboardSession(
            seq=seq, hashes=list(hashes), start=start, pages=list(pages),
            t0=time.perf_counter(), count_at_start=count_at_start,
        )
        seq.onboard_pending = len(pages)
        self._onboards.append(sess)
        self.onboard_sessions += 1
        self._onboard_pool.submit(self._onboard_fetch, sess)

    def _onboard_fetch(self, sess: _OnboardSession) -> None:
        """Background path: tier reads only — never touches scheduler state
        (the engine thread lands the session under step_lock). Any failure,
        including an armed store.op fault on the G4 path, degrades to an
        empty fetch: the row recomputes, the engine never sees the raise."""
        try:
            sess.payloads, sess.tiers = self.block_manager.fetch_prefix_tiered(
                sess.hashes, sess.start, len(sess.pages)
            )
        except Exception:
            logger.exception(
                "tier fetch failed for seq %d; onboarding degrades to recompute",
                sess.seq.seq_id,
            )
            sess.payloads, sess.tiers = [], []
        finally:
            sess.done.set()

    def _cancel_onboards(self, seq: Sequence) -> None:
        """Forget any session for ``seq`` (preempt/finish): its pages are
        being released, so a later landing would scatter stale payloads into
        reused pages. The orphaned fetch thread finishes into the dropped
        session object, which nothing reads."""
        if self._onboards:
            self._onboards = [s for s in self._onboards if s.seq is not seq]
        seq.onboard_pending = 0

    def _poll_onboards(self, *, wait: bool) -> None:
        """Land finished onboarding sessions (engine thread, under step_lock).

        ``wait`` blocks briefly on the oldest session when the caller has
        nothing else to schedule — bounded, so a hung tier read degrades to
        a slow poll loop rather than a wedged engine."""
        if wait and self._onboards:
            self._onboards[0].done.wait(timeout=0.05)
        rest: list[_OnboardSession] = []
        for sess in self._onboards:
            if sess.done.is_set():
                self._land_onboard(sess)
            else:
                rest.append(sess)
        self._onboards = rest

    def _land_onboard(self, sess: _OnboardSession) -> None:
        """Apply a finished session: batched device write, prefix-cache
        commit, and the row's ``num_cached`` advance. A shortfall (blocks
        evicted or a tier fault since the probe) leaves the trailing pages
        as plain compute pages — the next chunk recomputes those tokens,
        exactly like the synchronous path."""
        seq = sess.seq
        wait_s = time.perf_counter() - sess.t0
        self._onboard_waits.append(wait_s)
        self.onboard_wait_ms_sum += wait_s * 1e3
        self.onboard_wait_count += 1
        # Per-request onboard segment for /debug/explain: the fetch ran in
        # the background, so only the measured session wait is attributable
        # to this request's critical path.
        from dynamo_tpu.tracing import record_span, trace_of

        record_span(
            "engine_onboard_wait", round(wait_s * 1e3, 3),
            trace=trace_of(seq.context), request_id=seq.request.request_id,
            pages=len(sess.pages),
        )
        if seq.status is not SeqStatus.RUNNING or seq not in self.prefilling:
            seq.onboard_pending = 0  # finished/preempted while in flight
            return
        ps = self.config.page_size
        expected = len(sess.pages)
        landed = min(len(sess.payloads), expected)
        if landed:
            self.block_manager.onboard(sess.pages[:landed], sess.payloads[:landed])
            blocks = seq.block_seq.blocks
            for i, pid in enumerate(sess.pages[:landed]):
                blk = blocks[sess.start + i]
                self.allocator.commit(pid, blk.block_hash, blk.parent_hash, blk.tokens)
            for tier in sess.tiers[:landed]:
                self.onboard_page_counts[tier] = self.onboard_page_counts.get(tier, 0) + 1
        if landed < expected:
            self.onboard_shortfall_pages += expected - landed
        seq.num_cached += landed * ps
        seq.committed_pages += landed
        if sess.count_at_start:
            seq.num_cached_at_start = seq.num_cached
        seq.onboard_pending = 0

    def drain_onboard_waits(self) -> list[float]:
        """Hand the accumulated per-session wait times (seconds) to the
        metrics plane — observed into the histogram exactly once."""
        out, self._onboard_waits = self._onboard_waits, []
        return out

    def _cached_prefix_tokens(self, seq: Sequence) -> int:
        """Admission-time estimate of this prompt's reusable KV tokens:
        the resident G1 prefix (non-mutating peek — pricing must not touch
        refcounts or LRU order) extended by the capacity-tier probe (local
        membership only — prepare() must never block on a store
        round-trip). Capped at len-1: the final token always computes."""
        if not self.config.enable_prefix_caching:
            return 0
        hashes = seq.block_seq.block_hashes
        m = self.allocator.peek_prefix(hashes)
        t = (
            self.block_manager.probe_prefix(hashes, m, local_only=True)
            if self.block_manager is not None
            else 0
        )
        return max(0, min((m + t) * self.config.page_size, len(seq.tokens) - 1))

    # -- speculative decoding ----------------------------------------------

    def _spec_active(self) -> bool:
        """Speculation runs only with a proposer AND a runner that has the
        verify dispatch (mock/timing runners don't; spec_k is then inert)."""
        return (
            self.config.spec_k > 0
            and self._proposer is not None
            and hasattr(self.runner, "spec_step")
        )

    def _propose_drafts(
        self, decode_rows: list[Sequence], chunks: list[tuple[Sequence, int]]
    ) -> list[list[int]]:
        """Per decode row, up to spec_k draft tokens for this step's verify.

        Drafts are charged against the mixed step's chunk budget (whatever
        the scheduled chunks left of it) — a draft token costs the same
        forward FLOPs/bytes as a prefill-chunk token, so letting drafts
        bypass the budget would reintroduce exactly the decode stalls the
        budget bounds. Page extension is opportunistic: on exhaustion the
        row's drafts are dropped rather than preempting anyone (speculation
        is a throughput hint, never worth evicting real work).

        Rows with repetition penalties or a decoding constraint never
        draft: both sample from state that evolves per accepted token
        (history counts, grammar machine), which the per-column verify
        sample cannot replay. Their single-token column stays exact.
        """
        k = self.config.spec_k
        budget = None
        chunk_budget = self.chunk_budget_tokens()
        if chunk_budget > 0:
            budget = max(
                0,
                min(chunk_budget, self.config.max_prefill_tokens)
                - sum(n for _, n in chunks),
            )
        drafts: list[list[int]] = []
        for s in decode_rows:
            # remaining - 1: the verify step emits at most len(draft) + 1
            # tokens, which must never overrun max_tokens / the context
            # window (this is also what keeps every speculative KV write
            # inside the row's position_limit). Effective state: a chained
            # row's in-flight token already counts against the budget.
            dc, de = self._adv(s)
            cap = min(k, self._eff_remaining(s) - 1)
            if budget is not None:
                cap = min(cap, budget)
            sp = s.request.sampling
            if cap <= 0 or sp.frequency_penalty or sp.presence_penalty or s.constraint is not None:
                drafts.append([])
                continue
            # Chained rows (de=1): the host context is stale by the in-flight
            # token. Propose one extra and drop the head — the proposer's
            # first continuation guesses the in-flight token itself; the rest
            # align with the draft positions after it. Any mismatch is caught
            # (losslessly) by the exact-replay verify.
            d = [int(tok) for tok in self._proposer.propose(s.tokens, cap + de)][de:]
            if d:
                need = s.pages_needed(self.config.page_size, dc + 1 + len(d))
                if need:
                    try:
                        self._grow(s, need)
                    except OutOfPagesError:
                        d = []
            if budget is not None:
                budget -= len(d)
            self.spec_tokens_proposed += len(d)
            drafts.append(d)
        return drafts

    def _lp_cols(self, seq: Sequence, lp_aux, i: int, toks: list[int]) -> list[dict] | None:
        """Logprobs entries from the verify dispatch's per-column aux arrays
        ([B, V] / [B, V, k]): one entry per emitted token, column j of row i.
        Chunk rows pass a single token (their column 0)."""
        enc = seq.request.sampling.logprobs
        if not enc or lp_aux is None:
            return None
        alts = min(enc - 1, lp_aux["top_ids"].shape[-1])
        entries = []
        for j, tok in enumerate(toks):
            top = [
                [int(t), float(lp)]
                for t, lp in zip(lp_aux["top_ids"][i, j][:alts], lp_aux["top_lps"][i, j][:alts])
            ]
            entries.append({"id": int(tok), "logprob": float(lp_aux["logprob"][i, j]), "top": top})
        return entries

    def _run_mixed(self, chunks: list[tuple[Sequence, int]]) -> list[tuple[Sequence, EngineOutput]]:
        """One fused dispatch: a 1-token decode row per running sequence plus
        an n-token prefill row per scheduled chunk.

        Every row computes ``tokens[num_cached : num_cached + n]``; a decode
        row is just the degenerate chunk whose span ends at ``len(tokens)``.
        The runner samples every row; host-side, non-final chunk rows
        *discard* the sample — their rng fold counter (``num_generated``)
        does not advance, so the final chunk samples at exactly the fold a
        whole-prompt prefill would have used (golden parity, greedy and
        seeded). With chunking disabled this runs the scheduled whole
        prompts without decode rows — the legacy phase-exclusive step."""
        self.clock.mark(tracing.BUILD)
        fused = self.config.chunk_prefill_tokens > 0
        spec = self._spec_active()
        out: list[tuple[Sequence, EngineOutput]] = []
        decode_rows: list[Sequence] = []
        if (fused or (spec and not chunks)) and self.running:
            failed = self._ensure_next_page()
            if failed is not None:
                out.append((failed, self._final_output(failed)))
            decode_rows = list(self.running)
        # Speculative drafts per decode row (empty lists when spec is off).
        # Must run after _ensure_next_page: preemption there invalidates
        # the row list. A decode row with drafts becomes a verify row — its
        # span is [input token, draft_1..draft_k] at consecutive positions.
        drafts: list[list[int]] = (
            self._propose_drafts(decode_rows, chunks) if spec and decode_rows
            else [[] for _ in decode_rows]
        )
        use_spec = spec and bool(decode_rows)
        self.last_step_info = {
            "decode_rows": len(decode_rows),
            "chunk_rows": len(chunks),
            "chunk_tokens": int(sum(n for _, n in chunks)),
            "decodable": len(self.running),
        }
        if chunks and fused:
            self.mixed_steps += 1
        if chunks and self.running and not decode_rows:
            self.stall_violations += 1  # legacy XOR: this dispatch stalls decodes
        batch = decode_rows + [s for s, _ in chunks]
        if not batch:
            return out
        n_dec = len(decode_rows)
        ns = [1 + len(d) for d in drafts] + [n for _, n in chunks]
        ps = self.config.page_size
        t = max(ns)
        npg = max(len(s.pages) for s in batch)
        b = len(batch)
        tokens = np.zeros((b, t), np.int32)
        positions = np.zeros((b, t), np.int32)
        block_tables = np.zeros((b, npg), np.int32)
        slots = np.zeros((b, t), np.int32)
        last = np.zeros(b, np.int32)
        for i, (s, n) in enumerate(zip(batch, ns)):
            if i < n_dec and n > 1:
                # Verify row: the committed next input token + its drafts.
                # Drafts are NOT in s.tokens — they only join the sequence
                # (and its hash chain) if verification accepts them.
                new = [s.tokens[s.num_cached]] + drafts[i]
            else:
                new = s.tokens[s.num_cached : s.num_cached + n]
            tokens[i, :n] = new
            pos = np.arange(s.num_cached, s.num_cached + n, dtype=np.int32)
            positions[i, :n] = pos
            block_tables[i, : len(s.pages)] = s.pages
            page_arr = np.asarray(s.pages, dtype=np.int32)
            slots[i, :n] = page_arr[pos // ps] * ps + pos % ps
            last[i] = n - 1
        # A row samples iff its span reaches the end of its tokens: all
        # decode rows, and exactly the chunks that finish their prompt.
        samples = [
            i < n_dec or s.num_cached + n == len(s.tokens)
            for i, (s, n) in enumerate(zip(batch, ns))
        ]
        sb = self._sampling_batch(batch, tokens, positions, block_tables, slots, last)
        self._mm_rows(sb, batch, ns, n_dec, positions, lambda s: s.num_cached)
        sb.num_new = np.asarray(ns, np.int32)
        lp_k = LOGPROBS_TOP_K if any(
            s.request.sampling.logprobs and smp for s, smp in zip(batch, samples)
        ) else 0
        sb.logit_mask = self._constraint_masks(batch)
        targets = None
        self.clock.mark(tracing.DISPATCH)  # the runner marks dispatch -> wait
        try:
            if use_spec:
                # Verify dispatch: decode rows score every candidate column,
                # chunk rows only their last (start n-1) — so chunk sampling
                # stays bit-identical to the non-speculative step program.
                sb.spec_start = np.asarray(
                    [0] * n_dec + [n - 1 for _, n in chunks], np.int32
                )
                v = self.config.spec_k + 1
                stepped = (
                    self.runner.spec_step(sb, v, lp_k=lp_k) if lp_k
                    else self.runner.spec_step(sb, v)
                )
                targets, lp_aux = stepped if lp_k else (stepped, None)
                next_tokens = targets[:, 0]
            else:
                stepped = self.runner.step(sb, lp_k=lp_k) if lp_k else self.runner.step(sb)
                next_tokens, lp_aux = stepped if lp_k else (stepped, None)
        except Exception:
            # Chunk seqs live in self.prefilling (and decode rows in
            # self.running); _finish removes them and releases their pages.
            for s in batch:
                self._finish(s, FinishReason.ERROR)
            raise
        self.clock.mark(tracing.POST)
        rec = _InflightStep(
            batch, None, kind="spec" if use_spec else "step",
            ns=ns, n_dec=n_dec, samples=samples, drafts=drafts,
        )
        return out + self._apply_mixed_results(rec, next_tokens, targets, lp_aux)

    def _mm_rows(self, sb: StepBatch, batch, ns, n_dec, positions, cached_of) -> None:
        """Attach multimodal extras to a (possibly mixed) step batch: packed
        image embeddings for the prefill chunk rows and explicit 3-axis
        M-RoPE coords for every row when any row needs them. ``cached_of``
        maps a sequence to its first computed index this step — num_cached
        on the sync path, the effective (in-flight-advanced) state on the
        overlapped path. Both paths produce identical arrays for the same
        row span, which is what keeps chained multimodal dispatches
        bit-identical to the synchronous step."""
        b, t = positions.shape
        if any(s.mm_embeds is not None for s in batch[n_dec:]):
            d = next(s.mm_embeds.shape[1] for s in batch if s.mm_embeds is not None)
            m = max(s.mm_embeds.shape[0] for s in batch if s.mm_embeds is not None)
            img_id = self.runner.cfg.image_token_id
            vid_id = self.runner.cfg.video_token_id
            mm = np.zeros((b, m, d), np.float32)
            off = np.full(b, -1, np.int32)  # -1: text row, no substitution
            counts = np.zeros(b, np.int32)
            for i, (s, n) in enumerate(zip(batch, ns)):
                # Decode rows keep -1 (a sampled image-token id is an
                # ordinary token there, exactly as in pure decode steps).
                if s.mm_embeds is not None and i >= n_dec:
                    mm[i, : s.mm_embeds.shape[0]] = s.mm_embeds
                    counts[i] = s.mm_embeds.shape[0]
                    # Placeholders already covered by cached/previous chunks.
                    cached = np.asarray(s.tokens[: cached_of(s)], np.int32)
                    off[i] = int(np.count_nonzero(
                        (cached == img_id) | (cached == (vid_id if vid_id is not None else -1))
                    ))
            sb.mm_embeds, sb.mm_slot_offset, sb.mm_counts = mm, off, counts
        if any(s.mrope is not None for s in batch):
            # Per-token 3D rope coords for this step's columns. Rows without
            # mrope (text prompts sharing the batch) use sequential positions
            # on all axes — exactly 1D rope. Indices past the stored prompt
            # coords (recomputed generated tokens and decode rows) sit at
            # index + delta.
            mrope3 = np.broadcast_to(positions[:, None, :], (b, 3, t)).copy()
            for i, (s, n) in enumerate(zip(batch, ns)):
                if s.mrope is None:
                    continue
                pos3, delta = s.mrope
                ec = cached_of(s)
                idx = np.arange(ec, ec + n)
                in_prompt = idx < pos3.shape[1]
                cols = np.where(
                    in_prompt[None, :], pos3[:, np.minimum(idx, pos3.shape[1] - 1)],
                    (idx + delta)[None, :],
                )
                mrope3[i, :, :n] = cols
            sb.mrope_positions = mrope3.astype(np.int32)

    def _apply_mixed_results(
        self,
        rec: _InflightStep,
        next_tokens,
        targets,
        lp_aux,
    ) -> list[tuple[Sequence, EngineOutput]]:
        """Apply a (possibly mixed / speculative) step's sampled tokens.

        Shared by the synchronous path and the overlapped harvest. Rows
        whose sequence left RUNNING while the step was in flight
        (cancelled, preempted) are skipped — their samples are discarded.
        A plain dispatch records its ``_chain_map`` at dispatch time; a
        verify records none, so the dispatch after its harvest takes every
        row's token from the host. When called
        from the overlapped harvest, ``last_step_info`` is the *current*
        step's dict — a harvest step's spec fields therefore describe the
        previous dispatch's acceptance, which is when it becomes known."""
        batch, ns, n_dec = rec.batch, rec.ns, rec.n_dec
        drafts, samples = rec.drafts, rec.samples
        use_spec = rec.kind == "spec"
        ps = self.config.page_size
        out: list[tuple[Sequence, EngineOutput]] = []
        spec_accepted = 0
        for i, (s, n) in enumerate(zip(batch, ns)):
            if s.status is not SeqStatus.RUNNING:
                self._chain_map.pop(s.seq_id, None)
                continue
            if use_spec and i < n_dec:
                # Verify row: accept the longest draft prefix the target
                # tokens replay exactly, plus the bonus token after it.
                # targets[i, j] is the token the non-speculative engine
                # would sample after j accepted tokens (fold counter
                # num_generated + j), so once targets[i, j] != draft[j]
                # the later columns were scored on a context the real
                # stream never reaches and are discarded.
                draft = drafts[i]
                emitted = [int(next_tokens[i])]
                while len(emitted) <= len(draft) and emitted[-1] == draft[len(emitted) - 1]:
                    emitted.append(int(targets[i, len(emitted)]))
                accepted: list[int] = []
                for tok in emitted:
                    s.num_cached += 1
                    s.append_token(tok)
                    self._generated_tokens_total += 1
                    accepted.append(tok)
                    if s.check_stop(self._eos, self.config.max_seq_len) is not None:
                        break  # overshoot past EOS/length is discarded
                spec_accepted += max(0, len(accepted) - 1)
                # Roll back speculative pages the accepted span didn't
                # reach: they were freshly allocated this step (commit
                # never passes num_cached), so release returns them to the
                # free pool immediately.
                if not s.is_finished:
                    keep = s.num_cached // ps + 1
                    if len(s.pages) > keep:
                        self._release_pages(s.pages[keep:], s.window_pages[keep:])
                        del s.pages[keep:], s.window_pages[keep:]
                self._commit_filled_pages(s)
                self._release_out_of_window(s)
                # May finish the sequence (page release) — must follow commit.
                self._accept_constrained(s, accepted)
                out.append(self._emit_many(s, accepted, self._lp_cols(s, lp_aux, i, accepted)))
                continue
            # Prompt-token accounting: only the prompt part of the span
            # (recomputed generated tokens and decode rows contribute 0).
            self._prompt_tokens_total += max(0, min(s.num_cached + n, s.num_prompt) - s.num_cached)
            s.num_cached += n
            if n > 1 or not samples[i]:
                s.prefill_chunks += 1
            if samples[i]:
                tok = int(next_tokens[i])
                s.append_token(tok)
                self._generated_tokens_total += 1
                self._commit_filled_pages(s)
                self._release_out_of_window(s)
                # May finish the sequence (page release) — must follow commit.
                self._accept_constrained(s, [tok])
                lp = (self._lp_cols(s, lp_aux, i, [tok]) if use_spec
                      else self._lp_entries(s, lp_aux, i))
                out.append(self._emit(s, tok, lp))
            else:
                # Non-final chunk: publish its full pages (shareable before
                # the prefill finishes) and discard the sampled token — the
                # rng fold counter stays put for the final chunk.
                self._commit_filled_pages(s)
                self._release_out_of_window(s)
        if use_spec:
            drafted = sum(len(d) for d in drafts)
            self.spec_steps += 1
            self.spec_tokens_accepted += spec_accepted
            self.last_step_info["spec_drafted"] = drafted
            self.last_step_info["spec_accepted"] = spec_accepted
            self.last_step_info["spec_accept_rate"] = (
                round(spec_accepted / drafted, 4) if drafted else 0.0
            )
        # Chunks whose final span sampled are decodable now.
        for s in batch[n_dec:]:
            if s in self.prefilling and s.prompt_remaining <= 1 and not s.is_finished:
                self.prefilling.remove(s)
                self.running.append(s)
        return out

    # -- overlapped mixed pipeline -----------------------------------------

    def _ensure_lookahead_pages(
        self, rows: list[Sequence], horizon: int = 1
    ) -> Sequence | None:
        """Give every lookahead decode row pages covering its chained writes
        (positions ``eff_cached .. eff_cached + horizon - 1``, clamped to
        the row's finish line); preempt on exhaustion. horizon > 1 is the
        decode_steps burst composing multiple chained sub-steps up front.
        Rows preempted by an earlier row's allocation are dropped from
        ``rows`` in place (the driver re-filters afterwards for victims
        already behind the cursor). A sole row that cannot fit is returned
        *unfinished* — the step in flight may hold its legitimate finish."""
        ps = self.config.page_size
        i = 0
        while i < len(rows):
            s = rows[i]
            if s.status is not SeqStatus.RUNNING:
                rows.pop(i)
                continue
            need = s.pages_needed(
                ps, self._adv(s)[0] + max(1, min(horizon, self._eff_remaining(s)))
            )
            if need:
                try:
                    self._grow(s, need)
                except OutOfPagesError:
                    victim = self.running[-1] if self.running else s
                    if victim is s and len(self.running) <= 1:
                        return s
                    self._preempt(victim)
                    continue  # retry same index (rows may shrink behind us)
            i += 1
        return None

    def _abort_pipeline(self, batch: list[Sequence]) -> None:
        """A dispatch crashed mid-pipeline: fail its rows AND whatever was
        still in flight (rows finishing inside the in-flight step live only
        there), then reset the chain state so a recovering caller starts
        from a clean pipeline. No pages leak — ``_finish`` releases each
        sequence's pages exactly once."""
        failed: dict[int, Sequence] = {id(s): s for s in batch}
        if self._inflight is not None:
            self._aborted_inflight = len(self._inflight.batch)
            for s in self._inflight.batch:
                failed.setdefault(id(s), s)
            self._inflight = None
        self._inflight_adv = {}
        self._chain_map = {}
        if hasattr(self.runner, "reset_chain"):
            self.runner.reset_chain()
        for s in failed.values():
            if s.status is not SeqStatus.FINISHED:
                self._finish(s, FinishReason.ERROR)

    def _run_mixed_overlapped(
        self, chunks: list[tuple[Sequence, int]]
    ) -> list[tuple[Sequence, EngineOutput]]:
        """Depth-1 overlapped pipeline over *mixed* steps: the serving loop.

        Generalizes PR 10's pure-decode chaining: step N+1 is composed at
        the sequences' *effective* state (``_inflight_adv``) and dispatched
        before step N's tokens reach the host. Decode rows whose input
        token is still in flight gather it in-graph from the previous
        dispatch's device buffer (``_chain_map``); prefill chunk rows feed
        from host as always (their tokens are known). Penalty history is
        restored in-graph for chained rows and the pos_limit mask clamps
        any would-be overrun write, so penalized rows and budget-final
        tokens need no barrier. Rows that finish *inside* the in-flight
        step are excluded from the lookahead (their finish is detected at
        harvest, one step late — streams stay bit-identical to
        overlap=False). A speculative verify in flight is harvested first —
        its acceptance decides every position after it — and the dispatch
        composed on it takes its rows' tokens from the host.

        Compositions the pre-lookahead pipeline barriered on now ride it
        too: constrained rows select their mask in-graph from the
        precomputed lookahead groups (_plan_constraint_lookahead),
        multimodal/mrope rows thread their extras through the explicit-args
        chained program, and decode_steps>1 issues K-1 extra pure-decode
        sub-steps chained back-to-back behind the primary dispatch (the
        whole burst is harvested one step late, exactly like a single
        chained step).
        """
        fused = self.config.chunk_prefill_tokens > 0
        out: list[tuple[Sequence, EngineOutput]] = []
        info = self.last_step_info = {
            "decode_rows": 0,
            "chunk_rows": len(chunks),
            "chunk_tokens": int(sum(n for _, n in chunks)),
            "decodable": len(self.running),
            "chained_rows": 0,
        }
        inf = self._inflight
        if inf is not None and inf.kind == "spec":
            # A verify's acceptance decides every position that follows —
            # nothing can be composed until it lands. Harvest first; the
            # accepted tokens are in s.tokens, where the dispatch below
            # finds them (a verify leaves no entry in _chain_map).
            self._note_barrier("spec")
            out += self._harvest_inflight()
            self.clock.mark(tracing.SCHED)
            inf = None
        # Decode candidates at effective state: running rows still short of
        # their finish line, plus rows whose *final* prompt chunk is in
        # flight (decodable the moment it lands — the chained dispatch
        # consumes their sample device-side). Rows finishing inside the
        # in-flight step are excluded: a chained write would have no legal
        # position; the late stop check at harvest ends them.
        decode_rows = [
            s for s in self.running
            if s.status is SeqStatus.RUNNING and self._eff_remaining(s) >= 1
        ] + [
            s for s in self.prefilling
            if self._adv(s)[1] and self._eff_remaining(s) >= 1
        ]
        if not decode_rows and not chunks:
            # Everything live is finishing inside the in-flight step (or
            # the schedule is page-starved): commit it and rebuild the
            # pipeline next step.
            if inf is not None:
                self._note_barrier("drain")
                out += self._drain_inflight()
            return out
        spec = self._spec_active()  # the runner verifies asynchronously, or _overlap_route barriered
        # decode_steps>1 folds into the pipeline as K chained pure-decode
        # sub-steps behind the primary dispatch. Only clean decode batches
        # burst: chunks change composition mid-burst; speculation already
        # amortizes the round trip; constraints need a fresh mask per token
        # (the lookahead plan is depth-1); per-step logprobs and penalty
        # history need the host between tokens.
        k_cfg = max(1, self.config.decode_steps)
        want_burst = (
            k_cfg > 1
            and not chunks
            and not spec
            and bool(decode_rows)
            and not any(
                s.constraint is not None
                or s.request.sampling.logprobs
                or s.request.sampling.frequency_penalty
                or s.request.sampling.presence_penalty
                for s in decode_rows
            )
        )
        self.clock.mark(tracing.BUILD)
        failed = self._ensure_lookahead_pages(
            decode_rows, k_cfg if want_burst else 1
        )
        if failed is not None and want_burst:
            # The burst horizon didn't fit; a single lookahead token still
            # might — retry at depth 1 before declaring the row stuck.
            want_burst = False
            failed = self._ensure_lookahead_pages(decode_rows, 1)
        if failed is not None:
            # The sole candidate can't extend: the in-flight step may hold
            # its legitimate finish — commit that first, then re-check.
            self._note_barrier("pages")
            out += self._drain_inflight()
            if failed.status is SeqStatus.RUNNING:
                f2 = self._ensure_next_page()
                if f2 is not None:
                    out.append((f2, self._final_output(f2)))
            return out
        # _ensure_lookahead_pages may have preempted rows already behind
        # its cursor; drop them (their recompute is scheduled from waiting).
        decode_rows = [s for s in decode_rows if s.status is SeqStatus.RUNNING]
        k_burst = 1
        if want_burst and decode_rows:
            # Never burst a row past its finish line: every sub-step is a
            # real dispatch, so the shortest row clamps the depth.
            k_burst = max(
                1, min(k_cfg, min(self._eff_remaining(s) for s in decode_rows))
            )
        drafts = (
            self._propose_drafts(decode_rows, chunks) if spec and decode_rows
            else [[] for _ in decode_rows]
        )
        if any(s.mrope is not None for s in decode_rows):
            # mrope decode rows chain fine (their position delta rides the
            # packed buffer) but the verify program wants explicit 3-axis
            # positions; drop the drafts — losslessly — rather than barrier.
            drafts = [[] for _ in decode_rows]
        if any(s.constraint is not None for s in decode_rows) or any(
            s.constraint is not None or s.mm_embeds is not None or s.mrope is not None
            for s, _ in chunks
        ):
            # Verify dispatches carry neither lookahead mask groups nor mm
            # extras: a batch with constrained or multimodal rows anywhere
            # downgrades to a plain chained step (drafts dropped,
            # losslessly) instead of barriering.
            drafts = [[] for _ in decode_rows]
        # All-empty drafts degrade to a plain chained step (bit-identical
        # per the PR 6 contract) — which, unlike a verify, the *next* step
        # can overlap on top of.
        use_spec = spec and any(drafts)
        batch = decode_rows + [s for s, _ in chunks]
        if not batch:
            if inf is not None:
                self._note_barrier("drain")
                out += self._drain_inflight()
            return out
        n_dec = len(decode_rows)
        info["decode_rows"] = n_dec
        if chunks and fused:
            self.mixed_steps += 1
        ns = [1 + len(d) for d in drafts] + [n for _, n in chunks]
        ps = self.config.page_size
        t = max(ns)
        npg = max(len(s.pages) for s in batch)
        b = len(batch)
        tokens = np.zeros((b, t), np.int32)
        positions = np.zeros((b, t), np.int32)
        block_tables = np.zeros((b, npg), np.int32)
        slots = np.zeros((b, t), np.int32)
        last = np.zeros(b, np.int32)
        chain_src = np.full(b, -1, np.int32)
        samples = [False] * b
        for i, (s, n) in enumerate(zip(batch, ns)):
            ec = self._eff_cached(s)
            if i < n_dec:
                src = self._chain_map.get(s.seq_id, -1)
                if src >= 0:
                    chain_src[i] = src  # column 0 gathered in-graph
                else:
                    # Host knows the input token (nothing in flight for this
                    # row — an IndexError here would mean the chain map lost
                    # an in-flight row, never silence it).
                    tokens[i, 0] = s.tokens[ec]
                if n > 1:
                    tokens[i, 1:n] = drafts[i]
                samples[i] = True
            else:
                tokens[i, :n] = s.tokens[ec : ec + n]
                samples[i] = ec + n == len(s.tokens)
            block_tables[i, : len(s.pages)] = s.pages
            if n == 1:  # a decode row: one position, in plain integers
                positions[i, 0] = ec
                slots[i, 0] = s.pages[ec // ps] * ps + ec % ps
                continue  # last[i] stays 0
            pos = np.arange(ec, ec + n, dtype=np.int32)
            positions[i, :n] = pos
            page_arr = np.asarray(s.pages, dtype=np.int32)
            slots[i, :n] = page_arr[pos // ps] * ps + pos % ps
            last[i] = n - 1
        info["chained_rows"] = chained = int((chain_src >= 0).sum())
        info["chained_rows"] += b * (k_burst - 1)  # every sub-step row chains
        sb = self._sampling_batch(batch, tokens, positions, block_tables, slots, last)
        self._mm_rows(sb, batch, ns, n_dec, positions, self._eff_cached)
        sb.num_new = np.asarray(ns, np.int32)
        if any(s.constraint is not None for s in batch):
            if chained:
                # Chained dispatch: masks resolve in-graph against the
                # gathered token (la groups); a host logit_mask cannot ride.
                self._attach_lookahead_masks(sb, batch, chain_src)
            else:
                # Pipeline fill — every token host-known, exact masks ride
                # the plain logit_mask argument as on the sync path.
                sb.logit_mask = self._constraint_masks(batch)
        lp_k = LOGPROBS_TOP_K if any(
            s.request.sampling.logprobs and smp for s, smp in zip(batch, samples)
        ) else 0
        self.clock.mark(tracing.DISPATCH)
        try:
            if use_spec:
                sb.spec_start = np.asarray(
                    [0] * n_dec + [n - 1 for _, n in chunks], np.int32
                )
                v = self.config.spec_k + 1
                dev = self.runner.spec_step_async(
                    sb, v, lp_k=lp_k, chain_src=chain_src if chained else None
                )
                new_inf = _InflightStep(
                    batch, dev, kind="spec", ns=ns, n_dec=n_dec,
                    samples=samples, drafts=drafts,
                )
            else:
                dev = self.runner.step_async(
                    sb, lp_k=lp_k, chain=chained > 0,
                    chain_src=chain_src if chained else None,
                )
                new_inf = _InflightStep(
                    batch, dev, kind="step", ns=ns, n_dec=n_dec,
                    samples=samples, drafts=drafts,
                )
                for j in range(1, k_burst):
                    # decode_steps burst: one extra pure-decode sub-step per
                    # depth, each chaining row i's input from the previous
                    # dispatch's row-i sample (chain_src=None, the identity
                    # map). Host tokens are placeholders; positions/slots
                    # advance by j; sample_steps += j keeps the rng fold
                    # counter on the exact sync-loop lattice.
                    tok_j = np.zeros((b, 1), np.int32)
                    pos_j = positions[:, :1] + j
                    slots_j = np.zeros((b, 1), np.int32)
                    for i, s in enumerate(batch):
                        p = int(positions[i, 0]) + j
                        slots_j[i, 0] = s.pages[p // ps] * ps + p % ps
                    sbj = self._sampling_batch(
                        batch, tok_j, pos_j, block_tables, slots_j,
                        np.zeros(b, np.int32),
                    )
                    sbj.sample_steps += j
                    sbj.num_new = np.ones(b, np.int32)
                    new_inf.extra.append(
                        self.runner.step_async(sbj, chain=True, chain_src=None)
                    )
        except Exception:
            self._abort_pipeline(batch)
            raise
        self.clock.mark(tracing.POST)
        if inf is not None:
            self._overlap_mode = "overlapped"
        else:
            self._note_barrier("fill")
        # The new dispatch's chain map: a plain step's emitting rows; none
        # for a verify, which is harvested before anything is composed on it.
        # Installed *before* the harvest below so late finishes prune their
        # (now meaningless) entries.
        if use_spec:
            self._chain_map = {}
        else:
            self._chain_map = {
                s.seq_id: i
                for i, (s, smp) in enumerate(zip(batch, samples)) if smp
            }
        if inf is not None:
            out += self._harvest_inflight()
        self._inflight = new_inf
        if use_spec:
            # Verify decode rows advance 1..k+1 tokens — unknowable until
            # harvest, which is why the next step harvests first. Only the
            # chunk rows' advance is certain.
            self._inflight_adv = {
                s.seq_id: (n, 1 if smp else 0)
                for s, n, smp in zip(batch[n_dec:], ns[n_dec:], samples[n_dec:])
            }
        else:
            # A burst's sub-steps advance every row one more cached slot and
            # one more emitted token each (rows that finish mid-burst discard
            # the overshoot at harvest).
            self._inflight_adv = {
                s.seq_id: (n + k_burst - 1, (1 if smp else 0) + k_burst - 1)
                for s, n, smp in zip(batch, ns, samples)
            }
        return out

    # -- decode phase ------------------------------------------------------

    def _run_decode(self) -> list[tuple[Sequence, EngineOutput]]:
        """The synchronous decode step: one token a running row. The
        pipelined loop never reaches it (_step_locked drains the pipeline
        before a barrier falls through to the synchronous paths)."""
        if self._inflight is not None:  # config.overlap went off mid-run
            return self._drain_inflight()
        self.clock.mark(tracing.BUILD)
        failed = self._ensure_next_page()
        if failed is not None:
            return [(failed, self._final_output(failed))]
        # Snapshot: _finish() inside _emit() mutates self.running mid-loop.
        batch = list(self.running)
        if not batch:
            return []
        step_batch = self._decode_step_batch(batch)
        step_batch.logit_mask = self._constraint_masks(batch)
        lp_k = LOGPROBS_TOP_K if any(s.request.sampling.logprobs for s in batch) else 0
        self.clock.mark(tracing.DISPATCH)  # the runner marks dispatch -> wait
        try:
            stepped = self.runner.step(step_batch, lp_k=lp_k) if lp_k else self.runner.step(step_batch)
        except Exception:
            for s in batch:
                self._finish(s, FinishReason.ERROR)
            raise
        next_tokens, lp_aux = stepped if lp_k else (stepped, None)
        self.clock.mark(tracing.POST)
        b = len(batch)
        rec = _InflightStep(batch, None, ns=[1] * b, n_dec=b, samples=[True] * b)
        return self._apply_mixed_results(rec, next_tokens, None, lp_aux)

    def _ensure_next_page(self) -> Sequence | None:
        """Give every running sequence the page its next token lands in;
        preempt on exhaustion. If the sole remaining sequence cannot fit (its
        context outgrew the cache) it is finished with ERROR and returned."""
        i = 0
        while i < len(self.running):
            seq = self.running[i]
            need = seq.pages_needed(self.config.page_size, 1)
            if need:
                try:
                    self._grow(seq, need)
                except OutOfPagesError:
                    victim = self.running[-1]
                    if victim is seq and len(self.running) == 1:
                        self._finish(seq, FinishReason.ERROR)
                        return seq
                    self._preempt(victim)
                    continue  # retry same index (list shrank behind us)
            i += 1
        return None

    def _decode_step_batch(self, batch: list[Sequence]) -> StepBatch:
        """Host arrays for a synchronous decode step, each row at its
        committed state."""
        ps = self.config.page_size
        b = len(batch)
        n = max(len(s.pages) for s in batch)
        tokens = np.zeros((b, 1), np.int32)
        positions = np.zeros((b, 1), np.int32)
        block_tables = np.zeros((b, n), np.int32)
        slots = np.zeros((b, 1), np.int32)
        last = np.zeros(b, np.int32)
        for i, s in enumerate(batch):
            pos = s.num_cached
            tokens[i, 0] = s.tokens[pos]
            positions[i, 0] = pos
            block_tables[i, : len(s.pages)] = s.pages
            slots[i, 0] = s.pages[pos // ps] * ps + pos % ps
        return self._sampling_batch(batch, tokens, positions, block_tables, slots, last)

    def _harvest_inflight(self) -> list[tuple[Sequence, EngineOutput]]:
        """Consume the in-flight step, keeping the runner's device-resident
        sample buffer alive — the dispatch already composed on top of it
        chains out of that buffer. Clears the effective-state advance: the
        host has caught up."""
        inf = self._inflight
        if inf is None:
            return []
        self._inflight = None
        self._inflight_adv = {}
        clock = self.clock
        clock.mark(tracing.WAIT)
        res, lp_aux = inf.handle.result()
        clock.mark(tracing.POST)
        out = self._apply_mixed_results(inf, res[:, 0], res if inf.kind == "spec" else None, lp_aux)
        for h in inf.extra:
            # decode_steps burst sub-steps: one more pure-decode token per
            # row each, applied in dispatch order. Rows that finished in an
            # earlier sub-step are skipped by the RUNNING guard inside
            # _apply_mixed_results; their overshoot KV writes land in pages
            # that are only reallocated to dispatches composed *after* these
            # sub-steps, so device program order makes the stale writes
            # harmless (same argument as preemption under overlap).
            clock.mark(tracing.WAIT)
            res_j, lp_j = h.result()
            clock.mark(tracing.POST)
            b = len(inf.batch)
            rec = _InflightStep(
                inf.batch, h, kind="step", ns=[1] * b, n_dec=b,
                samples=[True] * b, drafts=[[] for _ in inf.batch],
            )
            out += self._apply_mixed_results(rec, res_j[:, 0], None, lp_j)
        return out

    def _drain_inflight(self) -> list[tuple[Sequence, EngineOutput]]:
        """Consume the in-flight step without composing on top of it: apply
        its results, then reset the chain state (the device buffer is dead
        until the pipeline refills)."""
        out = self._harvest_inflight()
        self._chain_map = {}
        if hasattr(self.runner, "reset_chain"):
            self.runner.reset_chain()
        return out

    # -- shared helpers ----------------------------------------------------

    def _sampling_batch(self, batch, tokens, positions, block_tables, slots, last) -> StepBatch:
        b = len(batch)
        temp = np.zeros(b, np.float32)
        top_k = np.zeros(b, np.int32)
        top_p = np.ones(b, np.float32)
        seeds = np.zeros(b, np.uint32)
        steps = np.zeros(b, np.int32)
        freq = np.zeros(b, np.float32)
        pres = np.zeros(b, np.float32)
        limits = np.zeros(b, np.int32)
        mrope_delta = np.zeros(b, np.int32)
        for i, s in enumerate(batch):
            sp = s.request.sampling
            temp[i] = sp.temperature
            top_k[i] = sp.top_k
            top_p[i] = sp.top_p
            seeds[i] = np.uint32((sp.seed if sp.seed is not None else s.seq_id * 0x9E3779B9 + 1) & 0xFFFFFFFF)
            # Effective fold counter: a chained row's in-flight token has
            # already consumed fold num_generated (the in-graph history
            # write restores that token at index steps-1; sync paths see
            # an empty advance map, so this stays num_generated there).
            steps[i] = s.num_generated + self._adv(s)[1]
            freq[i] = sp.frequency_penalty
            pres[i] = sp.presence_penalty
            limits[i] = s.position_limit(self.config.max_seq_len)
            if s.mrope is not None:
                mrope_delta[i] = s.mrope[1]
        # Generated-token history feeds the sampler's repetition penalties.
        # Only shipped when some request actually set a penalty: H collapses
        # to 1 otherwise, keeping the packed step input small. Width covers
        # a chained row's in-flight token (written in-graph at steps - 1).
        if freq.any() or pres.any():
            h = max(int(steps.max()) + self.config.decode_steps, 1)
            history = np.full((b, h), -1, np.int32)
            for i, s in enumerate(batch):
                gen = s.tokens[s.num_prompt:]
                history[i, : len(gen)] = gen
        else:
            history = np.full((b, 1), -1, np.int32)
        state_slots = None
        if self.state_slots is not None:
            state_slots = np.fromiter((s.state_slot for s in batch), np.int32, b)
        window_tables = window_slots = None
        if self.window_allocator is not None:
            # The sliding layers' tables, shaped as the full layers': a real
            # column (its full slot is not the null page's) lies inside the
            # window, so its block's window page is held.
            ps = self.config.page_size
            window_tables = np.zeros_like(block_tables)
            window_slots = np.zeros_like(slots)
            for i, s in enumerate(batch):
                window_tables[i, : len(s.window_pages)] = s.window_pages
                if slots.shape[1] == 1 and slots[i, 0]:  # a decode step: one position a row, in plain integers
                    pos = int(positions[i, 0])
                    window_slots[i, 0] = s.window_pages[pos // ps] * ps + pos % ps
            if slots.shape[1] > 1:
                pages = np.take_along_axis(window_tables, np.minimum(positions // ps, block_tables.shape[1] - 1), axis=1)
                window_slots = np.where(slots != 0, pages * ps + positions % ps, 0).astype(np.int32)
        return StepBatch(tokens, positions, block_tables, slots, last, temp, top_k, top_p,
                         seeds, steps, freq, pres, limits, history,
                         mrope_delta=mrope_delta, state_slots=state_slots,
                         window_block_tables=window_tables, window_slot_mapping=window_slots)

    def _release_out_of_window(self, seq: Sequence) -> None:
        """Free pages fully below the sliding-attention window: of a model
        whose layers are all windowed the sequence's pages, of a model that
        mixes window and full layers its *window* pool's pages (the full
        layers read the whole context: their pool's pages stay).

        The block table keeps its positional shape: released entries point
        at the reserved null page 0 — the SWA mask derives key positions
        from table INDEX, not page content, so reads of page 0 there are
        masked out regardless of what another sequence later writes in it.
        Release paths (finish/preempt) skip the zeros."""
        cfg = getattr(self.runner, "cfg", None)
        win = getattr(cfg, "sliding_window", 0)
        if not win or not self.config.swa_free_pages:
            return
        pages, allocator = seq.pages, self.allocator
        if getattr(cfg, "mixed_attention", False):
            if self.window_allocator is None:  # a runner that keeps one page-id space for every layer
                return
            pages, allocator = seq.window_pages, self.window_allocator
        ps = self.config.page_size
        # Tokens at absolute positions < (next_pos - win) are out of every
        # future query's window; a page is releasable once its LAST slot is.
        # The next query is the first token not yet cached: mid-prompt that
        # is the next chunk's first, not the prompt's last.
        keep_from = max(0, min(len(seq.tokens), seq.num_cached + 1) - win) // ps
        # Never release pages the commit walk hasn't published yet (caching
        # on: commit runs first each step, so this only guards odd orderings).
        if self.config.enable_prefix_caching:
            keep_from = min(keep_from, seq.committed_pages)
        if keep_from <= 0:
            return
        drop = [pid for pid in pages[:keep_from] if pid != 0]
        if not drop:
            return
        allocator.release(drop)
        pages[:keep_from] = [0] * keep_from
        if allocator is self.window_allocator:
            self.window_pages_released += len(drop)
            self._window_released_step += len(drop)

    def _commit_filled_pages(self, seq: Sequence) -> None:
        """Publish newly-filled pages to the prefix cache (emits stored events)
        and write them through to the capacity tiers."""
        if not self.config.enable_prefix_caching:
            return
        full_pages = seq.num_cached // self.config.page_size
        blocks = seq.block_seq.blocks
        while seq.committed_pages < full_pages:
            idx = seq.committed_pages
            blk = blocks[idx]
            newly_cached = self.allocator.commit(seq.pages[idx], blk.block_hash, blk.parent_hash, blk.tokens)
            if self.window_allocator is not None and seq.window_pages[idx]:  # under the same hash, in its own pool
                self.window_allocator.commit(seq.window_pages[idx], blk.block_hash, blk.parent_hash)
            if newly_cached and self.block_manager is not None:
                # Deferred: the device->host read happens in flush_offloads(),
                # batched, after the step's outputs have been routed.
                self.pending_offloads.append((blk.block_hash, seq.pages[idx]))
            seq.committed_pages += 1

    def flush_offloads(self) -> None:
        """Write-through pending committed pages to the capacity tiers.

        Called by the service between engine steps (same single-writer
        thread ordering, so committed pages are still live); uses the
        runner's batched multi-page gather when available.
        """
        with self.step_lock:
            if self.block_manager is None or not self.pending_offloads:
                self.pending_offloads = []
                return
            items, self.pending_offloads = self.pending_offloads, []
            self.block_manager.offload_batch(
                items,
                read_pages=getattr(self.runner, "read_pages", None),
                read_pages_async=getattr(self.runner, "read_pages_async", None),
            )

    def abort_all(self, reason: FinishReason = FinishReason.ERROR) -> None:
        """Finish every in-flight sequence (releasing its pages) — used when
        a step failure leaves device state suspect. Blocks until any step
        running in another thread completes (step_lock)."""
        with self.step_lock:
            self._abort_all_locked(reason)

    def _abort_all_locked(self, reason: FinishReason) -> None:
        self._inflight = None
        self._inflight_adv = {}
        self._chain_map = {}
        self._onboards = []  # orphaned fetch threads write into dropped sessions
        if hasattr(self.runner, "reset_chain"):
            self.runner.reset_chain()
        for seq in list(self.running) + list(self.prefilling) + list(self.waiting):
            seq.context.kill()
            self._finish(seq, reason)
        self.pending_offloads = []

    def _emit(self, seq: Sequence, token: int, logprobs: list[dict] | None = None) -> tuple[Sequence, EngineOutput]:
        return self._emit_many(seq, [token], logprobs)

    def _emit_many(self, seq: Sequence, tokens: list[int], logprobs: list[dict] | None = None) -> tuple[Sequence, EngineOutput]:
        reason = seq.check_stop(self._eos, self.config.max_seq_len)
        if reason is not None and not seq.is_finished:
            self._finish(seq, reason)
        # First delta for this sequence: attach the admission wait (frontend
        # RequestTracker observes it once) and close the predictor's loop
        # with the actual TTFT.
        wait_ms = prefill = None
        if seq.admitted_time is not None and not seq.admission_reported:
            seq.admission_reported = True
            wait_ms = max(0.0, (seq.admitted_time - seq.arrival_time) * 1e3)
            prefill = {
                "admitted_mono": time.perf_counter() - (time.monotonic() - seq.admitted_time),
                "chunks": seq.prefill_chunks,
                "steps": self.step_gap_ms_count + 1 - seq.admitted_step,  # this one is not counted yet
                "prompt_tokens": seq.num_prompt,
                "cached_tokens": seq.num_cached_at_start,
            }
            # Pre-admission wait is lost time: a quota-gated deferral is the
            # admission plane's doing, anything else is plain resource wait.
            self._charge_loss("admission" if seq.quota_deferred else "queue", wait_ms)
            if self.admission is not None and tokens:
                self.admission.on_first_token(seq, time.monotonic())
        out = EngineOutput(
            token_ids=tokens,
            finish_reason=seq.finish_reason,
            cumulative_tokens=seq.num_generated,
            prompt_tokens=seq.num_prompt if seq.finish_reason else None,
            cached_tokens=seq.num_cached_at_start if seq.finish_reason else None,
            logprobs=logprobs[: len(tokens)] if logprobs else None,
            admission_wait_ms=round(wait_ms, 3) if wait_ms is not None else None,
            prefill=prefill,
        )
        return seq, out

    def _constraint_masks(self, batch: list[Sequence]) -> np.ndarray | None:
        """bool[B, vocab] for a step: constrained rows get their machine's
        allowed set (force-closing near the budget), others all-True."""
        if not any(s.constraint is not None for s in batch):
            return None
        vocab = self.runner.cfg.vocab_size
        mask = np.ones((len(batch), vocab), bool)
        for i, s in enumerate(batch):
            if s.constraint is not None:
                mask[i] = s.constraint.mask(s.remaining_tokens(self.config.max_seq_len))
        return mask

    def _accept_constrained(self, seq: Sequence, tokens: list[int]) -> None:
        if seq.constraint is None:
            return
        for t in tokens:
            seq.constraint.accept(int(t))
        # Vocabularies without an EOS id can't signal completion through
        # sampling: end the sequence the moment its JSON completes. (With an
        # EOS, the mask steers the model to emit it instead.)
        st = seq.constraint.state
        definitively_done = st.complete() and st.mode == "A"  # not an extendable number
        if not self._eos and definitively_done and not seq.is_finished:
            self._finish(seq, FinishReason.STOP)

    def _lp_entries(self, seq: Sequence, lp_aux, i: int) -> list[dict] | None:
        """One request's logprobs entry from a step's aux arrays (row i):
        chosen-token logprob + this request's own alternatives slice.
        SamplingOptions.logprobs uses the +1 encoding (N = N-1 alternatives);
        the step always computes the full LOGPROBS_TOP_K bucket (one
        compiled program regardless of what each request asked for)."""
        enc = seq.request.sampling.logprobs
        if not enc or lp_aux is None:
            return None
        alts = min(enc - 1, lp_aux["top_ids"].shape[1])
        top = [
            [int(t), float(lp)]
            for t, lp in zip(lp_aux["top_ids"][i][:alts], lp_aux["top_lps"][i][:alts])
        ]
        return [{"id": int(seq.tokens[-1]), "logprob": float(lp_aux["logprob"][i]), "top": top}]

    def _final_output(self, seq: Sequence) -> EngineOutput:
        return EngineOutput(
            token_ids=[],
            finish_reason=seq.finish_reason,
            cumulative_tokens=seq.num_generated,
            prompt_tokens=seq.num_prompt,
            cached_tokens=seq.num_cached_at_start,
        )

    def _preempt(self, seq: Sequence) -> None:
        logger.info("preempting seq %d (%d pages)", seq.seq_id, len(seq.pages))
        self.num_preemptions += 1
        self._cancel_onboards(seq)
        self._release_pages(seq.pages, seq.window_pages)
        self._release_state_slot(seq)  # recompute: the next run starts from zeros in the slot it is given
        seq.pages, seq.window_pages = [], []
        seq.committed_pages = 0
        seq.num_cached = 0
        seq.prefill_chunks = 0
        seq.status = SeqStatus.PREEMPTED
        # Any in-flight advance is void: on re-admission the sequence
        # restarts from num_cached=0, so stale effective-state would
        # overshoot the prompt.
        self._inflight_adv.pop(seq.seq_id, None)
        self._chain_map.pop(seq.seq_id, None)
        if seq in self.running:
            self.running.remove(seq)
        if seq in self.prefilling:  # preempted mid-prompt: re-chunks on resume
            self.prefilling.remove(seq)
        self.waiting.appendleft(seq)

    def _allocate(self, n: int) -> tuple[list[int], list[int]]:
        """Pages for ``n`` more blocks: of the pool, and of a model with a pool
        per layer kind of each pool (else no window pages), or
        ``OutOfPagesError`` and nothing taken."""
        new = self.allocator.allocate(n)
        if self.window_allocator is None:
            return new, []
        try:
            return new, self.window_allocator.allocate(n)
        except OutOfPagesError:
            self.allocator.release(new)
            raise

    def _grow(self, seq: Sequence, need: int) -> None:
        """``need`` more blocks for ``seq`` (``_allocate``)."""
        new, window = self._allocate(need)
        seq.pages.extend(new)
        seq.window_pages.extend(window)

    def _release_pages(self, pages, window_pages=()) -> None:
        """Hands a sequence's pages back, each pool its own (the null page,
        which stands for a page already given back, is nobody's)."""
        if held := [p for p in pages if p != 0]:
            self.allocator.release(held)
        if held := [p for p in window_pages if p != 0]:
            self.window_allocator.release(held)

    def _match_window(self, hashes, matched: list[int]) -> list[int]:
        """The prefix rule of a model with a pool per layer kind. A sliding
        layer's keys cannot be recomputed from the full layers' pages, so a hit
        is the longest prefix of whole blocks whose full pages are cached
        (``matched``, acquired) *and* whose last ``ceil(window / page_size)``
        window pages are: ``matched`` is cut to it in place (what is cut is
        released), and the window pool's table of the hit comes back, acquired:
        the null page for every block wholly under the window. [] for every
        other model."""
        if self.window_allocator is None:
            return []
        reach = -(-self.runner.cfg.sliding_window // self.config.page_size)
        while matched:
            first = max(0, len(matched) - reach)
            got = [self.window_allocator.acquire_cached(h) for h in hashes[first: len(matched)]]
            if None not in got:
                return [0] * first + got
            self.window_allocator.release([p for p in got if p is not None])
            self.allocator.release([matched.pop()])
        return []

    def _take_window_released(self) -> int:
        """Window-pool pages given back since the last STEP record."""
        n, self._window_released_step = self._window_released_step, 0
        return n

    def _release_state_slot(self, seq: Sequence) -> None:
        if seq.state_slot:
            self.state_slots.release(seq.state_slot)
            seq.state_slot = 0

    def _finish(self, seq: Sequence, reason: FinishReason) -> None:
        seq.status = SeqStatus.FINISHED
        seq.finish_reason = reason
        self._cancel_onboards(seq)
        if self.admission is not None:
            self.admission.on_finish(seq)
        self._release_pages(seq.pages, seq.window_pages)
        seq.pages, seq.window_pages = [], []
        self._release_state_slot(seq)
        if seq in self.running:
            self.running.remove(seq)
        if seq in self.prefilling:
            self.prefilling.remove(seq)
        if seq in self.waiting:
            self.waiting.remove(seq)

    # -- bookkeeping -------------------------------------------------------

    def metrics(self) -> ForwardPassMetrics:
        from dynamo_tpu.parallel.moe import DROP_COUNTER

        st = self.allocator.stats()
        moe_choices, moe_dropped = DROP_COUNTER.snapshot()
        return ForwardPassMetrics(
            worker_id=self.config.worker_id,
            kv_active_blocks=st.active_pages,
            kv_total_blocks=st.total_pages,
            num_requests_waiting=len(self.waiting),
            num_requests_running=len(self.running) + len(self.prefilling),
            request_total_slots=self.config.max_batch_size,
            cache_hit_rate=st.hit_rate,
            prompt_tokens_total=self._prompt_tokens_total,
            generated_tokens_total=self._generated_tokens_total,
            moe_choices_total=moe_choices,
            moe_dropped_total=moe_dropped,
        )
