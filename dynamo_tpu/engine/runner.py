"""Bucketed jit execution of the paged forward + fused sampling.

XLA traces/compiles once per distinct input shape; the runner keeps shapes
drawn from a small bucket lattice (batch and prefill-length rounded up to
powers of two, block-table width in page-count steps) so steady-state serving
touches a handful of compiled programs. The KV cache buffers are donated each
step, so cache writes are in-place in HBM; only the sampled token ids
(i32[B]) come back to the host per step.

The forward + sampling are one fused jitted program: logits never leave the
device, avoiding a [B, vocab] device->host transfer per token.
"""

from __future__ import annotations

import contextlib
import dataclasses
import functools
import logging
import threading

import jax
import jax.numpy as jnp
import numpy as np

from dynamo_tpu import executable_store, tracing
from dynamo_tpu.models.config import ModelConfig
from dynamo_tpu.models import llama
from dynamo_tpu.observability.compile import CompileTracker, note_store, persistent_cache_hits, timed_dispatch
from dynamo_tpu.ops.sampling import sample_tokens
from dynamo_tpu.parallel.moe import HELD_COUNTS, router_select

logger = logging.getLogger(__name__)


def next_pow2(n: int) -> int:
    return 1 if n <= 1 else 1 << (n - 1).bit_length()


def _locked(fn):
    """Serialize cache-touching entry points on the runner's ``io_lock``.

    The KV cache buffers are *donated* to every jitted step/write: a second
    thread dispatching against ``self.k_cache`` while a step is in flight
    would either double-donate (JAX "array deleted" crash) or lose one
    thread's reassignment. The engine loop is single-writer, but KV transfer
    services and tier offload run on other executor threads — this mutex is
    what makes their access safe."""

    @functools.wraps(fn)
    def wrapper(self, *args, **kwargs):
        with self.io_lock, self._on_device():
            return fn(self, *args, **kwargs)

    return wrapper


def _delta_mrope(positions: jnp.ndarray, delta: jnp.ndarray | None) -> jnp.ndarray:
    """Equal-coords 3D rope positions from sequential positions + per-row
    delta: [B, T] (+ [B]) -> [B, 3, T]. Exact for decode and for text spans
    after the prompt (HF: position = seq_index + mrope_delta on all axes)."""
    b, t = positions.shape
    p = positions if delta is None else positions + delta[:, None]
    return jnp.broadcast_to(p[:, None, :], (b, 3, t))


def _pack(padded: "StepBatch", chain_src: np.ndarray | None = None) -> np.ndarray:
    """Flatten every step input into one i32 buffer (single host->device
    transfer — each separate transfer costs a fixed latency that dwarfs
    these few KB). ``chain_src`` (``_apply_chain``) rides at its end: -1 in
    every row where the host feeds the tokens."""
    if chain_src is None:
        chain_src = np.full(padded.tokens.shape[0], -1, np.int32)
    return np.concatenate(
        [
            padded.tokens.ravel(),
            padded.positions.ravel(),
            padded.block_tables.ravel(),
            padded.slot_mapping.ravel(),
            padded.last_token_index,
            padded.temperature.view(np.int32),
            padded.top_k,
            padded.top_p.view(np.int32),
            padded.seeds.view(np.int32),
            padded.sample_steps,
            padded.freq_pen.view(np.int32),
            padded.pres_pen.view(np.int32),
            padded.pos_limit,
            padded.history.ravel(),
            padded.mrope_delta,
            chain_src,
            *(() if padded.state_slots is None else (padded.state_slots,)),
            *(() if padded.window_block_tables is None
              else (padded.window_block_tables.ravel(), padded.window_slot_mapping.ravel())),
        ]
    )


def _unpack(packed: jnp.ndarray, b: int, t: int, n: int, h: int, slots: bool = False, pools: bool = False):
    """In-graph inverse of :func:`_pack` (static offsets, free slices);
    ``slots``: the rows' state slots ride at the end (a model with recurrent
    layers) and come back as one more part; ``pools``: behind them the window
    pool's block tables and slot mapping (a model with a pool per layer kind),
    two more parts, flat."""
    sizes = ([b * t, b * t, b * n, b * t, b, b, b, b, b, b, b, b, b, b * h, b, b] + [b] * slots
             + [b * n, b * t] * pools)
    offs = np.concatenate([[0], np.cumsum(sizes)])
    part = [packed[offs[i] : offs[i + 1]] for i in range(len(sizes))]
    return (
        part[0].reshape(b, t),
        part[1].reshape(b, t),
        part[2].reshape(b, n),
        part[3].reshape(b, t),
        part[4],
        jax.lax.bitcast_convert_type(part[5], jnp.float32),
        part[6],
        jax.lax.bitcast_convert_type(part[7], jnp.float32),
        jax.lax.bitcast_convert_type(part[8], jnp.uint32),
        part[9],
        jax.lax.bitcast_convert_type(part[10], jnp.float32),
        jax.lax.bitcast_convert_type(part[11], jnp.float32),
        part[12],
        part[13].reshape(b, h),
        part[14],
        *part[15:],
    )


#: Most chunk slots a split step seats. A step's chunk budget goes to the
#: prompt in progress first and what its tail leaves to the next prompt's head,
#: so a step carries one chunk row or, at a prompt boundary, two; each count
#: (1, 2) is a program of its own per (rows, chunk, pages) corner. More chunk
#: rows (prompts shorter than half the budget, a prefill pool's many-prompt
#: steps) keep the rectangle.
MAX_CHUNK_SLOTS = 2

ROWS_X_T, SPLIT = "rows_x_t", "split"  # a dispatch's layout, as the STEP record names it

#: The step programs' static keywords, by the jitted function's name: what a
#: compiled program is specialised on besides its arguments' forms
#: (``executable_store.StepPrograms`` keeps one a value of them).
STATIC_KEYWORDS = {
    "_step": ("impl", "lp_k"),
    "_step_split": ("nd", "nc", "tc", "n", "h", "lp_k"),
    "_step_packed": ("b", "t", "n", "h", "lp_k"),
    "_step_chained_explicit": ("impl", "lp_k"),
    "_spec_step": ("impl", "lp_k"),
    "_spec_step_chained": ("impl", "lp_k"),
}


def _pack_split(padded: "StepBatch", chunk: np.ndarray, nc: int,
                chain_src: np.ndarray | None = None) -> np.ndarray:
    """One i32 buffer for a chunk step laid out on one token axis: a position
    per decode slot (row i of ``padded`` keeps slot i, column 0), then ``tp``
    per chunk slot (the rows ``chunk`` of ``padded``, in order). A decode slot
    whose row moved to a chunk slot, and a chunk slot beyond the rows there
    are, is padding: it reads and writes the null page. At its end ride the
    decode slots' ``chain_src`` (``_apply_chain``; a chunk row's tokens are the
    host's) and the row each chunk slot samples for (-1: none)."""
    bp, tp = padded.tokens.shape
    c = len(chunk)
    if chain_src is None:
        chain_src = np.full(bp, -1, np.int32)
    chunk_row = np.full(nc, -1, np.int32)
    chunk_row[:c] = chunk
    src = np.zeros(bp + nc, np.intp)  # the row of ``padded`` behind each slot
    src[:bp] = np.arange(bp)
    src[bp: bp + c] = chunk
    pad = np.zeros(bp + nc, bool)
    pad[chunk] = True
    pad[bp + c:] = True

    def tokenwise(a):
        out = np.zeros(bp + nc * tp, np.int32)
        out[:bp] = a[:, 0]
        out[chunk] = 0
        out[bp: bp + c * tp] = a[chunk].ravel()
        return out

    def rowwise(a, fill=None):
        out = a[src]
        if fill is not None:
            out[pad] = fill
        return out.view(np.int32)

    last = np.arange(bp + nc, dtype=np.int32)  # a decode slot's one token is its last
    last[bp:] = bp + np.arange(nc, dtype=np.int32) * tp
    last[bp: bp + c] += padded.last_token_index[chunk]
    return np.concatenate(
        [
            tokenwise(padded.tokens),
            tokenwise(padded.positions),
            rowwise(padded.block_tables, fill=0).ravel(),
            tokenwise(padded.slot_mapping),
            last,
            rowwise(padded.temperature),
            rowwise(padded.top_k),
            rowwise(padded.top_p),
            rowwise(padded.seeds),
            rowwise(padded.sample_steps),
            rowwise(padded.freq_pen),
            rowwise(padded.pres_pen),
            rowwise(padded.pos_limit, fill=0),
            rowwise(padded.history).ravel(),
            chain_src,
            chunk_row,
            *(() if padded.state_slots is None else (rowwise(padded.state_slots, fill=0),)),
            *(() if padded.window_block_tables is None
              else (rowwise(padded.window_block_tables, fill=0).ravel(), tokenwise(padded.window_slot_mapping))),
        ]
    )


def _unpack_split(packed: jnp.ndarray, nd: int, nc: int, tc: int, n: int, h: int, slots: bool = False,
                  pools: bool = False):
    """In-graph inverse of :func:`_pack_split`: the token axis flat, one row
    of block table and sampling fields per slot (and, with ``slots``, one
    state slot: a padding slot's is the null slot; with ``pools``, the window
    pool's block tables and slot mapping, flat, as the last two parts)."""
    toks, r = nd + nc * tc, nd + nc
    sizes = ([toks, toks, r * n, toks, r, r, r, r, r, r, r, r, r, r * h, nd, nc] + [r] * slots
             + [r * n, toks] * pools)
    offs = np.concatenate([[0], np.cumsum(sizes)])
    part = [packed[offs[i] : offs[i + 1]] for i in range(len(sizes))]
    f32 = lambda a: jax.lax.bitcast_convert_type(a, jnp.float32)  # noqa: E731
    return (
        part[0], part[1], part[2].reshape(r, n), part[3], part[4],
        f32(part[5]), part[6], f32(part[7]), jax.lax.bitcast_convert_type(part[8], jnp.uint32),
        part[9], f32(part[10]), f32(part[11]), part[12], part[13].reshape(r, h),
        part[14], *part[15:],
    )


def _apply_chain(first, history, sample_steps, chain_buf, chain_src):
    """Per-row device-resident token sourcing for a chained dispatch.

    ``first`` i32[B] is each row's column-0 input token as the host packed it.
    ``chain_src`` i32[B] holds, per row, an index into ``chain_buf`` (the
    previous step's device-resident samples in its batch's row order) or -1
    for host-fed rows. Chained rows' token is gathered in-graph; host-fed rows (prefill
    chunks, fresh admissions, every row of a synchronous step) keep their
    host token bit for bit.

    The gathered token is also appended to the penalty ``history`` at index
    ``sample_steps - 1``: a chained row's host history is stale by exactly
    the one in-flight token it is chaining, and that token IS the gathered
    value, so the write restores bit-identical penalty state. Host-fed rows
    keep their history as it is; selects only, no scatter, so the program
    stays branch-free and a step's chain costs its trace next to nothing.
    """
    chained = chain_src >= 0
    gathered = chain_buf[jnp.clip(chain_src, 0, chain_buf.shape[0] - 1)]
    first = jnp.where(chained, gathered, first)
    idx = jnp.clip(sample_steps - 1, 0, history.shape[1] - 1)
    here = chained[:, None] & (jnp.arange(history.shape[1])[None, :] == idx[:, None])
    return first, jnp.where(here, gathered[:, None], history)


def _chain_rows(tokens, history, sample_steps, chain_buf, chain_src):
    """``_apply_chain`` on column 0 of a rows x T token rectangle."""
    first, history = _apply_chain(tokens[:, 0], history, sample_steps, chain_buf, chain_src)
    return tokens.at[:, 0].set(first), history


def _chain_out(row_tokens, width: int):
    """A dispatch's samples in the batch's row order, at the chain buffer's one
    width (rows past it, which no engine composes, are not chainable)."""
    n = min(row_tokens.shape[0], width)
    return jnp.pad(row_tokens[:n].astype(jnp.int32), (0, width - n))


@dataclasses.dataclass
class StepBatch:
    """Host-side arrays describing one engine step (pre-padding)."""

    tokens: np.ndarray  # i32[B, T]
    positions: np.ndarray  # i32[B, T]
    block_tables: np.ndarray  # i32[B, N]
    slot_mapping: np.ndarray  # i32[B, T]
    last_token_index: np.ndarray  # i32[B]
    temperature: np.ndarray  # f32[B]
    top_k: np.ndarray  # i32[B]
    top_p: np.ndarray  # f32[B]
    seeds: np.ndarray  # u32[B]
    sample_steps: np.ndarray  # i32[B] — rng fold counter (monotonic per request)
    freq_pen: np.ndarray  # f32[B] — OpenAI frequency_penalty
    pres_pen: np.ndarray  # f32[B] — OpenAI presence_penalty
    pos_limit: np.ndarray  # i32[B] first absolute position KV must never be written at
    history: np.ndarray  # i32[B, H] generated tokens so far, pad -1 (H=1 when no penalties)
    # Multimodal prefill only (None on text batches / decode):
    mm_embeds: np.ndarray | None = None  # f32[B, M, D] image embeddings
    mm_slot_offset: np.ndarray | None = None  # i32[B] placeholders already cached; -1 = text row
    mm_counts: np.ndarray | None = None  # i32[B] embedding rows provided per row
    # Qwen2-VL M-RoPE. Delta rides every packed step (one i32 per row; 0 for
    # text rows — equal coords reduce to 1D rope, so zero-delta is exact);
    # explicit per-token 3D coords are prefill-only (image spans need grid
    # coords a scalar shift can't express).
    mrope_delta: np.ndarray | None = None  # i32[B]; None -> zeros at pad time
    mrope_positions: np.ndarray | None = None  # i32[B, 3, T] (mm prefill only)
    # Constrained decoding, host-known tokens: bool[B, vocab] allowed
    # tokens (sync steps and unchained overlapped dispatches).
    logit_mask: np.ndarray | None = None
    # Constrained decoding, chained dispatches: one-step-lookahead mask
    # groups. Each row carries G candidate masks; the chained program picks
    # row i's mask in-graph as la_masks[i, la_groups[i, tokens[i, 0]]] AFTER
    # the chain gather resolves the device-resident input token. Group 0 is
    # all-True by convention (unconstrained rows, EOS candidates whose
    # sample the engine discards at harvest). Mutually exclusive with
    # logit_mask; requires chain=True.
    la_masks: np.ndarray | None = None  # bool[B, G, vocab]
    la_groups: np.ndarray | None = None  # i32[B, vocab]
    # Mixed-step metadata: real token columns per row (decode rows 1,
    # prefill-chunk rows their chunk length; padding rows 0). Host-side
    # only — never shipped to device (the kernels derive the same
    # information from positions/last_token_index: a decode row in a T>1
    # batch is exact because attention masks per-token positions and its
    # padding columns write KV to the null page). Consumed by the engine's
    # step-composition telemetry, tests, and the bench stall probe.
    num_new: np.ndarray | None = None  # i32[B]
    # Speculative verify (spec_step only): first column each row scores
    # logits at. Decode rows verify every real column (start 0); prefill
    # chunk rows score only their last column (start n-1), which keeps the
    # chunk rows' sampling bit-identical to the non-speculative step.
    spec_start: np.ndarray | None = None  # i32[B]
    # A model with recurrent layers: each row's state slot (the engine gives a
    # sequence one at admission). None = every row the null slot 0, which is
    # what a padding row has: a warm-up's null batch needs no more.
    state_slots: np.ndarray | None = None  # i32[B]
    # A model with a page pool per layer kind (window and full layers mixed):
    # the sliding layers' block tables and slot mapping, page ids of the window
    # pool, shaped as ``block_tables`` / ``slot_mapping``; a block wholly under
    # the window is the null page 0. None = every row the null page, which is
    # what a padding row has.
    window_block_tables: np.ndarray | None = None  # i32[B, N]
    window_slot_mapping: np.ndarray | None = None  # i32[B, T]

    @property
    def batch_size(self) -> int:
        return self.tokens.shape[0]


@dataclasses.dataclass
class DispatchReport:
    """What a runner dispatched during one engine step. Every dispatch site
    fills it (``ModelRunner._dispatch``) and the engine takes it once a step
    (``take_dispatch``) into its STEP record: the seconds sum over the step's
    dispatches, of everything else the last dispatch's value stands."""

    seconds: float = 0.0  # inside the dispatch sites' timed blocks
    attn_phase: str = ""  # "decode" / "verify" / "prefill"
    attn_path: str = ""  # "pallas" / "fallback" / "ring"
    moe_path: str = ""  # "fused" / "widened"; "" for a dense model
    router_select: str = ""  # "passes" / "sort": how the router takes the step's tokens' top k; "" for a dense model
    layout: str = ""  # ROWS_X_T / SPLIT
    step_tokens: int = 0  # token positions the dispatched program computes
    moe_pad_positions: int = 0  # those of them that are padding (slot 0): a routed model's expert layers route them nowhere
    kv_tokens_full: int = 0  # key tokens one full / one windowed attention (sub)layer visits
    kv_tokens_window: int = 0
    # parallel/moe.HELD_COUNTS, counted on the device by the programs of a model
    # whose expert layer holds a share or has identity experts, and summed over
    # the programs whose outputs had reached the host when the report was taken.
    moe_counts: tuple[int, ...] = (0,) * len(HELD_COUNTS)
    state_rows: int = 0  # rows whose state slot the dispatch touched (a model with recurrent layers)


class ModelRunner:
    """Owns device state (params + paged KV cache) and runs engine steps."""

    def __init__(
        self,
        cfg: ModelConfig,
        params: llama.Params,
        *,
        num_pages: int,
        page_size: int,
        max_batch_size: int = 64,
        prefill_bucket: int = 64,
        attn_impl: str | None = None,
        forward_fn=None,
        cache_dtype: jnp.dtype | None = None,
        mesh=None,  # jax.sharding.Mesh for TP/DP execution (see dynamo_tpu.parallel)
        device=None,  # single-device runners: the jax.Device everything lives on
        embed_pooling: str = "mean",  # /v1/embeddings pooling ("mean" | "last")
        window_chunk: int | None = None,  # most tokens a row brings to one step, where the caller bounds it
    ) -> None:
        # Everything this runner is built from but the weights, as the executable
        # store keys it (before any other local: every argument, by construction).
        arguments = {k: v for k, v in locals().items() if k not in ("self", "params")}
        from dynamo_tpu.ops.attention import default_impl

        self.cfg = cfg
        self.num_pages = num_pages
        self.page_size = page_size
        self.max_batch_size = max_batch_size
        self.prefill_bucket = prefill_bucket
        # Resolved once, here: the dispatch telemetry (_attn_dispatch) and
        # the jitted programs must agree on which implementation runs.
        self.attn_impl = attn_impl or default_impl()
        self.mesh = mesh
        # None = jax's default device. Replicas sharing a process each pin
        # their own: every cache-touching entry point runs under
        # jax.default_device(device) (see _locked), so step inputs are
        # created next to the params and cache they are dispatched with.
        self.device = device if mesh is None else None
        self._forward = forward_fn or llama.forward
        # Serializes every cache-donating/reading entry point (see _locked):
        # RLock so a locked method may call another (e.g. device transfer).
        self.io_lock = threading.RLock()
        # First-execution-per-shape observer over every dispatch site: the
        # bucket lattice bounds compiled programs, but it is data-dependent —
        # this is how a production recompile becomes visible (metrics plane
        # syncs counts(); the engine's flight recorder is its event sink).
        self.compile_tracker = CompileTracker()
        # The dispatch site's name and key while its block is open (_dispatch):
        # what _enqueue keeps a compiled program under.
        self._dispatching: tuple[str, tuple] = ("", ())
        #: The owning engine's phase clock (``EngineCore`` sets it): the
        #: blocking programs mark dispatch -> wait where the enqueue returns.
        self.clock: tracing.StepClock | None = None
        # Padded page-counts whose gather/scatter kernels are compiled for
        # this runner (device-transfer warm-up bookkeeping — keyed on the
        # runner object itself, so id() reuse after GC can't skip a warm-up).
        self._devxfer_warm: set[int] = set()
        # What has been dispatched since the engine last took it (take_dispatch).
        self._report: DispatchReport | None = None
        # A chunk step may lay its tokens out on one axis where the model step
        # has the flat path: text models on one device (llama.forward), GQA,
        # MHA and MLA attention alike.
        self._can_split = forward_fn is None and mesh is None and not cfg.mrope_section
        # A model with recurrent layers (KDA layers in place of attention, or a
        # Mamba-2 mixer beside it): a second kind of per-sequence state beside
        # the pages, a fixed-size slot a running sequence (models/kda.py,
        # models/mamba2.py); slot 0 is the null slot, as page 0 is the null page.
        self.recurrent = forward_fn is None and bool(cfg.recurrent_layers)
        if self.recurrent and mesh is not None:
            raise NotImplementedError(f"{cfg.name}: a model with recurrent layers is served on one device, not a mesh")
        self.state_slots = max_batch_size + 1 if self.recurrent else 0
        # A model that mixes window and full layers: a page pool and a block
        # table per layer kind (llama.init_kv_cache). ``num_pages`` is the full
        # layers' pool, which seats the context; the sliding layers' holds a
        # window of pages a row (llama.window_pool_pages: derived from the
        # caller's chunk bound, as many as ``num_pages`` without one).
        self.two_pool = forward_fn is None and cfg.mixed_attention
        self.window_pages = (llama.window_pool_pages(cfg, num_pages, page_size, max_batch_size, window_chunk)
                             if self.two_pool else 0)
        # The step programs of such a model return the expert layers' counters
        # beside their outputs (llama.forward's moe_counts); they wait here, on
        # the device, until a report takes those that are ready.
        self._moe_counted = forward_fn is None and cfg.moe_held_share
        counted = {"moe_counts": True} if self._moe_counted else {}  # llama.forward's keyword, where it applies
        self._moe_counts_pending: list[jax.Array] = []
        # The most recent dispatch's padded batch, until its key tokens are
        # counted into the report (_count_kv): after the enqueue, under the
        # device's shadow, never between a result and the next enqueue.
        self._kv_pending: StepBatch | None = None
        self._dp = 1
        if mesh is not None:
            from dynamo_tpu.parallel.sharding import cache_shardings, shard_params

            params = shard_params(params, mesh)
            # Allocated already sharded: the whole pool never exists on one
            # device (it may not fit there).
            cs = cache_shardings(mesh, cfg.attn_type)
            self.k_cache, self.v_cache = jax.jit(
                lambda: llama.init_kv_cache(cfg, num_pages, page_size, dtype=cache_dtype,
                                            window_pages=self.window_pages or None),
                out_shardings=(cs, cs),
            )()
            self._dp = int(mesh.shape["dp"])
        else:
            with self._on_device():
                self.k_cache, self.v_cache = llama.init_kv_cache(
                    cfg, num_pages, page_size, dtype=cache_dtype, window_pages=self.window_pages or None)
                if self.device is not None:
                    params = jax.device_put(params, self.device)
                # A latent model's per-head up-projections heads-major, so that a
                # step reads each from the stack once (models/mla.lay_heads_major).
                # Under a mesh the leaves shard by head as published.
                from dynamo_tpu.models.mla import lay_heads_major

                params = lay_heads_major(params)
        # The recurrent state buffers (state, conv; their shapes are the
        # model's, ``cfg.state_shapes``), donated through a step and handed
        # back like the caches; () for every other model, whose step programs
        # take and return nothing for it.
        self.state: tuple = ()
        if self.recurrent:
            from dynamo_tpu.models.kda import init_state

            with self._on_device():
                self.state = init_state(cfg, self.state_slots)
        self.params = params
        # Which formulation the routed experts take ("fused" / "widened" / ""
        # for a dense model): the predicate the forward itself dispatches on,
        # so the engine's STEP records carry it beside the attention path.
        from dynamo_tpu.parallel.moe import experts_path

        self.moe_path = experts_path(params.get("layers", {}), mesh=mesh)
        # The router's outputs (0 for a dense model): what a step's ``router_select`` label turns on.
        router = params.get("layers", {}).get("router")
        self._router_outputs = 0 if router is None else int(router.shape[-1])

        def _sample(logits, k_cache, v_cache, temperature, top_k, top_p, seeds, sample_steps,
                    freq_pen, pres_pen, history, logit_mask, lp_k):
            """The tail of a step program: one sampled token a row, with its
            logprobs where asked."""
            keys = jax.vmap(lambda s, c: jax.random.fold_in(jax.random.PRNGKey(s), c))(seeds, sample_steps)
            sample_logits = logits
            if logit_mask is not None:
                # Constrained decoding: disallowed tokens can never sample.
                # Logprobs (below) stay on the RAW logits — they report the
                # model's distribution, not the constrained one.
                from dynamo_tpu.ops.attention import NEG_INF

                sample_logits = jnp.where(logit_mask, logits, NEG_INF)
            with jax.named_scope("sample"):
                next_tokens = sample_tokens(
                    sample_logits, keys, temperature, top_k, top_p,
                    history=history, frequency_penalty=freq_pen, presence_penalty=pres_pen,
                )
            if lp_k:
                from dynamo_tpu.ops.sampling import token_logprobs

                chosen, top_ids, top_lps = token_logprobs(logits, next_tokens, lp_k)
                return next_tokens, k_cache, v_cache, chosen, top_ids, top_lps
            return next_tokens, k_cache, v_cache

        @functools.partial(jax.jit, static_argnames=STATIC_KEYWORDS["_step"], donate_argnums=(1, 2))
        def _step(params, k_cache, v_cache, tokens, positions, block_tables, slot_mapping,
                  last_idx, temperature, top_k, top_p, seeds, sample_steps,
                  freq_pen, pres_pen, pos_limit, history, mrope_delta=None,
                  mm_embeds=None, mm_slot_offset=None, mm_counts=None,
                  mrope_positions=None, logit_mask=None, window_tables=None, window_slots=None,
                  *, impl, lp_k=0, recurrent=None):
            # In-graph finish-line clamp: any column at/past a row's absolute
            # position limit writes KV to the reserved null page 0 instead of
            # a live slot. Host scheduling never dispatches such a column for
            # a live row (and pad rows carry limit 0 with slot 0 already), so
            # this is a no-op for today's callers — it is the guarantee that
            # lets the overlapped engine keep budget-clamped rows in a
            # chained dispatch instead of draining the pipeline.
            slot_mapping = jnp.where(positions < pos_limit[:, None], slot_mapping, 0)
            # mm_* None on text batches; jit specializes once per presence
            # pattern, so the text program carries no multimodal cost.
            mm_kw = {}
            if mm_embeds is not None:
                mm_kw = dict(mm_embeds=mm_embeds, mm_slot_offset=mm_slot_offset, mm_counts=mm_counts)
            if self.cfg.mrope_section:
                mm_kw["mrope_positions"] = (
                    mrope_positions if mrope_positions is not None
                    else _delta_mrope(positions, mrope_delta)
                )
            if recurrent is not None:  # (state, conv, slot ids): back as the last two outputs
                mm_kw["recurrent"] = recurrent
            if window_tables is not None:  # the sliding layers' pool, under the same clamp
                mm_kw.update(window_tables=window_tables, window_pages=self.window_pages,
                             window_slots=jnp.where(positions < pos_limit[:, None], window_slots, 0))
            logits, k_cache, v_cache, *counts = self._forward(
                params, self.cfg, tokens, positions, k_cache, v_cache,
                block_tables, slot_mapping, last_idx, attn_impl=impl, mesh=self.mesh,
                **mm_kw, **counted,
            )
            return (*_sample(logits, k_cache, v_cache, temperature, top_k, top_p, seeds, sample_steps,
                             freq_pen, pres_pen, history, logit_mask, lp_k), *counts)

        self._step_fn = _step

        # The two programs a text step of one device runs, from ``step`` and from
        # ``step_async`` alike: besides the packed inputs each takes the chain
        # buffer (the previous dispatch's samples, ``_chain_width`` wide whatever
        # the rows bucket, so its shape is no part of the jit key) and returns
        # its own samples in that form beside ``_step``'s outputs. A
        # synchronous step packs -1 into every ``chain_src``: the ``where`` in
        # ``_apply_chain`` hands the host's tokens through.
        recurrent, two_pool = self.recurrent, self.two_pool

        @functools.partial(jax.jit, static_argnames=STATIC_KEYWORDS["_step_split"], donate_argnums=(1, 2),
                           donate_argnames=("state",))
        def _step_split(params, k_cache, v_cache, packed, chain_buf, *, nd, nc, tc, n, h, lp_k=0, state=()):
            """A chunk step on one token axis (``_pack_split``): ``nd`` decode
            slots of one position, ``nc`` chunk slots of ``tc``. The same
            forward and sampling fold as ``_step``, row for row. A decode
            slot's chained token is gathered into its one position; the chain
            buffer it returns holds a chunk row's sample (emitted at ``nd +
            slot``) at its row index like any other."""
            (tokens, positions, block_tables, slot_mapping, last_idx, temperature, top_k, top_p,
             seeds, sample_steps, freq_pen, pres_pen, pos_limit, history,
             chain_src, chunk_row, *rest) = _unpack_split(packed, nd, nc, tc, n, h, slots=recurrent, pools=two_pool)
            kept = {"recurrent": (*state, rest[0])} if recurrent else {}
            first, hist = _apply_chain(tokens[:nd], history[:nd], sample_steps[:nd], chain_buf, chain_src)
            tokens = tokens.at[:nd].set(first)
            history = jnp.concatenate([hist, history[nd:]])
            limit = jnp.concatenate([pos_limit[:nd], jnp.repeat(pos_limit[nd:], tc)])
            slot_mapping = jnp.where(positions < limit, slot_mapping, 0)  # _step's finish-line clamp
            if two_pool:
                kept.update(window_tables=rest[-2].reshape(nd + nc, n), window_pages=self.window_pages,
                            window_slots=jnp.where(positions < limit, rest[-1], 0))
            logits, k_cache, v_cache, *counts = llama.forward(
                params, self.cfg, tokens, positions, k_cache, v_cache, block_tables, slot_mapping,
                last_idx, attn_impl=self.attn_impl, split=(nd, nc, tc), **counted, **kept,
            )
            out = (*_sample(logits, k_cache, v_cache, temperature, top_k, top_p, seeds, sample_steps,
                            freq_pen, pres_pen, history, None, lp_k), *counts)
            chain = _chain_out(out[0][:nd], chain_buf.shape[0])
            for k in range(nc):  # a slot without a row holds -1: no index matches
                chain = jnp.where(jnp.arange(chain.shape[0]) == chunk_row[k], out[0][nd + k], chain)
            return out, chain

        self._step_split_fn = _step_split

        @functools.partial(jax.jit, static_argnames=STATIC_KEYWORDS["_step_packed"], donate_argnums=(1, 2),
                           donate_argnames=("state",))
        def _step_packed(params, k_cache, v_cache, packed, chain_buf, *, b, t, n, h, lp_k=0, state=()):
            """The rows x T rectangle from one packed buffer. Each row's
            column-0 token is sourced per ``chain_src`` (the buffer's last
            part) from ``chain_buf`` where the overlapped loop dispatches a
            step before the one before it has reached the host."""
            args = list(_unpack(packed, b, t, n, h, slots=recurrent, pools=two_pool))
            kept = {}
            if two_pool:  # the window pool's tables and slots ride last of all
                kept.update(window_slots=args.pop().reshape(b, t), window_tables=args.pop().reshape(b, n))
            if recurrent:  # the rows' state slots ride behind the chain sources
                kept["recurrent"] = (*state, args.pop())
            chain_src = args.pop()
            # args: 0=tokens, 9=sample_steps, 13=history (see _pack order).
            args[0], args[13] = _chain_rows(args[0], args[13], args[9], chain_buf, chain_src)
            out = _step(params, k_cache, v_cache, *args, impl=self.attn_impl, lp_k=lp_k, **kept)
            return out, _chain_out(out[0], chain_buf.shape[0])

        self._step_packed_fn = _step_packed

        @functools.partial(jax.jit, static_argnames=STATIC_KEYWORDS["_step_chained_explicit"], donate_argnums=(1, 2))
        def _step_chained_explicit(params, k_cache, v_cache, chain_buf, chain_src,
                                   tokens, positions, block_tables, slot_mapping,
                                   last_idx, temperature, top_k, top_p, seeds,
                                   sample_steps, freq_pen, pres_pen, pos_limit,
                                   history, mrope_delta=None,
                                   mm_embeds=None, mm_slot_offset=None, mm_counts=None,
                                   mrope_positions=None, la_masks=None, la_groups=None,
                                   window_tables=None, window_slots=None, logit_mask=None, *, impl, lp_k=0):
            """Explicit-args chained step: mesh runners (the packed buffer
            cannot be row-sharded) and, on one device, any async dispatch
            carrying extras the packed buffer has no slots for — multimodal
            embeds, explicit 3-axis mrope coords, a host-built constraint mask
            (``logit_mask``: no row chains) or lookahead constraint-mask groups.
            Returns ``(_step's outputs, the new chain buffer)``: on one device
            the samples at the chain buffer's one width, as the text programs
            hand them back; a mesh's keep ``[Bp]`` (its programs only ever
            chain among themselves).

            The lookahead mask selection happens strictly AFTER the chain
            gather: each row's group id is looked up at its (possibly
            device-sourced) column-0 token, which is exactly the token the
            host could not know at compose time."""
            tokens, history = _chain_rows(tokens, history, sample_steps, chain_buf, chain_src)
            if la_masks is not None:
                rows = jnp.arange(tokens.shape[0])
                g = la_groups[rows, tokens[:, 0]]
                logit_mask = la_masks[rows, g]
            out = _step(
                params, k_cache, v_cache, tokens, positions, block_tables,
                slot_mapping, last_idx, temperature, top_k, top_p, seeds,
                sample_steps, freq_pen, pres_pen, pos_limit, history, mrope_delta,
                mm_embeds, mm_slot_offset, mm_counts, mrope_positions, logit_mask,
                window_tables, window_slots, impl=impl, lp_k=lp_k,
            )
            return out, (out[0] if self.mesh is not None else _chain_out(out[0], chain_buf.shape[0]))

        self._step_chained_explicit_fn = _step_chained_explicit

        @functools.partial(jax.jit, static_argnames=STATIC_KEYWORDS["_spec_step"], donate_argnums=(1, 2))
        def _spec_step(params, k_cache, v_cache, tokens, positions, block_tables, slot_mapping,
                       verify_indices, temperature, top_k, top_p, seeds, sample_steps,
                       freq_pen, pres_pen, history, mrope_delta=None,
                       mm_embeds=None, mm_slot_offset=None, mm_counts=None,
                       mrope_positions=None, logit_mask=None, window_tables=None, window_slots=None,
                       *, impl, lp_k=0):
            """Speculative verify: one forward scoring V candidate positions
            per row, then a target sample at every one of them.

            ``verify_indices`` i32[B, V] names the token columns to score.
            Losslessness hinges on two properties of the flat [B*V] sampling
            below: (1) every op in ``sample_tokens`` is row-independent, so
            flat row b*V+j computes exactly what a non-speculative step with
            row b's params would; (2) the rng key for column j folds in
            ``sample_steps + j`` — the fold counter the non-speculative
            engine would have reached after accepting j tokens. Acceptance
            on the host is then plain prefix comparison ("exact replay"):
            with counter-based deterministic sampling the Leviathan
            rejection-sampling correction degenerates to equality, because
            the target "draw" at each position is itself reproducible.
            """
            b, v = verify_indices.shape
            mm_kw = {}
            if mm_embeds is not None:
                mm_kw = dict(mm_embeds=mm_embeds, mm_slot_offset=mm_slot_offset, mm_counts=mm_counts)
            if self.cfg.mrope_section:
                mm_kw["mrope_positions"] = (
                    mrope_positions if mrope_positions is not None
                    else _delta_mrope(positions, mrope_delta)
                )
            if window_tables is not None:
                mm_kw.update(window_tables=window_tables, window_slots=window_slots, window_pages=self.window_pages)
            logits, k_cache, v_cache = self._forward(
                params, self.cfg, tokens, positions, k_cache, v_cache,
                block_tables, slot_mapping, verify_indices[:, 0],
                attn_impl=impl, mesh=self.mesh,
                logit_indices=verify_indices, contiguous_positions=False,
                **mm_kw,
            )  # f32[B, V, vocab]
            flat = logits.reshape(b * v, logits.shape[-1])
            cnt = (sample_steps[:, None] + jnp.arange(v, dtype=sample_steps.dtype)).reshape(-1)
            keys = jax.vmap(lambda s, c: jax.random.fold_in(jax.random.PRNGKey(s), c))(
                jnp.repeat(seeds, v), cnt
            )
            sample_logits = flat
            if logit_mask is not None:
                from dynamo_tpu.ops.attention import NEG_INF

                sample_logits = jnp.where(jnp.repeat(logit_mask, v, axis=0), flat, NEG_INF)
            with jax.named_scope("sample"):
                targets = sample_tokens(
                    sample_logits, keys,
                    jnp.repeat(temperature, v), jnp.repeat(top_k, v), jnp.repeat(top_p, v),
                    history=jnp.repeat(history, v, axis=0),
                    frequency_penalty=jnp.repeat(freq_pen, v),
                    presence_penalty=jnp.repeat(pres_pen, v),
                )
            if lp_k:
                from dynamo_tpu.ops.sampling import token_logprobs

                chosen, top_ids, top_lps = token_logprobs(flat, targets, lp_k)
                return (targets.reshape(b, v), k_cache, v_cache, chosen.reshape(b, v),
                        top_ids.reshape(b, v, lp_k), top_lps.reshape(b, v, lp_k))
            return targets.reshape(b, v), k_cache, v_cache

        self._spec_step_fn = _spec_step

        @functools.partial(jax.jit, static_argnames=STATIC_KEYWORDS["_spec_step_chained"], donate_argnums=(1, 2))
        def _spec_step_chained(params, k_cache, v_cache, chain_buf, chain_src,
                               tokens, positions, block_tables, slot_mapping,
                               verify_indices, temperature, top_k, top_p, seeds,
                               sample_steps, freq_pen, pres_pen, history,
                               mrope_delta=None, window_tables=None, window_slots=None, *, impl, lp_k=0):
            """Chained speculative verify: decode rows' column-0 (bonus/base)
            token gathers from the previous dispatch's device-resident
            samples; draft columns 1..K and prefill-chunk rows feed from host
            (drafts are host-proposed, chunk tokens are prompt text). The
            same losslessness argument as _spec_step applies unchanged — the
            gathered token equals the token the host would have shipped."""
            tokens, history = _chain_rows(tokens, history, sample_steps, chain_buf, chain_src)
            return _spec_step(
                params, k_cache, v_cache, tokens, positions, block_tables,
                slot_mapping, verify_indices, temperature, top_k, top_p, seeds,
                sample_steps, freq_pen, pres_pen, history, mrope_delta,
                window_tables=window_tables, window_slots=window_slots, impl=impl, lp_k=lp_k,
            )

        self._spec_step_chained_fn = _spec_step_chained

        # The latest async step's samples, device-resident, in the batch's row
        # order: i32[_chain_width] on one device, whatever the rows bucket and
        # whichever program ran; a mesh's programs keep [Bp]. None after a
        # verify, whose tokens the host hands on (nothing chains out of one).
        self._chain_tokens = None
        self._chain_width = self._bucket_batch(max_batch_size)
        # What a step that chains nothing passes for the buffer.
        self._chain_idle = jax.device_put(np.zeros(self._chain_width, np.int32), self.device)

        @functools.partial(jax.jit, donate_argnums=(0, 1))
        def _write_page(k_cache, v_cache, k, v, pid):
            return (
                k_cache.at[:, pid].set(k.astype(k_cache.dtype)),
                v_cache.at[:, pid].set(v.astype(v_cache.dtype)),
            )

        self._write_page_fn = _write_page

        @jax.jit
        def _gather_pages(k_cache, v_cache, pids):
            return k_cache[:, pids], v_cache[:, pids]

        self._gather_pages_fn = _gather_pages

        @functools.partial(jax.jit, donate_argnums=(0, 1))
        def _scatter_pages(k_cache, v_cache, ks, vs, pids):
            # ks/vs: [L, N, ps, W]; one in-place scatter along the page axis.
            return (
                k_cache.at[:, pids].set(ks.astype(k_cache.dtype)),
                v_cache.at[:, pids].set(vs.astype(v_cache.dtype)),
            )

        self._scatter_pages_fn = _scatter_pages

        @jax.jit
        def _embed(params, tokens, mask):
            return llama.encode(params, self.cfg, tokens, mask, pooling=embed_pooling)

        self._embed_fn = _embed
        #: The step programs kept compiled and the store they are loaded from
        #: and written to (``dynamo_tpu/executable_store.py``); None where the
        #: process has no store: _enqueue then calls the jitted function.
        self._programs = self._step_programs(arguments)

    def _step_programs(self, arguments: dict) -> executable_store.StepPrograms | None:
        store = executable_store.open_store()
        if store is None:
            return None
        try:  # the resolved implementation too: the default is the platform's and the environment's
            key = executable_store.built_from({**arguments, "attn_impl_resolved": self.attn_impl})
        except executable_store.Unkeyable as e:
            logger.info("%s: no executable store for this runner (%s)", self.cfg.name, e)
            return None
        devices = list(self.mesh.devices.flat) if self.mesh is not None else list(self.k_cache.devices())
        return executable_store.StepPrograms(store, key, devices, note=note_store, cache_hits=persistent_cache_hits,
                                             on_refusal=self.compile_tracker.refused)

    def _on_device(self):
        """Context placing uncommitted arrays on this runner's device."""
        if self.device is None:
            return contextlib.nullcontext()
        return jax.default_device(self.device)

    # -- tier access (block manager offload/onboard) -----------------------

    def _refuse_two_pool(self, what: str) -> None:
        if self.two_pool:
            raise NotImplementedError(
                f"{self.cfg.name}: {what} is not served for a model with a page pool per layer kind (window and "
                "full layers mixed): a page id names a page of one kind's pool, and a block's window pages may be gone")

    @_locked
    def read_page(self, page_id: int) -> tuple[np.ndarray, np.ndarray]:
        """Device->host copy of one page: ([L, ps, kv, hd], [L, ps, kv, hd])."""
        self._refuse_two_pool("reading a page across layers (offload, transfer)")
        return (
            np.asarray(self.k_cache[:, page_id]),
            np.asarray(self.v_cache[:, page_id]),
        )

    @_locked
    def read_pages(self, page_ids: list[int]) -> list[tuple[np.ndarray, np.ndarray]]:
        """Batched device->host copy: one gather + one transfer for N pages.

        Page ids are padded to a power-of-two bucket so the jitted gather
        compiles for a handful of shapes only.
        """
        return self.read_pages_async(page_ids).wait()

    @_locked
    def read_pages_async(self, page_ids: list[int]) -> "InFlightPages":
        """Dispatch a batched page gather WITHOUT blocking on the result.

        Holds ``io_lock`` only for the gather dispatch + D2H kickoff, then
        returns an :class:`InFlightPages` handle whose ``wait()`` blocks on
        the host buffers. The gather output is a fresh device array (not an
        alias of the cache), so engine steps that donate the cache buffers
        can run while the copy is in flight — this is what lets a chunked
        KV transfer overlap chunk N+1's gather with chunk N's pack + wire.
        Same pow2 bucketing as :meth:`read_pages`: no new compiled shapes.
        """
        if not page_ids:
            return InFlightPages(None, None, 0)
        self._refuse_two_pool("reading pages across layers (offload, transfer)")
        n = len(page_ids)
        padded = np.zeros(next_pow2(n), np.int32)
        padded[:n] = page_ids
        k, v = self._gather_pages_fn(self.k_cache, self.v_cache, jnp.asarray(padded))
        for buf in (k, v):
            try:  # start the device->host DMA early (best-effort API)
                buf.copy_to_host_async()
            except Exception:
                pass
        return InFlightPages(k, v, n)

    @_locked
    def write_page(self, page_id: int, k: np.ndarray, v: np.ndarray) -> None:
        """Host->device copy into one page (in place via buffer donation)."""
        self._refuse_two_pool("writing a page across layers (onboard, transfer)")
        self.k_cache, self.v_cache = self._write_page_fn(
            self.k_cache, self.v_cache, jnp.asarray(k), jnp.asarray(v), page_id
        )

    @_locked
    def write_pages(self, page_ids: list[int], ks, vs) -> None:
        """Batched host->device write: one transfer + one in-place scatter for
        N pages (the per-page path costs a full dispatch round-trip each).

        ``ks``/``vs``: per-page arrays [L, ps, W] (stacked on axis 1 here) or
        pre-stacked [L, N, ps, W] device/host arrays.
        """
        if not page_ids:
            return
        self._refuse_two_pool("writing pages across layers (onboard, transfer)")
        n = len(page_ids)
        k_stack = np.stack(ks, axis=1) if isinstance(ks, (list, tuple)) else ks
        v_stack = np.stack(vs, axis=1) if isinstance(vs, (list, tuple)) else vs
        padded_n = next_pow2(n)
        pids = np.zeros(padded_n, np.int32)
        pids[:n] = page_ids
        if padded_n != n:
            pad = ((0, 0), (0, padded_n - n)) + ((0, 0),) * (k_stack.ndim - 2)
            # Device inputs (pull-transport ingestion) must stay on device:
            # np.pad would bounce the whole stack through the host, defeating
            # the no-host-bounce pull path. jnp.pad keeps it a device op and
            # still works for host ndarrays.
            xp = jnp if isinstance(k_stack, jax.Array) else np
            k_stack = xp.pad(k_stack, pad)
            v_stack = xp.pad(v_stack, pad)
            pids[n:] = 0  # padding writes land in the reserved null page
        self.k_cache, self.v_cache = self._scatter_pages_fn(
            self.k_cache, self.v_cache, jnp.asarray(k_stack), jnp.asarray(v_stack),
            jnp.asarray(pids),
        )

    # -- bucketing ---------------------------------------------------------

    def _bucket_batch(self, b: int) -> int:
        bucket = min(next_pow2(b), max(self.max_batch_size, b))
        # Batch is dp-sharded: round up to a multiple of the dp axis size.
        return -(-bucket // self._dp) * self._dp

    def _bucket_time(self, t: int) -> int:
        # Mixed steps (decode rows fused with prefill chunks) draw T from
        # the same lattice: T = the longest chunk <= chunk_prefill_tokens,
        # so chunking adds no buckets beyond what whole-prompt prefill
        # already compiles (it strictly narrows the range, since the chunk
        # budget <= max_prefill_tokens).
        if t <= 1:
            return 1
        return min(next_pow2(t), max(self.prefill_bucket * ((t + self.prefill_bucket - 1) // self.prefill_bucket), t))

    def _bucket_pages(self, n: int) -> int:
        return max(1, next_pow2(n))

    def _pad(self, batch: StepBatch) -> StepBatch:
        b, t = batch.tokens.shape
        bp = self._bucket_batch(b)
        tp = self._bucket_time(t)
        np_ = self._bucket_pages(batch.block_tables.shape[1])
        if self.two_pool and batch.window_block_tables is None and batch.block_tables.any():
            # Only a batch of null rows (a warm-up) may leave the window pool's
            # tables out: real rows would read and write its null page.
            raise ValueError(f"{self.cfg.name}: a batch for a model with a page pool per layer kind names pages of the "
                             "full pool and carries no window_block_tables / window_slot_mapping")
        hp = next_pow2(batch.history.shape[1])  # 1 when no penalties in batch
        mm = None
        if batch.mm_embeds is not None:
            mp = next_pow2(batch.mm_embeds.shape[1])
            mm = np.zeros((bp, mp, batch.mm_embeds.shape[2]), batch.mm_embeds.dtype)
            mm[: batch.mm_embeds.shape[0], : batch.mm_embeds.shape[1]] = batch.mm_embeds
        mrope3 = None
        if batch.mrope_positions is not None:
            mrope3 = np.zeros((bp, 3, tp), np.int32)
            mrope3[: batch.mrope_positions.shape[0], :, : batch.mrope_positions.shape[2]] = batch.mrope_positions
        lmask = None
        if batch.logit_mask is not None:
            lmask = np.ones((bp, batch.logit_mask.shape[1]), bool)
            lmask[: batch.logit_mask.shape[0]] = batch.logit_mask
        la_m = la_g = None
        if batch.la_masks is not None:
            gb, g, vocab = batch.la_masks.shape
            gp = next_pow2(g)
            # Pad rows and pad groups are all-True with group id 0: padding
            # samples stay unconstrained, exactly as on the sync path.
            la_m = np.ones((bp, gp, vocab), bool)
            la_m[:gb, :g] = batch.la_masks
            la_g = np.zeros((bp, vocab), np.int32)
            la_g[: batch.la_groups.shape[0]] = batch.la_groups

        def pad2(a, rows, cols, fill=0):
            out = np.full((rows, cols), fill, a.dtype)
            out[: a.shape[0], : a.shape[1]] = a
            return out

        def pad1(a, rows, fill=0):
            out = np.full((rows,), fill, a.dtype)
            out[: a.shape[0]] = a
            return out

        return StepBatch(
            tokens=pad2(batch.tokens, bp, tp),
            positions=pad2(batch.positions, bp, tp),
            block_tables=pad2(batch.block_tables, bp, np_),
            slot_mapping=pad2(batch.slot_mapping, bp, tp),
            last_token_index=pad1(batch.last_token_index, bp),
            temperature=pad1(batch.temperature, bp),
            top_k=pad1(batch.top_k, bp),
            top_p=pad1(batch.top_p, bp, fill=1.0),
            seeds=pad1(batch.seeds, bp),
            sample_steps=pad1(batch.sample_steps, bp),
            freq_pen=pad1(batch.freq_pen, bp),
            pres_pen=pad1(batch.pres_pen, bp),
            pos_limit=pad1(batch.pos_limit, bp),  # pad rows: limit 0 -> null page
            history=pad2(batch.history, bp, hp, fill=-1),
            mm_embeds=mm,
            mm_slot_offset=None if batch.mm_slot_offset is None else pad1(batch.mm_slot_offset, bp, fill=-1),
            mm_counts=None if batch.mm_counts is None else pad1(batch.mm_counts, bp),
            mrope_delta=(np.zeros(bp, np.int32) if batch.mrope_delta is None
                         else pad1(batch.mrope_delta, bp)),
            mrope_positions=mrope3,
            logit_mask=lmask,
            la_masks=la_m,
            la_groups=la_g,
            num_new=None if batch.num_new is None else pad1(batch.num_new, bp),
            spec_start=None if batch.spec_start is None else pad1(batch.spec_start, bp),
            state_slots=(None if not self.recurrent else np.zeros(bp, np.int32) if batch.state_slots is None
                         else pad1(batch.state_slots.astype(np.int32), bp)),
            window_block_tables=(None if not self.two_pool else np.zeros((bp, np_), np.int32)
                                 if batch.window_block_tables is None else pad2(batch.window_block_tables, bp, np_)),
            window_slot_mapping=(None if not self.two_pool else np.zeros((bp, tp), np.int32)
                                 if batch.window_slot_mapping is None else pad2(batch.window_slot_mapping, bp, tp)),
        )

    # -- execution ---------------------------------------------------------

    def _select_impl(self, padded: StepBatch) -> str | None:
        """Pick the attention path for a (mesh-sharded) step.

        Whole-prompt prefills on a mesh with an ``sp`` axis run sequence-
        parallel ring attention (MLA included — its absorbed form rings the
        latent/rope stream, ``models/mla.py``): every sequence's context
        starts at position 0 inside this chunk, so attending only the
        in-flight K/V is exact. Chunk-continuations and decode use the
        paged path (they must read the cache)."""
        t = padded.tokens.shape[1]
        if (
            self.mesh is not None
            and int(self.mesh.shape.get("sp", 1)) > 1
            and t > 1
            and t % int(self.mesh.shape["sp"]) == 0
            and bool((padded.positions[:, 0] == 0).all())
        ):
            return "ring"
        return self.attn_impl

    def _attn_dispatch(self, padded: StepBatch, impl: str | None, *, verify: bool = False,
                       split: bool = False) -> tuple[str, str]:
        """(phase, path) the attention layer will take for this dispatch.

        A host-side mirror of the models/* routing predicates (pure shape
        math — no tracing), so every engine step can record whether its
        attention ran on a Pallas kernel ("pallas"), the XLA gather
        formulation ("fallback"), or the sequence-parallel ring path
        ("ring") without touching the jitted program. A model with
        recurrent layers also says "fallback" for a step whose decode rows
        (every row of a one-token step, the decode slots of a ``split``
        one) leave the conv kernel or the state kernel for the gathered XLA
        step; a chunk row's chunked recurrence in XLA is the served path."""
        t = int(padded.tokens.shape[1])
        phase = "verify" if (verify and t > 1) else ("decode" if t == 1 else "prefill")
        # (other models count nothing (0, 0); a model with recurrent layers counts its one kind of layer that attends)
        if self.cfg.sliding_window or self.cfg.attn_type == "mla" or self.cfg.recurrent_layers:
            self._kv_pending = padded
        if impl == "ring":
            return phase, "ring"
        if impl != "pallas":  # windowed layers take the kernels under the same predicates
            return phase, "fallback"
        from dynamo_tpu.ops.pallas_paged import interpret_mode

        interp = interpret_mode()
        t_q = t if phase == "verify" else 1  # prefill kernel tiles T freely
        if self.cfg.attn_type == "mla":
            from dynamo_tpu.ops.pallas_mla import mla_decode_supported

            # Every attention sublayer alike, and whatever T: a row's queries
            # ride the multi-query kernel in tiles within its row cap
            # (models/mla._attend_paged), on the split token axis too.
            ok = mla_decode_supported(
                self.k_cache.shape[-1], self.v_cache.shape[-1], 1, self.cfg.num_heads, interpret=interp,
            )
        else:
            from dynamo_tpu.ops.pallas_paged import decode_kernel_supported

            ok = decode_kernel_supported(
                self.cfg.num_heads, self.cfg.head_dim, self.k_cache.shape[-1],
                t_q, interpret=interp if phase != "prefill" else False,
            )
        if self.recurrent and (t == 1 or split):
            from dynamo_tpu.ops import pallas_conv, pallas_kda, pallas_mamba

            state, conv = self.state  # [layers * slots, heads, key | state, value | channels], [.., taps - 1, rows, lanes]
            ok = ok and pallas_conv.supported(1, *conv.shape[2:]) and (
                pallas_mamba if self.cfg.ssm_heads else pallas_kda).supported(*state.shape[2:])
        return phase, "pallas" if ok else "fallback"

    def _count_kv(self, report: DispatchReport) -> None:
        if self._kv_pending is not None:
            report.kv_tokens_full, report.kv_tokens_window = self._kv_tokens(self._kv_pending)
            self._kv_pending = None

    def _kv_tokens(self, padded: StepBatch) -> tuple[int, int]:
        """Key tokens one layer of each kind has to visit in this dispatch:
        a full layer (an MLA model's attention sublayer) every row's context;
        a windowed layer at most the window plus the row's new tokens less
        one. Padding rows (a null block table) count nothing. Only a model
        with a windowed layer, with latent attention or with recurrent layers
        (a mixer beside its attention, or periods round one layer that attends)
        is counted."""
        pos = np.asarray(padded.positions)[np.asarray(padded.block_tables).any(axis=1)]
        if not len(pos):
            return 0, 0
        context = pos.max(axis=1).astype(np.int64) + 1
        win = self.cfg.sliding_window
        if not win:
            return int(context.sum()), 0
        first = np.where(pos > 0, pos, np.iinfo(np.int32).max).min(axis=1)
        new = context - np.minimum(pos[:, 0], first)
        return int(context.sum()), int(np.minimum(context, win + new - 1).sum())

    # -- the step's dispatch report -------------------------------------------

    @contextlib.contextmanager
    def _dispatch(self, program: str, key: tuple, padded: StepBatch, impl: str | None,
                  layout: tuple[str, int], *, verify: bool = False):
        """What every dispatch site runs its jitted call under: the block is
        timed for the compile tracker, and the step's report takes the
        dispatch's labels and its seconds. ``key`` is everything the program
        specializes on after padding (the compile cache key XLA sees);
        ``layout`` the layout and the token positions it computes."""
        report = self._report
        if report is None:
            report = self._report = DispatchReport(moe_path=self.moe_path)
        report.attn_phase, report.attn_path = self._attn_dispatch(padded, impl, verify=verify, split=layout[0] == SPLIT)
        report.layout, report.step_tokens = layout
        report.router_select = router_select(report.step_tokens, self._router_outputs, self.cfg.num_experts_per_token)
        if report.moe_path:
            report.moe_pad_positions = report.step_tokens - int(np.count_nonzero(padded.slot_mapping))
        if padded.state_slots is not None:
            report.state_rows = int(np.count_nonzero(padded.state_slots))
        # A dispatch outside an engine step (a warm-up drives the runner
        # directly) is no part of the next step's dispatch time, and its
        # first call is no recompile of the serving path: the tracker's event
        # says which it was.
        in_step = self.clock is not None and self.clock.in_step
        timed = timed_dispatch(self.compile_tracker, program, key, in_step=in_step)
        self._dispatching = (program, key)
        with timed:
            yield
        if self.clock is None or in_step:
            report.seconds += timed.seconds

    def _enqueue(self, fn, *args, **kwargs):
        """Calls a step program from a frame of its own, inside the dispatch
        site's ``_dispatch`` block. With an executable store the program is the
        one kept compiled for the site's key (``StepPrograms.call``: loaded from
        the store at the key's first sight, or lowered, compiled and written
        there); without, the jitted function itself. Called from ``step``'s own
        frame, the first call of every chunk program took 0.45 to 0.55 s longer
        on the v5e's host (a tenth of a cell's warm set-up over its 25 to 28
        chunk programs), with the frame in between it does not: measured both
        ways on four machines, why is not established (PERF.md, PR 28)."""
        if self._programs is None:
            return fn(*args, **kwargs)
        return self._programs.call(fn, *self._dispatching, STATIC_KEYWORDS[fn.__name__], args, kwargs)

    def take_dispatch(self) -> DispatchReport | None:
        """The report of what was dispatched since the last take, which this
        take clears: a step that dispatched nothing (one that only harvests)
        takes ``None`` and cannot count the step before it again."""
        report, self._report = self._report, None
        if report is not None:
            self._count_kv(report)  # an async site has not counted yet
            if self._moe_counts_pending:
                report.moe_counts = self._ready_moe_counts()
        return report

    def _refuse_recurrent(self, what: str) -> None:
        if self.recurrent:
            raise NotImplementedError(
                f"{self.cfg.name}: {what} is not served for a model with recurrent layers (a rejected or replayed "
                "token cannot be taken out of a state again; rows with extras ride programs that carry no state)")

    def _keep_state(self, out: tuple) -> tuple:
        """A step program's outputs without the recurrent state buffers, its
        last two outputs, which become the runner's own again (as the caches
        do); every other model's programs return none."""
        if not self.recurrent:
            return out
        *out, state, conv = out
        self.state = (state, conv)
        return tuple(out)

    def _keep_moe_counts(self, out: tuple) -> tuple:
        """A step program's outputs without the expert layers' counters, which
        stay on the device (their copy to the host started) for a later report."""
        if not self._moe_counted:
            return out
        *out, counts = out
        counts.copy_to_host_async()
        self._moe_counts_pending.append(counts)
        return tuple(out)

    def _ready_moe_counts(self) -> tuple[int, ...]:
        """The pending counters of the programs that have ended, summed and
        dropped: a synchronous step's own, a pipelined step's predecessor's."""
        pending, ready = self._moe_counts_pending, 0
        while ready < len(pending) and pending[ready].is_ready():
            ready += 1
        self._moe_counts_pending = pending[ready:]
        total = np.sum([np.asarray(c) for c in pending[:ready]], axis=0, dtype=np.int64) if ready else np.zeros(len(HELD_COUNTS))
        return tuple(int(v) for v in total)

    @property
    def last_attn_dispatch(self) -> tuple[str, str] | None:
        """(phase, path) of the most recent dispatch not yet taken."""
        r = self._report
        return None if r is None else (r.attn_phase, r.attn_path)

    @property
    def last_step_layout(self) -> tuple[str, int] | None:
        """(layout, token positions) of the most recent dispatch not yet taken."""
        r = self._report
        return None if r is None else (r.layout, r.step_tokens)

    def _mark_wait(self) -> None:
        """The jitted call has returned (enqueued); what follows blocks on the
        result. Outside an engine step (warm-up, tests) there is no clock."""
        if self.clock is not None:
            self.clock.mark_in_step(tracing.WAIT)
        self._count_kv(self._report)  # the device is busy now: host work here costs no step time

    def _chunk_rows(self, padded: StepBatch, chain_src: np.ndarray | None = None) -> np.ndarray | None:
        """The rows of a chunk step that take the chunk slots of a split token
        axis: those with more than one real column (``last_token_index + 1``,
        a row's ``num_new``). Every other row, padding included, rides as one
        token. ``None`` keeps the rectangle: a decode step, a step outside what
        the flat model step serves (``_can_split``; multimodal, constrained or
        explicit M-RoPE rows), more chunk rows than ``MAX_CHUNK_SLOTS``, a
        chunk row whose first token is chained (no engine composes one: a
        chunk's tokens are the host's), or a step the split would not make
        smaller (a lone chunk row: its one decode slot would be padding, one
        position more than the rectangle computes)."""
        bp, tp = padded.tokens.shape
        if (not self._can_split or tp == 1 or padded.mm_embeds is not None
                or padded.logit_mask is not None or padded.mrope_positions is not None):
            return None
        chunk = np.flatnonzero(padded.last_token_index > 0)
        if len(chunk) > MAX_CHUNK_SLOTS or bp + next_pow2(len(chunk)) * tp >= bp * tp:
            return None
        if chain_src is not None and (chain_src[chunk] >= 0).any():
            return None
        return chunk

    def _text_step(self, padded: StepBatch, b_real: int, lp_k: int,
                   chain_src: np.ndarray | None = None):
        """The step of text rows on one device, as ``step`` and ``step_async``
        both dispatch it: ``(key, layout, rows, fn, pack, statics)``. ``key`` is
        what the layout adds to the dispatch key, ``rows`` where the batch's
        rows sit in the program's output, ``fn`` the layout's one jitted
        program, which takes ``(params, k_cache, v_cache, pack(), chain buffer,
        **statics)`` and returns ``(_step's outputs, the new chain buffer)``.
        The caller makes the call itself, through ``_enqueue``: the frames
        round a jitted call are part of what its first call costs."""
        bp, tp = padded.tokens.shape
        statics = dict(n=padded.block_tables.shape[1], h=padded.history.shape[1], lp_k=lp_k)
        chunk = self._chunk_rows(padded, chain_src)
        if chunk is None:
            return ((), (ROWS_X_T, bp * tp), slice(b_real), self._step_packed_fn,
                    lambda: _pack(padded, chain_src), dict(statics, b=bp, t=tp))
        # One position per row and tp per chunk slot instead of bp x tp. A
        # step of several rows without a chunk row (a warm-up's null
        # batch) is the one-chunk-slot program with that slot padding.
        nc = next_pow2(len(chunk))
        # Row i samples in decode slot i, a chunk row in its chunk slot.
        rows = np.arange(b_real)
        rows[chunk] = bp + np.arange(len(chunk))
        return ((SPLIT, nc), (SPLIT, bp + nc * tp), rows, self._step_split_fn,
                lambda: _pack_split(padded, chunk, nc, chain_src), dict(statics, nd=bp, nc=nc, tc=tp))

    def _explicit_inputs(self, padded: StepBatch):
        """``(opt, inputs)`` for the explicit-argument programs (``_step``'s
        positional order): each array placed where the program wants it, a
        mesh's rows sharded; ``opt`` places an optional extra, ``None`` kept."""
        if self.mesh is not None:
            from dynamo_tpu.parallel.sharding import batch_sharding

            def put(a):
                return jax.device_put(a, batch_sharding(self.mesh, a.ndim))
        else:
            put = jnp.asarray

        def opt(a):
            return None if a is None else put(a)

        return opt, (
            put(padded.tokens), put(padded.positions),
            put(padded.block_tables), put(padded.slot_mapping),
            put(padded.last_token_index), put(padded.temperature),
            put(padded.top_k), put(padded.top_p),
            put(padded.seeds), put(padded.sample_steps),
            put(padded.freq_pen), put(padded.pres_pen),
            put(padded.pos_limit), put(padded.history),
            put(padded.mrope_delta),
        )

    @_locked
    def step(self, batch: StepBatch, lp_k: int = 0):
        """Run one forward+sample step; returns sampled token ids i32[B_real].

        Rows may carry different real token counts (``num_new``): a mixed
        step fuses 1-token decode rows with multi-token prefill-chunk rows
        in one dispatch. Per-row ``last_token_index`` already makes the
        logit gather exact for that; a short row's padding columns attend
        nothing real (per-token position masks) and write KV to the null
        page, and only rows whose span completes their sequence have their
        sample accepted by the engine (the rest are discarded host-side).

        That rectangle is what the caller hands in, not what is computed: a
        ``T > 1`` step of several rows, of a text model on one device (GQA,
        MHA or MLA attention), runs a program whose token axis holds one position per row
        plus ``T`` per chunk row (``_chunk_rows``, ``_pack_split``,
        ``llama.forward``'s ``split``), and the sampled tokens come back in
        the batch's row order all the same.

        ``lp_k > 0`` additionally returns a logprobs dict (chosen-token
        logprob + top-``lp_k`` alternatives, OpenAI semantics):
        ``(tokens, {"logprob": f32[B], "top_ids": i32[B, k], "top_lps":
        f32[B, k]})``. A separate compiled program per lp_k presence — text
        traffic pays nothing."""
        b_real = batch.batch_size
        padded = self._pad(batch)
        impl = self._select_impl(padded) if self.mesh is not None else self.attn_impl
        bp, tp = padded.tokens.shape
        layout = (ROWS_X_T, bp * tp)
        dispatch_key = (
            bp, tp, padded.block_tables.shape[1], padded.history.shape[1],
            lp_k, impl, self.mesh is not None,
            padded.mm_embeds is not None, padded.logit_mask is not None,
        )
        rows = slice(b_real)  # where the batch's rows sit in the program's output
        explicit = self.mesh is not None or padded.mm_embeds is not None or padded.logit_mask is not None
        if not explicit:
            key, layout, rows, fn, pack, statics = self._text_step(padded, b_real, lp_k)
            dispatch_key += key
        else:
            self._refuse_recurrent("a step with image rows or a host-built constraint mask")
        with self._dispatch("step", dispatch_key, padded, impl, layout):
            if not explicit:
                out, _ = self._enqueue(fn, self.params, self.k_cache, self.v_cache,
                                       jnp.asarray(pack()), self._chain_idle, **statics, state=self.state)
            else:
                opt, inputs = self._explicit_inputs(padded)
                out = self._enqueue(
                    self._step_fn,
                    self.params, self.k_cache, self.v_cache, *inputs,
                    opt(padded.mm_embeds), opt(padded.mm_slot_offset), opt(padded.mm_counts),
                    opt(padded.mrope_positions), opt(padded.logit_mask),
                    opt(padded.window_block_tables), opt(padded.window_slot_mapping),
                    impl=impl, lp_k=lp_k,
                )
            out = self._keep_moe_counts(self._keep_state(out))
            self._mark_wait()
            if lp_k:
                next_tokens, self.k_cache, self.v_cache, chosen, top_ids, top_lps = out
                return np.asarray(next_tokens)[rows], {
                    "logprob": np.asarray(chosen)[rows],
                    "top_ids": np.asarray(top_ids)[rows],
                    "top_lps": np.asarray(top_lps)[rows],
                }
            next_tokens, self.k_cache, self.v_cache = out
            return np.asarray(next_tokens)[rows]

    @_locked
    def spec_step(self, batch: StepBatch, verify_width: int, lp_k: int = 0):
        """Speculative verify dispatch: returns target tokens i32[B_real, V].

        ``batch`` is a mixed StepBatch whose decode rows carry draft tokens
        as extra real columns (``num_new`` = 1 + draft length) and whose
        ``spec_start`` names each row's first verify column (0 for decode
        rows — they score every column — and n-1 for prefill-chunk rows,
        which score only their last column exactly like :meth:`step`).
        Verify columns beyond a row's real span clamp to its last column;
        the engine discards those duplicates host-side.

        ``verify_width`` (V = spec_k + 1) is a static program dimension —
        keep it constant per engine so speculation adds exactly one
        compiled program per (B, T, N) bucket. Column j of the result is
        the token the non-speculative engine would sample after accepting
        j draft tokens (rng fold ``sample_steps + j``); with ``lp_k`` the
        logprobs dict carries per-column arrays [B, V] / [B, V, k].
        """
        self._refuse_recurrent("speculative verify")
        b_real = batch.batch_size
        padded = self._pad(batch)
        bp = padded.tokens.shape[0]
        start = padded.spec_start if padded.spec_start is not None else np.zeros(bp, np.int32)
        vi = np.minimum(
            start[:, None] + np.arange(verify_width, dtype=np.int32)[None, :],
            padded.last_token_index[:, None],
        ).astype(np.int32)
        impl = self._select_impl(padded) if self.mesh is not None else self.attn_impl
        dispatch_key = (
            bp, padded.tokens.shape[1], padded.block_tables.shape[1],
            padded.history.shape[1], verify_width, lp_k, impl, self.mesh is not None,
            padded.mm_embeds is not None, padded.logit_mask is not None,
        )
        with self._dispatch("spec_step", dispatch_key, padded, impl,
                            (ROWS_X_T, padded.tokens.size), verify=True):
            if self.mesh is not None:
                from dynamo_tpu.parallel.sharding import batch_sharding

                def put(a):
                    return jax.device_put(a, batch_sharding(self.mesh, a.ndim))
            else:
                put = jnp.asarray

            def opt(a):
                return None if a is None else put(a)

            out = self._enqueue(
                self._spec_step_fn,
                self.params, self.k_cache, self.v_cache,
                put(padded.tokens), put(padded.positions),
                put(padded.block_tables), put(padded.slot_mapping),
                put(vi), put(padded.temperature), put(padded.top_k), put(padded.top_p),
                put(padded.seeds), put(padded.sample_steps),
                put(padded.freq_pen), put(padded.pres_pen), put(padded.history),
                put(padded.mrope_delta),
                opt(padded.mm_embeds), opt(padded.mm_slot_offset), opt(padded.mm_counts),
                opt(padded.mrope_positions), opt(padded.logit_mask),
                opt(padded.window_block_tables), opt(padded.window_slot_mapping),
                impl=impl, lp_k=lp_k,
            )
        self._mark_wait()
        if lp_k:
            targets, self.k_cache, self.v_cache, chosen, top_ids, top_lps = out
            return np.asarray(targets)[:b_real], {
                "logprob": np.asarray(chosen)[:b_real],
                "top_ids": np.asarray(top_ids)[:b_real],
                "top_lps": np.asarray(top_lps)[:b_real],
            }
        targets, self.k_cache, self.v_cache = out
        return np.asarray(targets)[:b_real]

    def _chain_src_padded(self, chain_src, b_real: int, bp: int) -> np.ndarray:
        """Pad a per-row chain source vector to the batch bucket (-1 = host).

        ``chain_src=None`` with chaining requested means the whole-batch
        form: row i chains from row i of the previous step."""
        src = np.full(bp, -1, np.int32)
        if chain_src is None:
            src[:b_real] = np.arange(b_real, dtype=np.int32)
        else:
            src[:b_real] = np.asarray(chain_src, np.int32)
        mx = int(src.max())
        assert mx < 0 or (
            self._chain_tokens is not None and mx < self._chain_tokens.shape[0]
        ), "chain_src points past the device-resident sample buffer"
        return src

    @_locked
    def step_async(self, batch: StepBatch, lp_k: int = 0, *, chain: bool = False,
                   chain_src: np.ndarray | None = None) -> "DeviceStepTokens":
        """Dispatch ONE (possibly mixed prefill+decode) step without blocking
        on its result.

        The engine's pipelined loop uses this to keep one step in flight: the
        sampled tokens stay device-resident (``self._chain_tokens``), so the
        next step can be dispatched with ``chain=True`` — each row's input
        token gathered in-graph per ``chain_src`` — before this step's
        tokens ever reach the host. ``chain_src`` i32[B_real] names, per
        row, its row index in the previous step's batch or -1 to feed
        that row from host (prefill chunks, fresh admissions). Rows may
        carry multiple real token columns exactly like :meth:`step` — only
        column 0 is ever chained, which is where mixed decode rows keep
        their single real token. Returns a :class:`DeviceStepTokens` handle
        whose ``result()`` blocks on the already-started device->host copy.

        Text rows on one device run the very program :meth:`step` runs for the
        shape, in the layout :meth:`step` would take (``_text_step``), under
        the dispatch key :meth:`step` records: what a warm-up through ``step``
        compiled is what this dispatches, chained or not. Extras the packed
        i32 buffer has no slots for — multimodal embeds, explicit 3-axis mrope
        coords, a host-known constraint mask (``logit_mask``, unchained rows
        only) or the lookahead mask groups (``la_masks``/``la_groups``,
        chained dispatches) — and a mesh's row-sharded inputs route through
        the explicit-args programs. ``lp_k`` rides along — the aux logprob
        arrays are fetched with the tokens.
        """
        assert batch.la_masks is None or chain, (
            "lookahead mask groups resolve against the chain gather; "
            "host-known tokens take logit_mask"
        )
        assert batch.logit_mask is None or not chain, (
            "chained dispatches carry constraint masks as la_masks/la_groups"
        )
        b_real = batch.batch_size
        padded = self._pad(batch)
        impl = self._select_impl(padded) if self.mesh is not None else self.attn_impl
        b, t = padded.tokens.shape
        n = padded.block_tables.shape[1]
        h = padded.history.shape[1]
        src = self._chain_src_padded(chain_src, b_real, b) if chain else None
        chain_buf = self._chain_tokens if chain else self._chain_idle
        rows = slice(b_real)
        if self.mesh is None and not (
            padded.mm_embeds is not None or padded.mrope_positions is not None
            or padded.logit_mask is not None or padded.la_masks is not None
        ):
            key, layout, rows, fn, pack, statics = self._text_step(padded, b_real, lp_k, src)
            with self._dispatch("step", (b, t, n, h, lp_k, impl, False, False, False) + key,
                                padded, impl, layout):
                out, chain_buf = self._enqueue(fn, self.params, self.k_cache, self.v_cache,
                                               jnp.asarray(pack()), chain_buf, **statics, state=self.state)
        else:
            self._refuse_recurrent("a step with image rows or constraint masks")
            # One device runs the chained program whether or not a row chains
            # (every source -1 then): its samples come back at the chain
            # buffer's width, so a text step can chain out of them as they are.
            chained_program = chain or self.mesh is None
            dispatch_key = (
                b, t, n, h, lp_k, chained_program, impl, self.mesh is not None,
                padded.mm_embeds is not None, padded.logit_mask is not None,
                padded.la_masks is not None,
            )
            with self._dispatch("step_async", dispatch_key, padded, impl, (ROWS_X_T, b * t)):
                opt, explicit = self._explicit_inputs(padded)
                extras = (opt(padded.mm_embeds), opt(padded.mm_slot_offset),
                          opt(padded.mm_counts), opt(padded.mrope_positions))
                pools = (opt(padded.window_block_tables), opt(padded.window_slot_mapping))
                if chained_program:
                    out, chain_buf = self._enqueue(
                        self._step_chained_explicit_fn,
                        self.params, self.k_cache, self.v_cache,
                        chain_buf, opt(src if chain else np.full(b, -1, np.int32)), *explicit, *extras,
                        opt(padded.la_masks), opt(padded.la_groups), *pools, opt(padded.logit_mask),
                        impl=impl, lp_k=lp_k,
                    )
                else:  # a mesh's step that chains nothing
                    out = self._enqueue(
                        self._step_fn,
                        self.params, self.k_cache, self.v_cache, *explicit, *extras,
                        opt(padded.logit_mask), *pools,
                        impl=impl, lp_k=lp_k,
                    )
                    chain_buf = out[0]
        out = self._keep_moe_counts(self._keep_state(out))
        if lp_k:
            toks, self.k_cache, self.v_cache, chosen, top_ids, top_lps = out
            aux = (chosen, top_ids, top_lps)
        else:
            toks, self.k_cache, self.v_cache = out
            aux = None
        self._chain_tokens = chain_buf
        for buf in (toks, *(aux or ())):
            try:  # start the device->host DMA early; overlaps the next step
                buf.copy_to_host_async()
            except Exception:
                pass
        return DeviceStepTokens(toks, aux, rows)

    @_locked
    def spec_step_async(self, batch: StepBatch, verify_width: int, lp_k: int = 0, *,
                        chain_src: np.ndarray | None = None) -> "DeviceSpecTokens":
        """Dispatch a speculative verify without blocking on its result.

        Same batch contract as :meth:`spec_step`. With ``chain_src`` (see
        :meth:`step_async`) the decode rows' column-0 base token gathers
        in-graph from the previous dispatch's device-resident samples, so a
        verify can itself be the pipeline's one-step lookahead after a plain
        chained step (a plain step emits exactly one token per row, so the
        verify's positions are host-predictable even before that token
        lands). Nothing chains out of a verify: the engine harvests it before
        it composes the next dispatch, whose tokens the host then knows.
        """
        assert batch.mm_embeds is None and batch.logit_mask is None, (
            "spec_step_async does not take multimodal/constrained batches"
        )
        self._refuse_recurrent("speculative verify")
        b_real = batch.batch_size
        padded = self._pad(batch)
        bp = padded.tokens.shape[0]
        start = padded.spec_start if padded.spec_start is not None else np.zeros(bp, np.int32)
        vi = np.minimum(
            start[:, None] + np.arange(verify_width, dtype=np.int32)[None, :],
            padded.last_token_index[:, None],
        ).astype(np.int32)
        impl = self._select_impl(padded) if self.mesh is not None else self.attn_impl
        chain = chain_src is not None
        src = self._chain_src_padded(chain_src, b_real, bp) if chain else None
        dispatch_key = (
            bp, padded.tokens.shape[1], padded.block_tables.shape[1],
            padded.history.shape[1], verify_width, lp_k, chain, impl,
            self.mesh is not None,
        )
        with self._dispatch("spec_step_async", dispatch_key, padded, impl,
                            (ROWS_X_T, padded.tokens.size), verify=True):
            if self.mesh is not None:
                from dynamo_tpu.parallel.sharding import batch_sharding

                def put(a):
                    return jax.device_put(a, batch_sharding(self.mesh, a.ndim))
            else:
                put = jnp.asarray
            explicit = (
                put(padded.tokens), put(padded.positions),
                put(padded.block_tables), put(padded.slot_mapping),
                put(vi), put(padded.temperature), put(padded.top_k), put(padded.top_p),
                put(padded.seeds), put(padded.sample_steps),
                put(padded.freq_pen), put(padded.pres_pen), put(padded.history),
                put(padded.mrope_delta),
            )
            pools = {} if padded.window_block_tables is None else dict(
                window_tables=put(padded.window_block_tables), window_slots=put(padded.window_slot_mapping))
            if chain:
                out = self._enqueue(
                    self._spec_step_chained_fn,
                    self.params, self.k_cache, self.v_cache,
                    self._chain_tokens, put(src), *explicit,
                    impl=impl, lp_k=lp_k, **pools,
                )
            else:
                out = self._enqueue(
                    self._spec_step_fn,
                    self.params, self.k_cache, self.v_cache, *explicit,
                    impl=impl, lp_k=lp_k, **pools,
                )
        if lp_k:
            targets, self.k_cache, self.v_cache, chosen, top_ids, top_lps = out
            aux = (chosen, top_ids, top_lps)
        else:
            targets, self.k_cache, self.v_cache = out
            aux = None
        self._chain_tokens = None  # the step after a verify takes its tokens from the host
        for buf in (targets, *(aux or ())):
            try:  # start the device->host DMA early; overlaps the next step
                buf.copy_to_host_async()
            except Exception:
                pass
        return DeviceSpecTokens(targets, aux, b_real)

    def embed(self, token_lists: list[list[int]]) -> np.ndarray:
        """Sentence embeddings for N token sequences; returns f32[N, D].

        Runs the cache-free encoder (`models/llama.encode`) — params are
        read-only and nothing is donated, so this deliberately does NOT take
        ``io_lock``: embedding traffic must not stall the decode loop.
        """
        if not token_lists:
            return np.zeros((0, self.cfg.hidden_size), np.float32)
        n = len(token_lists)
        t = max(1, max(len(ts) for ts in token_lists))
        bp, tp = next_pow2(n), self._bucket_time(t)
        tokens = np.zeros((bp, tp), np.int32)
        mask = np.zeros((bp, tp), bool)
        for i, ts in enumerate(token_lists):
            tokens[i, : len(ts)] = ts
            mask[i, : len(ts)] = True
        with self._on_device():
            out = self._embed_fn(self.params, jnp.asarray(tokens), jnp.asarray(mask))
        return np.asarray(out)[:n]

    def reset_chain(self) -> None:
        self._chain_tokens = None

    def cache_memory_bytes(self) -> int:
        return int(self.k_cache.nbytes + self.v_cache.nbytes + sum(buf.nbytes for buf in self.state))

    def memory_bytes_by_kind(self) -> dict[str, int]:
        """What this runner allocated beside the weights: ``kv_pool_bytes``
        (both caches; of a model with a pool per layer kind both pools, and
        ``window_pool_bytes`` the sliding layers' share of them) and
        ``state_bytes`` (the recurrent layers' buffers), each only where the
        model has it."""
        kv = int(self.k_cache.nbytes + self.v_cache.nbytes)
        out = {"kv_pool_bytes": kv}
        if self.two_pool:
            from dynamo_tpu.models.config import SLIDING

            out["window_pool_bytes"] = kv * self.cfg.cache_layers_of(SLIDING) * self.window_pages // self.k_cache.shape[1]
        if self.state:
            out["state_bytes"] = int(sum(buf.nbytes for buf in self.state))
        return out


class InFlightPages:
    """Handle to a dispatched page gather whose device->host copy is in
    flight (``ModelRunner.read_pages_async``)."""

    def __init__(self, k: jax.Array | None, v: jax.Array | None, n: int) -> None:
        self._k = k
        self._v = v
        self._n = n

    @property
    def num_pages(self) -> int:
        return self._n

    def wait(self) -> list[tuple[np.ndarray, np.ndarray]]:
        """Block until the pages are on host; returns [(k, v), ...] per page
        ([L, ps, W] each), pow2 padding sliced off."""
        if self._n == 0:
            return []
        k_host, v_host = np.asarray(self._k), np.asarray(self._v)
        return [(k_host[:, i], v_host[:, i]) for i in range(self._n)]


class DeviceStepTokens:
    """Handle to a single dispatched decode step's sampled tokens (and
    optional logprob aux arrays), device-resident (``ModelRunner.step_async``)."""

    def __init__(self, toks: jax.Array, aux, rows) -> None:
        self._toks = toks
        self._aux = aux  # (chosen, top_ids, top_lps) or None
        self._rows = rows  # where the batch's rows sit in the program's output

    def result(self) -> tuple[np.ndarray, dict | None]:
        """Block until on host; returns (tokens i32[B_real, 1], lp_aux|None)."""
        toks = np.asarray(self._toks)[self._rows, None]
        if self._aux is None:
            return toks, None
        chosen, top_ids, top_lps = self._aux
        return toks, {
            "logprob": np.asarray(chosen)[self._rows],
            "top_ids": np.asarray(top_ids)[self._rows],
            "top_lps": np.asarray(top_lps)[self._rows],
        }


class DeviceSpecTokens:
    """Handle to a dispatched speculative verify's target tokens (and
    optional logprob aux), device-resident (``ModelRunner.spec_step_async``)."""

    def __init__(self, targets: jax.Array, aux, b_real: int) -> None:
        self._targets = targets  # [Bp, V]
        self._aux = aux
        self._b_real = b_real

    def result(self) -> tuple[np.ndarray, dict | None]:
        """Block until on host; returns (targets i32[B_real, V], lp_aux|None)
        — the same values :meth:`ModelRunner.spec_step` returns."""
        targets = np.asarray(self._targets)[: self._b_real]
        if self._aux is None:
            return targets, None
        chosen, top_ids, top_lps = self._aux
        return targets, {
            "logprob": np.asarray(chosen)[: self._b_real],
            "top_ids": np.asarray(top_ids)[: self._b_real],
            "top_lps": np.asarray(top_lps)[: self._b_real],
        }
