"""Mocker: a simulated engine worker for router/planner testing at scale.

The reference ships a full vLLM-like simulator (`lib/llm/src/mocker/*`,
SURVEY.md §2 row 35) so KV routing, metrics, and autoscaling logic can be
exercised without GPUs. Here the real ``EngineCore`` *is* the scheduler —
the mocker is just a runner with a timing model instead of a TPU: scheduling,
paging, prefix cache, preemption, KV events and metrics are all the
production code paths, so what the router/planner sees is exactly what a
real fleet emits, at simulated speed.

Timing model: prefill costs ``prefill_us_per_token * new_tokens``; a decode
step costs ``decode_us_base + decode_us_per_seq * batch``. Generated tokens
are deterministic per (seed, position) so tests can assert streams.

Fleet fidelity (the fleetsim harness exposed these): ``jitter`` multiplies
every step's compute by deterministic lognormal noise (heteroscedastic —
absolute variance grows with the step cost, like real steps), and
``warmup_s``/``warmup_factor`` ramp a fresh worker from ``warmup_factor``×
compute down to 1× over its first ``warmup_s`` of stepping, so planner
scale-ups see realistic cold-start TTFT instead of instant capacity. Both
default off and leave the timing model bit-identical. Per-worker values
arrive via the ``DYN_MOCK_*`` env overlay (see :func:`build_mock_core`),
which is how the fleet plane gives each worker subprocess its own profile.
"""

from __future__ import annotations

import os
import time
from types import SimpleNamespace

import numpy as np

from dynamo_tpu.engine.core import EngineConfig, EngineCore
from dynamo_tpu.engine.runner import DispatchReport, StepBatch
from dynamo_tpu.engine.service import JaxEngineService


class MockRunner:
    """Drop-in for ModelRunner: no device, simulated latency."""

    def __init__(
        self,
        *,
        num_pages: int,
        page_size: int,
        vocab_size: int = 32000,
        prefill_us_per_token: float = 50.0,
        decode_us_base: float = 2000.0,
        decode_us_per_seq: float = 100.0,
        seed: int = 0,
        realtime: bool = True,
        d2h_us: float = 0.0,
        jitter: float = 0.0,
        warmup_s: float = 0.0,
        warmup_factor: float = 1.0,
    ) -> None:
        self.num_pages = num_pages
        self.page_size = page_size
        self.vocab_size = vocab_size
        # Constrained (JSON-mode) decode reads ``runner.cfg.vocab_size``
        # when sizing token-mask caches and lookahead banks; this minimal
        # model-config shim keeps the mock API-compatible there.
        self.cfg = SimpleNamespace(vocab_size=vocab_size)
        self.prefill_us_per_token = prefill_us_per_token
        self.decode_us_base = decode_us_base
        self.decode_us_per_seq = decode_us_per_seq
        self.seed = seed
        self.realtime = realtime
        # Heteroscedastic step noise: lognormal(0, jitter) multiplier on
        # compute. A separate rng keeps token generation untouched.
        self.jitter = jitter
        self._jitter_rng = np.random.default_rng(seed ^ 0x5EED)
        # Cold-start ramp: warmup_factor x compute at the first step,
        # decaying linearly to 1.0 over warmup_s of wall time. The clock
        # starts lazily at the first step, so a worker that sat idle after
        # spawn still shows its ramp to the first requests routed at it.
        self.warmup_s = warmup_s
        self.warmup_factor = warmup_factor
        self._warm_t0: float | None = None
        # Device->host result-transfer latency per step: the synchronous loop
        # pays it inline (step() blocks on compute + copy); the overlapped
        # loop (step_async) pays it only at harvest, where it hides under the
        # next step's compute. 0 keeps legacy timing for existing tests.
        self.d2h_us = d2h_us
        self._report: DispatchReport | None = None  # of what was dispatched since the last take
        self.simulated_us = 0.0
        # Device-busy accounting for the overlap bench probe: cumulative
        # compute time vs. wall elapsed gives device_idle_frac.
        self.busy_us = 0.0
        self._busy_until = 0.0  # wall timestamp the simulated device frees up
        self._chain_host: np.ndarray | None = None  # last step_async samples
        self._layers, self._kv, self._hd = 1, 1, 8  # page payload shape stub

    def _sleep_us(self, us: float) -> None:
        self.simulated_us += us
        if self.realtime and us > 0:
            time.sleep(us / 1e6)

    def _timing_scale(self) -> float:
        """Per-step compute multiplier: warm-up ramp x jitter noise.

        Exactly 1.0 (and the jitter rng untouched) at the defaults, keeping
        legacy timing bit-identical.
        """
        scale = 1.0
        if self.warmup_s > 0.0 and self.warmup_factor > 1.0:
            if self._warm_t0 is None:
                self._warm_t0 = time.monotonic()
            frac = min(1.0, (time.monotonic() - self._warm_t0) / self.warmup_s)
            scale *= self.warmup_factor - (self.warmup_factor - 1.0) * frac
        if self.jitter > 0.0:
            scale *= float(self._jitter_rng.lognormal(0.0, self.jitter))
        return scale

    def _tokens_for(self, positions: np.ndarray, row_tokens: np.ndarray) -> np.ndarray:
        # Deterministic pseudo-generation: next token = f(seed, pos, last token).
        return ((row_tokens.astype(np.int64) * 1103515245 + positions + self.seed) % (self.vocab_size - 2) + 1).astype(
            np.int32
        )

    def _lp_aux(self, toks: np.ndarray, lp_k: int) -> dict:
        # Synthetic but schema-complete logprobs (mock fleets exercise
        # the full API surface): chosen "probability" 0.5, alternatives
        # decaying deterministically.
        b = toks.shape[0]
        lps = np.full(b, np.log(0.5), np.float32)
        top_ids = (toks[:, None] + np.arange(lp_k)[None, :]) % self.vocab_size
        top_lps = np.log(0.5) - 0.5 * np.arange(1, lp_k + 1, dtype=np.float32)
        top_lps = np.broadcast_to(top_lps, (b, lp_k)).copy()
        top_lps[:, 0] = np.log(0.5)
        top_ids[:, 0] = toks
        return {"logprob": lps, "top_ids": top_ids.astype(np.int32), "top_lps": top_lps}

    def take_dispatch(self) -> DispatchReport | None:
        """``ModelRunner.take_dispatch``. There is no program, so the report
        holds no label and no dispatch time (the engine's step wall stands in)."""
        report, self._report = self._report, None
        return report

    def step(self, batch: StepBatch, lp_k: int = 0):
        b, t = batch.tokens.shape
        if t > 1:  # prefill
            new_tokens = int((batch.last_token_index + 1).sum())
            compute = self.prefill_us_per_token * new_tokens * self._timing_scale()
            self.busy_us += compute
            self._sleep_us(compute)
        else:
            compute = (self.decode_us_base + self.decode_us_per_seq * b) * self._timing_scale()
            self.busy_us += compute
            # The synchronous loop blocks on compute AND the result copy.
            self._sleep_us(compute + self.d2h_us)
        self._report = DispatchReport()
        last_tok = batch.tokens[np.arange(b), batch.last_token_index]
        last_pos = batch.positions[np.arange(b), batch.last_token_index]
        toks = self._tokens_for(last_pos, last_tok)
        if lp_k:
            return toks, self._lp_aux(toks, lp_k)
        return toks

    def _mixed_compute_us(self, batch: StepBatch) -> float:
        """Timing for a (possibly mixed) step: every row pays the decode
        per-seq cost, extra real columns (prefill-chunk tokens) pay the
        per-token prefill cost on top."""
        b, t = batch.tokens.shape
        if batch.num_new is not None:
            total_new = int(np.asarray(batch.num_new).sum())
        else:
            total_new = int((batch.last_token_index + 1).sum()) if t > 1 else b
        return (
            self.decode_us_base
            + self.decode_us_per_seq * b
            + self.prefill_us_per_token * max(0, total_new - b)
        ) * self._timing_scale()

    def _chain_col0(self, batch: StepBatch, chain: bool, chain_src) -> np.ndarray:
        """Column-0 input token per row, with per-row chain sourcing from the
        host-side sample buffer (mirrors runner._apply_chain)."""
        tok0 = batch.tokens[:, 0].copy()
        if not chain:
            return tok0
        assert self._chain_host is not None, "chained step requires a previous async step"
        b = tok0.shape[0]
        src = np.arange(b, dtype=np.int32) if chain_src is None else np.asarray(chain_src, np.int32)
        sel = src >= 0
        assert not sel.any() or int(src.max()) < self._chain_host.shape[0], (
            "chain_src points past the sample buffer"
        )
        tok0[sel] = self._chain_host[src[sel]]
        return tok0

    def step_async(self, batch: StepBatch, lp_k: int = 0, *, chain: bool = False,
                   chain_src=None):
        """Mock of ModelRunner.step_async: returns a handle whose ``result()``
        blocks until the simulated device finishes this step's compute plus
        the d2h copy. Dispatch itself never blocks — consecutive chained
        dispatches queue on ``_busy_until``, so wall time per token in the
        overlapped loop is ~max(compute, d2h) instead of compute + d2h.
        Mixed batches (T > 1) and per-row ``chain_src`` sourcing mirror the
        real runner's contract."""
        b = batch.tokens.shape[0]
        compute = self._mixed_compute_us(batch)
        self.busy_us += compute
        self.simulated_us += compute + self.d2h_us
        self._report = DispatchReport()
        now = time.monotonic()
        start = max(now, self._busy_until)
        self._busy_until = start + compute / 1e6
        ready_at = self._busy_until + self.d2h_us / 1e6
        tokens = batch.tokens.copy()
        tokens[:, 0] = self._chain_col0(batch, chain, chain_src)
        last_tok = tokens[np.arange(b), batch.last_token_index]
        last_pos = batch.positions[np.arange(b), batch.last_token_index]
        toks = self._tokens_for(last_pos, last_tok)
        self._chain_host = toks
        aux = self._lp_aux(toks, lp_k) if lp_k else None
        return MockStepTokens(self, toks, aux, ready_at)

    def _spec_targets(self, batch: StepBatch, verify_width: int,
                      tokens: np.ndarray) -> np.ndarray:
        """Exact-replay verify targets: column j's target is the token the
        sequential mock would generate from column j's input at its position
        (clamped to the row's last real column, like the device kernel)."""
        b = batch.tokens.shape[0]
        start = (batch.spec_start if batch.spec_start is not None
                 else np.zeros(b, np.int32))
        vi = np.minimum(
            start[:, None] + np.arange(verify_width, dtype=np.int32)[None, :],
            batch.last_token_index[:, None],
        )
        rows = np.arange(b)[:, None]
        return self._tokens_for(batch.positions[rows, vi], tokens[rows, vi])

    def spec_step(self, batch: StepBatch, verify_width: int, lp_k: int = 0):
        """Mock speculative verify (spec_k support for mock fleets)."""
        compute = self._mixed_compute_us(batch)
        self.busy_us += compute
        self._sleep_us(compute + self.d2h_us)
        self._report = DispatchReport()
        targets = self._spec_targets(batch, verify_width, batch.tokens)
        if lp_k:
            return targets, self._spec_lp_aux(targets, lp_k)
        return targets

    def _spec_lp_aux(self, targets: np.ndarray, lp_k: int) -> dict:
        base = self._lp_aux(targets[:, 0], lp_k)
        aux = {
            "logprob": np.broadcast_to(base["logprob"][:, None], targets.shape).copy(),
            "top_ids": np.broadcast_to(base["top_ids"][:, None, :], (*targets.shape, lp_k)).copy(),
            "top_lps": np.broadcast_to(base["top_lps"][:, None, :], (*targets.shape, lp_k)).copy(),
        }
        aux["top_ids"][..., 0] = targets
        return aux

    def spec_step_async(self, batch: StepBatch, verify_width: int, lp_k: int = 0, *,
                        chain_src=None):
        """Mock of ModelRunner.spec_step_async: verify as the pipeline's
        lookahead; nothing chains out of it."""
        compute = self._mixed_compute_us(batch)
        self.busy_us += compute
        self.simulated_us += compute + self.d2h_us
        self._report = DispatchReport()
        start = max(time.monotonic(), self._busy_until)
        self._busy_until = start + compute / 1e6
        ready_at = self._busy_until + self.d2h_us / 1e6
        tokens = batch.tokens.copy()
        tokens[:, 0] = self._chain_col0(batch, chain_src is not None, chain_src)
        targets = self._spec_targets(batch, verify_width, tokens)
        self._chain_host = None
        aux = self._spec_lp_aux(targets, lp_k) if lp_k else None
        return MockSpecTokens(self, targets, aux, ready_at)

    def reset_chain(self) -> None:
        self._chain_host = None

    # Tier hooks: payload-free stubs (pair with NullStorage tiers).
    def read_page(self, page_id: int):
        shape = (self._layers, self._kv, self.page_size, self._hd)
        return np.zeros(shape, np.float32), np.zeros(shape, np.float32)

    def write_page(self, page_id: int, k, v) -> None:
        pass

    def read_pages(self, page_ids):
        return [self.read_page(p) for p in page_ids]

    def write_pages(self, page_ids, ks, vs) -> None:
        pass

    def cache_memory_bytes(self) -> int:
        return 0


class MockStepTokens:
    """Handle to a MockRunner.step_async dispatch (mirrors DeviceStepTokens)."""

    def __init__(self, runner: MockRunner, toks: np.ndarray, aux, ready_at: float) -> None:
        self._runner = runner
        self._toks = toks
        self._aux = aux
        self._ready_at = ready_at

    def result(self):
        if self._runner.realtime:
            wait = self._ready_at - time.monotonic()
            if wait > 0:
                time.sleep(wait)
        return self._toks[:, None], self._aux


class MockSpecTokens:
    """Handle to a MockRunner.spec_step_async dispatch (mirrors
    DeviceSpecTokens)."""

    def __init__(self, runner: MockRunner, targets: np.ndarray, aux, ready_at: float) -> None:
        self._runner = runner
        self._targets = targets
        self._aux = aux
        self._ready_at = ready_at

    def result(self):
        if self._runner.realtime:
            wait = self._ready_at - time.monotonic()
            if wait > 0:
                time.sleep(wait)
        return self._targets, self._aux


#: Env -> MockRunner kwarg overlay: how a fleet gives each worker
#: subprocess its own timing profile (fleetsim WorkerTimingProfile.to_env).
_ENV_RUNNER_KW = (
    ("DYN_MOCK_PREFILL_US_PER_TOKEN", "prefill_us_per_token", float),
    ("DYN_MOCK_DECODE_US_BASE", "decode_us_base", float),
    ("DYN_MOCK_DECODE_US_PER_SEQ", "decode_us_per_seq", float),
    ("DYN_MOCK_JITTER", "jitter", float),
    ("DYN_MOCK_WARMUP_S", "warmup_s", float),
    ("DYN_MOCK_WARMUP_FACTOR", "warmup_factor", float),
    ("DYN_MOCK_SEED", "seed", int),
)


def mock_runner_env_kw(env=None) -> dict:
    """MockRunner kwargs taken from ``DYN_MOCK_*`` environment variables."""
    env = os.environ if env is None else env
    out = {}
    for key, name, cast in _ENV_RUNNER_KW:
        if key in env:
            out[name] = cast(env[key])
    return out


def build_mock_core(
    config: EngineConfig | None = None,
    *,
    on_kv_event=None,
    **runner_kw,
) -> EngineCore:
    config = config or EngineConfig(num_pages=1024, page_size=16, max_batch_size=256, max_seq_len=32768)
    runner_kw = {**mock_runner_env_kw(), **runner_kw}  # explicit kwargs win
    runner = MockRunner(num_pages=config.num_pages, page_size=config.page_size, **runner_kw)
    return EngineCore(runner, config, on_kv_event=on_kv_event)


async def build_mock_service(config: EngineConfig | None = None, **runner_kw) -> JaxEngineService:
    return await JaxEngineService(build_mock_core(config, **runner_kw)).start()
