"""Device-cost plane: a live roofline ledger from XLA's own cost analysis.

The serving path already knows *when* every compiled program runs (the
``CompileTracker`` observes all five runner dispatch sites) but not *what*
each dispatch moves: how many HBM bytes it streams and how many flops it
executes. XLA knows — ``jit(...).lower().compile().cost_analysis()``
reports ``flops`` / ``bytes accessed`` per compiled program — but asking on
the hot path would double-compile every bucket. The :class:`CostRegistry`
closes the gap lazily:

- at each dispatch site the runner does a cheap seen-set check on the exact
  padded-bucket key the CompileTracker uses; a first-seen bucket enqueues a
  *lowering thunk* (shape/dtype avatars of the real arguments, captured
  before the call so donation can't invalidate them) to one background
  daemon thread, which re-lowers and compiles the same signature once and
  extracts the XLA numbers;
- until (or in case) extraction fails or the backend reports nothing, the
  record carries a model-derived **estimate** (weights-minus-untied-embed
  stream + page-granular KV traffic — the same accounting ``bench.py`` and
  ``tools/profile_1b_decode.py`` use, exported here as the shared helpers
  :func:`weight_stream_bytes` / :func:`decode_step_estimate`);
- every dispatch accumulates its record's bytes/flops and measured dispatch
  wall into a per-step-kind ledger (``prefill``/``decode``/``mixed``/
  ``spec_verify``), and :meth:`CostRegistry.take_step` hands the engine
  core the bytes/flops of the dispatches inside one engine step for the
  STEP flight record join.

Achieved GB/s / FLOP/s divide by per-chip peaks: auto-detected from
``jax.devices()[0].device_kind`` (v4/v5e/v5p/v6e table below), overridable
with ``DYN_PEAK_HBM_GBPS`` / ``DYN_PEAK_TFLOPS``; an accelerator missing from
the table is an error. On the CPU platform the peaks are DDR-class proxies
(source ``cpu-proxy:``) — roofline *fractions* there are test plumbing, not
measurements (the bytes/flops themselves are still real XLA numbers; CPU
populates cost_analysis).

Wall-clock basis caveat: the ledger's wall is the ``timed_dispatch``
measurement. On the synchronous paths that spans device execution; on the
overlapped ``*_async`` paths it is enqueue wall only, so async-mode GB/s
reads high — bytes/step stays exact either way.

Everything is gated by ``DYN_COST_PLANE`` (default on): when off, the
runner never constructs a registry, no extraction runs (spy:
:data:`EXTRACTIONS`), and served tokens are bit-identical.
"""

from __future__ import annotations

import logging
import os
import queue
import threading
import time
from dataclasses import dataclass, field
from typing import Any, Callable

logger = logging.getLogger(__name__)

COST_PLANE_ENV = "DYN_COST_PLANE"
PEAK_HBM_ENV = "DYN_PEAK_HBM_GBPS"
PEAK_FLOPS_ENV = "DYN_PEAK_TFLOPS"
#: On-demand profile capture: hard cap on one window's duration (ms) and
#: the artifact root the XPlane dumps land under.
PROFILE_MAX_MS_ENV = "DYN_PROFILE_MAX_MS"
PROFILE_DIR_ENV = "DYN_PROFILE_DIR"

#: The ledger's step-kind vocabulary (runner-side classification of each
#: dispatch; the engine core's flight records keep their own kind field).
STEP_KINDS = ("prefill", "decode", "mixed", "spec_verify")

#: device_kind substring -> (peak HBM GB/s, peak bf16 dense TFLOPS).
#: Datasheet numbers per chip: v5e 819/197, v5p 2765/459, v6e 1640/918,
#: v4 1228/275. Matched case-insensitively against jax's device_kind
#: strings ("TPU v5 lite" == v5e, "TPU v6 lite" == v6e, "TPU v5p"/"TPU v5"
#: == v5p, "TPU v4" == v4).
CHIP_PEAKS: dict[str, tuple[float, float]] = {
    "v6e": (1640.0, 918.0),
    "v6 lite": (1640.0, 918.0),
    "v5e": (819.0, 197.0),
    "v5 lite": (819.0, 197.0),
    "v5p": (2765.0, 459.0),
    "v5": (2765.0, 459.0),  # bare "TPU v5" reports the p-class part
    "v4": (1228.0, 275.0),
}

#: CPU backends only: one DDR channel-class 50 GB/s and 0.5 TFLOPS —
#: deliberately round proxies so CPU rooflines read as test plumbing, never
#: as measurements (labelled ``cpu-proxy:`` in /debug/cost). An accelerator
#: that is not in :data:`CHIP_PEAKS` is an error, not a default.
CPU_PROXY_PEAKS = (50.0, 0.5)

#: Module-wide count of cost-extraction lowerings (background compiles).
#: The DYN_COST_PLANE=0 acceptance test spies on this staying flat.
EXTRACTIONS = 0


def cost_plane_enabled() -> bool:
    return os.environ.get(COST_PLANE_ENV, "1").lower() not in ("0", "false", "off")


def profile_max_ms() -> float:
    try:
        return float(os.environ.get(PROFILE_MAX_MS_ENV, "10000"))
    except ValueError:
        return 10000.0


def profile_artifact_dir() -> str:
    import tempfile

    return os.environ.get(PROFILE_DIR_ENV) or os.path.join(
        tempfile.gettempdir(), "dynamo-profiles"
    )


def profiler_available() -> bool:
    """Whether this process can arm a device trace (jax.profiler present)."""
    try:
        import jax.profiler  # noqa: F401

        return hasattr(jax.profiler, "start_trace")
    except Exception:
        return False


def chip_peaks() -> tuple[float, float, str]:
    """(peak HBM GB/s, peak TFLOPS, source) for device 0.

    Env overrides win; else the :data:`CHIP_PEAKS` table keyed on
    ``jax.devices()[0].device_kind``; :data:`CPU_PROXY_PEAKS` on the CPU
    platform only. Raises for an accelerator the table does not know.
    """
    import jax

    dev = jax.devices()[0]
    kind = dev.device_kind
    hbm = flops = None
    source = ""
    if dev.platform == "cpu":
        (hbm, flops), source = CPU_PROXY_PEAKS, f"cpu-proxy:{kind}"
    else:
        low = kind.lower()
        for sub, (h, f) in CHIP_PEAKS.items():
            if sub in low:
                hbm, flops, source = h, f, f"table:{kind}"
                break
    env_h, env_f = os.environ.get(PEAK_HBM_ENV), os.environ.get(PEAK_FLOPS_ENV)
    try:
        if env_h:
            hbm, source = float(env_h), "env"
        if env_f:
            flops = float(env_f)
            source = "env"
    except ValueError:
        logger.warning("ignoring malformed %s/%s", PEAK_HBM_ENV, PEAK_FLOPS_ENV)
    if hbm is None or flops is None:
        raise RuntimeError(
            f"no peak HBM bandwidth / FLOPS known for {dev.platform} device_kind "
            f"{kind!r}: add it to CHIP_PEAKS (observability/cost.py) or set both "
            f"{PEAK_HBM_ENV} and {PEAK_FLOPS_ENV}"
        )
    return float(hbm), float(flops), source


# -- shared byte/flop estimate helpers ---------------------------------------
# The single source of truth for the model-derived accounting bench.py and
# tools/profile_1b_decode.py previously each re-derived.


def tree_nbytes(tree) -> int:
    """Total bytes of every array leaf (packed quantized leaves count at
    their true storage size: int8 ~1 B/elem, packed int4 ~0.5)."""
    import jax

    return sum(x.nbytes for x in jax.tree.leaves(tree))


def tree_param_count(tree) -> int:
    """Total array elements — the flop estimate's 2*N*tokens numerator.
    Packed int4 leaves undercount by 2x; estimates only, XLA numbers win."""
    import jax

    return sum(x.size for x in jax.tree.leaves(tree))


def weight_stream_bytes(params, cfg) -> int:
    """HBM bytes of weights one decode step streams: measured tree bytes
    minus the embedding table when untied (decode gathers ``batch`` rows of
    it, never the full table; a tied table IS fully read as the lm_head)."""
    total = tree_nbytes(params)
    if not getattr(cfg, "tie_embeddings", True) and "embed" in params:
        total -= tree_nbytes(params["embed"])
    return total


def kv_window_bytes(cfg, context_tokens: float, cache_itemsize: int = 2) -> int:
    """Page-granular KV read bytes for one sequence's window of
    ``context_tokens`` (already rounded to whole pages by the caller)."""
    return int(context_tokens * cfg.kv_bytes_per_token(itemsize=cache_itemsize))


def decode_step_estimate(
    params, cfg, batch: int, context_tokens: float,
    *, cache_itemsize: int = 2, new_tokens: int | None = None,
) -> dict[str, float]:
    """Model-derived {bytes, flops} for one decode-shaped step.

    ``context_tokens`` is the per-sequence page-granular KV window (pages *
    page_size); flops ≈ 2 * params * tokens-generated (matmul floor).
    """
    toks = batch if new_tokens is None else new_tokens
    return {
        "bytes": float(
            weight_stream_bytes(params, cfg)
            + batch * kv_window_bytes(cfg, context_tokens, cache_itemsize)
        ),
        "flops": float(2 * tree_param_count(params) * toks),
    }


# -- extraction ---------------------------------------------------------------


def _avatar(x):
    """ShapeDtypeStruct stand-in for an array; non-arrays pass through.

    Captured eagerly at the dispatch site — *before* the jitted call — so
    donated cache buffers can't be invalidated under us. The avatar must
    lower to the very module the call lowered to, or the re-lowering compiles
    a second copy instead of finding the call's executable: a sharding rides
    along exactly when the array is committed to one (a mesh, a replica's
    device) — on an uncommitted array it would add annotations the call's
    module does not have.
    """
    import jax

    shape = getattr(x, "shape", None)
    dtype = getattr(x, "dtype", None)
    if shape is None or dtype is None:
        return x
    if getattr(x, "committed", False):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=x.sharding)
    return jax.ShapeDtypeStruct(shape, dtype)


def make_lower_thunk(fn, args: tuple, kwargs: dict) -> Callable[[], Any]:
    """A zero-arg closure lowering ``fn`` on avatars of the given call.

    Avatar conversion happens NOW (cheap tree-map); the expensive
    ``lower().compile()`` happens when the background thread calls it.
    """
    import jax

    av_args = tuple(jax.tree_util.tree_map(_avatar, a) for a in args)
    av_kwargs = dict(kwargs)

    def thunk():
        return fn.lower(*av_args, **av_kwargs)

    return thunk


def _parse_cost_analysis(ca) -> tuple[float, float]:
    """(flops, bytes accessed) from a cost_analysis() return value, which
    is a dict on some jax versions and a one-element list of dicts on
    others; absent keys read as 0."""
    if isinstance(ca, (list, tuple)):
        ca = ca[0] if ca else {}
    if not isinstance(ca, dict):
        return 0.0, 0.0
    return float(ca.get("flops", 0.0) or 0.0), float(ca.get("bytes accessed", 0.0) or 0.0)


@dataclass
class CostRecord:
    """Per compiled-program-bucket cost: XLA numbers once extracted, the
    model estimate until then (or forever, when the backend reports none)."""

    program: str
    key: tuple
    kind: str
    #: per-ITERATION cost: XLA's HloCostAnalysis counts a while/scan body
    #: once regardless of trip count (verified on this jax), so a
    #: multi-step burst program's numbers cover ONE decode iteration —
    #: callers scale by ``steps`` at observe time.
    bytes: float = 0.0
    flops: float = 0.0
    peak_memory_bytes: float = 0.0
    source: str = "pending"  # pending -> xla | estimate
    dispatches: int = 0
    #: iteration units accounted (== dispatches except for multi-step
    #: bursts, where one dispatch is ``num_steps`` units).
    step_units: int = 0
    wall_s: float = 0.0
    #: iteration units per observed step kind — a padded bucket is
    #: *usually* one kind, but a mixed-capable bucket may host
    #: prefill-only steps too. The retroactive XLA adjustment multiplies
    #: the per-iteration delta by these.
    kind_dispatches: dict = field(default_factory=dict)

    def to_doc(self) -> dict:
        achieved_gbps = self.bytes * self.step_units / self.wall_s / 1e9 if self.wall_s > 0 else 0.0
        return {
            "program": self.program,
            "key": list(self.key),
            "kind": self.kind,
            "bytes": int(self.bytes),
            "flops": int(self.flops),
            "peak_memory_bytes": int(self.peak_memory_bytes),
            "source": self.source,
            "dispatches": self.dispatches,
            "steps": self.step_units,
            "wall_ms": round(self.wall_s * 1e3, 3),
            "achieved_gbps": round(achieved_gbps, 3),
        }


class CostRegistry:
    """Per-runner ledger of per-program costs and per-step-kind totals.

    Hot-path surface is two O(1) calls: :meth:`seen` (set lookup) and
    :meth:`observe` (dict arithmetic under a lock). Extraction work rides
    :meth:`submit` -> one daemon thread. Never raises into the serving
    path: extraction failures degrade to the estimate and log once.
    """

    def __init__(self, *, worker: str = "", peaks: tuple[float, float] | None = None) -> None:
        self.worker = worker
        if peaks is None:
            hbm, tflops, src = chip_peaks()
        else:
            hbm, tflops, src = float(peaks[0]), float(peaks[1]), "caller"
        self.peak_hbm_gbps = hbm
        self.peak_tflops = tflops
        self.peak_source = src
        self._lock = threading.Lock()
        self._records: dict[tuple, CostRecord] = {}
        self._ledger: dict[str, dict[str, float]] = {}
        self._step_bytes = 0.0
        self._step_flops = 0.0
        self.extract_calls = 0
        self.extract_failures = 0
        self._q: queue.Queue = queue.Queue()
        self._thread: threading.Thread | None = None

    # -- hot path ------------------------------------------------------------

    def seen(self, program: str, key: tuple) -> bool:
        return (program, key) in self._records

    def submit(
        self,
        program: str,
        key: tuple,
        kind: str,
        *,
        lower: Callable[[], Any] | None = None,
        estimate: dict[str, float] | None = None,
    ) -> None:
        """Register a first-seen bucket: estimate now, XLA numbers later."""
        rid = (program, key)
        with self._lock:
            if rid in self._records:
                return
            rec = CostRecord(program=program, key=key, kind=kind)
            if estimate:
                rec.bytes = float(estimate.get("bytes", 0.0))
                rec.flops = float(estimate.get("flops", 0.0))
                rec.source = "estimate"
            self._records[rid] = rec
        if lower is not None:
            self._q.put((rid, lower))
            self._ensure_thread()

    def observe(
        self, program: str, key: tuple, seconds: float, kind: str | None = None, steps: int = 1
    ) -> None:
        """Account one dispatch of a registered bucket into the ledger.

        ``steps`` scales the record's per-iteration bytes/flops: XLA's cost
        analysis counts a while/scan body once regardless of trip count, so
        a multi-step burst dispatch passes its ``num_steps`` here to keep
        the ledger honest. Wall time stays measured — one dispatch's wall
        covers all its iterations, so GB/s math needs no correction.
        """
        rid = (program, key)
        steps = max(1, int(steps))
        with self._lock:
            rec = self._records.get(rid)
            if rec is None:  # estimate-less caller skipped submit
                rec = self._records[rid] = CostRecord(program=program, key=key, kind=kind or "decode")
            k = kind or rec.kind
            rec.dispatches += 1
            rec.step_units += steps
            rec.wall_s += max(0.0, seconds)
            rec.kind_dispatches[k] = rec.kind_dispatches.get(k, 0) + steps
            led = self._ledger.setdefault(
                k, {"bytes": 0.0, "flops": 0.0, "wall_s": 0.0, "dispatches": 0, "steps": 0}
            )
            led["bytes"] += rec.bytes * steps
            led["flops"] += rec.flops * steps
            led["wall_s"] += max(0.0, seconds)
            led["dispatches"] += 1
            led["steps"] += steps
            self._step_bytes += rec.bytes * steps
            self._step_flops += rec.flops * steps

    def take_step(self) -> tuple[float, float]:
        """(bytes, flops) accumulated since the previous take — the engine
        core calls this once per step to stamp its STEP flight record."""
        with self._lock:
            out = (self._step_bytes, self._step_flops)
            self._step_bytes = self._step_flops = 0.0
            return out

    # -- background extraction ----------------------------------------------

    def _ensure_thread(self) -> None:
        if self._thread is not None and self._thread.is_alive():
            return
        self._thread = threading.Thread(
            target=self._extract_loop, name="dyn-cost-extract", daemon=True
        )
        self._thread.start()

    def _extract_loop(self) -> None:
        while True:
            item = self._q.get()
            if item is None:
                return
            rid, lower = item
            try:
                self._extract(rid, lower)
            except Exception as exc:
                self.extract_failures += 1
                logger.debug("cost extraction failed for %s: %s", rid, exc)
            finally:
                self._q.task_done()

    def _extract(self, rid: tuple, lower: Callable[[], Any]) -> None:
        global EXTRACTIONS
        self.extract_calls += 1
        EXTRACTIONS += 1
        compiled = lower().compile()
        flops, byts = _parse_cost_analysis(compiled.cost_analysis())
        peak_mem = 0.0
        try:
            mem = compiled.memory_analysis()
            peak_mem = float(
                getattr(mem, "argument_size_in_bytes", 0)
                + getattr(mem, "output_size_in_bytes", 0)
                + getattr(mem, "temp_size_in_bytes", 0)
            )
        except Exception:
            pass
        if byts <= 0.0 and flops <= 0.0:
            return  # backend reported nothing: the estimate stands
        with self._lock:
            rec = self._records.get(rid)
            if rec is None:
                return
            db, df = byts - rec.bytes, flops - rec.flops
            rec.bytes, rec.flops = byts, flops
            rec.peak_memory_bytes = peak_mem
            rec.source = "xla"
            # Dispatches already accounted at the estimate retro-adjust to
            # the XLA numbers, per kind they were observed under.
            for k, n in rec.kind_dispatches.items():
                led = self._ledger.get(k)
                if led is not None:
                    led["bytes"] += db * n
                    led["flops"] += df * n

    def drain(self, timeout: float = 10.0) -> bool:
        """Block until queued extractions finish (tests/tools only)."""
        deadline = time.monotonic() + timeout
        while time.monotonic() < deadline:
            if self._q.unfinished_tasks == 0:
                return True
            time.sleep(0.01)
        return self._q.unfinished_tasks == 0

    # -- read side -----------------------------------------------------------

    def roofline_of(self, byts: float, flops: float, seconds: float) -> tuple[float, str]:
        """(roofline fraction, bound) for a measured window: achieved over
        peak on each axis, classified memory- vs compute-bound by which
        fraction dominates."""
        if seconds <= 0.0 or (byts <= 0.0 and flops <= 0.0):
            return 0.0, ""
        frac_mem = byts / seconds / (self.peak_hbm_gbps * 1e9) if self.peak_hbm_gbps > 0 else 0.0
        frac_comp = flops / seconds / (self.peak_tflops * 1e12) if self.peak_tflops > 0 else 0.0
        if frac_mem >= frac_comp:
            return frac_mem, "memory"
        return frac_comp, "compute"

    def ledger(self) -> dict[str, dict[str, float]]:
        """Per-step-kind achieved GB/s, FLOP/s and roofline fraction."""
        with self._lock:
            snap = {k: dict(v) for k, v in self._ledger.items()}
        out: dict[str, dict[str, float]] = {}
        for kind, led in snap.items():
            wall = led["wall_s"]
            gbps = led["bytes"] / wall / 1e9 if wall > 0 else 0.0
            tflops = led["flops"] / wall / 1e12 if wall > 0 else 0.0
            frac, bound = self.roofline_of(led["bytes"], led["flops"], wall)
            out[kind] = {
                **led,
                "gbps": round(gbps, 3),
                "tflops": round(tflops, 4),
                "roofline_frac": round(frac, 6),
                "bound": bound,
                "bytes_per_dispatch": led["bytes"] / led["dispatches"] if led["dispatches"] else 0.0,
                "bytes_per_step": led["bytes"] / led["steps"] if led.get("steps") else 0.0,
            }
        return out

    def totals(self) -> dict[str, dict[str, float]]:
        """Cumulative {kind: {bytes, flops}} — the Counter sync source."""
        with self._lock:
            return {
                k: {"bytes": v["bytes"], "flops": v["flops"]}
                for k, v in self._ledger.items()
            }

    def record_for(self, program: str, key: tuple | None = None) -> CostRecord | None:
        """The record for a program (first match when key is None)."""
        with self._lock:
            if key is not None:
                return self._records.get((program, key))
            for (prog, _), rec in self._records.items():
                if prog == program:
                    return rec
        return None

    def snapshot(self) -> dict:
        """The /debug/cost document: per-program table + ledger + peaks."""
        with self._lock:
            records = [rec.to_doc() for rec in self._records.values()]
        records.sort(key=lambda r: (-r["wall_ms"], r["program"]))
        return {
            "enabled": True,
            "worker": self.worker,
            "peaks": {
                "hbm_gbps": self.peak_hbm_gbps,
                "tflops": self.peak_tflops,
                "source": self.peak_source,
            },
            "extract_calls": self.extract_calls,
            "extract_failures": self.extract_failures,
            "programs": records,
            "ledger": self.ledger(),
        }

    def close(self) -> None:
        """Stop the extraction thread: drop what is queued, let the compile
        in flight finish, and join. A daemon thread still inside the compiler
        when the interpreter exits takes the process down with it."""
        thread = self._thread
        if thread is None or not thread.is_alive():
            return
        try:
            while True:
                self._q.get_nowait()
                self._q.task_done()
        except queue.Empty:
            pass
        self._q.put(None)
        thread.join()
