"""Launcher: the `dynamo-run` equivalent (reference SURVEY.md §2 row 37).

Wires the pieces into runnable topologies:

- ``serve_worker``     — build a JAX engine for a model and serve it on a
  runtime endpoint; publish the ModelDeploymentCard (lease-bound) so
  frontends discover it.
- ``serve_frontend``   — ModelManager + ModelWatcher + OpenAI HttpService.
- ``run_local``        — both in one process over the in-memory runtime
  (the `dynamo-run in=http out=<engine>` single-node path).
- CLI: ``python -m dynamo_tpu.launch --model test-tiny --http-port 8080``
  with ``--store tcp://...`` to join a multi-process deployment.
"""

from __future__ import annotations

import argparse
import asyncio
import logging
import os
import time
from dataclasses import dataclass, field
from typing import Any

from dynamo_tpu import tracing
from dynamo_tpu.config import env_flag
from dynamo_tpu.engine.core import EngineConfig, EngineCore
from dynamo_tpu.engine.runner import ModelRunner
from dynamo_tpu.engine.service import JaxEngineService
from dynamo_tpu.frontend.http import HttpService
from dynamo_tpu.frontend.metrics import FrontendMetrics
from dynamo_tpu.frontend.model_manager import ModelManager, ModelWatcher
from dynamo_tpu.model_card import ModelDeploymentCard
from dynamo_tpu.models import llama
from dynamo_tpu.models.config import PRESETS, ModelConfig
from dynamo_tpu.protocols.kv import KvCacheEvent
from dynamo_tpu.runtime.component import DistributedRuntime
from dynamo_tpu.tokenizer import load_tokenizer

logger = logging.getLogger(__name__)


@dataclass
class WorkerSpec:
    """Everything needed to bring up one engine worker."""

    model_config: ModelConfig
    card: ModelDeploymentCard
    engine_config: EngineConfig = field(default_factory=EngineConfig)
    params: Any = None  # model params pytree; random-init if None
    model_dir: str | None = None  # HF-style checkpoint dir: real weights + tokenizer
    attn_impl: str | None = None
    block_manager_config: Any = None  # blocks.BlockManagerConfig enables G2/G3 tiers
    # GSPMD execution: a parallel.mesh.MeshPlan, or "auto" to derive one from
    # the device count and model shape (tp <= kv heads, ep for wide MoE).
    mesh_plan: Any = None
    # The one device this worker's params, KV cache and step inputs live on
    # (replicas in one process: worker i on device i). None = jax's default
    # device; ignored under a mesh, which owns placement.
    device: Any = None
    # Timing-model engine instead of JAX (planner/router fleets in CI and the
    # planner's local connector; parity: reference mocker, SURVEY.md row 35).
    mock: bool = False
    # Weight-only quantization applied after load ("" = off, "int8"):
    # halves weight HBM reads on the decode path (models/quant.py).
    quantize: str = ""
    # VLM checkpoints: the vision tower's config (+ loaded params, filled at
    # engine build time so run_local can start a weight-sharing encode worker).
    # serve_vision=False skips loading the tower (extra workers in a fleet).
    vision_config: Any = None
    vision_params: Any = None
    serve_vision: bool = True

    @classmethod
    def from_preset(cls, preset: str, *, card: ModelDeploymentCard | None = None, **engine_kw: Any) -> "WorkerSpec":
        mc = PRESETS[preset]
        tokenizer = "byte"
        card = card or ModelDeploymentCard(
            name=preset,
            tokenizer=tokenizer,
            context_length=min(mc.max_position, 4096),
            eos_token_ids=sorted(load_tokenizer(tokenizer).eos_token_ids),
        )
        if mc.image_token_id is not None:
            card.extra.setdefault("image_token_id", mc.image_token_id)
        return cls(model_config=mc, card=card, engine_config=cls._engine_cfg(card, engine_kw))

    @classmethod
    def from_model_dir(cls, model_dir: str, *, name: str | None = None, **engine_kw: Any) -> "WorkerSpec":
        """Serve a real HF-style checkpoint directory (config.json +
        safetensors + tokenizer.json). Weights load at engine build time,
        directly onto the device/mesh.

        Parity: reference `lib/llm/src/local_model.rs:29-140` (local model
        resolution into a served card + engine)."""
        import pathlib

        p = pathlib.Path(model_dir)
        if p.is_file() and p.suffix == ".gguf":
            from dynamo_tpu.models.gguf import config_from_gguf, shared_reader

            # The shared reader serves config, card, tokenizer, and weights:
            # parsing the header eagerly decodes the full embedded vocab
            # (100k+ strings for a real model) — do it once per process.
            reader = shared_reader(p)
            mc = config_from_gguf(reader, name=name or p.stem)
            card = ModelDeploymentCard.from_gguf(name or p.stem, p, reader=reader)
        else:
            mc = ModelConfig.from_hf(p / "config.json", name=name or p.name)
            card = ModelDeploymentCard.from_model_dir(name or p.name, p)
        spec = cls(
            model_config=mc, card=card,
            engine_config=cls._engine_cfg(card, engine_kw), model_dir=str(p),
        )
        # LLaVA-class VLM checkpoint: record the tower config; the engine
        # build loads LM+tower via load_vlm and run_local starts a real
        # encode worker (models/loader.load_vlm, VERDICT r3 item 4).
        import json as _json

        if not (p.is_file() and p.suffix == ".gguf"):
            raw_cfg = _json.loads((p / "config.json").read_text())
            if "vision_config" in raw_cfg:
                if raw_cfg.get("model_type") == "qwen2_vl":
                    from dynamo_tpu.models.qwen2_vl import Qwen2VLVisionConfig

                    spec.vision_config = Qwen2VLVisionConfig.from_hf(raw_cfg)
                else:
                    from dynamo_tpu.models.vision import VisionConfig

                    spec.vision_config = VisionConfig.from_hf_llava(raw_cfg)
                if mc.image_token_id is not None:
                    card.extra.setdefault("image_token_id", mc.image_token_id)
                if mc.video_token_id is not None:
                    card.extra.setdefault("video_token_id", mc.video_token_id)
        return spec

    @staticmethod
    def _engine_cfg(card: ModelDeploymentCard, engine_kw: dict) -> EngineConfig:
        import os

        # Explicit engine_kw wins over the card-derived defaults (the bench
        # CLI overrides page_size/max_seq_len/decode_steps per run).
        defaults = dict(
            max_seq_len=card.context_length,
            eos_token_ids=tuple(card.eos_token_ids),
            page_size=card.kv_page_size,
            decode_steps=int(
                os.environ.get("DYNAMO_DECODE_STEPS")
                or os.environ.get("DYN_WORKER_DECODE_STEPS", "1")
            ),
            chunk_prefill_tokens=int(
                os.environ.get("DYNAMO_CHUNK_PREFILL_TOKENS")
                or os.environ.get("DYN_WORKER_CHUNK_PREFILL_TOKENS", "512")
            ),
            spec_k=int(
                os.environ.get("DYN_SPEC_K")
                or os.environ.get("DYN_WORKER_SPEC_K", "0")
            ),
            slo_sched=env_flag(os.environ, "DYN_SLO_SCHED"),
            cache_aware=env_flag(os.environ, "DYN_CACHE_AWARE"),
            # DYN_CACHE_AWARE implies async onboarding: residual pricing
            # assumes tier hits are cheap, which they only are pipelined.
            async_onboard=(
                env_flag(os.environ, "DYN_ASYNC_ONBOARD")
                or env_flag(os.environ, "DYN_CACHE_AWARE")
            ),
            constraint_lookahead_tokens=int(
                os.environ.get("DYN_CONSTRAINT_LOOKAHEAD_TOKENS", "32")
            ),
        )
        defaults.update(engine_kw)
        return EngineConfig(**defaults)


def _kv_cache_dtype():
    """Resolve DYN_KV_CACHE_DTYPE / DYN_WORKER_KV_CACHE_DTYPE to a jnp dtype.

    'bf16' (or unset) -> None: the runner keeps its model-dtype default.
    'fp8' -> float8_e4m3fn storage; every attention path upcasts fp8 KV to
    the query dtype at the matmul, so this only changes cache HBM footprint.
    """
    import os

    name = (
        os.environ.get("DYN_KV_CACHE_DTYPE")
        or os.environ.get("DYN_WORKER_KV_CACHE_DTYPE", "")
    ).strip().lower()
    if name in ("", "bf16", "bfloat16"):
        return None
    if name in ("fp8", "float8_e4m3fn", "fp8_e4m3"):
        import jax.numpy as jnp

        return jnp.float8_e4m3fn
    raise ValueError(f"unsupported kv cache dtype: {name!r} (want bf16 or fp8)")


def _parse_mesh(spec: str | None):
    """'auto' | 'dp=2,tp=4' | None -> mesh_plan value for WorkerSpec."""
    if spec is None or spec == "":
        return None
    if spec == "auto":
        return "auto"
    from dynamo_tpu.parallel.mesh import MeshPlan

    kw = {}
    for part in spec.split(","):
        k, _, v = part.partition("=")
        kw[k.strip()] = int(v)
    return MeshPlan(**kw)


def make_worker_spec(model: str, **engine_kw: Any) -> WorkerSpec:
    """Resolve ``model``: a preset name, or a path to an HF checkpoint dir."""
    import os

    if model in PRESETS:
        return WorkerSpec.from_preset(model, **engine_kw)
    if os.path.isdir(model) or (model.endswith(".gguf") and os.path.isfile(model)):
        return WorkerSpec.from_model_dir(model, **engine_kw)
    raise ValueError(
        f"unknown model {model!r}: not a preset ({', '.join(PRESETS)}), a checkpoint directory, or a .gguf file"
    )


#: The root span of a worker's coming-up, and the ``request_id`` of it and of
#: its three children: ``GET /debug/traces/worker_bring_up`` is the timeline.
BRING_UP_SPAN = "worker_bring_up"


def _bring_up_span(spec: WorkerSpec) -> tracing.Span:
    """``worker`` is filled in when the instance has its lease id."""
    return tracing.Span(BRING_UP_SPAN, request_id=BRING_UP_SPAN, worker="", model=spec.card.name)


def _tree_bytes(params: Any) -> int:
    import jax

    return int(sum(getattr(leaf, "nbytes", 0) for leaf in jax.tree_util.tree_leaves(params)))


async def build_engine_service(spec: WorkerSpec, *, on_kv_event=None, g4_storage=None,
                               bring_up: tracing.Span | None = None) -> JaxEngineService:
    """``bring_up`` is the caller's open ``worker_bring_up`` span
    (``serve_worker``'s); without one the build is its own bring-up."""
    if bring_up is None:
        with _bring_up_span(spec) as root:
            return await build_engine_service(spec, on_kv_event=on_kv_event, g4_storage=g4_storage, bring_up=root)
    trace = bring_up.context
    tracing.maybe_trace_from_env()  # DYN_TRACE_DIR=dir captures worker bring-up + first steps
    if spec.mock:
        from dynamo_tpu.mocker import build_mock_core

        return await JaxEngineService(build_mock_core(spec.engine_config, on_kv_event=on_kv_event)).start()

    def _build() -> ModelRunner:
        # Device work (param init, cache allocation) takes seconds — keep it
        # off the event loop so lease keep-alives and health endpoints stay
        # live.
        import contextlib

        import jax

        mesh = None
        if spec.mesh_plan is not None:
            from dynamo_tpu.parallel.mesh import MeshPlan, make_mesh

            plan = spec.mesh_plan
            if plan == "auto":
                plan = MeshPlan.auto(
                    len(jax.devices()),
                    num_kv_heads=spec.model_config.num_kv_heads,
                    num_experts=spec.model_config.num_experts,
                )
            mesh = make_mesh(plan)
        t_params = time.perf_counter()
        source = ("given" if spec.params is not None else "init" if spec.model_dir is None
                  else "gguf" if spec.model_dir.endswith(".gguf") else "checkpoint")
        if spec.params is not None:
            params = spec.params
        elif spec.model_dir is not None and spec.model_dir.endswith(".gguf"):
            from dynamo_tpu.models.gguf import load_gguf_params, shared_reader

            # int4 serving imports the file's own Q4_0/Q4_K codes directly
            # into packed leaves (lossless repack, no bf16 round trip); the
            # quantize_params pass below converts whatever fell back.
            params = load_gguf_params(
                shared_reader(spec.model_dir), spec.model_config, mesh=mesh,
                quantize=spec.quantize,
            )
        elif spec.model_dir is not None and spec.vision_config is not None:
            from dynamo_tpu.models.loader import load_vlm

            _tc, _vc, params, spec.vision_params = load_vlm(
                spec.model_dir, mesh=mesh, load_tower=spec.serve_vision
            )
        elif spec.model_dir is not None:
            from dynamo_tpu.models.loader import load_params

            # Direct-to-mesh: each device shard reads its own checkpoint
            # slice; the runner then skips re-placement of placed params.
            params = load_params(spec.model_dir, spec.model_config, mesh=mesh)
        else:
            params = None  # random-init below, possibly directly quantized
        # Random init lands where it will be served from: on this worker's
        # device, or (mesh) each device materializing only its own shard —
        # an 8B bf16 model built whole on the default device OOMs it before
        # shard_params could spread it.
        device = spec.device
        with jax.default_device(device) if device is not None else contextlib.nullcontext():
            if spec.quantize and params is None:
                # Random-init + quantize without ever materializing the
                # full-precision tree: an 8B-class random model OOMs a 16 GB
                # chip before quantize_params could shrink it.
                from dynamo_tpu.models.quant import init_params_quantized

                params = init_params_quantized(spec.model_config, 0, mode=spec.quantize)
            elif spec.quantize:
                from dynamo_tpu.models.quant import quantize_params

                params = quantize_params(params, mode=spec.quantize)
            elif params is None and mesh is not None:
                from dynamo_tpu.parallel.sharding import init_sharded

                params = init_sharded(lambda: llama.init_params(spec.model_config, 0), mesh)
            elif params is None:
                params = llama.init_params(spec.model_config, 0)
        t_runner = time.perf_counter()
        tracing.record_span("worker_params", (t_runner - t_params) * 1e3, trace=trace, start_mono=t_params,
                            request_id=BRING_UP_SPAN, source=source, bytes=_tree_bytes(params))
        runner = ModelRunner(
            spec.model_config,
            params,
            num_pages=spec.engine_config.num_pages,
            page_size=spec.engine_config.page_size,
            max_batch_size=spec.engine_config.max_batch_size,
            attn_impl=spec.attn_impl,
            mesh=mesh,
            device=device,
            cache_dtype=_kv_cache_dtype(),
            # What bounds a row's step, for a mixed model's window pool
            # (llama.window_pool_pages), where pages behind the window are
            # given back and prompts are chunked at all: the chunk beside
            # decoding rows, ``max_prefill_tokens`` in a step without any
            # (the chunk controller never goes above its base).
            window_chunk=(max(spec.engine_config.chunk_prefill_tokens, spec.engine_config.max_prefill_tokens)
                          if spec.engine_config.swa_free_pages and spec.engine_config.chunk_prefill_tokens > 0 else None),
        )
        tracing.record_span("runner_init", (time.perf_counter() - t_runner) * 1e3, trace=trace, start_mono=t_runner,
                            request_id=BRING_UP_SPAN, **runner.memory_bytes_by_kind())
        # The runner's first calls are part of this worker's coming-up,
        # whoever makes them: their spans go under the same trace.
        runner.compile_tracker.trace = trace
        return runner

    runner = await asyncio.get_running_loop().run_in_executor(None, _build)
    import jax

    dev0 = jax.devices()[0]
    logger.info(
        "engine for %s: platform=%s device_kind=%s devices=%d placement=%s attention=%s",
        spec.card.name, dev0.platform, dev0.device_kind, len(jax.devices()),
        dict(runner.mesh.shape) if runner.mesh is not None else (runner.device or dev0),
        runner.attn_impl,
    )
    block_manager = None
    if spec.block_manager_config is not None:
        from dynamo_tpu.blocks import KvBlockManager

        block_manager = KvBlockManager(
            spec.block_manager_config,
            read_page=runner.read_page,
            write_page=runner.write_page,
            write_pages=getattr(runner, "write_pages", None),
            g4_storage=g4_storage,
        )
    core = EngineCore(runner, spec.engine_config, on_kv_event=on_kv_event, block_manager=block_manager)
    # Constrained decoding (response_format json_object) needs token text;
    # warm the vocab piece table + hot masks on a background thread so the
    # first json_mode request doesn't stall the serving loop.
    import os
    import threading

    core.set_constraint_tokenizer_factory(lambda: load_tokenizer(spec.card.tokenizer))
    # Default-on warm-up trades a background thread at startup for never
    # paying the cold vocab walk on the serving loop; fleets that never see
    # json_mode can set DYNAMO_WARM_CONSTRAINTS=0 to skip it entirely (the
    # first constrained request then pays the build, serialized by the
    # cache's build lock).
    if os.environ.get("DYNAMO_WARM_CONSTRAINTS", "1") != "0":
        threading.Thread(target=core.warm_constraints, daemon=True,
                         name="constraint-warmup").start()
    return await JaxEngineService(core).start()


async def serve_worker(
    runtime: DistributedRuntime,
    spec: WorkerSpec,
    *,
    lease=None,
    disagg=None,  # disagg.DisaggConfig: serve as a disaggregated *decode* worker
) -> JaxEngineService:
    """Serve the engine + KV event stream + metrics and publish the model card.

    With ``disagg`` set, the worker also serves the KV transfer endpoint and
    fronts its engine with the disagg operator (remote prefill via the
    prefill queue; see dynamo_tpu.disagg).
    """
    with _bring_up_span(spec) as root:
        return await _serve_worker(runtime, spec, root, lease=lease, disagg=disagg)


async def _serve_worker(runtime: DistributedRuntime, spec: WorkerSpec, root: tracing.Span, *, lease, disagg):
    from dynamo_tpu.router.events import KV_EVENTS_ENDPOINT, KvEventBroadcaster
    from dynamo_tpu.router.metrics import WorkerMetricsPublisher

    broadcaster = KvEventBroadcaster()
    broadcaster.bind_loop(asyncio.get_running_loop())
    service = await build_engine_service(
        spec, on_kv_event=broadcaster.publish, g4_storage=_g4_storage_for(spec, runtime), bring_up=root
    )
    t_register = time.perf_counter()
    service.spec = spec  # run_local reads vision_config/params off it (VLM)
    if getattr(service.core, "state_slots", None) is not None and spec.card.router_mode == "kv":
        await service.close()
        raise ValueError(
            f"{spec.card.name}: router_mode 'kv' is not served for a model with recurrent layers: the KV router "
            "sends a request where its prefix's pages lie, and such a model reuses no prefix (pages alone do not "
            "bring back a state); use round_robin or random")
    broadcaster.bind_snapshot(service.core.allocator.cache_snapshot)
    ns, comp, ep = spec.card.endpoint
    component = runtime.namespace(ns).component(comp)

    serve_engine: Any = service
    transfer = None
    if disagg is not None:
        from dynamo_tpu.disagg.operator import DisaggDecodeService
        from dynamo_tpu.disagg.prefill_worker import PREFILL_QUEUE
        from dynamo_tpu.disagg.queue import DistributedQueue
        from dynamo_tpu.disagg.router import DisaggRouter
        from dynamo_tpu.disagg.transfer import KV_TRANSFER_ENDPOINT, KvTransferService

        transfer = KvTransferService(service.core)
        service.aux.append(transfer.start_sweeper())
        t_inst = await component.endpoint(KV_TRANSFER_ENDPOINT).serve(
            transfer, metadata={"model": spec.card.name}, lease=lease
        )
        # Device-path short-circuit for co-located prefill workers (ICI
        # instead of the TCP host-bounce) — see disagg/device_transfer.py.
        from dynamo_tpu.disagg.device_transfer import REGISTRY

        service.aux.append(REGISTRY.register(t_inst.address, transfer))
        disagg_router = await DisaggRouter(disagg, page_size=spec.engine_config.page_size).watch(runtime, ns)
        serve_engine = DisaggDecodeService(
            service, transfer, DistributedQueue(runtime, PREFILL_QUEUE), disagg_router, t_inst.address
        )
        service.disagg_operator = serve_engine  # remote/local prefill counters
        service.aux.append(disagg_router)

    instance = await component.endpoint(ep).serve(serve_engine, metadata={"model": spec.card.name}, lease=lease)
    await component.endpoint(KV_EVENTS_ENDPOINT).serve(broadcaster, metadata={"model": spec.card.name}, lease=lease)
    service.core.config.worker_id = instance.lease_id  # same object as spec.engine_config
    # Graceful drain needs both: re-publish the record with draining=True,
    # then revoke the lease once in-flight work finishes (drain_worker).
    service.instance = instance
    service.serve_lease = lease

    def snapshot():
        m = service.metrics()
        m.worker_id = instance.lease_id
        return m

    publisher = await WorkerMetricsPublisher(
        runtime, ns, comp, instance.lease_id, snapshot, interval=0.5, lease=lease
    ).start()
    service.aux.append(publisher)  # closed with the service by callers that track it
    await _serve_worker_telemetry(
        component, service, worker_id=f"{instance.lease_id:x}", lease=lease,
        transfer=transfer,
        queue=getattr(serve_engine, "queue", None) if disagg is not None else None,
        metadata={"model": spec.card.name},
    )
    card_lease = lease or await runtime.primary_lease()
    await runtime.put_leased(spec.card.instance_key(instance.lease_id), spec.card.to_bytes(), card_lease)
    root.fields["worker"] = f"{instance.lease_id:x}"
    tracing.record_span("worker_register", (time.perf_counter() - t_register) * 1e3, trace=root.context,
                        start_mono=t_register, request_id=BRING_UP_SPAN, worker=root.fields["worker"])
    logger.info("worker serving %s as instance %x", spec.card.name, instance.lease_id)
    return service


async def _serve_worker_telemetry(
    component,
    service: JaxEngineService,
    *,
    worker_id: str,
    lease=None,
    transfer=None,
    queue=None,
    metadata: dict | None = None,
):
    """Attach the per-worker telemetry plane (ISSUE: observability tentpole).

    Builds the EngineMetrics registry bound to this worker's engine
    internals, installs it as the process's KV-phase sink, and serves the
    span-query + metrics-scrape endpoints next to ``generate`` so the
    frontend can federate. ``DYN_WORKER_HTTP_PORT`` additionally opens the
    direct debug HTTP surface (0 = pick a free port).
    """
    from dynamo_tpu.observability import (
        DEBUG_EXPLAIN_ENDPOINT,
        DEBUG_TRACES_ENDPOINT,
        FLIGHT_ENDPOINT,
        METRICS_SCRAPE_ENDPOINT,
        EngineMetrics,
        ExplainQueryService,
        FlightQueryService,
        MetricsScrapeService,
        SpanQueryService,
    )
    from dynamo_tpu.observability.metrics import install
    from dynamo_tpu.observability.service import (
        DEBUG_INCIDENTS_ENDPOINT,
        PROFILE_ENDPOINT,
        IncidentQueryService,
        ProfileCaptureService,
    )

    metrics = EngineMetrics(worker=worker_id).bind_core(service.core)
    if transfer is not None:
        metrics.bind_transfer(transfer)
    if queue is not None:
        metrics.bind_queue(queue)
    # Process-global phase sink (plus the per-core route, so several
    # in-process workers each attribute their own KV phases — run_local is
    # now exact, not just multi-process deployments).
    install(metrics)
    service.engine_metrics = metrics  # reachable for tests / direct scraping
    await component.endpoint(DEBUG_TRACES_ENDPOINT).serve(
        SpanQueryService(host=worker_id), metadata=metadata, lease=lease
    )
    await component.endpoint(METRICS_SCRAPE_ENDPOINT).serve(
        MetricsScrapeService(metrics), metadata=metadata, lease=lease
    )
    flight = getattr(service.core, "flight", None)
    if flight is not None:
        await component.endpoint(FLIGHT_ENDPOINT).serve(
            FlightQueryService(flight, worker=worker_id), metadata=metadata, lease=lease
        )
        await component.endpoint(DEBUG_EXPLAIN_ENDPOINT).serve(
            ExplainQueryService(service.core, worker=worker_id),
            metadata=metadata, lease=lease,
        )
    incidents = getattr(service.core, "incidents", None)
    if incidents is not None:
        # Bundles captured before bring-up keep the pid label; everything
        # after carries the lease id the frontend addresses workers by.
        incidents.worker = worker_id
        await component.endpoint(DEBUG_INCIDENTS_ENDPOINT).serve(
            IncidentQueryService(incidents.store, worker=worker_id),
            metadata=metadata, lease=lease,
        )
    await component.endpoint(PROFILE_ENDPOINT).serve(
        ProfileCaptureService(worker=worker_id), metadata=metadata, lease=lease
    )
    port_spec = os.environ.get("DYN_WORKER_HTTP_PORT")
    if port_spec is not None:
        from dynamo_tpu.observability.http import WorkerDebugServer

        debug = WorkerDebugServer(
            metrics, flight=flight,
            incidents=incidents.store if incidents is not None else None,
        )
        await debug.start(port=int(port_spec))
        service.aux.append(debug)
    return metrics


def _g4_storage_for(spec: WorkerSpec, runtime: DistributedRuntime):
    """RemoteStorage for the G4 tier when configured (decode AND prefill
    workers): blocks offloaded here are onboardable by every worker joined
    to the same store (shared best-effort cache, `blocks/tier.py`)."""
    bm_cfg = spec.block_manager_config
    if bm_cfg is None or getattr(bm_cfg, "g4_capacity_blocks", 0) <= 0 or bm_cfg.null_storage:
        return None
    from dynamo_tpu.blocks.storage import RemoteStorage
    from dynamo_tpu.runtime.objects import ObjectStore

    return RemoteStorage(
        ObjectStore(runtime.store), asyncio.get_running_loop(), prefix=f"kv/{spec.card.name}"
    )


async def serve_prefill_worker(runtime: DistributedRuntime, spec: WorkerSpec, *, lease=None):
    """A prefill-fleet worker: engine + queue consumer, no model card."""
    from dynamo_tpu.disagg.prefill_worker import PrefillWorker

    service = await build_engine_service(spec, g4_storage=_g4_storage_for(spec, runtime))
    conc = int(os.environ.get("DYN_PREFILL_CONCURRENCY", "2"))
    worker = await PrefillWorker(runtime, service, max_concurrency=conc).start()
    service.aux.append(worker)
    service.prefill_worker = worker  # drain_worker stops claiming before closing
    service.serve_lease = lease
    ns, comp, _ep = spec.card.endpoint
    worker_id = f"{lease.id:x}" if lease is not None else f"prefill-{os.getpid()}"
    await _serve_worker_telemetry(
        runtime.namespace(ns).component(comp), service,
        worker_id=worker_id, lease=lease, queue=worker.queue,
        metadata={"model": spec.card.name, "role": "prefill"},
    )
    logger.info("prefill worker up for %s", spec.card.name)
    return service


async def drain_worker(
    runtime: DistributedRuntime, service: JaxEngineService, *, timeout: float | None = None
) -> bool:
    """Graceful worker shutdown: announce draining, finish in-flight work
    under a deadline, revoke the lease, close.

    Order matters: (1) the instance record is re-published with
    ``metadata.draining=True`` so clients stop routing new requests here
    while the record (and in-flight streams) stay alive; (2) the engine
    finishes admitted requests (and a prefill worker its claimed tasks)
    under ``timeout`` (``DYN_DRAIN_TIMEOUT_S``, default 30); (3) the lease
    is revoked, cascade-deleting every record this worker published; (4) the
    service closes. Returns True when everything finished in time.
    """
    import dataclasses

    if timeout is None:
        timeout = float(os.environ.get("DYN_DRAIN_TIMEOUT_S", "30"))
    instance = getattr(service, "instance", None)
    lease = getattr(service, "serve_lease", None)
    if lease is None:
        lease = await runtime.primary_lease()
    if instance is not None:
        draining = dataclasses.replace(
            instance, metadata={**instance.metadata, "draining": True}
        )
        try:
            await runtime.put_leased(instance.key, draining.to_bytes(), lease)
        except Exception:
            logger.exception("drain announcement failed; clients will retry against us")
    done = True
    worker = getattr(service, "prefill_worker", None)
    if worker is not None:
        done = await worker.drain(timeout)
    if hasattr(service, "drain"):
        done = await service.drain(timeout) and done
    if not done:
        logger.warning("drain deadline (%.1fs) hit with work still in flight", timeout)
    try:
        await lease.revoke()
    except Exception:
        logger.exception("lease revoke during drain failed (expiry will clean up)")
    await service.close()
    logger.info("worker drained and closed (clean=%s)", done)
    return done


async def serve_frontend(
    runtime: DistributedRuntime,
    *,
    host: str = "0.0.0.0",
    port: int = 8080,
    router_factory=None,
    clear_kv_hook=None,
) -> tuple[HttpService, ModelWatcher, int]:
    from dynamo_tpu.observability import WorkerTelemetryClient

    manager = ModelManager()
    watcher = await ModelWatcher(runtime, manager, router_factory=router_factory).start()
    service = HttpService(
        manager, metrics=FrontendMetrics(), clear_kv_hook=clear_kv_hook,
        telemetry=WorkerTelemetryClient(runtime),
    )
    actual_port = await service.start(host, port)
    return service, watcher, actual_port


async def run_local(
    preset: str = "test-tiny",
    *,
    host: str = "127.0.0.1",
    port: int = 8080,
    num_workers: int = 1,
    num_prefill_workers: int = 0,
    router_mode: str = "round_robin",
    disagg=None,  # DisaggConfig: enables the disaggregated topology
    **engine_kw: Any,
) -> dict[str, Any]:
    """Single-process serving: N (decode) workers [+ M prefill workers] + frontend."""
    runtime = DistributedRuntime.detached()
    services = []
    g2_blocks = engine_kw.pop("g2_blocks", 0)
    g3_blocks = engine_kw.pop("g3_blocks", 0)
    g4_blocks = engine_kw.pop("g4_blocks", 0)
    mesh_plan = engine_kw.pop("mesh", None)
    mock = engine_kw.pop("mock", False)
    quantize = engine_kw.pop("quantize", "")
    total_workers = num_workers + num_prefill_workers
    # Replicas in one process each get a device of their own, round-robin
    # (worker i on device i): left to jax's default they all sit on device 0.
    devices = []
    if total_workers > 1 and mesh_plan is None and not mock:
        import jax

        devices = jax.local_devices()

    def make_spec(i: int) -> WorkerSpec:
        spec = make_worker_spec(preset, **engine_kw)
        spec.serve_vision = i == 0  # one tower copy serves the whole fleet
        spec.card.router_mode = router_mode
        spec.mesh_plan = mesh_plan
        spec.mock = mock
        spec.quantize = quantize
        if devices:
            spec.device = devices[i % len(devices)]
        if g2_blocks or g3_blocks or g4_blocks:
            from dynamo_tpu.blocks import BlockManagerConfig

            spec.block_manager_config = BlockManagerConfig(
                g2_capacity_blocks=g2_blocks,
                g3_capacity_blocks=g3_blocks,
                g3_path=f"/tmp/dynamo_tpu_g3_w{i}",
                g4_capacity_blocks=g4_blocks,
            )
        return spec

    for i in range(num_workers):
        # Each worker needs its own lease/instance: secondary leases per worker.
        lease = await runtime.secondary_lease() if total_workers > 1 else None
        service = await serve_worker(runtime, make_spec(i), lease=lease, disagg=disagg)
        services.append(service)
    for i in range(num_prefill_workers):
        lease = await runtime.secondary_lease() if total_workers > 1 else None
        service = await serve_prefill_worker(runtime, make_spec(num_workers + i), lease=lease)
        services.append(service)
    # Vision-language models get an in-process encode worker automatically:
    # presets use the paired test tower; VLM checkpoint dirs serve the REAL
    # loaded tower (CLIP + projector weights from the checkpoint).
    from dynamo_tpu.encode import VISION_PRESETS, serve_encode_worker

    if preset in VISION_PRESETS:
        services.append(await serve_encode_worker(runtime, VISION_PRESETS[preset]))
    else:
        for svc in services:
            spec_v = getattr(svc, "spec", None)
            if spec_v is not None and spec_v.vision_config is not None:
                services.append(await serve_encode_worker(
                    runtime, spec_v.vision_config, params=spec_v.vision_params
                ))
                break

    async def clear_all() -> int:
        n = 0
        for s in services:
            core = getattr(s, "core", None)  # encode workers hold no KV
            if core is None:
                continue
            n += core.allocator.clear_cache()
            if core.block_manager is not None:
                n += core.block_manager.clear()
        return n

    http, watcher, actual_port = await serve_frontend(
        runtime, host=host, port=port, clear_kv_hook=clear_all
    )
    return {
        "runtime": runtime,
        "services": services,
        "http": http,
        "watcher": watcher,
        "port": actual_port,
    }


async def run_role(args: argparse.Namespace) -> None:
    """Multi-process deployment: one process per role, joined via the TCP
    store (``--serve-store`` in exactly one process, ``--store`` elsewhere)."""
    from dynamo_tpu.runtime.store_server import StoreClient, StoreServer
    from dynamo_tpu.runtime.tcp import TcpTransport

    store_server = None
    if args.serve_store_port is not None:
        backing = None
        if getattr(args, "store_persist", None):
            from dynamo_tpu.runtime.persist import PersistentStore

            backing = await PersistentStore.open(args.store_persist)
        store_server = await StoreServer(backing, host=args.host, port=args.serve_store_port).start()
        store = store_server.store
        replicas = [u.strip() for u in (getattr(args, "store_replicas", "") or "").split(",") if u.strip()]
        if len(replicas) > 1:
            from dynamo_tpu.config import load_store_settings
            from dynamo_tpu.runtime.replication import attach_replication

            ss = load_store_settings()
            coord = attach_replication(
                store_server, replicas, args.store_replica_index,
                promote_after_s=ss.promote_after_s, poll_s=ss.poll_s,
                epoch_grace_s=ss.epoch_grace_s,
            )
            await coord.start()
            logger.info(
                "store replica %d/%d (%s) as %s", args.store_replica_index,
                len(replicas), replicas[args.store_replica_index], coord.role,
            )
    else:
        if not args.store:
            raise SystemExit("--role requires --store tcp://host:port (or --serve-store-port)")
        store = StoreClient.from_url(args.store)
    runtime = DistributedRuntime(store, TcpTransport(host=args.host))

    if args.num_nodes > 1:
        # Multi-host worker: rendezvous through the store, then initialize
        # the global device runtime so the mesh below spans every node.
        from dynamo_tpu.parallel.multihost import MultiNodeConfig, bringup

        await bringup(
            MultiNodeConfig(
                num_nodes=args.num_nodes, node_rank=args.node_rank,
                leader_addr=args.leader_addr,
            ),
            runtime,
        )

    disagg = None
    if args.disagg_threshold is not None:
        from dynamo_tpu.disagg.router import DisaggConfig

        disagg = DisaggConfig(max_local_prefill_length=args.disagg_threshold)

    service = None  # engine-bearing roles get SIGTERM -> drain_worker below
    if args.role == "frontend":
        _, _, port = await serve_frontend(runtime, host=args.host, port=args.http_port)
        logger.info("frontend ready on port %d", port)
    elif args.role == "worker":
        spec = make_worker_spec(args.model, num_pages=args.num_pages, max_batch_size=args.max_batch_size)
        spec.card.router_mode = args.router_mode
        spec.mesh_plan = _parse_mesh(args.mesh)
        spec.mock = args.mock
        spec.quantize = args.quantize
        service = await serve_worker(runtime, spec, disagg=disagg)
        logger.info("worker ready")
    elif args.role == "prefill":
        spec = make_worker_spec(args.model, num_pages=args.num_pages, max_batch_size=args.max_batch_size)
        spec.mesh_plan = _parse_mesh(args.mesh)
        spec.mock = args.mock
        spec.quantize = args.quantize
        service = await serve_prefill_worker(runtime, spec)
        logger.info("prefill worker ready")
    elif args.role == "encode":
        from dynamo_tpu.encode import VISION_PRESETS, serve_encode_worker

        if args.model not in VISION_PRESETS:
            raise SystemExit(f"no vision tower for model {args.model!r}")
        await serve_encode_worker(runtime, VISION_PRESETS[args.model])
        logger.info("encode worker ready")
    elif args.role == "router":
        from dynamo_tpu.model_card import MODEL_PREFIX, ModelDeploymentCard
        from dynamo_tpu.router.service import serve_router

        # Router-only hosts need no checkpoint: take the block size from a
        # card already published in the store (fall back to the default).
        block_size = 16
        for value in (await runtime.store.get_prefix(f"{MODEL_PREFIX}/")).values():
            try:
                block_size = ModelDeploymentCard.from_bytes(value).kv_page_size
                break
            except Exception:
                continue
        await serve_router(runtime, namespace="dynamo", component="backend", block_size=block_size)
        logger.info("router service ready")
    elif args.role == "store":
        logger.info("store-only process")
    else:
        raise SystemExit(f"unknown role {args.role!r}")
    stop = asyncio.Event()
    if service is not None:
        import signal

        def _dump_flight(reason: str) -> None:
            # Planner scale-downs and rolling upgrades end with a signal,
            # not a crash — the flight ring's last seconds must land on
            # disk for those exits too, not only engine-loop failures.
            flight = getattr(service.core, "flight", None)
            if flight is None:
                return
            try:
                path = flight.dump_jsonl(reason=reason)
                logger.info("flight ring dumped on %s -> %s", reason, path)
            except Exception:
                logger.exception("flight dump on %s failed", reason)

        async def _drain_then_stop() -> None:
            try:
                await drain_worker(runtime, service)
            except Exception:
                logger.exception("drain on signal failed")
            finally:
                stop.set()

        def _on_signal(reason: str) -> None:
            logger.info("%s received: dumping flight ring, draining before exit", reason.upper())
            _dump_flight(reason)
            asyncio.ensure_future(_drain_then_stop())

        try:
            loop = asyncio.get_running_loop()
            loop.add_signal_handler(signal.SIGTERM, lambda: _on_signal("sigterm"))
            loop.add_signal_handler(signal.SIGINT, lambda: _on_signal("sigint"))
        except (NotImplementedError, RuntimeError):
            # Non-Unix loops (or nested-loop shims) don't support signal
            # handlers; the role then relies on lease expiry for cleanup.
            logger.debug("signal handlers unavailable; drain-on-terminate disabled")
    print(f"READY role={args.role}", flush=True)
    await stop.wait()


async def start_local(args: argparse.Namespace) -> dict[str, Any]:
    """``--role local``: bring the whole stack up in this process from parsed
    CLI args; returns run_local's handles (pass them to :func:`stop_local`)."""
    disagg = None
    if args.disagg_threshold is not None:
        from dynamo_tpu.disagg.router import DisaggConfig

        disagg = DisaggConfig(max_local_prefill_length=args.disagg_threshold)
    return await run_local(
        args.model,
        host=args.host,
        port=args.http_port,
        num_workers=args.workers,
        num_prefill_workers=args.prefill_workers,
        router_mode=args.router_mode,
        disagg=disagg,
        mesh=_parse_mesh(args.mesh),
        num_pages=args.num_pages,
        max_batch_size=args.max_batch_size,
        g2_blocks=args.g2_blocks,
        g3_blocks=args.g3_blocks,
        g4_blocks=args.g4_blocks,
        mock=args.mock,
        quantize=args.quantize,
    )


async def stop_local(handles: dict[str, Any]) -> None:
    """Full teardown of a :func:`start_local` stack. Leaving engines/runtime
    to loop-shutdown cancellation risks the shutdown-hang class the soak
    tests guard against. One shielded task runs every step (each isolated),
    so a Ctrl-C arriving during teardown can't skip the later closes."""

    async def _teardown() -> None:
        for closer in (
            handles["http"].stop,
            handles["watcher"].close,
            *(svc.close for svc in handles["services"]),
            handles["runtime"].close,
        ):
            try:
                await closer()
            except Exception:
                logger.exception("teardown step %r failed", closer)

    task = asyncio.ensure_future(_teardown())
    try:
        await asyncio.shield(task)
    except asyncio.CancelledError:
        if not task.done():
            await asyncio.wait([task])
        raise


async def _amain(args: argparse.Namespace) -> None:
    if args.role != "local":
        await run_role(args)
        return
    if args.input not in ("http", "text") and not args.input.startswith("batch:"):
        raise SystemExit(
            f"--input must be 'http', 'text', or 'batch:FILE.jsonl' (got {args.input!r})"
        )
    handles = await start_local(args)
    logger.info("serving %s on port %d", args.model, handles["port"])
    try:
        if args.input == "text":
            await run_text_input(handles["port"], args.model)
        elif args.input.startswith("batch:"):
            await run_batch_input(handles["port"], args.model, args.input[len("batch:"):])
        else:
            await asyncio.Event().wait()
    finally:
        await stop_local(handles)


async def run_text_input(port: int, model: str) -> None:
    """Interactive stdin chat against the local stack (``in=text``).

    Parity: reference `dynamo-run in=text` (`launch/dynamo-run/src/input/text.rs`).
    """
    import aiohttp

    loop = asyncio.get_running_loop()
    history: list[dict] = []
    print("interactive mode — empty line or EOF to exit", flush=True)
    async with aiohttp.ClientSession() as session:
        while True:
            try:
                line = await loop.run_in_executor(None, input, "> ")
            except (EOFError, KeyboardInterrupt):
                break
            if not line.strip():
                break
            import json as _json

            history.append({"role": "user", "content": line})
            reply = ""
            failed = False
            async with session.post(
                f"http://127.0.0.1:{port}/v1/chat/completions",
                json={"model": model, "messages": history, "stream": True},
            ) as resp:
                if resp.status != 200:
                    print(f"[error: {(await resp.text())[:200]}]", flush=True)
                    history.pop()  # keep the conversation consistent
                    continue
                async for raw in resp.content:
                    text = raw.decode().strip()
                    if not text.startswith("data: ") or text == "data: [DONE]":
                        continue
                    doc = _json.loads(text[6:])
                    if "error" in doc:
                        print(f"\n[error: {doc['error']}]", flush=True)
                        failed = True
                        break
                    delta = doc["choices"][0].get("delta", {})
                    piece = delta.get("content") or ""
                    reply += piece
                    print(piece, end="", flush=True)
            print(flush=True)
            if failed:
                history.pop()
            else:
                history.append({"role": "assistant", "content": reply})


async def run_batch_input(port: int, model: str, input_path: str, *, concurrency: int = 64) -> None:
    """Batch completion over a JSONL file of ``{"text": ...}`` entries.

    Writes ``output.jsonl`` beside the input (response, token counts,
    latency per entry) and prints an aggregate throughput line.
    Parity: reference `dynamo-run in=batch:` (`input/batch.rs`).
    """
    import json as _json
    import pathlib
    import time

    import aiohttp

    src = pathlib.Path(input_path)
    if not src.is_file():
        raise SystemExit(f"batch input {src} is not a file")
    entries = [
        _json.loads(line) for line in src.read_text().splitlines() if line.strip()
    ]
    out_path = src.parent / "output.jsonl"
    sem = asyncio.Semaphore(concurrency)
    t0 = time.perf_counter()
    totals = {"in": 0, "out": 0}

    async def one(session: aiohttp.ClientSession, entry: dict) -> dict:
        entry = dict(entry)
        async with sem:
            start = time.perf_counter()
            try:
                async with session.post(
                    f"http://127.0.0.1:{port}/v1/completions",
                    json={"model": model, "prompt": entry.get("text", ""), "max_tokens": 256},
                ) as resp:
                    try:
                        doc = await resp.json()
                    except Exception:
                        doc = {"error": (await resp.text())[:200]}
                if resp.status != 200 or "choices" not in doc:
                    entry["response"] = None
                    entry["finish_reason"] = "error"
                    entry["error"] = str(doc.get("error", f"http {resp.status}"))
                else:
                    choice = doc["choices"][0]
                    entry["response"] = choice.get("text", "")
                    entry["finish_reason"] = choice.get("finish_reason")
                    usage = doc.get("usage", {})
                    entry["tokens_in"] = usage.get("prompt_tokens", 0)
                    entry["tokens_out"] = usage.get("completion_tokens", 0)
                    totals["in"] += entry["tokens_in"]
                    totals["out"] += entry["tokens_out"]
            except Exception as exc:
                # One dead connection must not lose the rest of the batch.
                entry["response"] = None
                entry["finish_reason"] = "error"
                entry["error"] = f"{type(exc).__name__}: {exc}"
            entry["elapsed_ms"] = int((time.perf_counter() - start) * 1e3)
            return entry

    async with aiohttp.ClientSession() as session:
        results = await asyncio.gather(*(one(session, e) for e in entries))
    with out_path.open("w") as fh:
        for entry in results:
            fh.write(_json.dumps(entry) + "\n")
    dt = time.perf_counter() - t0
    print(
        f"batch done: {len(results)} entries, {totals['in']} tokens in, "
        f"{totals['out']} tokens out, {dt:.2f}s ({totals['out'] / max(dt, 1e-9):.0f} tok/s) "
        f"-> {out_path}",
        flush=True,
    )


def parse_args(argv: list[str] | None = None) -> argparse.Namespace:
    """Parse the launcher CLI and re-export the engine flags the worker
    settings cascade reads from the environment.

    Layered defaults (reference figment cascade, `config.rs:26-143`):
    dataclass defaults <- TOML (DYN_CONFIG) <- DYN_RUNTIME_*/DYN_WORKER_*
    env <- CLI flags (highest)."""
    from dynamo_tpu.config import load_runtime_settings, load_store_settings, load_worker_settings

    rs = load_runtime_settings()
    ws = load_worker_settings()
    ss_store = load_store_settings()
    if ws.router_mode not in ("round_robin", "random", "kv"):
        # Env/TOML-seeded defaults bypass argparse choices validation.
        raise SystemExit(f"invalid router_mode from config: {ws.router_mode!r}")
    parser = argparse.ArgumentParser(description="dynamo-tpu launcher")
    parser.add_argument("--model", default=ws.model, help="model preset name or HF checkpoint directory")
    parser.add_argument("--host", default=rs.host)
    parser.add_argument("--http-port", type=int, default=rs.http_port)
    parser.add_argument("--workers", type=int, default=1)
    parser.add_argument("--num-pages", type=int, default=ws.num_pages)
    parser.add_argument("--max-batch-size", type=int, default=ws.max_batch_size)
    parser.add_argument("--router-mode", default=ws.router_mode, choices=["round_robin", "random", "kv"])
    parser.add_argument("--g2-blocks", type=int, default=0, help="host-RAM KV tier capacity (blocks); 0 disables")
    parser.add_argument("--g3-blocks", type=int, default=0, help="disk KV tier capacity (blocks); 0 disables")
    parser.add_argument("--g4-blocks", type=int, default=0, help="remote (object-store) KV tier capacity (blocks); 0 disables")
    parser.add_argument("--prefill-workers", type=int, default=0, help="disaggregated prefill fleet size")
    parser.add_argument(
        "--role", default="local", choices=["local", "frontend", "worker", "prefill", "encode", "router", "store"],
        help="multi-process deployments: run one role per process",
    )
    parser.add_argument(
        "--store", default=rs.store or None,
        help="store server url(s): tcp://host:port, or a comma list of "
        "replica urls (tcp://a,tcp://b,...) for HA failover",
    )
    parser.add_argument("--mock", action="store_true", help="timing-model engine instead of JAX (fleet tests, planner)")
    parser.add_argument(
        "--quantize", default="", choices=["", "int8", "int4"],
        help="weight-only quantization for serving (int4: packed nibbles, "
        "group scales of DYN_QUANT_GROUP_SIZE, default 128)",
    )
    parser.add_argument(
        "--input", default="http",
        help="ingress: 'http' (serve), 'text' (interactive stdin chat), or 'batch:FILE.jsonl'",
    )
    parser.add_argument("--serve-store-port", type=int, default=None, help="run the store server in this process")
    parser.add_argument(
        "--store-persist", default=None,
        help="WAL path for durable (lease-less) store state; replayed on restart",
    )
    parser.add_argument(
        "--store-replicas", default=ss_store.replicas or None,
        help="HA store: comma list of ALL replica urls (this process's own "
        "included); index 0 bootstraps as leader",
    )
    parser.add_argument(
        "--store-replica-index", type=int, default=ss_store.replica_index,
        help="this store process's position in --store-replicas",
    )
    parser.add_argument(
        "--disagg-threshold", type=int, default=None,
        help="prompts longer than this prefill remotely (enables disaggregation)",
    )
    parser.add_argument(
        "--mesh", default=ws.mesh or None,
        help="GSPMD mesh: 'auto' or 'dp=2,tp=4,sp=1,ep=1' (default: single device)",
    )
    parser.add_argument(
        "--decode-steps", type=int, default=ws.decode_steps,
        help="chained decode sub-dispatches per step of the pipelined loop",
    )
    parser.add_argument(
        "--chunk-prefill-tokens", type=int, default=ws.chunk_prefill_tokens,
        help="per-step prefill chunk budget fused with decode "
        "(stall-free mixed steps); 0 = phase-exclusive prefill/decode",
    )
    parser.add_argument(
        "--spec-k", type=int, default=ws.spec_k,
        help="speculative decoding draft length (lossless n-gram "
        "self-drafting fused into mixed steps); 0 = off",
    )
    parser.add_argument(
        "--kv-cache-dtype", default=ws.kv_cache_dtype, choices=["bf16", "fp8"],
        help="KV-cache storage dtype; fp8 halves KV HBM (attention upcasts "
        "at the matmul)",
    )
    parser.add_argument("--num-nodes", type=int, default=1, help="hosts forming one worker's mesh")
    parser.add_argument("--node-rank", type=int, default=0)
    parser.add_argument(
        "--leader-addr", default=None,
        help="host:port of the rank-0 jax coordinator (default: rendezvous via the store)",
    )
    parser.add_argument(
        "--tune-profile", default=None,
        help="auto-tuner profile JSON (bench.py --tune output); applies its "
        "knob assignments as env defaults — explicit env/CLI still wins",
    )
    args = parser.parse_args(argv)
    if args.tune_profile:
        import os

        from dynamo_tpu.tuning.profile import apply_profile, load_profile

        # Precedence env > CLI > profile: a knob already in the environment
        # is untouched, and one the operator set via flag is claimed by the
        # CLI (its re-export below must not be shadowed by the profile).
        cli_set = set()
        if args.decode_steps != ws.decode_steps:
            cli_set.add("DYN_WORKER_DECODE_STEPS")
        if args.chunk_prefill_tokens != ws.chunk_prefill_tokens:
            cli_set.add("DYN_WORKER_CHUNK_PREFILL_TOKENS")
        if args.spec_k != ws.spec_k:
            cli_set.add("DYN_WORKER_SPEC_K")
        applied = apply_profile(
            load_profile(args.tune_profile), env=os.environ, cli_set=cli_set
        )
        if applied:
            print(
                "tune profile %s: %s" % (
                    args.tune_profile,
                    " ".join(f"{k}={v}" for k, v in sorted(applied.items())),
                ),
                flush=True,
            )
    if args.decode_steps != 1:
        import os

        os.environ["DYN_WORKER_DECODE_STEPS"] = str(args.decode_steps)
    if args.chunk_prefill_tokens != 512:
        import os

        os.environ["DYN_WORKER_CHUNK_PREFILL_TOKENS"] = str(args.chunk_prefill_tokens)
    if args.spec_k != 0:
        import os

        os.environ["DYN_WORKER_SPEC_K"] = str(args.spec_k)
    if args.kv_cache_dtype != "bf16":
        import os

        os.environ["DYN_WORKER_KV_CACHE_DTYPE"] = args.kv_cache_dtype
    args.runtime_settings = rs  # the cascade the flag defaults came from; main() logs by it
    return args


def main(argv: list[str] | None = None) -> None:
    from dynamo_tpu.compile_cache import enable_compile_cache
    from dynamo_tpu.runtime.logging import setup_logging

    args = parse_args(argv)
    # Cascade-resolved logging settings; reference-named env toggles
    # (DYN_LOGGING_JSONL etc.) still apply when the cascade left defaults.
    rs = args.runtime_settings
    setup_logging(
        jsonl=rs.log_jsonl or None,
        level=None if rs.log_level == "INFO" else rs.log_level,
    )
    enable_compile_cache()
    asyncio.run(_amain(args))


if __name__ == "__main__":
    main()
