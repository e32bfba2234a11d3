"""The system under test, brought up the way ``dynamo_tpu.launch --role local``
does it (``serve_worker`` + ``serve_frontend`` on a detached runtime), with the
configuration file's model, the benchmark's weights and the pinned engine
sizes. Also the warm-up of the step programs a cell can reach and the
snapshots of the program's counters around the window."""

from __future__ import annotations

import asyncio
import dataclasses
import json
import math
import pathlib
import time

import numpy as np

#: Keys of a configuration file that are the benchmark's own, not the model's.
OWN_KEYS = {"source", "assumed", "reference", "serve", "deployment", "preset_differences",
            "rehearsal", "reduced_why", "notes"}


def load_config(path, *, rehearsal: bool = False) -> dict:
    """``{"hf": published keys as run, "serve": ..., "reference": ...}``."""
    doc = json.loads(pathlib.Path(path).read_text())
    hf = {k: v for k, v in doc.items() if k not in OWN_KEYS}
    serve = json.loads(json.dumps(doc["serve"]))
    if rehearsal:
        tiny = doc["rehearsal"]
        hf.update(tiny.get("hf", {}))
        serve["engine"].update(tiny.get("engine", {}))
        serve["model_overrides"] = {**serve.get("model_overrides", {}), **tiny.get("model_overrides", {})}
    return {"hf": hf, "serve": serve, "reference": doc["reference"], "name": pathlib.Path(path).stem}


def model_config(conf: dict):
    from dynamo_tpu.models.config import ModelConfig

    mc = ModelConfig.from_hf(dict(conf["hf"]), name=conf["name"])
    return dataclasses.replace(mc, **conf["serve"].get("model_overrides", {}))


def engine_config(conf: dict, card):
    """The program's own defaults (its environment cascade included) with the
    configuration's pinned sizes on top."""
    from dynamo_tpu.launch import WorkerSpec

    eng = dict(conf["serve"]["engine"])
    pool = eng.pop("pool_tokens")
    eng["num_pages"] = pool // eng["page_size"] + 1  # page 0 is the null page
    return WorkerSpec._engine_cfg(card, eng)


async def start(conf: dict, mc, params) -> dict:
    from dynamo_tpu import launch
    from dynamo_tpu.model_card import ModelDeploymentCard
    from dynamo_tpu.runtime.component import DistributedRuntime
    from dynamo_tpu.tokenizer import load_tokenizer

    eng = conf["serve"]["engine"]
    card = ModelDeploymentCard(
        name=conf["name"], tokenizer="byte", context_length=eng["max_seq_len"],
        kv_page_size=eng["page_size"], eos_token_ids=sorted(load_tokenizer("byte").eos_token_ids))
    spec = launch.WorkerSpec(model_config=mc, card=card, engine_config=engine_config(conf, card), params=params)
    runtime = DistributedRuntime.detached()
    service = await launch.serve_worker(runtime, spec)
    http, watcher, port = await launch.serve_frontend(runtime, host="127.0.0.1", port=0)
    return {"runtime": runtime, "services": [service], "http": http, "watcher": watcher, "port": port,
            "model": conf["name"], "base": f"http://127.0.0.1:{port}"}


async def wait_listed(handles: dict, timeout: float = 60.0) -> None:
    import aiohttp

    deadline = time.monotonic() + timeout
    async with aiohttp.ClientSession() as s:
        while time.monotonic() < deadline:
            async with s.get(handles["base"] + "/v1/models") as r:
                if r.status == 200 and handles["model"] in [m["id"] for m in (await r.json())["data"]]:
                    return
            await asyncio.sleep(0.05)
    raise RuntimeError("the frontend never listed the model")


async def stop(handles: dict) -> None:
    from dynamo_tpu import launch

    await asyncio.wait_for(launch.stop_local(handles), timeout=60)


# -- warm-up --------------------------------------------------------------------


def _pow2_upto(n: int) -> list[int]:
    top = 1 << max(0, math.ceil(math.log2(max(1, n))))
    return [1 << i for i in range(top.bit_length())]


def warm_shapes(conf: dict, warm: dict) -> list[tuple[int, int, int]]:
    """(rows, tokens per row, pages per row) of every step program the cell's
    traffic can reach: rows and pages in the runner's power-of-two buckets, the
    time axis 1 (decode) or one chunk (a mixed step)."""
    eng = conf["serve"]["engine"]
    rows = _pow2_upto(min(int(warm["max_rows"]), eng["max_batch_size"]))
    pages = _pow2_upto(math.ceil(int(warm["max_context_tokens"]) / eng["page_size"]))
    return [(b, t, n) for t in (1, eng["chunk_prefill_tokens"]) for b in rows for n in pages]


def null_batch(b: int, t: int, n: int):
    """A step in which every row is padding: it reads and writes the null page."""
    from dynamo_tpu.engine.runner import StepBatch

    z = lambda *s: np.zeros(s, np.int32)  # noqa: E731
    f = lambda *s: np.zeros(s, np.float32)  # noqa: E731
    return StepBatch(tokens=z(b, t), positions=z(b, t), block_tables=z(b, n), slot_mapping=z(b, t),
                     last_token_index=z(b), temperature=f(b), top_k=z(b), top_p=np.ones(b, np.float32),
                     seeds=np.zeros(b, np.uint32), sample_steps=z(b), freq_pen=f(b), pres_pen=f(b),
                     pos_limit=z(b), history=np.full((b, 1), -1, np.int32), mrope_delta=z(b),
                     num_new=np.zeros(b, np.int32))


def warm_up(core, shapes, report) -> None:
    """Run each program once, through the runner's own dispatch (so its
    compile tracker has seen the shape before the window opens)."""
    runner = core.runner
    for b, t, n in shapes:
        t0 = time.perf_counter()
        runner.step(null_batch(b, t, n))
        report(b, t, n, time.perf_counter() - t0)


# -- counters -------------------------------------------------------------------


class CompileEvents:
    """JAX's own compile events (``jax.monitoring``): what really compiled."""

    def __init__(self) -> None:
        import jax

        self.backend_compiles = 0
        self.backend_compile_s = 0.0
        self.cache_hits = 0
        self.cache_misses = 0
        jax.monitoring.register_event_listener(self._event)
        jax.monitoring.register_event_duration_secs_listener(self._duration)

    def _event(self, event: str, **_kw) -> None:
        if event == "/jax/compilation_cache/cache_hits":
            self.cache_hits += 1
        elif event == "/jax/compilation_cache/cache_misses":
            self.cache_misses += 1

    def _duration(self, event: str, seconds: float, **_kw) -> None:
        if event == "/jax/core/compile/backend_compile_duration":
            self.backend_compiles += 1
            self.backend_compile_s += seconds

    def snapshot(self) -> dict:
        return dict(vars(self))


def counters(core, compiles: CompileEvents) -> dict:
    """What the program counts, read at one instant."""
    steps = core.flight.snapshot(kind="step")
    return {
        "flight_seq": steps[-1]["seq"] if steps else -1,
        "tracker_events": len(core.runner.compile_tracker.events()),
        "compiles": compiles.snapshot(),
        "attn_dispatch": {f"{ph}:{path}": n for (ph, path), n in core.attn_dispatch_counts.items()},
    }


def window_counters(core, compiles: CompileEvents, before: dict, after: dict) -> dict:
    ring = core.flight.snapshot()
    steps = [r for r in ring if r["kind"] == "step" and before["flight_seq"] < r["seq"] <= after["flight_seq"]]
    lost = bool(ring) and ring[0]["seq"] > before["flight_seq"] + 1  # the ring wrapped inside the window
    disp = {k: after["attn_dispatch"].get(k, 0) - before["attn_dispatch"].get(k, 0)
            for k in after["attn_dispatch"]}
    return {
        "steps": steps, "steps_lost": lost,
        "tracker_new_shapes": core.runner.compile_tracker.events()[before["tracker_events"]: after["tracker_events"]],
        "backend_compiles": after["compiles"]["backend_compiles"] - before["compiles"]["backend_compiles"],
        "attn_dispatch": disp,
    }
