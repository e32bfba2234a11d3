"""Phases of an engine step in the step's one flight record (ISSUE 24).

The contract: every STEP record carries the eleven phases; ``sched build
dispatch wait post`` tile its ``wall_ms`` and ``handoff route intake no_work
submit`` its ``gap_ms``; ``record`` (the telemetry tail of the step before)
rides in the next record as ``gap_ms`` does; with no device trace running no
``TraceAnnotation`` is built; with one running the phases open as
``phase.<name>`` (never ``engine.*``: the benchmark's reduction keeps those
for the step programs) inside the step's ``engine.*`` region, whose entry is
stamped as ``ann_ns``; and the marker changes no token.
"""

import pytest

from dynamo_tpu import tracing
from dynamo_tpu.engine.core import EngineConfig, EngineCore
from dynamo_tpu.engine.runner import ModelRunner
from dynamo_tpu.engine.service import JaxEngineService
from dynamo_tpu.models import llama
from dynamo_tpu.models.config import PRESETS
from dynamo_tpu.protocols.common import PreprocessedRequest, SamplingOptions, StopConditions
from dynamo_tpu.runtime.engine import Context

PAGE = 4
_RUNNER = []


def make_core(*, overlap=False, **cfg_kw):
    # The shapes of tests/test_overlap.py, so the compile cache is shared.
    if not _RUNNER:
        cfg = PRESETS["test-tiny"]
        _RUNNER.append(ModelRunner(cfg, llama.init_params(cfg, 0), num_pages=96, page_size=PAGE,
                                   max_batch_size=8, prefill_bucket=16, attn_impl="reference"))
    return EngineCore(_RUNNER[0], EngineConfig(
        num_pages=96, page_size=PAGE, max_batch_size=8, max_seq_len=256, chunk_prefill_tokens=16,
        overlap=overlap, **cfg_kw))


def request(i, prompt=20, out=10):
    return PreprocessedRequest(
        request_id=f"r{i}", token_ids=[(7 * i + j) % 200 + 3 for j in range(prompt)],
        sampling=SamplingOptions(temperature=0.0), stop=StopConditions(max_tokens=out, ignore_eos=True))


def drive(core, n_requests=3):
    tokens = {}
    for i in range(n_requests):
        tokens[core.add_request(request(i)).seq_id] = []
    while core.has_work:
        for seq, out in core.step():
            tokens[seq.seq_id].extend(out.token_ids)
    return tokens, core.flight.snapshot(kind="step")


class FakeAnnotation:
    """Stands in for jax.profiler.TraceAnnotation: logs every region's life."""

    log: list = []

    def __init__(self, name):
        self.name = name
        FakeAnnotation.log.append(("open", name))

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        FakeAnnotation.log.append(("close", self.name))


@pytest.fixture
def fake_annotation(monkeypatch):
    import jax

    FakeAnnotation.log = []
    monkeypatch.setattr(jax.profiler, "TraceAnnotation", FakeAnnotation)
    return FakeAnnotation


@pytest.mark.parametrize("overlap", [False, True], ids=["sync", "overlapped"])
def test_every_step_record_carries_the_eleven_phases(overlap):
    _, steps = drive(make_core(overlap=overlap))
    assert len(steps) >= 10
    for r in steps:
        assert tuple(r["phases_us"]) == tracing.PHASES and len(tracing.PHASES) == 11
        assert all(v >= 0 for v in r["phases_us"].values())
        assert r["t0_ns"] > 0 and r["traced"] is False and r["ann_ns"] == 0
    assert [b["t0_ns"] > a["t0_ns"] for a, b in zip(steps, steps[1:])] == [True] * (len(steps) - 1)
    # Every step scheduled and post-processed; all built and dispatched but the
    # pipeline's last ones, which only harvest what is in flight.
    for phase, least in (("sched", len(steps)), ("post", len(steps)), ("build", len(steps) - 3 * overlap),
                         ("dispatch", len(steps) - 3 * overlap), ("wait", len(steps) - 3)):
        assert sum(r["phases_us"][phase] > 0 for r in steps) >= least, phase
    # ``record`` is the tail of the step before: the first record has none, the rest do.
    assert steps[0]["phases_us"]["record"] == 0 and all(r["phases_us"]["record"] > 0 for r in steps[1:])


@pytest.mark.parametrize("overlap", [False, True], ids=["sync", "overlapped"])
def test_phases_sum_to_wall_and_gap(overlap):
    _, steps = drive(make_core(overlap=overlap))
    for r in steps:
        p = r["phases_us"]
        wall = sum(p[n] for n in tracing.STEP_PHASES if n != "record") / 1e3
        gap = sum(p[n] for n in tracing.GAP_PHASES) / 1e3
        # rounding: wall_ms and gap_ms to 1 us, each phase to 0.1 us
        assert wall == pytest.approx(r["wall_ms"], rel=0.01, abs=0.002)
        assert gap == pytest.approx(r["gap_ms"], rel=0.01, abs=0.002)
    assert steps[0]["gap_ms"] == 0 and all(r["gap_ms"] > 0 for r in steps[1:])


@pytest.mark.parametrize("overlap", [False, True], ids=["sync", "overlapped"])
def test_no_trace_annotation_is_built_while_no_trace_runs(overlap, fake_annotation):
    assert not tracing.trace_running()
    drive(make_core(overlap=overlap))
    assert fake_annotation.log == []


@pytest.mark.parametrize("overlap", [False, True], ids=["sync", "overlapped"])
def test_traced_steps_open_phase_regions_inside_the_engine_region(overlap, fake_annotation, monkeypatch):
    monkeypatch.setattr(tracing, "_annotating", True)
    _, steps = drive(make_core(overlap=overlap))
    log = fake_annotation.log
    opened = [n for ev, n in log if ev == "open"]
    engine = [n for n in opened if n.startswith("engine.")]
    # Named by what the step dispatches, in the pipelined loop as on the synchronous step:
    # the benchmark's trace readers select the step programs by these names.
    assert {"engine.decode"} <= set(engine) <= {"engine.decode", "engine.mixed", "engine.prefill"}
    kinds = {r["step_kind"]: n for r, n in zip([r for r in steps if r["traced"]], engine)}
    assert kinds["decode"] == "engine.decode"
    assert set(opened) - set(engine) == {f"phase.{p}" for p in tracing.STEP_PHASES}
    # One engine.* region per traced record, its entry stamped inside the step.
    traced = [r for r in steps if r["traced"]]
    assert len(traced) == len(engine) and len(traced) >= len(steps) - 2
    assert all(r["t0_ns"] < r["ann_ns"] <= r["t0_ns"] + r["wall_ms"] * 1e6 for r in traced)
    # Regions nest: whatever opens inside an engine.* region closes before it does.
    stack = []
    for ev, name in log:
        if ev == "open":
            if stack and stack[-1].startswith("phase."):
                pytest.fail(f"{name} opened inside {stack[-1]}")
            stack.append(name)
        else:
            assert stack and stack[-1] == name, (name, stack)
            stack.pop()
    assert stack == []  # the last step's ``record`` region closed at its end


def test_tokens_are_those_of_a_run_without_the_marker():
    class NoClock(tracing.StepClock):
        def mark(self, phase):
            return 0

    with_marker, _ = drive(make_core())
    core = make_core()
    core.clock = NoClock()
    core.runner.clock = None
    try:
        without, _ = drive(core)
    finally:
        core.runner.clock = None
    assert with_marker == without and all(len(t) == 10 for t in with_marker.values())


def test_a_step_that_only_drains_restarts_the_gap():
    core = make_core()
    drive(core, n_requests=1)
    before = len(core.flight.snapshot(kind="step"))
    assert core.step() == []  # nothing to do: no record, and the gap starts anew
    assert len(core.flight.snapshot(kind="step")) == before
    _, steps = drive(core, n_requests=1)
    first = steps[before]
    assert sum(first["phases_us"][n] for n in tracing.GAP_PHASES) / 1e3 == pytest.approx(first["gap_ms"], abs=0.002)
    assert first["phases_us"]["record"] == 0


def test_runner_marks_wait_only_inside_a_step():
    clock = tracing.StepClock()
    clock.mark_in_step(tracing.WAIT)  # a warm-up drives the runner between steps
    assert clock.ns == [0] * len(tracing.PHASES)
    clock.begin()
    clock.mark_in_step(tracing.WAIT)
    clock.mark(tracing.POST)
    assert clock.phases_us()["wait"] >= 0 and clock.ns[tracing.WAIT] > 0


async def test_the_service_splits_the_gap_into_its_five_parts():
    core = make_core()
    svc = JaxEngineService(core)
    try:
        async def run(i):
            return [t async for o in svc.generate(request(i).to_dict(), Context()) for t in o["token_ids"]]

        assert len(await run(0)) == 10
        assert len(await run(1)) == 10  # after a pause with nothing to run
    finally:
        await svc.close()
    steps = core.flight.snapshot(kind="step")
    for r in steps[1:]:
        p = r["phases_us"]
        assert sum(p[n] for n in tracing.GAP_PHASES) / 1e3 == pytest.approx(r["gap_ms"], rel=0.01, abs=0.002)
        assert p["handoff"] > 0 and p["route"] > 0 and p["intake"] > 0 and p["submit"] > 0
    # The second request came after the first had finished: its first step waited for it.
    assert sum(r["phases_us"]["no_work"] > 0 for r in steps) == 1


def test_named_scopes_reach_the_lowered_step_programs_op_names():
    """``jax.named_scope`` on the MoE stages, the attention and MLP blocks and
    the sampler: metadata only, read by a profile's op names."""
    import numpy as np

    from dynamo_tpu.engine.runner import _pack

    from dynamo_tpu.models.quant import init_params_quantized

    cfg = PRESETS["test-tiny-moe"]  # int8, as the benchmark serves it: ``moe.widen`` has ops to name
    runner = ModelRunner(cfg, init_params_quantized(cfg, 0, mode="int8"), num_pages=16, page_size=PAGE,
                         max_batch_size=2, prefill_bucket=16, attn_impl="reference")
    z = lambda *s: np.zeros(s, np.int32)  # noqa: E731
    f = lambda *s: np.zeros(s, np.float32)  # noqa: E731
    from dynamo_tpu.engine.runner import StepBatch

    b, t, n = 2, 1, 2
    batch = StepBatch(tokens=z(b, t), positions=z(b, t), block_tables=z(b, n), slot_mapping=z(b, t),
                      last_token_index=z(b), temperature=f(b), top_k=z(b), top_p=np.ones(b, np.float32),
                      seeds=np.zeros(b, np.uint32), sample_steps=z(b), freq_pen=f(b), pres_pen=f(b),
                      pos_limit=z(b), history=np.full((b, 1), -1, np.int32), mrope_delta=z(b),
                      num_new=np.ones(b, np.int32))
    padded = runner._pad(batch)
    bp, tp = padded.tokens.shape
    lowered = runner._step_packed_fn.lower(
        runner.params, runner.k_cache, runner.v_cache, _pack(padded), runner._chain_idle,
        b=bp, t=tp, n=padded.block_tables.shape[1], h=padded.history.shape[1], lp_k=0)
    import re

    parts = {part for name in re.findall(r'loc\("([^"]*)"', lowered.as_text(debug_info=True))
             for part in name.split("/")}
    assert {"attn", "mlp", "moe.router", "moe.widen", "moe.experts_gate_up", "moe.experts_down",
            "moe.combine", "sample"} <= parts


# -- the runner's dispatch report and the STEP record's keys (ISSUE 28) --------------


def _null_batch(b, t, n=4):
    from benchmark.serving import null_batch  # every row padding: shapes are all a dispatch site looks at

    return null_batch(b, t, n)


#: The runner's four dispatch sites: a call on a new bucket, the attention phase
#: it reports and the token positions of its padded rectangle.
SITES = {
    "step": (lambda r: r.step(_null_batch(2, 1)), "decode", 2),
    "spec_step": (lambda r: r.spec_step(_null_batch(2, 4), 3), "verify", 8),
    "step_async": (lambda r: r.step_async(_null_batch(2, 1)).result(), "decode", 2),
    "spec_step_async": (lambda r: r.spec_step_async(_null_batch(2, 4), 3).result(), "verify", 8),
}


def _runner(**kw):
    cfg = PRESETS["test-tiny"]
    return ModelRunner(cfg, llama.init_params(cfg, 0), num_pages=16, page_size=PAGE, max_batch_size=4,
                       prefill_bucket=4, attn_impl="reference", **kw)


def test_the_runners_step_programs_and_dispatch_sites_are_the_named_ones():
    """Every program a ``ModelRunner`` jits is found on it by attribute: the
    step programs are these six and the rest move pages or embed, so the next
    one is added on purpose (each is one more place a new mechanism is
    threaded through or refused in). The methods that dispatch are ``SITES``."""
    import inspect

    jitted = {name for name, v in vars(_runner()).items() if hasattr(v, "lower") and hasattr(v, "trace")}
    assert {n for n in jitted if "step" in n} == {
        "_step_fn", "_step_split_fn", "_step_packed_fn", "_step_chained_explicit_fn",
        "_spec_step_fn", "_spec_step_chained_fn"}
    assert {n for n in jitted if "step" not in n} == {
        "_write_page_fn", "_gather_pages_fn", "_scatter_pages_fn", "_embed_fn"}
    sites = {name for name, fn in vars(ModelRunner).items()
             if inspect.isfunction(fn) and "with self._dispatch(" in inspect.getsource(fn)}
    assert sites == set(SITES)


@pytest.mark.parametrize("site", SITES)
def test_a_new_bucket_is_traced_once(site, monkeypatch):
    """With no ``DYN_*`` set, the production dispatch path traces the model once
    per new bucket and starts no thread that lowers it again."""
    import os
    import threading

    for name in [n for n in os.environ if n.startswith("DYN_")]:
        monkeypatch.delenv(name)
    traces = []

    def forward(*args, **kwargs):
        traces.append(site)
        return llama.forward(*args, **kwargs)

    runner = _runner(forward_fn=forward)
    dispatch = SITES[site][0]
    dispatch(runner)
    assert len(traces) == 1
    dispatch(runner)  # the same bucket: the compiled program, no trace
    assert len(traces) == 1
    assert [t.name for t in threading.enumerate() if t.name == "dyn-cost-extract"] == []


@pytest.mark.parametrize("site", SITES)
def test_dispatch_report_is_taken_once(site):
    from dynamo_tpu.engine.runner import ROWS_X_T, DispatchReport

    dispatch, phase, tokens = SITES[site]
    runner = _runner()
    assert runner.take_dispatch() is None  # nothing dispatched yet
    dispatch(runner)
    report = runner.take_dispatch()
    assert report == DispatchReport(seconds=report.seconds, attn_phase=phase, attn_path="fallback", moe_path="",
                                    layout=ROWS_X_T, step_tokens=tokens, kv_tokens_full=0, kv_tokens_window=0)
    assert report.seconds > 0
    assert runner.take_dispatch() is None  # the take cleared it
    dispatch(runner)
    dispatch(runner)
    twice = runner.take_dispatch()  # seconds sum over a step's dispatches, of the labels the last stands
    assert twice.seconds > 0 and (twice.attn_phase, twice.step_tokens) == (phase, tokens)
    # An engine step that only harvests what is in flight dispatched nothing: no label in its record.
    _, steps = drive(make_core(overlap=True))
    harvests = [r for r in steps if not r["phases_us"]["dispatch"]]
    assert harvests and len(harvests) < len(steps)
    for r in steps:
        dispatched = r not in harvests
        assert bool(r["attn_phase"]) == bool(r["attn_path"]) == bool(r["layout"]) == dispatched, r
        assert (r["step_tokens"] > 0) == (r["dispatch_ms"] > 0) == dispatched and r["moe_path"] == ""


def _mock_core():
    from dynamo_tpu.mocker import build_mock_core

    return build_mock_core(realtime=False)


def _documented_step_keys():
    import pathlib
    import re

    doc = (pathlib.Path(__file__).resolve().parent.parent / "docs" / "OBSERVABILITY.md").read_text()
    table = doc.split("**Keys of a STEP record**")[1].split("- `compile`")[0]
    return re.findall(r"^\s*\| `(\w+)` \|", table, flags=re.M)


@pytest.mark.parametrize("make", [make_core, _mock_core], ids=["ModelRunner", "MockRunner"])
def test_step_record_keys(make):
    from dynamo_tpu.observability.flight import STEP_KEYS

    _, steps = drive(make())
    assert len(steps) >= 10 and len(set(STEP_KEYS)) == len(STEP_KEYS)
    for r in steps:
        assert tuple(r) == STEP_KEYS
    assert not {"hbm_bytes", "flops", "roofline_frac"} & set(STEP_KEYS)
    assert _documented_step_keys() == list(STEP_KEYS)


def test_step_record_holds_what_the_benchmark_reads():
    """The keys ``benchmark/program_spans.py`` and ``benchmark/layer_metrics/``
    read, with the types they hold today."""
    from dynamo_tpu.observability.flight import STEP_KEYS

    reads = {"phases_us": dict, "t0_ns": int, "ann_ns": int, "traced": bool, "decode_rows": int,
             "chunk_rows": int, "chunk_tokens": int, "step_tokens": int, "layout": str, "moe_path": str,
             "kv_tokens_full": int, "kv_tokens_window": int, "attn_phase": str, "attn_path": str,
             "wall_ms": float, "gap_ms": float}
    assert set(reads) <= set(STEP_KEYS)
    _, steps = drive(make_core())
    for r in steps:
        assert {k: type(r[k]) for k in reads} == reads
    assert {r["layout"] for r in steps} <= {"rows_x_t", "split"} and all(r["step_tokens"] > 0 for r in steps)
