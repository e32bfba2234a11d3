"""LongCat-Flash's configuration in the benchmark: its plain reference against
the program's whole forward at the rehearsal's toy size, its decode-step byte
count against hand arithmetic, the three per-layer readers its cell adds (on
made-up records and a made-up trace: what a program without the new fields
writes gives them nothing to read), and the cell's traffic table."""

import functools
import json
import pathlib
import statistics
import sys

import numpy as np
import pytest

ROOT = pathlib.Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))

NAME = "longcat-flash-chat-ep32-int8"
CELL = f"{NAME}.reason-saturated"
BENCH = json.loads((ROOT / "BENCHMARK.json").read_text())
MB = 1e6


def _conf(rehearsal=False):
    from benchmark import serving

    return serving.load_config(ROOT / "benchmark" / "configs" / f"{NAME}.json", rehearsal=rehearsal)


def _counts():
    from benchmark import plugins

    return plugins.load("kernel_counts", "longcat_flash_decode_step")


# -- the reference ------------------------------------------------------------------


@pytest.mark.parametrize("last", [47, 20, 3])
def test_reference_matches_program_forward_at_the_rehearsal_size(last):
    """One whole-sequence call of the program (48 tokens, the toy model of the
    rehearsal: 2 double layers, 8 of 16 experts held + 8 identities, top-4)
    against the reference, which imports nothing of the program: un-absorbed
    MLA against absorbed, one expert at a time against sorted rows. float32
    both sides at ``highest`` precision: 1e-4 of the logit range."""
    import jax
    import jax.numpy as jnp

    from benchmark import serving, weights
    from benchmark.reference import longcat_flash
    from dynamo_tpu.models import llama

    conf = _conf(rehearsal=True)
    cfg = serving.model_config(conf)
    assert cfg.dtype == "float32" and (cfg.num_layers, cfg.num_experts, cfg.routed_experts, cfg.router_outputs) == (2, 8, 16, 24)
    params = weights.make_weights(cfg, 2**31 + 11, quant="")
    toks = np.random.default_rng(3).integers(1, cfg.vocab_size, size=48)
    k, v = llama.init_kv_cache(cfg, 5, 16)
    got = llama.forward(params, cfg, jnp.asarray(toks)[None], jnp.arange(48)[None], k, v, jnp.asarray([[1, 2, 3]]),
                        (16 + jnp.arange(48))[None], jnp.asarray([last]))[0][0]
    want = np.asarray(jax.jit(functools.partial(longcat_flash.forward, hf=conf["hf"]))(params, tokens=jnp.asarray(toks)))
    assert np.abs(np.asarray(got) - want[last]).max() < 1e-4 * np.abs(want).max()


def test_reference_reads_the_served_int8_leaves_and_refuses_what_it_does_not_know():
    import jax
    import jax.numpy as jnp

    from benchmark import serving, weights
    from benchmark.reference import longcat_flash

    conf = _conf(rehearsal=True)
    cfg = serving.model_config(conf)
    params = weights.make_weights(cfg, 5, quant="int8")
    assert params["layers"]["sub1"]["w_down"]["qw"].dtype == jnp.int8 and params["layers"]["w_up"]["qw"].shape[:2] == (2, 8)
    assert params["layers"]["sub0"]["w_uk"].ndim == 4 and not isinstance(params["layers"]["router"], dict)
    assert float(jnp.abs(params["layers"]["router_bias"]).max()) == 0.0  # the benchmark's weights leave the bias out
    logits = jax.jit(functools.partial(longcat_flash.forward, hf=conf["hf"]))(params, tokens=jnp.arange(1, 17))
    assert logits.shape == (16, cfg.vocab_size) and bool(jnp.isfinite(logits).all())
    for edit in ({"zero_expert_type": "copy"}, {"attention_method": "GQA"}):
        with pytest.raises(ValueError, match="MLA attention and identity zero experts only"):
            longcat_flash.shape_of({**conf["hf"], **edit})


# -- the needed bytes and operations, by hand -------------------------------------------


def test_decode_step_bytes_by_hand():
    """ISSUE 34's arithmetic: 660 MB a layer outside the experts, 37.75 MB an
    expert, 10.2 of 16 held experts touched at 64 rows, 1,152 cache bytes a
    token a sublayer, a 101 MB head: about 8.3 GB, 10.1 ms at the HBM peak."""
    c, hf = _counts(), _conf()["hf"]
    got = c.decode_step(hf, rows=64, contexts_total=64 * 848)
    mla_int8 = 6144 * 1536 + 1536 * 64 * 192 + 6144 * 576 + 8192 * 6144
    mla_bf16 = 2 * 512 * 64 * 128
    assert mla_int8 == pytest.approx(82.2e6, rel=1e-3) and mla_int8 + 2 * mla_bf16 == pytest.approx(99.0 * MB, rel=1e-3)
    dense = 3 * 6144 * 12288
    outside = 2 * (mla_int8 + 2 * mla_bf16 + dense) + 6144 * 768 * 2
    assert got["outside_experts_bytes_per_layer"] == outside and outside == pytest.approx(660 * MB, rel=2e-3)
    touched = 16 * (1 - (1 - 12 / 768) ** 64)
    assert got["experts_touched"] == pytest.approx(touched) and touched == pytest.approx(10.2, abs=0.05)
    expert = 3 * 6144 * 2048
    assert expert == pytest.approx(37.75 * MB, rel=1e-3)
    cache = 14 * 64 * 848 * 1152
    assert got["cache_bytes"] == cache
    assert got["bytes"] == pytest.approx(7 * (outside + touched * expert) + 6144 * 16384 + 64 * 6144 * 2 + cache)
    assert 8.2e9 < got["bytes"] < 8.5e9
    peaks = json.loads((ROOT / "benchmark" / "peaks.json").read_text())["TPU v5 lite"]
    least, bound = c.least_seconds(got, peaks)
    assert bound == "memory" and 10.0e-3 < least < 10.4e-3
    # a count handed in takes the formula's place; bf16 weights double the matmul leaves only
    assert c.decode_step(hf, rows=64, contexts_total=0, experts_touched=16.0)["bytes"] == pytest.approx(
        7 * (outside + 16 * expert) + 6144 * 16384 + 64 * 6144 * 2)
    wide = c.decode_step(hf, rows=1, contexts_total=0, weight_bytes=2.0, experts_touched=0.0)
    assert wide["bytes"] == pytest.approx(7 * (2 * (2 * mla_int8 + 2 * mla_bf16 + 2 * dense) + 6144 * 768 * 2)
                                          + 2 * 6144 * 16384 + 6144 * 2)
    # operations, absorbed form: 2 a weight a token meets, 12 * 16 / 768 expert FFNs a token
    per_token = 7 * (2 * (mla_int8 + mla_bf16 + dense) + 6144 * 768 + 0.25 * expert) + 6144 * 16384
    assert got["flops"] == pytest.approx(2 * 64 * per_token + 14 * 2 * 64 * (2 * 512 + 64) * 64 * 848)


def test_attention_step_bytes_by_hand():
    c, hf = _counts(), _conf()["hf"]
    got = c.attention_step(hf, kv_tokens_full=54272, kv_tokens_window=0, rows=64)
    assert got["cache_bytes"] == 14 * 54272 * 576 * 2
    assert got["bytes"] == got["cache_bytes"] + 14 * 64 * 64 * (512 + 64 + 512) * 2
    chunk = c.attention_step(hf, kv_tokens_full=2000, rows=1, new_tokens=64)
    assert chunk["bytes"] == 14 * (2000 * 1152 + 64 * 64 * 1088 * 2)
    assert chunk["flops"] == pytest.approx(14 * 2 * 64 * 1088 * (64 * 2000 - 64 * 63 / 2))


def test_decode_roofline_reader_takes_these_counts():
    """``kernels.decode_roofline_pct`` (a file of the accepted benchmark) loads
    the configuration's counts by name and calls ``decode_step`` with what it
    has: rows, contexts, weight bytes. It has no experts count to hand in, so
    the even-routing formula stands (PERF.md section 7)."""
    from benchmark import plugins

    read = plugins.load("layer_metrics", "kernels.decode_roofline_pct").read
    steps = [{"step_kind": "decode", "decode_rows": 64}] * 3
    ctx = {"conf": _conf(), "peaks": {"hbm_bytes_per_s": 819e9, "bf16_flops_per_s": 197e12}, "trace": {}, "notes": {},
           "window": {"steps": steps}, "mean_context_tokens": 848.0,
           "step_programs": [{"span": "engine.decode", "dur": 14e6}] * 3}
    need = _counts().decode_step(_conf()["hf"], rows=64, contexts_total=64 * 848)["bytes"]
    assert read(ctx) == pytest.approx(100 * need / 819e9 / 14e-3)
    assert ctx["notes"]["decode_roofline"]["bound"] == "memory"


# -- the readers ------------------------------------------------------------------


def _reader(name):
    from benchmark import plugins

    return plugins.load("layer_metrics", name).read


def _step(kind, seq, *, kv=None, moe=None, traced=True, rows=64, chunk=0):
    rec = {"kind": "step", "seq": seq, "step_kind": kind, "decode_rows": rows, "chunk_rows": 1 if chunk else 0,
           "chunk_tokens": chunk, "traced": traced, "ann_ns": 1000 + seq, "t0_ns": 900 + seq}
    if kv is not None:
        rec.update(kv_tokens_full=kv, kv_tokens_window=0)
    if moe is not None:
        rec.update(zip(("moe_choices", "moe_choices_zero", "moe_choices_held", "moe_experts_touched"), moe))
    return rec


def _ctx(steps, trace=None):
    return {"conf": _conf(), "peaks": {"hbm_bytes_per_s": 819e9, "bf16_flops_per_s": 197e12},
            "window": {"steps": steps}, "trace": trace, "notes": {}}


def test_choice_share_readers_sum_the_decode_steps_counts():
    zero, held = _reader("engine.moe_zero_choice_pct"), _reader("engine.moe_held_choice_pct")
    steps = [_step("decode", 1, moe=(5376, 1800, 110, 70)), _step("decode", 2, moe=(5376, 1784, 114, 72)),
             _step("mixed", 3, moe=(10752, 9000, 9000, 112), chunk=64),  # mixed steps are not counted
             _step("decode", 4, moe=(0, 0, 0, 0))]  # a step that dispatched nothing, or whose counts came a step later
    ctx = _ctx(steps)
    assert zero(ctx) == pytest.approx(100 * 3584 / 10752) and held(ctx) == pytest.approx(100 * 224 / 10752)
    assert ctx["notes"]["moe_held"] == {"steps": 2, "experts_touched_per_layer": pytest.approx(71 / 7)}
    # nothing to read: a program from before the fields (the parent), a model whose counts stay 0, no step
    for empty in ([_step("decode", 1)], [_step("decode", 1, moe=(0, 0, 0, 0))], []):
        assert zero(_ctx(empty)) is None and held(_ctx(empty)) is None


def _trace(programs):
    """One device plane and one host line: per program (annotation name, start,
    dur, [(op name, offset, dur), ...]) in ns."""
    mods = [["jit__step(1)", s, d] for _, s, d, _ in programs]
    ops = [[name, s + off, dur] for _, s, _, evs in programs for name, off, dur in evs]
    anns = [[name, s - 50, d + 100] for name, s, d, _ in programs]
    return {"planes": [{"name": "/device:TPU:0", "lines": [{"name": "XLA Modules", "events": mods},
                                                           {"name": "XLA Ops", "events": ops}]},
                       {"name": "/host:CPU", "lines": [{"name": "exec", "events": anns}]}]}


def test_mla_decode_roofline_reader_pairs_each_traced_decode_step_with_its_program():
    c, hf = _counts(), _conf()["hf"]
    read = _reader("kernels.mla_decode_roofline_pct")
    need = c.attention_step(hf, kv_tokens_full=54272, kv_tokens_window=0, rows=1, new_tokens=64)["bytes"]
    least = need / 819e9 * 1e9  # ns
    kernel = [("mla_paged_decode_attention.3", 100 + 50_000 * i, least / 14 * 2.5) for i in range(14)]
    programs = [
        ("engine.decode", 10_000, 14_000_000, kernel + [("moe_grouped_matmul_int8.5", 5, 20)]),
        # a mixed step's kernel events (decode slots and chunk tiles) are another step kind's: not read here
        ("engine.mixed", 20_000_000, 16_000_000, [("mla_paged_decode_attention.3", 100 + 50_000 * i, 7.0) for i in range(28)]),
        ("engine.decode", 40_000_000, 14_000_000, kernel),
    ]
    steps = [_step("decode", 1, kv=54272), _step("mixed", 2, kv=56000, chunk=64), _step("decode", 3, kv=54272),
             _step("decode", 4, kv=54272, traced=False)]
    ctx = _ctx(steps, _trace(programs))
    assert read(ctx) == pytest.approx(40.0)
    assert ctx["notes"]["mla_decode_roofline"]["steps"] == 2 and ctx["notes"]["mla_decode_roofline"]["events"] == 14
    # nothing to read: no trace; a trace without the kernel (GQA attention's events are another kernel's)
    assert read(_ctx(steps)) is None
    other = [(n, s, d, [("paged_decode_attention.11", o, t) for _, o, t in evs]) for n, s, d, evs in programs]
    assert read(_ctx(steps, _trace(other))) is None
    assert _reader("kernels.attn_decode_roofline_pct")(ctx) is None  # and the GQA reader finds nothing in an MLA trace


def test_new_metrics_are_this_cells_and_the_cell_is_the_last():
    assert BENCH["workloads"][-1]["name"] == CELL and BENCH["workloads"][-1]["chips"] == 1
    mine = {m["name"]: m for m in BENCH["per_layer"] if CELL in m.get("workloads", [])}
    assert set(mine) == {"engine.mixed_pad_pct", "kernels.mla_decode_roofline_pct", "engine.moe_zero_choice_pct",
                         "engine.moe_held_choice_pct"}
    assert mine["kernels.mla_decode_roofline_pct"]["source"] == "device_trace"
    assert all(mine[n]["source"] == "program_counter" and mine[n]["moves"] == "itl_p50_ms"
               for n in ("engine.moe_zero_choice_pct", "engine.moe_held_choice_pct"))
    e2e = {m["name"] for m in BENCH["end_to_end"] if CELL in m.get("workloads", [CELL])}
    assert e2e == {"itl_p50_ms", "out_tok_s", "setup_s"}
    entry = BENCH["configs"][-1]
    assert entry["name"] == NAME and entry["reduced"] == ["num_layers", "n_routed_experts", "vocab_size"]


# -- the traffic --------------------------------------------------------------------


def test_the_mixs_table_is_what_the_issue_names():
    from benchmark import traffic

    mix = traffic.load_mix(ROOT / "benchmark" / "traffic" / "reason-saturated.json",
                           ROOT / "benchmark" / "cells" / f"{CELL}.json")
    rows = mix["lengths_per_100"]
    prompts, outputs = [p for p, _ in rows], [o for _, o in rows]
    assert len(rows) == 100 and set(prompts) == {64, 128, 192, 256} and all(prompts.count(p) == 25 for p in set(prompts))
    assert (min(outputs), max(outputs), statistics.median(outputs)) == (1024, 1728, 1376)
    assert max(p + o for p, o in rows) <= 1984 <= mix["warm"]["max_context_tokens"] == 2048
    assert all(abs(statistics.mean(outputs[:n]) - 1376) < 30 for n in (8, 16, 32, 64))  # every prefix is balanced
    assert (mix["loop"], mix["clients"], mix["requests_per_client"], mix["lead_in_s"]) == ("closed", 64, 6, 8)
    assert mix["first_answer_share"] == [0.05, 1.0] and mix["schedule_seed"] == 34 and not mix["prefix_levels"]
    assert mix["warm"] == {"max_rows": 64, "max_context_tokens": 2048}
    eng = _conf()["serve"]["engine"]
    assert mix["clients"] * max(p + o for p, o in rows) <= eng["pool_tokens"]  # no preemption
    from benchmark import serving

    assert len(serving.warm_shapes(_conf(), mix["warm"])) == 70
    plan = traffic.generate(mix, seed=2**31 + 7, seconds=51.0, vocab=_conf()["hf"]["vocab_size"])
    assert len(plan["clients"]) == 64 and max(max(r["prompt"]) for r in plan["requests"]) < 16384
