"""Mellum2's plain reference against the program at toy size, its decode-step
byte count against hand arithmetic, and the three per-layer readers the
configuration's cell adds (on made-up records and a made-up trace: what a
program without the new fields writes gives them nothing to read)."""

import functools
import json
import pathlib
import sys

import numpy as np
import pytest

ROOT = pathlib.Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))

NAME = "mellum2-12b-a2.5b-int8"
CELL = f"{NAME}.longctx-decode"
SLIDING, FULL = "sliding_attention", "full_attention"


def _conf(rehearsal=False):
    from benchmark import serving

    return serving.load_config(ROOT / "benchmark" / "configs" / f"{NAME}.json", rehearsal=rehearsal)


def _counts():
    from benchmark import plugins

    return plugins.load("kernel_counts", "moe_window_decode_step")


def test_yarn_frequencies_and_factor_are_the_programs():
    """The reference computes its own NTK-by-parts frequencies; the program's
    ``ops/rope.py`` computes them elsewhere: the two agree to float32."""
    from benchmark.reference import mellum2
    from dynamo_tpu.ops.rope import rope_attention_factor, rope_frequencies

    for kind in (SLIDING, FULL):
        p = _conf()["hf"]["rope_parameters"][kind]
        inv, factor = mellum2.inv_freq_of(128, p)
        scaling = {k: v for k, v in p.items() if k != "rope_theta"}
        assert np.allclose(inv, rope_frequencies(128, theta=p["rope_theta"], scaling=scaling), rtol=1e-6)
        assert factor == pytest.approx(rope_attention_factor(scaling))
    inv_s, _ = mellum2.inv_freq_of(128, _conf()["hf"]["rope_parameters"][SLIDING])
    inv_f, factor = mellum2.inv_freq_of(128, _conf()["hf"]["rope_parameters"][FULL])
    assert factor == pytest.approx(0.1 * np.log(16) + 1) and inv_f[0] == inv_s[0]
    assert inv_f[-1] == pytest.approx(inv_s[-1] / 16)  # the slowest dimension is fully interpolated


@pytest.mark.parametrize("last", [47, 30, 9])
def test_reference_matches_program_forward_past_the_window(last):
    """One whole-sequence call of the program (48 tokens, window 8, the toy
    4-layer model of the rehearsal) against the reference, at a position
    six windows in, one mid-way and one just past the first window; and the
    reference made wrong (window ignored, one RoPE) is far away."""
    import jax
    import jax.numpy as jnp

    from benchmark import serving, weights
    from benchmark.reference import mellum2
    from dynamo_tpu.models import llama

    conf = _conf(rehearsal=True)
    mc = serving.model_config(conf)
    params = weights.make_weights(mc, 2**31 + 13, quant="int8")
    t, ps = 48, 16
    tokens = np.random.default_rng(3).integers(1, mc.vocab_size, size=t).astype(np.int32)
    pos = np.arange(t, dtype=np.int32)
    tables = 1 + np.arange(t // ps, dtype=np.int32)[None]
    got, _, _ = llama.forward(
        params, mc, jnp.asarray(tokens[None]), jnp.asarray(pos[None]), *llama.init_kv_cache(mc, 1 + t // ps, ps),
        jnp.asarray(tables), jnp.asarray((tables[0][pos // ps] * ps + pos % ps)[None]),
        jnp.asarray([last], jnp.int32), attn_impl="reference")
    got = np.asarray(got[0])

    def ref(hf):
        return np.asarray(jax.jit(functools.partial(mellum2.forward, hf=hf))(params, tokens=jnp.asarray(tokens)))[last]

    hf = conf["hf"]
    want = ref(hf)
    scale = np.abs(want).max()
    assert np.abs(got - want).max() < 2e-4 * scale
    one_rope = {**hf, "rope_parameters": {k: hf["rope_parameters"][SLIDING] for k in (SLIDING, FULL)}}
    for wrong in ({**hf, "sliding_window": 10**6}, one_rope):
        assert np.abs(got - ref(wrong)).max() > 2e-2 * scale


def test_decode_step_bytes_by_hand():
    c, hf = _counts(), _conf()["hf"]
    got = c.decode_step(hf, rows=6, contexts_total=6 * 4500)
    touched = 64 * (1 - (1 - 8 / 64) ** 6)  # 35.28 experts a layer at 6 rows
    attn = 2304 * 4096 * 2 + 2304 * 512 * 2  # q and o; k and v at 4 KV heads of 128
    layer = attn + 2304 * 64 * 2 + touched * 3 * 2304 * 896
    cache = (7 * 6 * 4500 + 21 * 6 * 1024) * 2048  # full layers the contexts, sliding layers the window
    assert c.layer_counts(hf) == (7, 21)
    assert got["experts_touched"] == pytest.approx(touched) and got["cache_bytes"] == pytest.approx(cache)
    assert got["bytes"] == pytest.approx(28 * layer + 2304 * 98304 + 6 * 2304 * 2 + cache)
    assert 7.5e9 < got["bytes"] < 7.7e9  # ISSUE 26's 7.6 GB, 9.3 ms at the HBM peak
    per_token = 28 * (attn + 2304 * 64 + 8 * 3 * 2304 * 896) + 2304 * 98304
    assert got["flops"] == pytest.approx(2 * 6 * per_token + 4 * 32 * 128 * (7 * 27000 + 21 * 6144))
    # a context under the window is read whole by both kinds
    short = c.decode_step(hf, rows=2, contexts_total=2 * 300)
    assert short["cache_bytes"] == pytest.approx(28 * 600 * 2048)
    peaks = json.loads((ROOT / "benchmark" / "peaks.json").read_text())["TPU v5 lite"]
    assert c.least_seconds(got, peaks) == (pytest.approx(got["bytes"] / 819e9), "memory")


def test_attention_step_bytes_by_hand():
    c, hf = _counts(), _conf()["hf"]
    got = c.attention_step(hf, kv_tokens_full=27000, kv_tokens_window=6144, rows=6)
    assert got["cache_bytes"] == pytest.approx((7 * 27000 + 21 * 6144) * 2048)
    assert got["bytes"] == pytest.approx(got["cache_bytes"] + 28 * 6 * 4096 * 2 * 2)
    chunk = c.attention_step(hf, kv_tokens_full=4000, kv_tokens_window=1024 + 63, rows=1, new_tokens=64)
    assert chunk["bytes"] == pytest.approx((7 * 4000 + 21 * 1087) * 2048 + 28 * 64 * 4096 * 2 * 2)
    assert chunk["flops"] == pytest.approx(4 * 32 * 128 * (64 * (7 * 4000 + 21 * 1087) - 28 * 64 * 63 / 2))


# -- the readers ------------------------------------------------------------------


def _reader(name):
    from benchmark import plugins

    return plugins.load("layer_metrics", name).read


def _step(kind, seq, *, kv=None, traced=True, rows=6, chunk=0):
    rec = {"kind": "step", "seq": seq, "step_kind": kind, "decode_rows": rows, "chunk_rows": 1 if chunk else 0,
           "chunk_tokens": chunk, "traced": traced, "ann_ns": 1000 + seq, "t0_ns": 900 + seq}
    if kv:
        rec.update(kv_tokens_full=kv[0], kv_tokens_window=kv[1])
    return rec


def _ctx(steps, trace=None):
    return {"conf": _conf(), "peaks": {"hbm_bytes_per_s": 819e9, "bf16_flops_per_s": 197e12},
            "window": {"steps": steps}, "trace": trace, "notes": {}}


def test_window_kv_skipped_pct_from_the_step_records():
    read = _reader("engine.window_kv_skipped_pct")
    steps = [_step("decode", 1, kv=(27000, 6144)), _step("decode", 2, kv=(27000, 6144)),
             _step("mixed", 3, kv=(9000, 900), chunk=64)]  # mixed steps are not counted
    assert read(_ctx(steps)) == pytest.approx(100 * (1 - (7 * 27000 + 21 * 6144) / (28 * 27000)))
    assert read(_ctx([_step("decode", 1)])) is None  # a program from before the fields
    assert read(_ctx([])) is None


def _trace(programs):
    """One device plane and one host line: per program (annotation name, start,
    dur, [(op name, offset, dur), ...]) in ns."""
    mods = [["jit__step(1)", s, d] for _, s, d, _ in programs]
    ops = [[name, s + off, dur] for _, s, _, evs in programs for name, off, dur in evs]
    anns = [[name, s - 50, d + 100] for name, s, d, _ in programs]
    return {"planes": [{"name": "/device:TPU:0", "lines": [{"name": "XLA Modules", "events": mods},
                                                           {"name": "XLA Ops", "events": ops}]},
                       {"name": "/host:CPU", "lines": [{"name": "exec", "events": anns}]}]}


def test_attention_roofline_readers_pair_each_traced_step_with_its_program():
    c, hf = _counts(), _conf()["hf"]
    dec, pre = _reader("kernels.attn_decode_roofline_pct"), _reader("kernels.attn_prefill_roofline_pct")
    need_dec = c.attention_step(hf, kv_tokens_full=27000, kv_tokens_window=6144, rows=1, new_tokens=6)["bytes"]
    need_mix = c.attention_step(hf, kv_tokens_full=30000, kv_tokens_window=7000, rows=1, new_tokens=6 + 64)["bytes"]
    least_dec, least_mix = need_dec / 819e9 * 1e9, need_mix / 819e9 * 1e9  # ns
    programs = [
        ("engine.decode", 10_000, 2_000_000, [("paged_decode_attention.11", 100 + 50_000 * i, least_dec / 28 * 2)
                                              for i in range(28)] + [("fusion.3", 5, 20)]),
        ("engine.mixed", 3_000_000, 9_000_000, [("paged_prefill_attention.7", 100 + 200_000 * i, least_mix / 28 * 4)
                                                for i in range(28)]),
        ("engine.decode", 13_000_000, 2_000_000, [("paged_decode_attention.11", 100 + 50_000 * i, least_dec / 28 * 2)
                                                  for i in range(28)]),
    ]
    steps = [_step("decode", 1, kv=(27000, 6144)), _step("mixed", 2, kv=(30000, 7000), chunk=64),
             _step("decode", 3, kv=(27000, 6144)), _step("decode", 4, kv=(27000, 6144), traced=False)]
    ctx = _ctx(steps, _trace(programs))
    assert dec(ctx) == pytest.approx(50.0) and pre(ctx) == pytest.approx(25.0)
    assert ctx["notes"]["attn_decode_roofline"]["steps"] == 2 and ctx["notes"]["attn_decode_roofline"]["events"] == 28
    # nothing to read: no trace; a program without the fields; counts without attention_step
    assert dec(_ctx(steps)) is None
    assert dec(_ctx([_step("decode", 1), _step("mixed", 2, chunk=64), _step("decode", 3)], _trace(programs))) is None
    olmoe = {**ctx, "conf": {**ctx["conf"], "serve": {**ctx["conf"]["serve"], "kernel_counts": "moe_decode_step"}}}
    assert dec(olmoe) is None and pre(olmoe) is None


def test_the_cell_lists_what_the_issue_names():
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    cell = next(w for w in bench["workloads"] if w["name"] == CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == (NAME, "longctx-decode", 1)
    assert CELL in next(m for m in bench["end_to_end"] if m["name"] == "out_tok_s")["workloads"]
    new = {m["name"]: m for m in bench["per_layer"] if m.get("workloads") == [CELL]}
    assert set(new) == {"engine.window_kv_skipped_pct", "kernels.attn_decode_roofline_pct",
                        "kernels.attn_prefill_roofline_pct"}
    assert new["kernels.attn_prefill_roofline_pct"]["moves"] == "out_tok_s"
    from benchmark import traffic

    mix = traffic.load_mix(ROOT / "benchmark" / "traffic" / "longctx-decode.json",
                           ROOT / "benchmark" / "cells" / f"{CELL}.json")
    rows = mix["lengths_per_100"]
    prompts, outs = sorted(r[0] for r in rows), sorted(r[1] for r in rows)
    assert (prompts[0], prompts[-1], (prompts[49] + prompts[50]) / 2) == (2048, 5120, 3584)
    assert (outs[0], outs[-1], (outs[49] + outs[50]) / 2) == (768, 1280, 1024) and all(p % 64 == 0 for p in prompts)
    assert mix["clients"] == 6 and mix["warm"] == {"max_rows": 6, "max_context_tokens": 6400}
    for n in (6, 12, 24, 48):  # every prefix of the table is balanced
        assert abs(sum(r[0] for r in rows[:n]) / n - 3584) < 0.06 * 3584
        assert abs(sum(r[1] for r in rows[:n]) / n - 1024) < 0.06 * 1024
    conf = _conf()
    pool = conf["serve"]["engine"]["pool_tokens"]
    assert mix["clients"] * mix["warm"]["max_context_tokens"] <= pool  # no preemption
