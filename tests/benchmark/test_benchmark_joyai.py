"""JoyAI-LLM-Flash's configuration in the benchmark: its plain reference against
the program's whole forward at the rehearsal's toy size, its decode-step byte
count against hand arithmetic at the published sizes, the per-layer reader its
cell adds (on made-up records and a made-up trace: what a program without the
counters writes gives it nothing to read), and the cell's entries."""

import functools
import json
import pathlib
import sys

import numpy as np
import pytest

ROOT = pathlib.Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))

NAME = "joyai-llm-flash-ep8-int8"
CELL = f"{NAME}.reason-saturated"
BENCH = json.loads((ROOT / "BENCHMARK.json").read_text())
MB = 1e6
PEAKS = {"hbm_bytes_per_s": 819e9, "bf16_flops_per_s": 197e12}


def _conf(rehearsal=False):
    from benchmark import serving

    return serving.load_config(ROOT / "benchmark" / "configs" / f"{NAME}.json", rehearsal=rehearsal)


def _counts():
    from benchmark import plugins

    return plugins.load("kernel_counts", "joyai_flash_decode_step")


def _reader(name):
    from benchmark import plugins

    return plugins.load("layer_metrics", name).read


# -- the reference ------------------------------------------------------------------


@pytest.mark.parametrize("last", [47, 20, 3])
def test_reference_matches_program_forward_at_the_rehearsal_size(last):
    """One whole-sequence call of the program (48 tokens, the toy model of the
    rehearsal: a dense layer and two MoE layers, 8 of 32 experts held, rank 1,
    top-4, a shared expert) against the reference, which imports nothing of
    the program: un-absorbed MLA against absorbed, one expert at a time
    against sorted rows. float32 both sides at ``highest`` precision: 1e-4 of
    the logit range."""
    import jax
    import jax.numpy as jnp

    from benchmark import serving, weights
    from benchmark.reference import joyai_llm_flash
    from dynamo_tpu.models import llama

    conf = _conf(rehearsal=True)
    cfg = serving.model_config(conf)
    assert cfg.dtype == "float32" and (cfg.num_layers, cfg.first_k_dense, cfg.num_experts, cfg.routed_experts) == (3, 1, 8, 32)
    assert cfg.moe_expert_first == 8 and cfg.shared_expert_size == 32 and cfg.moe_held_share
    params = weights.make_weights(cfg, 2**31 + 11, quant="")
    toks = np.random.default_rng(3).integers(1, cfg.vocab_size, size=48)
    k, v = llama.init_kv_cache(cfg, 5, 16)
    got = llama.forward(params, cfg, jnp.asarray(toks)[None], jnp.arange(48)[None], k, v, jnp.asarray([[1, 2, 3]]),
                        (16 + jnp.arange(48))[None], jnp.asarray([last]))[0][0]
    want = np.asarray(jax.jit(functools.partial(joyai_llm_flash.forward, hf=conf["hf"]))(params, tokens=jnp.asarray(toks)))
    assert np.abs(np.asarray(got) - want[last]).max() < 1e-4 * np.abs(want).max()


def test_reference_reads_the_served_int8_leaves_and_refuses_what_it_does_not_know():
    import jax
    import jax.numpy as jnp

    from benchmark import serving, weights
    from benchmark.reference import joyai_llm_flash

    conf = _conf(rehearsal=True)
    cfg = serving.model_config(conf)
    params = weights.make_weights(cfg, 5, quant="int8")
    layers = params["layers"]
    assert layers["w_shared_down"]["qw"].dtype == jnp.int8 and layers["w_up"]["qw"].shape[:2] == (2, 8)
    assert params["dense_layers"]["w_gate"]["qw"].shape == (1, 64, 128) and layers["w_uk"].ndim == 4
    assert not isinstance(layers["router"], dict) and layers["router"].shape == (2, 64, 32)
    assert float(jnp.abs(layers["router_bias"]).max()) == 0.0  # the benchmark's weights leave the bias out
    logits = jax.jit(functools.partial(joyai_llm_flash.forward, hf=conf["hf"]))(params, tokens=jnp.arange(1, 17))
    assert logits.shape == (16, cfg.vocab_size) and bool(jnp.isfinite(logits).all())
    for edit, says in (({"scoring_func": "softmax"}, "sigmoid scores"), ({"topk_method": "greedy"}, "noaux_tc"),
                       ({"n_group": 8, "topk_group": 4}, "one routing group"), ({"moe_layer_freq": 2}, "one routing group")):
        with pytest.raises(ValueError, match=says):
            joyai_llm_flash.shape_of({**conf["hf"], **edit})
    import benchmark.reference.joyai_llm_flash as mod

    assert "dynamo_tpu" not in pathlib.Path(mod.__file__).read_text()  # nothing of the program


# -- the needed bytes and operations, by hand -------------------------------------------


def test_decode_step_bytes_by_hand():
    """ISSUE 36's arithmetic at the published sizes: 36.3 MB a MoE layer
    outside the routed experts, 4.72 MB an expert, 27.8 of 32 held experts
    touched at 64 rows, 1,152 cache bytes a token a layer, a 264.8 MB head:
    about 9.4 GB, 11.5 ms at the HBM peak."""
    c, hf = _counts(), _conf()["hf"]
    got = c.decode_step(hf, rows=64, contexts_total=64 * 870)
    mla_int8 = 2048 * 1536 + 1536 * 32 * 192 + 2048 * 576 + 4096 * 2048
    mla_bf16 = 2 * 512 * 32 * 128
    assert mla_int8 == pytest.approx(22.15 * MB, rel=1e-3) and 2 * mla_bf16 == pytest.approx(8.39 * MB, rel=1e-3)
    expert = 3 * 2048 * 768
    assert expert == pytest.approx(4.72 * MB, rel=1e-3) and 32 * expert == pytest.approx(151.0 * MB, rel=1e-3)
    outside = mla_int8 + 2 * mla_bf16 + expert + 2048 * 256 * 2  # the shared expert is one expert's size
    assert got["outside_experts_bytes_per_moe_layer"] == outside and outside == pytest.approx(36.3 * MB, rel=2e-3)
    dense_layer = mla_int8 + 2 * mla_bf16 + 3 * 2048 * 7168
    assert got["dense_layer_bytes"] == dense_layer and dense_layer == pytest.approx(74.6 * MB, rel=1e-3)
    touched = 32 * (1 - (1 - 8 / 256) ** 64)
    assert got["experts_touched"] == pytest.approx(touched) and touched == pytest.approx(27.8, abs=0.05)
    assert got["experts_bytes"] == pytest.approx(39 * touched * expert) and got["experts_bytes"] == pytest.approx(5.12e9, rel=2e-3)
    cache = 40 * 64 * 870 * 1152
    assert got["cache_bytes"] == cache and cache == pytest.approx(2.57e9, rel=2e-3)
    head = 2048 * 129280
    assert head == pytest.approx(264.8 * MB, rel=1e-3)
    assert got["bytes"] == pytest.approx(39 * outside + dense_layer + 39 * touched * expert + head + 64 * 2048 * 2 + cache)
    assert 39 * outside + dense_layer + head == pytest.approx(1.75e9, rel=5e-3)
    assert 9.3e9 < got["bytes"] < 9.6e9
    peaks = json.loads((ROOT / "benchmark" / "peaks.json").read_text())["TPU v5 lite"]
    least, bound = c.least_seconds(got, peaks)
    assert bound == "memory" and 11.3e-3 < least < 11.7e-3
    # a count handed in takes the formula's place; bf16 weights double the matmul leaves only
    assert c.decode_step(hf, rows=64, contexts_total=0, experts_touched=32.0)["bytes"] == pytest.approx(
        39 * (outside + 32 * expert) + dense_layer + head + 64 * 2048 * 2)
    wide = c.decode_step(hf, rows=1, contexts_total=0, weight_bytes=2.0, experts_touched=0.0)
    assert wide["bytes"] == pytest.approx(39 * (2 * mla_int8 + 2 * mla_bf16 + 2 * expert + 2048 * 256 * 2)
                                          + 2 * mla_int8 + 2 * mla_bf16 + 2 * 3 * 2048 * 7168 + 2 * head + 2048 * 2)
    # operations, absorbed form: 2 a weight a token meets, 8 * 32 / 256 = 1 routed expert FFN a token a MoE layer
    per_token = 40 * (mla_int8 + mla_bf16) + 3 * 2048 * 7168 + 39 * (expert + 2048 * 256 + expert) + head
    assert got["flops"] == pytest.approx(2 * 64 * per_token + 40 * 2 * 32 * (2 * 512 + 64) * 64 * 870)


def test_attention_and_experts_step_bytes_by_hand():
    c, hf = _counts(), _conf()["hf"]
    got = c.attention_step(hf, kv_tokens_full=55680, kv_tokens_window=0, rows=64)
    assert got["cache_bytes"] == 40 * 55680 * 576 * 2
    assert got["bytes"] == got["cache_bytes"] + 40 * 64 * 32 * (512 + 64 + 512) * 2
    chunk = c.attention_step(hf, kv_tokens_full=2000, rows=1, new_tokens=64)
    assert chunk["bytes"] == 40 * (2000 * 1152 + 64 * 32 * 1088 * 2)
    assert chunk["flops"] == pytest.approx(40 * 2 * 32 * 1088 * (64 * 2000 - 64 * 63 / 2))
    experts = c.experts_step(hf, experts_touched_total=1084)
    assert experts == {"bytes": 1084 * 3 * 2048 * 768, "choices_per_token": 8 * 39}
    assert c.experts_step(hf, experts_touched_total=10, weight_bytes=2.0)["bytes"] == 2 * 10 * 3 * 2048 * 768


def test_decode_roofline_reader_takes_these_counts():
    """``kernels.decode_roofline_pct`` (a file of the accepted benchmark) loads
    the configuration's counts by name; it has no experts count to hand in, so
    the even-routing formula stands (PERF.md section 7)."""
    read = _reader("kernels.decode_roofline_pct")
    steps = [{"step_kind": "decode", "decode_rows": 64}] * 3
    ctx = {"conf": _conf(), "peaks": PEAKS, "trace": {}, "notes": {}, "window": {"steps": steps},
           "mean_context_tokens": 870.0, "step_programs": [{"span": "engine.decode", "dur": 18e6}] * 3}
    need = _counts().decode_step(_conf()["hf"], rows=64, contexts_total=64 * 870)["bytes"]
    assert read(ctx) == pytest.approx(100 * need / 819e9 / 18e-3)
    assert ctx["notes"]["decode_roofline"]["bound"] == "memory"


# -- the new reader ------------------------------------------------------------------


def _step(kind, seq, *, moe=None, traced=True, rows=64, chunk=0, mode="overlapped", kv=55680):
    rec = {"kind": "step", "seq": seq, "step_kind": kind, "decode_rows": rows, "chunk_rows": 1 if chunk else 0,
           "chunk_tokens": chunk, "traced": traced, "ann_ns": 1000 + seq, "t0_ns": 900 + seq, "overlap_mode": mode,
           "kv_tokens_full": kv, "kv_tokens_window": 0}
    if moe is not None:
        rec.update(zip(("moe_choices", "moe_choices_zero", "moe_choices_held", "moe_experts_touched"), moe))
    return rec


def _trace(programs):
    """One device plane and one host line: per program (annotation name, start,
    dur, [(op name, offset, dur), ...]) in ns."""
    mods = [["jit__step(1)", s, d] for _, s, d, _ in programs]
    ops = [[name, s + off, dur] for _, s, _, evs in programs for name, off, dur in evs]
    anns = [[name, s - 50, d + 100] for name, s, d, _ in programs]
    return {"planes": [{"name": "/device:TPU:0", "lines": [{"name": "XLA Modules", "events": mods},
                                                           {"name": "XLA Ops", "events": ops}]},
                       {"name": "/host:CPU", "lines": [{"name": "exec", "events": anns}]}]}


def _ctx(steps, trace=None, conf=None):
    return {"conf": conf or _conf(), "peaks": PEAKS, "window": {"steps": steps}, "trace": trace, "notes": {}}


def test_moe_expert_roofline_reader_takes_each_programs_own_count():
    """Three traced decode steps in the pipelined loop: a program's counters
    are in the *next* record. The kernel runs twice a MoE layer (gate + up,
    down); its events inside a mixed step's program are another step kind's."""
    read = _reader("kernels.moe_expert_roofline_pct")
    choices = 64 * 8 * 39
    expert = 3 * 2048 * 768

    def kernel(total_ns):
        return [(f"moe_grouped_matmul_int8.{22 + i % 2}", 100 + 40_000 * i, total_ns / 78) for i in range(78)]

    least = {n: n * expert / 819e9 * 1e9 for n in (1000, 1100)}  # ns
    programs = [
        ("engine.decode", 10_000, 17_000_000, kernel(least[1100] * 2) + [("mla_paged_decode_attention.3", 5, 20)]),
        ("engine.mixed", 20_000_000, 20_000_000, kernel(9e6)),
        ("engine.decode", 45_000_000, 17_000_000, kernel(least[1000] * 4)),
        ("engine.decode", 65_000_000, 17_000_000, kernel(least[1000] * 2.5)),
    ]
    steps = [_step("decode", 1, moe=(choices, 0, 2496, 990)),  # its predecessor's counters: not this program's
             _step("mixed", 2, moe=(choices, 0, 2500, 1100), chunk=64),  # holds the first decode program's
             _step("decode", 3, moe=(choices + 64 * 8 * 39, 0, 5000, 1200)),  # the mixed program's: more choices
             _step("decode", 4, moe=(choices, 0, 2480, 1000)),  # the third program's
             _step("decode", 5, moe=(choices, 0, 2480, 1000), traced=False)]  # the fourth's; itself untraced
    ctx = _ctx(steps, _trace(programs))
    assert read(ctx) == pytest.approx(40.0)  # median of 50, 25 and 40
    note = ctx["notes"]["moe_expert_roofline"]
    assert note["steps"] == 3 and note["experts_touched"] == 1000 and note["needed_bytes"] == 1000 * expert
    # a holder whose choices are not this step's rows x 8 x 39 is another program's: the step is left out
    steps[3]["moe_choices"] += 8
    assert read(_ctx(steps, _trace(programs))) == pytest.approx(45.0)  # of 50 and 40
    # the synchronous loop: a record holds its own program's counters
    sync = [_step("decode", 1, moe=(choices, 0, 2496, 1100), mode="barrier"), _step("mixed", 2, chunk=64, mode="barrier"),
            _step("decode", 3, moe=(choices, 0, 2496, 1000), mode="barrier"), _step("decode", 4, moe=(choices, 0, 2496, 1000), mode="barrier")]
    assert read(_ctx(sync, _trace(programs))) == pytest.approx(40.0)
    # nothing to read: no trace; a program without the counters (the parent's); a trace without the kernel;
    # a configuration whose counts have no experts_step
    assert read(_ctx(steps)) is None
    bare = [{k: v for k, v in s.items() if not k.startswith("moe_")} for s in steps]
    assert read(_ctx(bare, _trace(programs))) is None
    other = [(n, s, d, [("ragged-dot.1", o, t) for _, o, t in evs]) for n, s, d, evs in programs]
    assert read(_ctx(steps, _trace(other))) is None
    from benchmark import serving

    longcat = serving.load_config(ROOT / "benchmark" / "configs" / "longcat-flash-chat-ep32-int8.json")
    assert read(_ctx(steps, _trace(programs), conf=longcat)) is None


def test_accepted_readers_find_this_cells_records():
    held, mla = _reader("engine.moe_held_choice_pct"), _reader("kernels.mla_decode_roofline_pct")
    choices = 64 * 8 * 39
    steps = [_step("decode", 1, moe=(choices, 0, 2496, 1084)), _step("decode", 2, moe=(choices, 0, 2496, 1084))]
    ctx = _ctx(steps)
    assert held(ctx) == pytest.approx(12.5)
    # that reader divides by every layer, the dense one too: 27.1 where the MoE layers' mean is 27.8 (PERF.md section 7)
    assert ctx["notes"]["moe_held"]["experts_touched_per_layer"] == pytest.approx(1084 / 40)
    need = _counts().attention_step(_conf()["hf"], kv_tokens_full=55680, rows=1, new_tokens=64)["bytes"]
    kernel = [("mla_paged_decode_attention.3", 100 + 50_000 * i, need / 819e9 * 1e9 / 40 * 2) for i in range(40)]
    programs = [("engine.decode", 10_000, 17_000_000, kernel), ("engine.decode", 30_000_000, 17_000_000, kernel)]
    assert mla(_ctx(steps, _trace(programs))) == pytest.approx(50.0)


def test_the_cells_entries():
    cell = next(w for w in BENCH["workloads"] if w["name"] == CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == (NAME, "reason-saturated", 1)
    mine = {m["name"]: m for m in BENCH["per_layer"] if CELL in m.get("workloads", [])}
    assert set(mine) == {"engine.mixed_pad_pct", "kernels.mla_decode_roofline_pct", "engine.moe_held_choice_pct",
                         "kernels.moe_expert_roofline_pct"}  # no identity outputs: not engine.moe_zero_choice_pct
    new = mine["kernels.moe_expert_roofline_pct"]
    assert (new["source"], new["layer"], new["moves"], new["unit"], new["workloads"]) == (
        "device_trace", "kernels", "itl_p50_ms", "%", [CELL])
    e2e = {m["name"] for m in BENCH["end_to_end"] if CELL in m.get("workloads", [CELL])}
    assert e2e == {"itl_p50_ms", "out_tok_s", "setup_s"}
    entry = next(c for c in BENCH["configs"] if c["name"] == NAME)
    assert entry["reduced"] == ["n_routed_experts"] and entry["file"] == f"benchmark/configs/{NAME}.json"
    doc = json.loads((ROOT / entry["file"]).read_text())
    assert (doc["n_routed_experts"], doc["n_routed_experts_published"], doc["expert_share_rank"], doc["expert_share_chips"]) == (32, 256, 0, 8)
    assert doc["num_hidden_layers"] == 40 and doc["vocab_size"] == 129280 and doc["num_nextn_predict_layers"] == 1


def test_the_cell_warms_seventy_programs_and_never_preempts():
    from benchmark import serving, traffic

    mix = traffic.load_mix(ROOT / "benchmark" / "traffic" / "reason-saturated.json",
                           ROOT / "benchmark" / "cells" / f"{CELL}.json")
    rows = mix["lengths_per_100"]
    assert (mix["loop"], mix["clients"], mix["requests_per_client"], mix["lead_in_s"], mix["schedule_seed"]) == ("closed", 64, 6, 8, 34)
    assert mix["warm"] == {"max_rows": 64, "max_context_tokens": 2048} and max(p + o for p, o in rows) <= 1984
    eng = _conf()["serve"]["engine"]
    assert mix["clients"] * max(p + o for p, o in rows) <= eng["pool_tokens"] == 131072  # no preemption
    assert len(serving.warm_shapes(_conf(), mix["warm"])) == 70
    plan = traffic.generate(mix, seed=2**31 + 7, seconds=51.0, vocab=_conf()["hf"]["vocab_size"])
    ids = [t for r in plan["requests"] for t in r["prompt"]]
    assert len(plan["clients"]) == 64 and max(ids) < 129280 and max(ids) > 100000  # the whole vocabulary
