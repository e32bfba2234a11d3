"""The executable store (ISSUE 52): a runner with a persistent cache directory
keeps each compiled step program, serialised, under a key it computes without
tracing, and a later runner loads it instead of tracing and lowering again.

A stale hit is the only way the store can be wrong, so most of this file is
about the key: a runner built from *other weights* loads what the first stored
and serves the very tokens a store-less runner does; anything else a program is
made from changes the key. The rest is the files: a broken entry is a miss and
is written again, a write is a rename, two builds' directories at most, no
store without a cache directory. The suite itself runs without a store
(``tests/conftest.py``): every runner here is given its own.
"""

import dataclasses
import inspect
import json
import os
import shutil

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from dynamo_tpu import executable_store as es
from dynamo_tpu.engine.runner import ModelRunner
from dynamo_tpu.models import llama
from dynamo_tpu.models.config import PRESETS
from tests.test_split_mixed_step import PAGE, step_batch

#: A GQA, a latent and a recurrent family (KDA layers closed by an MLA layer a period).
FAMILIES = {"gqa": "test-tiny", "latent": "test-tiny-mla", "recurrent": "test-tiny-hybrid"}
DECODE = [(5, 1), (21, 1), (12, 1)]
CHUNK = [(9, 1), (3, 6), (30, 1)]


@pytest.fixture
def cache_dir(tmp_path, fresh_compiles):
    """A persistent cache directory of this test's own, and the store under it."""
    es.set_cache_dir(str(tmp_path))
    return tmp_path  # (tests/conftest.py takes the store away again after every test)


def make_runner(family: str = "gqa", seed: int = 0, **kw) -> ModelRunner:
    cfg = PRESETS[FAMILIES[family]]
    kw = {"num_pages": 96, "page_size": PAGE, "max_batch_size": 8, "prefill_bucket": 16, "attn_impl": "reference", **kw}
    runner = ModelRunner(cfg, llama.init_params(cfg, seed), **kw)
    # A cache full of noise, the same in every runner: a row's context matters.
    shape, dt = runner.k_cache.shape, runner.k_cache.dtype
    runner.k_cache = jax.random.normal(jax.random.PRNGKey(1), shape, jnp.float32).astype(dt)
    runner.v_cache = jax.random.normal(jax.random.PRNGKey(2), shape, jnp.float32).astype(dt)
    return runner


def batch(rows, runner: ModelRunner):
    b = step_batch(rows)
    if runner.recurrent:
        b = dataclasses.replace(b, state_slots=np.arange(1, len(rows) + 1, dtype=np.int32))
    if not runner.two_pool:
        b = dataclasses.replace(b, window_block_tables=None, window_slot_mapping=None)
    return b


def serve(runner: ModelRunner):
    """A decode step with logprobs and a chunk step: everything that came back."""
    tokens, lps = runner.step(batch(DECODE, runner), lp_k=2)
    return [tokens, lps["logprob"], lps["top_ids"], lps["top_lps"], runner.step(batch(CHUNK, runner))]


def filled(build, drive, cache_dir):
    """``(runner, what it served)`` of a runner that wrote every program it
    compiled. XLA's CPU backend now and then refuses to serialise a program
    that sorts (``LessThan is not serializable``): the store counts a failed
    write, nothing else happens, and the next run misses that program; a test
    that counts entries starts again on an empty store then."""
    for _ in range(4):
        runner = build()
        served = drive(runner)
        if not runner._programs.store.failed_writes:
            return runner, served
        shutil.rmtree(cache_dir / es.DIRECTORY)
    pytest.skip("XLA's CPU backend did not serialise these programs in four tries")


def stores(runner: ModelRunner) -> list[str]:
    return [e["store"] for e in runner.compile_tracker.events()]


def entries(cache_dir) -> list:
    return sorted((cache_dir / es.DIRECTORY).glob(f"*/*{es.SUFFIX}"))


@pytest.mark.parametrize("family", FAMILIES)
def test_a_runner_of_other_weights_loads_every_program_and_serves_the_same_tokens(family, cache_dir):
    first, _ = filled(lambda: make_runner(family, seed=0), serve, cache_dir)
    assert stores(first) == ["miss", "miss"] and first._programs.store.counters()["written"] == 2
    assert len(entries(cache_dir)) == 2
    second = make_runner(family, seed=1)  # other weights: the key holds their forms, not their values
    loaded = serve(second)
    assert stores(second) == ["hit", "hit"]
    counters = second._programs.store.counters()
    assert (counters["hits"], counters["misses"], counters["written"]) == (2, 0, 0)
    es.set_cache_dir(None)
    plain = make_runner(family, seed=1)
    assert plain._programs is None
    for got, want in zip(loaded, serve(plain), strict=True):
        np.testing.assert_array_equal(got, want)  # bit for bit, the logprobs too
    assert stores(plain) == ["off", "off"]
    assert not np.array_equal(loaded[3], serve(make_runner(family, seed=0))[3])  # and the weights did matter


def test_a_kept_program_serves_the_later_dispatches_and_the_pipelined_loop(cache_dir):
    """``step`` and ``step_async`` of one bucket are one kept program; a warm
    runner calls nothing it would have to trace."""
    filled(make_runner, lambda r: r.step(batch(DECODE, r)), cache_dir)
    runner = make_runner(seed=3)
    want = runner.step(batch(DECODE, runner))
    got, _ = runner.step_async(batch(DECODE, runner)).result()
    np.testing.assert_array_equal(got[:, 0], want)
    assert stores(runner) == ["hit"] and len(runner._programs._kept) == 1
    assert runner._step_packed_fn._cache_size() == 0  # the jitted function was never called


def _mesh():
    from dynamo_tpu.parallel.mesh import MeshPlan, make_mesh

    return make_mesh(MeshPlan(dp=2, tp=2), jax.devices()[4:8])


#: The other runners and dispatch sites: the explicit-argument step of a mesh, a
#: replica pinned to a device that is not the first, the speculative verify.
OTHERS = {
    "a mesh": (lambda seed: make_runner(seed=seed, mesh=_mesh()), lambda r: r.step(batch(CHUNK, r))),
    "a pinned device": (lambda seed: make_runner(seed=seed, device=jax.devices()[3]), lambda r: serve(r)[-1]),
    "a verify": (lambda seed: make_runner(seed=seed), lambda r: r.spec_step(batch(CHUNK, r), 3)),
    "a chained verify": (lambda seed: make_runner(seed=seed),
                         lambda r: [r.step_async(batch(DECODE, r)).result()[0],
                                    r.spec_step_async(batch(DECODE, r), 2, chain_src=np.arange(3)).result()[0]][1]),
}


@pytest.mark.parametrize("who", OTHERS)
def test_every_runner_and_dispatch_site_goes_through_the_store(who, cache_dir):
    build, drive = OTHERS[who]
    first, _ = filled(lambda: build(0), drive, cache_dir)
    assert set(stores(first)) == {"miss"}
    second = build(1)
    got = drive(second)
    assert set(stores(second)) == {"hit"} and second.compile_tracker.refusals == 0
    es.set_cache_dir(None)
    np.testing.assert_array_equal(got, drive(build(1)))


# -- the key -------------------------------------------------------------------------


def _program_key(**changes):
    args = {"runner": "r", "fn_name": "_step_packed", "program": "step", "dispatch_key": (4, 1, 2, 1, 0, "reference"),
            "statics": (("b", 4), ("lp_k", 0)),
            "args": ({"w": jnp.zeros((4, 8), jnp.bfloat16)}, jnp.zeros(16, jnp.int32)), "kwargs": {"state": ()}}
    return es.program_key(**{**args, **changes})


def _built_from(cfg=PRESETS["test-tiny"], **changes):
    return es.built_from({"cfg": cfg, "num_pages": 96, "page_size": 4, "max_batch_size": 8, "prefill_bucket": 16,
                          "attn_impl": None, "forward_fn": None, "cache_dtype": None, "mesh": None, "device": None,
                          "embed_pooling": "mean", "window_chunk": None, **changes})


def _package(tmp_path, kernel: bytes = b"TILE = 128\n"):
    root = tmp_path / "pkg"
    shutil.rmtree(root, ignore_errors=True)
    (root / "ops").mkdir(parents=True)
    (root / "__init__.py").write_bytes(b"")
    (root / "ops" / "kernel.py").write_bytes(kernel)
    return es.package_digest(root)


KEY_CHANGES = {
    "one byte of a package file": lambda tmp: (_package(tmp), _package(tmp, b"TILE = 129\n")),
    "a package file more": lambda tmp: (_package(tmp), [(tmp / "pkg" / "new.py").write_bytes(b""), es.package_digest(tmp / "pkg")][1]),
    "one ModelConfig field": lambda tmp: (_built_from(), _built_from(dataclasses.replace(PRESETS["test-tiny"], rope_theta=5e5))),
    "one ModelRunner argument": lambda tmp: (_built_from(), _built_from(prefill_bucket=32)),
    "the cache's dtype": lambda tmp: (_built_from(), _built_from(cache_dtype=jnp.float8_e4m3fn)),
    "the device": lambda tmp: (_built_from(device=jax.devices()[0]), _built_from(device=jax.devices()[1])),
    "an argument's dtype": lambda tmp: (_program_key(), _program_key(args=({"w": jnp.zeros((4, 8), jnp.int8)}, jnp.zeros(16, jnp.int32)))),
    "an argument's shape": lambda tmp: (_program_key(), _program_key(args=({"w": jnp.zeros((4, 8), jnp.bfloat16)}, jnp.zeros(17, jnp.int32)))),
    "an argument's device": lambda tmp: (_program_key(), _program_key(args=({"w": jax.device_put(jnp.zeros((4, 8), jnp.bfloat16), jax.devices()[2])}, jnp.zeros(16, jnp.int32)))),
    "the arguments' tree": lambda tmp: (_program_key(), _program_key(kwargs={"state": (jnp.zeros(2),)})),
    "a static keyword": lambda tmp: (_program_key(), _program_key(statics=(("b", 4), ("lp_k", 20)))),
    "the dispatch key": lambda tmp: (_program_key(), _program_key(dispatch_key=(4, 1, 2, 1, 0, "pallas"))),
    "the jitted function": lambda tmp: (_program_key(), _program_key(fn_name="_step_split")),
    "the runner": lambda tmp: (_program_key(), _program_key(runner="another")),
}


@pytest.mark.parametrize("what", KEY_CHANGES)
def test_the_key_changes_with(what, tmp_path):
    before, after = KEY_CHANGES[what](tmp_path)
    assert before != after
    again, _ = KEY_CHANGES[what](tmp_path)
    assert again == before  # and with nothing else


def test_the_key_reads_the_settings_at_each_first_sight(monkeypatch):
    before = _program_key()
    monkeypatch.setenv("DYN_DECODE_SPLITS", "4")
    env = _program_key()
    monkeypatch.delenv("DYN_DECODE_SPLITS")
    jax.config.update("jax_default_matmul_precision", "default")
    try:
        config = _program_key()
    finally:
        jax.config.update("jax_default_matmul_precision", "highest")  # tests/conftest.py's
    assert len({before, env, config}) == 3 and _program_key() == before


def test_the_runners_part_of_the_key_holds_every_constructor_argument(cache_dir):
    runner = make_runner()
    held = json.loads(runner._programs.runner_key)
    assert set(inspect.signature(ModelRunner.__init__).parameters) - {"self", "params"} <= set(held)
    assert held["cfg"]["hidden_size"] == runner.cfg.hidden_size and held["attn_impl_resolved"] == "reference"
    assert {f.name for f in dataclasses.fields(runner.cfg)} <= set(held["cfg"])


def test_the_build_digest_names_the_directory(cache_dir):
    facts = es.build_facts()
    assert {"package", "jax", "jaxlib", "platform_version", "device_kind", "devices", "processes", "XLA_FLAGS",
            "LIBTPU_INIT_ARGS"} <= set(facts)
    assert facts["package"] == es.package_digest(os.path.dirname(es.__file__))
    filled(make_runner, lambda r: r.step(batch(DECODE, r)), cache_dir)
    (entry,) = entries(cache_dir)
    assert entry.parent.name == es.build_digest()
    assert json.loads((entry.parent / es.BUILD_FILE).read_text()) == facts


# -- the files -----------------------------------------------------------------------


def _mismatched(entry_paths):
    """The decode program's entry holds the chunk program: it loads, and its tree is another."""
    small, large = sorted(entry_paths, key=lambda p: p.stat().st_size)
    shutil.copyfile(large, small)
    return small


BROKEN = {
    "truncated": lambda paths: [p.write_bytes(p.read_bytes()[: p.stat().st_size // 2]) for p in paths],
    "empty": lambda paths: [p.write_bytes(b"") for p in paths],
    "not a program": lambda paths: [p.write_bytes(es._compress(b"no pickle")) for p in paths],
    "another program's": _mismatched,
}


@pytest.mark.parametrize("how", BROKEN)
def test_a_broken_entry_is_a_miss_and_is_written_again(how, cache_dir):
    _, want = filled(make_runner, serve, cache_dir)
    BROKEN[how](entries(cache_dir))
    n = 1 if how == "another program's" else 2
    second = make_runner()
    for got, w in zip(serve(second), want, strict=True):
        np.testing.assert_array_equal(got, w)
    counters = second._programs.store.counters()
    unwritten = counters["failed_writes"]  # (the CPU backend's refusal, see ``filled``: 0 but for once in a while)
    assert (counters["failed_loads"], counters["misses"], counters["written"] + unwritten) == (n, n, n)
    assert stores(second).count("miss") == n and all(e["trace_ms"] > 0 for e in second.compile_tracker.events()
                                                     if e["store"] == "miss")
    third = make_runner()  # whole again
    serve(third)
    assert stores(third).count("hit") == 2 - unwritten


def test_a_write_is_a_temporary_file_and_a_rename(cache_dir, monkeypatch):
    renames = []
    replace = os.replace

    def recording(src, dst):
        renames.append((src, dst, os.path.getsize(src)))
        replace(src, dst)

    monkeypatch.setattr(es.os, "replace", recording)
    filled(make_runner, lambda r: r.step(batch(DECODE, r)), cache_dir)
    ((src, dst, size),) = renames[-1:]
    (entry,) = entries(cache_dir)
    assert dst == str(entry) and os.path.dirname(src) == os.path.dirname(dst) and size == entry.stat().st_size > 0
    assert os.path.basename(src).startswith(".writing-") and not os.path.exists(src)
    assert sorted(os.listdir(entry.parent)) == sorted([es.BUILD_FILE, entry.name])


def test_a_write_that_fails_fails_no_step(cache_dir, monkeypatch):
    monkeypatch.setattr(es.os, "replace", lambda src, dst: (_ for _ in ()).throw(OSError(28, "No space left on device")))
    runner = make_runner()
    runner.step(batch(DECODE, runner))
    runner.step(batch(DECODE, runner))
    assert runner._programs.store.counters()["failed_writes"] == 1 and not entries(cache_dir)
    build = cache_dir / es.DIRECTORY / es.build_digest()
    assert not build.is_dir() or os.listdir(build) == [es.BUILD_FILE]  # no temporary file left


def test_a_program_out_of_jaxs_cache_is_written_only_where_it_serialises_whole(cache_dir, monkeypatch):
    """XLA's CPU backend serialises an executable it loaded from the persistent
    cache without some of its kernels; such a program is left to that cache."""
    from jax.experimental.compilation_cache import compilation_cache

    suite_cache = jax.config.jax_compilation_cache_dir

    def jax_cache(enabled: bool, path: str) -> None:
        jax.config.update("jax_enable_compilation_cache", enabled)
        jax.config.update("jax_compilation_cache_dir", path)
        compilation_cache.reset_cache()

    jax_cache(True, str(cache_dir))
    try:
        first, want = filled(make_runner, serve, cache_dir)
        assert len(entries(cache_dir)) == 2
        shutil.rmtree(cache_dir / es.DIRECTORY)
        second = make_runner()
        for got, w in zip(serve(second), want, strict=True):
            np.testing.assert_array_equal(got, w)
        assert [(e["cache"], e["store"]) for e in second.compile_tracker.events()] == [("hit", "miss")] * 2
        assert second._programs.left_to_cache == 2 and not entries(cache_dir)
        monkeypatch.setattr(es, "RESERIALISES", ("cpu",))  # a platform that does: written
        third = make_runner()
        third.step(batch(DECODE, third), lp_k=2)
        counters = third._programs.store.counters()
        assert third._programs.left_to_cache == 0 and counters["written"] + counters["failed_writes"] == 1
    finally:
        jax_cache(False, suite_cache)  # as the fixture left it


def test_a_third_builds_first_write_leaves_two_directories(tmp_path, fresh_compiles):
    compiled = jax.jit(lambda x: x + 1).lower(jnp.zeros(4)).compile()
    root = str(tmp_path / es.DIRECTORY)
    for age, digest in ((300, "tree-a"), (200, "tree-b")):
        assert es.ExecutableStore(root, digest).save("k", compiled)
        os.utime(os.path.join(root, digest), (1e9 - age, 1e9 - age))
    assert sorted(os.listdir(root)) == ["tree-a", "tree-b"]
    es.ExecutableStore(root, "tree-a")  # a process of the older tree opens its store: used now
    third = es.ExecutableStore(root, "tree-c")
    assert third.load("k", jax.devices()[:1]) is None and sorted(os.listdir(root)) == ["tree-a", "tree-b"]  # a read removes nothing
    assert third.save("k", compiled) and third.save("k2", compiled)
    assert sorted(os.listdir(root)) == ["tree-a", "tree-c"]  # the one used last stays, and is whole
    loaded = es.ExecutableStore(root, "tree-a").load("k", jax.devices()[:1])
    np.testing.assert_array_equal(loaded(jnp.zeros(4)), np.ones(4))


# -- who has a store -------------------------------------------------------------------


def test_no_store_where_there_is_no_cache_directory(tmp_path):
    assert es.root() is None and es.open_store() is None  # the suite's own state
    runner = make_runner()
    assert runner._programs is None
    runner.step(batch(DECODE, runner))
    assert stores(runner) == ["off"] and runner._step_packed_fn._cache_size() == 1  # the jitted function's own path
    assert not list(tmp_path.iterdir())


def test_the_compile_cache_helper_places_the_store(tmp_path, monkeypatch):
    from dynamo_tpu.compile_cache import CACHE_DIR_ENV, enable_compile_cache

    monkeypatch.setenv(CACHE_DIR_ENV, str(tmp_path / "cache"))
    assert enable_compile_cache() == str(tmp_path / "cache")
    assert es.root() == str(tmp_path / "cache" / "executables") and es.open_store().dir.startswith(es.root())


def test_a_process_of_several_hosts_has_no_store(cache_dir, monkeypatch):
    monkeypatch.setattr(jax, "process_count", lambda: 2)
    assert es.open_store() is None and make_runner()._programs is None


def test_a_runner_built_from_a_closure_has_no_store(cache_dir):
    def forward(*args, **kwargs):
        return llama.forward(*args, **kwargs)

    assert make_runner(forward_fn=forward)._programs is None
    assert make_runner(forward_fn=llama.forward)._programs is not None  # a function the package's bytes hold


def test_every_step_program_names_its_static_keywords_and_a_call_without_a_key_is_the_jitted_functions(cache_dir, monkeypatch):
    from dynamo_tpu.engine.runner import STATIC_KEYWORDS

    runner = make_runner()
    jitted = {v.__name__ for name, v in vars(runner).items() if "step" in name and hasattr(v, "lower") and hasattr(v, "trace")}
    assert jitted == set(STATIC_KEYWORDS)  # _enqueue looks a program's static names up by the function's name
    monkeypatch.setattr(es, "program_key", lambda *a, **k: 1 / 0)
    want = runner.step(batch(DECODE, runner))
    np.testing.assert_array_equal(runner.step(batch(DECODE, runner)), want)
    assert stores(runner) == ["off"] and runner._step_packed_fn._cache_size() == 1 and not runner._programs._kept


# -- a kept program that refuses a later dispatch ----------------------------------------


def test_a_refused_dispatch_goes_to_the_jitted_function_and_is_recorded(cache_dir):
    runner = make_runner()
    records = []
    runner.compile_tracker.bind_sink(lambda kind, **f: records.append((kind, f)))
    want = runner.step(batch(DECODE, runner))
    # The chain buffer at another width: no dispatch key holds it (the runner allocates one width).
    runner._chain_idle = jnp.zeros(2 * runner._chain_width, jnp.int32)
    np.testing.assert_array_equal(runner.step(batch(DECODE, runner)), want)
    np.testing.assert_array_equal(runner.step(batch(DECODE, runner)), want)
    assert runner.compile_tracker.refusals == 2
    assert runner._step_packed_fn._cache_size() == 1  # the jitted function compiled once, as it would have
    refused = [f for kind, f in records if kind == "program_refused"]
    assert len(refused) == 2 and refused[0]["program"] == "step" and refused[0]["bucket"][:2] == [4, 1]
    assert "Argument types differ" in refused[0]["error"]
