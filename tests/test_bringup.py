"""What the chip bring-up (PR 21) repaired, pinned on the CPU: one compile
cache directory, no platform flag, replicas and meshes placed where they are
served from, dispatch telemetry that names the implementation that runs, and
a chip_smoke.py that never reports success off the chip."""

import os
import pathlib
import subprocess
import sys

import jax
import numpy as np
import pytest

from dynamo_tpu.compile_cache import enable_compile_cache
from dynamo_tpu.engine.runner import ModelRunner
from dynamo_tpu.models import llama
from dynamo_tpu.models.config import PRESETS

ROOT = pathlib.Path(__file__).resolve().parent.parent


def test_compile_cache_honours_env_dir_and_sets_nothing_else(monkeypatch, tmp_path):
    target = tmp_path / "some" / "dir"
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(target))
    before = jax.config.jax_compilation_cache_dir
    assert enable_compile_cache() == str(target)
    assert target.is_dir()
    assert jax.config.jax_compilation_cache_dir == before  # JAX reads the env itself


def test_compile_cache_default_is_fixed_path_in_checkout(monkeypatch):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    before = jax.config.jax_compilation_cache_dir
    try:
        path = enable_compile_cache()
        assert path == str(ROOT / ".jax_cache") == jax.config.jax_compilation_cache_dir
        assert enable_compile_cache() == path  # no pid, time or temp name in it
    finally:
        jax.config.update("jax_compilation_cache_dir", before)


def test_launch_has_no_platform_flag():
    from dynamo_tpu import launch

    with pytest.raises(SystemExit):
        launch.parse_args(["--platform", "cpu"])
    assert launch.parse_args(["--model", "test-tiny"]).model == "test-tiny"


def test_runner_resolves_attention_impl_once():
    """attn_impl=None used to stay None in the runner while the model picked
    an implementation: the dispatch telemetry then named the wrong path."""
    cfg = PRESETS["test-tiny"]
    runner = ModelRunner(cfg, llama.init_params(cfg, 0), num_pages=8, page_size=16)
    assert runner.attn_impl == "reference"  # CPU; "pallas" on a TPU backend
    assert ModelRunner(cfg, runner.params, num_pages=8, page_size=16,
                       attn_impl="pallas").attn_impl == "pallas"


async def test_replicas_in_one_process_get_a_device_each():
    from conftest import start_stack, stop_stack

    handles, _ = await start_stack(num_workers=2)
    try:
        homes = []
        for svc in handles["services"]:
            runner = svc.core.runner
            (cache_dev,) = runner.k_cache.devices()
            assert {cache_dev} == jax.tree.leaves(runner.params)[0].devices() == {runner.device}
            homes.append(cache_dev)
        assert homes == jax.local_devices()[:2]
    finally:
        await stop_stack(handles)


def test_init_sharded_builds_each_shard_in_place(cpu_devices):
    from dynamo_tpu.parallel.mesh import MeshPlan, make_mesh
    from dynamo_tpu.parallel.sharding import init_sharded, param_shardings

    cfg = PRESETS["test-tiny"]
    mesh = make_mesh(MeshPlan(tp=2), cpu_devices[:2])
    eager = llama.init_params(cfg, 0)
    sharded = init_sharded(lambda: llama.init_params(cfg, 0), mesh)
    want = param_shardings(mesh, eager)
    for got, ref, sh in zip(jax.tree.leaves(sharded), jax.tree.leaves(eager), jax.tree.leaves(want)):
        assert got.sharding.is_equivalent_to(sh, got.ndim)
        np.testing.assert_allclose(np.asarray(got), np.asarray(ref), rtol=1e-6, atol=1e-7)
    runner = ModelRunner(cfg, sharded, num_pages=8, page_size=16, mesh=mesh)
    assert len(runner.k_cache.devices()) == 2  # the pool is allocated sharded too


def test_chip_smoke_cpu_rehearsal_runs_to_the_end_and_is_not_ok():
    """JAX_PLATFORMS=cpu pinned: the whole control flow at test-tiny, a last
    line that says so, a non-zero exit, and never an ``"ok": true``."""
    proc = subprocess.run(
        [sys.executable, str(ROOT / "chip_smoke.py")], capture_output=True, text=True,
        timeout=300, cwd=ROOT,
        env={**os.environ, "JAX_PLATFORMS": "cpu"},
    )
    lines = [line for line in proc.stdout.splitlines() if line.startswith("{")]
    assert proc.returncode == 3, proc.stderr[-2000:]
    assert '"cpu_rehearsal": "completed"' in lines[-1] and '"ok": false' in lines[-1]
    assert not any('"ok": true' in line for line in lines)
    assert any('"logits_check"' in line for line in lines)
