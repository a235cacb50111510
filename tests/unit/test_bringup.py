"""What has to hold for the program to meet a real chip (tier-1, CPU).

* importing the package starts no JAX backend — a launcher or autotuning
  parent that spawns per-trial children must stay off the chip;
* the compile cache lands where ``JAX_COMPILATION_CACHE_DIR`` says, else
  at one fixed path inside the checkout;
* ``chip_smoke.py`` refuses to run under its own name without a TPU, and
  its control flow — one-chip phases and the dp=4-vs-dp=1 comparison —
  passes at the tiny preset on the CPU mesh (``--rehearse``);
* Pallas kernels under a multi-device mesh go through ``shard_map``
  (Mosaic custom calls cannot be partitioned by GSPMD).
"""

import json
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from deepspeed_tpu.ops.pallas import _common
from deepspeed_tpu.utils import groups
from deepspeed_tpu.utils.compile_cache import (CACHE_ENV, DEFAULT_CACHE_DIR,
                                               enable_compile_cache)
from deepspeed_tpu.utils.groups import BATCH_AXES, TopologyConfig

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)
import chip_smoke  # noqa: E402


def _python(code):
    """Run ``code`` in a fresh interpreter from the checkout root, with no
    cache directory named from outside."""
    env = {k: v for k, v in os.environ.items() if k != CACHE_ENV}
    env["JAX_PLATFORMS"] = "cpu"
    return subprocess.run([sys.executable, "-c", code], cwd=ROOT, env=env,
                          capture_output=True, text=True, timeout=120)


def test_import_starts_no_backend_and_cache_dir_is_fixed():
    """A fresh interpreter: the imports leave ``xla_bridge._backends``
    empty; then the cache helper, with no variable set, names the fixed
    in-checkout path — the one this process computes too."""
    r = _python(
        "import deepspeed_tpu, deepspeed_tpu.launcher.runner\n"
        "import deepspeed_tpu.autotuning.scheduler\n"
        "import deepspeed_tpu.models\n"
        "import deepspeed_tpu.inference.v2.engine_v2\n"
        "from jax._src import xla_bridge\n"
        "assert not xla_bridge._backends, xla_bridge._backends\n"
        "from deepspeed_tpu.utils.compile_cache import enable_compile_cache\n"
        "import jax\n"
        "a = enable_compile_cache(); b = enable_compile_cache()\n"
        "assert a == b == jax.config.jax_compilation_cache_dir\n"
        "print(a)\n")
    assert r.returncode == 0, r.stderr[-2000:]
    assert r.stdout.strip() == DEFAULT_CACHE_DIR == \
        os.path.join(ROOT, ".cache", "jax")


def _record_config_updates(monkeypatch):
    calls = {}
    monkeypatch.setattr(jax.config, "update", calls.__setitem__)
    return calls


def test_compile_cache_dir_from_environment_is_left_to_jax(monkeypatch):
    monkeypatch.setenv(CACHE_ENV, "/some/dir")
    calls = _record_config_updates(monkeypatch)
    enable_compile_cache()
    assert "jax_compilation_cache_dir" not in calls
    # the thresholds drop either way, so serving buckets are cached too
    assert calls == {"jax_persistent_cache_min_compile_time_secs": 0.0,
                     "jax_persistent_cache_min_entry_size_bytes": -1}


def test_compile_cache_dir_default_is_the_same_on_every_call(monkeypatch):
    monkeypatch.delenv(CACHE_ENV, raising=False)
    calls = _record_config_updates(monkeypatch)
    enable_compile_cache()
    first = dict(calls)
    enable_compile_cache()
    assert calls == first
    assert calls["jax_compilation_cache_dir"] == DEFAULT_CACHE_DIR


def test_chip_smoke_refuses_to_run_without_a_tpu():
    r = subprocess.run([sys.executable, "chip_smoke.py"], cwd=ROOT,
                       env={**os.environ, "JAX_PLATFORMS": "cpu"},
                       capture_output=True, text=True, timeout=120)
    assert r.returncode != 0
    assert '"ok"' not in r.stdout
    assert "needs a TPU" in r.stderr


class _NoCompiles:
    def take(self):
        return {}


def _phases(capsys):
    return [json.loads(line) for line in capsys.readouterr().out.splitlines()
            if line.startswith('{"phase"')]


def test_chip_smoke_serve_rehearsal(capsys):
    """The serve phase end to end at the tiny preset: 4 requests through
    engine, Replica and Router under both serving configs, each emitted
    token held to the dense float32 logits."""
    chip_smoke.phase_serve(chip_smoke.TINY, jax.devices()[:1],
                           _NoCompiles())
    seen = {p["config"]: p for p in _phases(capsys)
            if p["phase"] == "serve"}
    assert set(seen) == {"default", "splitfuse"}
    assert all(len(t) == chip_smoke.TINY["new_tokens"]
               for p in seen.values() for t in p["tokens"])


def test_chip_smoke_four_device_rehearsal(capsys):
    """The training side: ZeRO-2 and ZeRO-3 at dp=4 against dp=1 with 4
    accumulation steps, the Pallas flash and fused-CE kernels (interpret
    mode) running per shard under the dp mesh."""
    chip_smoke.phase_multichip(chip_smoke.TINY, jax.devices()[:4],
                               _NoCompiles(), rehearse=True)
    runs = [p for p in _phases(capsys) if p["phase"] == "multichip"]
    assert [r["zero_stage"] for r in runs] == [2, 3]
    assert all(r["max_loss_gap"] <= chip_smoke.LOSS_TOL for r in runs)


def _double(x):
    return x * 2


class TestShardKernel:
    def test_no_mesh_or_one_device_calls_the_kernel_directly(self):
        spec = P(BATCH_AXES, None)
        assert _common.shard_kernel(_double, (spec,), spec) \
            is _double
        topo = groups.initialize(TopologyConfig(data_parallel_size=1),
                                 devices=jax.devices()[:1], force=True)
        with jax.set_mesh(topo.mesh):
            assert _common.shard_kernel(_double, (spec,), spec) \
                is _double

    def test_multi_device_mesh_runs_the_kernel_per_shard(self):
        topo = groups.initialize(TopologyConfig(data_parallel_size=4),
                                 devices=jax.devices()[:4], force=True)
        spec = P(BATCH_AXES, None)
        x = jnp.arange(32.0).reshape(8, 4)
        with jax.set_mesh(topo.mesh):
            fn = jax.jit(lambda v: _common.shard_kernel(
                _double, (spec,), spec)(v))
            assert "shard_map" in str(fn.trace(x).jaxpr)
            np.testing.assert_array_equal(np.asarray(fn(x)),
                                          2 * np.asarray(x))

    def test_axes_that_do_not_divide_are_dropped(self):
        assert _common.dividing_axes(3, BATCH_AXES) == BATCH_AXES  # no mesh
        topo = groups.initialize(TopologyConfig(data_parallel_size=4),
                                 devices=jax.devices()[:4], force=True)
        with jax.set_mesh(topo.mesh):
            assert _common.dividing_axes(8, BATCH_AXES) == BATCH_AXES
            assert _common.dividing_axes(6, BATCH_AXES) is None
            assert _common.dividing_axes(6, "tensor") == "tensor"
