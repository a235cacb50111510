"""Accelerator abstraction tests (reference tests/accelerator)."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from deepspeed_tpu.accelerator import (CpuAccelerator, DeepSpeedAccelerator,
                                       get_accelerator, set_accelerator)


@pytest.fixture(autouse=True)
def _cpu_accel():
    prev = get_accelerator()
    set_accelerator(CpuAccelerator())
    yield
    set_accelerator(prev)


def test_singleton_and_abc():
    acc = get_accelerator()
    assert isinstance(acc, DeepSpeedAccelerator)
    assert acc is get_accelerator()


def test_device_mgmt():
    acc = get_accelerator()
    assert acc.is_available()
    assert acc.device_count() == len(jax.devices())
    assert acc.device_name() == "cpu"
    assert acc.device_name(3) == "cpu:3"
    assert acc.device(0) is jax.devices()[0]
    acc.synchronize()


def test_rng():
    acc = get_accelerator()
    acc.manual_seed(42)
    assert acc.initial_seed() == 42
    k1 = acc.split_key()
    k2 = acc.split_key()
    a = jax.random.normal(k1, (4,))
    b = jax.random.normal(k2, (4,))
    assert not np.allclose(a, b)


def test_streams_events_noop():
    acc = get_accelerator()
    with acc.stream():
        x = jnp.ones((8,)) * 2
    ev = acc.Event()
    ev.record(value=x)
    ev.synchronize()
    assert ev.query()


def test_memory_and_dtypes():
    acc = get_accelerator()
    assert acc.total_memory() > 0
    assert acc.is_bf16_supported()
    assert jnp.bfloat16 in acc.supported_dtypes()


def test_op_builder_dispatch():
    acc = get_accelerator()
    b = acc.create_op_builder("quantizer")
    mod = b.load()
    assert hasattr(mod, "quantize_int8_blockwise") or mod is not None
    assert acc.get_op_builder("nonexistent") is None


def test_communication_backend():
    assert get_accelerator().communication_backend_name() == "xla"


def test_env_override(monkeypatch):
    import deepspeed_tpu.accelerator.real_accelerator as ra
    monkeypatch.setattr(ra, "_accelerator", None)
    monkeypatch.setenv("DS_ACCELERATOR", "cpu")
    assert isinstance(ra.get_accelerator(), CpuAccelerator)
    monkeypatch.setattr(ra, "_accelerator", None)
    monkeypatch.setenv("DS_ACCELERATOR", "bogus")
    with pytest.raises(ValueError):
        ra.get_accelerator()


def test_missing_tpu_falls_back_to_cpu_loudly_once(monkeypatch):
    """No override and no TPU: the CPU accelerator, with ONE warning — a
    run that claims the chip must be able to see it did not get it."""
    import deepspeed_tpu.accelerator.real_accelerator as ra
    from deepspeed_tpu.utils.logging import logger
    warned = []
    monkeypatch.setattr(logger, "warning", warned.append)
    monkeypatch.setattr(ra, "_accelerator", None)
    monkeypatch.delenv("DS_ACCELERATOR", raising=False)
    monkeypatch.delenv("DSTPU_ACCELERATOR", raising=False)
    assert isinstance(ra.get_accelerator(), CpuAccelerator)
    assert isinstance(ra.get_accelerator(), CpuAccelerator)
    assert len(warned) == 1 and "no TPU" in warned[0]

