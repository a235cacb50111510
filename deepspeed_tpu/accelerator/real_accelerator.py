"""Runtime accelerator detection.

Counterpart of reference ``accelerator/real_accelerator.py:51
get_accelerator()``: env override (``DS_ACCELERATOR``, reference :59-102)
else probe (reference order xpu→npu→mps→hpu→cuda→cpu, :106-162; here
tpu→cpu — gpu-via-jax would slot in between).
"""

import os

_accelerator = None


def get_accelerator():
    global _accelerator
    if _accelerator is not None:
        return _accelerator

    override = os.environ.get("DS_ACCELERATOR",
                              os.environ.get("DSTPU_ACCELERATOR"))
    if override:
        set_accelerator(_make(override))
        return _accelerator

    from .tpu_accelerator import TpuAccelerator
    acc = TpuAccelerator()
    if not acc.is_available():
        # right for tests and dev containers, wrong for a run that claims
        # the chip — so never silent (once: the choice is cached above)
        from ..utils.logging import logger
        logger.warning(
            "get_accelerator(): JAX found no TPU; using the CPU "
            "accelerator (set DS_ACCELERATOR to choose explicitly)")
        acc = _make("cpu")
    set_accelerator(acc)
    return _accelerator


def set_accelerator(accel):
    """Reference real_accelerator.py:30 set_accelerator."""
    global _accelerator
    _accelerator = accel
    return _accelerator


def _make(name):
    from .tpu_accelerator import CpuAccelerator, TpuAccelerator
    name = name.lower()
    if name == "tpu":
        return TpuAccelerator()
    if name == "cpu":
        return CpuAccelerator()
    raise ValueError(
        f"DS_ACCELERATOR='{name}' not supported; expected 'tpu' or 'cpu'")


def is_current_accelerator_supported():
    return get_accelerator().is_available()
