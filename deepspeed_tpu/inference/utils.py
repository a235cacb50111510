"""Shared inference-engine helpers."""

import jax
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P


def shard_params(model, mesh, dtype, params=None, seed=0, topology=None,
                 quantize=False):
    """Build NamedShardings from the model's ``partition_specs`` and place
    (or initialize) params under them, cast to ``dtype``.

    ``quantize``: ZeRO-Inference weight-only quantization — ``True`` /
    ``"int8"`` for W8, ``"int4"`` for W4 (two codes per byte, packed
    along the contracted dim). Block weights are quantized HOST-SIDE
    (HBM never holds the bf16 copy) and placed as Int8Weight /
    Int4Weight pytree nodes; serving paths dequantize one layer at a
    time, or keep the FFN weights quantized for the fused-dequant
    kernels when the engine sets ``_weight_quant_fused``
    (ops/int8_weights.py; reference inference/quantization/).

    Families whose served tree differs from the training tree (Mixtral:
    per-layer expert lists, ``Llama._PER_LAYER``) are placed in that
    form: given params are unstacked (``model.serving_params``), seeded
    ones are made unstacked (``model.init_served``) so that no stacked
    copy ever sits beside them on the device.

    Returns (params, param_shardings)."""
    served = hasattr(model, "serving_specs")
    specs = model.serving_specs(topology) if served \
        else model.partition_specs(topology)
    if served and params is not None:
        params = model.serving_params(params)
    if quantize not in (False, None, True, "int8", "int4"):
        raise ValueError(
            f"quantize must be False|True|'int8'|'int4', got "
            f"{quantize!r}")
    if quantize:
        bits = 4 if quantize == "int4" else 8
        from ..ops.int8_weights import (quantize_tree, quantized_shardings)
        if params is None:
            # init on HOST: the whole point is a model whose bf16 weights
            # exceed device memory — the fp32 init tree must never touch
            # the accelerator
            cpus = jax.local_devices(backend="cpu")
            with jax.default_device(cpus[0]):
                params = model.init(jax.random.key(seed))
            if served:
                params = model.serving_params(params)
        # consume-as-you-quantize: fp32 source leaves free one at a
        # time, so peak host memory is ~the source tree + one leaf
        # (not source + a full quantized copy)
        if not isinstance(params, dict):
            params = dict(params)
        qtree = quantize_tree(params, consume=True, bits=bits)
        del params
        # cast the un-quantized leaves (embeds/norms/biases) to dtype;
        # router weights stay fp32 (the same exclusion quantize_tree
        # honors — downcasting them to bf16 here would undo the
        # precision the exclusion exists to keep)
        from ..ops.int8_weights import Int8Weight, cast_unquantized
        qtree = cast_unquantized(qtree, dtype)
        shardings = quantized_shardings(specs, qtree, mesh)
        with jax.set_mesh(mesh):
            params = jax.tree.map(jax.device_put, qtree, shardings)
        return params, shardings
    shardings = jax.tree.map(lambda s: NamedSharding(mesh, s), specs,
                             is_leaf=lambda x: isinstance(x, P))
    with jax.set_mesh(mesh):
        if params is None:
            params = _seeded(model, dtype, shardings, jax.random.key(seed))
        else:
            # leafwise device_put: host (numpy) leaves transfer shard-by-
            # shard straight to their placement — the full tree never
            # materializes on one device (TP serving of > 1-chip models)
            def place(path, x, s):
                if not isinstance(x, jax.Array):
                    x = np.asarray(x)
                dt = np.dtype(_leaf_dtype(path, x, dtype))
                return jax.device_put(x.astype(dt, copy=False), s)
            params = jax.tree_util.tree_map_with_path(place, params,
                                                      shardings)
    return params, shardings


# router weights keep float32 whatever the serving dtype: a bf16 router
# flips near-ties between the k-th and (k+1)-th expert (the same
# exclusion ops/int8_weights.quantize_tree / cast_unquantized honor).
# So do a state-space layer's decay, skip and step bias (Mamba's own
# practice: the recurrence is float32, models/phi4flash.py)
_FP32_KEYS = ("moe_gate", "A_log", "D_skip", "dt_b")


def _leaf_dtype(path, x, dtype):
    """The dtype a served leaf is placed in."""
    import jax.numpy as jnp
    # jnp.issubdtype, not np.: host bf16 (ml_dtypes) is not a
    # np.floating subdtype
    if not jnp.issubdtype(x.dtype, jnp.floating):
        return x.dtype
    if any(getattr(p, "key", None) in _FP32_KEYS for p in path):
        return jnp.float32
    return dtype


def _seeded(model, dtype, shardings, rng):
    """Seeded weights made on the device in the serving dtype, in one
    program. A family with a served tree of its own gives it through
    ``init_served``, so that no stacked array and no second copy of any
    layer ever exists: OLMoE's 10.5 GB of experts cannot be unstacked
    next to themselves on a 16 GB chip (compiled for a v5e the program
    has 3 MB of temporaries beside its 10.48 GB of outputs; sandbox
    compile, PR 26)."""
    init = getattr(model, "init_served", model.init)
    return jax.jit(lambda r: jax.tree_util.tree_map_with_path(
        lambda p, x: x.astype(_leaf_dtype(p, x, dtype)), init(r)),
        out_shardings=shardings)(rng)
