"""Main-path Pallas kernels compiled for a described TPU v5e at GPT-2 350M
widths — no chip attached, no chip time.

The TPU compiler is installed beside the CPU backend and compiles for a
chip that is described, not attached (``topologies.get_topology_desc``).
Interpret-mode tests cannot see what it refuses: a slice off the tiling,
a kernel over its VMEM budget. These are the kernels ``chip_smoke.py``'s
train and serve phases run, at the shapes they run them; each must lower
to a Mosaic custom call. Nothing executes, so nothing is said about
results or speed. Skipped where the topology cannot be described.
"""

import functools
import json
import os
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.experimental.compilation_cache import compilation_cache
from jax.sharding import SingleDeviceSharding

from deepspeed_tpu.inference.v2.engine_v2 import _FUSED_STEPS
from deepspeed_tpu.ops.pallas.flash_attention import flash_attention
from deepspeed_tpu.ops.pallas.fused_ce import unembed_logits_stats
from deepspeed_tpu.ops.pallas.gated_delta_rule import (
    chunk_rule_kernel, live_slot_list, step_rule_kernel)
from deepspeed_tpu.ops.pallas.latent_attention import latent_chunk_attention
from deepspeed_tpu.ops.pallas.paged_attention import (
    as_pools, decode_entries_per_step, decode_work_list, like_boundary,
    paged_chunk_attention, paged_decode_attention, pool_block_dims)

# GPT-2 350M serving/training geometry (chip_smoke.py FULL)
B, T, H, HD, D, V = 8, 1024, 16, 64, 1024, 50304
BS, MB = 64, 16                  # kv_block_size, blocks per sequence
NB = 1 + B * MB                  # the v2 engine's default pool


@pytest.fixture(scope="module")
def v5e():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        from jax.experimental import topologies
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 - no TPU compiler here: skip
        pytest.skip(f"cannot describe a v5e topology: "
                    f"{type(e).__name__}: {e}")
    # a compile for a described device is written to the persistent cache
    # but cannot be read back without a chip; keep the cache out of it
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _compile(fn, shapes, sharding):
    args = [jax.ShapeDtypeStruct(s, d, sharding=sharding)
            for s, d in shapes]
    text = jax.jit(fn).lower(*args).compile().as_text()
    assert "tpu_custom_call" in text
    return text


bf16, i32 = jnp.bfloat16, jnp.int32
QKV_T = [((B, H, HD, T), bf16)] * 3          # the qkv einsum's own layout


def _flash_train(block, block_h):
    def loss(q, k, v):
        o = flash_attention(q, k, v, causal=True, qkv_t=True,
                            block_q=block, block_k=block, block_h=block_h,
                            interpret=False)
        return jnp.sum(o.astype(jnp.float32) ** 2)
    return jax.grad(loss, argnums=(0, 1, 2))


# cell 11's attention (``traffic/train-z2-micro2-8k.json``): 2 x 8,192
# tokens, 32 heads of 192-wide keys and 128-wide values, tiles of 1024, one
# instance a grid step, the standard layout
MLA_TRAIN = [((2, 8192, 32, 192), bf16)] * 2 + [((2, 8192, 32, 128), bf16)]


def _flash_mla(grad):
    def loss(q, k, v):
        o = flash_attention(q, k, v, causal=True, scale=192 ** -0.5,
                            block_q=1024, block_k=1024, block_h=1,
                            interpret=False)
        assert o.shape == v.shape
        return jnp.sum(o.astype(jnp.float32) ** 2)
    return jax.grad(loss, argnums=(0, 1, 2)) if grad else loss


def _paged_chunk(q, kc, vc, table, start, true_len, block_c=128):
    return paged_chunk_attention(q, kc, vc, table, start, true_len,
                                 block_c=block_c, interpret=False)


def _paged_chunk_shapes(c, h, kvh, hd, mb, nb):
    return [((c, h, hd), bf16)] + [((nb, kvh, BS, hd), bf16)] * 2 \
        + [((mb,), i32), ((), i32), ((), i32)]


# the chunk kernel at the long-prompt cells' calls (ISSUE 58): chunk
# tokens, query heads, KV heads, head dim, table entries, pool blocks, the
# engine's paged_block_c (0 = "auto": the tile is the shape rule's) -> the
# (tokens, KV heads, table entries) of a grid step
PAGED_CHUNK_CELLS = {
    "cell12_solar_open2": ((1024, 64, 8, 128, 528, 8192, 0), (64, 2, 8)),
    "cell9_olmo_hybrid": ((1024, 30, 30, 128, 136, 1024, 0), (128, 6, 8)),
    "cell4_opt1.3b_pinned": ((256, 32, 32, 64, 32, 128, 64), (64, 16, 8)),
}


def _paged_decode(q, kc, vc, tb, ln):
    return paged_decode_attention(q, kc, vc, tb, ln, interpret=False)


def _paged_decode_shapes(b, kvh, hd, mb, nb):
    return [((b, kvh, hd), bf16)] + [((nb, kvh, BS, hd), bf16)] * 2 \
        + [((b, mb), i32), ((b,), i32)]


# the decode kernel at the serving cells' shapes (slots, KV heads, head dim,
# table entries, pool blocks): GPT-2 medium's, OPT-1.3B's 32 heads, OLMoE's
# head dim 128 behind a 64-entry table
PAGED_DECODE_CELLS = {
    "gpt2m_chat": (32, 16, 64, 16, 320),
    "opt1.3b_docs": (16, 32, 64, 32, 128),
    "olmoe_chat": (32, 16, 128, 64, 512),
}

def _gdn_step(q, k, v, log_a, b, ssm, active):
    return step_rule_kernel(q, k, v, log_a, b, ssm, live_slot_list(active),
                            interpret=False)


# the gated delta rule at Olmo-Hybrid-7B's widths (30 heads, dk 96, dv 192,
# float32): the cell's 1,024-token chunk call and its 16 slots' decode step
GDN_H, GDN_DK, GDN_DV, GDN_T, GDN_SLOTS = 30, 96, 192, 1024, 16
f32 = jnp.float32


def _gdn_shapes(*lead):
    return [(lead + (GDN_H, GDN_DK), f32)] * 2 \
        + [(lead + (GDN_H, GDN_DV), f32)] + [(lead + (GDN_H,), f32)] * 2 \
        + [((lead[0], GDN_H, GDN_DK, GDN_DV), f32)]


# a latent layer's selected read at DeepSeek-V3.2-Exp's widths: cell 10's
# 1,024-token chunk of 128 heads (128 + 64 wide scores, 128 wide values)
# over the 264 x 64 keys of a table, latent rows of 512 + 64 in 640, the
# XLA read's 512-key passes; (rows, queries a row) lead the shapes
MLA_H, MLA_K = 128, 264 * 64


def _latent_read_shapes(b, c):
    return [((b, c, MLA_H, 192), bf16), ((b, MLA_K, 640), bf16),
            ((b, c, MLA_K), jnp.int8), ((MLA_H, 128, 512), bf16),
            ((MLA_H, 512, 128), bf16), ((b, c), i32), ((b,), i32)]


POOL = [((NB, H, BS, HD), bf16)] * 2
CASES = {
    # training: the headline's whole-sequence tile, and the config default
    "flash_fwd_bwd_1024x1024_bh1": (_flash_train(1024, 1), QKV_T),
    "flash_fwd_bwd_128x128_bh2": (_flash_train(128, 2), QKV_T),
    # keys of 192 beside values of 128, each at its own width (ISSUE 53)
    "flash_mla_fwd_8192_d192_dv128": (_flash_mla(False), MLA_TRAIN),
    "flash_mla_fwd_bwd_8192_d192_dv128": (_flash_mla(True), MLA_TRAIN),
    "fused_ce_unembed": (
        lambda h, w, t: unembed_logits_stats(h, w, t, block_m=512,
                                             block_n=512, interpret=False),
        [((B * 512, D), bf16), ((V, D), bf16), ((B * 512,), i32)]),
    # serving: decode step, split-fuse chunk, whole-prompt prefill buckets
    # (the last not a multiple of the q tile)
    "paged_decode": (_paged_decode, _paged_decode_shapes(B, H, HD, MB, NB)),
    "paged_chunk_c256": (
        _paged_chunk,
        [((256, H, HD), bf16)] + POOL + [((MB,), i32), ((), i32), ((), i32)]),
    "paged_prefill_c1024": (
        _paged_chunk,
        [((1024, H, HD), bf16)] + POOL + [((MB,), i32), ((), i32),
                                         ((), i32)]),
    "paged_prefill_c320": (
        _paged_chunk,
        [((320, H, HD), bf16)] + POOL + [((5,), i32), ((), i32), ((), i32)]),
    **{f"paged_chunk_{cell}": (
        functools.partial(_paged_chunk, block_c=shape[-1]),
        _paged_chunk_shapes(*shape[:-1]))
       for cell, (shape, _) in PAGED_CHUNK_CELLS.items()},
    # the gated delta rule's two kernels at cell 9's shapes
    "gdn_chunk_1024x30x96x192": (
        lambda *a: chunk_rule_kernel(*a, interpret=False),
        _gdn_shapes(1, GDN_T)),
    "gdn_step_16_slots": (
        _gdn_step, _gdn_shapes(GDN_SLOTS) + [((GDN_SLOTS,), jnp.bool_)]),
    # the step kernel with a gate a key channel at Solar-Open2's widths
    # (cell 12: 64 heads of 128 x 128, 32 slots)
    "kda_step_32_slots": (
        _gdn_step,
        [((32, 64, 128), f32)] * 4 + [((32, 64), f32),
                                      ((32, 64, 128, 128), f32),
                                      ((32,), jnp.bool_)]),
    # the latent read's kernel at cell 10's chunk, and at a prompt bucket
    # that two query tiles share (1,536 -> 2 x 768)
    "latent_read_c1024": (
        lambda *a: latent_chunk_attention(*a, key_tile=512, interpret=False),
        _latent_read_shapes(1, 1024)),
    "latent_read_c1536": (
        lambda *a: latent_chunk_attention(*a, key_tile=512, interpret=False),
        _latent_read_shapes(1, 1536)),
}


@pytest.mark.parametrize("name", sorted(CASES))
def test_kernel_compiles_for_v5e(v5e, name):
    fn, shapes = CASES[name]
    _compile(fn, shapes, v5e)


@pytest.mark.parametrize("cell", sorted(PAGED_DECODE_CELLS))
def test_paged_decode_is_the_custom_call_the_trace_reader_finds(v5e, cell):
    """The decode kernel's grid length is a device scalar: it must still
    lower to one Mosaic custom call whose output is (slots, KV heads, 1,
    head dim) and whose first operands are the int32 scalars — what
    ``perfbench/trace_names.json``'s ``paged_decode`` pattern looks for
    in a trace, where the event is named by the instruction with its
    operands' shapes."""
    b, kvh, hd, mb, nb = PAGED_DECODE_CELLS[cell]
    text = _compile(_paged_decode, _paged_decode_shapes(b, kvh, hd, mb, nb),
                    v5e)
    calls = [ln.strip() for ln in text.splitlines()
             if "tpu_custom_call" in ln and " custom-call(" in ln]
    assert len(calls) == 1, calls
    head = calls[0].split(" custom-call(")[0]
    operands = calls[0].split("operand_layout_constraints={")[1]
    operands = re.sub(r"\{[\d,]*\}", "", operands).split("}")[0]
    assert head.split(" = ")[1].startswith(f"bf16[{b},{kvh},1,{hd}]")
    assert operands.split(", ")[:3] == ["s32[]", f"s32[{b},{mb}]",
                                        f"s32[{b}]"]
    with open(os.path.join(os.path.dirname(__file__), "..", "..",
                           "perfbench", "trace_names.json")) as f:
        pattern = json.load(f)["kernels"]["paged_decode"]["pattern"]
    assert re.search(pattern, f"{head} custom-call({operands}), "
                     'custom_call_target="tpu_custom_call"')


@pytest.mark.parametrize("cell", sorted(PAGED_CHUNK_CELLS))
def test_paged_chunk_is_the_custom_call_the_trace_reader_finds(v5e, cell):
    """The chunk kernel's grid length is a device scalar too (ISSUE 58),
    and its step takes a block of the KV heads and several table entries:
    it must still lower to one Mosaic custom call whose output is the one
    rank-3 (KV heads, rows, head dim) array and whose first operands are
    the int32 scalars — ``perfbench/trace_names.json``'s ``paged_chunk``
    pattern, which ``paged_chunk_roofline`` finds the kernel by — and not
    one the ``paged_decode`` pattern takes for its own. The tile is the
    shape rule's."""
    (c, h, kvh, hd, mb, nb, block_c), tile = PAGED_CHUNK_CELLS[cell]
    from deepspeed_tpu.ops.pallas.paged_attention import chunk_tile
    assert chunk_tile(c, kvh, h // kvh, hd, BS, mb, bf16, block_c) == tile
    text = _compile(functools.partial(_paged_chunk, block_c=block_c),
                    _paged_chunk_shapes(c, h, kvh, hd, mb, nb), v5e)
    calls = [ln.strip() for ln in text.splitlines()
             if "tpu_custom_call" in ln and " custom-call(" in ln]
    assert len(calls) == 1, calls
    head = calls[0].split(" custom-call(")[0]
    operands = calls[0].split("operand_layout_constraints={")[1]
    operands = re.sub(r"\{[\d,]*\}", "", operands).split("}")[0]
    assert head.split(" = ")[1].startswith(
        f"bf16[{kvh},{c * (h // kvh)},{hd}]")
    assert operands.split(", ")[:3] == ["s32[]", f"s32[{mb}]", "s32[2]"]
    with open(os.path.join(os.path.dirname(__file__), "..", "..",
                           "perfbench", "trace_names.json")) as f:
        kernels = json.load(f)["kernels"]
    event = f"{head} custom-call({operands}), " \
        'custom_call_target="tpu_custom_call"'
    assert re.search(kernels["paged_chunk"]["pattern"], event)
    assert not re.search(kernels["paged_decode"]["pattern"], event)


# the decode kernel where a grid step takes several table entries (ISSUE
# 39): (slots, KV heads, query heads a KV head, head dim, table entries,
# pool blocks, window) -> the entries a step the shape rule gives
DECODE_RUNS = {
    # cell 7: layer 17's pool under the block tables, and a window ring
    "phi4flash_shared_kv": ((64, 10, 4, 128, 64, 2048, 0), 4),
    "phi4flash_window": ((64, 10, 4, 128, 64, 641, 512), 4),
    "olmoe": ((32, 16, 1, 128, 64, 512, 0), 2),
    # 32 heads of 64, rows padded to the lanes: one entry, by the pipeline
    "opt1.3b": ((16, 32, 1, 64, 32, 128, 0), 1),
}


def _decode_runs(b, kvh, g, hd, mb, nb, window, per_step):
    def fn(q, kc, vc, tb, ln):
        work = decode_work_list(ln, mb, BS, window, per_step=per_step)
        return paged_decode_attention(q, kc, vc, tb, ln, work=work,
                                      window=window, interpret=False)
    return fn, [((b, kvh * g, hd), bf16)] + [((nb, kvh, BS, hd), bf16)] * 2 \
        + [((b, mb), i32), ((b,), i32)]


@pytest.mark.parametrize("case", sorted(DECODE_RUNS))
def test_decode_kernel_takes_runs_of_entries(v5e, case):
    """At the rule's N the kernel lowers and its two buffers of N blocks
    fit VMEM (the chunk kernel's 32-head fault, ROADMAP S2, is what a
    buffer too many looks like), it is still one custom call with one
    output, and a pool left in HBM for the kernel's own copies is not
    copied on its way in (head dim 64 as a bare argument is
    ``test_kv_pools_keep_the_kernels_layout``'s)."""
    (b, kvh, g, hd, mb, nb, window), n = DECODE_RUNS[case]
    assert decode_entries_per_step(kvh, BS, hd, bf16, mb) == n
    text = _compile(*_decode_runs(b, kvh, g, hd, mb, nb, window, n), v5e)
    calls = [ln for ln in text.splitlines()
             if "tpu_custom_call" in ln and " custom-call(" in ln]
    assert len(calls) == 1
    assert calls[0].split(" = ")[1].startswith(f"bf16[{b},{kvh},{g},{hd}]")
    if n > 1:
        assert not re.findall(
            rf"= bf16\[{nb},{kvh},{BS},{hd}\]\S* copy\(", text)


def test_rows_under_the_lanes_cannot_be_cut_out_of_hbm(v5e):
    """Why ``decode_entries_per_step`` gives head dim 64 one entry a step:
    the copies that bring several are the kernel's own, out of a pool left
    in HBM, and Mosaic hands such an array over padded to whole tiles and
    refuses a slice of it that is not (jax 0.9.0). When this stops
    raising, the rule's ``d % 128`` line can go."""
    with pytest.raises(Exception, match="aligned to tiling"):
        _compile(*_decode_runs(32, 16, 1, 64, 16, 320, 0, 2), v5e)


def test_vmem_refusal_is_what_a_bad_tile_looks_like(v5e):
    """The failure kind this file exists to catch: whole-sequence flash
    tiles with two (batch, head) instances per grid step do not fit VMEM.
    The autotune search space proposes full-T blocks only with
    block_h=1 (autotuning/kernel_registry.py), so this is an example,
    not a repair to make."""
    with pytest.raises(Exception, match="(?i)vmem|RESOURCE_EXHAUSTED"):
        _compile(_flash_train(1024, 2), QKV_T, v5e)


def test_host_staging_compiles_to_the_host_memory_space(v5e, monkeypatch):
    """runtime/swap_tensor/host_stage.py on its TPU arm (this process sees
    the CPU, where staging is an identity — so the arm is chosen here, in
    the test): the round trip through ``jax.memory.Space.Host`` compiles
    for the v5e and the staged value carries the host space, S(5)."""
    from deepspeed_tpu.runtime.swap_tensor import host_stage
    monkeypatch.setattr(host_stage, "host_memory_kind",
                        lambda: "pinned_host")
    x = jax.ShapeDtypeStruct((1024, 1024), bf16, sharding=v5e)
    compiled = jax.jit(lambda v: host_stage.to_device(
        host_stage.to_host(v * 2)) + 1).lower(x).compile()
    assert "S(5)" in compiled.as_text()



# ------------------------------------------------- the KV pools' layout
# The serving programs as engine_v2 builds them (donated cache in its
# boundary shape, merged on the way in and split on the way out), two
# layers deep at GPT-2 350M widths: the pools must enter, stay and leave
# in the row-major layout the paged kernels read. A whole-pool ``copy``
# here is a relayout the chip pays for every layer of every step
# (PERF.md, PR 25).
POOL_NB, SLOTS, STEPS = 320, 32, 2


def _serving_model():
    from deepspeed_tpu.models import GPT2, GPT2Config
    model = GPT2(GPT2Config(n_layer=2, n_head=H, d_model=D, max_seq_len=T,
                            vocab_size=V, dtype="bfloat16"))
    model._paged_kernel, model._paged_block_c = True, 64
    return model


def _decode_dispatch(model):
    def decode(params, cache, tokens, lengths, tables):
        toks, pools = [], as_pools(cache)
        for _ in range(STEPS):
            logits, pools = model.apply_paged_decode(
                params, tokens, lengths, pools, tables)
            tokens = jnp.argmax(logits, axis=-1).astype(i32)
            lengths = lengths + 1
            toks.append(tokens)
        return jnp.stack(toks), like_boundary(pools, cache)
    return decode, [((SLOTS,), i32), ((SLOTS,), i32), ((SLOTS, MB), i32)]


def _chunk_program(model):
    def chunk(params, cache, ids, tb, to, start, tlen, table):
        logits, pools = model.apply_paged_chunk(
            params, ids, as_pools(cache), tb, to, start, tlen, table)
        return jnp.argmax(logits, axis=-1), like_boundary(pools, cache)
    return chunk, [((1, 256), i32), ((256,), i32), ((256,), i32),
                   ((), i32), ((), i32), ((MB,), i32)]


@pytest.mark.parametrize("program", [_decode_dispatch, _chunk_program])
def test_kv_pools_keep_the_kernels_layout(v5e, monkeypatch, program):
    import re
    # the code under test asks the backend whether its kernels are
    # kernels; this process sees the CPU, so the test answers for it
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    model = _serving_model()
    dims = pool_block_dims(POOL_NB, HD, kernel_layout=True)
    assert len(dims) > 1 and np.prod(dims) == POOL_NB
    params = jax.tree.map(
        lambda x: jax.ShapeDtypeStruct(x.shape, bf16, sharding=v5e),
        jax.eval_shape(model.init, jax.random.key(0)))
    cache = jax.tree.map(
        lambda x: jax.ShapeDtypeStruct(dims + x.shape[1:], x.dtype,
                                       sharding=v5e),
        jax.eval_shape(lambda: model.init_paged_cache(POOL_NB, BS,
                                                      dtype=bf16)))
    cache_sh = jax.tree.map(lambda x: x.sharding, cache)
    fn, shapes = program(model)
    rest = [jax.ShapeDtypeStruct(s, d, sharding=v5e) for s, d in shapes]
    compiled = jax.jit(
        fn, donate_argnums=(1,),
        in_shardings=(None, cache_sh) + (None,) * len(rest),
        out_shardings=(None, cache_sh)).lower(params, cache, *rest).compile()
    text = compiled.as_text()
    assert "tpu_custom_call" in text
    lead = "|".join((str(POOL_NB), ",".join(map(str, dims))))
    pool = rf"bf16\[(?:{lead}),{H},{BS},{HD}\]"
    assert not re.findall(rf"= {pool}\S* copy\(", text)
    entry = text[text.index("entry_computation_layout="):].split("\n")[0]
    layouts = re.findall(pool + r"\{([\d,]+)", entry)
    row_major = ",".join(map(str, reversed(range(len(dims) + 3))))
    assert len(layouts) == 8 and set(layouts) == {row_major}  # in and out
    one_pool = POOL_NB * H * BS * HD * 2
    assert compiled.memory_analysis().temp_size_in_bytes < one_pool


def test_cell3_decode_step_writes_live_rows_only(v5e, monkeypatch):
    """One decode step of ``serve-gpt2m-chat``'s program (24 layers, 32
    slots, 320 blocks) for the described v5e: a write and a decode kernel
    a layer, the write's grid length an operand (``s32[]``, the live rows'
    count) beside the three (N + 1)-entry lists and not the constant 32 it
    was, and nothing of a pool's size made by anything but the aliased
    kernels (PERF.md, PR 29)."""
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    from deepspeed_tpu.models import GPT2, GPT2Config
    n_layer = 24
    model = GPT2(GPT2Config(n_layer=n_layer, n_head=H, d_model=D,
                            max_seq_len=T, vocab_size=V, dtype="bfloat16"))
    model._paged_kernel, model._paged_block_c = True, 64
    dims = pool_block_dims(POOL_NB, HD, kernel_layout=True)
    params = jax.tree.map(
        lambda x: jax.ShapeDtypeStruct(x.shape, bf16, sharding=v5e),
        jax.eval_shape(model.init, jax.random.key(0)))
    cache = jax.tree.map(
        lambda x: jax.ShapeDtypeStruct(dims + x.shape[1:], x.dtype,
                                       sharding=v5e),
        jax.eval_shape(lambda: model.init_paged_cache(POOL_NB, BS,
                                                      dtype=bf16)))
    cache_sh = jax.tree.map(lambda x: x.sharding, cache)

    def decode(params, cache, tokens, lengths, tables):
        logits, pools = model.apply_paged_decode(
            params, tokens, lengths, as_pools(cache), tables)
        return (jnp.argmax(logits, axis=-1).astype(i32),
                like_boundary(pools, cache))

    rest = [jax.ShapeDtypeStruct(s, i32, sharding=v5e)
            for s in ((SLOTS,), (SLOTS,), (SLOTS, MB))]
    text = jax.jit(
        decode, donate_argnums=(1,),
        in_shardings=(None, cache_sh) + (None,) * len(rest),
        out_shardings=(None, cache_sh)).lower(params, cache,
                                              *rest).compile().as_text()
    calls = [ln for ln in text.split("\n")
             if 'custom_call_target="tpu_custom_call"' in ln]
    assert len(calls) == 2 * n_layer
    pool = rf"bf16\[{POOL_NB},{H},{BS},{HD}\]"
    writes = [ln for ln in calls
              if re.search(rf"= \({pool}\S*, {pool}\S*\) custom-call", ln)]
    assert len(writes) == n_layer
    for ln in writes:
        operands = ln[ln.index("operand_layout_constraints={"):]
        assert operands.startswith(
            "operand_layout_constraints={"
            f"s32[], s32[{SLOTS + 1}]{{0}}, s32[{SLOTS + 1}]{{0}}, "
            f"s32[{SLOTS + 1}]{{0}}, "), operands[:160]
    lead = "|".join((str(POOL_NB), ",".join(map(str, dims))))
    made_by = set(re.findall(
        rf"= \(?bf16\[(?:{lead}),{H},{BS},{HD}\]\S* (?:bf16\S* )?([\w-]+)\(",
        text))
    assert "custom-call" in made_by
    assert made_by <= {"custom-call", "bitcast", "get-tuple-element",
                       "parameter"}, made_by


# OLMoE-1B-7B at the published widths of a layer (hidden 2048, 16 heads of
# 128, experts of width 1024, 8 per token), two layers deep with 16 of the
# 64 experts so that it compiles in seconds. ``lax.ragged_dot`` (a Mosaic
# fusion on the TPU) and the Pallas grouped kernel cannot read a layer's
# slice of a stacked (L, E, D, F) array in place: the compiled program
# copies it, a weight-sized temporary per layer per step, and the published
# model does not fit its chip (ISSUE 26: 9.34 GB of temporaries at 12
# layers). The served tree keeps each layer's experts as operands of their
# own; at head dim 128 the pools keep the plain block axis.
MOE_E, MOE_T = 16, 256


def _olmoe():
    from deepspeed_tpu.models import OLMoE, OLMoEConfig
    model = OLMoE(OLMoEConfig(n_layer=2, num_experts=MOE_E))
    model._paged_kernel, model._paged_block_c = True, "auto"
    return model


def _olmoe_decode(model):
    def decode(params, cache, tokens, lengths, tables):
        logits, cache = model.apply_paged_decode(
            params, tokens, lengths, cache, tables)
        return jnp.argmax(logits, axis=-1).astype(i32), cache
    mb = model.config.max_seq_len // BS
    return decode, [((SLOTS,), i32), ((SLOTS,), i32), ((SLOTS, mb), i32)]


def _olmoe_prefill(model):
    def prefill(params, cache, ids, tb, to, length):
        logits, cache = model.apply_paged_prefill(
            params, ids, cache, tb, to, length)
        return jnp.argmax(logits, axis=-1), cache
    return prefill, [((1, MOE_T), i32), ((MOE_T,), i32), ((MOE_T,), i32),
                     ((), i32)]


def _olmoe_compiled(model, tree, program, v5e, POOL_NB=POOL_NB):
    cfg = model.config
    assert pool_block_dims(POOL_NB, cfg.d_head, kernel_layout=True) \
        == (POOL_NB,)                      # head dim 128: no split axis
    params = jax.tree.map(
        lambda x: jax.ShapeDtypeStruct(x.shape, bf16, sharding=v5e), tree)
    cache = jax.tree.map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=v5e),
        jax.eval_shape(lambda: model.init_paged_cache(POOL_NB, BS,
                                                      dtype=bf16)))
    fn, shapes = program(model)
    rest = [jax.ShapeDtypeStruct(s, d, sharding=v5e) for s, d in shapes]
    return jax.jit(fn, donate_argnums=(1,)).lower(
        params, cache, *rest).compile()


@pytest.mark.parametrize("program", [_olmoe_decode, _olmoe_prefill])
def test_olmoe_experts_are_read_in_place(v5e, monkeypatch, program):
    import re
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    model = _olmoe()
    cfg = model.config
    stacked = jax.eval_shape(model.init, jax.random.key(0))
    expert_bytes = cfg.n_layer * MOE_E * 3 * cfg.d_model * cfg.ffn_dim * 2
    served = _olmoe_compiled(
        model, jax.eval_shape(model.init_served, jax.random.key(0)),
        program, v5e)
    text = served.as_text()
    assert "tpu_custom_call" in text            # the paged kernels
    temp = served.memory_analysis().temp_size_in_bytes
    assert temp < 0.1 * expert_bytes, (temp, expert_bytes)
    weight = rf"bf16\[{MOE_E},(?:{cfg.d_model},{cfg.ffn_dim}" \
             rf"|{cfg.ffn_dim},{cfg.d_model})\]"
    assert not re.findall(rf"= {weight}\S* (?:copy|slice|fusion)\(", text)
    pool = rf"bf16\[{POOL_NB},{cfg.n_kv_heads},{BS},{cfg.d_head}\]"
    assert not re.findall(rf"= {pool}\S* copy\(", text)
    # what the guard is for: the same program over the stacked training
    # tree materialises each layer's experts
    if program is _olmoe_decode:
        stacked_temp = _olmoe_compiled(
            model, stacked, program, v5e).memory_analysis().temp_size_in_bytes
        assert stacked_temp > 0.3 * expert_bytes, (stacked_temp,
                                                   expert_bytes)


# The cell's own programs (ISSUE 32): all 64 experts, two layers deep, 32
# slots and the cell's 512-block pool. "auto" on a TPU takes the grouped
# products from their shape (``sharded_moe.resolve_grouped_params``): a
# decode step's 4 rows a group and every prefill bucket's 32-128 are the
# forward kernel, one Mosaic launch a layer call whose weight tiles are the
# expert's whole (2048, 1024) matrices.
OLMOE_E, OLMOE_NB, OLMOE_STEPS, OLMOE_LAYERS = 64, 512, 8, 12
OLMOE_WHOLE_GB = 13.9       # PR 26 compiled 13.88 with the ragged products


def _olmoe_decode_x8(model):
    step, shapes = _olmoe_decode(model)

    def decode(params, cache, tokens, lengths, tables):
        toks = []
        for _ in range(OLMOE_STEPS):
            tokens, cache = step(params, cache, tokens, lengths, tables)
            lengths = lengths + 1
            toks.append(tokens)
        return jnp.stack(toks), cache
    return decode, shapes


def _olmoe_prefill_of(T):
    def program(model):
        fn, _ = _olmoe_prefill(model)
        return fn, [((1, T), i32), ((T,), i32), ((T,), i32), ((), i32)]
    return program


def _nbytes(tree, dtype=None):
    return sum(x.size * jnp.dtype(dtype or x.dtype).itemsize
               for x in jax.tree.leaves(tree))


@pytest.mark.parametrize("T", [0, 256, 512, 768, 1024],
                         ids=lambda T: f"prefill{T}" if T else "decode_x8")
def test_olmoe_programs_take_the_expert_kernel(v5e, monkeypatch, T):
    from deepspeed_tpu.models import OLMoE, OLMoEConfig
    from deepspeed_tpu.ops.pallas._common import counting_calls
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    model = OLMoE(OLMoEConfig(n_layer=2, num_experts=OLMOE_E))
    model._paged_kernel, model._paged_block_c = True, "auto"
    cfg = model.config
    steps = 1 if T else OLMOE_STEPS
    with counting_calls() as counts:
        served = _olmoe_compiled(
            model, jax.eval_shape(model.init_served, jax.random.key(0)),
            _olmoe_prefill_of(T) if T else _olmoe_decode_x8, v5e, OLMOE_NB)
    # every call the kernel
    assert counts == {"expert": [cfg.n_layer * steps] * 2}
    text = served.as_text()
    weight = rf"bf16\[{OLMOE_E},(?:{cfg.d_model},{cfg.ffn_dim}" \
             rf"|{cfg.ffn_dim},{cfg.d_model})\]"
    # the expert products: one Mosaic call a layer call over the three
    # weight arrays in place, under the experts' scope, its output the
    # routed rows by the model width — two-dimensional, so that the trace
    # readers' patterns for the paged kernels (three and four dimensions,
    # perfbench/trace_names.json) cannot take it for one of theirs
    rows = (T or SLOTS) * cfg.moe_top_k
    calls = [ln for ln in text.split("\n")
             if 'custom_call_target="tpu_custom_call"' in ln
             and len(re.findall(weight, ln)) == 3]
    assert len(calls) == cfg.n_layer * steps
    for ln in calls:
        assert re.search(rf"= bf16\[{rows},{cfg.d_model}\]\S* custom-call\(",
                         ln), ln[:200]
        assert "dstpu.moe.experts" in ln
    assert not re.search(r"ragged[-_]dot", text)
    assert not re.findall(rf"= {weight}\S* (?:copy|slice|fusion)\(", text)
    temp = served.memory_analysis().temp_size_in_bytes
    layer_bytes = OLMOE_E * 3 * cfg.d_model * cfg.ffn_dim * 2
    assert temp < 0.1 * cfg.n_layer * layer_bytes, temp
    # the cell's 12 layers by arithmetic: a further layer is arguments
    # (its weights, its pools), not temporaries
    deep = OLMoE(OLMoEConfig(n_layer=OLMOE_LAYERS, num_experts=OLMOE_E))
    whole = temp + _nbytes(jax.eval_shape(
        deep.init_served, jax.random.key(0)), bf16) + _nbytes(jax.eval_shape(
            lambda: deep.init_paged_cache(OLMOE_NB, BS, dtype=bf16)))
    assert whole <= OLMOE_WHOLE_GB * 1e9, whole


# ------------------------------------------- Phi-4-mini-flash (ISSUE 30)
# the published widths: 10 head pairs of 128 lanes, 4 query heads a pair
P4_KV, P4_G, P4_W = 10, 4, 128


@pytest.mark.parametrize("block_c, tile", [(0, (64, 2, 8)),
                                           (128, (128, 2, 8)),
                                           (32, (32, 5, 8))])
def test_chunk_tile_under_gqa_folds(v5e, block_c, tile):
    """The chunk kernel folds a KV head's G query heads into its tile's
    rows: at 10 heads x 128 lanes a 128-token tile of every head is 512
    rows a head and asked for more VMEM than Mosaic's default gives a
    kernel, which refused every prefill of the cell's first compile (ISSUE
    30). Since ISSUE 58 a step takes a block of the heads and the call
    asks for the VMEM its blocks need: the shape rule's tile and the
    tokens an engine may pin compile, each with the heads and the entries
    the rule puts round it."""
    from deepspeed_tpu.models import paged
    from deepspeed_tpu.models.phi4flash import PHI4_MINI_FLASH, Phi4Flash
    geom = Phi4Flash(PHI4_MINI_FLASH).paged_geometry()
    assert (geom.n_kv_heads, geom.n_head // geom.n_kv_heads, geom.d_head) \
        == (P4_KV, P4_G, P4_W)
    assert paged._chunk_kernel(geom, 512, 64, BS) \
        == (False, (64, 2, 8))                               # on a CPU
    from deepspeed_tpu.ops.pallas.paged_attention import chunk_tile
    assert chunk_tile(512, P4_KV, P4_G, P4_W, BS, 64, bf16, block_c) == tile

    def chunk(q, kc, vc, table, start, true_len):
        return paged_chunk_attention(q, kc, vc, table, start, true_len,
                                     scale=1.0, window=512,
                                     block_c=block_c, interpret=False)

    _compile(chunk, _paged_chunk_shapes(512, P4_KV * P4_G, P4_KV, P4_W, 64,
                                        641), v5e)


def test_two_piece_product_keeps_its_rounding(v5e):
    """``phi4flash._mm`` hands a float32 activation to a bfloat16 weight
    as hi + lo. Written as a cast there and back, ``hi`` is excess
    precision to this compiler, which takes the round trip out and ``lo``
    with it (my chip run, PR 30); as ``reduce_precision`` it stays, and
    the product is over twice the rows."""
    from deepspeed_tpu.models.phi4flash import _mm
    args = [jax.ShapeDtypeStruct((64, 1, 2560), jnp.float32, sharding=v5e),
            jax.ShapeDtypeStruct((2560, 10240), bf16, sharding=v5e)]
    text = jax.jit(lambda x, w: _mm(x, w, "dstpu.mm.mlp")).lower(
        *args).compile().as_text()
    assert "reduce-precision(" in text
    assert re.search(r"bf16\[2,64,(1,)?2560\]", text)


# ------------------------------------------------ Olmo-Hybrid (ISSUE 41)
# the published widths: 30 K/V heads of 128 lanes, as many query heads;
# the cell's sizes (perfbench/traffic/longdoc-sat.json)
OH_KV, OH_W, OH_C, OH_SLOTS, OH_NB, OH_MB = 30, 128, 1024, 16, 1024, 136
V5E_GB = 15.75


def _geometry(n_head, n_kv_heads, d_head, block_c="auto"):
    from deepspeed_tpu.models import paged
    return paged.Geometry(
        n_head=n_head, n_kv_heads=n_kv_heads, d_head=d_head, dtype=bf16,
        scale=None, windows=(0,), alibi=False, alibi_inv_norm=False,
        alibi_bias=None, kernel="auto", block_c=block_c, kinds=(paged.KV,),
        ring_blocks=0)


@pytest.mark.parametrize("name, heads, C, tile", [
    ("cells-3-6-gpt2-medium", (16, 16, 64), 128, (128, 8, 8)),
    ("cell-4-opt-1.3b-pinned", (32, 32, 64, 64), 256, (64, 16, 8)),
    ("cells-5-8-olmoe", (16, 16, 128), 1024, (128, 8, 8)),
    ("cell-7-phi-4-mini-flash", (40, 10, 128), 512, (64, 2, 8)),
    ("cell-9-olmo-hybrid", (OH_KV, OH_KV, OH_W), OH_C, (128, 6, 8)),
    ("cell-12-solar-open2", (64, 8, 128), 1024, (64, 2, 8)),
], ids=lambda x: x if isinstance(x, str) else "")
def test_auto_chunk_tile_of_the_served_shapes(monkeypatch, name, heads, C,
                                              tile):
    """``"auto"`` reads the chunk kernel's tile off KV heads x G x head
    dim (``paged_attention.chunk_tile``, ISSUE 58): 128 rows a head but
    never under 64 tokens, then as many KV heads as keep q inside 256 KB,
    then 512 keys of table entries, eight at most (cell 4 pins its
    tokens, and the heads and entries follow)."""
    from deepspeed_tpu.models import paged
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    assert paged._chunk_kernel(_geometry(*heads), C, 64, BS) == (True, tile)


@pytest.mark.parametrize("block_c, tile", [(128, (128, 6, 8)),
                                           (64, (64, 15, 8))])
def test_chunk_tile_at_thirty_heads_of_128(v5e, monkeypatch, block_c, tile):
    """30 heads x 128 rows x 128 lanes asked for more VMEM than a kernel
    may have while a step took every head (ISSUE 41: the tile a cold
    winner cache gave; "auto" fell back to 64 rows). A step takes a block
    of the heads now, so the tokens an engine pins compile at the cell's C
    = 1024 over a table of 136 blocks, with the heads that fit beside
    them."""
    from deepspeed_tpu.models import paged
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    assert paged._chunk_kernel(
        _geometry(OH_KV, OH_KV, OH_W, block_c), OH_C, OH_MB, BS) \
        == (True, tile)

    def chunk(q, kc, vc, table, start, true_len):
        return paged_chunk_attention(q, kc, vc, table, start, true_len,
                                     block_c=block_c, interpret=False)

    _compile(chunk, _paged_chunk_shapes(OH_C, OH_KV, OH_KV, OH_W, OH_MB,
                                        OH_NB), v5e)


def _olmo_hybrid_programs(model):
    """The cell's three programs as the engine composes them: a chunk into
    one slot, 8 decode steps over every slot, and a chunk beside the
    engine's count of decode steps for its company in one (fused)."""
    def chunk(params, cache, ids, tb, to, start, n, table, slot):
        logits, cache = model.apply_paged_chunk(
            params, ids, cache, tb, to, start, n, table, slot)
        return jnp.argmax(logits, axis=-1), cache

    def decode(params, cache, tokens, lengths, tables, steps=8):
        toks = []
        for _ in range(steps):
            logits, cache = model.apply_paged_decode(
                params, tokens, lengths, cache, tables)
            tokens = jnp.argmax(logits, axis=-1).astype(i32)
            lengths = lengths + 1
            toks.append(tokens)
        return jnp.stack(toks), cache

    def fused(params, cache, ids, tb, to, start, n, table, slot, tokens,
              lengths, tables):
        c_tok, cache = chunk(params, cache, ids, tb, to, start, n, table,
                             slot)
        toks, cache = decode(params, cache, tokens, lengths, tables,
                             _FUSED_STEPS)
        return c_tok, toks, cache

    c = [((1, OH_C), i32), ((OH_C,), i32), ((OH_C,), i32), ((), i32),
         ((), i32), ((OH_MB,), i32), ((), i32)]
    d = [((OH_SLOTS,), i32), ((OH_SLOTS,), i32), ((OH_SLOTS, OH_MB), i32)]
    return {"chunk": (chunk, c), "decode_x8": (decode, d),
            "fused": (fused, c + d)}


def test_cell9_decode_step_updates_live_states_in_place(v5e, monkeypatch):
    """One decode step of one period of ``serve-olmohybrid-longdoc-sat``
    (three gated delta-rule layers and a full layer, 16 slots) for the
    described v5e: each linear layer's ``ssm`` leaf goes through the step
    kernel aliased (the program's donated argument is its output), and
    nothing of a leaf's size is made by anything else: no ``select`` puts
    dead slots' rows back, no copy (ISSUE 42; the pattern of
    ``test_cell3_decode_step_writes_live_rows_only``)."""
    import dataclasses
    from deepspeed_tpu.models.olmo_hybrid import OLMO_HYBRID_7B, OlmoHybrid
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    model = OlmoHybrid(dataclasses.replace(
        OLMO_HYBRID_7B, layer_types=OLMO_HYBRID_7B.layer_types[:4],
        max_seq_len=OH_MB * BS))
    model._paged_kernel, model._paged_block_c = "auto", "auto"
    params = jax.tree.map(
        lambda x: jax.ShapeDtypeStruct(
            x.shape, bf16 if x.ndim > 1 else x.dtype, sharding=v5e),
        jax.eval_shape(model.init, jax.random.key(0)))
    cache = jax.tree.map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=v5e),
        jax.eval_shape(lambda: model.init_paged_cache(
            OH_NB, BS, dtype=bf16, slots=OH_SLOTS)))

    def decode(params, cache, tokens, lengths, tables):
        logits, cache = model.apply_paged_decode(
            params, tokens, lengths, cache, tables)
        return jnp.argmax(logits, axis=-1).astype(i32), cache

    rest = [jax.ShapeDtypeStruct(s, i32, sharding=v5e)
            for s in ((OH_SLOTS,), (OH_SLOTS,), (OH_SLOTS, OH_MB))]
    text = jax.jit(decode, donate_argnums=(1,)).lower(
        params, cache, *rest).compile().as_text()
    leaf = rf"f32\[{OH_SLOTS},(?:{GDN_H}|5,6),{GDN_DK},{GDN_DV}\]"
    calls = [ln for ln in text.split("\n")
             if 'custom_call_target="tpu_custom_call"' in ln]
    steps = [ln for ln in calls if re.search(rf"{leaf}\S*\) custom-call", ln)]
    assert len(steps) == 3 and len(calls) == 3 + 2
    for ln in steps:
        # the grid: the live slots' list and its length, made on the device
        operands = ln[ln.index("operand_layout_constraints={"):]
        assert operands.startswith(
            f"operand_layout_constraints={{s32[], s32[{OH_SLOTS + 1}]{{0}}, "
        ), operands[:120]
        assert "output_to_operand_aliasing={" in ln
    made_by = set(re.findall(
        rf"= \(?(?:f32\S* )?{leaf}\S* (?:f32\S* )?([\w-]+)\(", text))
    assert "custom-call" in made_by
    assert made_by <= {"custom-call", "bitcast", "get-tuple-element",
                       "parameter"}, made_by
    # every leaf of the donated cache is its own output
    header = text[:text.index("\n")]
    assert header.count("-alias)") == len(jax.tree.leaves(cache))


@pytest.mark.parametrize("program", ["chunk", "decode_x8", "fused"])
def test_olmo_hybrid_programs_fit_the_chip(v5e, monkeypatch, program):
    """One period (three gated delta-rule layers and a full-attention
    layer) of the published widths, the cell's 16 slots, 1,024-block pool
    and 1,024-token chunk: the program compiles for a v5e with the paged
    kernels in it (the chunk kernel at the "auto" tile), and its
    temporaries beside the whole cell's arguments (16 layers' weights and
    cache: a further layer is arguments, not temporaries) stay inside the
    chip. The 16-layer programs themselves compiled to 13.5 / 13.0 / 13.6
    GB (sandbox compile, PR 41: PERF.md section 4)."""
    import dataclasses
    from deepspeed_tpu.models.olmo_hybrid import OLMO_HYBRID_7B, OlmoHybrid
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    cell = dataclasses.replace(
        OLMO_HYBRID_7B, layer_types=OLMO_HYBRID_7B.layer_types[:16],
        max_seq_len=OH_MB * BS)
    period = dataclasses.replace(cell, layer_types=cell.layer_types[:4])

    def trees(cfg):
        model = OlmoHybrid(cfg)
        model._paged_kernel, model._paged_block_c = "auto", "auto"
        params = jax.eval_shape(model.init, jax.random.key(0))
        params = jax.tree.map(lambda x: jax.ShapeDtypeStruct(
            x.shape, bf16 if x.ndim > 1 else x.dtype, sharding=v5e), params)
        cache = jax.tree.map(
            lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=v5e),
            jax.eval_shape(lambda: model.init_paged_cache(
                OH_NB, BS, dtype=bf16, slots=OH_SLOTS)))
        return model, params, cache

    model, params, cache = trees(period)
    fn, shapes = _olmo_hybrid_programs(model)[program]
    rest = [jax.ShapeDtypeStruct(s, d, sharding=v5e) for s, d in shapes]
    compiled = jax.jit(fn, donate_argnums=(1,)).lower(
        params, cache, *rest).compile()
    calls = compiled.as_text().count('custom_call_target="tpu_custom_call"')
    # a program pass: the full layer's K/V write and paged read, and the
    # three linear layers' rule (the chunk kernel, the step kernel)
    assert calls == {"chunk": 5, "decode_x8": 40,
                     "fused": 5 * (1 + _FUSED_STEPS)}[program]
    temp = compiled.memory_analysis().temp_size_in_bytes
    _, whole_params, whole_cache = trees(cell)
    assert abs(_nbytes(whole_params) - 8.20e9) < 0.01e9
    # a slot's state is 12 x (2.21 MB + the conv tail's 69 KB) as counted,
    # a third more as the chip tiles 192 lanes
    assert abs(_nbytes(whole_cache) - 4.46e9) < 0.01e9
    whole = temp + _nbytes(whole_params) + 1.05 * _nbytes(whole_cache)
    assert whole <= (V5E_GB - 1.5) * 1e9, (temp, whole)


# serve-dsv32-longctx-sat: 16 slots, 4,096 blocks of 64 tokens, 264 table
# entries a sequence (16,896 served positions), 1,024-token chunks
DS_SLOTS, DS_NB, DS_MB, DS_C = 16, 4096, 264, 1024


def _deepseek_programs(model):
    """The cell's three programs as the engine composes them: a chunk, 8
    decode steps over every slot, and a chunk beside the engine's count of
    decode steps for its company in one (fused)."""
    def chunk(params, cache, ids, tb, to, start, n, table):
        logits, cache = model.apply_paged_chunk(
            params, ids, cache, tb, to, start, n, table)
        return jnp.argmax(logits, axis=-1), cache

    def decode(params, cache, tokens, lengths, tables, steps=8):
        toks = []
        for _ in range(steps):
            logits, cache = model.apply_paged_decode(
                params, tokens, lengths, cache, tables)
            tokens = jnp.argmax(logits, axis=-1).astype(i32)
            lengths = lengths + 1
            toks.append(tokens)
        return jnp.stack(toks), cache

    def fused(params, cache, ids, tb, to, start, n, table, tokens, lengths,
              tables):
        c_tok, cache = chunk(params, cache, ids, tb, to, start, n, table)
        toks, cache = decode(params, cache, tokens, lengths, tables,
                             _FUSED_STEPS)
        return c_tok, toks, cache

    c = [((1, DS_C), i32), ((DS_C,), i32), ((DS_C,), i32), ((), i32),
         ((), i32), ((DS_MB,), i32)]
    d = [((DS_SLOTS,), i32), ((DS_SLOTS,), i32), ((DS_SLOTS, DS_MB), i32)]
    return {"chunk": (chunk, c), "decode_x8": (decode, d),
            "fused": (fused, c + d)}


@pytest.mark.parametrize("program", ["chunk", "decode_x8", "fused"])
def test_deepseek_share_programs_fit_the_chip(v5e, monkeypatch, program):
    """The dense layer and one expert layer of the published widths (16 of
    256 experts held, an eighth of the vocabulary), the cell's 16 slots,
    4,096-block latent pools and 1,024-token chunk: the program compiles for
    a v5e with the forward grouped kernel in its expert layer, keeps the
    latent pools in place (a pool whose rows were 576 wide was copied whole
    into and out of every program: its rows are 640), and its temporaries
    beside the whole cell's arguments (five layers' weights and pools: a
    further layer is arguments, not temporaries) stay inside the chip. The
    five-layer chunk and fused programs themselves compiled to 12.29 / 12.58 GB
    (sandbox compile, PR 43: PERF.md section 4). A chunk's selected read
    is the kernel of ``ops/pallas/latent_attention.py``, a layer (PR 44):
    no array of heads x queries x a block of keys is left in the program,
    and its temporaries are under what the XLA read's were."""
    import dataclasses
    from deepspeed_tpu.models.deepseek_v32 import DEEPSEEK_V32, DeepseekV32
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    cell = dataclasses.replace(
        DEEPSEEK_V32, n_layer=5, first_k_dense=1, experts_held=16,
        vocab_size=16160, max_seq_len=DS_MB * BS)
    two = dataclasses.replace(cell, n_layer=2)

    def trees(cfg):
        model = DeepseekV32(cfg)
        model._paged_kernel, model._paged_block_c = "auto", "auto"
        params = jax.tree.map(
            lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=v5e),
            jax.eval_shape(model.init, jax.random.key(0)))
        cache = jax.tree.map(
            lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=v5e),
            jax.eval_shape(lambda: model.init_paged_cache(DS_NB, BS,
                                                          dtype=bf16)))
        return model, params, cache

    model, params, cache = trees(two)
    fn, shapes = _deepseek_programs(model)[program]
    rest = [jax.ShapeDtypeStruct(s, d, sharding=v5e) for s, d in shapes]
    compiled = jax.jit(fn, donate_argnums=(1,)).lower(
        params, cache, *rest).compile()
    text = compiled.as_text()
    # one expert layer a pass through the forward grouped kernel, and a
    # chunk's two latent layers through the selected read's
    assert text.count('custom_call_target="tpu_custom_call"') \
        == {"chunk": 1 + 2, "decode_x8": 8,
            "fused": 1 + _FUSED_STEPS + 2}[program]
    # the XLA read's scores, (128 heads, 1,024 queries, 512 keys) float32
    assert not re.search(r"f32\[(1,)?128,1024,512\]", text)
    # no whole-pool copy of a latent pool
    assert not re.search(
        r"bf16\[%d,%d,640\]\{[^}]*\} copy\(" % (DS_NB, BS), text)
    temp = compiled.memory_analysis().temp_size_in_bytes
    # what 6e5a04b (PR 43, the XLA read) compiled to, two layers
    assert temp <= {"chunk": 568273408, "decode_x8": 82927616,
                    "fused": 778774016}[program]
    _, whole_params, whole_cache = trees(cell)
    assert abs(_nbytes(whole_params) - 9.286e9) < 0.01e9
    assert abs(_nbytes(whole_cache) - 2.349e9) < 0.01e9
    whole = temp + _nbytes(whole_params) + _nbytes(whole_cache)
    assert whole <= (V5E_GB - 1.5) * 1e9, (temp, whole)


# train-kanana2-share-8k: one chip's share of Kanana-2-30B-A3B, 2 x 8,192
# tokens a step (perfbench/traffic/train-z2-micro2-8k.json)
def test_kanana_share_step_fits_the_chip(v5e, monkeypatch):
    """The cell's training step at the published widths, the job file's own
    ``model_overrides``, one dense and ONE sparse layer (16 of 128 experts
    held, an eighth of the vocabulary), 2 x 8,192 tokens: loss, gradients,
    clipping and AdamW compile for a v5e with the flash kernel forward and
    backward under ``dstpu.attn.mla`` in every layer (a whole sequence's K
    and V in VMEM: the call asks for more than Mosaic's 16 MB default), and
    its temporaries beside the whole cell's state (five layers: float32
    master and moments, bfloat16 parameters and gradients) stay inside the
    chip. The five-layer step itself compiled to 14.86 GB (sandbox compile,
    PR 47: PERF.md section 4); with the flash residuals kept (``save_flash``)
    to 16.06 GB, which is why the job file says ``nothing_saveable``."""
    import dataclasses
    from deepspeed_tpu.models.deepseek_v3 import (KANANA_2_30B_A3B,
                                                  DeepseekV3)
    from deepspeed_tpu.ops.optimizers import FusedAdam
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    with open(os.path.join(os.path.dirname(__file__), "..", "..",
                           "perfbench", "traffic",
                           "train-z2-micro2-8k.json")) as f:
        job = json.load(f)
    micro, T = job["micro_batch_per_chip"], job["seq_len"]
    cell = dataclasses.replace(
        KANANA_2_30B_A3B, n_layer=5, experts_held=16, vocab_size=16032,
        max_seq_len=T, dtype="bfloat16", **job["model_overrides"])
    model = DeepseekV3(dataclasses.replace(cell, n_layer=2))
    opt = FusedAdam(lr=2e-4, weight_decay=0.01)
    buffers = model.buffer_names()

    def on_chip(tree, dtype, buffer):
        """``tree``'s leaves as ``dtype`` shapes on the chip, a buffer leaf
        as ``buffer`` says."""
        return jax.tree_util.tree_map_with_path(
            lambda path, x: buffer(x) if path[-1].key in buffers
            else jax.ShapeDtypeStruct(x.shape, dtype, sharding=v5e), tree)

    shapes = jax.eval_shape(model.init, jax.random.key(0))
    params = on_chip(shapes, bf16, lambda x: jax.ShapeDtypeStruct(
        x.shape, x.dtype, sharding=v5e))
    master = on_chip(shapes, jnp.float32, lambda x: None)

    def step(params, master, m, v, n, ids):
        loss, grads = jax.value_and_grad(
            lambda p: model.loss(p, {"input_ids": ids}))(params)
        grads = jax.tree_util.tree_map_with_path(
            lambda path, g: None if path[-1].key in buffers
            else g.astype(jnp.float32), grads)
        norm = jnp.sqrt(sum(jnp.sum(jnp.square(g))
                            for g in jax.tree.leaves(grads)))
        grads = jax.tree.map(lambda g: g * jnp.minimum(1.0, 1.0 / norm),
                             grads)
        new_master, state = opt.update(grads, {"step": n, "m": m, "v": v},
                                       master, lr=2e-4)
        return (jax.tree.map(lambda x: x.astype(bf16), new_master),
                new_master, state["m"], state["v"], loss)

    compiled = jax.jit(step, donate_argnums=(1, 2, 3)).lower(
        params, master, master, master,
        jax.ShapeDtypeStruct((), i32, sharding=v5e),
        jax.ShapeDtypeStruct((micro, T), i32, sharding=v5e)).compile()
    text = compiled.as_text()
    calls = re.findall(r'custom_call_target="tpu_custom_call"[^\n]*'
                       r'op_name="([^"]*)"', text)
    mla = [c for c in calls if "dstpu.attn.mla" in c]
    # a layer: the forward, its recomputation, the backward
    assert len(mla) == 3 * 2, calls
    assert sum("transpose(jvp(" in c for c in mla) == 2 * 2
    temp = compiled.memory_analysis().temp_size_in_bytes
    state = 16 * cell.num_params()       # 2 + 2 + 4 + 4 + 4 bytes each
    assert abs(state - 9.215e9) < 0.01e9
    # what 5 layers add to the 2-layer step's temporaries: three more sparse
    # layers' gradients and the inputs a step keeps of them
    sparse = sum(cell.layer_params()[::2])
    more = 3 * (2 * sparse + micro * T * cell.d_model * 2)
    # the absent experts' rows are never gathered (2,692,935,168 bytes:
    # sandbox compile, PR 56; 4,251,410,944 with the one pass over every
    # routed row, PR 53, and 4,891,139,072 with V padded besides, PR 51)
    assert temp <= 2.75e9, temp
    assert 14 * cell.num_params() + temp + more <= V5E_GB * 1e9, (temp, more)
