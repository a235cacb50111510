"""What ``train_moe_experts_roofline`` divides by since PR 55: the rows the
model's own router sent the held experts (``pbench/mla_moe.
held_rows_program`` over ``builders/deepseek_v3.blocks``) and the floor
built from them (``held_rows_work``), on the CPU, against the real model at
the cell's rehearsal sizes (128 routed experts of which 16 are held, 6 a
token).

    JAX_PLATFORMS=cpu python3 -m pytest perfbench/tests -q

(ISSUE 55 asked for these beside ``tests/unit/test_deepseek_v3.py``'s; a
benchmark PR adds files under ``perfbench/`` only, so they are here and the
driver's ``pytest tests/`` does not count them.)
"""

import json
import os
import subprocess
import sys
import types

import numpy as np
import pytest

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, HERE)                       # pbench/, runners/
sys.path.insert(0, os.path.dirname(HERE))      # deepspeed_tpu

from pbench import common, mla_moe             # noqa: E402

CELL = "train-kanana2-share-8k"


@pytest.fixture(scope="module")
def cell():
    _, _, cfg, job = common.load_cell(CELL, rehearse=True)
    builder = common.load_module("builders", cfg["builder"])
    return builder, cfg, job, builder.sizes(cfg)


@pytest.fixture(scope="module")
def sizes():
    """The cell's own sizes (the floor's arithmetic needs no model)."""
    _, _, cfg, _ = common.load_cell(CELL)
    return common.load_module("builders", cfg["builder"]).sizes(cfg)


def _model(cell):
    builder, cfg, job, _ = cell
    return builder.model(cfg, **job["model_overrides"], dtype="float32")


def _batches(s, seed, n=2, rows=2, T=256):
    rng = np.random.default_rng(seed)
    return [{"input_ids": rng.integers(0, s["vocab_size"], (rows, T),
                                       dtype=np.int32)} for _ in range(n)]


def _counts(cell, model, params, batches):
    s = cell[3]
    program = mla_moe.held_rows_program(cell[0].blocks(model), s)
    counts = [np.asarray(program(params, b["input_ids"])) for b in batches]
    assert all(c.shape == (s["n_sparse"], s["n_experts"]) for c in counts)
    return counts


def test_the_blocks_are_the_programs_forward(cell):
    """``builders/deepseek_v3.blocks`` copies ``DeepseekV3.apply``'s rotary
    tables and leaves ``jax.checkpoint`` off: the same hidden state, bit
    for bit."""
    import jax
    s = cell[3]
    model = _model(cell)
    params = jax.jit(model.init)(jax.random.key(7))
    ids = _batches(s, 7, n=1)[0]["input_ids"]
    np.testing.assert_array_equal(
        jax.jit(cell[0].blocks(model))(params, ids),
        jax.jit(lambda p, i: model.apply(p, i, return_hidden=True))(
            params, ids))


def test_a_tap_that_sees_no_routing_says_so(cell):
    """A program that no longer looks ``route_topk`` up in its module at
    every call leaves the tap blind: a sentence, not an empty stack."""
    program = mla_moe.held_rows_program(lambda p, ids: ids, cell[3])
    with pytest.raises(common.CheckFailed, match="saw 0 routings"):
        program({}, np.zeros((1, 8), np.int32))


def test_seeded_weights_route_like_an_even_router(cell):
    """Seeded gate weights over uniform ids: top_k x held / published rows
    a token, to a few percent over six seeds' ~37,000 picks; a single seed
    favours some experts (at this width by up to a fifth of the share's
    rows), which is why the rows are counted and not assumed."""
    import jax
    s = cell[3]
    model = _model(cell)
    assert mla_moe.held_experts_per_token(s) == 0.75
    sent = []
    for seed in range(6):
        params = jax.jit(model.init)(jax.random.key(seed))
        batches = _batches(s, seed, n=4)
        counts = _counts(cell, model, params, batches)
        tokens = sum(b["input_ids"].size for b in batches)
        even = tokens * s["n_sparse"] * mla_moe.held_experts_per_token(s)
        sent.append(sum(c.sum() for c in counts) / even)
        assert all(12 <= (c > 0).sum(axis=1).min() for c in counts)
    assert abs(np.mean(sent) - 1) < 0.05
    assert 0.8 < min(sent) < max(sent) < 1.2


def test_a_router_biased_away_sends_fewer_and_the_floor_falls(cell, sizes):
    """The correction bias only chooses: lowered on the held experts it
    sends them fewer rows, far enough none; the floor's operations follow
    the rows and its bytes the rows and the experts called, down to the
    held experts' gradients, which are written whatever was sent."""
    import jax
    s = cell[3]
    model = _model(cell)
    params = jax.jit(model.init)(jax.random.key(3))
    batches = _batches(s, 3)

    def biased(by):
        layers = [dict(p, gate_bias=p["gate_bias"].at[:s["n_experts"]]
                       .add(-by)) if "gate_bias" in p else p
                  for p in params["layers"]]
        counts = _counts(cell, model, {**params, "layers": layers}, batches)
        return (int(sum(c.sum() for c in counts)),
                int(sum((c > 0).sum() for c in counts)))

    layer_steps = len(batches) * s["n_sparse"]
    seeded, some, none = biased(0.0), biased(0.05), biased(2.0)
    assert seeded[0] > 2 * some[0] > 0 and none == (0, 0)
    assert seeded[1] >= some[1] > 0
    floors = [mla_moe.held_rows_work(r, c, sizes, layer_steps)
              for r, c in (seeded, some, none)]
    assert floors[0][0] > floors[1][0] > floors[2][0] == 0
    gradients = layer_steps * 16 * (3 * 2048 * 768) * 2
    assert floors[0][1] > floors[1][1] > floors[2][1] == gradients
    # in step: the operations by the rows' ratio
    assert floors[1][0] / floors[0][0] == pytest.approx(some[0] / seeded[0])


def _dense_experts(xs, weights, experts, w1, w3, w2, grouped="auto",
                   int8=False, held=None, out_dtype=None):
    """Every held expert over every row, weighed by the router's choice:
    what ``moe_swiglu_routed(held=)`` computes, with no sort, gather or
    grouped product."""
    import jax
    import jax.numpy as jnp
    offset, count = held
    chosen = jnp.sum(jnp.where(
        experts[..., None] == offset + jnp.arange(count),
        weights[..., None], 0), axis=1)                          # (S, E)
    g = jnp.einsum("sd,edf->esf", xs, w1)
    u = jnp.einsum("sd,edf->esf", xs, w3)
    y = jnp.einsum("esf,efd->esd", jax.nn.silu(g) * u, w2)
    return jnp.einsum("se,esd->sd", chosen.astype(y.dtype), y).astype(
        out_dtype or xs.dtype)


@pytest.mark.parametrize("seed", [4, 5])
def test_the_count_is_the_same_whatever_multiplies_the_experts(
        cell, seed, monkeypatch):
    """``lax.ragged_dot``, the Pallas grouped kernels and a dense product
    over every row: one seed, one count, expert by expert."""
    import jax
    from deepspeed_tpu.moe import sharded_moe
    s = cell[3]
    model = _model(cell)
    params = jax.jit(model.init)(jax.random.key(seed))
    batches = _batches(s, seed, n=1)
    by_path = {}
    for path in (False, True):
        model._moe_cfg = types.SimpleNamespace(grouped_kernel=path)
        by_path[path] = _counts(cell, model, params, batches)
    monkeypatch.setattr(sharded_moe, "moe_swiglu_routed", _dense_experts)
    by_path["dense"] = _counts(cell, model, params, batches)
    # the tap put back what it took
    assert sharded_moe.route_topk.__name__ == "route_topk"
    for path in (True, "dense"):
        for a, b in zip(by_path[False], by_path[path]):
            np.testing.assert_array_equal(a, b)
    assert by_path[False][0].sum() > 0


def test_the_even_routers_count_is_what_it_was(sizes):
    """``held_experts_work(tokens, s)`` is ``held_rows_work`` of the even
    router's rows with every held expert called: TRAIN_MOE.md's 0.348
    TFLOP, 0.65 GB, 1.77 ms a layer-step, compute-bound. A hundredth of
    the rows over 3 of the 16 experts is weight-bound: 3 experts' weights
    twice, 16 experts' gradients once."""
    peaks = common.peaks_for("TPU v5 lite")
    flops = common.load_module("pbench", "flops")
    ops, moved = mla_moe.held_experts_work(16384, sizes)
    assert (ops, moved) == mla_moe.held_rows_work(12288, 16, sizes)
    least, bound = flops.roofline_s(ops, moved, peaks)
    assert bound == "compute" and abs(least - 1.77e-3) < 0.005e-3
    ops, moved = mla_moe.held_rows_work(123, 3, sizes)
    assert ops == 18 * 123 * 2048 * 768
    assert moved == (2 * 3 + 16) * (3 * 2048 * 768) * 2 + 4 * 123 * 2048 * 2
    least, bound = flops.roofline_s(ops, moved, peaks)
    assert bound == "memory" and least == moved / peaks["hbm_bytes_per_s"]
    # sums over layer-steps are the sums' work: 8 layer-steps, 50 calls
    one = [mla_moe.held_rows_work(r, c, sizes)
           for r, c in ((4566, 14), (55, 9), (51, 8), (49, 7), (3728, 6),
                        (5, 3), (5, 2), (3, 1))]
    assert mla_moe.held_rows_work(8462, 50, sizes, layer_steps=8) == (
        sum(o for o, _ in one), sum(m for _, m in one))


def _view(sizes, took, rows, called, chips=1):
    """A reader's view whose trace says ``took`` seconds under the experts'
    scope (``mla_moe.scope_seconds`` keeps its answer on the trace)."""
    trace = types.SimpleNamespace(
        path="recorded", mla_moe_seconds=({mla_moe.EXPERTS: took}, 1.0))
    said = []
    return types.SimpleNamespace(
        trace=trace, sizes=sizes, chips=chips,
        peaks=common.peaks_for("TPU v5 lite"),
        counters={"steps_traced": 2, "tokens_traced": 32768,
                  "held_rows_traced": rows,
                  "held_experts_called_traced": called},
        say=lambda what, **k: said.append((what, k))), said


@pytest.mark.parametrize("rows,called", [(1354, 27), (39150, 66),
                                         (98304, 128)])
def test_time_at_the_floor_reads_100_and_only_less_time_reads_more(
        sizes, rows, called):
    """Over 2 traced steps x 4 sparse layers: the steps' rows, the called
    experts' weights and 8 layer-steps of held gradients."""
    reader = common.load_module("layer_metrics", "train_moe_experts_roofline")
    flops = common.load_module("pbench", "flops")
    floor, _ = flops.roofline_s(
        *mla_moe.held_rows_work(rows, called, sizes, layer_steps=8),
        common.peaks_for("TPU v5 lite"))
    for took, reads in ((floor, 100.0), (2 * floor, 50.0),
                        (0.5 * floor, 200.0)):
        view, said = _view(sizes, took, rows, called)
        assert reader.read(view) == pytest.approx(reads)
        assert (reader.read(view) > 100) == (took < floor)
        assert said[0][1]["least_seconds"] == pytest.approx(floor)
    # two chips: which experts a chip's own rows called is not counted
    view, _ = _view(sizes, floor, 2 * rows, called, chips=2)
    assert reader.read(view) is None


def test_the_reader_finds_nothing_without_the_count(sizes):
    """A runner that made no count (the parent's; another family's
    builder): None, never the even router's floor."""
    reader = common.load_module("layer_metrics", "train_moe_experts_roofline")
    view, _ = _view(sizes, 0.03, 1354, 27)
    del view.counters["held_rows_traced"]
    assert reader.read(view) is None
    view, _ = _view({"n_sparse": 4}, 0.03, 1354, 27)
    assert reader.read(view) is None


def test_the_tally_counts_each_step_on_its_own_parameters(cell):
    """``HeldRows``: the first step counted at once, a later one from the
    copy kept before its parameters went (``leaf.delete()`` is what a
    donating step does to them); the sums are the two counts'."""
    import jax
    s = cell[3]
    model = _model(cell)
    tally = cell[0].traced_counters(model, s)
    first = jax.jit(model.init)(jax.random.key(8))
    second = jax.jit(model.init)(jax.random.key(9))
    batches = _batches(s, 8)
    own = [_counts(cell, model, p, [b])[0]
           for p, b in zip((first, second), batches)]
    tally.warm(first, batches[1])
    tally.count(first, batches[0])
    tally.keep(second, batches[1])
    for leaf in jax.tree.leaves(second):
        leaf.delete()
    got = tally.counters("a test")
    assert got == {
        "held_rows_traced": int(own[0].sum() + own[1].sum()),
        "held_experts_called_traced": int((own[0] > 0).sum()
                                          + (own[1] > 0).sum())}
    assert not tally.counts and not tally.later


def test_the_runner_counts_the_traced_steps():
    """``run.py --rehearse --trace 1``: one count in set-up (the programs
    compile there, nothing in the window), one of the traced steps, said
    once the profiler has closed and before the window has; it says the
    sent rows beside the even router's, a traced step and sparse layer at
    a time; an untraced run makes no count."""
    env = dict(os.environ, JAX_PLATFORMS="cpu")

    def lines(trace):
        out = subprocess.run(
            [sys.executable, os.path.join(HERE, "run.py"), "--workload", CELL,
             "--seed", "3000000551", "--seconds", "2", "--trace", str(trace),
             "--rehearse"], env=env, capture_output=True, text=True,
            timeout=300).stdout
        said = [json.loads(x) for x in out.splitlines()
                if x.startswith('{"perfbench"')]
        assert json.loads(out.splitlines()[-1])["correct"]
        return [x["perfbench"] for x in said], said

    names, said = lines(1)
    first, second = [i for i, x in enumerate(names) if x == "held_rows"]
    assert first < names.index("warm") < second < names.index("window")
    assert said[first]["at"].startswith("set-up")
    assert np.shape(said[first]["rows_by_step_and_layer"]) == (1, 2)
    held = said[second]
    assert held["at"] == "the traced steps"
    assert held["even_router_a_layer_step"] == 2 * 256 * 0.75
    assert np.shape(held["rows_by_step_and_layer"]) == (2, 2)
    assert held["rows_a_layer_step"] == pytest.approx(
        np.mean(held["rows_by_step_and_layer"]))
    assert held["sent_of_even"] == pytest.approx(
        held["rows_a_layer_step"] / held["even_router_a_layer_step"])
    assert 0 < held["called_a_layer_step"] <= held["held_experts"] == 16
    ok = [x for x in said if x["perfbench"] == "check"
          and x["what"] == "no compilation inside the window"]
    assert ok and ok[0]["ok"]
    assert "held_rows" not in lines(0)[0]
