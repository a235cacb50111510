"""What ``serve_tok_s`` counts, on the CPU, against the real engine at a tiny
size (a two-layer GPT-2 as ``tests/unit/test_serving_spans.py`` builds it):
``runners/serve.py:Driver`` drives ``Router`` -> ``Replica`` ->
``InferenceEngineV2`` and counts a prompt's tokens as the program takes
them in. And what ``fused_dispatch_ms`` reads.

    JAX_PLATFORMS=cpu python3 -m pytest perfbench/tests -q

(ISSUE 51 asked for ``tests/unit/test_perfbench_counting.py``; a benchmark
PR adds files under ``perfbench/`` only, so they are here and the driver's
``pytest tests/`` does not count them.)
"""

import os
import sys
import types

import numpy as np
import pytest

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, HERE)                       # pbench/, runners/
sys.path.insert(0, os.path.dirname(HERE))      # deepspeed_tpu

from pbench import common, trace as pb_trace   # noqa: E402

serve = common.load_module("runners", "serve")

CHUNK = 16
_BASE = {"dtype": "float32", "kv_block_size": 8, "prompt_bucket": 16,
         "max_batch_size": 4, "decode_steps_per_dispatch": 2,
         "paged_kernel": False}


@pytest.fixture(scope="module")
def tiny():
    import jax
    from deepspeed_tpu.models import GPT2, GPT2Config
    model = GPT2(GPT2Config(n_layer=2, n_head=4, d_model=64, max_seq_len=128,
                            vocab_size=256, remat=False, dtype="float32"))
    return model, model.init(jax.random.key(0))


def _router(tiny, splitfuse_tokens):
    from deepspeed_tpu.autotuning import kernel_dispatch
    from deepspeed_tpu.inference.v2 import InferenceEngineV2, Router
    from deepspeed_tpu.inference.v2.replica import Replica
    from deepspeed_tpu.utils import groups
    kernel_dispatch.reset()
    groups.reset()
    model, params = tiny
    engine = InferenceEngineV2(model, params=params, config=dict(
        _BASE, splitfuse_tokens=splitfuse_tokens))
    return Router([Replica("r0", engine)])


class Ticks:
    """A clock that moves one second a reading: every step has its own
    return time, whatever the machine."""

    def __init__(self):
        self.t = 0.0

    def __call__(self):
        self.t += 1.0
        return self.t


def _recs(lengths, max_new, seed=0):
    rng = np.random.default_rng(seed)
    return [serve.Rec({"due_s": 0.0, "max_new_tokens": m,
                       "prompt": rng.integers(1, 250, n, dtype=np.int32)})
            for n, m in zip(lengths, max_new)]


def _drive(router, recs, every=2):
    """Put a request every ``every`` steps and step until all are done."""
    driver, clock = serve.Driver(router), Ticks()
    todo = list(recs)
    while todo or driver.live:
        if todo and len(driver.steps) % every == 0:
            driver.put(todo.pop(0), clock())
        driver.step(clock)
    assert all(r.t_done is not None and not r.failed for r in recs)
    return driver


def _old_count(driver, recs):
    """The count before PR 51: a prompt whole, and the token its prefill
    emits, at the step whose return made its first token readable."""
    return [sum(len(r.prompt) + 1 for r in recs if r.t_first == st[1])
            for st in driver.steps]


@pytest.mark.parametrize("length", [33, 48, 70])
def test_a_streamed_prompt_counts_chunk_by_chunk(tiny, length):
    """A prompt of >= 3 chunks is counted in as many different steps, a
    chunk each, and sums to len(prompt) + 1."""
    rec, = recs = _recs([length], [4])
    driver = _drive(_router(tiny, CHUNK), recs)
    brought = [st[4] for st in driver.steps if st[4]]
    chunks = -(-length // CHUNK)
    assert chunks >= 3 and len(brought) == chunks
    assert brought[:-1] == [CHUNK] * (chunks - 1)
    assert brought[-1] == length - CHUNK * (chunks - 1) + 1
    assert sum(brought) == rec.counted == length + 1


@pytest.mark.parametrize("splitfuse_tokens", [0, CHUNK])
def test_a_drained_run_counts_every_token_once(tiny, splitfuse_tokens):
    """Over a drained run the steps' prompt tokens sum to sum(len(prompt) +
    1) and their pairs to the decode tokens: a request's tokens but the one
    its prefill emits."""
    recs = _recs([5, 40, 17, 64, 33, 16, 9], [6, 3, 8, 5, 2, 7, 4], seed=1)
    driver = _drive(_router(tiny, splitfuse_tokens), recs)
    assert sum(st[4] for st in driver.steps) \
        == sum(len(r.prompt) + 1 for r in recs)
    assert sum(st[2] for st in driver.steps) \
        == sum(r.max_new - 1 for r in recs)
    assert [len(r.tokens) for r in recs] == [r.max_new for r in recs]
    assert all(r.counted == len(r.prompt) + 1 for r in recs)


@pytest.mark.parametrize("inside", [1, 2, 3, 4])
def test_a_prefill_across_the_close_gives_the_window_its_chunks(tiny, inside):
    """A request whose prefill straddles the window's close gives the
    window only the chunks whose steps returned inside it."""
    rec, = recs = _recs([70], [3])                     # five chunks
    driver = _drive(_router(tiny, CHUNK), recs)
    chunk_steps = [st for st in driver.steps if st[4]]
    close = chunk_steps[inside][1]     # the window ends as this one returns
    assert serve.tokens_processed(driver.steps, close) == CHUNK * inside
    assert rec.t_first >= close         # the old count gave the window 0
    after = [(*st[:1], st[1] - close, *st[2:]) for st in driver.steps]
    assert serve.tokens_processed(after, 1e9) \
        == 71 - CHUNK * inside + rec.max_new - 1


@pytest.mark.parametrize("every", [1, 2, 5])
def test_a_one_shot_prefill_counts_as_it_did(tiny, every):
    """Where a prefill is one bucketed program nothing advances the offset
    before the first token is readable: the new count is the old one, step
    for step (cells 3, 5, 6, 7, 8 did not move)."""
    recs = _recs([5, 40, 17, 64, 33, 16, 9], [6, 3, 8, 5, 2, 7, 4], seed=2)
    driver = _drive(_router(tiny, 0), recs, every)
    assert [st[4] for st in driver.steps] == _old_count(driver, recs)
    assert sum(1 for st in driver.steps if st[4]) == len(recs)


def test_a_streamed_prefill_does_not_count_as_it_did(tiny):
    """The guard's guard: with chunks the two counts differ step by step
    and agree in total."""
    recs = _recs([40, 64, 33], [6, 3, 8], seed=3)
    driver = _drive(_router(tiny, CHUNK), recs)
    new, old = [st[4] for st in driver.steps], _old_count(driver, recs)
    assert new != old and sum(new) == sum(old)


def test_count_prompt_is_monotone_and_exact():
    rec, = _recs([100], [4])
    got = [serve.Driver.count_prompt(rec, upto)
           for upto in (0, 32, 32, 64, 48, 96, 101, 101)]
    assert got == [0, 32, 0, 32, 0, 32, 5, 0] and rec.counted == 101


# ---------------------------------------------------------------- the reader

def _span(kind, ms):
    return types.SimpleNamespace(start=0.0, end=ms / 1e3, dur=ms / 1e3,
                                 stats={"kind": kind})


def _view(spans, said):
    return types.SimpleNamespace(
        trace=types.SimpleNamespace(host_spans=lambda name: spans),
        say=lambda line, **fields: said.append((line, fields)))


FUSED = [_span("fused", ms) for ms in (130.0, 140.0, 150.0)]
CHUNKS = [_span("chunk", ms) for ms in (2.0, 3.0, 90.0)]
DECODES = [_span("decode", ms) for ms in (20.0, 21.0)]


@pytest.mark.parametrize("spans, want, by_kind", [
    (CHUNKS + DECODES, 3.0, {"fused": 0, "chunk": 3}),
    (FUSED + DECODES, 140.0, {"fused": 3, "chunk": 0}),
    (FUSED + CHUNKS, 110.0, {"fused": 3, "chunk": 3}),
])
def test_fused_dispatch_ms_reads_every_dispatch_with_a_chunk(
        spans, want, by_kind):
    """A number on a trace holding only ``chunk`` dispatches (cell 4 under
    its knee), the number it gave before on one holding only ``fused``, one
    median over both."""
    said = []
    reader = common.load_module("layer_metrics", "fused_dispatch_ms")
    assert reader.read(_view(spans, said)) == pytest.approx(want)
    (line, fields), = said
    assert line == "fused_dispatch_ms" and fields["by_kind"] == by_kind
    if not by_kind["chunk"]:        # the parent's reader: kind fused alone
        ms = sorted(1e3 * e.dur for e in spans if e.stats["kind"] == "fused")
        assert want == common.percentile(ms, 50)


@pytest.mark.parametrize("trace", ["none", "no-spans", "decode-only",
                                   "recorded"])
def test_fused_dispatch_ms_reads_nothing_where_nothing_is(trace):
    """None, nothing said and nothing raised without a trace, on a program
    that opens no span (the recorded four-chip training trace), and on a
    slice holding plain decode dispatches alone."""
    said = []
    view = _view({"no-spans": [], "decode-only": DECODES}.get(trace, []),
                 said)
    if trace == "none":
        view.trace = None
    if trace == "recorded":
        view.trace = pb_trace.Trace(os.path.join(
            HERE, "fixtures", "tiny4.xplane.pb"), rehearse=True)
    reader = common.load_module("layer_metrics", "fused_dispatch_ms")
    assert reader.read(view) is None and not said


def test_decode_dispatch_ms_reads_what_it_read():
    said = []
    reader = common.load_module("layer_metrics", "decode_dispatch_ms")
    assert reader.read(_view(FUSED + CHUNKS + DECODES, said)) \
        == pytest.approx(20.5)
    assert said[0][1]["spans"] == 2
