"""Multi-process ZeRO-Offload: a 2-process jax.distributed CPU ring
trains with offload_optimizer and matches the single-process loss
(reference stage_1_and_2.py:1181 — every DP rank cpu-steps its own
partition at any world size).

Processes are real (subprocess + jax.distributed rendezvous on
localhost), mirroring the reference's DistributedExec multi-process
harness (tests/unit/common.py:105)."""

import json
import os
import socket
import subprocess
import sys

import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(
    os.path.dirname(os.path.abspath(__file__))))

# multi-process spawn: excluded from the fast core set
pytestmark = pytest.mark.slow

_WORKER = r"""
import json, os, sys
os.environ["JAX_PLATFORMS"] = "cpu"
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count={ndev}"
sys.path.insert(0, {repo!r})
import jax
jax.config.update("jax_platforms", "cpu")
jax.distributed.initialize(coordinator_address={coord!r},
                           num_processes={nproc},
                           process_id={pid})
import numpy as np
import deepspeed_tpu
from deepspeed_tpu.models import GPT2, PRESETS
from deepspeed_tpu.utils import groups

groups.reset()
model = GPT2(PRESETS["tiny"])
engine, _, _, _ = deepspeed_tpu.initialize(
    model=model,
    config={{"train_micro_batch_size_per_gpu": 1,
             "steps_per_print": 0,
             "optimizer": {{"type": "AdamW", "params": {{"lr": 1e-3}}}},
             "bf16": {{"enabled": True}},
             "zero_optimization": {{"stage": 2,
                                    "offload_optimizer":
                                        {{"device": "cpu"}}}}}})
rng = np.random.RandomState(0)
bsz = engine.config.train_batch_size
batch = {{"input_ids": rng.randint(0, 1024, (bsz, 128)).astype(np.int32)}}
losses = [float(engine.train_batch(batch)) for _ in range(4)]
if jax.process_index() == 0:
    print("LOSSES=" + json.dumps(losses))
"""


def _free_port():
    s = socket.socket()
    s.bind(("127.0.0.1", 0))
    port = s.getsockname()[1]
    s.close()
    return port


def _run_world(nproc):
    coord = f"127.0.0.1:{_free_port()}"
    procs = []
    for pid in range(nproc):
        code = _WORKER.format(repo=REPO, coord=coord, nproc=nproc,
                              pid=pid, ndev=2 // nproc)
        env = dict(os.environ)
        env.pop("XLA_FLAGS", None)
        env.pop("JAX_PLATFORMS", None)
        procs.append(subprocess.Popen(
            [sys.executable, "-c", code], env=env,
            stdout=subprocess.PIPE, stderr=subprocess.PIPE, text=True))
    outs = [p.communicate(timeout=600) for p in procs]
    for p, (so, se) in zip(procs, outs):
        assert p.returncode == 0, f"worker failed:\n{se[-3000:]}"
    for so, _ in outs:
        for line in so.splitlines():
            if line.startswith("LOSSES="):
                return json.loads(line[len("LOSSES="):])
    raise AssertionError("no LOSSES line from rank 0")


@pytest.mark.slow
def test_two_process_offload_matches_single():
    # same global batch (2 x micro 1 vs 1 x ... both dp=2 over 2 devices;
    # the 2-process run splits the SAME 2-device mesh across processes)
    multi = _run_world(2)
    single = _run_world(1)
    assert len(multi) == 4 and len(single) == 4
    np.testing.assert_allclose(multi, single, rtol=2e-4, atol=2e-4)
    assert multi[-1] < multi[0]          # it actually trains
