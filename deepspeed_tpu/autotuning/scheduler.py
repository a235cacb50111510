"""Experiment scheduler — run autotuning trials across a resource pool.

Counterpart of reference ``autotuning/scheduler.py`` (ResourceManager +
Node/Reservation): the reference reserves GPU slots on hosts and launches
each experiment as its own ``deepspeed`` job, polling for completion and
parsing metrics from the experiment directory. TPU translation: a slot is
a host's worth of chips (JAX is one process per host), an experiment runs
as a subprocess with the reservation exported through env, and results
come back as one JSON line on stdout (the last one printed) or via an
injectable runner — which is also what the tests fake.

Capacity > 1 runs independent trials concurrently (grid/random search);
the model-based tuner proposes per-round batches sized to the free
capacity, records them, and proposes again — the reference's
"experiment queue + scheduler loop" shape.
"""

import json
import os
import shlex
import subprocess
import sys
import threading

from ..utils.logging import logger
from .tuner import GridSearchTuner, ModelBasedTuner, RandomTuner


class Node:
    """reference scheduler.py Node: a host with ``max_slots`` chip slots.
    Reserve/restore are called from the manager thread AND worker
    threads (Reservation.release), so the node carries its own lock."""

    def __init__(self, host, max_slots):
        self.host = host
        self.max_slots = int(max_slots)
        self.free = list(range(self.max_slots))
        self._lock = threading.Lock()

    def reserve(self, n):
        with self._lock:
            if len(self.free) < n:
                return None
            slots, self.free = self.free[:n], self.free[n:]
            return slots

    def restore(self, slots):
        with self._lock:
            self.free.extend(slots)


class Reservation:
    def __init__(self, node, slots):
        self.node = node
        self.slots = slots

    def release(self):
        self.node.restore(self.slots)

    def env(self):
        """Env the launched experiment sees (which host/chips it owns)."""
        return {"DSTPU_EXP_HOST": self.node.host,
                "DSTPU_EXP_SLOTS": ",".join(map(str, self.slots))}


class SubprocessRunner:
    """Launch one experiment as ``python script --exp '<json>'`` on the
    reserved host (ssh for remote hosts, direct for local), parse the
    LAST JSON line of stdout as the result (the reference scheduler
    parses the experiment dir instead)."""

    def __init__(self, script, timeout_s=1800, python=None):
        self.script = script
        self.timeout_s = timeout_s
        self.python = python or sys.executable

    def __call__(self, exp, reservation):
        argv = [self.python, self.script, "--exp", json.dumps(exp)]
        env = dict(os.environ, **reservation.env())
        if reservation.node.host not in ("localhost", "127.0.0.1"):
            exports = " ".join(f"{k}={shlex.quote(v)}"
                               for k, v in reservation.env().items())
            argv = ["ssh", reservation.node.host,
                    f"cd {shlex.quote(os.getcwd())} && {exports} "
                    + " ".join(shlex.quote(a) for a in argv)]
        proc = subprocess.run(argv, capture_output=True, text=True,
                              timeout=self.timeout_s, env=env)
        for line in reversed(proc.stdout.strip().splitlines()):
            line = line.strip()
            if line.startswith("{"):
                return json.loads(line)
        return {"error": f"no JSON result (rc={proc.returncode}): "
                         f"{proc.stderr[-300:]}"}


class ResourceManager:
    """Schedule experiments over the node pool.

    ``run(experiments, run_fn, slots_per_exp=...)`` executes every
    experiment, up to pool capacity concurrently, returning results in
    submission order. ``run_model_based(space, run_fn, metric, ...)``
    drives a :class:`ModelBasedTuner` in rounds: propose as many trials
    as there is capacity, run them concurrently, record, repeat — the
    cost model stays sequential-in-rounds while the pool stays busy.
    """

    def __init__(self, nodes):
        self.nodes = [n if isinstance(n, Node) else Node(*n)
                      for n in nodes]
        self._lock = threading.Lock()

    @property
    def capacity(self):
        return sum(n.max_slots for n in self.nodes)

    def _reserve(self, n_slots):
        with self._lock:
            for node in self.nodes:
                slots = node.reserve(n_slots)
                if slots is not None:
                    return Reservation(node, slots)
        return None

    def _run_batch(self, batch, run_fn, slots_per_exp):
        """Run up to capacity concurrently; block until all done."""
        if slots_per_exp > max(n.max_slots for n in self.nodes):
            raise ValueError(
                f"slots_per_exp={slots_per_exp} exceeds every node's "
                f"capacity (max "
                f"{max(n.max_slots for n in self.nodes)}) — no "
                "reservation can ever succeed")
        results = [None] * len(batch)
        sem = threading.Semaphore(0)
        pending = list(enumerate(batch))
        running = []

        def work(i, exp, res):
            try:
                results[i] = run_fn(exp, res)
            except Exception as e:  # noqa: BLE001 - trial failure is data
                results[i] = {"error": f"{type(e).__name__}: {e}"}
            finally:
                res.release()
                sem.release()

        launched = 0
        while pending or launched:
            while pending:
                res = self._reserve(slots_per_exp)
                if res is None:
                    break
                i, exp = pending.pop(0)
                t = threading.Thread(target=work, args=(i, exp, res),
                                     daemon=True)
                t.start()
                running.append(t)
                launched += 1
            if launched:
                sem.acquire()
                launched -= 1
        for t in running:
            t.join()
        return results

    def run(self, experiments, run_fn, slots_per_exp=1):
        experiments = list(experiments)
        logger.info(f"scheduler: {len(experiments)} experiments over "
                    f"capacity {self.capacity}")
        return self._run_batch(experiments, run_fn, slots_per_exp)

    def run_model_based(self, space, run_fn, metric="samples_per_sec",
                        max_trials=None, slots_per_exp=1, **tuner_kw):
        """Model-guided search over the pool. Returns (best_exp,
        best_result, all (exp, result) pairs)."""
        tuner = ModelBasedTuner(space, max_trials=max_trials, **tuner_kw)
        per_round = max(1, self.capacity // slots_per_exp)
        all_results = []
        it = iter(tuner)
        done = False
        while not done:
            batch = []
            for _ in range(per_round):
                try:
                    batch.append(next(it))
                except StopIteration:
                    done = True
                    break
            if not batch:
                break
            results = self._run_batch(batch, run_fn, slots_per_exp)
            for exp, res in zip(batch, results):
                failed = bool(res.get("error")) or metric not in res
                if not failed:
                    tuner.record(exp, float(res[metric]))
                # failed trials simply stay unrecorded: the tuner keeps
                # yielded-but-unrecorded configs in its pending set, so
                # they are excluded from re-proposal, from best(), and —
                # critically — from the cost-model fit (an -inf
                # observation would NaN the ridge solve)
                all_results.append((exp, res))
        if not tuner.observed:
            raise RuntimeError(
                "model-based tuning: every trial failed; see results")
        best_exp, _ = tuner.best()
        best_res = next(r for e, r in all_results if e == best_exp)
        return best_exp, best_res, all_results
