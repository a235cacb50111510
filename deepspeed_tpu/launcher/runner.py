"""Multi-host launcher — the ``dstpu`` CLI.

Counterpart of reference ``launcher/runner.py:388 main`` (the ``deepspeed``
command): parse a hostfile (fetch_hostfile:200), apply --include/--exclude
filters (:255), pick a multi-node runner (PDSH/ssh), and start one worker
per HOST. TPU difference from the CUDA design: JAX is one PROCESS per host
driving all local chips (multi-controller SPMD), so there is no per-rank
``launch.py`` fan-out — each host runs the user script once with
``COORDINATOR_ADDRESS/NUM_PROCESSES/PROCESS_ID`` env for
``jax.distributed.initialize`` (comm/comm.py:130 init_distributed reads
these). ``--num_hosts 1`` (default with no hostfile) just execs locally.
"""

import argparse
import os
import shlex
import subprocess
import sys

from ..utils.logging import logger

DEFAULT_COORD_PORT = 8476


def fetch_hostfile(path, with_slices=False):
    """Parse a DeepSpeed-style hostfile: ``hostname slots=N [slice=K]``
    per line, '#' comments. Returns ordered {hostname: slots} (slots =
    TPU chips on that host; informational for JAX, which discovers local
    chips itself). The optional ``slice=K`` token records which TPU
    slice the host belongs to (multi-slice pods over DCN); with
    ``with_slices=True`` the return is ``({host: slots}, {host: slice})``
    where the slice map only holds hosts that declared one — the
    elastic agent uses it for cross-slice replica placement and
    dead-slice classification.
    """
    resource_pool = {}
    slice_map = {}
    with open(path) as f:
        for ln, line in enumerate(f, 1):
            line = line.split("#")[0].strip()
            if not line:
                continue
            parts = line.split()
            host = parts[0]
            slots = 0
            for tok in parts[1:]:
                if tok.startswith("slots="):
                    slots = int(tok.split("=", 1)[1])
                elif tok.startswith("slice="):
                    slice_map[host] = tok.split("=", 1)[1]
                else:
                    raise ValueError(
                        f"{path}:{ln}: malformed line {line!r} "
                        "(want 'host slots=N [slice=K]')")
            if host in resource_pool:
                raise ValueError(f"{path}:{ln}: duplicate host {host}")
            resource_pool[host] = slots
    if with_slices:
        return resource_pool, slice_map
    return resource_pool


def parse_inclusion_exclusion(resource_pool, include_str="",
                              exclude_str=""):
    """Apply ``--include``/``--exclude`` host filters (reference
    runner.py:255 parse_resource_filter, host-granularity; TPU chips are
    not individually maskable from the launcher). Syntax:
    ``host1@host2`` selects hosts; '@' separates entries."""
    if include_str and exclude_str:
        raise ValueError("--include and --exclude are mutually exclusive")
    hosts = list(resource_pool)

    def split(s):
        out = []
        for part in s.split("@"):
            part = part.strip()
            if not part:
                continue
            if part not in resource_pool:
                raise ValueError(f"unknown host {part!r} in filter")
            out.append(part)
        return out

    if include_str:
        keep = split(include_str)
        return {h: resource_pool[h] for h in hosts if h in keep}
    if exclude_str:
        drop = split(exclude_str)
        return {h: resource_pool[h] for h in hosts if h not in drop}
    return dict(resource_pool)


def build_worker_cmds(hosts, coordinator, script, script_args,
                      env_passthrough=(), extra_env=None,
                      per_host_env=None):
    """One (host, argv, env) per host. env carries the jax.distributed
    rendezvous triplet. ``per_host_env``: optional ``host -> dict``
    (the elastic agent's ``worker_env`` — heartbeat file + hot-tier
    ring — differs per host)."""
    cmds = []
    n = len(hosts)
    for pid, host in enumerate(hosts):
        env = {
            "COORDINATOR_ADDRESS": coordinator,
            "NUM_PROCESSES": str(n),
            "PROCESS_ID": str(pid),
        }
        if extra_env:
            env.update(extra_env)
        if per_host_env is not None:
            env.update(per_host_env(host))
        for k in env_passthrough:
            if k in os.environ:
                env[k] = os.environ[k]
        argv = [sys.executable, script] + list(script_args)
        cmds.append((host, argv, env))
    return cmds


def _compose_remote_cmd(argv, env, extra_prefix=""):
    """'cd <cwd> && EXPORTS [prefix] argv...' — the one remote command
    string every runner hands to its transport."""
    exports = " ".join(f"{k}={shlex.quote(v)}" for k, v in env.items())
    return (f"cd {shlex.quote(os.getcwd())} && {exports} "
            + (extra_prefix + " " if extra_prefix else "")
            + " ".join(shlex.quote(a) for a in argv))


class PDSHRunner:
    """reference multinode_runner.py:51 — pdsh fan-out."""

    def __init__(self, args):
        self.args = args

    def available(self):
        from shutil import which
        return which("pdsh") is not None

    def launch(self, cmds):
        procs = []
        for host, argv, env in cmds:
            remote = _compose_remote_cmd(argv, env)
            procs.append(subprocess.Popen(
                ["pdsh", "-R", "ssh", "-w", host, remote]))
        return procs


class SSHRunner:
    """Plain ssh fan-out (covers the reference's OpenMPI/MVAPICH role of
    'just start my processes' without an MPI dependency)."""

    def __init__(self, args):
        self.args = args

    def available(self):
        return True

    def launch(self, cmds):
        procs = []
        for host, argv, env in cmds:
            remote = _compose_remote_cmd(argv, env)
            if host in ("localhost", "127.0.0.1"):
                procs.append(subprocess.Popen(
                    ["bash", "-c", remote]))
            else:
                # -tt forces a pty so killing the local ssh client HUPs the
                # remote session (otherwise a compute-bound worker only
                # dies on its next write to the closed socket)
                procs.append(subprocess.Popen(["ssh", "-tt", host, remote]))
        return procs


class SlurmRunner:
    """reference multinode_runner.py:340 SlurmRunner — one ``srun`` fans
    the whole job out instead of per-host ssh sessions. Per-process rank
    comes from ``SLURM_PROCID`` at runtime (srun starts all tasks with
    identical argv), so the worker env maps it onto ``PROCESS_ID`` for
    ``jax.distributed.initialize``."""

    def __init__(self, args):
        self.args = args

    def available(self):
        from shutil import which
        return which("srun") is not None

    def build_cmd(self, cmds):
        """Compose the single srun invocation from per-host worker cmds.

        Rank AND coordinator both come from Slurm's runtime view: srun
        orders --nodelist nodes its own way (sorted, not as given), so a
        statically chosen coordinator host could differ from the node
        SLURM_PROCID 0 lands on — and jax.distributed starts the
        coordinator service on process 0. Resolving the first job node
        via scontrol inside the task keeps the two consistent."""
        hosts = [h for h, _, _ in cmds]
        _, argv, env = cmds[0]
        port = env.get("COORDINATOR_ADDRESS", ":8476").rsplit(":", 1)[-1]
        env = {k: v for k, v in env.items()
               if k not in ("PROCESS_ID", "COORDINATOR_ADDRESS")}
        prefix = ("PROCESS_ID=$SLURM_PROCID COORDINATOR_ADDRESS="
                  '$(scontrol show hostnames "$SLURM_JOB_NODELIST" '
                  f"| head -n1):{port} exec")
        inner = _compose_remote_cmd(argv, env, extra_prefix=prefix)
        return ["srun", f"--nodes={len(hosts)}", f"--ntasks={len(hosts)}",
                "--ntasks-per-node=1", f"--nodelist={','.join(hosts)}",
                "bash", "-c", inner]

    def launch(self, cmds):
        return [subprocess.Popen(self.build_cmd(cmds))]


def parse_args(argv=None):
    parser = argparse.ArgumentParser(
        prog="dstpu", description="DeepSpeed-TPU multi-host launcher")
    parser.add_argument("-H", "--hostfile", default=None,
                        help="'host slots=N' lines; omit for single-host")
    parser.add_argument("-i", "--include", default="",
                        help="host filter, e.g. host1@host2")
    parser.add_argument("-e", "--exclude", default="",
                        help="host filter, e.g. host3")
    parser.add_argument("--master_addr", default=None,
                        help="coordinator host (default: first host)")
    parser.add_argument("--master_port", type=int,
                        default=DEFAULT_COORD_PORT)
    parser.add_argument("--launcher", default="ssh",
                        choices=["ssh", "pdsh", "slurm"])
    parser.add_argument("--env", action="append", default=[],
                        help="env var names to pass through to workers")
    parser.add_argument("--elastic", action="store_true",
                        help="supervise workers and restart the world on "
                             "membership change (reference ds_elastic / "
                             "DSElasticAgent)")
    parser.add_argument("--max_elastic_restarts", type=int, default=10)
    parser.add_argument("--elastic_hot_root", default="",
                        help="hot-tier store root exported to workers "
                             "(DSTPU_HOT_TIER_ROOT/NODE/PEERS; the "
                             "agent purges a dead host's store on "
                             "membership change). Empty = no hot-tier "
                             "ring wiring")
    parser.add_argument("--elastic_flightrec_root", default="",
                        help="flight-recorder dump dir exported to "
                             "workers (DSTPU_FLIGHTREC_DIR/NODE; also "
                             "arms telemetry 'auto'). On a membership "
                             "change the agent reads the failed hosts' "
                             "dumps and logs their event tails. Must "
                             "be on a shared filesystem with remote "
                             "hosts. Empty = no flight-record wiring")
    parser.add_argument("--elastic_heartbeat_timeout", type=float,
                        default=None,
                        help="seconds without a worker heartbeat before "
                             "it is killed as hung (default: hang "
                             "detection off)")
    parser.add_argument("--elastic_heartbeat_dir", default=None,
                        help="heartbeat file dir — MUST be on a "
                             "filesystem shared between the agent and "
                             "every worker; the agent refuses the /tmp "
                             "default with remote hosts")
    parser.add_argument("--min_hosts", type=int, default=1)
    parser.add_argument(
        "--autotuning", choices=["tune", "run"], default=None,
        help="autotune the script's config before (run) or instead of "
             "(tune) launching it (reference launcher/runner.py:359 "
             "deepspeed --autotuning). The script must accept "
             "--exp '<json>' and print one JSON result line.")
    parser.add_argument(
        "--autotuning_space", default=None,
        help="JSON file {knob: [values...]}; default: micro-batch + "
             "remat policy + flash block sizes")
    parser.add_argument(
        "--autotuning_metric", default="value",
        help="result-JSON key to maximize")
    parser.add_argument("--autotuning_trials", type=int, default=12)
    parser.add_argument("--autotuning_results",
                        default="autotuning_results")
    parser.add_argument("script", help="training script")
    parser.add_argument("script_args", nargs=argparse.REMAINDER)
    return parser.parse_args(argv)


# the VERDICT-named bench knobs: micro-batch, remat, flash blocks
DEFAULT_TUNING_SPACE = {
    "BENCH_MICRO_BS": [16, 24, 32],
    "BENCH_REMAT_POLICY": ["save_flash", "save_mid"],
    "BENCH_FLASH_BQ": [512, 1024],
    "BENCH_FLASH_BK": [512, 1024],
}


def run_autotuning(args, hosts=None):
    """``dstpu --autotuning {tune,run} script`` — drive the Autotuner's
    search through the ResourceManager over the host pool (localhost
    when no hostfile), each trial a subprocess of ``script --exp
    '<json>'`` whose last JSON stdout line is the result (reference
    launcher/runner.py:359-386 + autotuning/scheduler.py). Writes
    ``exps.jsonl``, ``best_config.json`` and ``report.txt`` under
    --autotuning_results; 'run' mode then launches the script with the
    winning knobs exported."""
    import json as _json
    from ..autotuning.scheduler import (Node, ResourceManager,
                                        SubprocessRunner)
    if args.autotuning_space:
        with open(args.autotuning_space) as f:
            space = _json.load(f)
    else:
        space = dict(DEFAULT_TUNING_SPACE)
    nodes = [Node(h, 1) for h in (hosts or ["localhost"])]
    rm = ResourceManager(nodes)
    runner = SubprocessRunner(args.script)
    best_exp, best_res, all_results = rm.run_model_based(
        space, runner, metric=args.autotuning_metric,
        max_trials=args.autotuning_trials)
    os.makedirs(args.autotuning_results, exist_ok=True)
    with open(os.path.join(args.autotuning_results, "exps.jsonl"),
              "w") as f:
        for exp, res in all_results:
            f.write(_json.dumps({"exp": exp, "result": res}) + "\n")
    with open(os.path.join(args.autotuning_results,
                           "best_config.json"), "w") as f:
        _json.dump(best_exp, f, indent=1)
    lines = [f"autotuning: {len(all_results)} trials over "
             f"{len(nodes)} node(s); metric={args.autotuning_metric}"]
    for exp, res in sorted(
            all_results,
            key=lambda er: float(er[1].get(args.autotuning_metric,
                                           float("-inf"))),
            reverse=True):
        val = res.get(args.autotuning_metric, res.get("error", "?"))
        lines.append(f"  {val}  {exp}")
    lines.append(f"best: {best_exp} -> "
                 f"{best_res.get(args.autotuning_metric)}")
    report = "\n".join(lines)
    with open(os.path.join(args.autotuning_results, "report.txt"),
              "w") as f:
        f.write(report + "\n")
    logger.info(report)
    return best_exp


def main(argv=None):
    args = parse_args(argv)
    if args.autotuning:
        hosts = None
        if args.hostfile is not None:
            pool = parse_inclusion_exclusion(
                fetch_hostfile(args.hostfile), args.include, args.exclude)
            hosts = list(pool)
        best = run_autotuning(args, hosts)
        if args.autotuning == "tune":
            return 0
        # 'run': export the winning knobs and FALL THROUGH to the normal
        # launch path — single-host exec or the hostfile ssh launch (env
        # passthrough carries the knobs to every worker)
        os.environ.update({k: str(v) for k, v in best.items()})
        args.env = list(args.env) + list(best.keys())
    if args.hostfile is None:
        # single host: exec in place; jax discovers local chips
        os.execvpe(sys.executable,
                   [sys.executable, args.script] + args.script_args,
                   os.environ.copy())

    pool, slice_map = fetch_hostfile(args.hostfile, with_slices=True)
    pool = parse_inclusion_exclusion(pool, args.include, args.exclude)
    if not pool:
        raise SystemExit("no hosts left after filters")
    hosts = list(pool)
    slice_map = {h: s for h, s in slice_map.items() if h in pool}
    coordinator = (f"{args.master_addr or hosts[0]}:{args.master_port}")
    cmds = build_worker_cmds(
        hosts, coordinator, args.script, args.script_args,
        env_passthrough=tuple(args.env) + ("PYTHONPATH", "JAX_PLATFORMS",
                                           "XLA_FLAGS"))
    if args.launcher == "slurm" and args.master_addr:
        logger.warning(
            "--master_addr is ignored with --launcher slurm: the "
            "coordinator must live where SLURM_PROCID 0 runs, which "
            "Slurm decides (resolved from SLURM_JOB_NODELIST at task "
            "startup)")
    if args.elastic and args.launcher == "slurm":
        # one srun proc stands for N hosts: per-host supervision (and
        # per-host blame on failure) is impossible — Slurm's own
        # requeue/--no-kill machinery owns that role there
        raise SystemExit(
            "--elastic requires a per-host launcher (ssh/pdsh); "
            "with SLURM use its native requeue instead")
    runner = {"pdsh": PDSHRunner, "slurm": SlurmRunner,
              "ssh": SSHRunner}[args.launcher](args)
    if not runner.available():
        raise SystemExit(f"launcher {args.launcher} not available")
    if args.elastic:
        from ..elasticity.elastic_agent import DSElasticAgent

        def launch_fn(world_hosts):
            coord = f"{args.master_addr or world_hosts[0]}:{args.master_port}"
            wc = build_worker_cmds(
                world_hosts, coord, args.script, args.script_args,
                env_passthrough=tuple(args.env) + (
                    "PYTHONPATH", "JAX_PLATFORMS", "XLA_FLAGS"),
                extra_env={"ELASTIC_GENERATION": str(agent.restart_count)},
                # heartbeat file + hot-tier ring (DSTPU_HOT_*) — the
                # agent-side contract its docstring promises
                per_host_env=agent.worker_env)
            return list(zip(world_hosts, runner.launch(wc)))

        # hostfile slots = chips per host (uniform pods; the agent
        # validates the surviving world with them)
        slots = {pool[h] for h in hosts}
        agent = DSElasticAgent(launch_fn, hosts,
                               max_restarts=args.max_elastic_restarts,
                               min_hosts=args.min_hosts,
                               chips_per_host=(slots.pop() if
                                               len(slots) == 1 else 1),
                               hot_root=args.elastic_hot_root or None,
                               flightrec_root=(
                                   args.elastic_flightrec_root or None),
                               heartbeat_timeout_s=(
                                   args.elastic_heartbeat_timeout),
                               heartbeat_dir=args.elastic_heartbeat_dir,
                               # hostfile slice=K tokens: cross-slice
                               # replica placement + dead_slice class
                               slices=slice_map or None)
        agent.run()
        return 0
    logger.info(f"launching on {len(hosts)} hosts via {args.launcher}; "
                f"coordinator {coordinator}")
    procs = runner.launch(cmds)
    rc = 0
    try:
        for p in procs:
            rc |= p.wait()
    except KeyboardInterrupt:
        # kill-switch semantics (reference launch.py:118): tear everyone
        # down on interrupt so no stragglers hold the TPU
        for p in procs:
            p.terminate()
        for p in procs:
            p.wait()
        raise
    return rc


if __name__ == "__main__":
    raise SystemExit(main())
