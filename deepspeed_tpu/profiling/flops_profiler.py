"""Flops profiler — compiled-program cost accounting.

Counterpart of reference ``profiling/flops_profiler/profiler.py:28``.
The reference monkeypatches torch functionals and walks module hooks to
count MACs; on TPU the compiler already knows: ``jax.jit(fn).lower(...)
.compile().cost_analysis()`` returns XLA's flop/byte counts for the exact
program that runs. The profiler wraps that, adds parameter counts and
wall-clock measurement, and keeps the reference's report surface
(get_total_flops/macs/params/duration, print_model_profile).
"""

import time

import numpy as np
import jax


def _param_count(params):
    return sum(int(np.prod(x.shape)) for x in jax.tree.leaves(params))


def compiled_costs(compiled):
    """Normalize ``Compiled.cost_analysis()`` into one flat dict (some
    backends return ``[dict]``; key spellings vary between
    ``bytes accessed`` and ``bytes_accessed``). The single
    extraction point the engine's flops hook and the telemetry layer's
    MFU both read — the two can never disagree on what "step flops"
    means."""
    try:
        costs = compiled.cost_analysis()
    except Exception:  # noqa: BLE001 - some backends ship no analysis
        return {}
    if isinstance(costs, (list, tuple)):
        costs = costs[0] if costs else {}
    costs = dict(costs or {})
    if "bytes accessed" not in costs and "bytes_accessed" in costs:
        costs["bytes accessed"] = costs["bytes_accessed"]
    return costs


def _cost_analysis(fn, *args, static_argnums=()):
    compiled = jax.jit(fn, static_argnums=static_argnums).lower(
        *args).compile()
    return compiled, compiled_costs(compiled)


class FlopsProfiler:
    """``prof = FlopsProfiler(model); prof.start_profile()`` then run the
    engine / call ``profile_fn``; read totals.

    For jitted work the unit of accounting is a compiled program, not a
    module hook, so ``profile_fn(fn, *args)`` is the native entry; the
    engine drives it on the train-step program when
    ``flops_profiler.enabled`` (engine.py parity with reference
    engine.py:2240-2252).
    """

    def __init__(self, model=None, config=None):
        self.model = model
        self.config = config
        self.reset()

    def reset(self):
        self._flops = 0.0
        self._bytes = 0.0
        self._params = 0
        self._duration = 0.0
        self._per_program = {}
        self._started = False

    # -- reference API surface --
    def start_profile(self, **kw):
        self.reset()
        self._started = True

    def stop_profile(self):
        self._started = False

    def end_profile(self):
        self.reset()

    def record(self, name, flops, nbytes=0.0, duration=0.0):
        """Account an externally-measured program (e.g. the engine's
        already-built train step)."""
        self._per_program[name] = {"flops": float(flops),
                                   "bytes": float(nbytes),
                                   "duration": float(duration)}
        self._flops += float(flops)
        self._bytes += float(nbytes)
        self._duration += float(duration)

    def profile_fn(self, fn, *args, name="program", static_argnums=(),
                   measure_time=True):
        """Account one jitted callable on example args. Returns its flops."""
        compiled, costs = _cost_analysis(fn, *args,
                                         static_argnums=static_argnums)
        flops = float(costs.get("flops", 0.0))
        nbytes = float(costs.get("bytes accessed", 0.0))
        dur = 0.0
        if measure_time:
            out = compiled(*args)
            jax.block_until_ready(out)
            t0 = time.perf_counter()
            out = compiled(*args)
            jax.block_until_ready(out)
            dur = time.perf_counter() - t0
        self._per_program[name] = {"flops": flops, "bytes": nbytes,
                                   "duration": dur}
        self._flops += flops
        self._bytes += nbytes
        self._duration += dur
        return flops

    def set_params(self, params):
        self._params = _param_count(params)

    def get_total_flops(self, as_string=False):
        return _fmt(self._flops, "FLOPs") if as_string else self._flops

    def get_total_macs(self, as_string=False):
        macs = self._flops / 2  # XLA counts mul+add
        return _fmt(macs, "MACs") if as_string else macs

    def get_total_params(self, as_string=False):
        return (_fmt(self._params, "params") if as_string
                else self._params)

    def get_total_duration(self, as_string=False):
        return (f"{self._duration * 1e3:.2f} ms" if as_string
                else self._duration)

    def get_flops_per_sec(self):
        return self._flops / self._duration if self._duration else 0.0

    def print_model_profile(self, file=None):
        import sys
        f = file or sys.stdout
        print("-" * 60, file=f)
        print("DeepSpeed-TPU flops profiler", file=f)
        print(f"params:   {self.get_total_params(True)}", file=f)
        print(f"flops:    {self.get_total_flops(True)}", file=f)
        print(f"macs:     {self.get_total_macs(True)}", file=f)
        print(f"duration: {self.get_total_duration(True)}", file=f)
        if self._duration:
            print(f"flops/s:  {_fmt(self.get_flops_per_sec(), 'FLOPS')}",
                  file=f)
        for name, d in self._per_program.items():
            line = f"  {name:24s} {_fmt(d['flops'], 'FLOPs'):>14s}"
            if d["duration"]:
                line += f"  {d['duration'] * 1e3:8.2f} ms"
            print(line, file=f)
        print("-" * 60, file=f)


def get_model_profile(model, batch, rng=None, train=False,
                      print_profile=False):
    """(flops, macs, params) for one forward of ``model`` on ``batch``
    (reference get_model_profile: builds the model, runs with shape args).
    """
    if rng is None:
        rng = jax.random.key(0)
    params = model.init(rng)
    prof = FlopsProfiler(model)
    prof.set_params(params)

    def fwd(p, b):
        return model.loss(p, b, train=train) if train else \
            model.apply(p, b["input_ids"])

    prof.profile_fn(fwd, params, batch, name="forward", measure_time=False)
    if print_profile:
        prof.print_model_profile()
    return prof.get_total_flops(), prof.get_total_macs(), \
        prof.get_total_params()


def _fmt(x, unit):
    for scale, pre in [(1e12, "T"), (1e9, "G"), (1e6, "M"), (1e3, "K")]:
        if abs(x) >= scale:
            return f"{x / scale:.2f} {pre}{unit}"
    return f"{x:.0f} {unit}"


# ----------------------------------------------------- per-module breakdown
def _dot_flops(eqn):
    """2 * batch * M * N * K for a dot_general eqn."""
    import numpy as np
    lhs, rhs = (v.aval for v in eqn.invars[:2])
    (lc, rc), (lb, rb) = eqn.params["dimension_numbers"]
    batch = int(np.prod([lhs.shape[d] for d in lb], dtype=np.int64)) \
        if lb else 1
    k = int(np.prod([lhs.shape[d] for d in lc], dtype=np.int64)) if lc else 1
    m = int(np.prod([lhs.shape[d] for d in range(lhs.ndim)
                     if d not in tuple(lc) + tuple(lb)], dtype=np.int64))
    n = int(np.prod([rhs.shape[d] for d in range(rhs.ndim)
                     if d not in tuple(rc) + tuple(rb)], dtype=np.int64))
    return 2.0 * batch * m * n * k


_ELEMENTWISE = {
    "add", "sub", "mul", "div", "max", "min", "exp", "log", "tanh",
    "logistic", "rsqrt", "sqrt", "erf", "pow", "integer_pow", "neg",
    "select_n", "convert_element_type", "and", "or", "xor", "sign",
    "abs", "floor", "ceil", "round",
}


def _eqn_flops(eqn):
    import numpy as np
    name = eqn.primitive.name
    if name == "dot_general":
        return _dot_flops(eqn)
    if name == "ragged_dot":
        # grouped GEMM: rows x (per-group N*K summed = total expert mats)
        lhs, rhs = (v.aval for v in eqn.invars[:2])
        return 2.0 * lhs.shape[0] * rhs.shape[-2] * rhs.shape[-1]
    if name in _ELEMENTWISE or name.startswith("reduce_"):
        out = eqn.outvars[0].aval
        return float(np.prod(out.shape, dtype=np.int64)) if out.shape \
            else 1.0
    return 0.0


def _module_of(eqn, code_root):
    """Attribute an eqn to the innermost model-code frame 'fn:line'."""
    src = eqn.source_info
    try:
        frames = list(src.traceback.frames)
    except Exception:  # noqa: BLE001
        return "<unknown>"
    for fr in frames:
        fname = getattr(fr, "file_name", "")
        if code_root in fname:
            short = fname.split("/")[-1].rsplit(".", 1)[0]
            return f"{short}.{fr.function_name}"
    return "<outside-model>"


def per_module_flops(fn, *args, code_root="models"):
    """Walk the jaxpr of ``fn(*args)`` and attribute flops to the model
    source function that emitted each op (reference
    print_model_profile's per-module rows, realized as a jaxpr walk:
    module hooks don't exist under jit, source provenance does).

    Returns {module_name: flops} including scan bodies scaled by trip
    count. Elementwise ops count 1 flop/element; dots count 2*M*N*K.
    """
    jaxpr = jax.make_jaxpr(fn)(*args)
    groups = {}

    def add(name, fl):
        groups[name] = groups.get(name, 0.0) + fl

    def walk(jaxpr, scale):
        for eqn in jaxpr.eqns:
            name = eqn.primitive.name
            sub = None
            sub_scale = scale
            if name == "scan":
                sub = eqn.params["jaxpr"].jaxpr
                sub_scale = scale * eqn.params["length"]
            elif name in ("pjit", "closed_call", "core_call",
                          "remat_call", "checkpoint", "custom_jvp_call",
                          "custom_vjp_call", "custom_vjp_call_jaxpr"):
                p = eqn.params
                j = (p.get("jaxpr") or p.get("call_jaxpr")
                     or p.get("fun_jaxpr"))
                if j is not None:
                    sub = getattr(j, "jaxpr", j)
            elif name == "while":
                sub = eqn.params["body_jaxpr"].jaxpr
                # trip count unknown statically; count one iteration
            elif name == "cond":
                for br in eqn.params["branches"]:
                    walk(br.jaxpr, scale)
                continue
            if sub is not None:
                walk(sub, sub_scale)
                continue
            fl = _eqn_flops(eqn)
            if fl:
                add(_module_of(eqn, code_root), fl * scale)
    walk(jaxpr.jaxpr, 1.0)
    return groups


def print_module_profile(fn, *args, code_root="models", file=None):
    """Reference ``print_model_profile`` analogue: per-module flops table
    sorted by share."""
    groups = per_module_flops(fn, *args, code_root=code_root)
    total = sum(groups.values()) or 1.0
    lines = [f"{'module':44s} {'GFLOPs':>12s} {'share':>7s}"]
    for name, fl in sorted(groups.items(), key=lambda kv: -kv[1]):
        lines.append(f"{name:44s} {fl / 1e9:12.3f} {fl / total:6.1%}")
    lines.append(f"{'TOTAL':44s} {total / 1e9:12.3f} {1:6.1%}")
    out = "\n".join(lines)
    print(out, file=file)
    return groups
