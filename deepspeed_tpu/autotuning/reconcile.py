"""Modeled-vs-measured planner reconciliation: align a
``profiling.step_trace.StepDecomposition`` with the ``_score`` term
breakdown of ``autotuning/planner.py`` and close ROADMAP item 1's open
thread — feed *measured* costs back into the planner.

Three pieces:

  * :func:`reconcile` — run the planner's ``_score`` for the mesh the
    trace was captured on and pair every cost term with the measured
    decomposition key (``TERM_MAP``; the two-direction lint in
    ``tests/unit/test_reconcile.py`` keeps planner and tracer
    vocabularies aligned). The result is a :class:`DriftReport` ranked
    by absolute modeled-vs-measured error — "where is the model most
    wrong" is the first question every perf PR asks.
  * :func:`seed_rows` / :func:`seed_cache` — distill the measured run
    into winner-cache rows via the existing
    ``kernel_cache.seed_entries`` path: ``comm_link`` rows whose
    alpha-beta is refit from measured exposed collective time against
    the planner's own wire-byte model (``calibrate_links`` picks them
    up on the next ``plan()``), and ``op_cost`` rows carrying measured
    per-step unit costs for each named Pallas kernel plus the compute
    tick. Both are cache-file-only pseudo-ops exactly like
    ``comm_bench``'s ``comm_link``: never in the op REGISTRY, invisible
    to dispatch, device-kind refusal rules intact.
  * :func:`from_engine` — the telemetry wiring's entry: build the
    planner descriptors from a live engine and reconcile the trace its
    ``ProfilerControl`` just captured.

Every path here is advisory: parse/model failures degrade to ``None``
with a warning, never an exception into the step path.
"""

from dataclasses import dataclass, field, asdict

from ..utils.logging import logger
from . import planner
from .planner import ModelDesc, PodDesc, calibrate_links
from .kernel_cache import seed_entries

# planner ``_score`` term -> StepDecomposition ``terms`` key. Identity
# today — kept explicit so a future split (e.g. grad_reduce into
# ici/dcn legs) must touch this table and re-run the lint.
TERM_MAP = {t: t for t in planner.SCORE_TERMS}

# terms whose modeled time is communication priced by calibrate_links
# (the comm_link refit's numerator); compute and host_offload are not.
_COMM_TERMS = ("grad_reduce", "tp_reduce", "pipe_handoff",
               "ring_rotate", "expert_a2a")


def topo_bucket(mesh_shape):
    """The collective bucket signature string for a planner mesh dict
    (``ops/pallas/_common.topo_signature`` format — exact axis sizes,
    so a measured row can never steer a different topology)."""
    g = lambda a: int(mesh_shape.get(a, 1))
    return (f"pp{g('pipe')},do{g('data_outer')},dp{g('data')},"
            f"ep{g('expert')},sp{g('seq')},tp{g('tensor')}")


@dataclass
class DriftReport:
    """Modeled vs measured, per term, ranked by absolute error."""
    rows: list                         # [{term, modeled_ms, measured_ms,
    #                                     drift_ms}] worst-first
    modeled_wall_ms: float
    measured_wall_ms: float            # decomposition total device ms
    wall_err_pct: float                # 100*|modeled-measured|/measured
    coverage_pct: float                # from the decomposition
    mesh: dict
    schedule: str
    micro_batches: int
    offload: bool
    steps: int
    links: dict = field(default_factory=dict)
    unmodeled: dict = field(default_factory=dict)

    def top(self):
        return self.rows[0] if self.rows else None

    def summary(self):
        """The compact dict telemetry/flight-recorder surfaces carry
        (term reported both by name and by SCORE_TERMS index — metric
        values are floats)."""
        t = self.top() or {}
        term = t.get("term", "")
        return {
            "top_term": term,
            "top_term_index": (planner.SCORE_TERMS.index(term)
                               if term in planner.SCORE_TERMS else -1),
            "top_drift_ms": round(abs(t.get("drift_ms", 0.0)), 6),
            "wall_err_pct": self.wall_err_pct,
            "coverage_pct": self.coverage_pct,
            "modeled_wall_ms": self.modeled_wall_ms,
            "measured_wall_ms": self.measured_wall_ms,
            "steps": self.steps,
        }

    def table(self):
        """Human-readable drift table (the CLI's default output)."""
        lines = [f"{'term':>14} {'modeled_ms':>12} {'measured_ms':>12} "
                 f"{'drift_ms':>10}"]
        for r in self.rows:
            lines.append(f"{r['term']:>14} {r['modeled_ms']:>12.4f} "
                         f"{r['measured_ms']:>12.4f} "
                         f"{r['drift_ms']:>+10.4f}")
        lines.append(
            f"{'wall':>14} {self.modeled_wall_ms:>12.4f} "
            f"{self.measured_wall_ms:>12.4f} "
            f"{self.measured_wall_ms - self.modeled_wall_ms:>+10.4f}"
            f"   ({self.wall_err_pct:.1f}% off, coverage "
            f"{self.coverage_pct:.1f}%)")
        for k, v in sorted(self.unmodeled.items()):
            lines.append(f"{k:>14} {'(unmodeled)':>12} {v:>12.4f}")
        return "\n".join(lines)

    def to_dict(self):
        return asdict(self)


def reconcile(decomp, model, pod, mesh_shape, *, schedule="none",
              micro_batches=1, offload=False, batch_tokens=None,
              cache=None, links=None):
    """Pair every planner ``_score`` term with its measured
    decomposition value. Every term gets a row — a term the mesh never
    exercises pairs modeled 0.0 against measured 0.0, so "is the model
    silent where the hardware is loud" is visible, not dropped."""
    mesh = {a: int(mesh_shape.get(a, 1)) for a in planner.MESH_AXES}
    if links is None:
        links = calibrate_links(pod, cache=cache)
    if batch_tokens is None:
        batch_tokens = max(1, 8 * pod.n_chips) * model.max_seq_len
    M = max(1, int(micro_batches))
    sched = schedule if mesh["pipe"] > 1 else "none"
    _, terms = planner._score(model, pod, mesh, sched, M, bool(offload),
                              links, batch_tokens)
    rows = []
    for t in planner.SCORE_TERMS:
        modeled = float(terms.get(t, 0.0))
        measured = float(decomp.terms.get(TERM_MAP[t], 0.0))
        rows.append({"term": t, "modeled_ms": round(modeled, 6),
                     "measured_ms": round(measured, 6),
                     "drift_ms": round(measured - modeled, 6)})
    rows.sort(key=lambda r: -abs(r["drift_ms"]))
    modeled_wall = sum(float(terms.get(t, 0.0))
                       for t in planner.SCORE_TERMS)
    measured_wall = float(decomp.total_device_ms)
    err = (100.0 * abs(modeled_wall - measured_wall) / measured_wall
           if measured_wall > 0 else 0.0)
    return DriftReport(
        rows=rows, modeled_wall_ms=round(modeled_wall, 6),
        measured_wall_ms=round(measured_wall, 6),
        wall_err_pct=round(err, 3),
        coverage_pct=float(decomp.coverage_pct),
        mesh=mesh, schedule=sched, micro_batches=M,
        offload=bool(offload), steps=int(decomp.steps),
        links={k: list(v) for k, v in links.items()},
        unmodeled=dict(decomp.unmodeled))


# ------------------------------------------------------------- seeding

def _comm_bytes_by_link(model, mesh, schedule, M, batch_tokens):
    """Per-step wire bytes per link class, mirroring ``_score``'s
    payload formulas (ring 2(W-1)/W, shard (W-1)/W, exchange 1x). The
    denominator of the measured-busbw refit: measured seconds over
    these bytes is the effective beta the run actually achieved."""
    pp, do, dp = mesh["pipe"], mesh["data_outer"], mesh["data"]
    ep, sp, tp = mesh["expert"], mesh["seq"], mesh["tensor"]
    shard = pp * tp * max(1, ep)
    tokens_micro = batch_tokens / (dp * do * M)
    layers = max(1, model.n_layer // pp)
    ici = dcn = 0.0
    gbytes = model.grad_bytes * model.params / shard
    if dp > 1:
        ici += 2 * (dp - 1) / dp * gbytes
    if do > 1:
        dcn += 2 * (do - 1) / do * gbytes / max(1, dp)
    act_b = tokens_micro / sp * model.d_model * model.param_bytes
    if tp > 1:
        ici += M * layers * 2 * 2 * (tp - 1) / tp * act_b
    if pp > 1:
        from ..runtime.pipe.schedule import executor_tick_units
        n_ticks = len(executor_tick_units(schedule, M, pp))
        ici += n_ticks * act_b
    if sp > 1:
        kv_b = 2 * tokens_micro / sp * model.d_model * model.param_bytes
        ici += M * layers * (sp - 1) * kv_b
    if ep > 1:
        tok_b = tokens_micro * model.d_model * model.param_bytes
        ici += M * layers * 2 * (ep - 1) / ep * tok_b
        if do > 1:
            dcn += M * layers * 2 * (do - 1) / do * tok_b
    return {"ici": ici, "dcn": dcn}


def seed_rows(decomp, report, device_kind=None):
    """Winner-cache rows distilled from one reconciled run, in the
    exact shape ``kernel_cache.seed_entries`` ingests:

      * one ``comm_link`` row per link class with measured time on it —
        beta refit as (modeled wire bytes) / (measured exposed seconds)
        with the calibrated alpha carried over; ``calibrate_links``
        reads these on the next ``plan()``;
      * one ``op_cost`` row per named Pallas kernel the trace attributed
        time to, plus the measured compute tick — the measured per-op
        unit costs a later planner iteration prices ticks from.

    Both ops are cache-file-ONLY pseudo-ops (the comm_bench precedent):
    never registered in the op REGISTRY, never consulted by dispatch.
    """
    if device_kind is None:
        from .kernel_dispatch import device_kind as dk
        device_kind = dk()
    mesh = report.mesh
    topo = topo_bucket(mesh)
    rows = []

    # measured collective seconds per leg — TOTAL wall, not just
    # exposed, because ``_t_coll`` models raw alpha-beta time before
    # the overlap discount; legless collectives (no replica-group text
    # in the trace) default to the ICI class — the DCN leg is only ever
    # credited on positive evidence
    measured_s = {"ici": 0.0, "dcn": 0.0}
    for c in decomp.collectives:
        leg = c.get("leg") or "ici"
        measured_s[leg] += float(c.get("total_ms", 0.0)) / 1e3

    # recover the model/batch scale _score used from the report itself:
    # re-derive wire bytes with the same inputs reconcile() scored with
    model = report._model if hasattr(report, "_model") else None
    if model is not None:
        wire = _comm_bytes_by_link(model, mesh, report.schedule,
                                   report.micro_batches,
                                   report._batch_tokens)
        for kind in ("ici", "dcn"):
            t = measured_s[kind]
            b = wire[kind]
            if t <= 0 or b <= 0:
                continue
            alpha = float(report.links.get(kind, (0.0, 0.0))[0])
            beta_eff = b / t
            rows.append({
                "device_kind": device_kind, "op": "comm_link",
                "bucket": f"{topo},k{kind}", "dtype": "float32",
                "params": {
                    "kind": kind,
                    "alpha_us": round(alpha * 1e6, 3),
                    "beta_gbps": round(beta_eff / 1e9, 3),
                    "busbw_gbps": round(beta_eff / 1e9, 3),
                    "source": "reconcile",
                },
                "measured_ms": round(t * 1e3, 4),
            })

    # per-op unit costs: every named Pallas kernel with attributed time,
    # plus the compute tick itself
    unit = dict(decomp.kernels)
    unit["compute_step"] = float(decomp.terms.get("compute", 0.0))
    for op_name, ms in sorted(unit.items()):
        if ms <= 0:
            continue
        rows.append({
            "device_kind": device_kind, "op": "op_cost",
            "bucket": f"{topo},{op_name}", "dtype": "float32",
            "params": {"op": op_name, "ms_per_step": round(ms, 4),
                       "source": "reconcile"},
            "measured_ms": round(ms, 4),
        })
    return rows


def seed_cache(rows, path=None):
    """Merge rows into the winner cache (atomic; returns count)."""
    return seed_entries(rows, path=path)


# ------------------------------------------------------------ wiring

def reconcile_trace(trace_dir, *, steps=1, model, pod, mesh_shape,
                    schedule="none", micro_batches=1, offload=False,
                    batch_tokens=None, mesh=None, cache=None):
    """Parse + reconcile in one call (the CLI / engine entry). Returns
    (decomp, report) or (None, None) when the trace yields no
    decomposition — one warning, never an exception."""
    from ..profiling import step_trace
    decomp = step_trace.decompose_dir(trace_dir, steps=steps, mesh=mesh)
    if decomp is None:
        return None, None
    try:
        report = reconcile(decomp, model, pod, mesh_shape,
                           schedule=schedule,
                           micro_batches=micro_batches, offload=offload,
                           batch_tokens=batch_tokens, cache=cache)
    except Exception as e:  # noqa: BLE001 - advisory, never fatal
        logger.warning(f"reconcile: scoring failed "
                       f"({type(e).__name__}: {e})")
        return decomp, None
    # stash the scoring inputs seed_rows needs to re-derive wire bytes
    report._model = model
    report._batch_tokens = (batch_tokens if batch_tokens is not None
                            else max(1, 8 * pod.n_chips)
                            * model.max_seq_len)
    return decomp, report


def from_engine(engine, trace_dir, steps=1):
    """Reconcile a live engine's freshly captured trace: descriptors
    from the engine's model/config, the mesh from its topology, the
    schedule/microbatch/offload facts from its pipeline state. Returns
    (decomp, report) or (None, None)."""
    model = ModelDesc.from_model_config(
        getattr(engine.model, "config", None))
    pod = PodDesc.from_devices()
    mesh_shape = dict(engine.mesh.shape)
    pinfo = engine.pipeline_report() or {}
    schedule = pinfo.get("schedule", "none") or "none"
    micro = int(pinfo.get("micro_batches", 1) or 1)
    offload = bool(getattr(engine, "offload_enabled", False))
    batch_tokens = int(engine.config.train_batch_size) \
        * model.max_seq_len
    return reconcile_trace(
        trace_dir, steps=steps, model=model, pod=pod,
        mesh_shape=mesh_shape, schedule=schedule, micro_batches=micro,
        offload=offload, batch_tokens=batch_tokens, mesh=engine.mesh)
