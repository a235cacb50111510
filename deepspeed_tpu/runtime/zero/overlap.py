"""Communication overlap for ZeRO — collectives hidden under compute.

Counterpart of the reference's ``overlap_comm`` machinery (stage_1_and_2.py
reduce_independent_p_g_buckets_and_remove_grads:926 — per-bucket async
reduce during backward; partitioned_param_coordinator.py:261 __all_gather
prefetch) and the ZeRO++ hierarchical collectives (utils/groups.py:505).
Where the reference owns CUDA streams and fires NCCL ops from grad hooks,
here the SAME schedule is obtained declaratively, in three layers:

1. **XLA flags** (`xla_overlap_flags`): the latency-hiding scheduler and
   async-collective-fusion flags make XLA split every collective into
   ``*-start``/``*-done`` pairs and slide compute between them; the
   backward all-gather pipelining pass double-buffers in-loop gathers
   across scan iterations (the ZeRO-3 prefetch engine, in the compiler).
   Flags must land *before* backend init — the engine applies them when
   it can, and ``DSTPU_COMM_OVERLAP=1`` applies them at
   ``import deepspeed_tpu`` time for launcher/bench paths. Channel and
   gating are platform-dependent (`overlap_env_var`): ``--xla_tpu_*``
   flags live only in libtpu's own flag registry — host-side
   ``XLA_FLAGS`` parsing FATALs on them (and on any name outside the
   DebugOptions proto) — so the TPU set rides ``LIBTPU_INIT_ARGS``
   while the GPU set, whose names are proto-resident, rides
   ``XLA_FLAGS``. Off TPU/GPU no flags are emitted at all.

2. **Per-layer gradient reduction** (`make_layer_comm_hook`): a
   ``custom_vjp`` identity wrapped around each scanned layer's params.
   Its backward constrains the layer's cotangent to the per-layer ZeRO
   grad sharding, which forces GSPMD to emit that layer's reduce-scatter
   INSIDE the backward scan body — grad comm for layer i overlaps
   backward compute of layer i-1 — instead of one monolithic reduction
   of the stacked (L, ...) tree after the loop. ``bucket_bytes`` gates
   which layers get an in-scan collective (small layers coalesce into
   the post-loop reduction, the reference's bucket semantics). With
   ``hierarchical``, the constraint is staged: inner ('data','expert')
   axes first (ICI reduce-scatter of the full payload), then the full
   spec including 'data_outer' (the DCN hop moves only the 1/W_inner
   scattered shard — MiCS/ZeRO++ two-stage). The forward optionally
   constrains the layer to its gathered (TP-only) spec — one explicit
   all-gather at the top of the scan body for ZeRO-3, the op the
   pipelining pass prefetches.

3. **HLO verification** (`overlap_report`): the schedule above is a
   *request*; this parses ``compiled.as_text()`` and reports what XLA
   actually emitted — collectives, ``*-start/*-done`` async pairs,
   which sit inside while (scan) bodies, and which mesh axes each
   collective's replica groups correspond to. CPU lowers collectives
   synchronously (no start/done in HLO), so async-pair assertions are
   only meaningful on TPU/GPU; placement and axis checks work anywhere.
"""

import os
import re

import numpy as np

from jax.sharding import PartitionSpec as P

from ...utils.logging import logger

# ---------------------------------------------------------------- XLA flags

# The v5e/v4 overlap set (latency-hiding scheduler + async collective
# fusion + data-parallel all-reduce optimization). Every flag is
# boolean-valued and safe at dp=1.
TPU_OVERLAP_FLAGS = (
    "--xla_tpu_enable_latency_hiding_scheduler=true",
    "--xla_enable_async_all_gather=true",
    "--xla_enable_async_collective_permute=true",
    "--xla_tpu_enable_async_collective_fusion=true",
    "--xla_tpu_enable_async_collective_fusion_fuse_all_gather=true",
    "--xla_tpu_enable_async_collective_fusion_multiple_steps=true",
    "--xla_tpu_overlap_compute_collective_tc=true",
    "--xla_tpu_enable_data_parallel_all_reduce_opt=true",
    "--xla_tpu_data_parallel_opt_different_sized_ops=true",
)
# ZeRO-3: rotate in-loop all-gathers across backward scan iterations
# (the compiler-level double buffer the prefetch hook's explicit gather
# feeds).
TPU_PREFETCH_FLAGS = (
    "--xla_tpu_enable_ag_backward_pipelining=true",
)
GPU_OVERLAP_FLAGS = (
    "--xla_gpu_enable_latency_hiding_scheduler=true",
)


def platform_guess():
    """Best-effort platform WITHOUT initializing the backend (reading
    jax.default_backend() would lock in the current XLA_FLAGS)."""
    plats = os.environ.get("JAX_PLATFORMS", "")
    if plats:
        return plats.split(",")[0].strip() or None
    import importlib.util
    if importlib.util.find_spec("libtpu") is not None:
        return "tpu"
    return None


def xla_overlap_flags(platform, prefetch=True, bucket_mb=0):
    """The flag list for ``platform`` (None/cpu -> no flags: names
    outside the host DebugOptions proto are fatal in XLA_FLAGS, and
    there is no scheduler to tune on CPU anyway)."""
    if platform == "tpu":
        flags = list(TPU_OVERLAP_FLAGS)
        if prefetch:
            flags += list(TPU_PREFETCH_FLAGS)
        return flags
    if platform in ("gpu", "cuda", "rocm"):
        flags = list(GPU_OVERLAP_FLAGS)
        if bucket_mb:
            nbytes = int(bucket_mb) * (1 << 20)
            flags += [
                f"--xla_gpu_all_reduce_combine_threshold_bytes={nbytes}",
                f"--xla_gpu_all_gather_combine_threshold_bytes={nbytes}",
                f"--xla_gpu_reduce_scatter_combine_threshold_bytes={nbytes}",
            ]
        return flags
    return []


def overlap_env_var(platform):
    """Which env var carries the overlap flags: ``--xla_tpu_*`` names
    exist only in libtpu's flag registry (host XLA_FLAGS parsing FATALs
    on them), so TPU rides LIBTPU_INIT_ARGS; GPU names are DebugOptions-
    proto-resident and ride XLA_FLAGS."""
    return "LIBTPU_INIT_ARGS" if platform == "tpu" else "XLA_FLAGS"


def backend_initialized():
    try:
        from jax._src import xla_bridge as xb
        return bool(getattr(xb, "_backends", None))
    except Exception:  # noqa: BLE001 - conservative: assume live
        return True


def apply_xla_flags(flags, env_var="XLA_FLAGS"):
    """Append ``flags`` to ``env_var`` (LIBTPU_INIT_ARGS for the TPU
    set, see ``overlap_env_var``) if the backend has not initialized
    yet. Returns (applied, reason) — never raises; flags that are
    already present count as applied."""
    if not flags:
        return True, "no flags for this platform"
    current = os.environ.get(env_var, "")
    have = {f.split("=")[0] for f in current.split()}
    missing = [f for f in flags if f.split("=")[0] not in have]
    if not missing:
        return True, f"already set in {env_var}"
    if backend_initialized():
        return False, ("backend already initialized; set "
                       "DSTPU_COMM_OVERLAP=1 before first device use")
    os.environ[env_var] = (current + " " + " ".join(missing)).strip()
    return True, f"appended {len(missing)} flags to {env_var}"


def apply_env_overlap_flags():
    """Import-time hook (deepspeed_tpu/__init__.py): DSTPU_COMM_OVERLAP=1
    applies the overlap flag set before anything touches the backend —
    the only reliable path for bench/launcher subprocesses."""
    if os.environ.get("DSTPU_COMM_OVERLAP") != "1":
        return False
    platform = platform_guess()
    flags = xla_overlap_flags(
        platform,
        prefetch=os.environ.get("DSTPU_COMM_PREFETCH", "1") == "1",
        bucket_mb=int(os.environ.get("DSTPU_COMM_BUCKET_MB", "0") or 0))
    applied, reason = apply_xla_flags(flags, overlap_env_var(platform))
    if flags and not applied:
        logger.warning(f"comm_overlap env flags not applied: {reason}")
    return applied


# ------------------------------------------------------ per-layer specs

SKIP = "skip"  # sentinel leaf: annotator leaves this one to XLA


def drop_layer_dim(spec):
    """Per-layer spec from a stacked (L, ...) leaf spec. The scan slices
    dim 0; a spec that shards dim 0 cannot be expressed per-layer ->
    SKIP."""
    entries = list(spec)
    if entries and entries[0] is not None:
        return SKIP
    return P(*entries[1:])


def split_inner(spec, outer_axis="data_outer"):
    """Spec with ``outer_axis`` removed from every entry — stage 1 of the
    hierarchical reduction (constrain here first: GSPMD reduce-scatters
    over the remaining inner axes on ICI; the later full-spec constraint
    adds only the small cross-slice hop). Returns SKIP if the spec never
    mentions outer_axis (nothing to stage)."""
    if spec == SKIP:
        return SKIP
    out, changed = [], False
    for e in spec:
        if isinstance(e, tuple) and outer_axis in e:
            rest = tuple(a for a in e if a != outer_axis)
            out.append(rest if len(rest) > 1 else
                       (rest[0] if rest else None))
            changed = True
        elif e == outer_axis:
            out.append(None)
            changed = True
        else:
            out.append(e)
    return P(*out) if changed else SKIP


def _is_spec_leaf(x):
    return isinstance(x, P) or x == SKIP


def layer_grad_bytes(layer_tree, gdtype=None):
    """Static per-layer gradient payload (bytes) — the bucket gate."""
    import jax
    import jax.numpy as jnp
    itemsize = (jnp.dtype(gdtype).itemsize if gdtype is not None else None)
    total = 0
    for leaf in jax.tree.leaves(layer_tree):
        n = int(np.prod(leaf.shape)) if leaf.shape else 1
        total += n * (itemsize if itemsize is not None
                      else leaf.dtype.itemsize)
    return total


def make_layer_comm_hook(grad_specs, *, gather_specs=None,
                         hierarchical=False, outer_axis="data_outer",
                         dcn_quantize=False, bucket_bytes=0, gdtype=None):
    """Build the per-layer annotation hook the engine installs on the
    model (``model._layer_comm_hook``); the model calls it on each
    scanned layer's param slice (gpt2.block_forward).

    grad_specs / gather_specs: pytrees of PER-LAYER PartitionSpec (or
    SKIP), structurally matching one layer's param tree. Forward:
    constrain to gather_specs (the explicit ZeRO-3 all-gather). Backward:
    constrain the cotangent to grad_specs — staged via ``split_inner``
    when hierarchical — forcing the per-scan-iteration reduce-scatter.
    Specs are plain PartitionSpecs resolved against the ambient mesh
    (the engine traces under ``jax.set_mesh``).
    """
    import jax

    inner_specs = (jax.tree.map(
        lambda s: split_inner(s, outer_axis), grad_specs,
        is_leaf=_is_spec_leaf) if hierarchical else None)
    if dcn_quantize and inner_specs is None:
        # no hierarchical stage -> no DCN hop to compress: clamping the
        # full local cotangent would be silent precision loss for zero
        # bandwidth benefit
        logger.warning("comm_overlap.dcn_quantize ignored: no "
                       "hierarchical data_outer stage on this mesh")
        dcn_quantize = False

    def _constrain(tree, specs):
        def leaf(s, x):
            if s == SKIP:
                return x
            return jax.lax.with_sharding_constraint(x, s)
        return jax.tree.map(leaf, specs, tree, is_leaf=_is_spec_leaf)

    def should_annotate(layer_tree):
        """Static bucket gate: small layers skip the in-scan collective
        (they coalesce into the post-backward reduction instead — the
        reference never fires a reduce below its bucket size either)."""
        return (not bucket_bytes
                or layer_grad_bytes(layer_tree, gdtype) >= bucket_bytes)

    @jax.custom_vjp
    def annotate(layer):
        return (_constrain(layer, gather_specs)
                if gather_specs is not None else layer)

    def fwd(layer):
        return annotate(layer), None

    def bwd(_, g):
        if inner_specs is not None:
            # stage 1: ICI reduce-scatter of the full payload
            g = _constrain(g, inner_specs)
            if dcn_quantize:
                # qgZ placement: clamp the inner-reduced shard feeding
                # the DCN hop — only leaves that actually HAVE a
                # data_outer stage (inner spec != SKIP); without a
                # hierarchical stage there is no DCN wire and the clamp
                # would be pure precision loss (the factory drops it,
                # see below)
                from ...comm.quantized import dcn_precision_clamp

                def clamp(s, x):
                    return x if s == SKIP else dcn_precision_clamp(x)
                g = jax.tree.map(clamp, inner_specs, g,
                                 is_leaf=_is_spec_leaf)
        # final (or only) stage: the full ZeRO grad partition; under
        # hierarchical this adds just the cross-DCN hop of the shard
        g = _constrain(g, grad_specs)
        return (g,)

    annotate.defvjp(fwd, bwd)

    def hook(layer):
        if not should_annotate(layer):
            return (_constrain(layer, gather_specs)
                    if gather_specs is not None else layer)
        return annotate(layer)

    hook.should_annotate = should_annotate  # exposed for tests
    return hook


# ------------------------------------------------------- HLO inspection

_COLL_OPS = ("all-reduce", "reduce-scatter", "all-gather", "all-to-all",
             "collective-permute")
# '%name = TYPE opcode(' — opcode may carry -start/-done and .N
# suffixes; TYPE may be a tuple (async start shapes) so anything between
# '=' and the first 'opcode(' is skipped lazily
_COLL_LINE_RE = re.compile(
    r"%[\w.\-]+\s*=\s*.*?\s"
    r"(all-reduce|reduce-scatter|all-gather|all-to-all|collective-permute)"
    r"(-start|-done)?(?:\.\d+)?\(")
# computation header: '%name (params...) -> ret {' (params nest parens,
# so only the leading '%name (' — instruction lines have '= ' after the
# name and never match)
_COMP_RE = re.compile(r"^\s*(?:ENTRY\s+)?%([\w.\-]+)\s*\(")
_WHILE_BODY_RE = re.compile(r"\bbody=%([\w.\-]+)")
_RG_EXPLICIT_RE = re.compile(r"replica_groups=\{(\{[\d,{} ]*\})\}")
_RG_IOTA_RE = re.compile(
    r"replica_groups=\[(\d+),(\d+)\]<=\[([\d,]+)\](?:T\(([\d,]+)\))?")


def parse_replica_groups(line):
    """Replica groups from one HLO line -> list of tuples of device ids,
    handling both the explicit ``{{0,1},{2,3}}`` and the iota
    ``[G,S]<=[dims]T(perm)`` forms. None if the line carries none."""
    m = _RG_IOTA_RE.search(line)
    if m:
        g, s = int(m.group(1)), int(m.group(2))
        dims = [int(x) for x in m.group(3).split(",")]
        ids = np.arange(int(np.prod(dims))).reshape(dims)
        if m.group(4):
            perm = [int(x) for x in m.group(4).split(",")]
            ids = ids.transpose(perm)
        return [tuple(int(x) for x in row)
                for row in ids.reshape(g, s)]
    m = _RG_EXPLICIT_RE.search(line)
    if m:
        return [tuple(int(x) for x in grp.split(",") if x.strip())
                for grp in re.findall(r"\{([\d, ]*)\}", m.group(1))]
    return None


def parse_collectives(hlo_text):
    """All collective ops in an HLO module text. Returns a list of dicts:
    {op, phase ('start'|'done'|None), computation, groups, line}."""
    out = []
    bodies = set()
    cur = None
    for line in hlo_text.splitlines():
        mc = _COMP_RE.match(line)
        if mc and line.rstrip().endswith("{"):
            cur = mc.group(1)
        for mb in _WHILE_BODY_RE.finditer(line):
            bodies.add(mb.group(1))
        m = _COLL_LINE_RE.search(line)
        if m:
            out.append({
                "op": m.group(1),
                "phase": (m.group(2) or "").lstrip("-") or None,
                "computation": cur,
                "groups": parse_replica_groups(line),
                "line": line.strip(),
            })
    for c in out:
        c["in_loop"] = c["computation"] in bodies
    return out


# host-staging copies: the XLA host-offload pass legalizes memory-kind
# transfers (pipeline activation rings, moment placement) into
# copy-start/copy-done pairs whose shapes carry the host memory space
# marker S(5). CPU has a single memory space, so these only appear on
# TPU/GPU programs.
_COPY_LINE_RE = re.compile(
    r"%[\w.\-]+\s*=\s*.*?\scopy(-start|-done)?(?:\.\d+)?\(")
_HOST_SPACE_RE = re.compile(r"S\(5\)")


def parse_host_copies(hlo_text):
    """Copy ops whose shapes carry the host memory space (S(5)) — the
    staging traffic host offload generates. Returns dicts
    {phase, computation, in_loop, line} like parse_collectives."""
    out = []
    bodies = set()
    cur = None
    for line in hlo_text.splitlines():
        mc = _COMP_RE.match(line)
        if mc and line.rstrip().endswith("{"):
            cur = mc.group(1)
        for mb in _WHILE_BODY_RE.finditer(line):
            bodies.add(mb.group(1))
        m = _COPY_LINE_RE.search(line)
        if m and _HOST_SPACE_RE.search(line):
            out.append({
                "phase": (m.group(1) or "").lstrip("-") or None,
                "computation": cur,
                "line": line.strip(),
            })
    for c in out:
        c["in_loop"] = c["computation"] in bodies
    return out


def count_async_pairs(collectives):
    """Matched ``*-start``/``*-done`` pairs per collective op kind."""
    pairs = 0
    for op in _COLL_OPS:
        starts = sum(1 for c in collectives
                     if c["op"] == op and c["phase"] == "start")
        dones = sum(1 for c in collectives
                    if c["op"] == op and c["phase"] == "done")
        pairs += min(starts, dones)
    return pairs


def expected_axis_groups(mesh, axes):
    """The replica-group partition a collective over mesh ``axes`` uses:
    a set of frozensets of device ids."""
    axes = (axes,) if isinstance(axes, str) else tuple(axes)
    names = list(mesh.axis_names)
    ids = np.asarray(
        [d.id for d in mesh.devices.flat]).reshape(mesh.devices.shape)
    perm = ([names.index(a) for a in names if a not in axes]
            + [names.index(a) for a in axes])
    size = int(np.prod([mesh.shape[a] for a in axes]))
    rows = ids.transpose(perm).reshape(-1, size)
    return {frozenset(int(x) for x in row) for row in rows}


def match_axes(groups, mesh):
    """Which mesh axes a collective's replica groups correspond to.
    Tries each single axis plus the canonical DP combinations; returns
    the first (smallest) matching axis tuple or None."""
    if not groups:
        return None
    got = {frozenset(g) for g in groups}
    from ...utils.groups import (DP_AXES, INNER_DP_AXES, EXPERT_DP_AXES,
                                 GRAD_REDUCE_AXES)
    candidates = ([(a,) for a in mesh.axis_names]
                  + [INNER_DP_AXES, EXPERT_DP_AXES, DP_AXES,
                     GRAD_REDUCE_AXES, tuple(mesh.axis_names)])
    for axes in candidates:
        try:
            if expected_axis_groups(mesh, axes) == got:
                return axes
        except KeyError:
            continue
    return None


def overlap_report(hlo_text, mesh=None):
    """Summarize a compiled module's collective schedule: counts, async
    start/done pairs, in-(scan)-loop placement, and per-collective mesh
    axes (when ``mesh`` is given). The dict the engine's
    ``verify_comm_overlap`` returns and the tier-1 HLO tests assert on."""
    colls = parse_collectives(hlo_text)
    axes = []
    for c in colls:
        c["axes"] = (match_axes(c["groups"], mesh)
                     if mesh is not None else None)
        if c["axes"]:
            axes.append(c["axes"])
    in_loop_by_op = {}
    for c in colls:
        if c["in_loop"]:
            in_loop_by_op[c["op"]] = in_loop_by_op.get(c["op"], 0) + 1
    # host staging traffic (pipeline ring offload / moment placement):
    # S(5)-space copies, async pairs counted like the collectives
    copies = parse_host_copies(hlo_text)
    copy_starts = sum(1 for c in copies if c["phase"] == "start")
    copy_dones = sum(1 for c in copies if c["phase"] == "done")
    return {
        "n_collectives": len(colls),
        "async_pairs": count_async_pairs(colls),
        "in_loop": sum(1 for c in colls if c["in_loop"]),
        # per-op in-(scan)-loop counts: a ring-attention step reports its
        # KV rotation here as 'collective-permute' (engine
        # verify_comm_overlap's acceptance signal for the overlap); a
        # pipelined step its stage rotation
        "in_loop_by_op": in_loop_by_op,
        "ops": sorted({c["op"] for c in colls}),
        "axes": sorted({tuple(a) for a in axes}),
        "host_copies": len(copies),
        "host_copy_async_pairs": min(copy_starts, copy_dones),
        "in_loop_host_copies": sum(1 for c in copies if c["in_loop"]),
        "collectives": colls,
    }
