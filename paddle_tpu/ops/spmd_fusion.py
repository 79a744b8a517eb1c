"""Distributed step fusion: collective-aware promotion to ONE shard_map
executable per mesh.

The whole-step promoter (ops/step_fusion.py) collapses a stable eager
training cycle into one jitted executable — but a DATA-PARALLEL cycle, whose
batch lives sharded over a device mesh, used to promote into a plain jit and
leave every collective decision (gradient all-reduce placement, sharded
optimizer update, found-inf sync) to the GSPMD partitioner's mood. This
module makes the promoter see the mesh: it classifies the recorded cycle's
external inputs by their placement (distributed/mesh.value_mesh_and_spec)
and, when the cycle is a recognizable data-parallel or group-sharded step,
lowers the promoted program GShard-style through `shard_map` instead —
explicit, deterministic collectives fused into the ONE launch:

  fwd + vjp            per-device on the local batch shard
  gradient psum        `lax.pmean` over the batch axes (the Fleet
                       fused-allreduce gradient merge: ALL gradients ride
                       one fused region, not one all-reduce per tensor)
  clip + update        replicated — or SHARDED when the optimizer states
                       carry a NamedSharding over the "sharding" axis
                       (ZeRO stage 1/2): each device updates its 1/Nth
                       slice and all-gathers the fresh parameter, the
                       DistributedFusedLamb shape
  guardian skip        the all-finite predicate is all-reduced (min) over
                       the mesh so every shard takes the SAME skip/keep
                       branch even when only one shard saw the blowup
  GradScaler           found-inf is computed on the post-psum grads and
                       all-reduced with the same predicate, so the
                       loss-scale transition is globally consistent

Safety: the lowering assumes the canonical data-parallel contract — a
scalar loss whose per-shard value is the mean over the local batch shard,
so `pmean(local losses)` IS the global loss and `pmean(local grads)` IS the
global gradient. Cycles that fit the shape but violate the contract (a
sum-reduced loss, a batch-coupled normalization) are caught by PROBATION:
the first fired replay runs the shard_map executable on scratch buffers,
replays the step eagerly (bitwise, through the existing transactional
split machinery), and compares. A divergence demotes the program to the
plain-jit lowering — still ONE executable, GSPMD-exact — attributed as
`spmd_divergence` in the flight recorder. Promotion itself never changes
numerics beyond the documented single-program layout caveat.

A plan is refused (plain jit promotion proceeds) when: no external input is
mesh-sharded; sharded inputs span different meshes (`mesh_mismatch`, also
the split reason when a fired program's inputs move to another mesh);
parameters themselves are sharded (model parallel / ZeRO-3 — GSPMD already
owns that placement); the loss is not scalar; or optimizer-state sharding
is not the uniform one-axis layout `shard_optimizer_states` produces.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from ..framework.flags import _FLAGS

__all__ = ["MeshPlan", "plan_program", "enabled", "sync_root_and_grads",
           "global_finite", "sharded_single_update", "compile_step",
           "compile_accum", "compile_update", "zero_accum",
           "fire_mismatch", "probation_tolerance",
           "pipeline_signature", "promote_pipeline", "fire_pipeline",
           "clear_pipeline_programs"]


def enabled():
    """SPMD lowering of promoted steps (FLAGS_eager_step_fusion_spmd)."""
    return bool(_FLAGS.get("FLAGS_eager_step_fusion_spmd", True))


class MeshPlan:
    """Everything the step compiler needs to lower one promoted cycle
    through shard_map over one mesh."""

    __slots__ = ("mesh", "mesh_token", "data_axes", "all_axes", "ext_specs",
                 "shard_checks", "param_specs", "param_gather",
                 "param_checks", "param_shard", "acc_layout", "accf_specs",
                 "acc_out_specs", "axes_label")

    def __init__(self):
        self.mesh = None
        self.mesh_token = None
        self.data_axes = ()       # grad/loss pmean axes (batch placement)
        self.all_axes = ()        # every size>1 axis (predicate all-reduce)
        self.ext_specs = ()       # PartitionSpec per program.ext_order slot
        self.shard_checks = ()    # (ext slot, expected NamedSharding)
        self.param_specs = ()     # per param: P() | its stored-shard spec
        self.param_gather = ()    # per param: None | (dim, nshard) — the
                                  # param is STORED sharded (GSPMD placed
                                  # it beside its ZeRO slots) and must be
                                  # all-gathered for the forward
        self.param_checks = ()    # per param: None (must be replicated) |
                                  # the expected NamedSharding
        self.param_shard = ()     # per param: None | (dim, nshard) sliced
                                  # (ZeRO) update
        self.acc_layout = ()      # per param: tuple of present-bools
        self.accf_specs = ()      # spec per present accumulator, flattened
        self.acc_out_specs = ()   # per param: tuple of specs (acc_names order)
        self.axes_label = ""


def _spec_of(norm):
    """PartitionSpec from the normalized per-dim axis tuples of
    distributed/mesh.value_mesh_and_spec."""
    entries = []
    for axes in norm:
        if not axes:
            entries.append(None)
        elif len(axes) == 1:
            entries.append(axes[0])
        else:
            entries.append(tuple(axes))
    while entries and entries[-1] is None:
        entries.pop()
    return P(*entries)


def plan_program(chain, slot_inputs, ext_order, updated, opt,
                 acc_names, root_flat):
    """(MeshPlan, None) when the cycle lowers through shard_map;
    (None, None) when it should promote through plain jit; (None, reason)
    when a mesh-level contradiction is worth attributing (the reason is a
    REASON_CODES entry, e.g. `mesh_mismatch`)."""
    from ..distributed.mesh import mesh_key, value_mesh_and_spec
    if not enabled():
        return None, None
    mesh = None
    token = None
    ext_info = {}
    for s in ext_order:
        t = slot_inputs.get(s)
        v = getattr(t, "_value", None)
        if v is None:
            return None, None
        m, norm = value_mesh_and_spec(v)
        if m is None:
            continue
        tk = mesh_key(m)
        if tk is None:
            return None, None
        if mesh is None:
            mesh, token = m, tk
        elif tk != token:
            return None, "mesh_mismatch"
        ext_info[s] = (norm, v.sharding)
    # parameters: replicated, or STORED sharded over the "sharding" axis
    # on exactly one dim — the placement GSPMD gives them after an eager
    # step beside ZeRO-sharded slots. Anything else (tensor-parallel
    # placements, "data"-sharded params) keeps the plain GSPMD lowering.
    param_gather = []
    param_info = []
    for p in updated:
        m, norm = value_mesh_and_spec(p._value)
        if m is None:
            param_gather.append(None)
            param_info.append(None)
            continue
        tk = mesh_key(m)
        if mesh is not None and tk != token:
            return None, "mesh_mismatch"
        if mesh is None:
            mesh, token = m, tk
        dims = [i for i, axes in enumerate(norm) if axes]
        if len(dims) != 1 or norm[dims[0]] != ("sharding",):
            return None, None
        nsh = int(mesh.shape.get("sharding", 1))
        pshape = tuple(p._value.shape)
        if nsh <= 1 or not pshape or pshape[dims[0]] % nsh:
            return None, None
        param_gather.append((dims[0], nsh))
        param_info.append((norm, p._value.sharding))
    if mesh is None:
        return None, None
    data_axes = sorted({a for norm, _ in ext_info.values()
                        for axes in norm for a in axes})
    if any(a not in ("data", "sharding") for a in data_axes):
        return None, None     # pipeline/model placements: plain jit
    if tuple(chain.flat_avals[root_flat][0]) != ():
        return None, None     # non-scalar loss: the pmean contract is moot

    nshard = int(mesh.shape.get("sharding", 1))
    param_shard = []
    acc_layout = []
    accf_specs = []
    acc_out_specs = []
    for k, p in enumerate(updated):
        row_present = []
        row_out = []
        shard_dim = None
        full_unsharded = False
        pshape = tuple(p._value.shape)
        for n in acc_names:
            a = opt._accumulators[n].get(p.name)
            row_present.append(a is not None)
            if a is None:
                row_out.append(P())
                continue
            m2, norm2 = value_mesh_and_spec(a)
            if m2 is None:
                if tuple(a.shape) == pshape and pshape:
                    full_unsharded = True
                accf_specs.append(P())
                row_out.append(P())
                continue
            if mesh_key(m2) != token:
                return None, "mesh_mismatch"
            dims = [i for i, axes in enumerate(norm2) if axes]
            if len(dims) != 1 or norm2[dims[0]] != ("sharding",) \
                    or nshard <= 1:
                return None, None   # non-canonical state sharding
            if shard_dim is None:
                shard_dim = dims[0]
            elif shard_dim != dims[0]:
                return None, None
            spec = _spec_of(norm2)
            accf_specs.append(spec)
            row_out.append(spec)
        if shard_dim is not None:
            if full_unsharded or not pshape \
                    or pshape[shard_dim] % nshard:
                # a full-shape replicated slot beside sharded ones (or an
                # indivisible dim) breaks the slice-update contract
                return None, None
            if param_gather[k] is not None \
                    and param_gather[k][0] != shard_dim:
                return None, None
            param_shard.append((shard_dim, nshard))
        else:
            if param_gather[k] is not None:
                # a stored-sharded param with replicated slots has no
                # slice-update to keep it local: plain lowering
                return None, None
            param_shard.append(None)
        acc_layout.append(tuple(row_present))
        acc_out_specs.append(tuple(row_out))

    plan = MeshPlan()
    plan.mesh = mesh
    plan.mesh_token = token
    plan.data_axes = tuple(data_axes)
    plan.all_axes = tuple(a for a, s in zip(mesh.axis_names,
                                            mesh.devices.shape)
                          if int(s) > 1)
    plan.ext_specs = tuple(
        _spec_of(ext_info[s][0]) if s in ext_info else P()
        for s in ext_order)
    plan.shard_checks = tuple(
        (s, ext_info[s][1]) for s in ext_order if s in ext_info)
    plan.param_specs = tuple(
        P() if info is None else _spec_of(info[0]) for info in param_info)
    plan.param_gather = tuple(param_gather)
    plan.param_checks = tuple(
        None if info is None else info[1] for info in param_info)
    plan.param_shard = tuple(param_shard)
    plan.acc_layout = tuple(acc_layout)
    plan.accf_specs = tuple(accf_specs)
    plan.acc_out_specs = tuple(acc_out_specs)
    plan.axes_label = "×".join(
        f"{a}{int(mesh.shape[a])}" for a in plan.all_axes) or "1"
    return plan, None


# ---------------------------------------------------------------------------
# traced pieces, woven into the step body by ops/step_fusion._compile
# ---------------------------------------------------------------------------

def sync_root_and_grads(plan, root_val, grads):
    """The gradient all-reduce + loss sync of the data-parallel contract:
    pmean over the batch axes. One fused region for EVERY gradient — the
    Fleet fused-allreduce gradient merge, emitted by construction."""
    if not plan.data_axes:
        return root_val, grads
    root_val = jax.lax.pmean(root_val, plan.data_axes)
    grads = [jax.lax.pmean(g, plan.data_axes) for g in grads]
    return root_val, grads


def global_finite(plan, vals):
    """The guardian's all-finite predicate, all-reduced (min) over every
    live mesh axis so the skip-step where()-rescue takes the same branch on
    every shard — a single poisoned shard skips the step EVERYWHERE."""
    from . import guardian
    return guardian.finite_all_reduced(vals, plan.all_axes)


def gather_params(plan, pvals):
    """Stored-sharded params (GSPMD keeps a ZeRO param beside its sharded
    slots) arrive as local shards: all-gather them to full for the forward
    — the ZeRO-3-style just-in-time gather, one per param per step."""
    out = []
    for k, pv in enumerate(pvals):
        g = plan.param_gather[k]
        out.append(pv if g is None else
                   jax.lax.all_gather(pv, "sharding", axis=g[0],
                                      tiled=True))
    return out


def sharded_single_update(plan, k, opt, pv, gv, acc_dict, lr, step_count):
    """ZeRO-sharded optimizer update for parameter k: slice the (full,
    post-psum) grad — and the param, unless it is stored sharded already —
    to this device's 1/Nth along the state-sharded dim, update with the
    LOCAL accumulator shard, and (for replicated storage) all-gather the
    fresh parameter back — the DistributedFusedLamb shape. The new
    accumulator stays local (its out_spec keeps it sharded)."""
    dim, n = plan.param_shard[k]
    chunk = gv.shape[dim] // n
    idx = jax.lax.axis_index("sharding")
    gv_s = jax.lax.dynamic_slice_in_dim(gv, idx * chunk, chunk, dim)
    stored_local = plan.param_gather[k] is not None
    pv_s = pv if stored_local else \
        jax.lax.dynamic_slice_in_dim(pv, idx * chunk, chunk, dim)
    np_s, na = opt._single_update(pv_s, gv_s, acc_dict, lr, step_count)
    if stored_local:
        return np_s, na        # storage stays sharded (out_spec local)
    return jax.lax.all_gather(np_s, "sharding", axis=dim, tiled=True), na


def _shard_map(local, mesh, in_specs, out_specs):
    """The promoted bodies were recorded from eager ops that know nothing
    of varying-axis types (a `jnp.ones` cotangent seeds a loss that
    varies over "data"), so the manual region runs without the
    varying-axis check; replication is what the explicit pmean/all_gather
    in the body establish, and probation verifies it numerically."""
    return jax.shard_map(local, mesh=mesh, in_specs=in_specs,
                         out_specs=out_specs, check_vma=False)


def compile_step(plan, step_fn, n_params, n_scaler, n_extras,
                 donate_argnums):
    """Wrap the (local-semantics) step body in shard_map over the plan's
    mesh and jit the whole thing — the ONE executable per mesh. The outer
    call signature is identical to the plain lowering (pvals, ext, accs,
    lr, step_count[, scale, good, bad]), so the firing hook and the
    donation argnums are shared verbatim."""
    P0 = P()
    acc_layout = plan.acc_layout
    in_specs = (
        tuple(plan.param_specs),     # params: replicated or stored-sharded
        tuple(plan.ext_specs),       # batch shards / replicated side inputs
        tuple(plan.accf_specs),      # optimizer slots (sharded slots local)
        P0, P0,                      # lr, step_count
    ) + (P0,) * n_scaler
    out_specs = (
        P0,                          # loss (post-pmean, replicated)
        (P0,) * n_params,            # grads (post-pmean, replicated)
        tuple(plan.param_specs),     # new params (storage layout preserved)
        tuple(plan.acc_out_specs),   # new slots (sharded ones stay local)
    ) + (P0,) * n_extras

    def local(pv_t, ext_t, accf_t, lr, step_count, *sargs):
        it = iter(accf_t)
        accs = [[next(it) if pres else None for pres in row]
                for row in acc_layout]
        out = step_fn(list(pv_t), list(ext_t), accs, lr, step_count, *sargs)
        return (out[0], tuple(out[1]), tuple(out[2]),
                tuple(tuple(r) for r in out[3])) + tuple(out[4:])

    smapped = _shard_map(local, plan.mesh, in_specs, out_specs)

    def wrapper(pvals, ext, accs, lr, step_count, *sargs):
        flat = tuple(a for row in accs for a in row if a is not None)
        return smapped(tuple(pvals), tuple(ext), flat, lr, step_count,
                       *sargs)

    return jax.jit(wrapper, donate_argnums=donate_argnums)


# ---------------------------------------------------------------------------
# super-cycle (grad accumulation) lowering: the sub-executable accumulates
# LOCAL gradients — no collective per micro-batch — and the update
# executable fires ONE fused pmean over the accumulated sums before the
# optimizer update: k× less gradient traffic than per-micro-batch sync,
# numerically pmean(Σ local) == Σ pmean(local) (linearity; probation
# verifies within single-program tolerance).
#
# A device-varying accumulator must cross launch boundaries as a real
# global array: it carries ONE stacked leading dim of size
# Π|data axes|, sharded over those axes — each device owns its [1, ...]
# slab of local gradient sums.
# ---------------------------------------------------------------------------

def _stack_spec(plan):
    """PartitionSpec of the stacked-accumulator leading dim."""
    axes = plan.data_axes
    return P(axes[0] if len(axes) == 1 else tuple(axes))


def stack_devices(plan):
    import math
    return math.prod(int(plan.mesh.shape[a]) for a in plan.data_axes)


def zero_accum(plan, shapes):
    """Zero grad accumulators for one super-cycle program: per param a
    [n_dev, *shape] array sharded over the data axes on dim 0."""
    from jax.sharding import NamedSharding
    n = stack_devices(plan)
    sharding = NamedSharding(plan.mesh, _stack_spec(plan))
    return [jax.device_put(jnp.zeros((n,) + tuple(s), d), sharding)
            for s, d in shapes]


def compile_accum(plan, sub_fn, n_params, n_tail):
    """shard_map lowering of the micro-batch sub-executable: per-device
    fwd+vjp on the local batch shard, local gradient sums into the stacked
    accumulator, NO gradient collective (only the scalar loss pmean the
    sub body emits). `n_tail` counts replicated scalar tail args (hoisted
    RNG + the running fwd-finite predicate)."""
    P0 = P()
    sspec = _stack_spec(plan)
    in_specs = (
        tuple(plan.param_specs),
        tuple(plan.ext_specs),
        (sspec,) * n_params,
    ) + (P0,) * n_tail
    def local(pv_t, ext_t, acc_t, *tail):
        acc_in = [a[0] for a in acc_t]
        out = sub_fn(list(pv_t), list(ext_t), acc_in, *tail)
        new_acc = tuple(a[None] for a in out[1])
        return (out[0], new_acc) + tuple(out[2:])

    # the sub body returns (loss, new_acc[, fwd_ok]) — fwd_ok present iff
    # the program checks, signalled by the builder via an fn attribute
    n_extra = 1 if getattr(sub_fn, "_returns_fwd_ok", False) else 0
    specs = (P0, (sspec,) * n_params) + (P0,) * n_extra
    m = _shard_map(local, plan.mesh, in_specs, specs)

    def wrapper(pvals, ext, acc, *tail):
        return m(tuple(pvals), tuple(ext), tuple(acc), *tail)
    return jax.jit(wrapper)


def compile_update(plan, upd_fn, n_params, n_tail, n_extras,
                   donate_argnums):
    """shard_map lowering of the boundary update executable: ONE fused
    pmean region over the accumulated gradient sums (inside `upd_fn`),
    then the same clip/update/guardian/scaler weave as the whole-step
    lowering — sharded (ZeRO) slots update their local 1/Nth."""
    P0 = P()
    sspec = _stack_spec(plan)
    acc_layout = plan.acc_layout
    in_specs = (
        tuple(plan.param_specs),
        tuple(plan.accf_specs),
        (sspec,) * n_params,
        P0, P0,
    ) + (P0,) * n_tail
    out_specs = (
        (P0,) * n_params,            # grads (post-pmean, replicated)
        tuple(plan.param_specs),
        tuple(plan.acc_out_specs),
    ) + (P0,) * n_extras

    def local(pv_t, accf_t, gsum_t, lr, step_count, *tail):
        it = iter(accf_t)
        accs = [[next(it) if pres else None for pres in row]
                for row in acc_layout]
        gsum = [g[0] for g in gsum_t]
        out = upd_fn(list(pv_t), accs, gsum, lr, step_count, *tail)
        return (tuple(out[0]), tuple(out[1]),
                tuple(tuple(r) for r in out[2])) + tuple(out[3:])

    smapped = _shard_map(local, plan.mesh, in_specs, out_specs)

    def wrapper(pvals, accs, gsum, lr, step_count, *tail):
        flat = tuple(a for row in accs for a in row if a is not None)
        return smapped(tuple(pvals), flat, tuple(gsum), lr, step_count,
                       *tail)

    return jax.jit(wrapper, donate_argnums=donate_argnums)


# ---------------------------------------------------------------------------
# fire-time verification + probation
# ---------------------------------------------------------------------------

def fire_mismatch(plan, ext_vals, params):
    """None when this fire's placements still match the plan, else
    "mesh_mismatch": the batch moved to another mesh/layout or a parameter
    got sharded under the program's feet — the compiled collectives would
    run over the WRONG axes, so the program must die and re-promote."""
    from ..distributed.mesh import value_mesh_and_spec
    try:
        for s, expected in plan.shard_checks:
            if getattr(ext_vals[s], "sharding", None) != expected:
                return "mesh_mismatch"
        for p, expected in zip(params, plan.param_checks):
            if expected is None:
                m, _ = value_mesh_and_spec(p._value)
                if m is not None:
                    return "mesh_mismatch"
            elif getattr(p._value, "sharding", None) != expected:
                return "mesh_mismatch"
    except Exception:
        return "mesh_mismatch"
    return None


def probation_tolerance(dtype):
    """(rtol, atol) for the probation fused-vs-eager comparison: layout
    differences only, scaled to the compute dtype."""
    d = jnp.dtype(dtype)
    if d in (jnp.dtype(jnp.bfloat16), jnp.dtype(jnp.float16)):
        return 3e-2, 1e-2
    return 2e-3, 1e-5


# ---------------------------------------------------------------------------
# pipeline promotion registry
# ---------------------------------------------------------------------------
# The pipe-axis train step (meta_parallel/spmd_pipeline.PipelineTrainStep)
# is already ONE shard_map program — k micro-batches rotated between stages
# by a single lax.ppermute per scan step, fwd+bwd+update fused. What it
# lacked was the funnel's bookkeeping: programs compiled as anonymous bare
# jits, invisible to the flight recorder and the retrace counters, and a
# schedule change (micro-batch count, virtual-stage interleave, optimizer
# swap) silently rebuilt the whole step. This registry gives every pipeline
# program the same lifecycle as a promoted cycle: a canonical mesh-keyed
# signature, step.promote / step.fire events, STEP_STATS accounting, and a
# `pipe_schedule_mismatch` record when a new schedule forces a second
# program over the same mesh + stage structure.

_PIPE_PROGRAMS = {}        # sig -> _PipelineProgram
_PIPE_BASES = {}           # base key -> last schedule tuple seen


class _PipelineProgram:
    """One promoted pipeline train-step executable."""

    __slots__ = ("sig", "label", "exe", "fires", "n_launches", "chain",
                 "entries", "spmd_plan")

    def __init__(self, sig, label, exe, n_launches):
        self.sig = sig
        self.label = label
        self.exe = exe
        self.fires = 0
        self.n_launches = n_launches
        # goodput.on_fused_fire introspection surface (no recorded cycle:
        # a caller pins exact FLOPs for pipeline programs)
        self.chain = None
        self.entries = ()
        self.spmd_plan = None


def pipeline_signature(mesh, axis, num_stages, num_virtual, num_micro,
                       stage_struct, opt):
    """Canonical identity of one pipeline train-step program: the mesh key
    + pipe axis name + stage structure (what is compiled in) and the
    schedule + optimizer binding (what forces a recompile). Returns None
    when the mesh has no canonical key — the caller falls back to an
    anonymous jit and the build is attributed `collective_unkeyed`."""
    from ..distributed.mesh import mesh_key
    mk = mesh_key(mesh)
    if mk is None:
        return None
    try:
        opt_key = (type(opt).__qualname__, tuple(opt._extra_cache_key()))
    except Exception:
        opt_key = (type(opt).__qualname__,)
    return ("pipe", mk, axis,
            (int(num_stages), int(num_virtual), int(num_micro)),
            tuple(stage_struct), opt_key)


def _pipe_base(sig):
    # everything but the schedule triple: same mesh + stage structure
    return (sig[1], sig[2], sig[4], sig[5])


def promote_pipeline(sig, label, build, n_launches=1):
    """Look up or build the pipeline program for `sig`. `build()` returns
    the compiled step callable; the first build of a signature emits
    `step.promote` and counts as a promotion, and a signature that differs
    from a previously promoted one ONLY in its schedule triple is recorded
    as `pipe_schedule_mismatch` before building — the doctor's hint for
    schedule churn. `sig=None` (unkeyable mesh) builds uncached and poisons
    as `collective_unkeyed`."""
    from ..profiler.events import EVENTS as _EVENTS
    from ..profiler.step_fusion import STEP_STATS
    if sig is None:
        _EVENTS.emit("step.record", "pipeline_step",
                     reason="collective_unkeyed",
                     detail={"kind": "pipe", "label": label})
        return _PipelineProgram(None, label, build(), n_launches)
    prog = _PIPE_PROGRAMS.get(sig)
    if prog is not None:
        return prog
    base = _pipe_base(sig)
    prev_sched = _PIPE_BASES.get(base)
    if prev_sched is not None and prev_sched != sig[3]:
        _EVENTS.emit("step.record", "pipeline_step",
                     reason="pipe_schedule_mismatch",
                     detail={"kind": "pipe", "label": label,
                             "prev_schedule": prev_sched,
                             "schedule": sig[3]})
    prog = _PipelineProgram(sig, label, build(), n_launches)
    _PIPE_PROGRAMS[sig] = prog
    _PIPE_BASES[base] = sig[3]
    if len(_PIPE_PROGRAMS) > 16:
        _PIPE_PROGRAMS.pop(next(iter(_PIPE_PROGRAMS)))
    STEP_STATS.promoted(label)
    _EVENTS.emit("step.promote", label,
                 detail={"pipe": True, "schedule": sig[3],
                         "mesh_axes": sig[1][0] if sig[1] else None,
                         "launches_estimate": n_launches})
    return prog


def fire_pipeline(prog):
    """One completed pipeline step through `prog.exe`: the step.fire /
    goodput accounting of a fused replay (launch savings are the unfused
    schedule's per-micro-batch launches collapsed into one program)."""
    from ..profiler.events import EVENTS as _EVENTS
    from ..profiler.step_fusion import STEP_STATS
    from ..profiler import goodput as _goodput
    prog.fires += 1
    STEP_STATS.replay(prog.label, prog.n_launches, 0)
    _goodput.on_fused_fire(prog)
    _EVENTS.emit("step.fire", prog.label,
                 detail={"pipe": True, "fires": prog.fires,
                         "launches_saved": prog.n_launches - 1})


def clear_pipeline_programs():
    """Test/teardown hook: drop every promoted pipeline program."""
    _PIPE_PROGRAMS.clear()
    _PIPE_BASES.clear()
