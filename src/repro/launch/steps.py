"""Step builders + sharding assembly for train / prefill / decode.

Everything here works on ShapeDtypeStructs (``jax.eval_shape``) so the same
code path serves the 512-device dry-run (no allocation) and real execution.
"""

from __future__ import annotations

import functools
from typing import Any, Optional

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.core import ccache
from repro.core.ccache import Topology
from repro.core.defer_schedule import DeferSchedule
from repro.core.grad_merge import merge_gradients, microbatched_value_and_grad
from repro.core.merge_functions import ADD, int8_compressed_add
from repro.models.module import split_params
from repro.models.registry import build_model
from repro.optim import make_optimizer, warmup_cosine
from repro.optim.optimizers import OptState
from repro.sharding import partition
from repro.sharding.partition import sharding_rules, spec_for

PyTree = Any


# ---------------------------------------------------------------------------
# Lowering rules: per (arch x shape x mesh) logical->mesh adjustments.
# ---------------------------------------------------------------------------


def lowering_rules(cfg, shape_cfg, mesh: Mesh) -> dict:
    rules: dict = {}
    model_size = mesh.shape.get("model", 1)
    dp = mesh.shape.get("data", 1) * mesh.shape.get("pod", 1)
    if shape_cfg.kind == "train":
        # Megatron-style sequence parallelism for the *stored* residual
        # stream (remat-saved layer inputs shard over the model axis) — only
        # when the saved stack would otherwise blow past a few GB/device;
        # for small models the resharding collectives aren't worth it.
        tokens_per_dev = shape_cfg.global_batch * shape_cfg.seq_len // max(dp, 1)
        saved_bytes = cfg.n_layers * tokens_per_dev * cfg.d_model * 2
        if saved_bytes > 4 * 1024**3 and model_size > 1:
            rules["seq_res"] = "model"
    if shape_cfg.kind == "decode":
        if cfg.n_kv_heads % model_size != 0:
            # KV heads don't divide TP: shard the cache on sequence instead.
            rules["kv_heads"] = None
            rules["cache_seq"] = "model"
    if cfg.n_params() > 1e11:
        # Giants: FSDP the embed dim across pods too.
        rules["embed"] = ("pod", "data")
    return rules


def axes_to_shardings(axes_tree: PyTree, specs_tree: PyTree, mesh: Mesh,
                      rules: dict) -> PyTree:
    """Tree of logical-axes tuples + tree of SDS -> tree of NamedShardings."""
    is_axes = lambda x: isinstance(x, tuple) and all(
        isinstance(e, (str, type(None))) for e in x)
    flat_ax, treedef = jax.tree_util.tree_flatten(axes_tree, is_leaf=is_axes)
    flat_sp = treedef.flatten_up_to(specs_tree)
    out = [NamedSharding(mesh, spec_for(tuple(s.shape), a, mesh, rules))
           for a, s in zip(flat_ax, flat_sp)]
    return jax.tree_util.tree_unflatten(treedef, out)


def opt_state_axes(opt_specs: OptState, param_axes: PyTree) -> OptState:
    """Logical axes for optimizer state, mirroring the parameter axes."""
    def nu_axes(ax, nu_leaf):
        if isinstance(nu_leaf, dict) and "row" in nu_leaf:
            return {"row": tuple(ax[:-1]), "col": tuple(ax[:-2]) + (ax[-1],)}
        if isinstance(nu_leaf, dict) and "full" in nu_leaf:
            return {"full": tuple(ax)}
        return tuple(ax)

    is_axes = lambda x: isinstance(x, tuple) and all(
        isinstance(e, (str, type(None))) for e in x)
    flat_ax, treedef = jax.tree_util.tree_flatten(param_axes, is_leaf=is_axes)

    mu_axes = None
    if opt_specs.mu is not None:
        mu_axes = jax.tree_util.tree_unflatten(treedef, flat_ax)
    flat_nu = treedef.flatten_up_to(opt_specs.nu)
    nu = jax.tree_util.tree_unflatten(
        treedef, [nu_axes(a, n) for a, n in zip(flat_ax, flat_nu)])
    return OptState(step=(), mu=mu_axes, nu=nu)


def train_shardings(model, shape_cfg, mesh: Mesh, rules: dict,
                    param_axes: PyTree, param_specs: PyTree,
                    opt_specs: OptState) -> tuple[dict, PyTree]:
    """NamedShardings of the train state ``{"params", "opt"}`` and of a
    batch, by the logical rules (``specs`` may be arrays or SDS)."""
    opt_ax = opt_state_axes(opt_specs, param_axes)
    opt_sh = OptState(
        step=NamedSharding(mesh, P()),
        mu=(None if opt_specs.mu is None
            else axes_to_shardings(opt_ax.mu, opt_specs.mu, mesh, rules)),
        nu=axes_to_shardings(opt_ax.nu, opt_specs.nu, mesh, rules))
    state_sh = {"params": axes_to_shardings(param_axes, param_specs, mesh,
                                            rules),
                "opt": opt_sh}
    batch_sh = axes_to_shardings(model.input_axes(shape_cfg),
                                 model.input_specs(shape_cfg), mesh, rules)
    return state_sh, batch_sh


def defer_shardings(defer_specs: dict, mesh: Mesh, axis) -> dict:
    """NamedShardings of ``state["defer"]``: the step counter replicated,
    the pendings (and in-flight buffer) leading-dim sharded over ``axis``."""
    return {k: (NamedSharding(mesh, P()) if k == "t" else jax.tree.map(
                lambda _: NamedSharding(mesh, P(axis)), v))
            for k, v in defer_specs.items()}


# ---------------------------------------------------------------------------
# Train step
# ---------------------------------------------------------------------------


def merge_axes_for(mesh: Mesh, topology: Optional[Topology]):
    """The mesh axes a gradient-merge topology reduces over.

    A topology pinned to an axis (string or tuple of mesh axes) wins;
    otherwise the data-parallel axes of the mesh — ``("pod", "data")`` on
    the multi-pod production mesh, treated by the engine as one flattened
    merge axis, plain ``"data"`` elsewhere.
    """
    axis = getattr(topology, "axis_name", None)
    if axis is None:
        dp = tuple(a for a in ("pod", "data") if a in mesh.shape)
        axis = dp[0] if len(dp) == 1 else (dp or "data")
    return axis


def make_train_step(model, cfg, optimizer, num_microbatches: int = 1,
                    mesh: Optional[Mesh] = None,
                    merge_topology: Optional[Topology] = None,
                    merge_compress: bool = False,
                    defer_schedule: Optional[DeferSchedule] = None):
    """Build the train step.

    Default: implicit gradient reduction — XLA inserts the collectives the
    output shardings demand. With ``merge_topology`` (a two-level
    ``MergeTopology`` or an N-level ``MergePlan``) and a ``mesh``, the
    gradient merge is *explicit*: per-shard grads are computed under
    ``shard_map`` manual over the merge axes and reconciled by the CCache
    hierarchical engine (fused innermost collective, representative-only or
    lane-parallel upper-level exchange, optionally compressed).

    Plans with ``defer`` levels additionally need a ``defer_schedule``
    (``repro.core.defer_schedule``): the step then runs the merge-on-evict
    cascade — each step's gradient settles through the eager levels into a
    per-deferred-level ``PendingUpdate``, each deferred level's exchange is
    paid once per its commit interval, and the optimizer steps once per
    full-commit cycle on the cycle's mean gradient (``defer_cascade``; K
    deferred commits are numerically K-step gradient accumulation over the
    eagerly-merged gradients — property-tested in
    ``tests/test_defer_schedule.py``). The return value is then a
    :class:`DeferredTrainStep` (one variant per due-count) rather than a
    plain function. Without a schedule, ``defer`` plans are rejected: the
    optimizer would silently train on partially merged gradients. An
    *overlapped* schedule (``DeferSchedule(overlap=True)``) double-buffers
    the full commit: the launch step moves the cycle aggregate into
    ``state["defer"]["inflight"]`` and the next step's program runs the
    top-level exchange alongside its own compute, stepping the optimizer
    one step stale (K-step accumulation with a one-step delay).

    All remaining mesh axes stay on the compiler (shard_map's
    ``axis_names`` names only the merge axes), which is what lets the same
    step serve the implicit ``plan_train`` path; they must have size 1 (see
    the refusal below), and params are replicated over the merge axes (the
    data-parallel path, not the FSDP path).
    """

    def loss_fn(params, batch):
        return model.loss(params, batch)[0]

    def grads_of(params, batch):
        if num_microbatches > 1:
            return microbatched_value_and_grad(
                loss_fn, num_microbatches)(params, batch)
        return jax.value_and_grad(loss_fn)(params, batch)

    if merge_topology is None and defer_schedule is not None:
        raise ValueError("defer_schedule needs a merge_topology with :defer "
                         "levels")
    if merge_topology is not None:
        assert mesh is not None, "explicit merge needs the mesh"
        has_deferred = getattr(merge_topology, "has_deferred", False)
        if has_deferred and defer_schedule is None:
            raise ValueError(
                "merge plan has :defer levels but no commit schedule: the "
                "optimizer consumes the merged gradient, so deferred levels "
                "need a DeferSchedule (train.py: --merge-defer auto|K; "
                "library: repro.core.defer_schedule.solve_defer_schedule or "
                "DeferSchedule.fixed). Deferred-K training accumulates K "
                "steps' gradients and steps the optimizer once per commit; "
                "alternatively drop the :defer flags.")
        if defer_schedule is not None and not has_deferred:
            raise ValueError("defer_schedule given but the merge plan has "
                             "no :defer levels")

        axis = merge_axes_for(mesh, merge_topology)
        axes_set = set(axis) if isinstance(axis, tuple) else {axis}
        nontrivial_auto = sorted(a for a in mesh.axis_names
                                 if a not in axes_set and mesh.shape[a] > 1)
        if nontrivial_auto:
            # A shard_map manual over the merge axes only (partial auto)
            # over this repo's models aborts the process inside XLA's SPMD
            # partitioner on jax/jaxlib 0.9.0 (fatal check "Invalid binary
            # instruction opcode copy", hlo_instruction.cc) — fail loudly
            # here instead of crashing.
            raise NotImplementedError(
                f"explicit hierarchical gradient merge needs the non-merge "
                f"mesh axes to be trivial, but {nontrivial_auto} have size "
                f"> 1; XLA's SPMD partitioner aborts on this model under a "
                f"partial-auto shard_map (fatal check: invalid binary "
                f"instruction opcode copy). Use a pure data-parallel mesh "
                f"for the merge plan, or the implicit XLA reduction for "
                f"tensor-parallel cells.")
        # every other axis has size 1: go manual over the whole mesh, so
        # the partitioner never sees a partial-auto region
        manual = frozenset(mesh.axis_names)
        grad_merge_fn = int8_compressed_add() if merge_compress else ADD

        if defer_schedule is not None:
            return _make_deferred_train_step(
                grads_of, optimizer, mesh, merge_topology, merge_compress,
                defer_schedule, axis, axes_set, manual, grad_merge_fn)

        def sharded_grads(params, batch):
            def shard_fn(params, batch):
                # Model-code sharding constraints must not name the manual
                # (merge) axes — values are per-shard local along them.
                with partition.manual_axes(axes_set):
                    loss, grads = grads_of(params, batch)
                grads = merge_gradients(grads, axis,
                                        merge_fn=grad_merge_fn,
                                        topology=merge_topology,
                                        compress=merge_compress)
                return lax.pmean(loss, axis), grads

            return jax.shard_map(shard_fn, mesh=mesh,
                                 in_specs=(P(), P(axis)),
                                 out_specs=(P(), P()), axis_names=manual,
                                 check_vma=False)(params, batch)

        grad_step = sharded_grads
    else:
        grad_step = grads_of

    def train_step(state, batch):
        params = state["params"]
        loss, grads = grad_step(params, batch)
        params, opt_state, stats = optimizer.step(params, grads, state["opt"])
        return ({"params": params, "opt": opt_state},
                {"loss": loss, **stats})

    return train_step


class DeferredTrainStep:
    """Scheduled deferred-commit train step: one step callable per due-count.

    ``variants[due]`` is a plain ``step(state, batch)`` for a step on which
    ``due`` leading deferred stages commit — index 0 only accumulates, the
    last settles every deferred level and steps the optimizer on the
    cycle's mean gradient. ``state`` carries ``{"params", "opt", "defer":
    {"t", "pending"}}``; seed the extra entry with ``init_defer_state``.

    The due-count is a *host-side* decision (it selects which compiled
    program runs, so the skipped commits' collectives never execute —
    that is the wire saving). Calling the object dispatches eagerly off the
    step counter; ``jit()`` returns a dispatcher over per-variant jitted
    functions for the train loop. With nested intervals there are at most
    ``num_deferred + 1`` variants, so the compile count is bounded.

    With an *overlapped* schedule (``schedule.overlap``), the full-commit
    step launches the top-level exchange instead of running it: the cycle
    aggregate moves into ``state["defer"]["inflight"]`` and the next step's
    program runs the exchange concurrently with its own compute
    (``land_variants[due]``), stepping the optimizer one step stale —
    K-step gradient accumulation applied with a one-step delay. ``flush``
    drains whatever is outstanding (an in-flight launch and/or a trailing
    partial cycle) at end of run so no gradient mass is lost.
    """

    def __init__(self, variants, schedule: DeferSchedule, init_fn, dp: int,
                 deferred_names: tuple, land_variants=None, flush_fn=None,
                 topology=None, merge_fn=None, merge_compress: bool = False,
                 optimizer=None, strides: Optional[tuple] = None,
                 settle_mode: Optional[str] = None):
        self.variants = variants
        self.land_variants = land_variants
        self.schedule = schedule
        self._init_fn = init_fn
        self._flush_fn = flush_fn
        self.dp = dp
        self.deferred_names = deferred_names
        self.topology = topology
        self.merge_fn = merge_fn
        self.merge_compress = merge_compress
        self.optimizer = optimizer
        self.strides = strides
        self._settle_mode = settle_mode

    @property
    def overlap(self) -> bool:
        return self.schedule.overlap

    def scheduled_manifest(self, due: Optional[int] = None) -> list:
        """The collective schedule ``variants[due]`` is licensed to emit
        (``ccache.program_manifest``: eager stages + the leading ``due``
        deferred stages); ``due=None`` = the full-commit variant. The
        static verifier walks each variant's HLO against this."""
        if self.topology is None:
            raise ValueError("step was built without its merge topology")
        if due is None:
            due = len(self.deferred_names)
        return ccache.program_manifest(self.topology, self.dp, due,
                                       merge_fn=self.merge_fn,
                                       compress=self.merge_compress)

    def init_defer_state(self, params) -> dict:
        """Zeroed pendings (merge identity) + step counter (+ in-flight
        buffer when overlapped), as a state entry:
        ``state["defer"] = step.init_defer_state(params)``."""
        return self._init_fn(params)

    def due(self, state) -> int:
        return self.schedule.due_count(int(state["defer"]["t"]) + 1)

    def land_due(self, state) -> bool:
        """Whether this step lands a previously launched commit: true iff
        the *previous* step was a full-commit (launch) step."""
        t = int(state["defer"]["t"])
        return (self.overlap and t >= 1
                and self.schedule.due_count(t) == self.schedule.num_levels)

    def __call__(self, state, batch):
        fns = (self.land_variants if self.land_due(state)
               else self.variants)
        return fns[self.due(state)](state, batch)

    def jit(self, **jit_kwargs):
        jitted = [jax.jit(v, **jit_kwargs) for v in self.variants]
        jitted_land = ([jax.jit(v, **jit_kwargs) for v in self.land_variants]
                       if self.land_variants is not None else None)

        def call(state, batch):
            fns = jitted_land if self.land_due(state) else jitted
            return fns[self.due(state)](state, batch)

        return call

    def durability_manifest(self) -> dict:
        """The checkpoint-recorded identity of this step's defer state
        (``repro.checkpoint.defer_state``): plan/schedule fingerprints plus
        the geometry (per-level strides, dp, period, settle mode) the
        elastic restore path needs to settle restored pendings host-side."""
        if self.topology is None or self.strides is None:
            raise ValueError("step was built without its merge topology")
        from repro.checkpoint.defer_state import defer_manifest
        return defer_manifest(self.topology, self.schedule, self.dp,
                              self.merge_fn, self.strides, self._settle_mode)

    def defer_save_extras(self, state) -> dict:
        """Extras a checkpoint of ``state`` must record so restore can
        validate (and, on mismatch, settle) the defer state."""
        return {"defer": self.durability_manifest(),
                "defer_land_pending": bool(self.land_due(state)),
                "defer_t": int(state["defer"]["t"])}

    def volatile_spec(self, params_like) -> dict:
        """The ShapeDtypeStruct tree of ``state["defer"]`` — what a durable
        checkpoint of this step must cover (analysis CC040)."""
        from repro.checkpoint.defer_state import defer_state_spec
        return defer_state_spec(
            jax.tree.map(lambda p: jax.ShapeDtypeStruct(p.shape, p.dtype),
                         params_like),
            len(self.deferred_names), self.dp, self.overlap)

    def flush(self, state) -> tuple[dict, Optional[dict]]:
        """Final flush: drain everything outstanding at end of run.

        Lands an in-flight launched cycle (overlap mode), then settles any
        trailing partial cycle — the steps accumulated since the last full
        commit — through every deferred level and steps the optimizer on
        their mean. An N-step run with ``N % period != 0`` therefore loses
        zero gradient mass versus the eager twin. Returns
        ``(new_state, metrics)``; metrics is ``None`` when there was
        nothing to flush.
        """
        return self._flush_fn(state)


def _make_deferred_train_step(grads_of, optimizer, mesh: Mesh, plan,
                              merge_compress: bool,
                              schedule: DeferSchedule, axis, axes_set,
                              manual, grad_merge_fn) -> DeferredTrainStep:
    """The merge-on-evict train step family over ``defer_cascade``.

    Gradients are contributions to an ADD merge, so the pending cascade IS
    gradient accumulation: each rank's pending rides a ``(dp, ...)``-leading
    global array sharded over the merge axes, eager levels settle per step,
    and each deferred level's exchange runs only in the variants where it is
    due. The optimizer consumes ``settled / (dp * period)`` — the mean over
    ranks and over the cycle's steps — which makes K deferred commits
    numerically identical to accumulating K eagerly-merged mean gradients.

    An overlapped schedule routes through ``ccache.overlap_cascade``: the
    full-commit variant launches (cycle aggregate -> ``inflight``, no
    top-level traffic), and every variant gains a ``land`` twin whose
    program carries the top-level exchange on ``inflight`` next to the
    step's own compute — independent values, so the scheduler overlaps
    them — and steps the optimizer on the landed cycle one step stale.
    """

    dp = 1
    for a in (axis if isinstance(axis, tuple) else (axis,)):
        dp *= mesh.shape.get(a, 1)
    deferred = ccache.deferred_stages_of(plan, dp, merge_fn=grad_merge_fn)
    if not deferred:
        raise ValueError("the merge plan's :defer levels all compile away "
                         f"(size 1) on a {dp}-rank merge axis; drop the "
                         ":defer flags")
    names = tuple(s.name for s in deferred)
    if schedule.num_levels != len(deferred) or schedule.level_names != names:
        raise ValueError(
            f"DeferSchedule levels {schedule.level_names} with intervals "
            f"{schedule.intervals} do not match the plan's compiled "
            f"deferred stages {names}")
    n_def = len(deferred)
    period = schedule.period
    overlap = schedule.overlap
    # The merge's algebra decides how a settled cycle reaches the optimizer:
    # scalable merges take the delayed mean over ranks x steps (mirrors
    # merge_gradients), idempotent merges re-apply the settled join as-is,
    # anything else has no sound deferred train path.
    if overlap:
        grad_merge_fn.check_overlap("make_train_step(overlapped schedule)")
    settle_mode = grad_merge_fn.settle_mode()
    if settle_mode is None:
        raise ValueError(
            f"make_train_step: merge '{grad_merge_fn.name}' has no deferred "
            "settle mode — it is neither scalable (delayed mean) nor "
            "idempotent (re-apply); a K-step deferred commit cannot be "
            "reconciled with per-step optimizer semantics. Use an eager "
            "plan (no :defer) for this merge.")
    mean = settle_mode == "mean"
    scale = 1.0 / (dp * period) if mean else 1.0

    def _opt_step(params, opt_state, settled, s):
        grads = jax.tree.map(lambda g: g * jnp.asarray(s, g.dtype), settled)
        return optimizer.step(params, grads, opt_state)

    def _zero_metrics(loss):
        return {"loss": loss, "grad_norm": jnp.zeros((), jnp.float32),
                "lr": jnp.zeros((), jnp.float32)}

    def make_variant(due: int, land: bool = False):
        # One builder for both pipelines. The step's carried buffers are
        # (inflight?, *pendings); the optimizer consumes a settled cycle on
        # a serialized full-commit step or an overlapped land step.
        commits = land if overlap else due == n_def

        def region(params, batch, *bufs):
            with partition.manual_axes(axes_set):
                loss, grads = grads_of(params, batch)
            local = [jax.tree.map(lambda x: x[0], b) for b in bufs]
            if overlap:
                local_if, *local_p = local
                new_p, new_if, settled = ccache.overlap_cascade(
                    grads, local_p, local_if, due, land, axis,
                    grad_merge_fn, plan, compress=merge_compress)
                new_bufs = (new_if,) + tuple(new_p)
            else:
                new_p, settled = ccache.defer_cascade(
                    grads, local, due, axis, grad_merge_fn, plan,
                    compress=merge_compress)
                new_bufs = tuple(new_p)
            out = tuple(jax.tree.map(lambda x: x[None], b)
                        for b in new_bufs)
            loss = lax.pmean(loss, axis)
            if commits:
                return loss, out, settled
            return loss, out

        n_buf = n_def + (1 if overlap else 0)
        in_specs = (P(), P(axis)) + (P(axis),) * n_buf
        out_specs = (P(), P(axis), P()) if commits else (P(), P(axis))
        sharded = jax.shard_map(region, mesh=mesh, in_specs=in_specs,
                                out_specs=out_specs, axis_names=manual,
                                check_vma=False)

        def step(state, batch):
            params = state["params"]
            d = state["defer"]
            bufs_in = (((d["inflight"],) if overlap else ())
                       + tuple(d["pending"]))
            if commits:
                loss, bufs, settled = sharded(params, batch, *bufs_in)
                params, opt_state, stats = _opt_step(
                    params, state["opt"], settled, scale)
                metrics = {"loss": loss, **stats}
            else:
                loss, bufs = sharded(params, batch, *bufs_in)
                opt_state = state["opt"]
                metrics = _zero_metrics(loss)
            new_defer = {"t": d["t"] + 1}
            if overlap:
                new_defer["inflight"], bufs = bufs[0], bufs[1:]
            new_defer["pending"] = tuple(bufs)
            new_state = {"params": params, "opt": opt_state,
                         "defer": new_defer}
            return new_state, metrics

        return step

    def init_defer_state(params):
        def zeros_like_pending(_=None):
            return jax.tree.map(
                lambda p: grad_merge_fn.identity((dp,) + p.shape, p.dtype),
                params)
        pending = tuple(zeros_like_pending() for _ in range(n_def))
        state = {"t": jnp.zeros((), jnp.int32), "pending": pending}
        if overlap:
            state["inflight"] = zeros_like_pending()
        return state

    # -- final flush: land any in-flight launch, settle the partial cycle --

    def _land_flush_program():
        def region(inflight):
            local = jax.tree.map(lambda x: x[0], inflight)
            return ccache.settle_inflight(local, axis, grad_merge_fn, plan,
                                          compress=merge_compress)
        return jax.shard_map(region, mesh=mesh, in_specs=(P(axis),),
                             out_specs=P(), axis_names=manual,
                             check_vma=False)

    def _partial_flush_program():
        def region(*pendings):
            local = [jax.tree.map(lambda x: x[0], p) for p in pendings]
            zero = grad_merge_fn.tree_identity(local[0])
            _, settled = ccache.defer_cascade(
                zero, local, n_def, axis, grad_merge_fn, plan,
                compress=merge_compress)
            return settled
        return jax.shard_map(region, mesh=mesh,
                             in_specs=(P(axis),) * n_def, out_specs=P(),
                             axis_names=manual, check_vma=False)

    def flush(state):
        d = state["defer"]
        t = int(d["t"])
        params, opt_state = state["params"], state["opt"]
        metrics = None
        new_defer = dict(d)
        reset = functools.partial(
            jax.tree.map, lambda x: grad_merge_fn.identity(x.shape, x.dtype))
        if (overlap and t >= 1
                and schedule.due_count(t) == n_def):
            # The last step launched a cycle that never landed.
            landed = jax.jit(_land_flush_program())(d["inflight"])
            params, opt_state, stats = _opt_step(params, opt_state, landed,
                                                 scale)
            new_defer["inflight"] = reset(d["inflight"])
            metrics = {"flushed_inflight": True, **stats}
        m = t % period
        if m > 0:
            # Trailing partial cycle: settle every deferred level on the
            # outstanding pendings (zero delta — no new gradient) and step
            # the optimizer on the mean over the m accumulated steps.
            settled = jax.jit(_partial_flush_program())(*d["pending"])
            pscale = 1.0 / (dp * m) if mean else 1.0
            params, opt_state, stats = _opt_step(params, opt_state, settled,
                                                 pscale)
            new_defer["pending"] = tuple(reset(p) for p in d["pending"])
            metrics = {**(metrics or {}), "flushed_steps": m, **stats}
        if metrics is None:
            return state, None
        new_state = {"params": params, "opt": opt_state,
                     "defer": new_defer}
        return new_state, metrics

    variants = [make_variant(due) for due in range(n_def + 1)]
    land_variants = ([make_variant(due, land=True)
                      for due in range(n_def + 1)] if overlap else None)
    return DeferredTrainStep(variants, schedule, init_defer_state, dp, names,
                             land_variants=land_variants, flush_fn=flush,
                             topology=plan, merge_fn=grad_merge_fn,
                             merge_compress=merge_compress,
                             optimizer=optimizer,
                             strides=tuple(s.stride for s in deferred),
                             settle_mode=settle_mode)


class LoweredPlan:
    """Everything needed to lower one (arch x shape x mesh) cell.

    For deferred-commit train plans, ``fn`` is the full-commit variant (the
    superset program: every level's exchange — what a per-step cost walk
    should see at worst); ``defer_step`` carries the whole
    :class:`DeferredTrainStep` (all variants + schedule) for executing
    callers.
    """

    def __init__(self, fn, in_specs, in_shardings, out_shardings, rules,
                 defer_step: Optional[DeferredTrainStep] = None):
        self.fn = fn
        self.in_specs = in_specs
        self.in_shardings = in_shardings
        self.out_shardings = out_shardings
        self.rules = rules
        self.defer_step = defer_step

    def lower(self, mesh: Mesh):
        return self.lower_variant(mesh, self.fn)

    def lower_variant(self, mesh: Mesh, fn):
        """Lower a specific step variant (e.g. ``defer_step.variants[due]``)
        against this plan's specs/shardings — all variants share the state
        and metrics structure, only the commit depth differs."""
        jitted = jax.jit(fn, in_shardings=self.in_shardings,
                         out_shardings=self.out_shardings)
        with mesh, sharding_rules(mesh, self.rules):
            return jitted.lower(*self.in_specs)

    @property
    def noncommit_fn(self):
        """The zero-commit (due=0) step — what a deferred plan runs between
        commits; ``None`` for plans without deferred levels. The static
        verifier lowers this and asserts zero cross-device collectives on
        the deferred levels (CC020)."""
        if self.defer_step is None:
            return None
        return self.defer_step.variants[0]


def plan_train(cfg, shape_cfg, mesh: Mesh,
               num_microbatches: Optional[int] = None,
               extra_rules: Optional[dict] = None,
               merge_plan: Optional[Topology] = None,
               merge_compress: bool = False,
               defer_schedule: Optional[DeferSchedule] = None) -> LoweredPlan:
    """Build the implicit production train plan.

    With ``merge_plan`` the data-parallel gradient reduction inside the
    otherwise-implicit step is routed through the CCache hierarchical
    engine (shard_map manual over the dp axes) instead of the XLA-inserted
    all-reduce — the N-level MergePlan threaded into the production path,
    not just the explicit shard_map step. A plan with ``:defer`` levels
    additionally takes a ``defer_schedule``; the state then carries the
    pending cascade (``state["defer"]``, leading-dim sharded over the merge
    axes) and the returned plan's ``defer_step`` holds every commit
    variant. Every non-merge mesh axis must have size 1 (pure data-parallel
    meshes): ``make_train_step`` raises on tensor-parallel cells, which
    keep the implicit XLA reduction.
    """
    model = build_model(cfg)
    rules = lowering_rules(cfg, shape_cfg, mesh)
    rules.update(extra_rules or {})
    nmb = (num_microbatches if num_microbatches is not None
           else cfg.microbatches.get(shape_cfg.name, 1))

    tagged = jax.eval_shape(model.init, jax.random.key(0))
    param_specs, param_axes = split_params(tagged)
    optimizer = make_optimizer(cfg, warmup_cosine(3e-4, 100, 10_000))
    opt_specs = jax.eval_shape(optimizer.init, param_specs)

    state_specs = {"params": param_specs, "opt": opt_specs}
    state_sh, batch_sh = train_shardings(model, shape_cfg, mesh, rules,
                                         param_axes, param_specs, opt_specs)
    batch_specs = model.input_specs(shape_cfg)

    step = make_train_step(model, cfg, optimizer, nmb, mesh=mesh,
                           merge_topology=merge_plan,
                           merge_compress=merge_compress,
                           defer_schedule=defer_schedule)
    defer_step = None
    fn = step
    if isinstance(step, DeferredTrainStep):
        defer_step = step
        # The cost-walk superset program: for overlapped schedules that is
        # the land twin of the full-commit variant (every level's exchange
        # including the top-level land appears in one program).
        fn = (step.land_variants[-1] if step.land_variants is not None
              else step.variants[-1])
        defer_specs = jax.eval_shape(step.init_defer_state, param_specs)
        state_specs["defer"] = defer_specs
        state_sh["defer"] = defer_shardings(
            defer_specs, mesh, merge_axes_for(mesh, merge_plan))
    metrics_sh = NamedSharding(mesh, P())
    out_sh = (state_sh, {"loss": metrics_sh, "grad_norm": metrics_sh,
                         "lr": metrics_sh})
    return LoweredPlan(fn, (state_specs, batch_specs),
                       (state_sh, batch_sh), out_sh, rules,
                       defer_step=defer_step)


# ---------------------------------------------------------------------------
# Serve steps (prefill / decode)
# ---------------------------------------------------------------------------


def plan_prefill(cfg, shape_cfg, mesh: Mesh,
                 extra_rules: Optional[dict] = None) -> LoweredPlan:
    model = build_model(cfg)
    rules = lowering_rules(cfg, shape_cfg, mesh)
    rules.update(extra_rules or {})

    tagged = jax.eval_shape(model.init, jax.random.key(0))
    param_specs, param_axes = split_params(tagged)
    params_sh = axes_to_shardings(param_axes, param_specs, mesh, rules)
    batch_specs = model.input_specs(shape_cfg)
    batch_sh = axes_to_shardings(model.input_axes(shape_cfg), batch_specs,
                                 mesh, rules)

    def prefill_step(params, batch):
        logits, caches = model.prefill(params, batch, shape_cfg.seq_len)
        return jnp.argmax(logits, axis=-1).astype(jnp.int32), caches

    return LoweredPlan(prefill_step, (param_specs, batch_specs),
                       (params_sh, batch_sh), None, rules)


def plan_decode(cfg, shape_cfg, mesh: Mesh,
                extra_rules: Optional[dict] = None) -> LoweredPlan:
    model = build_model(cfg)
    rules = lowering_rules(cfg, shape_cfg, mesh)
    rules.update(extra_rules or {})

    tagged = jax.eval_shape(model.init, jax.random.key(0))
    param_specs, param_axes = split_params(tagged)
    params_sh = axes_to_shardings(param_axes, param_specs, mesh, rules)

    in_specs = model.input_specs(shape_cfg)   # tokens, caches, position
    in_axes = model.input_axes(shape_cfg)
    in_sh = axes_to_shardings(in_axes, in_specs, mesh, rules)

    def serve_step(params, tokens, caches, position):
        logits, new_caches = model.decode_step(params, tokens, caches,
                                               position)
        return jnp.argmax(logits, axis=-1).astype(jnp.int32), new_caches

    out_sh = (axes_to_shardings(("batch",),
                                jax.ShapeDtypeStruct(
                                    (shape_cfg.global_batch,), jnp.int32),
                                mesh, rules),
              in_sh["caches"])
    return LoweredPlan(
        serve_step,
        (param_specs, in_specs["tokens"], in_specs["caches"],
         in_specs["position"]),
        (params_sh, in_sh["tokens"], in_sh["caches"], in_sh["position"]),
        out_sh, rules)


def plan_for(cfg, shape_cfg, mesh: Mesh, **kw) -> LoweredPlan:
    if shape_cfg.kind == "train":
        return plan_train(cfg, shape_cfg, mesh, **kw)
    if shape_cfg.kind == "prefill":
        return plan_prefill(cfg, shape_cfg, mesh, **kw)
    return plan_decode(cfg, shape_cfg, mesh, **kw)
