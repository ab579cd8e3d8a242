"""Batched multi-field NeurLZ compression engine.

The serial engine trains one field's enhancer at a time, synchronously: one
jitted dispatch per epoch *per field* with a host sync after every epoch to
collect the loss, and the CPU-side conventional compressor runs strictly
before any training starts.  Real deployments compress many fields of the
same snapshot at once (the paper's cross-field design assumes they are
resident together), so this engine restructures the hot path around the
*snapshot*:

  * **Field groups** — fields whose slice geometry and channel count match
    are planned into groups (``NeurLZConfig.group_size`` caps fields per
    group to tune the pipeline depth).  Slice-count-ragged groups are
    handled natively: each field scans its own step count inside the shared
    dispatch.
  * **Fused training dispatch** (``field_batching="unroll"``) — *every
    epoch of every field of a group* runs in a single jitted ``lax.scan``
    dispatch.  Each field's scan body is exactly
    :func:`repro.core.online_trainer.scan_train` — the serial trace — so
    trained weights, archives and reconstructions are **bit-identical** to
    the serial engine.
  * **``field_batching="vmap"``** — per-field params are stacked on a
    leading ``F`` axis (:func:`repro.core.skipping_dnn.stack_params`) and
    each epoch runs as one ``jax.vmap``-over-fields ``lax.scan``.  The
    skipping-DNN forward is built from
    shift-and-accumulate ``lax.dot_general`` contractions that lower
    identically under ``vmap`` (see :mod:`repro.core.skipping_dnn`), so
    equal-slice-count groups are bit-identical to serial at most training
    signatures (XLA:CPU can still partition a gradient GEMM differently
    at some sizes); ragged fields train the padded step count per epoch
    with modulo-resampled slices and diverge from the serial trajectory
    (error-bound guarantees are unaffected either way).
  * **``field_batching="auto"`` (default)** — per group: the stacked
    ``vmap`` path for multi-field groups with matching slice counts,
    *verified* by a cached per-signature byte-parity probe
    (:func:`vmap_bit_parity`) before use; ``unroll`` for ragged or
    single-field groups, or when the probe finds the stacked gradient is
    not bit-identical (:func:`resolve_batching`).  The default therefore
    always round-trips byte-identical to serial.
  * **Async pipeline** — training *and* inference for every group are
    dispatched before any result is awaited, so the device queue never
    drains; the host meanwhile runs the *next* groups' conventional
    compression and dataset construction, with ``jax.device_put`` moving
    tensors early so upload overlaps compute.  With more than one device,
    the conventional compressor's jitted stages run on the last device so
    they never queue behind training (``prefetch=True``).
  * **Batched inference** — encode- and decode-side ``predict_residual``
    for a whole group run in one dispatch.  Inference always uses the exact
    per-field graph regardless of the training strategy, so the
    encoder-side reconstruction used for strict-mode outlier capture is
    always reproducible by any decoder: archives stay bit-compatible.
"""
from __future__ import annotations

import contextlib
import dataclasses
import time
from functools import partial
from typing import Mapping

import jax
import jax.numpy as jnp
import numpy as np

from .. import faults as faults_lib
from ..compressors import registry
from ..obs import telemetry as obs_lib
from ..optim import adamw_init, adamw_update, cosine_schedule
from . import bounds as bounds_lib
from . import conv_stage as conv_stage_lib
from . import neurlz, online_trainer, skipping_dnn


# ---------------------------------------------------------------------------
# Group planning
# ---------------------------------------------------------------------------

@dataclasses.dataclass
class FieldGroup:
    names: list[str]                 # fields, input order
    slice_hw: tuple[int, int]        # per-slice spatial shape
    c_in: int                        # input channels (1 + aux fields)
    mode: str | None = None          # per-field regulation mode override
    #   (None -> the session config's mode; groups are mode-homogeneous so
    #   one group shares one network signature / outlier-capture rule)


def group_config(config, group: FieldGroup):
    """Effective :class:`NeurLZConfig` for one group under its per-field
    regulation-mode override (identity for legacy single-mode runs)."""
    return neurlz.field_config(config, group.mode)


def sliced_shape(shape: tuple, slice_axis: int) -> tuple:
    """``np.moveaxis(x, slice_axis, 0).shape`` from the shape alone (no
    array needed — the streaming planner works off source metadata)."""
    axis = slice_axis % len(shape)
    return (shape[axis],) + tuple(s for i, s in enumerate(shape) if i != axis)


def plan_groups_from_meta(shapes: Mapping[str, tuple],
                          c_ins: Mapping[str, int],
                          config,
                          modes: Mapping[str, str] | None = None
                          ) -> list[FieldGroup]:
    """Group-plan from field *metadata* only (shapes + channel counts).

    This is the plan export used by the streaming scheduler, which must
    plan a snapshot bigger than memory before loading any field data.
    ``modes`` optionally carries per-field regulation modes (the
    :class:`repro.core.bounds.ErrorBound` overrides): fields only share a
    group when their modes agree, since a group shares one network
    signature (regulated flag) and one outlier-capture rule.
    """
    groups: dict[tuple, FieldGroup] = {}
    for name, shape in shapes.items():
        sshape = sliced_shape(tuple(shape), config.slice_axis)
        mode = modes.get(name) if modes is not None else None
        key = (sshape[1:], c_ins[name], mode)
        if key not in groups:
            groups[key] = FieldGroup(names=[], slice_hw=tuple(sshape[1:]),
                                     c_in=c_ins[name], mode=mode)
        groups[key].names.append(name)
    out = []
    for g in groups.values():
        size = config.group_size if config.group_size > 0 else len(g.names)
        for i in range(0, len(g.names), size):
            out.append(FieldGroup(names=g.names[i:i + size],
                                  slice_hw=g.slice_hw, c_in=g.c_in,
                                  mode=g.mode))
    return out


def plan_groups(fields: Mapping[str, np.ndarray], config,
                modes: Mapping[str, str] | None = None) -> list[FieldGroup]:
    """Group fields by slice geometry, channel count and regulation mode.

    A group is the unit of batched dispatch: every field in it shares the
    jitted graph's spatial/channel signature.  Slice *counts* may differ
    within a group (ragged path).  ``config.group_size > 0`` chunks groups
    to that many fields, trading per-dispatch batching for pipeline overlap
    of conventional compression with training.
    """
    shapes = {name: np.asarray(x).shape for name, x in fields.items()}
    c_ins = {name: 1 + len(neurlz._aux_names(config, name, fields))
             for name in fields}
    return plan_groups_from_meta(shapes, c_ins, config, modes=modes)


# ---------------------------------------------------------------------------
# Batched dispatches
# ---------------------------------------------------------------------------

@partial(jax.jit, static_argnames=("spec", "epochs", "base_lr", "min_lr_frac",
                                   "loss", "lowering"))
def _train_group_fused(params_t, opt_t, xs_t, ys_t, base_key, *, spec, epochs,
                       base_lr, min_lr_frac, loss, lowering="auto"):
    """All epochs of every field of a group in ONE dispatch.

    ``spec`` is a static tuple of per-field
    ``(steps, batch, total_steps, regulated, skip)``; per-field tensors ride
    in tuples (slice counts may differ).  Per-epoch batch matrices come from
    :func:`online_trainer.epoch_batches` with the same folded keys as the
    serial trainer, and each field scans
    :func:`online_trainer.scan_train` — the serial trace — which makes this
    engine bit-identical to the serial one.  Returns per-epoch mean losses
    ``[epochs, F]``.
    """
    new_p, new_o, losses = [], [], []
    for f, (steps, batch, total_steps, reg, skip) in enumerate(spec):
        n = xs_t[f].shape[0]
        batches = jnp.concatenate([
            online_trainer.epoch_batches(jax.random.fold_in(base_key, e),
                                         n, steps, batch)
            for e in range(epochs)], axis=0)        # [epochs*steps, batch]
        p, o, lvals = online_trainer.scan_train(
            params_t[f], opt_t[f], xs_t[f], ys_t[f], batches,
            jnp.asarray(0, jnp.int32), cfg_reg=reg, cfg_skip=skip,
            total_steps=total_steps, base_lr=base_lr,
            min_lr_frac=min_lr_frac, loss=loss, lowering=lowering)
        new_p.append(p)
        new_o.append(o)
        losses.append(jnp.mean(lvals.reshape(epochs, steps), axis=1))
    return tuple(new_p), tuple(new_o), jnp.stack(losses, axis=1)


@partial(jax.jit, static_argnames=("steps", "batch", "total_steps", "reg",
                                   "skip", "base_lr", "min_lr_frac", "loss",
                                   "lowering"))
def _epoch_vmapped(params_st, opt_st, xs, ys, epoch_key, start_step,
                   n_valid, *, steps, batch, total_steps, reg, skip,
                   base_lr, min_lr_frac, loss, lowering="auto"):
    """One epoch as a single ``jax.vmap``-over-fields ``lax.scan``.

    ``xs``/``ys`` are padded to the group's max slice count ``[F,N,H,W,C]``
    and every field runs ``steps`` (the padded count's) steps per epoch;
    ``n_valid`` maps the shared per-epoch permutation into each ragged
    field's own valid range (short fields resample slices modulo their
    count), so the cosine horizon ``total_steps`` is shared and static.
    """
    n_pad = xs.shape[1]
    batches = online_trainer.epoch_batches(epoch_key, n_pad, steps, batch)
    lr_fn = cosine_schedule(base_lr, total_steps, min_lr_frac)

    def loss_fn(p, xb, yb):
        return online_trainer.batch_loss(p, xb, yb, regulated=reg, skip=skip,
                                         loss=loss, lowering=lowering)

    def body(carry, idx):
        p, o, step = carry

        def field_step(p_f, o_f, x_f, y_f, nv):
            idx_f = idx % nv
            xb = jnp.take(x_f, idx_f, axis=0)
            yb = jnp.take(y_f, idx_f, axis=0)
            lval, grads = jax.value_and_grad(loss_fn)(p_f, xb, yb)
            p_f, o_f = adamw_update(grads, o_f, p_f, lr=lr_fn(step))
            return p_f, o_f, lval

        p, o, lvals = jax.vmap(field_step)(p, o, xs, ys, n_valid)
        return (p, o, step + 1), lvals

    (params_st, opt_st, _), losses = jax.lax.scan(
        body, (params_st, opt_st, start_step), batches)
    return params_st, opt_st, jnp.mean(losses, axis=0)


@partial(jax.jit, static_argnames=("spec", "lowering"))
def _predict_group(params_t, xs_t, *, spec, lowering="auto"):
    """Batched ``predict_residual``: every field of a group, one dispatch.

    Always the exact per-field inference graph
    (:func:`online_trainer.predict_graph`), so encode- and decode-side
    reconstructions match the serial engine bit-for-bit regardless of the
    training strategy.
    """
    return tuple(
        online_trainer.predict_graph(params_t[f], xs_t[f], regulated=reg,
                                     skip=skip, lowering=lowering)
        for f, (reg, skip) in enumerate(spec))


# ---------------------------------------------------------------------------
# Group state through the pipeline
# ---------------------------------------------------------------------------

@dataclasses.dataclass
class _GroupState:
    group: FieldGroup
    net_cfg: skipping_dnn.SkippingDNNConfig
    inputs: list       # per-field device arrays [N_f, H, W, C]
    targets: list
    stats: list        # per-field normalization stats
    params: tuple      # per-field trees (device; lazy while training runs)
    opt: tuple
    steps: list        # per-field steps/epoch
    batch: list        # per-field batch size
    total_steps: list  # per-field cosine horizon
    losses: object = None   # device [epochs, F] once training is dispatched
    resids: tuple = ()      # per-field lazy [N, H, W] residual predictions
    devices: tuple = ()     # ids of the devices holding the training arrays


def _prepare_group(group: FieldGroup, fields, recs, ebs, config, tcfg,
                   device=None) -> _GroupState:
    """Host-side stage: datasets + async device upload + param init.

    ``device`` pins the whole group (field sharding: groups are
    round-robined over devices, and jit runs each group's program where its
    operands live — identical programs, so results stay bit-identical)."""
    config = group_config(config, group)
    net_cfg = config.net_config(group.c_in)
    inputs, targets, stats = [], [], []
    steps, batches, totals = [], [], []
    for name in group.names:
        x = np.asarray(fields[name])
        aux = [recs[a] for a in neurlz._aux_names(config, name, fields)]
        inp, tgt, st = neurlz.build_dataset(x, recs[name], ebs[name], aux,
                                            config)
        n = inp.shape[0]
        b = min(tcfg.batch, n)
        s = max(1, n // b)
        steps.append(s)
        batches.append(b)
        totals.append(s * tcfg.epochs)
        # device_put is async: upload overlaps earlier groups' training.
        inputs.append(jax.device_put(inp, device))
        targets.append(jax.device_put(tgt, device))
        stats.append(st)
    key = jax.random.PRNGKey(tcfg.seed)
    params = tuple(jax.device_put(skipping_dnn.init_params(key, net_cfg),
                                  device)
                   for _ in group.names)
    opt = tuple(adamw_init(p) for p in params)
    return _GroupState(group=group, net_cfg=net_cfg, inputs=inputs,
                       targets=targets, stats=stats, params=params, opt=opt,
                       steps=steps, batch=batches, total_steps=totals)


def resolve_batching(strategy: str, slice_counts: list[int]) -> str:
    """Structural strategy choice for one group.

    ``auto`` proposes the stacked ``vmap`` path for multi-field groups
    whose slice counts match; ragged groups (and single-field ones, where
    stacking buys nothing) unroll — the vmap path would train them on the
    padded step count with modulo-resampled slices, which diverges from
    the serial trajectory.  An ``auto``-proposed vmap is additionally
    gated by :func:`vmap_bit_parity` in :func:`_dispatch_group` before it
    is used (verified, not assumed — same contract as the kernel-lowering
    dispatch).
    """
    if strategy != "auto":
        return strategy
    uniform = len(set(slice_counts)) == 1
    return "vmap" if uniform and len(slice_counts) > 1 else "unroll"


# (slice_hw, c_in, batch, regulated, skip, loss, lowering) -> bool
_vmap_parity: dict[tuple, bool] = {}


def vmap_bit_parity(net_cfg, slice_hw: tuple, batch: int, tcfg) -> bool:
    """Byte-parity probe for the stacked vmap strategy at one training
    signature.

    The fast shift-and-accumulate forward lowers identically under
    ``jax.vmap`` for most shapes, but XLA:CPU may partition a *gradient*
    contraction differently between the single and the batched GEMM at
    some sizes, reassociating the reduction.  Lowered code is
    shape-dependent, not value-dependent, so one byte-compare of
    ``value_and_grad`` on canary inputs — per (spatial, channels, batch,
    loss) signature, cached — decides whether the stacked path is
    bit-identical to the per-field trace here.
    """
    key = (tuple(slice_hw), net_cfg.c_in, batch, net_cfg.regulated,
           net_cfg.skip, tcfg.loss, tcfg.lowering)
    if key in _vmap_parity:
        return _vmap_parity[key]
    h, w = slice_hw
    kp = jax.random.PRNGKey(0)
    params = skipping_dnn.init_params(kp, net_cfg)
    k1, k2 = jax.random.split(jax.random.fold_in(kp, 1))
    xs = jax.random.normal(k1, (2, batch, h, w, net_cfg.c_in), jnp.float32)
    ys = jnp.clip(jax.random.normal(k2, (2, batch, h, w, 1), jnp.float32),
                  -1.0, 1.0)

    def loss_fn(p, xb, yb):
        return online_trainer.batch_loss(
            p, xb, yb, regulated=net_cfg.regulated, skip=net_cfg.skip,
            loss=tcfg.loss, lowering=tcfg.lowering)

    singles = [jax.jit(jax.value_and_grad(loss_fn))(params, xs[i], ys[i])
               for i in range(2)]
    pst = skipping_dnn.stack_params([params, params])
    lv, gv = jax.jit(jax.vmap(jax.value_and_grad(loss_fn)))(pst, xs, ys)
    ok = True
    for i, (l1, g1) in enumerate(singles):
        if np.asarray(l1).tobytes() != np.asarray(lv[i]).tobytes():
            ok = False
        for a, b in zip(jax.tree.leaves(g1), jax.tree.leaves(gv)):
            if np.asarray(a).tobytes() != np.asarray(b[i]).tobytes():
                ok = False
    _vmap_parity[key] = ok
    return ok


def _device_ids(x) -> tuple:
    return tuple(sorted(d.id for d in x.devices()))


def _dispatch_group(state: _GroupState, config, tcfg) -> None:
    """Enqueue the group's full training AND inference without blocking."""
    net_cfg = state.net_cfg
    key = jax.random.PRNGKey(tcfg.seed)
    strategy = resolve_batching(config.field_batching,
                                [int(x.shape[0]) for x in state.inputs])
    if strategy == "vmap" and config.field_batching == "auto":
        n_max = max(int(x.shape[0]) for x in state.inputs)
        if not vmap_bit_parity(net_cfg, state.group.slice_hw,
                               min(tcfg.batch, n_max), tcfg):
            strategy = "unroll"
    if strategy == "vmap":
        _dispatch_vmapped(state, tcfg, key)
    elif strategy == "unroll":
        state.devices = _device_ids(state.inputs[0])
        if tcfg.epochs <= 0:
            state.losses = jnp.zeros((0, len(state.group.names)), jnp.float32)
        else:
            spec = tuple((state.steps[f], state.batch[f],
                          state.total_steps[f], net_cfg.regulated,
                          net_cfg.skip)
                         for f in range(len(state.group.names)))
            state.params, state.opt, state.losses = _train_group_fused(
                state.params, state.opt, tuple(state.inputs),
                tuple(state.targets), key, spec=spec, epochs=tcfg.epochs,
                base_lr=tcfg.lr, min_lr_frac=tcfg.min_lr_frac,
                loss=tcfg.loss, lowering=tcfg.lowering)
    else:
        raise ValueError(f"unknown field_batching {config.field_batching!r} "
                         "(want 'auto', 'unroll' or 'vmap')")
    # Inference consumes the (still lazy) trained params — queues right
    # behind training on the device, before any host sync.
    pspec = tuple((net_cfg.regulated, net_cfg.skip)
                  for _ in state.group.names)
    state.resids = _predict_group(tuple(state.params), tuple(state.inputs),
                                  spec=pspec, lowering=tcfg.lowering)


def _dispatch_vmapped(state: _GroupState, tcfg, key) -> None:
    """vmap strategy: stack fields, pad ragged slice counts, train stacked."""
    net_cfg = state.net_cfg
    n_max = max(int(x.shape[0]) for x in state.inputs)
    b = min(tcfg.batch, n_max)
    steps = max(1, n_max // b)

    def pad(a):
        short = n_max - a.shape[0]
        return a if short == 0 else jnp.pad(
            a, ((0, short),) + ((0, 0),) * (a.ndim - 1))

    xs = jnp.stack([pad(x) for x in state.inputs])
    ys = jnp.stack([pad(y) for y in state.targets])
    params_st = skipping_dnn.stack_params(list(state.params))
    opt_st = jax.tree.map(lambda *a: jnp.stack(a), *state.opt)
    n_valid = jnp.asarray([x.shape[0] for x in state.inputs], jnp.int32)
    state.devices = _device_ids(xs)
    losses = []
    for e in range(tcfg.epochs):
        ekey = jax.random.fold_in(key, e)
        start = jnp.asarray(e * steps, jnp.int32)
        params_st, opt_st, mloss = _epoch_vmapped(
            params_st, opt_st, xs, ys, ekey, start, n_valid,
            steps=steps, batch=b, total_steps=steps * tcfg.epochs,
            reg=net_cfg.regulated, skip=net_cfg.skip,
            base_lr=tcfg.lr, min_lr_frac=tcfg.min_lr_frac, loss=tcfg.loss,
            lowering=tcfg.lowering)
        losses.append(mloss)
    state.losses = jnp.stack(losses) if losses else \
        jnp.zeros((0, len(state.group.names)), jnp.float32)
    state.params = tuple(
        skipping_dnn.unstack_params(params_st, len(state.group.names)))
    state.opt = tuple(jax.tree.map(lambda a, i=i: a[i], opt_st)
                      for i in range(len(state.group.names)))


def group_results(state: _GroupState):
    """Sync point: block on the group's training/inference and yield
    ``(f, name, history, resid)`` per field — shared by this engine's
    finalize and the streaming pipeline's (which defers packing to the
    writer thread)."""
    history = np.asarray(state.losses)          # blocks on training
    for f, name in enumerate(state.group.names):
        yield (f, name, [float(v) for v in history[:, f]],
               np.asarray(state.resids[f]))


def _finalize_group(state: _GroupState, fields, recs, ebs, conv_arcs, config,
                    collect_stats, out_fields, on_entry=None,
                    tel=obs_lib.NULL, fc=faults_lib.DEFAULT,
                    degraded=None) -> None:
    """Blocking stage: fetch residuals, enhancement, entry packing.

    A per-field enhancer failure (injected fault at ``train.<name>``,
    non-finite loss, OOM in enhancement) degrades that field to a conv-only
    entry — same normalized reason and entry bytes as the serial engine —
    instead of aborting the snapshot."""
    config = group_config(config, state.group)
    with tel.span("finalize", group=",".join(state.group.names)):
        for f, name, hist, resid in group_results(state):
            x = np.asarray(fields[name])
            aux_names = neurlz._aux_names(config, name, fields)
            entry, reason = None, None
            try:
                fc.check(f"train.{name}")
                if fc.degrade and not neurlz.history_is_finite(hist):
                    reason = faults_lib.degrade_reason()
                else:
                    entry = neurlz.pack_entry(
                        config, conv_arcs[name], state.params[f],
                        state.stats[f], aux_names, ebs[name], state.net_cfg,
                        hist, collect_stats)
                    neurlz.finalize_entry(entry, x, recs[name], resid,
                                          ebs[name], state.stats[f], config)
            except Exception as exc:
                if not (fc.degrade and faults_lib.is_degradable(exc)):
                    raise
                reason = faults_lib.degrade_reason(exc)
            if reason is not None:
                entry = neurlz.pack_degraded_entry(config, conv_arcs[name],
                                                   ebs[name], reason)
                if degraded is not None:
                    degraded.append(name)
                tel.counter("faults.degraded").add()
            elif tel.enabled and tel.config.learning_traces:
                obs_lib.learning_trace(
                    tel, name, hist, eb=ebs[name],
                    vrange=neurlz.field_vrange(x),
                    base_bytes=neurlz.entry_base_bytes(entry),
                    n_points=int(x.size), mode=config.mode)
            out_fields[name] = entry
            if on_entry is not None:
                on_entry(name, entry)


# ---------------------------------------------------------------------------
# Engine entry points
# ---------------------------------------------------------------------------

def _conv_device():
    """Device for the conventional compressor's jitted stages: the last one,
    so they never queue behind enhancer training on device 0."""
    devs = jax.devices()
    return devs[-1] if len(devs) > 1 else None


def compress(fields: Mapping[str, np.ndarray], rel_eb: float | None = None, *,
             abs_eb: float | None = None, config=None,
             collect_stats: bool = True, on_entry=None, bounds=None) -> dict:
    """Batched-engine compression; same archive contract as the serial path.

    ``on_entry(name, entry)`` fires as each field's archive entry completes
    (groups finalize as soon as the next group is dispatched, not at end of
    run), which lets callers archive incrementally and bounds how many
    groups' tensors stay resident at once.  ``bounds`` carries per-field
    :class:`repro.core.bounds.ErrorBound` specs; groups are planned
    mode-homogeneous so each fused dispatch keeps one network signature.
    """
    config = config or neurlz.NeurLZConfig(engine="batched")
    tel = obs_lib.of(config)
    fc = faults_lib.of(config)
    t0 = time.time()
    with tel.span("compress", root=True, engine="batched",
                  fields=len(fields)):
        tcfg = config.train_config()
        resolved = None
        if bounds is not None:
            resolved = bounds_lib.resolve_bounds(list(fields), bounds,
                                                 rel_eb, abs_eb,
                                                 default_mode=config.mode)
        modes = ({n: b.mode for n, b in resolved.items()}
                 if resolved is not None else None)
        groups = plan_groups(fields, config, modes=modes)

        conv_arcs, recs, ebs = {}, {}, {}
        conv_dev = _conv_device() if config.prefetch else None
        # Shared conventional stage: each call batches the handed fields by
        # (shape, dtype, bound spec) through the fused compressor entry.
        stage = conv_stage_lib.ConvStage(config.compressor, rel_eb, abs_eb,
                                         batch=config.conv_batch,
                                         bounds=resolved, telemetry=tel,
                                         lowering=config.lowering)

        def conv_compress(names):
            todo = {n: fields[n] for n in names if n not in conv_arcs}
            if not todo:
                return
            ctx = jax.default_device(conv_dev) if conv_dev is not None \
                else contextlib.nullcontext()
            with ctx:
                for name, (arc, rec) in stage.run(todo).items():
                    conv_arcs[name], recs[name], ebs[name] = \
                        arc, rec, arc["abs_eb"]

        # Cross-field aux may reference fields in later groups; resolve the
        # whole conventional stage upfront in that case.  Otherwise it runs
        # lazily per group, overlapping earlier groups' device-side training.
        if config.cross_field or not config.prefetch:
            conv_compress(list(fields))

        # Field sharding: spread whole groups across training devices — all
        # but the conventional-compressor device, so conv work never shares
        # a queue with enhancer training.  A group's program runs unsplit
        # on its device: splitting a stacked group's field axis over a mesh
        # changes each device's batched contractions, and with them the
        # archive bytes.
        train_devs = jax.devices()
        if conv_dev is not None and len(train_devs) > 1:
            train_devs = train_devs[:-1]
        t_train0 = time.time()
        conv_before = stage.stats.conv_s
        # Per-group completion: finalize a group as soon as enough later
        # groups are dispatched to keep every training device's queue
        # non-empty (depth >= devices + 1), instead of holding all groups'
        # tensors until an end-of-run finalize pass.
        depth = max(2, len(train_devs) + 1)
        out_fields: dict = {}
        degraded: list[str] = []
        states: list[_GroupState] = []
        for gi, group in enumerate(groups):
            conv_compress(group.names)
            dev = train_devs[gi % len(train_devs)] \
                if config.field_shard and len(train_devs) > 1 else None
            with tel.span("train", group=",".join(group.names)) as sp:
                state = _prepare_group(group, fields, recs, ebs, config,
                                       tcfg, device=dev)
                _dispatch_group(state, config, tcfg)   # async: no host sync
                sp.set(devices=list(state.devices))
            states.append(state)
            if len(states) >= depth:
                _finalize_group(states.pop(0), fields, recs, ebs, conv_arcs,
                                config, collect_stats, out_fields, on_entry,
                                tel=tel, fc=fc, degraded=degraded)
        for state in states:
            _finalize_group(state, fields, recs, ebs, conv_arcs, config,
                            collect_stats, out_fields, on_entry, tel=tel,
                            fc=fc, degraded=degraded)
        # Conventional compression that ran lazily inside the loop belongs
        # to conv_s, not train_s (keep the two disjoint, like the serial
        # engine).
        train_time = ((time.time() - t_train0)
                      - (stage.stats.conv_s - conv_before))

        timing = obs_lib.build_timing(
            tel, total_s=time.time() - t0, conv_s=stage.stats.conv_s,
            train_s=train_time, conv_stage=stage.stats.as_dict(),
            degraded_fields=degraded)
        with tel.span("assemble"):
            return neurlz.assemble_archive(fields, out_fields, config,
                                           timing)


def decompress(arc) -> dict[str, np.ndarray]:
    """Batched decode: all enhancer inference in one dispatch per signature,
    and the conventional stage amortized through the registry's symmetric
    ``decompress_batched`` capability (same-``decode_key`` archives decode
    as one stacked eager dispatch).

    Output is bit-identical to ``neurlz.decompress(arc, engine="serial")``
    because the per-field inference graph — and, contractually, the batched
    conventional decode — are the same.
    """
    slice_axis = arc["slice_axis"]
    recs = registry.decompress_many(
        {name: e["conv"] for name, e in arc["fields"].items()})

    # Group fields by inference signature so each dispatch is shape-static.
    # Degraded (conv-only) entries have no network: their conventional
    # reconstruction IS the decode, same as the serial path.
    sig_groups: dict[tuple, list[str]] = {}
    prepared: dict[str, tuple] = {}
    out = {}
    for name, e in arc["fields"].items():
        if e.get("degraded"):
            out[name] = np.asarray(recs[name])
            continue
        net_cfg, params = neurlz.decode_entry_net(e)
        aux = [recs[a] for a in e["aux"]]
        stats = [tuple(s) for s in e["stats"]]
        inputs, _, _ = online_trainer.make_dataset(
            recs[name], None, e["abs_eb"], aux=aux, slice_axis=slice_axis,
            stats=stats)
        sig = (inputs.shape, net_cfg.regulated, net_cfg.skip)
        sig_groups.setdefault(sig, []).append(name)
        prepared[name] = (net_cfg, params, jnp.asarray(inputs))

    for sig, names in sig_groups.items():
        spec = tuple((prepared[n][0].regulated, prepared[n][0].skip)
                     for n in names)
        resids = _predict_group(tuple(prepared[n][1] for n in names),
                                tuple(prepared[n][2] for n in names),
                                spec=spec)
        for f, name in enumerate(names):
            out[name] = neurlz.apply_decoded_entry(
                arc["fields"][name], recs[name], np.asarray(resids[f]),
                slice_axis)
    return {name: out[name] for name in arc["fields"]}
