"""SPMD trainer: one jitted, mesh-sharded train step for a Gluon block.

This is the TPU-native replacement for the reference's whole multi-device
training path — ``DataParallelExecutorGroup`` batch slicing
(``python/mxnet/module/executor_group.py:282-304``), KVStore gradient
allreduce (``src/kvstore/comm.h``) and the optimizer update loop — collapsed
into a single ``jax.jit`` over a ``Mesh``: the batch is sharded on ``dp``,
parameters on ``tp`` per the sharding rules, and XLA inserts the psum that the
KVStore used to perform.  Donated buffers give the in-place update semantics
of the reference's engine (weights/optimizer state update without extra HBM).
"""
from __future__ import annotations

import contextlib

import jax
import jax.numpy as jnp

from .. import autograd
from .. import ndarray as nd_mod
from .. import random as _rnd
from ..analysis import divergence as _div
from ..analysis import sanitizer as _san
from ..ndarray import NDArray
from ..telemetry import bus as _tel
from ..telemetry import flight as _flight
from ..telemetry import jax_hooks as _tel_jax
from ..telemetry import trace as _trace
from .optim import FunctionalOptimizer
from .sharding import infer_param_specs, named_sharding

__all__ = ["SPMDTrainer", "make_train_step"]


def _functional_apply(net, trainable, aux, n_in):
    """Pure fn (param_arrays, aux_arrays, *inputs, key) → (outputs, new_aux).

    Same handle-swap trick as ``CachedOp`` (gluon/block.py): parameter
    NDArrays temporarily carry tracers so the block's eager ``forward``
    records into the trace.
    """
    handles = [p.data() for p in trainable]
    aux_handles = [p.data() for p in aux]

    def apply_fn(par_raw, aux_raw, *inputs, __key__=None):
        old = [h._data for h in handles]
        old_aux = [h._data for h in aux_handles]
        with autograd.pause(train_mode=True), _rnd.key_scope(__key__):
            try:
                for h, r in zip(handles, par_raw):
                    h._data = r
                for h, r in zip(aux_handles, aux_raw):
                    h._data = r
                wrapped = [nd_mod._wrap(x) for x in inputs[:n_in]]
                out = net.forward(*wrapped)
                new_aux = [p.data()._data for p in aux]
            finally:
                for h, o in zip(handles, old):
                    h._data = o
                for h, o in zip(aux_handles, old_aux):
                    h._data = o
        return out, new_aux

    return apply_fn


def make_train_step(net, loss_fn, optimizer, mesh, data_spec=None,
                    label_spec=None,
                    param_rules=None, tp_axis="tp", dp_axis="dp",
                    donate=True, n_in=1, amp_bf16=False,
                    param_dtype=None, nan_guard=False):
    """Build ``(step_fn, init_args)`` for SPMD training of ``net``.

    - ``net``: an initialized (non-hybridized) Gluon block.
    - ``loss_fn``: gluon loss block or ``(pred, label) -> NDArray``.
    - ``optimizer``: :class:`FunctionalOptimizer`, eager Optimizer, or name.
    - ``data_spec``: PartitionSpec for each input batch (default: first axis
      sharded over ``dp``).
    - ``amp_bf16``: fp32 master weights, bf16 compute+activations (AMP).
    - ``nan_guard``: compile a non-finite-step guard into the jitted step
      (resilience layer): when the loss or any gradient is non-finite the
      params/optimizer slots/aux keep their OLD values — the bad update is
      skipped entirely on-device, no host round-trip.  The loss is still
      returned non-finite so a host-side ``StepGuard`` can count the streak
      and escalate to a checkpoint rollback.  Off by default: the guard
      adds an isfinite reduction over every gradient plus a select over the
      state, so the unguarded hot path is left untouched.
    - ``param_dtype=jnp.bfloat16``: pure-bf16 STORAGE — params and
      optimizer state live in bf16 (half the HBM prefetch traffic of the
      AMP master copies); the optimizer update itself computes in fp32
      and rounds back — intra-step arithmetic is exact, but slots still
      ROUND to bf16 between steps (per-step contributions below the
      slot's bf16 ulp are lost).  Use amp_bf16 (fp32 master) when exact
      long-run accumulation matters.

    Returns ``(step_fn, state)`` where ``state = (params, opt_state, aux)``
    holds sharded ``jax.Array``s and
    ``step_fn(state, data, label, key, t) -> (state', loss)``.
    """
    from jax.sharding import PartitionSpec as P

    if isinstance(optimizer, str):
        optimizer = FunctionalOptimizer(optimizer)
    elif not isinstance(optimizer, FunctionalOptimizer):
        optimizer = FunctionalOptimizer.from_optimizer(optimizer)

    items = sorted(net.collect_params().items())
    trainable = [p for _, p in items if p.grad_req != "null"]
    aux = [p for _, p in items if p.grad_req == "null"]
    names = [p.name for p in trainable]

    specs = infer_param_specs(
        {p.name: p.shape for p in trainable}, mesh, rules=param_rules,
        tp_axis=tp_axis)
    if n_in > 1:
        if data_spec is None:
            data_spec = tuple(P(dp_axis) for _ in range(n_in))
        elif isinstance(data_spec, P) or len(data_spec) != n_in:
            # P is itself a tuple subclass — iterating it would yield raw
            # axis names, so demand an explicit sequence of n_in specs
            raise ValueError(
                f"with n_in={n_in}, data_spec must be a sequence of {n_in} "
                f"PartitionSpecs, got {data_spec!r}")
    elif data_spec is None:
        data_spec = P(dp_axis)
    if label_spec is None:
        label_spec = P(dp_axis)

    def _store(a):
        if param_dtype is not None and a.dtype == jnp.float32:
            a = a.astype(param_dtype)
        return a

    params = {p.name: jax.device_put(_store(p.data()._data),
                                     named_sharding(mesh, specs[p.name]))
              for p in trainable}
    aux_arrays = [jax.device_put(p.data()._data, named_sharding(mesh, P()))
                  for p in aux]
    opt_state = {k: tuple(jax.device_put(s, named_sharding(mesh, specs[k]))
                          for s in v)
                 for k, v in optimizer.init_state(params).items()}

    apply_fn = _functional_apply(net, trainable, aux, n_in=n_in)

    def loss_of(par_dict, aux_raw, data, label, key):
        inputs = data if isinstance(data, tuple) else (data,)
        par_vals = [par_dict[n] for n in names]
        if amp_bf16 or param_dtype is not None:
            # mixed precision, TPU style: fp32 master weights, bf16 compute
            # AND bf16 activations — the fwd/bwd HBM traffic halves, which
            # is the actual bottleneck (measured: ResNet-50 fwd 0.29 → 0.52
            # MFU).  Gradients flow back through the casts as fp32.  Under
            # param_dtype=bf16 the param cast is a no-op (already stored
            # bf16) and only inputs cast.
            par_vals = [p.astype(jnp.bfloat16) if p.dtype == jnp.float32
                        else p for p in par_vals]
            inputs = tuple(x.astype(jnp.bfloat16)
                           if x.dtype == jnp.float32 else x for x in inputs)
        out, new_aux = apply_fn(par_vals, aux_raw, *inputs, __key__=key)
        with autograd.pause(train_mode=True):
            loss = loss_fn(out, nd_mod._wrap(label))
            if isinstance(loss, NDArray):
                loss = loss._data
        # cast BEFORE the reduction: a bf16-accumulated mean would round
        # the only convergence signal step() reports
        return jnp.mean(loss.astype(jnp.float32)), new_aux

    def step(state, data, label, key, t):
        params, opt_state, aux_raw = state
        (loss, new_aux), grads = jax.value_and_grad(
            loss_of, has_aux=True)(params, aux_raw, data, label, key)
        if nan_guard:
            ok = jnp.isfinite(loss)
            for g in jax.tree_util.tree_leaves(grads):
                ok = ok & jnp.all(jnp.isfinite(g))
        if param_dtype is not None:
            # bf16 storage: do the update arithmetic in fp32 (a fused
            # convert on each side), round the results back to storage
            f32 = jax.tree_util.tree_map(
                lambda a: a.astype(jnp.float32), (params, grads, opt_state))
            new_params, new_opt = optimizer.update(*f32[:2], f32[2], t)
            new_params = {k: v.astype(params[k].dtype)
                          for k, v in new_params.items()}
            new_opt = {k: tuple(s.astype(opt_state[k][i].dtype)
                                for i, s in enumerate(v))
                       for k, v in new_opt.items()}
        else:
            new_params, new_opt = optimizer.update(params, grads,
                                                   opt_state, t)
        if nan_guard:
            # non-finite step: keep the old state wholesale.  jnp.where on
            # a scalar predicate lowers to a select XLA fuses into the
            # update; donation stays valid (old buffers feed the select).
            keep = lambda new, old: jnp.where(ok, new, old)
            new_params = jax.tree_util.tree_map(keep, new_params, params)
            new_opt = jax.tree_util.tree_map(keep, new_opt, opt_state)
            new_aux = jax.tree_util.tree_map(keep, new_aux, list(aux_raw))
        return (new_params, new_opt, new_aux), loss

    state_sh = (
        {k: named_sharding(mesh, v) for k, v in specs.items()},
        {k: tuple(named_sharding(mesh, specs[k]) for _ in v)
         for k, v in opt_state.items()},
        [named_sharding(mesh, P()) for _ in aux_arrays],
    )
    data_sh = tuple(named_sharding(mesh, s) for s in data_spec) \
        if n_in > 1 else named_sharding(mesh, data_spec)
    label_sh = named_sharding(mesh, label_spec)
    step_jit = jax.jit(step,
                       in_shardings=(state_sh, data_sh, label_sh, None, None),
                       out_shardings=(state_sh, None),
                       donate_argnums=(0,) if donate else ())
    return step_jit, (params, opt_state, aux_arrays)


class SPMDTrainer:
    """Object wrapper keeping the Gluon block usable after training.

    Mirrors :class:`mxnet_tpu.gluon.Trainer`'s role in the SPMD world:
    ``step(data, label)`` runs the fused forward/backward/allreduce/update,
    ``sync_to_block()`` writes the (sharded) weights back into the block's
    Parameters for eager inference / ``save_parameters``.

    Keyword args forward to :func:`make_train_step` — pass
    ``nan_guard=True`` to skip non-finite updates on-device (pair with
    ``resilience.ResilientTrainer`` for checkpoint/rollback handling).
    """

    def __init__(self, net, loss_fn, optimizer, mesh,
                 sequence_parallel=False, sp_axis="sp", dp_axis="dp",
                 sp_impl="ring", **kw):
        self._net = net
        self._mesh = mesh
        if sequence_parallel and mesh.shape.get(sp_axis, 1) <= 1:
            raise ValueError(
                f"sequence_parallel=True requires mesh axis {sp_axis!r} with "
                f"size > 1; mesh has {dict(mesh.shape)}")
        self._dp_axis = dp_axis
        self._tp_axis = kw.get("tp_axis", "tp")
        self._sp = (mesh, sp_axis, dp_axis, sp_impl) \
            if sequence_parallel else None
        with self._sp_scope():
            self._step_fn, self._state = make_train_step(
                net, loss_fn, optimizer, mesh, dp_axis=dp_axis, **kw)
        self._donate = bool(kw.get("donate", True))
        self._preempt = None
        self._t = 0
        items = sorted(net.collect_params().items())
        self._trainable = [p for _, p in items if p.grad_req != "null"]
        self._aux = [p for _, p in items if p.grad_req == "null"]

    @contextlib.contextmanager
    def _sp_scope(self):
        """What a layer may ask while the step is traced: the mesh, and
        the sequence-parallel routing where that is on."""
        from .sp_context import traced_mesh_scope, sequence_parallel_scope
        with traced_mesh_scope(self._mesh, self._dp_axis, self._tp_axis), \
                sequence_parallel_scope(*self._sp) if self._sp is not None \
                else contextlib.nullcontext():
            yield

    def install_preemption(self, handler, manager, extra=None):
        """Preemption-safe training without the ResilientTrainer wrapper:
        a triggered ``handler`` (SIGTERM/SIGINT, or ``.trigger()``) makes
        the next :meth:`step` call do one final synchronous durable save
        through ``manager`` and raise ``TrainingPreempted`` (clean exit
        code 0) instead of dispatching.  One attribute check per step when
        installed, zero when not."""
        self._preempt = (handler, manager, extra)
        return handler

    def step(self, data, label):
        import jax as _jax
        from jax.sharding import NamedSharding, PartitionSpec as _P

        if self._preempt is not None:
            handler, manager, extra = self._preempt
            if handler.triggered:
                from ..resilience import preempt as _pre
                _pre.save_and_exit(manager, self, extra=extra)

        def _raw(x):
            if isinstance(x, NDArray):
                x = x._data
                if getattr(x, "committed", False) and \
                        len(x.devices()) < self._mesh.devices.size:
                    # committed single-device arrays cannot be resharded
                    # implicitly by the jitted step; async device_put onto
                    # the batch sharding (uncommitted arrays pass through —
                    # jit places those itself)
                    return _jax.device_put(
                        x, NamedSharding(self._mesh, _P(self._dp_axis)))
                return x
            return jnp.asarray(x)
        data = tuple(_raw(d) for d in data) \
            if isinstance(data, (tuple, list)) else _raw(data)
        label = _raw(label)
        key = _rnd.next_key()
        if _tel.enabled and self._t == 0:
            self._record_telemetry(data, label, key)
        if _san.collectives:
            # the jitted step is one collective program (grad psum + any
            # sharding collectives): fingerprint it so hosts that disagree
            # on step order/shape are caught at the next sync point
            d0 = data[0] if isinstance(data, tuple) else data
            _div.record(
                "trainer.step",
                axis=",".join(str(a) for a in self._mesh.axis_names),
                shape=tuple(getattr(d0, "shape", ())),
                dtype=getattr(d0, "dtype", None),
                site=f"SPMDTrainer.step t={self._t}")
        _flight.record("trainer.step", value=self._t)
        # the scope matters while jax traces the step (first call / retrace):
        # attention layers consult it to route through ring attention
        old_leaves = None
        if _san.donation and self._donate:
            # the jitted step donates arg 0 (the whole train state): snap
            # the pre-call leaves so they can be poisoned with this site
            old_leaves = _jax.tree_util.tree_leaves(self._state)
        # step-scoped trace root — unless the caller (ResilientTrainer,
        # a serving layer) already activated one on this thread, in which
        # case the step span nests under it
        ctx = None
        if _tel.enabled and _tel.trace_current() is None:
            ctx = _trace.start()
        with _trace.use(ctx), self._sp_scope(), \
                _tel.span("trainer.step", t=self._t):
            self._state, loss = self._step_fn(self._state, data, label, key,
                                              jnp.uint32(self._t))
        if old_leaves is not None:
            _san.poison(old_leaves,
                        f"SPMDTrainer.step t={self._t} (donated train "
                        f"state)")
        _tel.count("trainer.steps")
        self._t += 1
        return NDArray(loss)

    def _record_telemetry(self, data, label, key):
        """One-time gauges: donated-buffer bytes (the state XLA updates
        in place) and the psum/collective payload the lowered HLO moves
        per step.  Only runs with telemetry on, before the first step.

        The collective analysis needs the SPMD-partitioned HLO, which
        costs one extra trace + compile at step 0 (the result is not
        shared with jax's jit cache).  Worth it on the CPU test mesh and
        small models; set ``MXNET_TELEMETRY_HLO=0`` to keep telemetry on
        but skip the analysis on models where startup compile dominates."""
        import os
        nbytes = sum(getattr(leaf, "nbytes", 0)
                     for leaf in jax.tree_util.tree_leaves(self._state))
        _tel.gauge("trainer.donated_bytes", int(nbytes))
        if os.environ.get("MXNET_TELEMETRY_HLO", "1") in ("0", "false"):
            return
        try:
            with self._sp_scope():
                lowered = self._step_fn.lower(self._state, data, label, key,
                                              jnp.uint32(0))
            _tel_jax.record_collectives(lowered, prefix="trainer")
        except Exception:
            pass   # lowering is best-effort diagnosis, never a step failure

    def sync_to_block(self):
        params, _, aux_arrays = self._state
        for p in self._trainable:
            arr = params[p.name]
            want = p.data()._data.dtype
            if arr.dtype != want:
                # param_dtype=bf16 storage: the block's Parameters keep
                # their declared dtype — cast back on the way out
                arr = arr.astype(want)
            p.data()._data = arr
        for p, a in zip(self._aux, aux_arrays):
            p.data()._data = a
