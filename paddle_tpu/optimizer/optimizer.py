"""Optimizer base. Reference analog: python/paddle/optimizer/optimizer.py
(class Optimizer: accumulators, grad clip, regularization, LR scheduling).

TPU-first: `step()` gathers (param, grad, accumulator) pytrees and applies ONE
jitted update function with buffer donation — the whole optimizer update is a
single fused XLA executable per parameter-group structure, not per-op eager
dispatch (reference analog: fused optimizer ops like
fluid/operators/optimizers/distributed_fused_lamb_op.cu).
"""
from __future__ import annotations

from collections import defaultdict

import numpy as np
import jax
import jax.numpy as jnp

from ..framework.core import Tensor, Parameter
from ..nn.clip import ClipGradBase
from .lr import LRScheduler

__all__ = ["Optimizer"]


class Optimizer:
    def __init__(self, learning_rate=0.001, parameters=None, weight_decay=None,
                 grad_clip=None, name=None):
        if parameters is None:
            raise ValueError(
                "parameters is required in dygraph mode "
                "(pass model.parameters())")
        self._parameter_list = list(parameters)
        self._learning_rate = learning_rate
        self._grad_clip = grad_clip
        self._name = name
        if isinstance(weight_decay, (float, int)) and weight_decay:
            from .regularizer import L2Decay
            self.regularization = L2Decay(float(weight_decay))
        else:
            self.regularization = weight_decay
        self._accumulators = defaultdict(dict)  # name -> {param_name: value}
        self._jitted_update = {}

    # -- learning rate ------------------------------------------------------
    def get_lr(self):
        if isinstance(self._learning_rate, LRScheduler):
            return float(self._learning_rate())
        return float(self._learning_rate)

    def set_lr(self, value):
        if isinstance(self._learning_rate, LRScheduler):
            raise RuntimeError(
                "cannot set_lr when the lr is an LRScheduler; call "
                "scheduler.step() instead")
        self._learning_rate = float(value)

    def set_lr_scheduler(self, scheduler):
        self._learning_rate = scheduler

    # -- accumulators -------------------------------------------------------
    def _add_accumulator(self, name, param, fill_value=0.0, dtype=None,
                         shape=None):
        key = param.name
        if key not in self._accumulators[name]:
            shp = shape if shape is not None else param._value.shape
            dt = dtype if dtype is not None else param._value.dtype
            # a slot shaped like a sharded parameter is created with the
            # parameter's placement: made on the default device it would
            # put the whole optimizer state on device 0 first
            sharding = getattr(param._value, "sharding", None)
            if tuple(shp) != tuple(param._value.shape) or sharding is None \
                    or len(sharding.device_set) <= 1:
                sharding = None
            self._accumulators[name][key] = jnp.full(shp, fill_value, dt,
                                                     device=sharding)
        return self._accumulators[name][key]

    def _get_accumulator(self, name, param):
        return self._accumulators[name][param.name]

    # -- subclass interface -------------------------------------------------
    def _create_accumulators(self, params):
        pass

    def _single_update(self, pval, grad, accs, lr, step_count):
        """Pure function: (param, grad, {accs}, lr) -> (new_param, {new_accs}).
        Subclasses implement this; it gets jit-compiled over the whole
        parameter list in one go."""
        raise NotImplementedError

    def _extra_cache_key(self):
        """Subclass hook: anything baked into the traced update as a constant
        (e.g. per-param decay flags) MUST be part of the jit cache key."""
        return ()

    # -- main entry points --------------------------------------------------
    def step(self):
        # whole-step fusion (ops/step_fusion.py): when a fused train-step
        # replay is pending and verified, ONE compiled executable has
        # already computed loss, grads, and this update — nothing left to
        # do. In observation mode the hook just delimits the step cycle.
        from ..ops.step_fusion import STEP as _step_fusion
        from ..ops import guardian
        from ..profiler import goodput as _goodput
        if _step_fusion.on_optimizer_step(self):
            guardian.maybe_flush()
            # goodput accountant (profiler/goodput.py): every training
            # step — fused replay or eager — crosses this boundary; one
            # flag check when FLAGS_metrics is off
            _goodput.on_step(self)
            return
        params = [p for p in self._parameter_list
                  if not p.stop_gradient or p.grad is not None]
        params_grads = [(p, p.grad) for p in params if p.grad is not None]
        # flight recorder: an EAGER (unfused) optimizer step ran — during a
        # never-promoting loop this is the per-step heartbeat the doctor
        # correlates with the poison events that explain why
        from ..profiler.events import EVENTS as _EVENTS
        _EVENTS.emit("step.record", "optimizer_step",
                     detail={"kind": "eager_step",
                             "params": len(params_grads)})
        if not params_grads:
            guardian.maybe_flush()
            _goodput.on_step(self)
            return
        if self.regularization is not None:
            params_grads = [
                (p, self.regularization.apply(p, g)) for p, g in params_grads]
        if self._grad_clip is not None:
            params_grads = self._grad_clip(params_grads)
        self._create_accumulators([p for p, _ in params_grads])
        self._apply_optimize(params_grads)
        # the step boundary resolves the guardian's queued in-graph checks
        # (one batched device->host transfer); a no-op when the queue is
        # empty (FLAGS_check_numerics off)
        guardian.maybe_flush()
        _goodput.on_step(self)

    def _apply_optimize(self, params_grads):
        from ..ops import guardian
        # guardian skip-step rescue (FLAGS_check_numerics): the finite
        # check and the where() no-op rescue compile INTO the jitted
        # update (keyed), matching the fused whole-step semantics bitwise
        check = guardian.skip_step_enabled()
        lr = jnp.asarray(self.get_lr(), jnp.float32)
        acc_names = sorted(self._accumulators.keys())
        step_key = "_step_count"
        if not hasattr(self, step_key):
            self._step_count = 0
        self._step_count += 1
        step_count = jnp.asarray(self._step_count, jnp.int32)

        pvals = [p._value for p, _ in params_grads]
        gvals = [g._value for _, g in params_grads]
        # .get: a param can lack an entry for some accumulator (e.g. no
        # master_weight for params already f32 under multi_precision)
        accs = [[self._accumulators[n].get(p.name) for n in acc_names]
                for p, _ in params_grads]

        structure_key = (len(params_grads),
                         tuple((v.shape, str(v.dtype)) for v in pvals),
                         tuple(acc_names),
                         self._extra_cache_key(), check)
        update = self._jitted_update.get(structure_key)
        if update is None:
            single = self._single_update

            def batch_update(pvals, gvals, accs, lr, step_count):
                new_p, new_a = [], []
                for pv, gv, ac in zip(pvals, gvals, accs):
                    acc_dict = dict(zip(acc_names, ac))
                    np_, na_ = single(pv, gv, acc_dict, lr, step_count)
                    new_p.append(np_)
                    new_a.append([na_.get(n) for n in acc_names])
                if not check:
                    return new_p, new_a, None
                # non-finite grads OR non-finite NEW state -> the whole
                # update is a bitwise no-op on params AND slots; ONE
                # fused scalar predicate. The new params/slots join the
                # predicate because finite grads can still overflow the
                # state (LR spike, saturating momentum) — matching the
                # fused whole-step gate (ops/step_fusion.py) bitwise
                new_state = list(new_p) + [v for row in new_a
                                           for v in row if v is not None]
                finite = guardian.finite_all(list(gvals) + new_state)
                new_p = [jnp.where(finite, nv, pv)
                         for nv, pv in zip(new_p, pvals)]
                new_a = [[None if nv is None else jnp.where(finite, nv, ov)
                          for nv, ov in zip(row, ac)]
                         for row, ac in zip(new_a, accs)]
                return new_p, new_a, finite

            # only accumulator buffers are donated: param buffers may be
            # aliased by user-held tensors (detach() shares storage), and
            # donating them would invalidate those aliases
            update = jax.jit(batch_update, donate_argnums=(2,))
            self._jitted_update[structure_key] = update

        new_pvals, new_accs, finite = update(pvals, gvals, accs, lr,
                                             step_count)
        for (p, _), npv, nac in zip(params_grads, new_pvals, new_accs):
            p._value = npv
            for n, v in zip(acc_names, nac):
                if v is not None:
                    self._accumulators[n][p.name] = v
        if check:
            guardian.note_step("eager_step", finite,
                               step_index=self._step_count)

    def minimize(self, loss, startup_program=None, parameters=None,
                 no_grad_set=None):
        loss.backward()
        self.step()
        return None, None

    def clear_grad(self, set_to_zero=True):
        from ..ops.step_fusion import STEP as _step_fusion
        _step_fusion.on_clear_grad(self)
        for p in self._parameter_list:
            p.grad = None

    clear_gradients = clear_grad

    # -- state dict ---------------------------------------------------------
    def state_dict(self):
        state = {}
        for name, per_param in self._accumulators.items():
            for pname, val in per_param.items():
                state[f"{pname}_{name}"] = Tensor(val)
        if isinstance(self._learning_rate, LRScheduler):
            state["LR_Scheduler"] = self._learning_rate.state_dict()
        state["_step_count"] = getattr(self, "_step_count", 0)
        return state

    def set_state_dict(self, state_dict):
        if "LR_Scheduler" in state_dict and \
                isinstance(self._learning_rate, LRScheduler):
            self._learning_rate.set_state_dict(state_dict["LR_Scheduler"])
        self._step_count = int(state_dict.get("_step_count", 0))
        self._create_accumulators(self._parameter_list)
        for name, per_param in self._accumulators.items():
            for pname in list(per_param.keys()):
                key = f"{pname}_{name}"
                if key in state_dict:
                    v = state_dict[key]
                    arr = v._value if isinstance(v, Tensor) else jnp.asarray(v)
                    per_param[pname] = arr

    load_state_dict = set_state_dict
