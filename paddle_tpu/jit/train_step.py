"""TrainStep: a fully-fused jitted training step.

Reference analog: the whole dygraph hot loop (forward ad_funcs + RunBackward +
optimizer ops) collapsed into one XLA executable — the TPU-first answer to the
reference's per-op C++ dispatch war (phi README §1.2).

    step = TrainStep(model, loss_fn, optimizer)
    loss = step(batch_x, batch_y)          # one compiled fwd+bwd+update

Parameters and optimizer slots live as donated pytrees across steps; the
model's wrapper tensors are refreshed after each call so eager inspection
(state_dict, p.numpy()) still works.
"""
from __future__ import annotations

import itertools
import weakref

import jax
import jax.numpy as jnp

from ..framework.core import Tensor
from ..framework import random as _random
from ..framework.autograd import set_grad_enabled
from ..profiler import RecordEvent, watch_gc
from ..profiler.metrics import LogHistogram

__all__ = ["TrainStep", "TrainStepStats", "train_step_stats",
           "bake_decay_flags", "donation_argnums"]

# every TrainStep alive in the process, in order of creation, for readers
# that hold no handle to one (`train_step_stats`; held weakly, the pattern
# of telemetry_server.register_engine)
_LIVE = weakref.WeakValueDictionary()
_SERIAL = itertools.count()


def train_step_stats():
    """`stats()` of every live `TrainStep`, oldest first."""
    return [step.stats() for step in list(_LIVE.values())]


class TrainStepStats:
    """Host-side counters of one `TrainStep`: the seconds of each phase of
    `__call__` in a bounded histogram keyed by the phase's span, which
    feeds it, the programs the jitted step has traced (bumped INSIDE
    the traced function, which runs only while tracing), and, of a model
    that counts (`train_counter_names` and a buffer `train_counters` its
    forward fills: expert loads, loss terms), the newest step's counters
    and, as `first_step_<name>`, the first step's: they leave the compiled
    step with the other buffers, in the same turn as the loss, and are
    fetched only when the stats are read.

    What `snapshot()` holds, `<phase>` being `call`, `gather_state`,
    `dispatch`, `write_back` and `gc` (a collection of the Python heap
    wherever it falls: `profiler.watch_gc`):

    `steps`, `compiles`, `flash_width_fallbacks`
        calls; programs traced; long attentions a TPU sent to XLA's N^2
        path
    `<phase>_p50_ms`, `<phase>_p99_ms`
        mids of the histogram's log buckets
    `<phase>_max_ms`
        the longest single span since the step was built
    `dispatches`
        calls of the compiled step after the first, the calls that trace
        left out
    `dispatches_device_idle`, `starved_dispatch_share`
        of those, the calls that found the loss of the step before READY
        at entry (nothing was queued on the device), and their share
    `dispatches_blocked`, `dispatch_blocked_share`
        those that found it not ready at entry and ready at return (the
        call outlasted the step before), and their share
    a counting model's names, and `first_step_<name>`
        as above

    Nothing here is windowed (there is no reset): a caller's checked
    steps, which wait for their results, count in `dispatches`."""

    # spans that own a histogram (`train_step.build` is for the trace alone)
    PHASES = ("train_step.call", "train_step.gather_state",
              "train_step.dispatch", "train_step.write_back")
    GC_SPAN = "train_step.gc"

    def __init__(self):
        self.compiles = 0
        self.phase = {name: LogHistogram()
                      for name in self.PHASES + (self.GC_SPAN,)}
        watch_gc(self.GC_SPAN, self.phase[self.GC_SPAN])
        self.dispatches = 0
        self.dispatches_device_idle = 0
        self.dispatches_blocked = 0
        # what the model counted in its newest step: (names, the device
        # array the step returned beside the loss), read on `snapshot()`;
        # and in its FIRST step (a router that trains moves its loads)
        self.model_counters = None
        self.first_model_counters = None

    def count_dispatch(self, ready_at_entry, ready_at_return):
        """One call of the compiled step, by whether the loss of the step
        before was ready when the call began and when it returned."""
        self.dispatches += 1
        if ready_at_entry:
            self.dispatches_device_idle += 1
        elif ready_at_return:
            self.dispatches_blocked += 1

    def snapshot(self):
        from ..kernels import flash_attention
        asked = self.dispatches
        out = {"steps": self.phase["train_step.call"].count,
               "compiles": self.compiles,
               # long causal attentions a TPU sent to XLA's N^2 path for
               # their head widths alone (process-wide; 0, or a model has
               # lost its kernel)
               "flash_width_fallbacks": flash_attention.width_fallbacks(),
               "dispatches": asked,
               "dispatches_device_idle": self.dispatches_device_idle,
               "dispatches_blocked": self.dispatches_blocked,
               "starved_dispatch_share":
                   self.dispatches_device_idle / asked if asked else 0.0,
               "dispatch_blocked_share":
                   self.dispatches_blocked / asked if asked else 0.0}
        if self.model_counters is not None:
            for prefix, (names, values) in (
                    ("", self.model_counters),
                    ("first_step_", self.first_model_counters)):
                out.update(zip((prefix + n for n in names),
                               jax.device_get(values).tolist()))
        for name, hist in self.phase.items():
            short = name.split(".", 1)[1]
            out[f"{short}_p50_ms"] = hist.percentile(50) * 1e3
            out[f"{short}_p99_ms"] = hist.percentile(99) * 1e3
            out[f"{short}_max_ms"] = (hist.max or 0.0) * 1e3
        return out


def bake_decay_flags(opt, params):
    """Prime the optimizer's per-param weight-decay flag list for a traced
    update: AdamW/Lamb/Lars `_single_update` implementations consume
    `_current_decay_flags` in parameter order at trace time, so any builder
    that jit-compiles `_single_update` over a parameter list (TrainStep and
    the eager auto-TrainStep in ops/step_fusion.py) must bake them first."""
    if hasattr(opt, "_decay_skip"):
        opt._current_decay_flags = [p.name not in opt._decay_skip
                                    for p in params]
    elif hasattr(opt, "_decay_flags"):
        opt._current_decay_flags = [opt._decay_flags.get(p.name, True)
                                    for p in params]


def donation_argnums(donate_params, params_pos, accs_pos):
    """Donation spec shared by TrainStep and the eager auto-TrainStep:
    optimizer-slot (accumulator) buffers are always donated — exactly what
    the eager optimizer's own fused update does — while parameter buffers
    are only donated on request, because user-held aliases of `p._value`
    (detach() shares storage) would be invalidated."""
    return (params_pos, accs_pos) if donate_params else (accs_pos,)


class TrainStep:
    def __init__(self, model, loss_fn, optimizer, donate=True):
        self.model = model
        self.loss_fn = loss_fn
        self.optimizer = optimizer
        self._jitted = None
        self._params = None
        self._acc_names = None
        self._donate = donate
        self._stats = TrainStepStats()
        self._loss_before = None    # the newest call's loss, on the device
        _LIVE[next(_SERIAL)] = self

    def stats(self):
        """The host's side of every call since the step was built: the
        keys of the table in `TrainStepStats`' docstring."""
        return self._stats.snapshot()

    def _span(self, name):
        """The span `name` of this step; its seconds go to the phase
        histogram of that name where `TrainStepStats` keeps one."""
        return RecordEvent(name, hist=self._stats.phase.get(name))

    def _build(self, example_args):
        model = self.model
        loss_fn = self.loss_fn
        opt = self.optimizer
        params = [p for p in model.parameters() if not p.stop_gradient]
        buffers = [b for _, b in model.named_buffers()]
        self._params = params
        self._buffers = buffers
        names = [n for n, _ in model.named_buffers()]
        self._counters_at = names.index("train_counters") \
            if getattr(model, "train_counter_names", None) else None
        opt._create_accumulators(params)
        acc_names = sorted(opt._accumulators.keys())
        self._acc_names = acc_names

        def pure_loss(pvals, bvals, args, key):
            saved_p = [p._value for p in params]
            saved_b = [b._value for b in buffers]
            saved_flags = [p.stop_gradient for p in params]
            try:
                for p, v in zip(params, pvals):
                    p._value = v
                    p.stop_gradient = True
                for b, v in zip(buffers, bvals):
                    b._value = v
                targs = [Tensor(a, stop_gradient=True) for a in args]
                with _random.tracing_key_scope(key):
                    with set_grad_enabled(False):
                        out = model(*targs[:-1]) if loss_fn is not None \
                            else model(*targs)
                        loss = loss_fn(out, targs[-1]) if loss_fn is not None \
                            else out
                new_b = [b._value for b in buffers]
                return loss._value, new_b
            finally:
                for p, v, sg in zip(params, saved_p, saved_flags):
                    p._value = v
                    p.stop_gradient = sg
                for b, v in zip(buffers, saved_b):
                    b._value = v

        # bake per-param decay flags for AdamW/Lamb before tracing
        bake_decay_flags(opt, params)

        stats = self._stats

        def step(pvals, accs, bvals, args, lr, step_count, key):
            stats.compiles += 1         # runs only while jit traces
            (loss, new_b), grads = jax.value_and_grad(
                pure_loss, has_aux=True)(pvals, bvals, args, key)
            new_p, new_accs = [], []
            for pv, gv, ac in zip(pvals, grads, accs):
                acc_dict = dict(zip(acc_names, ac))
                np_, na_ = opt._single_update(pv, gv, acc_dict, lr, step_count)
                new_p.append(np_)
                # .get: f32 params have no master_weight entry under
                # multi_precision
                new_accs.append([na_.get(n) for n in acc_names])
            return loss, new_p, new_accs, new_b

        # donate accumulators by default; donating params would invalidate
        # user-held aliases of p._value (detach() shares storage). Pass
        # donate="all" for maximum-memory-efficiency training loops that
        # never alias parameters.
        if self._donate == "all":
            donate = donation_argnums(True, 0, 1) + (2,)
        elif self._donate:
            donate = donation_argnums(False, 0, 1)
        else:
            donate = ()
        self._jitted = jax.jit(step, donate_argnums=donate)

    def _state_args(self, args):
        """The step program's leading arguments (params, slots, buffers,
        inputs, lr) as they stand now; builds the program on first use."""
        arg_vals = [a._value if isinstance(a, Tensor) else jnp.asarray(a)
                    for a in args]
        if self._jitted is None:
            with self._span("train_step.build"):
                self._build(arg_vals)
        params = self._params
        opt = self.optimizer
        opt._create_accumulators(params)
        pvals = [p._value for p in params]
        accs = [[opt._accumulators[n].get(p.name) for n in self._acc_names]
                for p in params]
        bvals = [b._value for b in self._buffers]
        lr = jnp.asarray(opt.get_lr(), jnp.float32)
        return pvals, accs, bvals, arg_vals, lr

    def lower(self, *args):
        """`jax.jit(...).lower` of the program `__call__` would run on
        these arguments: nothing executes and neither the optimizer's step
        count nor the RNG stream moves. `.compile()` the result to read
        the program text or `memory_analysis()`."""
        state = self._state_args(args)
        key = jax.eval_shape(lambda: jax.random.key(0))
        return self._jitted.lower(*state, jnp.asarray(1, jnp.int32), key)

    def __call__(self, *args):
        with self._span("train_step.call"):
            return self._call(args)

    def _call(self, args):
        opt = self.optimizer
        with self._span("train_step.gather_state"):
            state = self._state_args(args)
            if not hasattr(opt, "_step_count"):
                opt._step_count = 0
            opt._step_count += 1
            step_count = jnp.asarray(opt._step_count, jnp.int32)
            key = _random.get_rng_key()
        params = self._params
        acc_names = self._acc_names

        # did this call find the device idle, and did it outlast the step
        # before? Asked of that step's loss, without waiting
        before, traced = self._loss_before, self._stats.compiles
        ready_at_entry = before is not None and before.is_ready()
        with self._span("train_step.dispatch"):
            loss, new_p, new_accs, new_b = self._jitted(*state, step_count,
                                                        key)
        if before is not None and self._stats.compiles == traced:
            self._stats.count_dispatch(ready_at_entry, before.is_ready())
        self._loss_before = loss
        from ..framework.flags import _FLAGS
        if _FLAGS.get("FLAGS_check_nan_inf") and \
                not bool(jnp.isfinite(loss)):
            # keep the (non-donated) pre-step parameters so an eager re-run
            # can locate the bad op; the donated accumulator buffers are
            # gone, so their new values must land regardless
            for p, ac in zip(params, new_accs):
                for n, v in zip(acc_names, ac):
                    if v is not None:
                        opt._accumulators[n][p.name] = v
            raise FloatingPointError(
                "TrainStep produced a non-finite loss "
                "(FLAGS_check_nan_inf); parameters were NOT updated "
                "(optimizer accumulators were) — re-run the step eagerly "
                "to locate the offending op")
        with self._span("train_step.write_back"):
            for p, v in zip(params, new_p):
                p._value = v
            for p, ac in zip(params, new_accs):
                for n, v in zip(acc_names, ac):
                    if v is not None:
                        opt._accumulators[n][p.name] = v
            for b, v in zip(self._buffers, new_b):
                b._value = v
            if self._counters_at is not None:
                counted = (self.model.train_counter_names,
                           new_b[self._counters_at])
                self._stats.model_counters = counted
                if self._stats.first_model_counters is None:
                    # a copy: the buffer itself is donated to the next step
                    self._stats.first_model_counters = (
                        counted[0], jnp.copy(counted[1]))
            # goodput accountant (profiler/goodput.py): the explicit fused
            # TrainStep never crosses Optimizer.step, so the boundary is
            # here
            from ..profiler import goodput as _goodput
            _goodput.on_step(opt)
        return Tensor(loss)
