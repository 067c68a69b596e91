"""Optimizer base (reference: python/paddle/optimizer/optimizer.py).

TPU-native design: every optimizer defines ONE pure update rule
`_update(param, grad, slots, lr, **hp) -> (new_param, new_slots)` in
jnp. Dygraph `step()` runs it eagerly per parameter; the jit train-step
harness (paddle_tpu/jit) calls the same rule inside the compiled step
so forward+backward+update fuse into a single XLA program (the analog
of the reference's fused_adam / multi_tensor paths).
"""
from __future__ import annotations


import numpy as np
import jax.numpy as jnp

from ..core.engine import no_grad
from ..core.tensor import Tensor
from .lr import LRScheduler

__all__ = ["Optimizer"]


class Optimizer:
    _slot_names = ()  # e.g. ("moment1", "moment2")
    # multi-tensor Pallas fusion (incubate.nn.pallas.optim): subclasses
    # whose _update rule has a fused-kernel twin set this to its kind;
    # apply_gradients then replaces the per-parameter loop with ONE
    # kernel launch under PADDLE_PALLAS_FUSION=1
    _pallas_fused_kind = None

    def __init__(self, learning_rate=0.001, parameters=None,
                 weight_decay=None, grad_clip=None, name=None,
                 multi_precision=False):
        if parameters is not None:
            parameters = list(parameters)
            if parameters and isinstance(parameters[0], dict):
                # param groups: flatten (group-specific lr unsupported yet)
                flat = []
                for g in parameters:
                    flat.extend(g["params"])
                parameters = flat
        self._parameter_list = parameters
        self._learning_rate = learning_rate
        self._weight_decay = weight_decay
        self._grad_clip = grad_clip
        self._multi_precision = multi_precision
        self._accumulators = {}  # param name -> {slot: jnp array}
        self._step_count = 0
        self._current_param_name = None  # set per-param during step()

    # -- lr ---------------------------------------------------------------
    def get_lr(self):
        lr = self._learning_rate
        if isinstance(lr, LRScheduler):
            return float(lr())
        return float(lr)

    def set_lr(self, value):
        if isinstance(self._learning_rate, LRScheduler):
            raise RuntimeError(
                "can't set_lr when the lr is an LRScheduler; call "
                "scheduler.step() instead")
        self._learning_rate = float(value)

    def set_lr_scheduler(self, scheduler):
        self._learning_rate = scheduler

    # -- slots ------------------------------------------------------------
    def _get_slots(self, p: Tensor):
        key = p.name
        slots = self._accumulators.get(key)
        if slots is None:
            slots = self._create_slots(p)
            self._accumulators[key] = slots
        return slots

    def _create_slots(self, p: Tensor):
        slots = {name: jnp.zeros(tuple(p.shape), jnp.float32)
                 for name in self._slot_names}
        if self._multi_precision and p._value.dtype in (jnp.bfloat16,
                                                        jnp.float16):
            # O2 master weights: fp32 copy updated each step, half-
            # precision param re-derived from it (reference:
            # optimizer.py _create_master_weight / fp16_utils.py)
            slots["master_weight"] = p._value.astype(jnp.float32)
        return slots

    # -- core rule (override) ---------------------------------------------
    def _update(self, param, grad, slots, lr):
        raise NotImplementedError

    def _wd_coeff(self):
        wd = self._weight_decay
        if wd is None:
            return 0.0
        if hasattr(wd, "_coeff"):  # L2Decay object
            return float(wd._coeff)
        return float(wd)

    # -- dygraph step -----------------------------------------------------
    @no_grad()
    def step(self):
        params = self._parameter_list or []
        lr = self.get_lr()
        grads_and_params = [(p, p._grad) for p in params
                            if p._grad is not None and p.trainable]
        if self._grad_clip is not None:
            clipped = self._grad_clip(
                [(p, g) for p, g in grads_and_params])
            grads_and_params = clipped
        wd = self._wd_coeff()
        decoupled = getattr(self, "_decoupled_wd", False)
        for p, g in grads_and_params:
            gv = g._value if isinstance(g, Tensor) else g
            gv = gv.astype(jnp.float32)
            pv = p._value
            slots = self._get_slots(p)
            mw = slots.get("master_weight")
            base = mw if mw is not None else pv
            if wd and not decoupled:
                gv = gv + wd * base.astype(jnp.float32)
            self._current_param_name = p.name
            if mw is not None:
                sub = {k: v for k, v in slots.items()
                       if k != "master_weight"}
                new_master, new_slots = self._update(mw, gv, sub, lr)
                new_slots["master_weight"] = new_master
                p._value = new_master.astype(pv.dtype)
            else:
                new_p, new_slots = self._update(pv, gv, slots, lr)
                p._value = new_p
            self._accumulators[p.name] = new_slots
        self._current_param_name = None
        self._step_count += 1

    minimize_step = step

    def minimize(self, loss, startup_program=None, parameters=None,
                 no_grad_set=None):
        from ..static import _static_mode, _record_minimize
        from ..static.graph import Variable

        if _static_mode() and isinstance(loss, Variable):
            # static graph: record the train spec; the Executor's
            # compiled step computes grads + applies this optimizer
            return _record_minimize(self, loss, parameter_list=parameters)
        loss.backward()
        self.step()
        return None, None

    def clear_grad(self, set_to_zero=False):
        for p in (self._parameter_list or []):
            p.clear_gradient(set_to_zero)

    clear_gradients = clear_grad

    # -- functional API for the jit harness -------------------------------
    def init_state(self, params: dict):
        """params: name -> array. Returns state pytree."""
        state = {name: {s: jnp.zeros(v.shape, jnp.float32)
                        for s in self._slot_names}
                 for name, v in params.items()}
        if self._multi_precision:
            for name, v in params.items():
                if v.dtype in (jnp.bfloat16, jnp.float16):
                    state[name]["master_weight"] = v.astype(jnp.float32)
        return state

    def apply_gradients(self, params: dict, grads: dict, state: dict, lr):
        """Pure: used inside jit. Applies clip + wd + rule. When a
        'master_weight' slot exists (multi_precision), the fp32 master
        is updated and the half-precision param re-derived from it.

        Under PADDLE_PALLAS_FUSION=1 (and a backend that can run the
        kernels) optimizers with a fused twin (_pallas_fused_kind)
        route through incubate.nn.pallas.optim.apply_fused — the whole
        parameter set updates in ONE kernel launch; anything the fused
        path can't express exactly falls back to the loop below."""
        if self._grad_clip is not None:
            grads = self._grad_clip.functional_clip(grads)
        if self._pallas_fused_kind is not None:
            from ..incubate.nn import pallas as _pallas

            if _pallas.optim_supported():
                out = _pallas.optim.apply_fused(self, params, grads,
                                                state, lr)
                if out is not None:
                    return out
        wd = self._wd_coeff()
        decoupled = getattr(self, "_decoupled_wd", False)
        new_params, new_state = {}, {}
        for name, pv in params.items():
            g = grads.get(name)
            if g is None:
                new_params[name] = pv
                new_state[name] = state[name]
                continue
            g = g.astype(jnp.float32)
            mw = state[name].get("master_weight")
            base = mw if mw is not None else pv
            if wd and not decoupled:
                g = g + wd * base.astype(jnp.float32)
            self._current_param_name = name
            if mw is not None:
                sub = {k: v for k, v in state[name].items()
                       if k != "master_weight"}
                new_master, ns_ = self._update(mw, g, sub, lr)
                ns_ = dict(ns_)
                ns_["master_weight"] = new_master
                new_params[name] = new_master.astype(pv.dtype)
            else:
                np_, ns_ = self._update(pv, g, state[name], lr)
                new_params[name] = np_
            new_state[name] = ns_
        self._current_param_name = None
        return new_params, new_state

    # -- state dict -------------------------------------------------------
    def state_dict(self):
        out = {}
        for pname, slots in self._accumulators.items():
            for sname, v in slots.items():
                out[f"{pname}.{sname}"] = Tensor(np.asarray(v))
        out["@step"] = self._step_count
        if isinstance(self._learning_rate, LRScheduler):
            out["LR_Scheduler"] = self._learning_rate.state_dict()
        return out

    def set_state_dict(self, state_dict):
        self._step_count = int(state_dict.get("@step", 0))
        if "LR_Scheduler" in state_dict and isinstance(
                self._learning_rate, LRScheduler):
            self._learning_rate.set_state_dict(state_dict["LR_Scheduler"])
        for key, v in state_dict.items():
            if key in ("@step", "LR_Scheduler"):
                continue
            pname, _, sname = key.rpartition(".")
            arr = v._value if isinstance(v, Tensor) else jnp.asarray(
                np.asarray(v))
            self._accumulators.setdefault(pname, {})[sname] = arr

    set_dict = set_state_dict

    # -- elastic checkpoint slot state ------------------------------------
    def _slot_state(self, named_params):
        """Live accumulator slots re-keyed by STRUCTURED parameter
        name (`named_parameters()` keys). The internal key — `p.name`
        — embeds a per-process generated counter, so it cannot survive
        a relaunch; the structured name can. This is the key space the
        elastic training-state snapshot (incubate.checkpoint.elastic /
        Model._training_state) stores slots under."""
        rev = {p.name: sname for sname, p in named_params}
        return {rev.get(pn, pn): dict(sl)
                for pn, sl in self._accumulators.items()}

    def _load_slot_state(self, slots, named_params):
        """Inverse of _slot_state: re-key a structured-name slot tree
        back onto this process's `p.name`s and install it as the live
        eager accumulators (the compiled path preloads separately via
        TrainStepCompiler.restore_state)."""
        fwd = {sname: p.name for sname, p in named_params}
        self._accumulators = {
            fwd.get(n, n): {s: jnp.asarray(np.asarray(v))
                            for s, v in sl.items()}
            for n, sl in slots.items()}

    @property
    def _param_groups(self):
        return self._parameter_list
