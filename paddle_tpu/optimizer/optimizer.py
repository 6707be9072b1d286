"""Optimizer base + SGD/Momentum/Adam/AdamW/... (reference:
python/paddle/optimizer/optimizer.py and per-optimizer files; fused kernels
phi/kernels/fused_adam_kernel etc.)

TPU-native: each step runs ONE jitted multi-tensor update over the whole
parameter pytree (the reference needs fused_adam/multi_tensor_adam CUDA
kernels for this; XLA fuses it for free). Buffers are donated so parameter
memory is updated in place in HBM.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from ..core.tensor import Tensor
from ..core.dispatch import no_grad
from .lr import LRScheduler


class Optimizer:
    _state_names = ()  # per-param slot names

    def __init__(self, learning_rate=0.001, parameters=None, weight_decay=None,
                 grad_clip=None, name=None):
        if parameters is None:
            raise ValueError("parameters must be provided in eager mode")
        self._parameter_list = list(parameters)
        self._learning_rate = learning_rate
        self._grad_clip = grad_clip
        if isinstance(weight_decay, (int, float)) or weight_decay is None:
            self._weight_decay = float(weight_decay or 0.0)
        else:
            # L2Decay-style objects expose a coeff
            self._weight_decay = float(getattr(weight_decay, "_coeff",
                                               getattr(weight_decay, "coeff", 0.0)))
        self._accumulators = {}  # id(param) -> dict(name -> jax array)
        self._step_count = 0
        self._jitted_update = None

    # -- lr ----------------------------------------------------------------
    def get_lr(self):
        if isinstance(self._learning_rate, LRScheduler):
            return float(self._learning_rate())
        return float(self._learning_rate)

    def set_lr(self, value):
        if isinstance(self._learning_rate, LRScheduler):
            raise RuntimeError("cannot set_lr when using an LRScheduler")
        self._learning_rate = float(value)

    def set_lr_scheduler(self, scheduler):
        self._learning_rate = scheduler

    # -- state -------------------------------------------------------------
    def _ensure_state(self, params):
        for p in params:
            if id(p) not in self._accumulators:
                self._accumulators[id(p)] = {
                    name: jnp.zeros_like(p._value) for name in self._state_names
                }

    def state_dict(self):
        out = {"_step_count": self._step_count}
        for i, p in enumerate(self._parameter_list):
            acc = self._accumulators.get(id(p))
            if acc:
                for name, v in acc.items():
                    out[f"{name}_{i}"] = Tensor(v)
        if isinstance(self._learning_rate, LRScheduler):
            out["LR_Scheduler"] = self._learning_rate.state_dict()
        return out

    def set_state_dict(self, state):
        self._step_count = int(state.get("_step_count", 0))
        for i, p in enumerate(self._parameter_list):
            acc = {}
            for name in self._state_names:
                key = f"{name}_{i}"
                if key in state:
                    v = state[key]
                    acc[name] = v._value if isinstance(v, Tensor) else jnp.asarray(v)
            if acc:
                self._accumulators[id(p)] = acc
        if "LR_Scheduler" in state and isinstance(self._learning_rate, LRScheduler):
            self._learning_rate.set_state_dict(state["LR_Scheduler"])

    # -- update ------------------------------------------------------------
    def _update_one(self, param, grad, state, lr, step):
        """Pure function: returns (new_param, new_state). Override."""
        raise NotImplementedError

    def _batch_update(self, params, grads, states, lr, step):
        new_params, new_states = [], []
        for p, g, s in zip(params, grads, states):
            np_, ns = self._update_one(p, g, s, lr, step)
            new_params.append(np_)
            new_states.append(ns)
        return new_params, new_states

    def _get_jitted(self):
        if self._jitted_update is None:
            def fn(params, grads, states, lr, step):
                return self._batch_update(params, grads, states, lr, step)
            self._jitted_update = jax.jit(fn, donate_argnums=(0, 2))
        return self._jitted_update

    @no_grad()
    def step(self):
        params = [p for p in self._parameter_list
                  if p.grad is not None and p.trainable]
        if not params:
            self._step_count += 1
            return
        pgs = [(p, p.grad) for p in params]
        if self._grad_clip is not None:
            pgs = self._grad_clip(pgs)
        self._ensure_state(params)

        # host-offloaded params/moments stream to device for the update and
        # return to their host residency after (group_sharded offload=True)
        def _host_sharding(x):
            sh = getattr(x, "sharding", None)
            if getattr(sh, "memory_kind", None) in ("pinned_host",
                                                    "unpinned_host"):
                return sh
            return None

        def _to_device(x):
            sh = _host_sharding(x)
            return x if sh is None else jax.device_put(
                x, sh.with_memory_kind("device"))

        host_sh = [_host_sharding(p._value) for p, _ in pgs]
        p_vals = [_to_device(p._value) for p, _ in pgs]
        g_vals = [g._value.astype(p._value.dtype) for p, g in pgs]
        states = [jax.tree_util.tree_map(_to_device,
                                         self._accumulators[id(p)])
                  for p, _ in pgs]
        self._step_count += 1
        lr = jnp.asarray(self.get_lr(), jnp.float32)
        step = jnp.asarray(self._step_count, jnp.int32)
        new_p, new_s = self._get_jitted()(p_vals, g_vals, states, lr, step)
        for (p, _), np_, ns, hs in zip(pgs, new_p, new_s, host_sh):
            if hs is None:
                p._value = np_
                self._accumulators[id(p)] = ns
            else:
                # offloaded param: the update AND its optimizer moments
                # return to host residency (adam-offload semantics)
                p._value = jax.device_put(np_, hs)
                self._accumulators[id(p)] = jax.tree_util.tree_map(
                    lambda x: jax.device_put(
                        x, x.sharding.with_memory_kind(hs.memory_kind))
                    if hasattr(x, "sharding") else x,
                    ns)

    def minimize(self, loss, startup_program=None, parameters=None, no_grad_set=None):
        from .. import static as static_mod
        if static_mod._static_enabled():
            # static build: record the training hook; Executor.run replays
            # the captured graph, backprops, and steps (static/__init__.py)
            static_mod.default_main_program()._register_minimize(self, loss)
            return None, [(p, None) for p in self._parameter_list]
        loss.backward()
        self.step()
        return None, [(p, p.grad) for p in self._parameter_list]

    def clear_grad(self, set_to_zero=False):
        for p in self._parameter_list:
            p.clear_grad()

    clear_gradients = clear_grad

    def _append_optimize_op(self, *a, **k):
        raise NotImplementedError("static-graph path not used on TPU build")


class SGD(Optimizer):
    _state_names = ()

    def _update_one(self, param, grad, state, lr, step):
        if self._weight_decay:
            grad = grad + self._weight_decay * param
        return param - lr.astype(param.dtype) * grad, state


class Momentum(Optimizer):
    _state_names = ("velocity",)

    def __init__(self, learning_rate=0.001, momentum=0.9, parameters=None,
                 use_nesterov=False, weight_decay=None, grad_clip=None, name=None):
        super().__init__(learning_rate, parameters, weight_decay, grad_clip)
        self._momentum = momentum
        self._nesterov = use_nesterov

    def _update_one(self, param, grad, state, lr, step):
        if self._weight_decay:
            grad = grad + self._weight_decay * param
        v = self._momentum * state["velocity"] + grad
        if self._nesterov:
            upd = grad + self._momentum * v
        else:
            upd = v
        return param - lr.astype(param.dtype) * upd, {"velocity": v}


class Adam(Optimizer):
    _state_names = ("moment1", "moment2")

    def __init__(self, learning_rate=0.001, beta1=0.9, beta2=0.999,
                 epsilon=1e-08, parameters=None, weight_decay=None,
                 grad_clip=None, lazy_mode=False, multi_precision=False,
                 name=None, amsgrad=False):
        super().__init__(learning_rate, parameters, weight_decay, grad_clip)
        self._beta1 = beta1
        self._beta2 = beta2
        self._epsilon = epsilon

    def _update_one(self, param, grad, state, lr, step):
        if self._weight_decay:
            grad = grad + self._weight_decay * param
        f32 = jnp.float32
        g = grad.astype(f32)
        m = self._beta1 * state["moment1"].astype(f32) + (1 - self._beta1) * g
        v = self._beta2 * state["moment2"].astype(f32) + (1 - self._beta2) * g * g
        t = step.astype(f32)
        mhat = m / (1 - self._beta1 ** t)
        vhat = v / (1 - self._beta2 ** t)
        upd = lr * mhat / (jnp.sqrt(vhat) + self._epsilon)
        new_p = (param.astype(f32) - upd).astype(param.dtype)
        return new_p, {"moment1": m.astype(state["moment1"].dtype),
                       "moment2": v.astype(state["moment2"].dtype)}


class AdamW(Adam):
    """Decoupled weight decay (reference: optimizer/adamw.py)."""

    def __init__(self, learning_rate=0.001, beta1=0.9, beta2=0.999,
                 epsilon=1e-08, parameters=None, weight_decay=0.01,
                 lr_ratio=None, apply_decay_param_fun=None, grad_clip=None,
                 lazy_mode=False, multi_precision=False, name=None,
                 amsgrad=False):
        super().__init__(learning_rate, beta1, beta2, epsilon, parameters,
                         None, grad_clip)
        self._wd = float(weight_decay) if isinstance(weight_decay, (int, float)) else float(getattr(weight_decay, "_coeff", 0.01))
        self._apply_decay_param_fun = apply_decay_param_fun
        self._decay_mask = None

    @no_grad()
    def step(self):
        # build decay mask aligned with params (by name filter)
        if self._apply_decay_param_fun is not None and self._decay_mask is None:
            self._decay_mask = {
                id(p): bool(self._apply_decay_param_fun(p.name or str(i)))
                for i, p in enumerate(self._parameter_list)}
        super().step()

    def _update_one(self, param, grad, state, lr, step):
        f32 = jnp.float32
        g = grad.astype(f32)
        m = self._beta1 * state["moment1"].astype(f32) + (1 - self._beta1) * g
        v = self._beta2 * state["moment2"].astype(f32) + (1 - self._beta2) * g * g
        t = step.astype(f32)
        mhat = m / (1 - self._beta1 ** t)
        vhat = v / (1 - self._beta2 ** t)
        p32 = param.astype(f32)
        p32 = p32 * (1.0 - lr * self._wd)
        upd = lr * mhat / (jnp.sqrt(vhat) + self._epsilon)
        return (p32 - upd).astype(param.dtype), {
            "moment1": m.astype(state["moment1"].dtype),
            "moment2": v.astype(state["moment2"].dtype)}


class Adagrad(Optimizer):
    _state_names = ("moment",)

    def __init__(self, learning_rate, epsilon=1e-06, parameters=None,
                 weight_decay=None, grad_clip=None, initial_accumulator_value=0.0,
                 name=None):
        super().__init__(learning_rate, parameters, weight_decay, grad_clip)
        self._epsilon = epsilon
        self._init_acc = initial_accumulator_value

    def _update_one(self, param, grad, state, lr, step):
        if self._weight_decay:
            grad = grad + self._weight_decay * param
        mom = state["moment"] + grad * grad
        upd = lr.astype(param.dtype) * grad / (jnp.sqrt(mom) + self._epsilon)
        return param - upd, {"moment": mom}


class RMSProp(Optimizer):
    _state_names = ("mean_square", "mean_grad", "momentum")

    def __init__(self, learning_rate, rho=0.95, epsilon=1e-06, momentum=0.0,
                 centered=False, parameters=None, weight_decay=None,
                 grad_clip=None, name=None):
        super().__init__(learning_rate, parameters, weight_decay, grad_clip)
        self._rho = rho
        self._epsilon = epsilon
        self._momentum = momentum
        self._centered = centered

    def _update_one(self, param, grad, state, lr, step):
        if self._weight_decay:
            grad = grad + self._weight_decay * param
        ms = self._rho * state["mean_square"] + (1 - self._rho) * grad * grad
        if self._centered:
            mg = self._rho * state["mean_grad"] + (1 - self._rho) * grad
            denom = jnp.sqrt(ms - mg * mg + self._epsilon)
        else:
            mg = state["mean_grad"]
            denom = jnp.sqrt(ms + self._epsilon)
        mom = self._momentum * state["momentum"] + lr.astype(param.dtype) * grad / denom
        return param - mom, {"mean_square": ms, "mean_grad": mg, "momentum": mom}


class Adadelta(Optimizer):
    _state_names = ("avg_squared_grad", "avg_squared_update")

    def __init__(self, learning_rate=0.001, epsilon=1e-06, rho=0.95,
                 parameters=None, weight_decay=None, grad_clip=None, name=None):
        super().__init__(learning_rate, parameters, weight_decay, grad_clip)
        self._epsilon = epsilon
        self._rho = rho

    def _update_one(self, param, grad, state, lr, step):
        if self._weight_decay:
            grad = grad + self._weight_decay * param
        asg = self._rho * state["avg_squared_grad"] + (1 - self._rho) * grad * grad
        upd = grad * jnp.sqrt(state["avg_squared_update"] + self._epsilon) / jnp.sqrt(asg + self._epsilon)
        asu = self._rho * state["avg_squared_update"] + (1 - self._rho) * upd * upd
        return param - lr.astype(param.dtype) * upd, {
            "avg_squared_grad": asg, "avg_squared_update": asu}


class Adamax(Optimizer):
    _state_names = ("moment", "inf_norm")

    def __init__(self, learning_rate=0.001, beta1=0.9, beta2=0.999,
                 epsilon=1e-08, parameters=None, weight_decay=None,
                 grad_clip=None, name=None):
        super().__init__(learning_rate, parameters, weight_decay, grad_clip)
        self._beta1, self._beta2, self._epsilon = beta1, beta2, epsilon

    def _update_one(self, param, grad, state, lr, step):
        if self._weight_decay:
            grad = grad + self._weight_decay * param
        m = self._beta1 * state["moment"] + (1 - self._beta1) * grad
        u = jnp.maximum(self._beta2 * state["inf_norm"], jnp.abs(grad))
        t = step.astype(jnp.float32)
        lr_t = (lr / (1 - self._beta1 ** t)).astype(param.dtype)
        return param - lr_t * m / (u + self._epsilon), {"moment": m, "inf_norm": u}


class Lamb(Optimizer):
    """Layer-wise adaptive moments (reference: optimizer/lamb.py)."""

    _state_names = ("moment1", "moment2")

    def __init__(self, learning_rate=0.001, lamb_weight_decay=0.01, beta1=0.9,
                 beta2=0.999, epsilon=1e-06, parameters=None, grad_clip=None,
                 exclude_from_weight_decay_fn=None, name=None):
        super().__init__(learning_rate, parameters, None, grad_clip)
        self._beta1, self._beta2, self._epsilon = beta1, beta2, epsilon
        self._lamb_wd = lamb_weight_decay
        self._exclude_fn = exclude_from_weight_decay_fn

    def _update_one(self, param, grad, state, lr, step):
        f32 = jnp.float32
        g = grad.astype(f32)
        m = self._beta1 * state["moment1"].astype(f32) + (1 - self._beta1) * g
        v = self._beta2 * state["moment2"].astype(f32) + (1 - self._beta2) * g * g
        t = step.astype(f32)
        mhat = m / (1 - self._beta1 ** t)
        vhat = v / (1 - self._beta2 ** t)
        r = mhat / (jnp.sqrt(vhat) + self._epsilon) + self._lamb_wd * param.astype(f32)
        w_norm = jnp.linalg.norm(param.astype(f32).reshape(-1))
        r_norm = jnp.linalg.norm(r.reshape(-1))
        trust = jnp.where((w_norm > 0) & (r_norm > 0), w_norm / r_norm, 1.0)
        new_p = param.astype(f32) - lr * trust * r
        return new_p.astype(param.dtype), {
            "moment1": m.astype(state["moment1"].dtype),
            "moment2": v.astype(state["moment2"].dtype)}


class Rprop(Optimizer):
    """Resilient backprop (reference: optimizer/rprop.py) — per-element
    step sizes grown/shrunk by gradient sign agreement."""

    _state_names = ("prev_grad", "step_size")

    def __init__(self, learning_rate=0.001, learning_rate_range=(1e-5, 50.0),
                 parameters=None, etas=(0.5, 1.2), grad_clip=None,
                 name=None):
        super().__init__(learning_rate, parameters, None, grad_clip, name)
        self.lr_min, self.lr_max = learning_rate_range
        self.eta_minus, self.eta_plus = etas

    def _ensure_state(self, params):
        for p in params:
            if id(p) not in self._accumulators:
                self._accumulators[id(p)] = {
                    "prev_grad": jnp.zeros_like(p._value),
                    "step_size": jnp.full_like(p._value, self.get_lr()),
                }

    def _update_one(self, p, g, s, lr, step):
        sign = jnp.sign(g * s["prev_grad"])
        size = jnp.clip(
            jnp.where(sign > 0, s["step_size"] * self.eta_plus,
                      jnp.where(sign < 0, s["step_size"] * self.eta_minus,
                                s["step_size"])),
            self.lr_min, self.lr_max)
        g_eff = jnp.where(sign < 0, jnp.zeros_like(g), g)
        new_p = p - jnp.sign(g_eff) * size
        return new_p, {"prev_grad": g_eff, "step_size": size}


class ASGD(Optimizer):
    """Averaged SGD (reference: optimizer/asgd.py simplified — SGD step +
    running average of iterates available as the 'averaged' slot)."""

    _state_names = ("avg",)

    def _update_one(self, p, g, s, lr, step):
        wd = self._weight_decay
        if wd:
            g = g + wd * p
        new_p = p - lr * g
        t = jnp.maximum(step.astype(new_p.dtype), 1.0)
        avg = s["avg"] + (new_p - s["avg"]) / t
        return new_p, {"avg": avg}


class LBFGS(Optimizer):
    """Limited-memory BFGS with closure (reference: optimizer/lbfgs.py —
    step(closure) re-evaluates the loss; two-loop recursion over a
    history of (s, y) pairs; optional backtracking line search)."""

    def __init__(self, learning_rate=1.0, max_iter=20, max_eval=None,
                 tolerance_grad=1e-7, tolerance_change=1e-9,
                 history_size=100, line_search_fn=None, parameters=None,
                 weight_decay=None, grad_clip=None, name=None):
        super().__init__(learning_rate, parameters, weight_decay, grad_clip,
                         name)
        self.max_iter = int(max_iter)
        self.tolerance_grad = float(tolerance_grad)
        self.tolerance_change = float(tolerance_change)
        self.history_size = int(history_size)
        self.line_search_fn = line_search_fn
        self.max_eval = int(max_eval) if max_eval is not None else \
            self.max_iter * 5 // 4
        self._s_hist = []
        self._y_hist = []

    def _flat(self, vals):
        return jnp.concatenate([v.reshape(-1) for v in vals])

    def _unflat(self, flat):
        out, off = [], 0
        for p in self._parameter_list:
            n = int(np.prod(p.shape)) if p.shape else 1
            out.append(flat[off:off + n].reshape(p._value.shape))
            off += n
        return out

    def _gather_grad(self):
        return self._flat([
            (p.grad._value if p.grad is not None
             else jnp.zeros_like(p._value)).astype(jnp.float32)
            for p in self._parameter_list])

    def _direction(self, flat_grad):
        # two-loop recursion
        q = -flat_grad
        alphas = []
        for s, y in zip(reversed(self._s_hist), reversed(self._y_hist)):
            rho = 1.0 / jnp.maximum(jnp.vdot(y, s), 1e-10)
            a = rho * jnp.vdot(s, q)
            alphas.append((a, rho, s, y))
            q = q - a * y
        if self._y_hist:
            y, s = self._y_hist[-1], self._s_hist[-1]
            q = q * (jnp.vdot(s, y) / jnp.maximum(jnp.vdot(y, y), 1e-10))
        for a, rho, s, y in reversed(alphas):
            b = rho * jnp.vdot(y, q)
            q = q + (a - b) * s
        return q

    def step(self, closure):
        """closure(): zero grads, compute loss, backward, return loss.
        Closure evaluations are capped at max_eval (reference parity)."""
        evals = [0]
        user_closure = closure

        def closure():
            evals[0] += 1
            return user_closure()

        loss = closure()
        cur = float(loss)
        flat_grad = self._gather_grad()
        for _ in range(self.max_iter):
            if evals[0] >= self.max_eval:
                break
            if float(jnp.abs(flat_grad).max()) <= self.tolerance_grad:
                break
            d = self._direction(flat_grad)
            lr = self.get_lr()
            x0 = self._flat([p._value.astype(jnp.float32)
                             for p in self._parameter_list])
            if self.line_search_fn in ("strong_wolfe", "backtracking"):
                # the line search shares the eval budget (reserve one for
                # the post-step gradient evaluation below)
                budget = max(0, self.max_eval - evals[0] - 1)
                lr = self._backtrack(
                    closure, x0, d, cur, flat_grad, lr,
                    max_ls=min(10, budget),
                    curvature=self.line_search_fn == "strong_wolfe")
            self._assign(x0 + lr * d)
            new_loss = closure()
            new_flat = self._gather_grad()
            s = lr * d
            y = new_flat - flat_grad
            if float(jnp.vdot(y, s)) > 1e-10:
                self._s_hist.append(s)
                self._y_hist.append(y)
                if len(self._s_hist) > self.history_size:
                    self._s_hist.pop(0)
                    self._y_hist.pop(0)
            if abs(float(new_loss) - cur) < self.tolerance_change:
                cur = float(new_loss)
                flat_grad = new_flat
                break
            cur = float(new_loss)
            flat_grad = new_flat
        self._step_count += 1
        return cur

    def _backtrack(self, closure, x0, d, f0, g0, lr, c1=1e-4, c2=0.9,
                   shrink=0.5, max_ls=10, curvature=False):
        """Armijo backtracking; with curvature=True also enforces the
        (strong) Wolfe curvature condition |g1.d| <= c2 |g0.d| so accepted
        steps give y.s > 0 and the history stays well-conditioned."""
        gd = float(jnp.vdot(g0, d))
        for _ in range(max_ls):
            self._assign(x0 + lr * d)
            f = float(closure())
            if f <= f0 + c1 * lr * gd:
                if not curvature:
                    return lr
                g1d = float(jnp.vdot(self._gather_grad(), d))
                if abs(g1d) <= c2 * abs(gd):
                    return lr
                if g1d < 0:  # still descending: step further
                    lr /= shrink
                    continue
            lr *= shrink
        return lr

    def _assign(self, flat):
        for p, v in zip(self._parameter_list, self._unflat(flat)):
            p._value = v.astype(p._value.dtype)
