"""Optimizers.

Reference: ``python/paddle/fluid/optimizer.py:38-1208`` — Optimizer base
(minimize = append_backward + regularize/clip + per-param optimize ops) and
SGD/Momentum/Adagrad/Adam/Adamax/DecayedAdagrad/Adadelta/RMSProp/Ftrl/
ModelAverage, each executed as graph ops
(``paddle/fluid/operators/*_op.cc`` sgd_op, momentum_op, adam_op, ...).

TPU-native: each optimizer is a pure per-leaf update rule; ``minimize`` wires
jax.value_and_grad + regularization + clip + the update into ONE jittable
train-step function — the whole thing compiles to a single XLA executable
with fused update kernels (no per-param op dispatch). Optimizer slot
variables (moments etc.) live in an explicit state pytree, sharded alongside
params under pjit.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Callable, Dict, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp

from paddle_tpu import lr_scheduler as lrs
from paddle_tpu import regularizer as reg_mod
from paddle_tpu.core.enforce import enforce
from paddle_tpu.framework import Model, ParamInfo, Variables


class OptState(NamedTuple):
    step: jax.Array  # int32 global step
    slots: Dict[str, Dict[str, jax.Array]]  # slot name → per-param dict


class StepOutput(NamedTuple):
    variables: Variables
    opt_state: OptState
    loss: jax.Array
    outputs: Any
    # set (scalar bool array) when flags().check_nan_inf was on at trace
    # time: in-step isfinite over loss+grads — the compiled-in analogue of
    # the reference's per-op FLAGS_check_nan_inf (operator.cc:725-737)
    finite: Any = None


class Optimizer:
    """Base optimizer. Subclasses define slot init + per-leaf update."""

    def __init__(self, learning_rate=0.001, regularization=None, grad_clip=None, name: Optional[str] = None):
        self.scheduler = lrs.resolve(learning_rate)
        self.regularization = regularization
        self.grad_clip = grad_clip
        self.name = name or type(self).__name__

    # -- subclass interface -------------------------------------------------
    def _slot_names(self) -> Tuple[str, ...]:
        return ()

    def _init_slot(self, slot: str, param: jax.Array) -> jax.Array:
        return jnp.zeros_like(param, dtype=jnp.float32)

    def _update(self, param, grad, lr, slots: Dict[str, jax.Array], step) -> Tuple[jax.Array, Dict[str, jax.Array]]:
        raise NotImplementedError

    # -- state --------------------------------------------------------------
    def create_state(self, params: Dict[str, jax.Array]) -> OptState:
        slots = {
            s: {k: self._init_slot(s, p) for k, p in params.items()}
            for s in self._slot_names()
        }
        return OptState(step=jnp.zeros((), jnp.int32), slots=slots)

    # -- functional application --------------------------------------------
    def apply_gradients(
        self,
        params: Dict[str, jax.Array],
        grads: Dict[str, jax.Array],
        opt_state: OptState,
        param_info: Optional[Dict[str, ParamInfo]] = None,
    ) -> Tuple[Dict[str, jax.Array], OptState]:
        grads = reg_mod.apply_regularization(params, grads, self.regularization, param_info)
        if self.grad_clip is not None:
            grads = self.grad_clip(grads)
        lr = self.scheduler(opt_state.step)
        new_params = dict(params)
        new_slots = {s: dict(d) for s, d in opt_state.slots.items()}
        # name-aware updates (Lamb's decay/trust exclusions) declare a
        # `name` parameter on _update; plain optimizers keep the short form
        import inspect

        accepts_name = "name" in inspect.signature(self._update).parameters
        for name, p in params.items():
            info = param_info.get(name) if param_info else None
            if info is not None and not info.trainable:
                continue
            g = grads[name].astype(jnp.float32)
            p_lr = lr * (info.learning_rate if info is not None else 1.0)
            slot_view = {s: new_slots[s][name] for s in self._slot_names()}
            kw = {"name": name} if accepts_name else {}
            new_p, slot_out = self._update(p.astype(jnp.float32), g, p_lr, slot_view, opt_state.step, **kw)
            new_params[name] = new_p.astype(p.dtype)
            for s, v in slot_out.items():
                new_slots[s][name] = v
        return new_params, OptState(step=opt_state.step + 1, slots=new_slots)

    def minimize(
        self,
        model: Model,
        loss_index: int = 0,
        axis_name: Optional[str] = None,
        accum_steps: int = 1,
    ) -> Callable:
        """Build the full train-step function (the analogue of
        fluid ``optimizer.minimize(avg_cost)`` + Executor.run of the
        resulting program):

            step_fn(variables, opt_state, *batch, rng=None)
                -> StepOutput(variables, opt_state, loss, outputs)

        When ``axis_name`` is given, gradients (and BN stat updates) are
        mean-reduced across that mesh axis — replacing the reference's
        AllReduceOpHandle + ScaleLossGradOpHandle pair
        (``details/all_reduce_op_handle.cc:48``,
        ``scale_loss_grad_op_handle.cc:63``).

        ``accum_steps > 1`` splits each batch arg's leading dim into that
        many microbatches and accumulates gradients over a ``lax.scan``
        before the single optimizer update — activation memory then scales
        with the microbatch, letting a fixed HBM train a larger effective
        batch. Equivalent to the full-batch step for mean losses; model
        state (BN stats) threads through microbatches sequentially.
        ``outputs`` carries a leading [accum_steps] dim.
        """
        param_info = model.param_info

        def grad_of(params, state, batch, rng):
            def loss_fn(p):
                out, new_state = model.apply(Variables(p, state), *batch, rng=rng, is_train=True)
                loss = out[loss_index] if isinstance(out, (tuple, list)) else out
                return jnp.mean(loss.astype(jnp.float32)), (new_state, out)

            return jax.value_and_grad(loss_fn, has_aux=True)(params)

        def finish(params, state, opt_state, loss, new_state, grads, outputs):
            if axis_name is not None:
                grads = jax.lax.pmean(grads, axis_name)
                loss = jax.lax.pmean(loss, axis_name)
                new_state = jax.tree_util.tree_map(
                    lambda a, b: jax.lax.pmean(a, axis_name) if a is not b else a,
                    new_state,
                    state,
                ) if new_state else new_state
            info = param_info or model.param_info
            with jax.named_scope("optimizer_update"):
                new_params, new_opt = self.apply_gradients(params, grads, opt_state, info)
            finite = None
            from paddle_tpu.core import config as _cfg

            if _cfg.flags().check_nan_inf:
                finite = jnp.isfinite(loss)
                for g in jax.tree_util.tree_leaves(grads):
                    finite = jnp.logical_and(finite, jnp.all(jnp.isfinite(g)))
                # a non-finite step returns the state it was handed: a
                # caller that donated that state (Trainer, DataParallel) has
                # no older copy to fall back on, so what the step returns is
                # always what is carried. The select fuses into the update,
                # which reads the old leaf anyway.
                new_params, new_state, new_opt = jax.tree_util.tree_map(
                    lambda new, old: jnp.where(finite, new, old.astype(new.dtype)),
                    (new_params, new_state, new_opt), (params, state, opt_state))
            return StepOutput(
                Variables(new_params, new_state), new_opt, loss, outputs, finite
            )

        def step_fn(variables: Variables, opt_state: OptState, *batch, rng=None):
            params, state = variables.params, variables.state
            (loss, (new_state, outputs)), grads = grad_of(params, state, batch, rng)
            return finish(params, state, opt_state, loss, new_state, grads, outputs)

        if accum_steps == 1:
            return step_fn

        enforce(accum_steps > 1, f"accum_steps must be >= 1, got {accum_steps}")

        def accum_step_fn(variables: Variables, opt_state: OptState, *batch, rng=None):
            params, state = variables.params, variables.state
            n = accum_steps
            micro = []
            for b in batch:
                b = jnp.asarray(b)
                enforce(
                    b.shape[0] % n == 0,
                    f"batch dim {b.shape[0]} not divisible by accum_steps {n}",
                )
                micro.append(b.reshape((n, b.shape[0] // n) + b.shape[1:]))
            keys = jax.random.split(rng, n) if rng is not None else None

            def body(carry, xs):
                st, gacc, lacc = carry
                if rng is not None:
                    mb, key = xs
                else:
                    mb, key = xs, None
                (loss, (new_st, out)), grads = grad_of(params, st, mb, key)
                gacc = jax.tree_util.tree_map(jnp.add, gacc, grads)
                return (new_st, gacc, lacc + loss), out

            init = (
                state,
                jax.tree_util.tree_map(lambda p: jnp.zeros(p.shape, jnp.float32), params),
                jnp.zeros((), jnp.float32),
            )
            xs = (tuple(micro), keys) if rng is not None else tuple(micro)
            (new_state, gsum, lsum), outputs = jax.lax.scan(body, init, xs)
            grads = jax.tree_util.tree_map(
                lambda g, p: (g / n).astype(p.dtype), gsum, params
            )
            return finish(params, state, opt_state, lsum / n, new_state, grads, outputs)

        return accum_step_fn


class SGD(Optimizer):
    """Plain SGD (reference ``sgd_op.cc``)."""

    def _update(self, p, g, lr, slots, step):
        return p - lr * g, {}


class Momentum(Optimizer):
    """Heavy-ball / Nesterov momentum (reference ``momentum_op.cc``)."""

    def __init__(self, learning_rate, momentum: float = 0.9, use_nesterov: bool = False, **kw):
        super().__init__(learning_rate, **kw)
        self.momentum = momentum
        self.use_nesterov = use_nesterov

    def _slot_names(self):
        return ("velocity",)

    def _update(self, p, g, lr, slots, step):
        v = self.momentum * slots["velocity"] + g
        if self.use_nesterov:
            new_p = p - lr * (g + self.momentum * v)
        else:
            new_p = p - lr * v
        return new_p, {"velocity": v}


class LARS(Optimizer):
    """Layer-wise Adaptive Rate Scaling with momentum: per-parameter
    effective lr = lr * ||p|| / (||g|| + wd*||p||) — the reference exposed
    this as the ``append_LARS`` lr rewrite
    (``layers/learning_rate_scheduler.py:310``); here it is a first-class
    optimizer so it composes with schedulers/clipping like the rest."""

    def __init__(self, learning_rate, momentum: float = 0.9, lars_weight_decay: float = 0.0005, epsilon: float = 1e-9, **kw):
        super().__init__(learning_rate, **kw)
        self.momentum = momentum
        self.lars_weight_decay = lars_weight_decay
        self.epsilon = epsilon

    def _slot_names(self):
        return ("velocity",)

    def _update(self, p, g, lr, slots, step):
        p_norm = jnp.sqrt(jnp.sum(jnp.square(p)))
        g_norm = jnp.sqrt(jnp.sum(jnp.square(g)))
        local_lr = lr * p_norm / (g_norm + self.lars_weight_decay * p_norm + self.epsilon)
        v = self.momentum * slots["velocity"] + local_lr * (
            g + self.lars_weight_decay * p
        )
        return p - v, {"velocity": v}


class Adagrad(Optimizer):
    def __init__(self, learning_rate, epsilon: float = 1e-6, initial_accumulator_value: float = 0.0, **kw):
        super().__init__(learning_rate, **kw)
        self.epsilon = epsilon
        self.init_acc = initial_accumulator_value

    def _slot_names(self):
        return ("moment",)

    def _init_slot(self, slot, param):
        return jnp.full_like(param, self.init_acc, dtype=jnp.float32)

    def _update(self, p, g, lr, slots, step):
        m = slots["moment"] + jnp.square(g)
        return p - lr * g / (jnp.sqrt(m) + self.epsilon), {"moment": m}


class Adam(Optimizer):
    """Adam with the reference's bias-correction-in-lr formulation
    (``adam_op.cc``: lr * sqrt(1-b2^t)/(1-b1^t))."""

    def __init__(self, learning_rate=0.001, beta1: float = 0.9, beta2: float = 0.999, epsilon: float = 1e-8, lazy_mode: bool = False, **kw):
        super().__init__(learning_rate, **kw)
        self.beta1, self.beta2, self.epsilon = beta1, beta2, epsilon

    def _slot_names(self):
        return ("moment1", "moment2")

    def _update(self, p, g, lr, slots, step):
        t = (step + 1).astype(jnp.float32)
        m1 = self.beta1 * slots["moment1"] + (1 - self.beta1) * g
        m2 = self.beta2 * slots["moment2"] + (1 - self.beta2) * jnp.square(g)
        lr_t = lr * jnp.sqrt(1 - self.beta2 ** t) / (1 - self.beta1 ** t)
        new_p = p - lr_t * m1 / (jnp.sqrt(m2) + self.epsilon)
        return new_p, {"moment1": m1, "moment2": m2}


def _name_excluded(name: str, tokens: Tuple[str, ...]) -> bool:
    """Decay-exclusion matching: tokens without '/' match the LEAF name
    EXACTLY (so a trainable 'logit_scale' weight is not silently swept up by
    the 'scale' token); tokens containing '/' match anywhere in the full
    scoped name for whole-scope exclusions."""
    leaf = name.rsplit("/", 1)[-1]
    for tok in tokens:
        if "/" in tok:
            if tok in name:
                return True
        elif tok == leaf:
            return True
    return False


class AdamW(Adam):
    """Adam with DECOUPLED weight decay (Loshchilov & Hutter) — the decay
    is applied to the parameter directly, scaled by the schedule, not fed
    through the moments like an L2 regularizer. Post-parity extension (the
    reference era predates AdamW); the standard for transformer training.
    ``exclude_from_decay`` controls which params skip decay: tokens
    without '/' match the leaf parameter name (so the defaults exempt
    biases and norm scales), tokens with '/' match anywhere in the scoped
    name (whole-scope exclusion)."""

    def __init__(
        self, learning_rate=0.001, beta1: float = 0.9, beta2: float = 0.999,
        epsilon: float = 1e-8, weight_decay: float = 0.01,
        exclude_from_decay: Tuple[str, ...] = ("b", "bias", "scale", "norm"),
        **kw,
    ):
        super().__init__(learning_rate, beta1, beta2, epsilon, **kw)
        self.weight_decay = weight_decay
        self.exclude_from_decay = tuple(exclude_from_decay)

    def _decay_excluded(self, name: str) -> bool:
        return _name_excluded(name, self.exclude_from_decay)

    def apply_gradients(self, params, grads, opt_state, param_info=None):
        lr = self.scheduler(opt_state.step)  # pre-increment step, as base does
        new_params, new_state = super().apply_gradients(params, grads, opt_state, param_info)
        if not self.weight_decay:
            return new_params, new_state
        # decoupled decay as a post-pass against the PRE-update params:
        # p_{t+1} = p_t - lr*adam(g) - lr*wd*p_t
        for name, p in params.items():
            info = param_info.get(name) if param_info else None
            if info is not None and not info.trainable:
                continue
            if self._decay_excluded(name):
                continue
            p_lr = lr * (info.learning_rate if info is not None else 1.0)
            new_params[name] = (
                new_params[name].astype(jnp.float32)
                - p_lr * self.weight_decay * p.astype(jnp.float32)
            ).astype(p.dtype)
        return new_params, new_state


class Lamb(Optimizer):
    """LAMB (You et al.) — layerwise adaptive moments for very large batch
    training: the Adam update direction is rescaled per layer by
    ||p|| / ||update||. Post-parity extension; pairs with
    ``minimize(accum_steps=...)`` and data-parallel meshes."""

    def __init__(
        self, learning_rate=0.001, beta1: float = 0.9, beta2: float = 0.999,
        epsilon: float = 1e-6, weight_decay: float = 0.01,
        exclude_from_decay: Tuple[str, ...] = ("b", "bias", "scale", "norm"),
        **kw,
    ):
        super().__init__(learning_rate, **kw)
        self.beta1, self.beta2, self.epsilon = beta1, beta2, epsilon
        self.weight_decay = weight_decay
        self.exclude_from_decay = tuple(exclude_from_decay)

    def _slot_names(self):
        return ("moment1", "moment2")

    def _update(self, p, g, lr, slots, step, name=""):
        t = (step + 1).astype(jnp.float32)
        m1 = self.beta1 * slots["moment1"] + (1 - self.beta1) * g
        m2 = self.beta2 * slots["moment2"] + (1 - self.beta2) * jnp.square(g)
        m1_hat = m1 / (1 - self.beta1 ** t)
        m2_hat = m2 / (1 - self.beta2 ** t)
        # biases/norm params: no decay and trust=1 (LAMB paper / BERT
        # reference masks) — they're tiny-norm and would be crushed
        excluded = _name_excluded(name, self.exclude_from_decay)
        wd = 0.0 if excluded else self.weight_decay
        update = m1_hat / (jnp.sqrt(m2_hat) + self.epsilon) + wd * p
        if excluded:
            return p - lr * update, {"moment1": m1, "moment2": m2}
        p_norm = jnp.linalg.norm(p)
        u_norm = jnp.linalg.norm(update)
        trust = jnp.where(
            (p_norm > 0) & (u_norm > 0), p_norm / jnp.maximum(u_norm, 1e-12), 1.0
        )
        return p - lr * trust * update, {"moment1": m1, "moment2": m2}


class Adamax(Optimizer):
    def __init__(self, learning_rate=0.001, beta1: float = 0.9, beta2: float = 0.999, epsilon: float = 1e-8, **kw):
        super().__init__(learning_rate, **kw)
        self.beta1, self.beta2, self.epsilon = beta1, beta2, epsilon

    def _slot_names(self):
        return ("moment", "inf_norm")

    def _update(self, p, g, lr, slots, step):
        t = (step + 1).astype(jnp.float32)
        m = self.beta1 * slots["moment"] + (1 - self.beta1) * g
        u = jnp.maximum(self.beta2 * slots["inf_norm"], jnp.abs(g))
        lr_t = lr / (1 - self.beta1 ** t)
        new_p = p - lr_t * m / (u + self.epsilon)
        return new_p, {"moment": m, "inf_norm": u}


class DecayedAdagrad(Optimizer):
    def __init__(self, learning_rate, decay: float = 0.95, epsilon: float = 1e-6, **kw):
        super().__init__(learning_rate, **kw)
        self.decay, self.epsilon = decay, epsilon

    def _slot_names(self):
        return ("moment",)

    def _update(self, p, g, lr, slots, step):
        m = self.decay * slots["moment"] + (1 - self.decay) * jnp.square(g)
        return p - lr * g / (jnp.sqrt(m) + self.epsilon), {"moment": m}


class Adadelta(Optimizer):
    def __init__(self, learning_rate=1.0, epsilon: float = 1e-6, rho: float = 0.95, **kw):
        super().__init__(learning_rate, **kw)
        self.epsilon, self.rho = epsilon, rho

    def _slot_names(self):
        return ("avg_squared_grad", "avg_squared_update")

    def _update(self, p, g, lr, slots, step):
        sg = self.rho * slots["avg_squared_grad"] + (1 - self.rho) * jnp.square(g)
        update = g * jnp.sqrt(slots["avg_squared_update"] + self.epsilon) / jnp.sqrt(sg + self.epsilon)
        su = self.rho * slots["avg_squared_update"] + (1 - self.rho) * jnp.square(update)
        return p - lr * update, {"avg_squared_grad": sg, "avg_squared_update": su}


class RMSProp(Optimizer):
    def __init__(self, learning_rate, rho: float = 0.95, epsilon: float = 1e-6, momentum: float = 0.0, centered: bool = False, **kw):
        super().__init__(learning_rate, **kw)
        self.rho, self.epsilon, self.momentum, self.centered = rho, epsilon, momentum, centered

    def _slot_names(self):
        return ("mean_square", "moment", "mean_grad") if self.centered else ("mean_square", "moment")

    def _update(self, p, g, lr, slots, step):
        ms = self.rho * slots["mean_square"] + (1 - self.rho) * jnp.square(g)
        out = {"mean_square": ms}
        if self.centered:
            mg = self.rho * slots["mean_grad"] + (1 - self.rho) * g
            denom = jnp.sqrt(ms - jnp.square(mg) + self.epsilon)
            out["mean_grad"] = mg
        else:
            denom = jnp.sqrt(ms + self.epsilon)
        mom = self.momentum * slots["moment"] + lr * g / denom
        out["moment"] = mom
        return p - mom, out


class Ftrl(Optimizer):
    """FTRL-proximal (reference ``ftrl_op.cc``)."""

    def __init__(self, learning_rate, l1: float = 0.0, l2: float = 0.0, lr_power: float = -0.5, **kw):
        super().__init__(learning_rate, **kw)
        self.l1, self.l2, self.lr_power = l1, l2, lr_power

    def _slot_names(self):
        return ("squared", "linear")

    def _update(self, p, g, lr, slots, step):
        sq_new = slots["squared"] + jnp.square(g)
        sigma = (jnp.power(sq_new, -self.lr_power) - jnp.power(jnp.maximum(slots["squared"], 1e-12), -self.lr_power)) / lr
        lin = slots["linear"] + g - sigma * p
        quad = jnp.power(sq_new, -self.lr_power) / lr + 2 * self.l2
        pre = jnp.clip(lin, -self.l1, self.l1) - lin
        new_p = jnp.where(jnp.abs(lin) > self.l1, pre / quad, jnp.zeros_like(p))
        return new_p, {"squared": sq_new, "linear": lin}


class ModelAverage:
    """Polyak-style parameter averaging over a sliding window (reference
    ``optimizer.py`` ModelAverage: accumulates param sums, applies the
    average for eval, restores after). Functional version: feed every new
    params pytree to ``update``; ``average()`` yields eval params."""

    def __init__(self, average_window_rate: float = 0.15, min_average_window: int = 10000, max_average_window: int = 10000):
        self.rate = average_window_rate
        self.min_window = min_average_window
        self.max_window = max_average_window

    def create_state(self, params):
        return {
            "sum": jax.tree_util.tree_map(lambda p: jnp.zeros_like(p, jnp.float32), params),
            "count": jnp.zeros((), jnp.int32),
            "updates": jnp.zeros((), jnp.int32),
        }

    def _window(self, num_updates):
        # reference semantics (optimizer.py ModelAverage): window grows with
        # training length at average_window_rate, clamped to [min, max]
        w = jnp.floor(num_updates.astype(jnp.float32) * self.rate)
        return jnp.clip(w, self.min_window, self.max_window).astype(jnp.int32)

    def update(self, state, params):
        updates = state["updates"] + 1
        window = self._window(updates)
        decay = jnp.where(
            state["count"] >= window, 1.0 - 1.0 / window.astype(jnp.float32), 1.0
        )
        new_sum = jax.tree_util.tree_map(lambda s, p: s * decay + p.astype(jnp.float32), state["sum"], params)
        new_count = jnp.minimum(state["count"] + 1, window)
        return {"sum": new_sum, "count": new_count, "updates": updates}

    def average(self, state, like_params):
        c = jnp.maximum(state["count"], 1).astype(jnp.float32)
        return jax.tree_util.tree_map(lambda s, p: (s / c).astype(p.dtype), state["sum"], like_params)


# fluid-style aliases
SGDOptimizer = SGD
MomentumOptimizer = Momentum
AdagradOptimizer = Adagrad
AdamOptimizer = Adam
AdamaxOptimizer = Adamax
DecayedAdagradOptimizer = DecayedAdagrad
AdadeltaOptimizer = Adadelta
RMSPropOptimizer = RMSProp
FtrlOptimizer = Ftrl
