"""Paddle-style optimizer classes over the functional core.

Reference: ``python/paddle/optimizer/__init__.py`` (SGD, Momentum, Adam,
AdamW, Adamax, Adagrad, Adadelta, RMSProp, Lamb) and
``python/paddle/fluid/optimizer.py`` (LarsMomentum ``:1603``,
Lamb ``:2960``). Usage is functional:

    opt = AdamW(learning_rate=3e-4, weight_decay=0.1)
    state = opt.init(model)
    updates, state = opt.update(grads, state, model)
    model = apply_updates(model, updates)

or in one shot ``model, state = opt.apply_gradients(model, grads, state)``.
"""

from __future__ import annotations

from typing import Any, Callable

import jax.numpy as jnp

from paddle_tpu.core.module import apply_updates
from paddle_tpu.optimizer import transform as T

__all__ = ["Optimizer", "SGD", "Momentum", "Adam", "AdamW", "Adamax",
           "Adagrad", "Adadelta", "RMSProp", "Lamb", "LarsMomentum",
           "Ftrl", "Dpsgd", "ExponentialMovingAverage"]


def _as_schedule(lr) -> Callable:
    if callable(lr):
        return lr
    return lambda step: jnp.asarray(lr, jnp.float32)


class Optimizer:
    """Wraps a transformation chain; subclasses define ``_build``."""

    _applies_own_lr = False   # FTRL-style rules embed lr in the update

    def __init__(self, learning_rate=0.001, *, grad_clip=None,
                 weight_decay: float = 0.0, multi_precision: bool = True,
                 **kwargs):
        self.learning_rate = learning_rate
        self.grad_clip = grad_clip
        # paddle.regularizer.L1Decay/L2Decay objects: their transform
        # joins the gradient before moment accumulation (reference
        # regularizer semantics); plain floats keep the per-class handling
        reg_transform = None
        if hasattr(weight_decay, "transform"):
            reg_transform = weight_decay.transform()
            weight_decay = 0.0
        self.weight_decay = float(weight_decay)
        self.multi_precision = multi_precision  # moments always fp32 here
        transforms = []
        if grad_clip is not None:
            transforms.append(grad_clip if isinstance(
                grad_clip, T.GradientTransformation) else grad_clip.transform())
        if reg_transform is not None:
            transforms.append(reg_transform)
        transforms.extend(self._build(**kwargs))
        if not self._applies_own_lr:
            transforms.append(
                T.scale_by_schedule(_as_schedule(learning_rate)))
        self._tx = T.chain(*transforms)

    def _build(self, **kwargs):  # pragma: no cover - abstract
        raise NotImplementedError

    def init(self, params) -> Any:
        return self._tx.init(params)

    def update(self, grads, state, params=None):
        return self._tx.update(grads, state, params)

    def apply_gradients(self, params, grads, state):
        updates, state = self._tx.update(grads, state, params)
        return apply_updates(params, updates), state


class SGD(Optimizer):
    def _build(self):
        out = []
        if self.weight_decay:
            out.append(T.add_decayed_weights(self.weight_decay))
        return out


class Momentum(Optimizer):
    def __init__(self, learning_rate=0.001, momentum: float = 0.9,
                 use_nesterov: bool = False, **kwargs):
        self._momentum, self._nesterov = momentum, use_nesterov
        super().__init__(learning_rate, **kwargs)

    def _build(self):
        out = []
        if self.weight_decay:
            out.append(T.add_decayed_weights(self.weight_decay))
        out.append(T.trace(self._momentum, self._nesterov))
        return out


class Adam(Optimizer):
    def __init__(self, learning_rate=0.001, beta1: float = 0.9,
                 beta2: float = 0.999, epsilon: float = 1e-8, **kwargs):
        self._b1, self._b2, self._eps = beta1, beta2, epsilon
        super().__init__(learning_rate, **kwargs)

    def _build(self):
        out = []
        if self.weight_decay:
            # L2 regularization: wd*p joins the *gradient* before moment
            # accumulation (reference Adam semantics; AdamW decouples it)
            out.append(T.add_decayed_weights(self.weight_decay))
        out.append(T.scale_by_adam(self._b1, self._b2, self._eps))
        return out


class AdamW(Optimizer):
    """Decoupled weight decay (reference ``python/paddle/optimizer/adamw.py``).
    ``apply_decay_param_fun``/mask: decay only where mask is True (the
    reference excludes LayerNorm/bias via that callback).

    Kernel note: inside a jitted train step XLA fuses this pure-jnp
    update chain into one elementwise kernel per parameter, so no custom
    kernel is dispatched here."""

    def __init__(self, learning_rate=0.001, beta1: float = 0.9,
                 beta2: float = 0.999, epsilon: float = 1e-8,
                 weight_decay: float = 0.01, decay_mask=None, **kwargs):
        self._b1, self._b2, self._eps = beta1, beta2, epsilon
        self._decay_mask = decay_mask
        super().__init__(learning_rate, weight_decay=weight_decay, **kwargs)

    def _build(self):
        out = [T.scale_by_adam(self._b1, self._b2, self._eps)]
        if self.weight_decay:
            out.append(T.add_decayed_weights(self.weight_decay,
                                             self._decay_mask))
        return out


class Adamax(Optimizer):
    def __init__(self, learning_rate=0.001, beta1: float = 0.9,
                 beta2: float = 0.999, epsilon: float = 1e-8, **kwargs):
        self._b1, self._b2, self._eps = beta1, beta2, epsilon
        super().__init__(learning_rate, **kwargs)

    def _build(self):
        return [T.scale_by_adamax(self._b1, self._b2, self._eps)]


class Adagrad(Optimizer):
    def __init__(self, learning_rate=0.001, epsilon: float = 1e-6,
                 initial_accumulator_value: float = 0.0, **kwargs):
        self._eps, self._init_acc = epsilon, initial_accumulator_value
        super().__init__(learning_rate, **kwargs)

    def _build(self):
        return [T.scale_by_adagrad(self._eps, self._init_acc)]


class Adadelta(Optimizer):
    def __init__(self, learning_rate=1.0, rho: float = 0.95,
                 epsilon: float = 1e-6, **kwargs):
        self._rho, self._eps = rho, epsilon
        super().__init__(learning_rate, **kwargs)

    def _build(self):
        return [T.scale_by_adadelta(self._rho, self._eps)]


class RMSProp(Optimizer):
    def __init__(self, learning_rate=0.001, rho: float = 0.95,
                 epsilon: float = 1e-6, momentum: float = 0.0,
                 centered: bool = False, **kwargs):
        self._rho, self._eps = rho, epsilon
        self._momentum, self._centered = momentum, centered
        super().__init__(learning_rate, **kwargs)

    def _build(self):
        return [T.scale_by_rms(self._rho, self._eps, self._momentum,
                               self._centered)]


class Lamb(Optimizer):
    """Layer-adaptive large-batch optimizer
    (reference ``fluid/optimizer.py:2960`` LambOptimizer)."""

    def __init__(self, learning_rate=0.001, lamb_weight_decay: float = 0.01,
                 beta1: float = 0.9, beta2: float = 0.999,
                 epsilon: float = 1e-6, **kwargs):
        self._b1, self._b2, self._eps = beta1, beta2, epsilon
        self._lamb_wd = lamb_weight_decay
        super().__init__(learning_rate, **kwargs)

    def _build(self):
        out = [T.scale_by_adam(self._b1, self._b2, self._eps)]
        if self._lamb_wd:
            out.append(T.add_decayed_weights(self._lamb_wd))
        out.append(T.scale_by_lamb_trust())
        return out


class LarsMomentum(Optimizer):
    """LARS (reference ``fluid/optimizer.py:1603`` LarsMomentumOptimizer,
    CUDA kernel ``optimizers/lars_momentum_op.cu``)."""

    def __init__(self, learning_rate=0.001, momentum: float = 0.9,
                 lars_coeff: float = 0.001, lars_weight_decay: float = 0.0005,
                 **kwargs):
        self._momentum = momentum
        self._coeff = lars_coeff
        self._lars_wd = lars_weight_decay
        super().__init__(learning_rate, **kwargs)

    def _build(self):
        out = []
        if self._lars_wd:
            out.append(T.add_decayed_weights(self._lars_wd))
        out.append(T.scale_by_lars_trust(self._coeff))
        out.append(T.trace(self._momentum))
        return out


class Ftrl(Optimizer):
    """FTRL-proximal (reference ``fluid/optimizer.py`` FtrlOptimizer +
    ``operators/optimizers/ftrl_op.h``): the closed-form proximal update
    embeds the learning rate, so no trailing lr scale is chained."""

    _applies_own_lr = True

    def __init__(self, learning_rate=0.001, l1: float = 0.0,
                 l2: float = 0.0, lr_power: float = -0.5, **kwargs):
        self._l1, self._l2, self._lrp = l1, l2, lr_power
        super().__init__(learning_rate, **kwargs)

    def _build(self):
        return [T.scale_by_ftrl(_as_schedule(self.learning_rate),
                                self._l1, self._l2, self._lrp)]


class Dpsgd(Optimizer):
    """Differentially-private SGD (reference ``fluid/optimizer.py``
    DpsgdOptimizer + ``operators/optimizers/dpsgd_op.h``): global-norm
    clip then Gaussian noise scaled by (clip, sigma, batch_size)."""

    def __init__(self, learning_rate=0.001, clip: float = 10.0,
                 batch_size: int = 16, sigma: float = 1.0, seed: int = 0,
                 **kwargs):
        self._dp = (clip, batch_size, sigma, seed)
        super().__init__(learning_rate, **kwargs)

    def _build(self):
        clip, bs, sigma, seed = self._dp
        return [T.scale_by_dpsgd(clip, bs, sigma, seed)]


class ExponentialMovingAverage:
    """EMA of model parameters for evaluation (reference
    ``fluid/optimizer.py:3441`` ExponentialMovingAverage: shadow vars
    updated each step with a thresholded decay; apply()/restore() swap
    the shadow values in for eval).

    Functional form: the EMA is explicit state; ``apply`` returns an
    EMA-weighted copy of the model instead of mutating scopes::

        ema = ExponentialMovingAverage(0.999)
        ema_state = ema.init(model)
        ...
        ema_state = ema.update(ema_state, state.model)   # each step
        eval_model = ema.apply(ema_state, state.model)
    """

    def __init__(self, decay: float = 0.999,
                 thres_steps: bool = True):
        self.decay = float(decay)
        self.thres_steps = thres_steps

    def init(self, model):
        import jax

        shadow = jax.tree_util.tree_map(
            lambda p: jnp.asarray(p, jnp.float32) if hasattr(p, "dtype")
            else p, model)
        return {"shadow": shadow, "count": jnp.zeros((), jnp.int32)}

    def update(self, state, model):
        import jax

        count = state["count"] + 1
        if self.thres_steps:
            # reference thresholds decay = min(decay, (1+t)/(10+t))
            d = jnp.minimum(self.decay,
                            (1.0 + count) / (10.0 + count))
        else:
            d = jnp.asarray(self.decay)
        shadow = jax.tree_util.tree_map(
            lambda s, p: d * s + (1.0 - d) * p.astype(jnp.float32)
            if hasattr(p, "dtype") else s,
            state["shadow"], model)
        return {"shadow": shadow, "count": count}

    def apply(self, state, model):
        """Model with EMA parameter values (dtype preserved)."""
        import jax

        return jax.tree_util.tree_map(
            lambda p, s: s.astype(p.dtype) if hasattr(p, "dtype") else p,
            model, state["shadow"])
