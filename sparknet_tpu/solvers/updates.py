"""Optimizer update rules with Caffe solver semantics.

Re-designs the 6-member solver family (ref:
caffe/src/caffe/solvers/{sgd,nesterov,adagrad,rmsprop,adadelta,adam}_solver.cpp)
plus AdamW (decoupled decay; no reference analog) as pure per-tensor
update functions over pytrees — the optax shape, but with
Caffe's exact formulations (e.g. SGD's V = mu*V + lr*g; W -= V, which folds
the LR *into* the momentum buffer, unlike optax's sgd).

Update-order parity with SGDSolver::ApplyUpdate (sgd_solver.cpp:102-117):
  clip_gradients (global L2, on raw grads) -> normalize (1/iter_size) ->
  regularize (L2/L1 with per-blob decay_mult) -> per-rule update with
  local_rate = rate * lr_mult.

The reference's libccaffe shim hardcoded SGD (ref: libccaffe/ccaffe.cpp:131,
making the other five unreachable from SparkNet!); here all six are
first-class.
"""

from __future__ import annotations

from typing import Callable, NamedTuple

import jax
import jax.numpy as jnp


class UpdateCtx(NamedTuple):
    rate: jnp.ndarray  # global lr for this iter
    lr_mult: float
    momentum: float
    momentum2: float  # adam beta2
    rms_decay: float
    delta: float  # numerical epsilon (adagrad/rmsprop/adadelta/adam)
    it: jnp.ndarray  # iteration (adam bias correction)
    decay: float = 0.0  # decoupled weight decay (adamw), decay_mult applied


# Each rule: (ctx, w, g, slots) -> (delta_w, new_slots).  ``slots`` is the
# per-parameter history list; W_new = w - delta_w is applied by the caller.


def _sgd(ctx, w, g, slots):
    """ref: sgd_solver.cpp ComputeUpdateValue — history folds in the lr."""
    (h,) = slots
    h = ctx.momentum * h + (ctx.rate * ctx.lr_mult) * g
    return h, [h]


def _nesterov(ctx, w, g, slots):
    """ref: nesterov_solver.cpp — update = (1+mu)*h_new - mu*h_old."""
    (h,) = slots
    h_new = ctx.momentum * h + (ctx.rate * ctx.lr_mult) * g
    return (1.0 + ctx.momentum) * h_new - ctx.momentum * h, [h_new]


def _adagrad(ctx, w, g, slots):
    (h,) = slots
    h = h + g * g
    return (ctx.rate * ctx.lr_mult) * g / (jnp.sqrt(h) + ctx.delta), [h]


def _rmsprop(ctx, w, g, slots):
    (h,) = slots
    h = ctx.rms_decay * h + (1.0 - ctx.rms_decay) * g * g
    return (ctx.rate * ctx.lr_mult) * g / (jnp.sqrt(h) + ctx.delta), [h]


def _adadelta(ctx, w, g, slots):
    """ref: adadelta_solver.cpp — momentum is the squared-accumulator decay;
    two histories (grad^2 and update^2); local_rate still applies."""
    h, h2 = slots
    mu = ctx.momentum
    h = mu * h + (1.0 - mu) * g * g
    val = g * jnp.sqrt((h2 + ctx.delta) / (h + ctx.delta))
    h2 = mu * h2 + (1.0 - mu) * val * val
    return (ctx.rate * ctx.lr_mult) * val, [h, h2]


def _adam(ctx, w, g, slots):
    """ref: adam_solver.cpp — beta1=momentum, beta2=momentum2, eps=delta;
    correction uses t = iter+1."""
    m, v = slots
    b1, b2 = ctx.momentum, ctx.momentum2
    t = jnp.asarray(ctx.it, jnp.float32) + 1.0
    m = b1 * m + (1.0 - b1) * g
    v = b2 * v + (1.0 - b2) * g * g
    correction = jnp.sqrt(1.0 - jnp.power(b2, t)) / (1.0 - jnp.power(b1, t))
    return (ctx.rate * ctx.lr_mult) * correction * m / (jnp.sqrt(v) + ctx.delta), [m, v]


def _adamw(ctx, w, g, slots):
    """Adam with DECOUPLED weight decay (Loshchilov & Hutter 2019, as
    torch.optim.AdamW applies it): the decay term never enters the
    moments, ``w -= lr * (m_hat / (sqrt(v_hat) + eps) + wd * w)``.  Both
    moments are bias-corrected before the division, so ``eps`` sits
    beside sqrt(v_hat), not (as in Caffe's ``_adam``) beside sqrt(v)."""
    m, v = slots
    b1, b2 = ctx.momentum, ctx.momentum2
    t = jnp.asarray(ctx.it, jnp.float32) + 1.0
    m = b1 * m + (1.0 - b1) * g
    v = b2 * v + (1.0 - b2) * g * g
    m_hat = m / (1.0 - jnp.power(b1, t))
    v_hat = v / (1.0 - jnp.power(b2, t))
    step = m_hat / (jnp.sqrt(v_hat) + ctx.delta) + ctx.decay * w
    return (ctx.rate * ctx.lr_mult) * step, [m, v]


OPTIMIZERS: dict[str, tuple[Callable, int]] = {
    # name -> (rule, number of history slots)
    "SGD": (_sgd, 1),
    "Nesterov": (_nesterov, 1),
    "AdaGrad": (_adagrad, 1),
    "RMSProp": (_rmsprop, 1),
    "AdaDelta": (_adadelta, 2),
    "Adam": (_adam, 2),
    "AdamW": (_adamw, 2),
}


def init_slots(solver_type: str, params) -> dict:
    """Zero history slots shaped like each param blob
    (ref: SGDSolver::PresolveHistory / history_)."""
    _, n_slots = OPTIMIZERS[solver_type]
    return jax.tree_util.tree_map(
        lambda p: [jnp.zeros_like(p) for _ in range(n_slots)],
        params,
        is_leaf=lambda x: isinstance(x, jax.Array),
    )


def global_grad_norm(grads) -> jnp.ndarray:
    leaves = jax.tree_util.tree_leaves(grads)
    return jnp.sqrt(sum(jnp.sum(jnp.square(g.astype(jnp.float32))) for g in leaves))


def apply_update(
    cfg,
    params: dict[str, list[jax.Array]],
    grads: dict[str, list[jax.Array]],
    slots: dict[str, list[list[jax.Array]]],
    specs: dict[str, list],
    rate: jnp.ndarray,
    it: jnp.ndarray,
):
    """One full Caffe-ordered update. cfg is a SolverConfig; specs maps
    layer -> [ParamSpec per blob]. Returns (new_params, new_slots)."""
    rule, _ = OPTIMIZERS[cfg.solver_type]
    # AdamW takes its decay inside the rule, outside the moments; every
    # other rule gets Caffe's Regularize (the decay added to the gradient)
    decoupled = cfg.solver_type == "AdamW"

    # 1. clip on raw accumulated grads (ref: ClipGradients, sgd_solver.cpp:81-100)
    if cfg.clip_gradients > 0:
        norm = global_grad_norm(grads)
        scale = jnp.where(norm > cfg.clip_gradients, cfg.clip_gradients / norm, 1.0)
        grads = jax.tree_util.tree_map(lambda g: g * scale, grads)

    new_params: dict[str, list] = {}
    new_slots: dict[str, list] = {}
    for lname, plist in params.items():
        out_p, out_s = [], []
        for i, w in enumerate(plist):
            g = grads[lname][i].astype(w.dtype)
            spec = specs[lname][i]
            # 2. normalize (ref: Normalize — 1/iter_size)
            if cfg.iter_size > 1:
                g = g / cfg.iter_size
            # 3. regularize (ref: Regularize — L2: g += wd*W; L1: g += wd*sign(W))
            wd = cfg.weight_decay * spec.decay_mult
            if wd != 0.0 and not decoupled:
                if cfg.regularization_type == "L1":
                    g = g + wd * jnp.sign(w)
                else:
                    g = g + wd * w
            ctx = UpdateCtx(
                rate=rate,
                lr_mult=spec.lr_mult,
                momentum=cfg.momentum,
                momentum2=cfg.momentum2,
                rms_decay=cfg.rms_decay,
                delta=cfg.delta,
                it=it,
                decay=wd if decoupled else 0.0,
            )
            dw, s = rule(ctx, w, g, slots[lname][i])
            out_p.append(w - dw.astype(w.dtype))
            # ctx.rate is an f32 scalar, so rule math promotes a low-
            # precision history slot to f32; cast back so slot dtype is
            # a fixpoint (pure-bf16 training stores slots in bf16, and a
            # drifting dtype breaks the lax.scan carry contract).
            out_s.append([x.astype(w.dtype) for x in s])
        new_params[lname] = out_p
        new_slots[lname] = out_s
    return new_params, new_slots
