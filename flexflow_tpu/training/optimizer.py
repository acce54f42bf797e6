"""Optimizers: SGD (momentum/nesterov) and Adam.

Capability parity with reference src/runtime/optimizer.cc (610 LoC) +
optimizer_kernel.cu: the reference has two sync modes (parameter-server
reduction vs NCCL allreduce, include/flexflow/optimizer.h:36,77). On TPU both
collapse into one SPMD update: gradients of replicated params are psum-reduced
by GSPMD automatically inside the jitted train step, so the update below is
written as a pure per-shard function of (param, grad, state).
"""

from __future__ import annotations

from typing import Any, Dict

import jax
import jax.numpy as jnp

from flexflow_tpu.ffconst import ParameterSyncType


class Optimizer:
    sync_type = ParameterSyncType.NCCL

    def __init__(self, ffmodel=None):
        self.ffmodel = ffmodel

    def init_state(self, params) -> Any:
        raise NotImplementedError

    def update_step(self, params, grads, state):
        """Returns (new_params, new_state). Pure; called under jit."""
        raise NotImplementedError

    # reference API parity (flexflow_cffi.py SGDOptimizer.set_lr etc.).
    # The live rate is part of the (device-side) optimizer state so that a
    # scheduler can change it between steps without re-tracing the jitted
    # train step.
    def set_learning_rate(self, lr: float):
        self.lr = lr
        m = self.ffmodel
        if m is not None and getattr(m, "opt_state", None) is not None \
                and "lr" in m.opt_state:
            # placed like the value it replaces, or the next step retraces
            m.opt_state = dict(m.opt_state, lr=jax.device_put(
                jnp.asarray(lr, jnp.float32), m.opt_state["lr"].sharding))


class SGDOptimizer(Optimizer):
    """SGD with momentum/nesterov/weight-decay
    (reference optimizer.h:36 SGDOptimizer)."""

    def __init__(self, ffmodel=None, lr: float = 0.01, momentum: float = 0.0,
                 nesterov: bool = False, weight_decay: float = 0.0):
        super().__init__(ffmodel)
        self.lr = lr
        self.momentum = momentum
        self.nesterov = nesterov
        self.weight_decay = weight_decay

    def init_state(self, params):
        state = {"step": jnp.zeros((), jnp.int32),
                 "lr": jnp.asarray(self.lr, jnp.float32)}
        if self.momentum != 0.0:
            state["velocity"] = jax.tree.map(jnp.zeros_like, params)
        return state

    def update_step(self, params, grads, state):
        lr, mu, wd = state["lr"], self.momentum, self.weight_decay

        if wd > 0.0:
            grads = jax.tree.map(lambda g, p: g + wd * p, grads, params)
        if mu == 0.0:
            new_params = jax.tree.map(lambda p, g: p - lr * g, params, grads)
            return new_params, {"step": state["step"] + 1, "lr": lr}
        new_vel = jax.tree.map(lambda v, g: mu * v + g, state["velocity"], grads)
        if self.nesterov:
            upd = jax.tree.map(lambda g, v: g + mu * v, grads, new_vel)
        else:
            upd = new_vel
        new_params = jax.tree.map(lambda p, u: p - lr * u, params, upd)
        return new_params, {"step": state["step"] + 1, "lr": lr,
                            "velocity": new_vel}


class AdamOptimizer(Optimizer):
    """Adam (reference optimizer.h:77 AdamOptimizer — note the reference decays
    alpha_t by beta powers each next(), reproduced here via the step count)."""

    def __init__(self, ffmodel=None, alpha: float = 0.001, beta1: float = 0.9,
                 beta2: float = 0.999, weight_decay: float = 0.0,
                 epsilon: float = 1e-8):
        super().__init__(ffmodel)
        self.lr = alpha
        self.beta1 = beta1
        self.beta2 = beta2
        self.weight_decay = weight_decay
        self.epsilon = epsilon

    def init_state(self, params):
        return {
            "step": jnp.zeros((), jnp.int32),
            "lr": jnp.asarray(self.lr, jnp.float32),
            "m": jax.tree.map(jnp.zeros_like, params),
            "v": jax.tree.map(jnp.zeros_like, params),
        }

    def update_step(self, params, grads, state):
        b1, b2, eps, wd = self.beta1, self.beta2, self.epsilon, self.weight_decay
        step = state["step"] + 1
        if wd > 0.0:
            grads = jax.tree.map(lambda g, p: g + wd * p, grads, params)
        new_m = jax.tree.map(lambda m, g: b1 * m + (1 - b1) * g, state["m"], grads)
        new_v = jax.tree.map(lambda v, g: b2 * v + (1 - b2) * jnp.square(g),
                             state["v"], grads)
        t = step.astype(jnp.float32)
        alpha_t = state["lr"] * jnp.sqrt(1 - b2 ** t) / (1 - b1 ** t)
        new_params = jax.tree.map(
            lambda p, m, v: p - alpha_t * m / (jnp.sqrt(v) + eps),
            params, new_m, new_v)
        return new_params, {"step": step, "lr": state["lr"],
                            "m": new_m, "v": new_v}
