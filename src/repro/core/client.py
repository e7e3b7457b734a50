"""Client-side local training: K preconditioned steps with optional
FedPAC correction (Eq. 9) — the shared engine for FedSOA and FedPAC.

All of this is jit/vmap-friendly: one client's round is a ``lax.scan`` over K
steps; the cohort is a ``vmap`` over the client axis (sharded over the mesh's
"data"/"pod" axes by the launcher).
"""
from __future__ import annotations

import dataclasses
from typing import Any, Callable, Optional

import jax
import jax.numpy as jnp

from repro.optim.api import LocalOptimizer


@dataclasses.dataclass(frozen=True)
class LocalRunConfig:
    lr: float
    local_steps: int           # K
    beta: float = 0.0          # correction strength (Eq. 9); 0 => no correction
    hessian_freq: int = 10     # Sophia's f_h
    align: bool = True         # warm-start Theta from the global reference

    def __post_init__(self):
        # validate eagerly: hessian_freq=0 would only surface as a cryptic
        # `k % 0` ZeroDivisionError deep inside the jitted scan below
        if self.local_steps < 1:
            raise ValueError(
                f"local_steps must be >= 1, got {self.local_steps}")
        if self.hessian_freq < 1:
            raise ValueError(
                f"hessian_freq must be >= 1 (step k refreshes the Hutchinson "
                f"estimate when k % hessian_freq == 0), got "
                f"{self.hessian_freq}")


def hutchinson_estimate(loss_fn, params, batch, key):
    """u * (H u) with Rademacher u (Pearlmutter HVP via jvp-of-grad)."""
    leaves, treedef = jax.tree.flatten(params)
    keys = jax.random.split(key, len(leaves))
    u = jax.tree.unflatten(
        treedef,
        [jax.random.rademacher(k, l.shape).astype(jnp.float32)
         for k, l in zip(keys, leaves)])
    g_fn = lambda p: jax.grad(loss_fn)(p, batch)
    _, hvp = jax.jvp(g_fn, (params,), (jax.tree.map(
        lambda uu, p: uu.astype(p.dtype), u, params),))
    return jax.tree.map(lambda uu, hh: uu * hh.astype(jnp.float32), u, hvp)


def client_round(
    loss_fn: Callable,
    opt: LocalOptimizer,
    run: LocalRunConfig,
    x0,
    theta,            # global preconditioner reference (or None / zeros-like)
    g_global,         # estimated global direction g_G^r (params-like)
    batches,          # pytree with leading (K, ...) axis
    rng,
    beta=None,        # runtime override (drift-adaptive beta); None -> run.beta
):
    """One client's round. Returns (delta_x, theta_final, mean_loss)."""
    beta = run.beta if beta is None else beta
    opt_state = opt.init(x0)
    if run.align and theta is not None:
        opt_state = opt.set_precond(opt_state, theta)

    def step(carry, inp):
        x, st, k = carry
        batch, key = inp
        with jax.named_scope("local_model"):
            loss, grads = jax.value_and_grad(loss_fn)(x, batch)
        extras = None
        if opt.needs_hessian:
            gate = (k % run.hessian_freq) == 0
            with jax.named_scope("precond_refresh"):
                est = jax.lax.cond(
                    gate,
                    lambda: hutchinson_estimate(loss_fn, x, batch, key),
                    lambda: jax.tree.map(
                        lambda p: jnp.zeros(p.shape, jnp.float32), x),
                )
            extras = {"h_est": est, "h_gate": gate}
        with jax.named_scope("local_precond"):
            direction, st = opt.update(grads, st, x, k, extras)
        # Eq. 9: x <- x - lr [ (1-beta) P_Theta(g) + beta g_G ]
        def mix(d, gg, p):
            upd = (1.0 - beta) * d + beta * gg
            return (p.astype(jnp.float32) - run.lr * upd).astype(p.dtype)
        with jax.named_scope("local_correction"):
            x = jax.tree.map(mix, direction, g_global, x)
        return (x, st, k + 1), loss

    keys = jax.random.split(rng, run.local_steps)
    (x_final, opt_state, _), losses = jax.lax.scan(
        step, (x0, opt_state, jnp.int32(0)), (batches, keys))
    delta = jax.tree.map(lambda a, b: (a.astype(jnp.float32)
                                       - b.astype(jnp.float32)), x_final, x0)
    return delta, opt.get_precond(opt_state), jnp.mean(losses)
