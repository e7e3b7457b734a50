"""Plain reference of synchronous FedAvg rounds: local SGD, parameter averaging.

Written from McMahan et al. (arXiv:1602.05629), the FedPAC paper's first-order
baseline, in straightforward ``jax.numpy`` at float32 and the highest
matrix-product precision, one client at a time.  It imports nothing of the
system under test.  The mix's ``sgd`` block sets ``momentum`` and
``weight_decay`` (both 0 where it leaves them out).

One round, for a cohort of S clients that each take K local steps:

  client i   starts from the server's x with a zero momentum m.  Step k:
               G = grad of the loss on the step's batch
               m = momentum m + G + weight_decay x      (m = G at 0 and 0)
               x = x - lr m
             and uploads Delta_i = x_K - x_start; it has no preconditioner,
             so no Theta.
  server     x     = x + server_lr * mean_i Delta_i
             g_G   = -(sum_i Delta_i / S) / (K lr)
             Theta: none
FedAvg neither reads g_G nor aligns, but the server keeps g_G from the
uploads as for every algorithm, so it is compared as the server's view of the
first gradient.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np


def leaf_paths(tree) -> list:
    """[(path tuple, leaf)] with dict keys and list indices as path parts."""
    flat, _ = jax.tree_util.tree_flatten_with_path(tree)
    out = []
    for path, leaf in flat:
        parts = tuple(getattr(p, "key", getattr(p, "idx", None))
                      for p in path)
        out.append((parts, leaf))
    return out


def path_name(path: tuple) -> str:
    return "/".join(str(p) for p in path)


def _norms(leaves):
    return [jnp.sqrt(jnp.sum(jnp.square(a.astype(jnp.float32))))
            for a in leaves]


def _local_round_fn(loss_fn, treedef, hp):
    sgd = hp["sgd"]
    mu = sgd.get("momentum", 0.0)
    wd = sgd.get("weight_decay", 0.0)
    lr, k_steps = hp["lr"], hp["local_steps"]

    def model_loss(leaves, batch):
        return loss_fn(jax.tree_util.tree_unflatten(treedef, leaves), batch)

    def step(carry, batch):
        x, m = carry
        loss, grads = jax.value_and_grad(model_loss)(x, batch)
        grads = [g.astype(jnp.float32) for g in grads]
        m = [mu * mi + g + wd * xi for mi, g, xi in zip(m, grads, x)]
        new_x = [xi - lr * mi for xi, mi in zip(x, m)]
        return (new_x, m), (loss, jnp.stack(_norms(grads)))

    def local_round(x0, batches):
        m0 = [jnp.zeros(a.shape) for a in x0]
        (x_end, _), (losses, gnorms) = jax.lax.scan(
            step, (x0, m0), batches, length=k_steps)
        return ([a - b for a, b in zip(x_end, x0)], jnp.mean(losses),
                gnorms[0])

    return jax.jit(local_round)


def run(params0, loss_fn, preconditioned, rounds, hp) -> dict:
    """Follow ``len(rounds)`` rounds from ``params0`` on the cohorts'
    batches (``rounds[r]``: pytree of (S, K, ...) host arrays);
    ``preconditioned`` is not read: SGD treats every leaf alike.

    Returns the readings ``correct`` compares, each by leaf name:
      loss        each round's mean client loss
      grad        |g_G| after round 1
      theta       empty: FedAvg keeps no Theta
      delta       |x_R - x_0| after the last round
      first_grad  |G| of round 1's first local step, mean over the cohort
    """
    del preconditioned
    named = leaf_paths(params0)
    names = [path_name(p) for p, _ in named]
    treedef = jax.tree_util.tree_structure(params0)
    x = [jnp.asarray(a, jnp.float32) for _, a in named]
    x_start = list(x)
    k_steps, lr = hp["local_steps"], hp["lr"]
    out = {"loss": [], "theta": {}}
    with jax.default_matmul_precision("highest"):
        local = _local_round_fn(loss_fn, treedef, hp)
        for r, batches in enumerate(rounds):
            s = jax.tree.leaves(batches)[0].shape[0]
            d_sum = [jnp.zeros(a.shape) for a in x]
            losses, first = [], []
            for i in range(s):
                batch_i = jax.tree.map(lambda a: jnp.asarray(a[i]), batches)
                delta, loss_i, g0 = local(x, batch_i)
                d_sum = [a + b for a, b in zip(d_sum, delta)]
                losses.append(loss_i)
                first.append(g0)
            x = [a + hp["server_lr"] * d / s for a, d in zip(x, d_sum)]
            out["loss"].append(float(np.mean(jax.device_get(losses))))
            if r == 0:
                g_glob = [-(d / s) / (k_steps * lr) for d in d_sum]
                out["grad"] = dict(zip(names, map(float, _norms(g_glob))))
                mean_first = np.mean(np.stack(jax.device_get(first)), axis=0)
                out["first_grad"] = dict(zip(names, map(float, mean_first)))
    out["delta"] = dict(zip(names, map(float, _norms(
        [a - b for a, b in zip(x, x_start)]))))
    return out
