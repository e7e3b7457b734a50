"""Plain reference of synchronous FedPAC rounds with SOAP local steps.

Written from the paper's Algorithm 2 (alignment and correction) with SOAP
(Vyas et al., arXiv:2409.11321) as the local optimizer, in straightforward
``jax.numpy`` at float32 and the highest matrix-product precision, one client
at a time.  It imports nothing of the system under test.

One round, for a cohort of S clients that each take K local steps:

  client i   starts from the server's x, with SOAP's curvature factors L, R
             set to the global reference Theta (alignment; zero in round 1),
             eigenbases Q_L = Q_R = I and zero moments.  Step k (t = k + 1):
               G        = grad of the loss on the step's batch
               L        = b2 L + (1 - b2) G G^T,   R = b2 R + (1 - b2) G^T G
               Q_L, Q_R = Q of QR(L Q_L), QR(R Q_R)       when k % f == 0
               N        = Q_L^T G Q_R
               M        = b1 M + (1 - b1) N,       V = b2 V + (1 - b2) N^2
               D        = Q_L [M / (1 - b1^t)] / [sqrt(V / (1 - b2^t)) + eps] Q_R^T
             for the hidden matrices (batched over a leading layer axis);
             other weights take bias-corrected Adam (adam_b1, adam_b2, eps).
               x        = x - lr [(1 - beta) D + beta g_G]          (correction)
             and uploads Delta_i = x_K - x_start and Theta_i = {L, R}, the
             latter through the Theta codec (``qblock``: int8 in blocks of
             ``qblock_size`` with one float32 scale, max|x| / 127, each).
  server     x     = x + server_lr * mean_i Delta_i
             g_G   = -(sum_i Delta_i / S) / (K lr)
             Theta = sum_i decode(Theta_i) / S
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


def qblock_roundtrip(x, block: int):
    """Encode and decode one leaf through the blockwise int8 codec."""
    flat = x.reshape(-1).astype(jnp.float32)
    n = flat.size
    nb = -(-n // block)
    flat = jnp.pad(flat, (0, nb * block - n)).reshape(nb, block)
    scale = jnp.maximum(jnp.max(jnp.abs(flat), axis=1, keepdims=True)
                        / 127.0, 1e-12)
    q = jnp.clip(jnp.round(flat / scale), -127, 127)
    return (q * scale).reshape(-1)[:n].reshape(x.shape)


def _norms(leaves):
    return [jnp.sqrt(jnp.sum(jnp.square(a.astype(jnp.float32))))
            for a in leaves]


def _local_round_fn(loss_fn, treedef, flags, hp):
    s = hp["soap"]
    b1, b2, eps, freq = s["b1"], s["b2"], s["eps"], s["precond_freq"]
    a1, a2 = s["adam_b1"], s["adam_b2"]
    lr, beta, k_steps = hp["lr"], hp["beta"], hp["local_steps"]

    def direction(g, st, k, flag):
        t = (k + 1).astype(jnp.float32)
        if not flag:
            m = a1 * st["m"] + (1 - a1) * g
            v = a2 * st["v"] + (1 - a2) * g * g
            d = (m / (1 - a1 ** t)) / (jnp.sqrt(v / (1 - a2 ** t)) + eps)
            return d, {"m": m, "v": v}
        lmat = b2 * st["L"] + (1 - b2) * jnp.einsum("...ik,...jk->...ij", g, g)
        rmat = b2 * st["R"] + (1 - b2) * jnp.einsum("...ki,...kj->...ij", g, g)
        ql, qr = jax.lax.cond(
            k % freq == 0,
            lambda: (jnp.linalg.qr(lmat @ st["QL"])[0],
                     jnp.linalg.qr(rmat @ st["QR"])[0]),
            lambda: (st["QL"], st["QR"]))
        rot = jnp.swapaxes(ql, -1, -2) @ g @ qr
        m = b1 * st["M"] + (1 - b1) * rot
        v = b2 * st["V"] + (1 - b2) * rot * rot
        n = (m / (1 - b1 ** t)) / (jnp.sqrt(v / (1 - b2 ** t)) + eps)
        d = ql @ n @ jnp.swapaxes(qr, -1, -2)
        return d, {"L": lmat, "R": rmat, "QL": ql, "QR": qr, "M": m, "V": v}

    def group_of(shapes):
        """Indices of the preconditioned matrices, grouped by shape: a
        group is stepped as one stack (the arithmetic stays per matrix;
        stacking only shrinks the program)."""
        out = {}
        for i, f in enumerate(flags):
            if f:
                out.setdefault(shapes[i], []).append(i)
        return list(out.values())

    def local_round(x0, theta, g_glob, batches):
        mats = group_of([a.shape for a in x0])
        adam = [i for i, f in enumerate(flags) if not f]
        state = {"adam": [{"m": jnp.zeros(x0[i].shape),
                           "v": jnp.zeros(x0[i].shape)} for i in adam],
                 "mat": []}
        for idx in mats:
            m, n = x0[idx[0]].shape[-2:]
            lead = (len(idx),) + x0[idx[0]].shape[:-2]
            state["mat"].append({
                "L": jnp.stack([theta[i][0] for i in idx]),
                "R": jnp.stack([theta[i][1] for i in idx]),
                "QL": jnp.broadcast_to(jnp.eye(m), (*lead, m, m)),
                "QR": jnp.broadcast_to(jnp.eye(n), (*lead, n, n)),
                "M": jnp.zeros((*lead, m, n)), "V": jnp.zeros((*lead, m, n))})

        def model_loss(leaves, batch):
            return loss_fn(jax.tree_util.tree_unflatten(treedef, leaves),
                           batch)

        def step(carry, batch):
            x, st, k = carry
            loss, grads = jax.value_and_grad(model_loss)(x, batch)
            grads = [g.astype(jnp.float32) for g in grads]
            d = [None] * len(x)
            new_st = {"adam": [], "mat": []}
            for i, sti in zip(adam, st["adam"]):
                d[i], s_new = direction(grads[i], sti, k, False)
                new_st["adam"].append(s_new)
            for idx, sti in zip(mats, st["mat"]):
                dg, s_new = direction(jnp.stack([grads[i] for i in idx]),
                                      sti, k, True)
                for j, i in enumerate(idx):
                    d[i] = dg[j]
                new_st["mat"].append(s_new)
            new_x = [xi - lr * ((1 - beta) * di + beta * gg)
                     for xi, di, gg in zip(x, d, g_glob)]
            return (new_x, new_st, k + 1), (loss, jnp.stack(_norms(grads)))

        (x_end, st_end, _), (losses, gnorms) = jax.lax.scan(
            step, (x0, state, jnp.int32(0)), batches, length=k_steps)
        delta = [a - b for a, b in zip(x_end, x0)]
        theta_out = [None] * len(x0)
        for idx, st_g in zip(mats, st_end["mat"]):
            for j, i in enumerate(idx):
                theta_out[i] = (st_g["L"][j], st_g["R"][j])
        return delta, theta_out, jnp.mean(losses), gnorms[0]

    return jax.jit(local_round)


def run(params0, loss_fn, preconditioned, rounds, hp) -> dict:
    """Follow ``len(rounds)`` rounds from ``params0`` on the cohorts'
    batches (``rounds[r]``: pytree of (S, K, ...) host arrays).

    Returns the readings ``correct`` compares, each by leaf name:
      loss        each round's mean client loss
      grad        |g_G| after round 1 (the global direction the correction
                  reads: the server's view of the first gradient)
      theta       |L|, |R| of the global Theta after round 1
      delta       |x_R - x_0| after the last round
      first_grad  |G| of round 1's first local step, mean over the cohort
    """
    named = leaf_paths(params0)
    names = [path_name(p) for p, _ in named]
    flags = [bool(preconditioned(p)) for p, _ in named]
    treedef = jax.tree_util.tree_structure(params0)
    x = [jnp.asarray(a, jnp.float32) for _, a in named]
    x_start = list(x)
    g_glob = [jnp.zeros(a.shape) for a in x]
    theta = [(jnp.zeros(a.shape[:-2] + (a.shape[-2],) * 2),
              jnp.zeros(a.shape[:-2] + (a.shape[-1],) * 2)) if f else None
             for a, f in zip(x, flags)]
    k_steps, lr = hp["local_steps"], hp["lr"]
    codec = hp["theta_codec"]
    out = {"loss": []}
    with jax.default_matmul_precision("highest"):
        local = _local_round_fn(loss_fn, treedef, flags, hp)
        roundtrip = jax.jit(lambda a: qblock_roundtrip(a, hp["qblock_size"]))
        for r, batches in enumerate(rounds):
            s = jax.tree.leaves(batches)[0].shape[0]
            d_sum = [jnp.zeros(a.shape) for a in x]
            th_sum = [None if t is None else (jnp.zeros(t[0].shape),
                                              jnp.zeros(t[1].shape))
                      for t in theta]
            losses, first = [], []
            for i in range(s):
                batch_i = jax.tree.map(lambda a: jnp.asarray(a[i]), batches)
                delta, th_i, loss_i, g0 = local(x, theta, g_glob, batch_i)
                d_sum = [a + b for a, b in zip(d_sum, delta)]
                for j, t in enumerate(th_i):
                    if t is None:
                        continue
                    if codec == "qblock":
                        t = (roundtrip(t[0]), roundtrip(t[1]))
                    th_sum[j] = (th_sum[j][0] + t[0], th_sum[j][1] + t[1])
                losses.append(loss_i)
                first.append(g0)
            x = [a + hp["server_lr"] * d / s for a, d in zip(x, d_sum)]
            g_glob = [-(d / s) / (k_steps * lr) for d in d_sum]
            theta = [None if t is None else (t[0] / s, t[1] / s)
                     for t in th_sum]
            out["loss"].append(float(np.mean(jax.device_get(losses))))
            if r == 0:
                out["grad"] = dict(zip(names, map(float, _norms(g_glob))))
                out["theta"] = {}
                for name, t in zip(names, theta):
                    if t is not None:
                        out["theta"][name + "/L"] = float(_norms([t[0]])[0])
                        out["theta"][name + "/R"] = float(_norms([t[1]])[0])
                mean_first = np.mean(np.stack(jax.device_get(first)), axis=0)
                out["first_grad"] = dict(zip(names, map(float, mean_first)))
    out["delta"] = dict(zip(names, map(float, _norms(
        [a - b for a, b in zip(x, x_start)]))))
    return out

