"""SOAP (Alg. 4/5): Shampoo-style Kronecker factors L = EMA[G G^T],
R = EMA[G^T G]; eigenbasis (Q_L, Q_R) refreshed by one QR power-iteration every
``precond_freq`` steps; AdamW run in the rotated basis.

Theta = {L, R} (the curvature statistics the paper aligns; Q is re-derived
from the aggregated factors after alignment — averaging orthogonal bases
directly would leave the Stiefel manifold).

Matrices with a dimension above ``max_precond_dim`` go one-sided (identity on
that side), matching the official SOAP treatment of huge layers.  3-D expert
tensors are batched matrices (vmap over the expert dim).  Non-matrix leaves
fall back to AdamW.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

from repro.kernels.householder_qr import ops as qr_ops
from repro.optim.api import LocalOptimizer, matrix_mask, as_matrix
from repro.utils import hw


def _tree_unzip(tree, n):
    is_leaf = lambda x: isinstance(x, tuple) and len(x) == n
    return tuple(jax.tree.map(lambda t: t[i], tree, is_leaf=is_leaf)
                 for i in range(n))


def _eig_refresh(p_mat, q, method: str = "qr"):
    """Eigenvectors(P, Q): one power iteration + orthogonalization.

    method="qr"  — the paper's Alg. 4 (QR decomposition): the blocked
                   Householder QR of ``kernels.householder_qr`` on the TPU,
                   XLA's QR elsewhere (``householder_qr.ops.route``);
    method="ns"  — Newton–Schulz orthogonalization of P@Q: pure matmuls,
                   MXU-aligned (beyond-paper TPU adaptation; QR lowers poorly
                   on the systolic array at large m).
    """
    s = p_mat @ q
    if method == "ns":
        from repro.kernels.ns_ortho import ref as ns_ref
        flat = s.reshape(-1, s.shape[-2], s.shape[-1]) if s.ndim > 2 else s
        out = (jax.vmap(ns_ref.newton_schulz)(flat)
               if flat.ndim == 3 else ns_ref.newton_schulz(flat))
        return out.reshape(s.shape)
    return qr_ops.qr_q(s)


def _rot(g, ql, qr, inverse=False):
    """Rotate into (or out of) the eigenbasis; None side = identity."""
    if ql is not None:
        g = jnp.einsum("...ij,...ik->...jk", ql, g) if not inverse else \
            jnp.einsum("...ij,...jk->...ik", ql, g)
    if qr is not None:
        g = jnp.einsum("...ij,...jk->...ik", g, qr) if not inverse else \
            jnp.einsum("...ik,...jk->...ij", g, qr)
    return g


def make(b1: float = 0.95, b2: float = 0.95, eps: float = 1e-8,
         precond_freq: int = 10, max_precond_dim: int = 8192,
         weight_decay: float = 0.0, state_dtype=jnp.float32,
         adam_b1: float = 0.9, adam_b2: float = 0.999,
         eig_method: str = "qr") -> LocalOptimizer:
    sd = state_dtype

    def _leaf_state(p, is_mat):
        if not is_mat:
            return None
        pm, _ = as_matrix(p)
        m, n = pm.shape[-2], pm.shape[-1]
        batch = pm.shape[:-2]
        st = {}
        if m <= max_precond_dim:
            st["L"] = jnp.zeros((*batch, m, m), sd)
            st["QL"] = jnp.broadcast_to(jnp.eye(m, dtype=sd), (*batch, m, m))
        if n <= max_precond_dim:
            st["R"] = jnp.zeros((*batch, n, n), sd)
            st["QR"] = jnp.broadcast_to(jnp.eye(n, dtype=sd), (*batch, n, n))
        st["M"] = jnp.zeros(pm.shape, jnp.float32)
        st["V"] = jnp.zeros(pm.shape, jnp.float32)
        return st

    def init(params):
        mask = matrix_mask(params)
        mat = jax.tree.map(_leaf_state, params, mask)
        # Masked AdamW fallback: moments only for non-matrix leaves (a dense
        # fallback costs ~2x params of f32 on MoE-scale models).
        adam = jax.tree.map(
            lambda im, p: None if im else jnp.zeros(p.shape, jnp.float32),
            mask, params)
        return {"mat": mat, "am": adam, "av": adam}

    def _leaf_update(g, st, p, step, is_mat, am, av):
        if not is_mat:
            g = g.astype(jnp.float32)
            t = jnp.asarray(step, jnp.float32) + 1.0
            am_new = adam_b1 * am + (1 - adam_b1) * g
            av_new = adam_b2 * av + (1 - adam_b2) * g * g
            d = (am_new / (1 - adam_b1 ** t)) / (
                jnp.sqrt(av_new / (1 - adam_b2 ** t)) + 1e-8)
            if weight_decay:
                d = d + weight_decay * p.astype(jnp.float32)
            return d, st, am_new, av_new
        g, orig_shape = as_matrix(g.astype(jnp.float32))
        ql = st.get("QL")
        qr = st.get("QR")
        new = dict(st)
        if "L" in st:
            gl = jnp.einsum("...ik,...jk->...ij", g, g)  # G G^T
            new["L"] = (b2 * st["L"].astype(jnp.float32)
                        + (1 - b2) * gl).astype(sd)
        if "R" in st:
            gr = jnp.einsum("...ki,...kj->...ij", g, g)  # G^T G
            new["R"] = (b2 * st["R"].astype(jnp.float32)
                        + (1 - b2) * gr).astype(sd)

        refresh = (step % precond_freq) == 0

        @jax.named_scope("precond_refresh")
        def do_refresh(args):
            ln, rn, qlo, qro = args
            qln = _eig_refresh(ln.astype(jnp.float32),
                               qlo.astype(jnp.float32),
                               eig_method).astype(sd) \
                if qlo is not None else None
            qrn = _eig_refresh(rn.astype(jnp.float32),
                               qro.astype(jnp.float32),
                               eig_method).astype(sd) \
                if qro is not None else None
            return qln, qrn

        def no_refresh(args):
            _, _, qlo, qro = args
            return qlo, qro

        ql_new, qr_new = jax.lax.cond(
            refresh, do_refresh, no_refresh,
            (new.get("L"), new.get("R"), ql, qr))
        if ql is not None:
            new["QL"] = ql_new
        if qr is not None:
            new["QR"] = qr_new

        qlf = ql_new.astype(jnp.float32) if ql_new is not None else None
        qrf = qr_new.astype(jnp.float32) if qr_new is not None else None
        g_rot = _rot(g, qlf, qrf)  # Q_L^T G Q_R
        m_new = b1 * st["M"] + (1 - b1) * g_rot
        v_new = b2 * st["V"] + (1 - b2) * g_rot * g_rot
        # Bias-corrected Adam in the rotated basis (matches the non-matrix
        # fallback).  With warm restarts from zeroed moments every federated
        # round, the uncorrected step is ~sqrt(1-b2^t)/(1-b1^t) of nominal
        # for all K local steps — slow enough to sink Alg. 2's convergence.
        t = jnp.asarray(step, jnp.float32) + 1.0
        n_rot = (m_new / (1 - b1 ** t)) / (
            jnp.sqrt(v_new / (1 - b2 ** t)) + eps)
        d = _rot(n_rot, qlf, qrf, inverse=True)  # Q_L N Q_R^T
        if orig_shape is not None:
            d = d.reshape(orig_shape)
        if weight_decay:
            d = d + weight_decay * p.astype(jnp.float32)
        new["M"], new["V"] = m_new, v_new
        return d, new, None, None

    def update(grads, state, params, step, extras=None):
        mask = matrix_mask(params)
        out = jax.tree.map(
            lambda g, st, p, im, am, av: _leaf_update(g, st, p, step, im,
                                                      am, av),
            grads, state["mat"], params, mask, state["am"], state["av"],
            is_leaf=lambda x: x is None,
        )
        # out has 4-tuples at param-leaf positions of the grads tree
        direction, mat_state, am, av = _tree_unzip(out, 4)
        return direction, {"mat": mat_state, "am": am, "av": av}

    def get_precond(state):
        def leaf(st):
            if st is None:
                return None
            return {k: st[k] for k in ("L", "R") if k in st}
        return {"LR": jax.tree.map(leaf, state["mat"],
                                   is_leaf=lambda x: x is None or (
                                       isinstance(x, dict) and "M" in x))}

    def set_precond(state, theta):
        # Alignment replaces the curvature statistics (paper Alg. 5 line 3);
        # the eigenbasis Q re-derives from the aggregated L/R at the next
        # scheduled refresh (k % precond_freq == 0, i.e. the first local
        # step of the round), not eagerly here.
        def leaf(st, th):
            if st is None:
                return None
            new = dict(st)
            for k in ("L", "R"):
                if k in st and th is not None and k in th:
                    new[k] = th[k]
            return new

        mat = jax.tree.map(
            leaf, state["mat"], theta["LR"],
            is_leaf=lambda x: x is None or (isinstance(x, dict) and "M" in x))
        return dict(state, mat=mat)

    def refresh_routes(params):
        """How many refresh matrices of each shape take each QR route
        (``householder_qr.ops.route``), read off the factors ``init`` keeps:
        {route: {"<m>x<m>": count}}, a stacked leaf counting once per
        matrix."""
        use = hw.default_use_pallas()
        routes = {}
        for f in jax.tree.leaves(get_precond(jax.eval_shape(init, params))):
            r = routes.setdefault(qr_ops.route(f.shape, use), {})
            key = "x".join(map(str, f.shape[-2:]))
            r[key] = r.get(key, 0) + math.prod(f.shape[:-2])
        return routes

    return LocalOptimizer("soap", init, update, get_precond, set_precond,
                          precond_multiplier=2.0,
                          refresh_routes=(refresh_routes if eig_method == "qr"
                                          else None))
