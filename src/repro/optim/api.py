"""Unified local-optimizer API: the paper's (Theta, P_Theta) abstraction.

Every optimizer is a ``LocalOptimizer`` of pure functions:

  init(params)                      -> state
  update(grads, state, params, step, extras) -> (direction, new_state)
      ``direction`` is the *preconditioned* update P_Theta(g) (descent
      direction; caller applies x <- x - lr * mix(direction, g_G)).
  get_precond(state)                -> Theta   (the alignable geometry)
  set_precond(state, theta)         -> state   (FedPAC alignment warm-start)

``extras`` carries optional per-step inputs (e.g. Sophia's Hutchinson
diagonal-Hessian estimate).  All states are float32 pytrees mirroring params.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Callable, Optional

import jax


@dataclasses.dataclass(frozen=True)
class LocalOptimizer:
    name: str
    init: Callable[[Any], Any]
    update: Callable[..., Any]  # (grads, state, params, step, extras) -> (dir, state)
    get_precond: Callable[[Any], Any]
    set_precond: Callable[[Any, Any], Any]
    # True if the client loop must supply a Hutchinson diag-Hessian estimate.
    needs_hessian: bool = False
    # Fraction/structure of Theta uploaded per round, for comm accounting.
    precond_multiplier: float = 1.0
    # params -> {route: {"<m>x<m>": matrices}}: how the optimizer's curvature
    # refresh computes each matrix (SOAP's QR route); None if it has none.
    refresh_routes: Optional[Callable[[Any], dict]] = None


def path_str(path) -> str:
    return "/".join(str(getattr(k, "key", getattr(k, "idx", k))) for k in path)


_NON_MATRIX_TOKENS = ("embed", "tok", "head", "norm", "bias", "scale",
                      "conv", "a_log", "lam", "cls", "pos", "dt_bias")


def is_hidden_matrix(path, leaf) -> bool:
    """Hidden-layer weight (Muon/SOAP domain): excludes embeddings, lm heads,
    norms/biases/convs/recurrence constants."""
    if leaf.ndim < 2:
        return False
    if leaf.shape[-1] < 8 or leaf.shape[-2] < 8:
        # degenerate matrices (cls tokens, tiny gates) -> Adam fallback
        if not (leaf.ndim == 4 and leaf.shape[0] <= 7):
            return False
    s = path_str(path).lower()
    return not any(tok in s for tok in _NON_MATRIX_TOKENS)


def as_matrix(x):
    """Canonical matrix view for structured preconditioners.

    2-D: as-is; 3-D (layers-or-experts, m, n): batched matrices;
    4-D conv HWIO (small spatial dims): flattened to (k*k*c_in, c_out), the
    Muon/Shampoo convention; other 4-D+ (stacked expert tensors (L,E,m,n)):
    batch dims collapsed.  Returns (mat, orig_shape_or_None).
    """
    if x.ndim <= 3:
        return x, None
    if x.ndim == 4 and x.shape[0] <= 7 and x.shape[1] <= 7:
        return x.reshape(-1, x.shape[-1]), x.shape
    return x.reshape(-1, x.shape[-2], x.shape[-1]), x.shape


def matrix_mask(params):
    """Pytree of bools: which leaves get the matrix preconditioner."""
    flat = jax.tree_util.tree_flatten_with_path(params)
    paths, treedef = flat[0], flat[1]
    leaves = [is_hidden_matrix(p, l) for p, l in paths]
    return jax.tree_util.tree_unflatten(treedef, leaves)
