"""Optimizer unit tests: descent, state round-trips, paper Assumption 5.4
(coercivity/boundedness) spot checks."""
import jax
import jax.numpy as jnp
import pytest

from repro import optim
from repro.optim.api import matrix_mask, as_matrix
from repro.utils.tree import tree_dot, tree_norm_sq

KEY = jax.random.key(0)


def _quadratic_problem():
    k1, k2, k3 = jax.random.split(KEY, 3)
    W = jax.random.normal(k1, (12, 8))
    X = jax.random.normal(k2, (128, 12))
    Y = X @ W
    params = {"layer": {"w": jax.random.normal(k3, (12, 8)) * 0.1,
                        "b": jnp.zeros(8)},
              "embed": {"tok": jnp.zeros((4, 8))}}

    def loss(p):
        return jnp.mean((X @ p["layer"]["w"] + p["layer"]["b"] - Y) ** 2)

    return params, loss


@pytest.mark.parametrize("name,lr", [("sgd", 0.05), ("adamw", 0.05),
                                     ("muon", 0.05), ("soap", 0.05),
                                     ("sophia", 0.5)])
def test_descent(name, lr):
    params, loss = _quadratic_problem()
    opt = optim.make(name)
    state = opt.init(params)
    p = params

    @jax.jit
    def step(p, state, i):
        g = jax.grad(loss)(p)
        extras = None
        if opt.needs_hessian:
            u = jax.tree.map(
                lambda x: jnp.sign(jax.random.normal(
                    jax.random.fold_in(KEY, i), x.shape)), p)
            _, hvp = jax.jvp(jax.grad(loss), (p,), (u,))
            extras = {"h_est": jax.tree.map(lambda a, b: a * b, u, hvp),
                      "h_gate": True}
        d, state = opt.update(g, state, p, i, extras)
        return jax.tree.map(lambda x, dd: x - lr * dd, p, d), state

    l0 = float(loss(p))
    for i in range(50):
        p, state = step(p, state, jnp.int32(i))
    assert float(loss(p)) < 0.5 * l0


@pytest.mark.parametrize("name", ["muon", "soap", "sophia", "adamw", "sgd"])
def test_precond_roundtrip(name):
    params, loss = _quadratic_problem()
    opt = optim.make(name)
    state = opt.init(params)
    g = jax.grad(loss)(params)
    _, state = opt.update(g, state, params, jnp.int32(0),
                          {"h_est": jax.tree.map(jnp.abs, g), "h_gate": True}
                          if opt.needs_hessian else None)
    theta = opt.get_precond(state)
    state2 = opt.set_precond(state, theta)
    d1, _ = opt.update(g, state, params, jnp.int32(1))
    d2, _ = opt.update(g, state2, params, jnp.int32(1))
    for a, b in zip(jax.tree.leaves(d1), jax.tree.leaves(d2)):
        assert jnp.allclose(a, b, atol=1e-5)


@pytest.mark.parametrize("name", ["adamw", "sophia", "soap"])
def test_coercivity_assumption(name):
    """Assumption 5.4(i): <g, P(g)> > 0 after warmup (descent direction)."""
    params, loss = _quadratic_problem()
    opt = optim.make(name)
    state = opt.init(params)
    p = params
    for i in range(5):
        g = jax.grad(loss)(p)
        extras = ({"h_est": jax.tree.map(lambda x: jnp.abs(x) + 0.1, g),
                   "h_gate": True} if opt.needs_hessian else None)
        d, state = opt.update(g, state, p, jnp.int32(i), extras)
        p = jax.tree.map(lambda x, dd: x - 0.01 * dd, p, d)
    g = jax.grad(loss)(p)
    d, _ = opt.update(g, state, p, jnp.int32(5))
    assert float(tree_dot(g, d)) > 0.0


def test_muon_direction_orthogonalized():
    params, loss = _quadratic_problem()
    opt = optim.make("muon", b1=0.0)
    state = opt.init(params)
    g = jax.grad(loss)(params)
    d, _ = opt.update(g, state, params, jnp.int32(0))
    w_dir = d["layer"]["w"] / jnp.sqrt(jnp.maximum(1.0, 12 / 8))
    s = jnp.linalg.svd(w_dir, compute_uv=False)
    assert float(s.max()) < 1.4 and float(s.min()) > 0.3


def test_sophia_clip_bound():
    params, loss = _quadratic_problem()
    opt = optim.make("sophia", rho=0.03)
    state = opt.init(params)
    g = jax.grad(loss)(params)
    d, _ = opt.update(g, state, params, jnp.int32(0),
                      {"h_est": jax.tree.map(jnp.abs, g), "h_gate": True})
    for leaf in jax.tree.leaves(d):
        assert float(jnp.max(jnp.abs(leaf))) <= 0.03 + 1e-7


def test_matrix_mask_excludes_embeddings_and_vectors():
    params = {"embed": {"tok": jnp.zeros((100, 32))},
              "layers": [{"mixer": {"wq": jnp.zeros((32, 32))},
                          "pre_norm": {"scale": jnp.zeros(32)}}],
              "head": {"w": jnp.zeros((32, 100))}}
    mask = matrix_mask(params)
    assert mask["layers"][0]["mixer"]["wq"] is True
    assert mask["embed"]["tok"] is False
    assert mask["head"]["w"] is False
    assert mask["layers"][0]["pre_norm"]["scale"] is False


def test_as_matrix_conv_flattening():
    x = jnp.zeros((3, 3, 8, 16))
    mat, orig = as_matrix(x)
    assert mat.shape == (72, 16) and orig == (3, 3, 8, 16)


def test_soap_one_sided_for_huge_dims():
    opt = optim.make("soap", max_precond_dim=32)
    params = {"layer": {"w": jnp.zeros((64, 16))}}
    state = opt.init(params)
    st = state["mat"]["layer"]["w"]
    assert "L" not in st and "R" in st  # 64 > 32 -> left side skipped


def _well_conditioned(key, shape):
    """Orthogonal times singular values in [1, 2]: full-rank SOAP factors,
    so the refresh's Q (up to column signs, which the step-0 direction does
    not see) is well determined."""
    q = jnp.linalg.qr(jax.random.normal(key, shape))[0]
    return q * jnp.linspace(1.0, 2.0, shape[-1])


def test_soap_refresh_kernel_path_matches_ref(monkeypatch):
    from repro.kernels.householder_qr import ops as hq_ops
    from repro.utils import hw
    k1, k2, k3 = jax.random.split(KEY, 3)
    params = {"attn": {"wo": jnp.zeros((64, 64))},
              "stack": jnp.zeros((2, 40, 40)), "b": jnp.zeros((40,))}
    grads = {"attn": {"wo": _well_conditioned(k1, (64, 64))},
             "stack": _well_conditioned(k2, (2, 40, 40)),
             "b": jax.random.normal(k3, (40,))}
    opt = optim.make("soap")
    state = opt.init(params)          # step 0: Q = I, M = V = 0
    want, _ = opt.update(grads, state, params, 0)

    # steer the refresh onto the kernel path (interpret mode off the TPU)
    calls = []
    blocked = hq_ops.blocked_qr
    monkeypatch.setattr(hw, "default_use_pallas", lambda: True)
    monkeypatch.setattr(hq_ops, "MIN_ROWS", 8)
    monkeypatch.setattr(hq_ops, "blocked_qr",
                        lambda s, **kw: calls.append(s.shape) or
                        blocked(s, **kw))
    got, _ = opt.update(grads, state, params, 0)
    assert sorted(calls) == [(2, 40, 40), (2, 40, 40), (64, 64), (64, 64)]
    assert opt.refresh_routes(params) == {"pallas": {"64x64": 2,
                                                     "40x40": 4}}
    for w, g in zip(jax.tree.leaves(want), jax.tree.leaves(got)):
        assert float(jnp.max(jnp.abs(w - g))) < 1e-5


def test_soap_refresh_routes_count_every_matrix(monkeypatch):
    from repro import configs
    from repro.models import model as M, vision
    from repro.utils import hw
    opt = optim.make("soap")
    # a small ViT: per block wqkv 64|192, wo 64|64, w1 64|256, w2 256|64
    params, _ = vision.init_vit(KEY, d_model=64, layers=2)
    assert opt.refresh_routes(params) == {
        "xla": {"64x64": 10, "192x192": 2, "256x256": 4}}
    monkeypatch.setattr(hw, "default_use_pallas", lambda: True)  # TPU rule
    assert opt.refresh_routes(params) == {
        "xla": {"64x64": 10, "192x192": 2}, "pallas": {"256x256": 4}}
    # the chip benchmark's cells route every refresh matrix to the kernel
    vit_s = jax.eval_shape(lambda k: vision.init_vit(
        k, image_size=32, patch=4, d_model=384, layers=12, heads=6,
        n_classes=100)[0], KEY)
    assert opt.refresh_routes(vit_s) == {
        "pallas": {"384x384": 60, "1152x1152": 12, "1536x1536": 24}}
    smollm = M.param_shapes(configs.get_config("smollm-360m").replace(
        num_layers=4))
    assert opt.refresh_routes(smollm) == {
        "pallas": {"960x960": 36, "320x320": 8, "2560x2560": 12}}
