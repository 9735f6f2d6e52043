"""The EWA projection against a plain (N,3,3)-matmul formulation.

``project`` writes every 3x3 and 2x3 product out as elementwise f32
arithmetic. Here it is held to the textbook matrix form: in float64 numpy
for its outputs, and in jnp (f32, ``highest``) for its gradients. A last
test keeps ``dot_general`` out of its forward and its VJP.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import gaussians as G
from repro.core import lie
from repro.core.camera import Camera, Intrinsics, look_at
from repro.core.projection import _COV2D_BLUR, _NEAR, project

INTR = Intrinsics(fx=90.0, fy=85.0, cx=47.5, cy=35.5, width=96, height=72)
N = 384


def _field(seed: int) -> G.GaussianField:
    """Anisotropic, rotated Gaussians: most in front of the camera, some
    near or behind the near plane, some off screen, some dead."""
    rng = np.random.RandomState(seed)
    mu = np.stack([rng.uniform(-1.5, 1.5, N), rng.uniform(-1.0, 1.0, N),
                   rng.uniform(1.0, 5.0, N)], -1)
    mu[: N // 16, 2] = rng.uniform(-0.5, 0.1, N // 16)      # near plane
    mu[N // 16: N // 8, 0] = rng.uniform(8.0, 40.0, N // 16)  # off screen
    alive = np.ones(N, bool)
    alive[rng.choice(N, N // 8, replace=False)] = False
    f32 = lambda x: jnp.asarray(x, jnp.float32)  # noqa: E731
    return G.GaussianField(
        mu=f32(mu),
        log_scale=f32(rng.uniform(np.log(0.005), np.log(0.15), (N, 3))),
        quat=f32(rng.normal(size=(N, 4)) * rng.uniform(0.5, 2.0, (N, 1))),
        logit_o=f32(rng.normal(size=N)),
        color=f32(rng.normal(size=(N, 3))),
        alive=jnp.asarray(alive),
    )


def _pose(k: int) -> jnp.ndarray:
    w2c = look_at(jnp.zeros(3), jnp.array([0.0, 0.0, 3.0]), jnp.array([0.0, -1.0, 0.0]))
    xi = 0.15 * k * jnp.array([0.3, -0.2, 0.1, 0.4, -0.3, 0.25], jnp.float32)
    return lie.se3_exp(xi) @ w2c


CASES = [(0, 0), (1, 1), (2, 2), (3, 1)]


def _rotations(q, xp):
    q = q / (xp.linalg.norm(q, axis=-1, keepdims=True) + 1e-9)
    w, x, y, z = q[:, 0], q[:, 1], q[:, 2], q[:, 3]
    return xp.stack([
        xp.stack([1 - 2 * (y * y + z * z), 2 * (x * y - w * z), 2 * (x * z + w * y)], -1),
        xp.stack([2 * (x * y + w * z), 1 - 2 * (x * x + z * z), 2 * (y * z - w * x)], -1),
        xp.stack([2 * (x * z - w * y), 2 * (y * z + w * x), 1 - 2 * (x * x + y * y)], -1),
    ], -2)


def _project_matmul(mu, log_scale, quat, w2c, xp):
    """The matrix form: p = mu W^T + t, Sigma = RS (RS)^T,
    cov2d = (J W) Sigma (J W)^T + blur I. Returns mu2d, conic, depth, radius,
    and the unguarded det and depth for ``valid``."""
    W, t = w2c[:3, :3], w2c[:3, 3]
    p = mu @ W.T + t
    z = p[:, 2]
    zs = xp.maximum(z, _NEAR)
    mu2d = xp.stack([INTR.fx * p[:, 0] / zs + INTR.cx, INTR.fy * p[:, 1] / zs + INTR.cy], -1)
    zero = xp.zeros_like(z)
    J = xp.stack([
        xp.stack([INTR.fx / zs, zero, -INTR.fx * p[:, 0] / zs**2], -1),
        xp.stack([zero, INTR.fy / zs, -INTR.fy * p[:, 1] / zs**2], -1),
    ], -2)
    RS = _rotations(quat, xp) * xp.exp(log_scale)[:, None, :]
    JW = J @ W
    cov2d = JW @ (RS @ xp.swapaxes(RS, -1, -2)) @ xp.swapaxes(JW, -1, -2)
    cov2d = cov2d + _COV2D_BLUR * xp.eye(2)
    det = cov2d[:, 0, 0] * cov2d[:, 1, 1] - cov2d[:, 0, 1] * cov2d[:, 1, 0]
    inv = 1.0 / xp.maximum(det, 1e-12)
    conic = xp.stack([cov2d[:, 1, 1] * inv, -cov2d[:, 0, 1] * inv, cov2d[:, 0, 0] * inv], -1)
    mid = 0.5 * (cov2d[:, 0, 0] + cov2d[:, 1, 1])
    lam = mid + xp.sqrt(xp.maximum(mid * mid - xp.maximum(det, 1e-12), 0.0) + 1e-12)
    radius = xp.ceil(3.0 * xp.sqrt(xp.maximum(lam, 0.0)))
    return mu2d, conic, z, radius, det


@pytest.mark.parametrize("seed,pose", CASES)
def test_project_matches_matmul_formulation(seed, pose):
    g, w2c = _field(seed), _pose(pose)
    out = project(g, Camera(INTR, w2c))
    f64 = lambda x: np.asarray(x, np.float64)  # noqa: E731
    mu2d, conic, depth, radius, det = _project_matmul(
        f64(g.mu), f64(g.log_scale), f64(g.quat), f64(w2c), np)
    on = ((mu2d[:, 0] + radius >= 0) & (mu2d[:, 0] - radius <= INTR.width)
          & (mu2d[:, 1] + radius >= 0) & (mu2d[:, 1] - radius <= INTR.height))
    valid = np.asarray(g.alive) & (depth > _NEAR) & (det > 1e-12) & on

    np.testing.assert_array_equal(np.asarray(out.valid), valid)
    assert 0 < valid.sum() < N
    assert (depth <= _NEAR).any() and (~on & (depth > _NEAR)).any()
    np.testing.assert_allclose(out.mu2d, mu2d, rtol=1e-5, atol=1e-5 * np.abs(mu2d).max())
    np.testing.assert_allclose(out.depth, depth, rtol=1e-5, atol=1e-6)
    np.testing.assert_allclose(out.conic, conic, rtol=1e-5, atol=1e-5 * np.abs(conic).max())
    np.testing.assert_allclose(out.radius, radius, rtol=1e-5)


def _loss_weights(seed):
    rng = np.random.RandomState(1000 + seed)
    return {k: jnp.asarray(rng.normal(size=s), jnp.float32)
            for k, s in [("mu2d", (N, 2)), ("conic", (N, 3)), ("depth", (N,)),
                         ("color", (N, 3)), ("opacity", (N,))]}


def _loss(mu2d, conic, depth, color, opacity, valid, wts):
    m = jax.lax.stop_gradient(valid).astype(jnp.float32)
    return (jnp.sum(m[:, None] * wts["mu2d"] * mu2d) + jnp.sum(m[:, None] * wts["conic"] * conic)
            + jnp.sum(m * wts["depth"] * depth) + jnp.sum(m[:, None] * wts["color"] * color)
            + jnp.sum(m * wts["opacity"] * opacity))


def _grads_of(loss_of_params, g, w2c):
    return jax.grad(loss_of_params, argnums=(0, 1))(G.params_of(g), w2c)


@pytest.mark.parametrize("seed,pose", CASES)
def test_project_gradients_match_matmul_formulation(seed, pose):
    g, w2c, wts = _field(seed), _pose(pose), _loss_weights(seed)
    valid = project(g, Camera(INTR, w2c)).valid

    def new(params, w2c):
        out = project(G.with_params(g, params), Camera(INTR, w2c))
        return _loss(out.mu2d, out.conic, out.depth, out.color, out.opacity, valid, wts)

    def matmul(params, w2c):
        with jax.default_matmul_precision("highest"):
            mu2d, conic, depth, _, _ = _project_matmul(
                params["mu"], params["log_scale"], params["quat"], w2c, jnp)
        return _loss(mu2d, conic, depth, jax.nn.sigmoid(params["color"]),
                     jax.nn.sigmoid(params["logit_o"]), valid, wts)

    got, want = _grads_of(new, g, w2c), _grads_of(matmul, g, w2c)
    for name in G.PARAM_FIELDS:
        scale = np.abs(want[0][name]).max()
        np.testing.assert_allclose(got[0][name], want[0][name], rtol=1e-4,
                                   atol=1e-5 * scale, err_msg=name)
        assert scale > 0, name
    np.testing.assert_allclose(got[1], want[1], rtol=1e-4, atol=1e-5 * np.abs(want[1]).max())


def _count_primitive(jaxpr, name: str) -> int:
    """Occurrences of primitive ``name`` in ``jaxpr`` and every sub-jaxpr."""
    n = 0
    for eqn in jaxpr.eqns:
        n += eqn.primitive.name == name
        for p in eqn.params.values():
            for sub in p if isinstance(p, (tuple, list)) else (p,):
                sub = getattr(sub, "jaxpr", sub)
                if hasattr(sub, "eqns"):
                    n += _count_primitive(sub, name)
    return n


def test_project_forward_and_vjp_have_no_dot_general():
    g, w2c = _field(0), _pose(1)

    def loss(params, w2c):
        out = project(G.with_params(g, params), Camera(INTR, w2c))
        return sum(jnp.sum(x) for x in (out.mu2d, out.conic, out.depth, out.color, out.opacity))

    fwd = jax.make_jaxpr(loss)(G.params_of(g), w2c)
    bwd = jax.make_jaxpr(jax.grad(loss, argnums=(0, 1)))(G.params_of(g), w2c)
    assert _count_primitive(fwd.jaxpr, "mul") > 0  # the walk sees project's body
    assert _count_primitive(fwd.jaxpr, "dot_general") == 0
    assert _count_primitive(bwd.jaxpr, "dot_general") == 0
