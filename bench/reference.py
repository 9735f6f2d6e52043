"""Plain reference of one RGB-D SLAM stream, for the benchmark's ``correct``.

A straightforward ``jax.numpy`` implementation of the semantics the system
under test states for a MonoGS-style stream with fixed-interval keyframes
and section 4.1 pruning: seed the map from frame 0's RGB-D, map frame 0,
then per frame track the pose (Adam on a left se(3) delta from a
constant-velocity prediction, pruning at interval boundaries) and, on a
keyframe, densify and map the keyframe window.  It imports nothing of the
program and is given nothing the program made: the configuration, the
frames and the seed are all it sees.

Rendering is EWA projection, per-16-px-tile fragment lists of the ``K``
nearest Gaussians, and front-to-back alpha blending with early termination,
all as dense arrays (no kernels, no schedule).  Fragment lists are rebuilt
exactly where the stated algorithm rebuilds them (frame start, pruning
boundaries, the mapping stride), because stale lists are part of what it
computes.

``precision`` is the precision of every matrix product: the configuration
states float32 at ``highest``; the lower-precision control (``high``) is the
same code with each product formed as a TPU forms it at ``high``, from the
bfloat16 halves of its operands in three passes (emulated with exact
bfloat16 products, so it means the same on every backend).
"""

from __future__ import annotations

import contextvars
import dataclasses
import math
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

TILE = 16
ALPHA_MIN = 1.0 / 255.0
ALPHA_MAX = 0.99
TERM_EPS = 1e-4
NEAR = 0.05
COV2D_BLUR = 0.3
SILENT_LOGIT = -30.0

_PRECISION = contextvars.ContextVar("reference_precision", default="highest")
_HI = jax.lax.Precision.HIGHEST


def _split(x):
    hi = x.astype(jnp.bfloat16).astype(jnp.float32)
    return hi, (x - hi).astype(jnp.bfloat16).astype(jnp.float32)


def dot(subscripts: str, a, b):
    """``einsum`` at the precision of the phase being traced."""
    if _PRECISION.get() == "highest":
        return jnp.einsum(subscripts, a, b, precision=_HI)
    (ah, al), (bh, bl) = _split(a), _split(b)
    return (jnp.einsum(subscripts, ah, bh, precision=_HI)
            + jnp.einsum(subscripts, ah, bl, precision=_HI)
            + jnp.einsum(subscripts, al, bh, precision=_HI))


def mm(a, b):
    """``a @ b``: matrices with leading batch dimensions, or a vector ``b``."""
    if b.ndim == 1:
        return dot("...ij,j->...i", a, b)
    return dot("...ij,...jk->...ik", a, b)


@dataclasses.dataclass(frozen=True)
class Setup:
    """What the reference needs of a configuration (all static)."""

    height: int
    width: int
    fx: float
    fy: float
    cx: float
    cy: float
    capacity: int
    k: int
    iters_track: int
    iters_map: int
    map_window: int
    interval: int
    lr_pose: float
    lr_map: float
    lambda_pho: float
    densify_per_kf: int
    seed_stride: int
    seed_opacity: float
    map_rebuild_stride: int
    # section 4.1 pruning
    prune_lam: float
    prune_k0: int
    prune_churn: float
    prune_step_frac: float
    prune_max_ratio: float
    prune_k_min: int
    prune_k_max: int

    @property
    def grid_h(self) -> int:
        return self.height // TILE

    @property
    def grid_w(self) -> int:
        return self.width // TILE

    @property
    def tiles(self) -> int:
        return self.grid_h * self.grid_w


def setup(cfg: dict, k: int | None = None) -> Setup:
    """The reference's view of a configuration file (``bench/configs``)."""
    fr, slam, pr = cfg["frame"], cfg["slam"], cfg["slam"]["prune"]
    return Setup(
        height=fr["height"], width=fr["width"], fx=fr["fx"], fy=fr["fy"],
        cx=fr["cx"], cy=fr["cy"], capacity=slam["capacity"],
        k=k or slam["frag_capacity"], iters_track=slam["iters_track"],
        iters_map=slam["iters_map"], map_window=slam["map_window"],
        interval=slam["keyframe"]["interval"], lr_pose=slam["lr_pose"],
        lr_map=slam["lr_map"], lambda_pho=slam["lambda_pho"],
        densify_per_kf=slam["densify_per_kf"], seed_stride=slam["seed_stride"],
        seed_opacity=slam["seed_opacity"],
        map_rebuild_stride=slam["map_rebuild_stride"],
        prune_lam=pr["lam"], prune_k0=pr["k0"], prune_churn=pr["churn_threshold"],
        prune_step_frac=pr["step_frac"], prune_max_ratio=pr["max_ratio"],
        prune_k_min=pr["k_min"], prune_k_max=pr["k_max"])


class Field(NamedTuple):
    mu: jnp.ndarray
    log_scale: jnp.ndarray
    quat: jnp.ndarray
    logit_o: jnp.ndarray
    color: jnp.ndarray
    alive: jnp.ndarray


PARAMS = ("mu", "log_scale", "quat", "logit_o", "color")


def params(f: Field) -> dict:
    return {k: getattr(f, k) for k in PARAMS}


class Prune(NamedTuple):
    score: jnp.ndarray
    masked: jnp.ndarray
    interval: jnp.ndarray
    iters_left: jnp.ndarray
    prev_count: jnp.ndarray
    initial_alive: jnp.ndarray
    removed: jnp.ndarray


class Adam(NamedTuple):
    step: jnp.ndarray
    m: object
    v: object


def adam_zero(tree) -> Adam:
    z = jax.tree.map(jnp.zeros_like, tree)
    return Adam(jnp.zeros((), jnp.int32), z, jax.tree.map(jnp.zeros_like, tree))


def adam_step(grads, st: Adam, lr: float):
    """Adam (b1 0.9, b2 0.999, eps 1e-8) with bias correction; returns the
    update and the new state."""
    b1, b2, eps = 0.9, 0.999, 1e-8
    step = st.step + 1
    m = jax.tree.map(lambda m, g: b1 * m + (1 - b1) * g, st.m, grads)
    v = jax.tree.map(lambda v, g: b2 * v + (1 - b2) * g * g, st.v, grads)
    t = step.astype(jnp.float32)
    a = lr / (1.0 - b1 ** t)
    inv_sqrt_bc2 = jax.lax.rsqrt(1.0 - b2 ** t)
    upd = jax.tree.map(lambda m, v: -a * m / (jnp.sqrt(v) * inv_sqrt_bc2 + eps),
                       m, v)
    return upd, Adam(step, m, v)


# ---------------------------------------------------------------------------
# geometry
# ---------------------------------------------------------------------------


def _hat(w):
    z = jnp.zeros_like(w[0])
    return jnp.stack([jnp.stack([z, -w[2], w[1]]),
                      jnp.stack([w[2], z, -w[0]]),
                      jnp.stack([-w[1], w[0], z])])


def se3_exp(xi):
    """(6,) [rho, omega] -> (4, 4), with series forms near zero angle."""
    rho, w = xi[:3], xi[3:]
    th2 = jnp.sum(w * w)
    series = th2 < 1e-8
    t2 = jnp.where(series, 1.0, th2)
    t = jnp.sqrt(t2)
    a = jnp.where(series, 1.0 - th2 / 6.0, jnp.sin(t) / t)
    b = jnp.where(series, 0.5 - th2 / 24.0, (1.0 - jnp.cos(t)) / t2)
    c = jnp.where(th2 < 1e-2, 1.0 / 6.0 - th2 / 120.0, (t - jnp.sin(t)) / (t2 * t))
    W = _hat(w)
    W2 = mm(W, W)
    eye = jnp.eye(3, dtype=xi.dtype)
    R = eye + a * W + b * W2
    V = eye + b * W + c * W2
    top = jnp.concatenate([R, mm(V, rho)[:, None]], axis=1)
    return jnp.concatenate([top, jnp.array([[0.0, 0.0, 0.0, 1.0]], xi.dtype)], 0)


def rotations(quat):
    q = quat / (jnp.linalg.norm(quat, axis=-1, keepdims=True) + 1e-9)
    w, x, y, z = q[:, 0], q[:, 1], q[:, 2], q[:, 3]
    return jnp.stack([
        jnp.stack([1 - 2 * (y * y + z * z), 2 * (x * y - w * z), 2 * (x * z + w * y)], -1),
        jnp.stack([2 * (x * y + w * z), 1 - 2 * (x * x + z * z), 2 * (y * z - w * x)], -1),
        jnp.stack([2 * (x * z - w * y), 2 * (y * z + w * x), 1 - 2 * (x * x + y * y)], -1),
    ], axis=-2)


class Splats(NamedTuple):
    mu2d: jnp.ndarray
    conic: jnp.ndarray
    rgb: jnp.ndarray
    opacity: jnp.ndarray
    depth: jnp.ndarray
    radius: jnp.ndarray
    valid: jnp.ndarray


def project(f: Field, w2c, s: Setup) -> Splats:
    """EWA splatting of every Gaussian into the camera ``w2c``."""
    R, t = w2c[:3, :3], w2c[:3, 3]
    p = mm(f.mu, R.T) + t
    z = p[:, 2]
    zs = jnp.maximum(z, NEAR)
    mu2d = jnp.stack([s.fx * p[:, 0] / zs + s.cx, s.fy * p[:, 1] / zs + s.cy], -1)
    iz = 1.0 / zs
    zero = jnp.zeros_like(z)
    J = jnp.stack([
        jnp.stack([s.fx * iz, zero, -s.fx * p[:, 0] * iz * iz], -1),
        jnp.stack([zero, s.fy * iz, -s.fy * p[:, 1] * iz * iz], -1)], -2)
    rs = rotations(f.quat) * jnp.exp(f.log_scale)[:, None, :]
    cov3 = mm(rs, jnp.swapaxes(rs, -1, -2))
    jw = dot("nij,jk->nik", J, R)
    cov2 = mm(mm(jw, cov3), jnp.swapaxes(jw, -1, -2)) + COV2D_BLUR * jnp.eye(2)
    det = cov2[:, 0, 0] * cov2[:, 1, 1] - cov2[:, 0, 1] * cov2[:, 1, 0]
    inv = 1.0 / jnp.maximum(det, 1e-12)
    conic = jnp.stack([cov2[:, 1, 1] * inv, -cov2[:, 0, 1] * inv,
                       cov2[:, 0, 0] * inv], -1)
    mid = 0.5 * (cov2[:, 0, 0] + cov2[:, 1, 1])
    lam = mid + jnp.sqrt(jnp.maximum(mid * mid - jnp.maximum(det, 1e-12), 0.0)
                         + 1e-12)
    radius = jnp.ceil(3.0 * jnp.sqrt(jnp.maximum(lam, 0.0)))
    on = ((mu2d[:, 0] + radius >= 0.0) & (mu2d[:, 0] - radius <= s.width)
          & (mu2d[:, 1] + radius >= 0.0) & (mu2d[:, 1] - radius <= s.height))
    valid = f.alive & (z > NEAR) & (det > 1e-12) & on
    return Splats(mu2d, conic, jax.nn.sigmoid(f.color),
                  jax.nn.sigmoid(f.logit_o), z, radius, valid)


def tile_lists(sp: Splats, s: Setup):
    """(T, K) Gaussian indices per tile, nearest first, -1 padded: the ``K``
    nearest valid Gaussians whose 3-sigma box touches the tile."""
    sg = jax.lax.stop_gradient
    mu2d, depth, radius = sg(sp.mu2d), sg(sp.depth), sg(sp.radius)
    n = mu2d.shape[0]
    order = jnp.argsort(jnp.where(sp.valid, depth, jnp.inf), stable=True)
    mu, rad, ok = mu2d[order], radius[order], sp.valid[order]

    def span(c, lo_hi, size):
        return jnp.clip(jnp.floor((c + lo_hi * rad) / TILE), 0, size - 1)

    x0, x1 = span(mu[:, 0], -1.0, s.grid_w), span(mu[:, 0], 1.0, s.grid_w)
    y0, y1 = span(mu[:, 1], -1.0, s.grid_h), span(mu[:, 1], 1.0, s.grid_h)
    ty = jnp.arange(s.grid_h, dtype=jnp.float32)[:, None]
    tx = jnp.arange(s.grid_w, dtype=jnp.float32)[:, None]
    member = (((ty >= y0) & (ty <= y1))[:, None, :]
              & ((tx >= x0) & (tx <= x1))[None, :, :]
              & ok[None, None, :]).reshape(s.tiles, n)
    rank = jnp.cumsum(member.astype(jnp.int32), axis=1)
    slots = jnp.arange(1, s.k + 1, dtype=jnp.int32)
    first = jax.vmap(lambda r: jnp.searchsorted(r, slots, side="left"))(rank)
    listed = slots[None, :] <= rank[:, -1:]
    return jnp.where(listed, order[jnp.minimum(first, n - 1)], -1)


def pixel_centres(s: Setup):
    ty, tx = jnp.meshgrid(jnp.arange(s.grid_h), jnp.arange(s.grid_w),
                          indexing="ij")
    py, px = jnp.meshgrid(jnp.arange(TILE), jnp.arange(TILE), indexing="ij")
    x = tx.reshape(-1, 1) * TILE + px.reshape(1, -1) + 0.5
    y = ty.reshape(-1, 1) * TILE + py.reshape(1, -1) + 0.5
    return x.astype(jnp.float32), y.astype(jnp.float32)


def untile(x, s: Setup):
    chan = x.shape[2:]
    x = x.reshape((s.grid_h, s.grid_w, TILE, TILE) + chan)
    return jnp.swapaxes(x, 1, 2).reshape((s.height, s.width) + chan)


def blend(sp: Splats, lists, s: Setup):
    """Front-to-back alpha blending of each tile's list at its 256 pixel
    centres.  Returns image (H, W, 3), alpha-weighted depth (H, W) and the
    final transmittance (H, W)."""
    present = lists >= 0
    idx = jnp.maximum(lists, 0)

    def take(a):
        return jnp.where(present, a[idx], 0.0)

    px, py = pixel_centres(s)
    dx = px[:, :, None] - take(sp.mu2d[:, 0])[:, None, :]
    dy = py[:, :, None] - take(sp.mu2d[:, 1])[:, None, :]
    q = (take(sp.conic[:, 0])[:, None, :] * dx * dx
         + 2.0 * take(sp.conic[:, 1])[:, None, :] * dx * dy
         + take(sp.conic[:, 2])[:, None, :] * dy * dy)
    alpha = jnp.minimum(take(sp.opacity)[:, None, :]
                        * jnp.exp(-0.5 * jnp.maximum(q, 0.0)), ALPHA_MAX)
    alpha = jnp.where((alpha >= ALPHA_MIN) & present[:, None, :], alpha, 0.0)
    trans = jnp.cumprod(1.0 - alpha, axis=-1)
    trans = jnp.concatenate([jnp.ones_like(trans[..., :1]), trans[..., :-1]], -1)
    keep = trans > TERM_EPS
    w = trans * alpha * keep
    rgb = jnp.stack([take(sp.rgb[:, c]) for c in range(3)], axis=1)
    color = dot("tpk,tck->tpc", w, rgb)
    depth = dot("tpk,tk->tp", w, take(sp.depth))
    final_t = jnp.prod(1.0 - alpha * keep, axis=-1)
    return untile(color, s), untile(depth, s), untile(final_t, s)


def slam_loss(img, dep, final_t, rgb, depth, lam):
    alpha = 1.0 - final_t
    e_pho = jnp.mean(jnp.abs(img - rgb))
    cover = (depth > 1e-3) & (alpha > 0.5)
    e_geo = (jnp.sum(jnp.abs(dep / jnp.maximum(alpha, 1e-6) - depth) * cover)
             / jnp.maximum(jnp.sum(cover), 1.0))
    return lam * e_pho + (1.0 - lam) * e_geo


def psnr(a, b):
    return 10.0 * jnp.log10(1.0 / jnp.maximum(jnp.mean((a - b) ** 2), 1e-12))


def silenced(f: Field, masked) -> Field:
    return f._replace(logit_o=jnp.where(masked | ~f.alive, SILENT_LOGIT,
                                        f.logit_o))


# ---------------------------------------------------------------------------
# the stream
# ---------------------------------------------------------------------------


def seed_field(rgb0: np.ndarray, depth0: np.ndarray, w2c0: np.ndarray,
               s: Setup) -> Field:
    """Back-project a stride grid of frame 0's valid depth into Gaussians
    (at most half the pool), isotropic at the median-depth pixel footprint."""
    ys = np.arange(0, s.height, s.seed_stride)
    xs = np.arange(0, s.width, s.seed_stride)
    vv, uu = np.meshgrid(ys, xs, indexing="ij")
    uu, vv = uu.reshape(-1), vv.reshape(-1)
    d = depth0[vv, uu]
    ok = d > 1e-3
    uu, vv, d = uu[ok], vv[ok], d[ok]
    cam = np.stack([(uu + 0.5 - s.cx) / s.fx * d,
                    (vv + 0.5 - s.cy) / s.fy * d, d], -1)
    c2w = np.linalg.inv(w2c0)
    pts = cam @ c2w[:3, :3].T + c2w[:3, 3]
    n = min(len(pts), s.capacity // 2)
    cols = jnp.clip(jnp.asarray(np.clip(rgb0[vv, uu][:n], 0.02, 0.98)),
                    1e-4, 1 - 1e-4)
    scale = float(np.median(d)) / s.fx * s.seed_stride
    cap = s.capacity

    def pad(a, fill, width):
        out = jnp.full((cap, width), fill, jnp.float32)
        return out.at[:n].set(jnp.asarray(a, jnp.float32).reshape(n, width))

    quat = jnp.zeros((cap, 4), jnp.float32).at[:, 0].set(1.0)
    return Field(
        mu=pad(pts[:n], 0.0, 3),
        log_scale=jnp.full((cap, 3), -10.0, jnp.float32).at[:n].set(
            jnp.log(jnp.float32(scale))),
        quat=quat,
        logit_o=jnp.full((cap,), -10.0, jnp.float32).at[:n].set(
            math.log(s.seed_opacity / (1 - s.seed_opacity))),
        color=pad(jnp.log(cols / (1 - cols)), 0.0, 3),
        alive=jnp.zeros((cap,), bool).at[:n].set(True))


def _track_loss_and_grads(f: Field, masked, lists, xi, base, rgb, depth,
                          s: Setup):
    fe = silenced(f, masked)

    def loss(xi_, p):
        sp = project(fe._replace(**p), mm(se3_exp(xi_), base), s)
        img, dep, ft = blend(sp, lists, s)
        return slam_loss(img, dep, ft, rgb, depth, s.lambda_pho)

    return jax.value_and_grad(loss, argnums=(0, 1))(xi, params(fe))


def _interval_update(pr: Prune, f: Field, count, s: Setup):
    alive = f.alive & ~pr.masked
    removed = pr.removed + jnp.sum(pr.masked & f.alive).astype(jnp.int32)
    n_alive = jnp.sum(alive.astype(jnp.int32))
    budget = jnp.maximum(
        pr.initial_alive - removed
        - jnp.ceil(pr.initial_alive * (1.0 - s.prune_max_ratio)).astype(jnp.int32), 0)
    want = jnp.minimum(jnp.floor(n_alive * s.prune_step_frac).astype(jnp.int32),
                       budget)
    order = jnp.argsort(jnp.where(alive, pr.score, jnp.inf), stable=True)
    rank = jnp.zeros_like(order).at[order].set(
        jnp.arange(order.shape[0], dtype=order.dtype))
    churn = jnp.where(
        jnp.any(pr.prev_count < 0), 0.0,
        jnp.sum(jnp.abs(count - pr.prev_count))
        / jnp.maximum(jnp.sum(pr.prev_count), 1))
    k = jnp.where(churn > s.prune_churn,
                  jnp.maximum(pr.interval // 2, s.prune_k_min),
                  jnp.minimum(pr.interval * 2, s.prune_k_max)).astype(jnp.int32)
    return (Prune(jnp.zeros_like(pr.score), alive & (rank < want), k, k, count,
                  pr.initial_alive, removed),
            f._replace(alive=alive))


def track(f: Field, pr: Prune, base, rgb, depth, s: Setup):
    """One frame's tracking from the predicted pose ``base``: returns the
    pose delta, the pruned field and state, and the per-iteration losses."""
    lists = tile_lists(project(silenced(f, pr.masked), base, s), s)

    def body(carry, _):
        xi, opt, f, pr, lists = carry
        loss, (g_xi, g_p) = _track_loss_and_grads(f, pr.masked, lists, xi,
                                                  base, rgb, depth, s)
        upd, opt = adam_step(g_xi, opt, s.lr_pose)
        xi = xi + upd
        score = (jnp.linalg.norm(g_p["mu"], axis=-1)
                 + s.prune_lam * (jnp.linalg.norm(g_p["log_scale"], axis=-1)
                                  + jnp.linalg.norm(g_p["quat"], axis=-1)))
        pr = pr._replace(score=pr.score + score, iters_left=pr.iters_left - 1)

        def boundary(op):
            f, pr, _ = op
            fresh = tile_lists(project(silenced(f, pr.masked),
                                       mm(se3_exp(xi), base), s), s)
            count = jnp.sum((fresh >= 0).astype(jnp.int32), axis=1)
            pr, f = _interval_update(pr, f, count, s)
            return f, pr, fresh

        f, pr, lists = jax.lax.cond(pr.iters_left <= 0, boundary,
                                    lambda op: op, (f, pr, lists))
        return (xi, opt, f, pr, lists), loss

    xi0 = jnp.zeros((6,), jnp.float32)
    (xi, _, f, pr, _), losses = jax.lax.scan(
        body, (xi0, adam_zero(xi0), f, pr, lists), None, length=s.iters_track)
    return xi, f, pr, losses


def map_window(f: Field, masked, poses, rgbs, depths, n_valid, s: Setup):
    """Mapping over the first ``n_valid`` of the keyframe window's slots:
    Adam from zero moments on the mean window loss, one slot's lists rebuilt
    round-robin every ``map_rebuild_stride`` iterations; returns the field,
    the per-iteration losses and the render of the newest keyframe."""
    def build(f, pose):
        return tile_lists(project(silenced(f, masked), pose, s), s)

    lists = jnp.stack([build(f, poses[b]) for b in range(n_valid)])
    fe = silenced(f, masked)

    def body(carry, it):
        f, opt, lists = carry

        def view_loss(p, b):
            # Mapping renders with the parameters it optimizes: opacities
            # of mask-pruned rows are not silenced here, only their lists'
            # membership (built above from the silenced field) is fixed.
            sp = project(fe._replace(**p), poses[b], s)
            img, dep, ft = blend(sp, lists[b], s)
            return slam_loss(img, dep, ft, rgbs[b], depths[b], s.lambda_pho)

        def window_loss(p):
            return sum(view_loss(p, b) for b in range(n_valid)) / n_valid

        loss, grads = jax.value_and_grad(window_loss)(params(f))
        upd, opt = adam_step(grads, opt, s.lr_map)
        f = f._replace(**jax.tree.map(lambda p, u: p + u, params(f), upd))
        slot = jnp.mod((it + 1) // s.map_rebuild_stride - 1, n_valid)
        lists = jax.lax.cond(
            jnp.mod(it + 1, s.map_rebuild_stride) == 0,
            lambda ls: ls.at[slot].set(build(f, poses[slot])),
            lambda ls: ls, lists)
        return (f, opt, lists), loss

    (f, _, _), losses = jax.lax.scan(
        body, (f, adam_zero(params(f)), lists),
        jnp.arange(s.iters_map, dtype=jnp.int32))
    pose = poses[n_valid - 1]
    sp = project(silenced(f, masked), pose, s)
    img, _, _ = blend(sp, build(f, pose), s)
    return f, losses, img


def densify(f: Field, masked, rgb, depth, w2c, key, s: Setup) -> Field:
    """Back-project ``densify_per_kf`` pixels drawn at random from the
    2x that many of largest photometric error (valid depth only) into the
    lowest-index dead rows."""
    per = s.densify_per_kf
    sp = project(silenced(f, masked), w2c, s)
    img, _, _ = blend(sp, tile_lists(sp, s), s)
    err = jnp.abs(img - rgb).mean(-1)
    score = jnp.where(depth > 1e-3, err, 0.0).reshape(-1)
    cand = jnp.argsort(-score)[: 2 * per]
    pick = jax.random.permutation(key, cand)[:per]
    v, u = jnp.unravel_index(pick, err.shape)
    d = depth[v, u]
    ok = d > 1e-3
    cam = jnp.stack([(u + 0.5 - s.cx) / s.fx * d,
                     (v + 0.5 - s.cy) / s.fy * d, d], -1)
    c2w = jnp.linalg.inv(w2c)
    pts = mm(cam, c2w[:3, :3].T) + c2w[:3, 3]
    cols = jnp.clip(rgb[v, u], 0.02, 0.98)
    scale = jnp.nanmedian(jnp.where(ok, d, jnp.nan)) / s.fx * 2.0
    new = dict(mu=pts, log_scale=jnp.broadcast_to(jnp.log(scale), (per, 3)),
               quat=jnp.tile(jnp.array([1.0, 0.0, 0.0, 0.0]), (per, 1)),
               logit_o=jnp.full((per,), math.log(0.6 / 0.4)),
               color=jnp.log(cols / (1.0 - cols)))
    # k-th dead row <- k-th valid pick
    cap = f.mu.shape[0]
    dead = jnp.nonzero(~f.alive, size=per, fill_value=cap)[0]
    src = jnp.nonzero(ok, size=per, fill_value=per)[0]
    use = (dead < cap) & (src < per)
    dst = jnp.where(use, dead, cap)
    srcc = jnp.minimum(src, per - 1)
    out = {k: getattr(f, k).at[dst].set(new[k][srcc].astype(jnp.float32),
                                        mode="drop")
           for k in PARAMS}
    return Field(alive=f.alive.at[dst].set(True, mode="drop"), **out)


class Stream:
    """The reference stream: frame 0 seeds and maps the field, then each
    frame is tracked and each keyframe densified and mapped.  Each phase is
    one jitted program, traced at ``precision``."""

    def __init__(self, s: Setup, precision: str = "highest"):
        self.s = s
        self.precision = precision
        self._boot = self._jit(self._boot_fn)
        self._track = self._jit(self._track_fn)
        self._map = self._jit(self._map_fn)

    def _jit(self, fn):
        def traced(*a):
            token = _PRECISION.set(self.precision)
            try:
                with jax.default_matmul_precision("highest"):
                    return fn(*a)
            finally:
                _PRECISION.reset(token)
        return jax.jit(traced)

    def _boot_fn(self, f, pose0, rgb0, depth0):
        masked = jnp.zeros(f.alive.shape, bool)
        f, _, _ = map_window(f, masked, pose0[None], rgb0[None], depth0[None],
                             1, self.s)
        return f

    def _track_fn(self, f, pr, pose, vel, rgb, depth):
        base = mm(vel, pose)
        xi, f, pr, losses = track(f, pr, base, rgb, depth, self.s)
        new_pose = mm(se3_exp(xi), base)
        return f, pr, new_pose, mm(new_pose, jnp.linalg.inv(pose)), losses

    def _map_fn(self, f, masked, pose, rgb, depth, key, poses, rgbs, depths):
        f = densify(f, masked, rgb, depth, pose, key, self.s)
        n = poses.shape[0]
        f, losses, img = map_window(f, masked, poses, rgbs, depths, n, self.s)
        return f, losses, psnr(img, rgb)

    def run(self, frames, n_steps: int, session_seed: int):
        """Frames 0..n_steps of ``frames`` (a list of (rgb, depth, w2c0)
        with w2c0 used for frame 0 only).  Returns per step (1..n_steps) a
        dict of the pose, the tracking losses and, on keyframes, the
        mapping losses and PSNR."""
        s = self.s
        rgb0, depth0, w2c0 = frames[0]
        f = seed_field(np.asarray(rgb0), np.asarray(depth0), np.asarray(w2c0), s)
        pr = Prune(jnp.zeros((s.capacity,), jnp.float32),
                   jnp.zeros((s.capacity,), bool),
                   jnp.asarray(s.prune_k0, jnp.int32),
                   jnp.asarray(s.prune_k0, jnp.int32),
                   jnp.zeros((s.tiles,), jnp.int32),
                   jnp.sum(f.alive.astype(jnp.int32)),
                   jnp.zeros((), jnp.int32))
        pose0 = jnp.asarray(w2c0, jnp.float32)
        f = self._boot(f, pose0, jnp.asarray(rgb0), jnp.asarray(depth0))
        kf = [(pose0, jnp.asarray(rgb0), jnp.asarray(depth0))]
        pose, vel, last_kf = pose0, jnp.eye(4, dtype=jnp.float32), 0
        rng = jax.random.PRNGKey(session_seed)
        out = []
        for i in range(1, n_steps + 1):
            rgb, depth = jnp.asarray(frames[i][0]), jnp.asarray(frames[i][1])
            f, pr, new_pose, vel, losses = self._track(f, pr, pose, vel, rgb,
                                                       depth)
            pose = new_pose
            rec = {"pose": np.asarray(new_pose), "track_losses": np.asarray(losses)}
            if i - last_kf >= s.interval:
                kf = (kf + [(new_pose, rgb, depth)])[-s.map_window:]
                poses, rgbs, depths = (jnp.stack(x) for x in zip(*kf))
                f, mlosses, p = self._map(f, pr.masked, new_pose, rgb, depth,
                                          jax.random.fold_in(rng, i),
                                          poses, rgbs, depths)
                rec["map_losses"] = np.asarray(mlosses)
                rec["psnr"] = float(p)
                last_kf = i
            out.append(rec)
        return out

