"""Pytree optimizers built from scratch (no optax in this environment).

Shared by the SLAM pipeline (pose + Gaussian Adam) and the LM trainer
(AdamW + cosine schedule + global-norm clipping). Functional style:
``init(params) -> state``, ``update(grads, state, params) -> (updates, state)``
— apply with ``apply_updates``.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Callable, NamedTuple, Optional

import jax
import jax.numpy as jnp

from repro.obs.profiling import scoped


class AdamState(NamedTuple):
    step: jnp.ndarray
    mu: Any
    nu: Any


@dataclasses.dataclass(frozen=True)
class Adam:
    lr: float | Callable[[jnp.ndarray], jnp.ndarray] = 1e-3
    b1: float = 0.9
    b2: float = 0.999
    eps: float = 1e-8
    weight_decay: float = 0.0       # AdamW-style decoupled decay
    clip_norm: Optional[float] = None

    def init(self, params) -> AdamState:
        zeros = lambda p: jnp.zeros_like(p)
        return AdamState(
            step=jnp.zeros((), jnp.int32),
            mu=jax.tree.map(zeros, params),
            nu=jax.tree.map(zeros, params),
        )

    def _lr(self, step):
        return self.lr(step) if callable(self.lr) else self.lr

    @scoped("adam")
    def update(self, grads, state: AdamState, params=None):
        """Dtype-preserving update: every tensor op stays in the leaf's own
        dtype (bf16 moments in -> bf16 moments out). Mixing in f32 scalars
        would promote whole param-sized temporaries to f32 AND break
        donation aliasing (donated bf16 buffers can't alias f32 outputs) —
        measured at +30 GB/device on llama3-405b before this was fixed."""
        step = state.step + 1
        if self.clip_norm is not None:
            gnorm = global_norm(grads)
            scale = jnp.minimum(1.0, self.clip_norm / (gnorm + 1e-12))
            grads = jax.tree.map(lambda g: g * scale.astype(g.dtype), grads)

        mu = jax.tree.map(lambda m, g: self.b1 * m + (1 - self.b1) * g, state.mu, grads)
        nu = jax.tree.map(lambda v, g: self.b2 * v + (1 - self.b2) * g * g, state.nu, grads)
        bc1 = 1.0 - self.b1 ** step.astype(jnp.float32)
        bc2 = 1.0 - self.b2 ** step.astype(jnp.float32)
        lr = jnp.asarray(self._lr(step), jnp.float32)
        a = lr / bc1                       # f32 scalars, cast per leaf below
        inv_sqrt_bc2 = jax.lax.rsqrt(bc2)

        def upd(m, v, p):
            dt = m.dtype
            u = -a.astype(dt) * m / (jnp.sqrt(v) * inv_sqrt_bc2.astype(dt)
                                     + jnp.asarray(self.eps, dt))
            if self.weight_decay and p is not None:
                u = u - (lr * self.weight_decay).astype(dt) * p
            return u

        if params is None:
            updates = jax.tree.map(lambda m, v: upd(m, v, None), mu, nu)
        else:
            updates = jax.tree.map(upd, mu, nu, params)
        return updates, AdamState(step=step, mu=mu, nu=nu)

    @scoped("adam")
    def update_masked(self, grads, state: AdamState, row_mask, params=None):
        """Row-masked :meth:`update` for the sparse stable/unstable path:
        rows where ``row_mask`` is False (stable Gaussians) get a zero
        update and keep their first/second moments untouched, so a frozen
        Gaussian's optimizer state is exactly what it was when it froze.
        The shared () step counter still advances (bias correction is a
        global scalar).

        With an all-True mask this is **bitwise-equal** to :meth:`update`
        (``jnp.where(True, new, old) == new``) — the dense oracle the
        sparse engine tests hold it to.  ``row_mask`` is (N,) bool and
        broadcasts over each leaf's trailing dims."""
        updates, new = self.update(grads, state, params)
        sel = lambda n, o: jnp.where(_row_mask(row_mask, n), n, o)
        return (
            jax.tree.map(lambda u: sel(u, jnp.zeros_like(u)), updates),
            AdamState(step=new.step,
                      mu=jax.tree.map(sel, new.mu, state.mu),
                      nu=jax.tree.map(sel, new.nu, state.nu)),
        )


def gather_rows(state: AdamState, idx: jnp.ndarray) -> AdamState:
    """Row-gather an Adam state whose moment leaves are (N, ...)-shaped onto
    a paged view: ``idx`` is the (M,) storage-row index per view row.  The
    shared () step counter passes through (bias correction is global)."""
    take = lambda leaf: leaf[idx]
    return AdamState(step=state.step,
                     mu=jax.tree.map(take, state.mu),
                     nu=jax.tree.map(take, state.nu))


def scatter_rows(full: AdamState, view: AdamState,
                 idx: jnp.ndarray) -> AdamState:
    """Scatter a paged view's moment rows back into full storage; the step
    counter comes from the view (that is where updates ran)."""
    put = lambda f, v: f.at[idx].set(v)
    return AdamState(step=view.step,
                     mu=jax.tree.map(put, full.mu, view.mu),
                     nu=jax.tree.map(put, full.nu, view.nu))


class SGDState(NamedTuple):
    step: jnp.ndarray
    momentum: Any


@dataclasses.dataclass(frozen=True)
class SGD:
    lr: float = 1e-2
    momentum: float = 0.0

    def init(self, params) -> SGDState:
        return SGDState(
            step=jnp.zeros((), jnp.int32),
            momentum=jax.tree.map(jnp.zeros_like, params),
        )

    def update(self, grads, state: SGDState, params=None):
        mom = jax.tree.map(lambda m, g: self.momentum * m + g, state.momentum, grads)
        updates = jax.tree.map(lambda m: -self.lr * m, mom)
        return updates, SGDState(step=state.step + 1, momentum=mom)


def _row_mask(mask, x):
    """Broadcast a (N,) row mask over a (N, ...) leaf."""
    return mask.reshape(mask.shape + (1,) * (x.ndim - 1))


@scoped("adam")
def apply_updates(params, updates):
    return jax.tree.map(lambda p, u: p + u.astype(p.dtype), params, updates)


@scoped("adam")
def apply_updates_masked(params, updates, row_mask):
    """:func:`apply_updates` restricted to rows where ``row_mask`` is True.

    Frozen rows return the ORIGINAL param array values (a ``where`` select,
    not ``p + 0``, which would flip ``-0.0`` to ``+0.0``) — stable Gaussians
    stay bit-frozen across mapping iterations.  All-True mask ==
    :func:`apply_updates` bitwise."""
    def one(p, u):
        return jnp.where(_row_mask(row_mask, p), p + u.astype(p.dtype), p)
    return jax.tree.map(one, params, updates)


def global_norm(tree) -> jnp.ndarray:
    leaves = jax.tree.leaves(tree)
    return jnp.sqrt(sum(jnp.sum(jnp.square(l.astype(jnp.float32))) for l in leaves))


def cosine_schedule(base_lr: float, warmup: int, total: int, floor: float = 0.1):
    """Linear warmup then cosine decay to ``floor * base_lr``."""

    def lr(step):
        step = step.astype(jnp.float32)
        warm = base_lr * step / max(warmup, 1)
        frac = jnp.clip((step - warmup) / max(total - warmup, 1), 0.0, 1.0)
        cos = floor * base_lr + (1 - floor) * base_lr * 0.5 * (1 + jnp.cos(jnp.pi * frac))
        return jnp.where(step < warmup, warm, cos)

    return lr
