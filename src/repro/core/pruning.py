"""§4.1 Adaptive Gaussian Pruning — gradient-reuse importance + mask-prune.

Importance (Eq. 7):  Score_k = ||dL/dmu_k|| + lambda * ||dL/dSigma_k||

The gradients are the ones tracking BP already computes to optimize the pose
(dL/dpose factors through dL/dGaussian), so scoring is free — the paper's
central "no identification overhead" property. Sigma is covariance; under
our (log_scale, quat) parameterization we take ||dL/dlog_scale|| +
||dL/dquat|| as the covariance-gradient norm (recorded hardware-adaptation:
reparameterized covariance, same information up to the fixed Jacobian of the
parameterization).

Mask-prune protocol (verbatim from the paper):
  * scores accumulate over the current interval of K iterations;
  * at the interval end, the lowest-score alive Gaussians (``step_frac`` of
    the alive set, subject to the global ``max_ratio`` cap — Fig. 14a shows
    >=50% pruning degrades sharply, so the cap defaults to 0.5) are MASKED:
    excluded from rendering but kept resident;
  * at the next interval end the previously-masked set is PERMANENTLY
    removed (alive=False) — the one-interval grace period lets the
    tile-intersection churn ratio be computed over the unpruned set;
  * interval adaptation: churn > 5%  -> K <- K/2  (scene moving fast,
    re-evaluate sooner); else K <- 2K (stable, prune lazily).

Fragment lists are rebuilt only at interval boundaries; within the interval
the cached lists are reused (the paper reuses Step 1-2 + Step 2 results),
with masked Gaussians silenced through zeroed opacity.

Stable/unstable stability bit (RTG-SLAM / Splatonic sparsity)
-------------------------------------------------------------
On top of the removal protocol, :class:`PruneState` carries a per-Gaussian
**stability bit**: a Gaussian whose Eq. 7 gradient-magnitude EMA has stayed
below a (relative) threshold for ``stable_age`` consecutive tracking
iterations is *stable* — converged, safe to freeze.  The EMA/age update
rides :func:`accumulate`, i.e. it reuses the §4.1 tracking gradients and
costs **zero extra backward passes** (the same gradient-reuse trick as the
importance score itself).  The sparse mapping path
(``SLAMConfig.sparse_opt=True``) consumes the bit three ways: masked Adam
(stable params bit-frozen), stability-masked fragment builds (stable
Gaussians emit no fragments), and the WSU schedule built from the masked
counts (stable-only tiles get zero-trip programs).  A Gaussian whose EMA
rises back above the threshold resets its age and thaws immediately;
densified newcomers are reset via :func:`mark_born`.
"""

from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp

from repro.core.gaussians import GaussianField
from repro.obs.profiling import scoped


class PruneConfig(NamedTuple):
    lam: float = 0.8            # lambda in Eq. 7 (paper's fixed setting)
    k0: int = 5                 # initial pruning interval K0
    churn_threshold: float = 0.05
    step_frac: float = 0.10     # fraction of alive Gaussians masked per interval
    max_ratio: float = 0.5      # global pruning cap (Fig. 14a)
    k_min: int = 2
    k_max: int = 40
    # -- stability bit (sparse stable/unstable optimization) ---------------
    stable_ema_beta: float = 0.8   # EMA decay of the Eq. 7 score per iteration
    stable_rel: float = 0.5        # stable when EMA < stable_rel * mean alive
                                   # EMA (relative: robust across scene scale)
    stable_thresh: float = 0.0     # absolute EMA floor OR-ed into the test
    stable_age: int = 8            # consecutive low-EMA iterations to freeze
    stable_warmup: int = 0         # accumulate() calls before bits may set:
                                   # the early map trains dense (bitwise —
                                   # an all-False mask IS the oracle) and
                                   # only the late, converged trajectory
                                   # freezes.  EMA/age still mature during
                                   # warmup, so quiet Gaussians freeze the
                                   # moment it ends.


class PruneState(NamedTuple):
    score: jnp.ndarray          # (N,) accumulated importance this interval
    masked: jnp.ndarray         # (N,) bool — mask-pruned, pending removal
    interval: jnp.ndarray       # () int32 current K
    iters_left: jnp.ndarray     # () int32 iterations until interval end
    prev_tile_count: jnp.ndarray  # (T,) int32 fragment counts at last boundary
    initial_alive: jnp.ndarray  # () int32 alive count at frame start (for cap)
    removed: jnp.ndarray        # () int32 total permanently removed
    grad_ema: jnp.ndarray       # (N,) f32 Eq. 7 gradient-magnitude EMA
    age: jnp.ndarray            # (N,) i32 consecutive low-EMA iterations
    stable: jnp.ndarray         # (N,) bool — stability bit (age >= stable_age)
    opt_steps: jnp.ndarray      # () i32 total accumulate() calls — the
                                #   stable_warmup clock


def init_state(g: GaussianField, num_tiles: int, cfg: PruneConfig) -> PruneState:
    n = g.capacity
    return PruneState(
        score=jnp.zeros((n,), jnp.float32),
        masked=jnp.zeros((n,), bool),
        interval=jnp.asarray(cfg.k0, jnp.int32),
        iters_left=jnp.asarray(cfg.k0, jnp.int32),
        prev_tile_count=jnp.zeros((num_tiles,), jnp.int32),
        initial_alive=g.num_alive().astype(jnp.int32),
        removed=jnp.zeros((), jnp.int32),
        grad_ema=jnp.zeros((n,), jnp.float32),
        age=jnp.zeros((n,), jnp.int32),
        stable=jnp.zeros((n,), bool),
        opt_steps=jnp.zeros((), jnp.int32),
    )


#: The (N,)-shaped per-Gaussian leaves of :class:`PruneState`.  Everything
#: else is a scalar or the (T,) ``prev_tile_count`` and rides through a
#: paged-view gather/scatter untouched.
ROW_FIELDS = ("score", "masked", "grad_ema", "age", "stable")


def gather_rows(state: PruneState, idx: jnp.ndarray) -> PruneState:
    """Row-gather the per-Gaussian leaves onto a paged view: ``idx`` is the
    (M,) storage-row index per view row.  Scalars and ``prev_tile_count``
    pass through (they are map-global, not per-row)."""
    return state._replace(**{f: getattr(state, f)[idx] for f in ROW_FIELDS})


def scatter_rows(full: PruneState, view: PruneState,
                 idx: jnp.ndarray) -> PruneState:
    """Scatter a paged view's per-Gaussian leaves back into full storage and
    take every map-global leaf (scalars + ``prev_tile_count``) from the
    view — the view is where the step ran, so its clocks/baselines are the
    current ones."""
    out = {f: getattr(full, f).at[idx].set(getattr(view, f))
           for f in ROW_FIELDS}
    return view._replace(**out)


def importance_scores(param_grads: dict, cfg: PruneConfig) -> jnp.ndarray:
    """Eq. 7 from the gradients tracking BP already produced."""
    g_mu = jnp.linalg.norm(param_grads["mu"], axis=-1)
    g_cov = jnp.linalg.norm(param_grads["log_scale"], axis=-1) + jnp.linalg.norm(
        param_grads["quat"], axis=-1
    )
    return g_mu + cfg.lam * g_cov


@scoped("prune")
def accumulate(state: PruneState, param_grads: dict, cfg: PruneConfig,
               alive: jnp.ndarray | None = None) -> PruneState:
    """Per-tracking-iteration score accumulation (jit-safe).

    With ``alive`` (the field's (N,) alive mask) the stability bit is
    maintained too, from the same Eq. 7 scores — gradient-magnitude EMA,
    consecutive-low-EMA age, and ``stable = alive & (age >= stable_age)``.
    The threshold is relative (``stable_rel`` x mean alive EMA, with the
    heavy-tailed unstable set pulling the mean up) OR-ed with the absolute
    ``stable_thresh`` floor, and the bit is additionally gated by the
    ``stable_warmup`` clock (``opt_steps``): during warmup EMA and age
    mature but nothing freezes, so the early (unconverged) map always
    trains dense.  Without ``alive`` only the score accumulates (the
    pre-stability behavior)."""
    s = importance_scores(param_grads, cfg)
    new_score = state.score + s
    iters_left = state.iters_left - 1
    opt_steps = state.opt_steps + 1
    if alive is None:
        return state._replace(score=new_score, iters_left=iters_left,
                              opt_steps=opt_steps)
    alive_f = alive.astype(jnp.float32)
    ema = cfg.stable_ema_beta * state.grad_ema + (1.0 - cfg.stable_ema_beta) * s
    mean_ema = jnp.sum(ema * alive_f) / jnp.maximum(jnp.sum(alive_f), 1.0)
    thresh = jnp.maximum(cfg.stable_rel * mean_ema, cfg.stable_thresh)
    low = alive & (ema < thresh)
    age = jnp.where(low, state.age + 1, 0)
    return state._replace(
        score=new_score,
        iters_left=iters_left,
        opt_steps=opt_steps,
        grad_ema=ema,
        age=age,
        stable=alive & (age >= cfg.stable_age)
               & (opt_steps >= cfg.stable_warmup),
    )


def optimizable_mask(state: PruneState) -> jnp.ndarray:
    """(N,) bool — the rows the sparse mapping path optimizes and rasterizes:
    everything not stability-frozen.  Dead/masked rows stay in the mask on
    purpose: they are already silenced and carry ~zero gradients, and keeping
    them is what makes the all-unstable case bitwise-equal to the dense
    path (``jnp.where(True, new, old) == new``)."""
    return ~state.stable


def mark_born(state: PruneState, born: jnp.ndarray) -> PruneState:
    """Reset stability for newly inserted Gaussians.  Densification writes
    into previously-dead slots whose stale EMA/age would otherwise freeze a
    newcomer for its first mapping phase — exactly the Gaussians mapping
    must optimize hardest."""
    return state._replace(
        grad_ema=jnp.where(born, 0.0, state.grad_ema),
        age=jnp.where(born, 0, state.age),
        stable=state.stable & ~born,
    )


def effective_opacity_mask(g: GaussianField, state: PruneState) -> jnp.ndarray:
    """(N,) multiplier silencing mask-pruned Gaussians in cached fragment
    lists (they stay listed until the next rebuild; zero opacity = zero
    alpha = excluded from rendering, per the paper's mask-prune)."""
    return (~state.masked).astype(jnp.float32)


def retile_state(state: PruneState, num_tiles: int,
                 baselines: dict | None = None) -> PruneState:
    """Host-side shape adaptation when the render stage (downsample factor)
    changes between frames: the carried ``prev_tile_count`` must match the
    new grid's tile count for the scan/cond bundles to trace.

    With ``baselines`` (a host dict keyed by tile count), the displaced
    grid's baseline is parked there and the target grid's previous baseline
    is restored, so churn at a later same-grid boundary still compares
    against real counts.  A grid seen for the first time gets the ``-1``
    sentinel, which ``interval_update`` reads as "no comparable baseline →
    churn 0".

    Only ``prev_tile_count`` is tile-shaped; every per-Gaussian leaf —
    including the stability leaves ``grad_ema``/``age``/``stable`` — is
    (N,)-shaped and carried through ``_replace`` untouched, so a factor
    switch never thaws or freezes anything
    (tests/test_pruning_downsample.py::test_retile_carries_stability_leaves).
    """
    cur = state.prev_tile_count
    if cur.shape[0] == num_tiles:
        return state
    if baselines is not None:
        baselines[cur.shape[0]] = cur
        restored = baselines.get(num_tiles)
        if restored is not None:
            return state._replace(prev_tile_count=restored)
    return state._replace(
        prev_tile_count=jnp.full((num_tiles,), -1, jnp.int32)
    )


def interval_update(
    state: PruneState,
    g: GaussianField,
    tile_count: jnp.ndarray,
    cfg: PruneConfig,
) -> tuple[PruneState, GaussianField, jnp.ndarray]:
    """Interval-boundary step (jit-safe): permanently remove the previously
    masked set, mask the next lowest-score batch, adapt K from tile churn.

    Returns (new_state, new_field, did_anything).
    """
    # 1. Permanent removal of last interval's masked set.
    alive = g.alive & ~state.masked
    removed = state.removed + jnp.sum(state.masked & g.alive).astype(jnp.int32)

    # 2. Select the next mask batch by accumulated score.
    alive_count = jnp.sum(alive.astype(jnp.int32))
    budget_left = jnp.maximum(
        state.initial_alive
        - removed
        - jnp.ceil(state.initial_alive * (1.0 - cfg.max_ratio)).astype(jnp.int32),
        0,
    )
    want = jnp.minimum(
        jnp.floor(alive_count * cfg.step_frac).astype(jnp.int32), budget_left
    )
    score = jnp.where(alive, state.score, jnp.inf)  # only alive are candidates
    order = jnp.argsort(score)  # ascending: least important first
    rank = jnp.zeros((g.capacity,), jnp.int32).at[order].set(
        jnp.arange(g.capacity, dtype=jnp.int32)
    )
    new_mask = alive & (rank < want)

    # 3. Adapt the interval from tile-Gaussian intersection churn (§4.1).
    # A negative prev_tile_count is the ``retile_state`` sentinel: the grid
    # changed since the last boundary, so there is no comparable baseline
    # and churn is defined as zero (interval grows).
    denom = jnp.maximum(jnp.sum(state.prev_tile_count), 1)
    churn = jnp.where(
        jnp.any(state.prev_tile_count < 0),
        0.0,
        jnp.sum(jnp.abs(tile_count - state.prev_tile_count)) / denom,
    )
    k_next = jnp.where(
        churn > cfg.churn_threshold,
        jnp.maximum(state.interval // 2, cfg.k_min),
        jnp.minimum(state.interval * 2, cfg.k_max),
    ).astype(jnp.int32)

    new_state = PruneState(
        score=jnp.zeros_like(state.score),
        masked=new_mask,
        interval=k_next,
        iters_left=k_next,
        prev_tile_count=tile_count,
        initial_alive=state.initial_alive,
        removed=removed,
        grad_ema=state.grad_ema,
        age=state.age,
        stable=state.stable & alive,  # removed rows can never stay frozen
        opt_steps=state.opt_steps,
    )
    return new_state, g._replace(alive=alive), want > 0


@scoped("prune")
def cond_interval_update(
    state: PruneState,
    g: GaussianField,
    cur_frags,
    build_fn,
    cfg: PruneConfig,
):
    """Scan-body form of the interval boundary: when ``iters_left`` has run
    out, rebuild fragment lists (``build_fn(g, masked) -> FragmentLists``)
    and run :func:`interval_update` — all under ``lax.cond`` so the whole
    tracking loop stays a single device dispatch.  Off-boundary iterations
    pass ``state``/``g``/``cur_frags`` through unchanged.

    Returns ``(state, g, frags, fired)`` with ``fired`` a () bool.
    """

    def boundary(operand):
        st, gg, _ = operand
        fresh = build_fn(gg, st.masked)
        new_st, new_g, _ = interval_update(st, gg, fresh.count, cfg)
        return new_st, new_g, fresh

    def steady(operand):
        return operand

    fired = state.iters_left <= 0
    state, g, frags = jax.lax.cond(fired, boundary, steady, (state, g, cur_frags))
    return state, g, frags, fired


def prune_ratio(state: PruneState) -> jnp.ndarray:
    return state.removed / jnp.maximum(state.initial_alive, 1)
