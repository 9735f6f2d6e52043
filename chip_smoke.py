#!/usr/bin/env python3
"""Chip smoke test: the fused RTGS SLAM step at TUM RGB-D size on a TPU.

Run from the repository root (it imports ``src/repro`` next to itself):

    python3 chip_smoke.py              # one chip: phases (a)-(d) below
    python3 chip_smoke.py --chips 4    # only the sharded serving path, 4 chips
    python3 chip_smoke.py --rehearse   # CPU dry run at tiny sizes

Phases on one chip, each of which exits non-zero on failure:

(a) the device: platform, kind and count.  No TPU, no run — there is no
    CPU fallback.
(b) one 640x480 stream (the TUM RGB-D shape: 1,200 16-px tiles, K=128
    fragments per tile, a 131,072-Gaussian pool) through ``make_dataset``
    and ``run_sequence`` on the WSU-scheduled Pallas kernels with §4.1
    pruning in the fused one-dispatch step; then the same stream again,
    each step timed to ``block_until_ready``.
(c) the first frames of (b) against the pure-jnp ``ref`` oracle (poses and
    keyframe PSNR), and one render plus its gradients from the compiled
    kernels against ``ref`` on identical inputs.
(d) the served path: ``ShardedPool`` + ``SlamServer`` with two streams,
    one dispatch per frame-step, each row bitwise-equal to its solo run.

``--chips 4`` runs only the sharded pool over a 4-device ``"data"`` mesh
(one row per chip) against single-device ``step_many`` of the same
sessions.  ``--rehearse`` runs the same phases on the CPU at tiny sizes
(Pallas in interpret mode) and never reports a chip result.

The last line of a chip run is one JSON object:
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": ...}}``.
"""

from __future__ import annotations

import argparse
import dataclasses
import json
import os
import sys

import numpy as np

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "src"))


@dataclasses.dataclass(frozen=True)
class Size:
    height: int
    width: int
    capacity: int       # Gaussian pool per stream
    gt_gaussians: int   # synthetic scene the frames are rendered from
    frames: int         # frames of the (b) stream, frame 0 included


# TUM RGB-D (Sturm et al., IROS 2012): 640x480 frames; a 10^5-Gaussian map.
FULL = Size(height=480, width=640, capacity=131072, gt_gaussians=8192,
            frames=9)
TINY = Size(height=48, width=64, capacity=1024, gt_gaussians=400, frames=9)
# The 4-chip phase compiles a four-row step for one chip as its baseline,
# besides the sharded one: 320x240 frames (a quarter of TUM's) and a
# smaller pool keep those compiles short.
SHARDED = Size(height=240, width=320, capacity=8192, gt_gaussians=2048,
               frames=9)

K = 128                 # fragments per tile
KF_INTERVAL = 4         # keyframes at frames 0, 4, 8: mapping runs twice
REF_FRAMES = 5          # (c) compares frames 0..4 (one mapped keyframe)
SERVE_STEPS = 4         # (d)/(4-chip) frame-steps, keyframe included

# (c) bounds.  The render and its gradients: tests/test_kernels.py's
# kernel-vs-ref bounds, inputs being identical.  Poses and keyframe PSNR
# after whole frames: 5x the fused-vs-per-iteration bounds of
# tests/test_session.py (2e-3, 0.2 dB), which hold for a 400-Gaussian
# scene; with ~3x10^4 seeded Gaussians, Adam's normalized steps turn
# rounding-level gradient differences into visibly different parameters
# (the one-ulp line of (c) shows how far on the chip).
POSE_TOL = 5 * 2e-3
PSNR_TOL_DB = 5 * 0.2
IMG_TOL = dict(atol=2e-5, rtol=1e-4)
DEPTH_TOL = dict(atol=1e-4, rtol=1e-4)
GRAD_RTOL = 3e-5        # of the largest |gradient| of the leaf,
GRAD_ATOL = 3e-6        # or this, whichever is larger

COMPILE_EVENTS = "/jax/core/compile/"


def log(*parts) -> None:
    print(*parts, flush=True)


class CompileClock:
    """Seconds JAX spends tracing, lowering and compiling, from its own
    monitoring events (cache hits skip the backend compile)."""

    def __init__(self):
        import jax

        self.total = 0.0
        jax.monitoring.register_event_duration_secs_listener(self._on_event)

    def _on_event(self, event, duration, **_):
        if event.startswith(COMPILE_EVENTS):
            self.total += duration


def leaves_equal(a, b) -> bool:
    import jax

    la, ta = jax.tree.flatten(a)
    lb, tb = jax.tree.flatten(b)
    if ta != tb:
        return False
    return all(np.array_equal(np.asarray(x), np.asarray(y), equal_nan=True)
               if np.issubdtype(np.asarray(x).dtype, np.floating)
               else np.array_equal(np.asarray(x), np.asarray(y))
               for x, y in zip(la, lb))


def slam_config(size: Size, backend: str = "schedule"):
    from repro.core.keyframes import KeyframePolicy
    from repro.core.pruning import PruneConfig
    from repro.slam.session import SLAMConfig

    # scan_unroll=1: the rolled scan bodies compile once (the CPU-tuned
    # unroll of 4 only multiplies compile time on the chip).
    return SLAMConfig(backend=backend, capacity=size.capacity,
                      frag_capacity=K, prune=PruneConfig(), fused=True,
                      keyframe=KeyframePolicy(kind="monogs",
                                              interval=KF_INTERVAL),
                      scan_unroll=1)


def dataset(name: str, size: Size, frames: int, seed: int):
    from repro.slam.datasets import make_dataset

    return make_dataset(name, num_frames=frames, height=size.height,
                        width=size.width, num_gaussians=size.gt_gaussians,
                        seed=seed, frag_capacity=K)


# ---------------------------------------------------------------------------
# (b) one stream at full size
# ---------------------------------------------------------------------------


def phase_stream(size: Size, clock: CompileClock):
    import jax

    from repro.kernels import resolve_interpret
    from repro.obs import Stopwatch
    from repro.slam.engine import get_stage
    from repro.slam.session import run_sequence, session_init, session_step

    ds = dataset("room0", size, size.frames, seed=0)
    cfg = slam_config(size)
    plan = get_stage(ds.intrinsics, cfg, 1).plan
    log(f"[b] backend={cfg.backend} interpret={plan.interpret} "
        f"frames={ds.num_frames} {size.width}x{size.height} "
        f"tiles={plan.grid.num_tiles} K={K} pool={cfg.capacity} "
        f"prune=on fused={cfg.fused} scan_unroll={cfg.scan_unroll}")
    on_chip = jax.default_backend() != "cpu"
    if on_chip and (plan.interpret or resolve_interpret()):
        raise AssertionError("Pallas kernels would run in the interpreter "
                             "on the chip")

    c0, sw = clock.total, Stopwatch()
    res = run_sequence(ds, cfg)
    cold_s, compile_s = sw.elapsed(), clock.total - c0
    poses = np.stack(res.est_w2c)
    if not (np.isfinite(poses).all() and np.isfinite(res.ate)
            and np.isfinite(res.keyframe_psnr).all()):
        raise AssertionError("non-finite poses, ATE or PSNR")
    log(f"[b] run_sequence: ATE={res.ate * 100:.3f} cm "
        f"mean_psnr={res.mean_psnr:.3f} dB keyframe_psnr="
        f"{[round(p, 3) for p in res.keyframe_psnr]} "
        f"seeded={res.alive_per_frame[0]} alive_end={res.alive_per_frame[-1]} "
        f"dispatches={res.dispatches} syncs={res.syncs}")
    log(f"[b] cold run {cold_s:.1f} s, of which compile {compile_s:.1f} s")

    # Warm: the same stream, each step blocked on.  The solo step donates
    # its session, so the input's buffers must be gone after the call.
    sess = session_init(ds, cfg)
    jax.block_until_ready(sess)
    step_ms, is_kf = [], []
    for i in range(1, ds.num_frames):
        donated = sess.g.mu
        sw = Stopwatch()
        sess, out = session_step(sess, ds.frames[i])
        jax.block_until_ready((sess, out))
        step_ms.append(sw.elapsed() * 1e3)
        is_kf.append(bool(out.is_kf))
        if not donated.is_deleted():
            raise AssertionError("session_step did not donate its session")
    kf_ms = [t for t, k in zip(step_ms, is_kf) if k]
    track_ms = [t for t, k in zip(step_ms, is_kf) if not k]
    log(f"[b] steady ms/frame: mean={np.mean(step_ms):.2f} "
        f"tracking-only={np.mean(track_ms):.2f} "
        f"keyframe={np.mean(kf_ms) if kf_ms else float('nan'):.2f} "
        f"per-frame={[round(t, 2) for t in step_ms]}")
    return ds, cfg, res


# ---------------------------------------------------------------------------
# (c) agreement with the ref oracle
# ---------------------------------------------------------------------------


def phase_render_agreement(ds):
    """One render and its gradients, compiled kernels vs the oracle, on
    identical inputs (the GT scene from frame 1's pose)."""
    import jax
    import jax.numpy as jnp

    from repro.core.camera import Camera
    from repro.core.lie import f32_jit
    from repro.core.raster_api import RasterInputs, RasterPlan
    from repro.core.render import render
    from repro.core.sorting import make_tile_grid
    from repro.kernels import ops

    grid = make_tile_grid(ds.intrinsics.height, ds.intrinsics.width)
    plan_ref = RasterPlan(grid=grid, backend="ref", capacity=K)
    out = f32_jit(lambda w2c: render(ds.gt_field, Camera(ds.intrinsics, w2c),
                                     plan_ref))(jnp.asarray(ds.frames[1].w2c_gt))
    proj, frags = out.proj, out.frags
    target = jnp.asarray(ds.frames[2].rgb)
    args = (proj.mu2d, proj.conic, proj.color, proj.opacity, proj.depth)
    names = ("mu2d", "conic", "color", "opacity", "depth")

    def grad_atol(ref_grad):
        return max(GRAD_ATOL, GRAD_RTOL * float(np.max(np.abs(ref_grad))))

    def loss_and_grads(plan):
        def loss(*a):
            img, dep, ft = ops.rasterize(RasterInputs(*a, frags=frags), plan)
            value = (jnp.mean((img - target) ** 2) + 0.1 * jnp.mean(dep)
                     + 0.05 * jnp.mean(ft))
            return value, (img, dep, ft)

        fn = f32_jit(jax.value_and_grad(loss, argnums=tuple(range(5)),
                                        has_aux=True))
        (_, fwd), grads = fn(*args)
        return [np.asarray(x) for x in fwd], [np.asarray(g) for g in grads]

    fwd_r, g_r = loss_and_grads(plan_ref)
    for backend in ("schedule", "pallas"):
        fwd_k, g_k = loss_and_grads(
            dataclasses.replace(plan_ref, backend=backend))
        # Per leaf: the largest |kernel - ref| over the leaf's tolerance.
        used = [float(np.max(np.abs(a - b))) / grad_atol(b)
                for a, b in zip(g_k, g_r)]
        log(f"[c] render {backend} vs ref: max |image diff|="
            f"{float(np.max(np.abs(fwd_k[0] - fwd_r[0]))):.3e}, "
            f"max |depth diff|={float(np.max(np.abs(fwd_k[1] - fwd_r[1]))):.3e}"
            f", grad max |diff| / tolerance "
            f"{dict(zip(names, (f'{u:.3g}' for u in used)))} (1 passes); "
            f"fragments={int(frags.total)} overflow={int(frags.overflow)}")
        for a, b, tol, what in zip(fwd_k, fwd_r,
                                   (IMG_TOL, DEPTH_TOL, IMG_TOL),
                                   ("image", "depth", "final_T")):
            np.testing.assert_allclose(a, b, err_msg=f"{backend} {what}",
                                       **tol)
        for a, b, name in zip(g_k, g_r, names):
            np.testing.assert_allclose(a, b, atol=grad_atol(b),
                                       err_msg=f"{backend} grad {name}")


def _ref_prefix(ds, cfg):
    """Frames 0..REF_FRAMES-1 of ``ds`` on the ``ref`` backend."""
    from repro.slam.session import (
        session_finalize,
        session_init,
        session_step,
    )

    sess = session_init(ds, dataclasses.replace(cfg, backend="ref"),
                        max_frames=REF_FRAMES)
    for i in range(1, REF_FRAMES):
        sess, _ = session_step(sess, ds.frames[i])
    return session_finalize(sess, gt_w2c=[f.w2c_gt for f in ds.frames])


def _divergence(a, b):
    """(max |pose entry diff|, max |keyframe PSNR diff|) of two runs over
    their common frames and keyframes."""
    n, k = min(len(a.est_w2c), len(b.est_w2c)), min(
        len(a.keyframe_psnr), len(b.keyframe_psnr))
    pose = np.max(np.abs(np.stack(a.est_w2c[:n]) - np.stack(b.est_w2c[:n])))
    psnr = np.max(np.abs(np.asarray(a.keyframe_psnr[:k])
                         - np.asarray(b.keyframe_psnr[:k])))
    return float(pose), float(psnr)


def phase_session_agreement(ds, cfg, res):
    """The first frames of (b) against the ``ref`` oracle.  Printed beside
    it, for scale: how far ``ref`` itself moves when its input frames move
    by one float32 ulp — the amplification of rounding by the tracking and
    mapping optimizers at this map size."""
    from repro.slam.datasets import Frame

    ref = _ref_prefix(ds, cfg)
    pose_diff, psnr_diff = _divergence(res, ref)
    ulp = dataclasses.replace(ds, frames=[
        Frame(rgb=np.nextafter(f.rgb, np.float32(np.inf)),
              depth=np.where(f.depth > 0,  # 0 stays "no depth"
                             np.nextafter(f.depth, np.float32(np.inf)), 0.0
                             ).astype(np.float32),
              w2c_gt=f.w2c_gt) for f in ds.frames[:REF_FRAMES]])
    floor_pose, floor_psnr = _divergence(_ref_prefix(ulp, cfg), ref)
    log(f"[c] schedule vs ref over frames 0..{REF_FRAMES - 1}: "
        f"max |pose diff|={pose_diff:.3e} (bound {POSE_TOL:g}), "
        f"max |keyframe PSNR diff|={psnr_diff:.4f} dB (bound "
        f"{PSNR_TOL_DB:g}); schedule PSNR "
        f"{[round(p, 3) for p in res.keyframe_psnr[:len(ref.keyframe_psnr)]]}"
        f", ref PSNR {[round(p, 3) for p in ref.keyframe_psnr]}")
    log(f"[c] ref vs ref on frames moved by one ulp: max |pose diff|="
        f"{floor_pose:.3e}, max |keyframe PSNR diff|={floor_psnr:.4f} dB")
    if not pose_diff <= POSE_TOL:
        raise AssertionError(f"pose diff {pose_diff} > {POSE_TOL}")
    if not psnr_diff <= PSNR_TOL_DB:
        raise AssertionError(f"PSNR diff {psnr_diff} > {PSNR_TOL_DB}")


# ---------------------------------------------------------------------------
# (d) served path on one chip; the 4-chip sharded pool
# ---------------------------------------------------------------------------


def serve(streams, cfg, mesh):
    """Queue-fed lockstep serving of ``streams`` for SERVE_STEPS steps;
    returns the pool after the drain."""
    from repro.slam.server import ShardedPool, SlamServer
    from repro.slam.session import session_init

    pool = ShardedPool([session_init(d, cfg, max_frames=SERVE_STEPS + 1)
                        for d in streams], mesh=mesh)
    srv = SlamServer(pool)
    for t in range(1, SERVE_STEPS + 1):
        for i, d in enumerate(streams):
            srv.submit(i, d.frames[t])
        srv.pump()
    srv.drain()
    ratio = pool.stats.dispatches / srv.stats.steps
    if srv.stats.steps != SERVE_STEPS or ratio != 1.0:
        raise AssertionError(f"{pool.stats.dispatches} dispatches for "
                             f"{srv.stats.steps} frame-steps")
    return pool, ratio


def phase_served(size: Size, ds, cfg):
    from repro.launch.mesh import make_data_mesh
    from repro.slam.session import session_init, session_step

    streams = [ds, dataset("stairs0", size, SERVE_STEPS + 1, seed=1)]
    pool, ratio = serve(streams, cfg, make_data_mesh(1))
    for i, d in enumerate(streams):
        solo = session_init(d, cfg, max_frames=SERVE_STEPS + 1)
        for t in range(1, SERVE_STEPS + 1):
            solo, _ = session_step(solo, d.frames[t])
        if not leaves_equal(pool.session(i), solo):
            raise AssertionError(f"served row {i} differs from its solo run")
    log(f"[d] ShardedPool+SlamServer S={len(streams)} on 1 device: "
        f"{SERVE_STEPS} frame-steps, dispatches/frame-step={ratio:.2f}, "
        f"every row bitwise-equal to its solo session_step run")


def phase_sharded(size: Size, chips: int):
    import jax

    from repro.launch.mesh import make_data_mesh
    from repro.slam.session import (
        session_init,
        session_row,
        stack_sessions,
        step_many,
    )

    cfg = slam_config(size)
    names = ("room0", "room1", "hall0", "stairs0")[:chips]
    streams = [dataset(n, size, SERVE_STEPS + 1, seed=i)
               for i, n in enumerate(names)]
    log(f"[4] {chips} rows of {size.width}x{size.height}, pool={cfg.capacity}"
        f", backend={cfg.backend}, over a {chips}-device 'data' mesh")

    stack = jax.device_put(
        stack_sessions([session_init(d, cfg, max_frames=SERVE_STEPS + 1)
                        for d in streams]), jax.devices()[0])
    for t in range(1, SERVE_STEPS + 1):
        stack, _ = step_many(stack, [d.frames[t] for d in streams])

    pool, ratio = serve(streams, cfg, make_data_mesh(chips))
    for leaf in jax.tree.leaves(pool.stacked):
        devices = {s.device for s in leaf.addressable_shards}
        if len(devices) != chips:
            raise AssertionError(f"a leaf of shape {leaf.shape} sits on "
                                 f"{len(devices)} devices, not {chips}")
    for i in range(chips):
        if not leaves_equal(pool.session(i), session_row(stack, i)):
            raise AssertionError(f"sharded row {i} differs from "
                                 "single-device step_many")
    log(f"[4] ShardedPool over {chips} devices: {SERVE_STEPS} frame-steps, "
        f"dispatches/frame-step={ratio:.2f}, every leaf on {chips} distinct "
        "devices, every row bitwise-equal to single-device step_many")


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, default=1, choices=(1, 4),
                    help="4: run only the sharded serving path on 4 chips")
    ap.add_argument("--rehearse", action="store_true",
                    help="CPU dry run at tiny sizes; reports no chip result")
    args = ap.parse_args()

    import jax

    devs = jax.devices()
    dev = devs[0]
    log(f"[a] platform={dev.platform} device_kind={dev.device_kind} "
        f"count={len(devs)}")
    if args.rehearse:
        size, sharded = TINY, TINY
    elif dev.platform != "tpu":
        sys.exit("chip_smoke: no TPU found (there is no CPU fallback; "
                 "--rehearse runs a CPU dry run at tiny sizes)")
    else:
        size, sharded = FULL, SHARDED
    if len(devs) < args.chips:
        sys.exit(f"chip_smoke: --chips {args.chips} needs {args.chips} "
                 f"devices, found {len(devs)}")

    from repro.launch.cache import use_compile_cache
    from repro.obs import Stopwatch

    log(f"[a] compile cache: {use_compile_cache()}")
    clock, sw = CompileClock(), Stopwatch()
    if args.chips == 4:
        phase_sharded(sharded, args.chips)
    else:
        ds, cfg, res = phase_stream(size, clock)
        phase_render_agreement(ds)
        phase_session_agreement(ds, cfg, res)
        phase_served(size, ds, cfg)
    log(f"[a] total {sw.elapsed():.1f} s, of which compile "
        f"{clock.total:.1f} s")
    if args.rehearse:
        log("rehearsal passed (CPU, tiny sizes): no chip result")
        return
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devs)}}))


if __name__ == "__main__":
    main()
