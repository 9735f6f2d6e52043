"""Profiler names (``repro.obs.profiling``): every phase and work scope of
the fused step reaches the compiled HLO's ``op_name`` metadata, the
program's host spans land in a profile, and the build counters count what
JAX built.

The scope check guards against a refactor that silently drops a scope: a
chip trace reduced by ``bench/scopes.py`` would then lose that layer."""

import glob
import os
import re

import jax
import jax.numpy as jnp
import pytest
from jax.profiler import ProfileData

from repro.core.keyframes import KeyframePolicy
from repro.core.pruning import PruneConfig
from repro.obs import TraceRecorder
from repro.obs.profiling import PHASES, WORK_SCOPES, build_counters, scoped
from repro.obs.profiling import _Union
from repro.slam import session as S
from repro.slam.datasets import make_dataset
from repro.slam.map import PagedConfig


def _host_events(log_dir) -> set:
    path = glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                     recursive=True)[0]
    return {e.name for plane in ProfileData.from_file(path).planes
            if plane.name.startswith("/host:")
            for line in plane.lines for e in line.events}


def _profiled(log_dir, fn):
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(str(log_dir), profiler_options=opts)
    try:
        return fn()
    finally:
        jax.profiler.stop_trace()


@pytest.fixture(scope="module")
def solo(tmp_path_factory):
    """A rehearsal-size solo session on the WSU-scheduled backend with
    pruning and a paged map (so every phase exists), started and stepped
    once under the profiler.  Returns (session, frame, host span names)."""
    ds = make_dataset("room0", num_frames=2, height=48, width=64,
                      num_gaussians=400, frag_capacity=48)
    cfg = S.SLAMConfig(
        iters_track=2, iters_map=2, capacity=1024, frag_capacity=48,
        map_window=2, map_rebuild_stride=2, scan_unroll=1, densify_per_kf=32,
        backend="schedule", keyframe=KeyframePolicy(kind="monogs", interval=1),
        prune=PruneConfig(k0=2, step_frac=0.1),
        paged=PagedConfig(page_capacity=128, visible_pages=8))
    log_dir = tmp_path_factory.mktemp("profile")

    def start_and_step():
        sess = S.session_init(ds, cfg, max_frames=4)
        frame = S._as_obs(ds.frames[1])
        # The step donates the session: step a copy, keep the original.
        nxt, out = S.session_step(jax.tree.map(jnp.copy, sess), frame)
        jax.block_until_ready(out.pose)
        return sess

    sess = _profiled(log_dir, start_and_step)
    return sess, S._as_obs(ds.frames[1]), _host_events(log_dir)


def _scope_names(hlo: str) -> set:
    """Every scope name of every op_name path, transforms unwrapped
    (``transpose(jvp(slam.map))`` -> ``slam.map``)."""
    names = set()
    for path in re.findall(r'op_name="([^"]*)"', hlo):
        for one in path.split(";"):
            for part in one.split("/"):
                m = re.fullmatch(r"(?:[\w.-]+\()*([^()]*)\)*", part)
                names.add(m.group(1) if m else part)
    return names


def test_every_scope_reaches_the_compiled_step(solo):
    sess, frame, _ = solo
    fn = S._step_fn(sess.meta, 1, None)
    hlo = fn.lower(sess, frame).compile().as_text()
    names = _scope_names(hlo)
    missing = [s for s in (*PHASES, *WORK_SCOPES) if s not in names]
    assert not missing, f"scopes missing from the compiled step: {missing}"


def test_program_host_spans_land_in_the_profile(solo):
    spans = solo[2]
    for name in ("slam.session_init", "slam.seed_map", "slam.boot",
                 "slam.step"):
        assert name in spans


def test_trace_recorder_spans_land_in_the_profile(tmp_path):
    tr = TraceRecorder(process="unit")

    def spans():
        with tr.span("outer"):
            with tr.span("inner"):
                jnp.ones(3).block_until_ready()

    _profiled(tmp_path, spans)
    assert {"slam.outer", "slam.inner"} <= _host_events(tmp_path)
    assert [e["name"] for e in tr.events] == ["inner", "outer"]


def test_scoped_names_every_call_and_nests():
    @scoped("outer")
    def outer(x):
        return inner(x) + 1

    @scoped("inner")
    def inner(x):
        return jnp.sin(x)

    hlo = jax.jit(outer).lower(jnp.ones(3)).as_text(debug_info=True)
    assert "outer/inner" in hlo
    # A second trace names its operations again: the scope is not spent.
    assert "outer/inner" in jax.jit(lambda x: outer(x) * 2).lower(
        jnp.ones(3)).as_text(debug_info=True)


def test_build_counters_count_programs_and_seconds():
    before = build_counters()
    jax.jit(lambda x: jnp.cos(x) * 3.0 + 1.0)(jnp.ones(7)).block_until_ready()
    after = build_counters()
    assert after["programs"] > before["programs"]
    for key in ("trace_s", "lower_s", "compile_s", "jit_s"):
        assert after[key] > before[key]
    assert after["jit_s"] <= (after["trace_s"] + after["lower_s"]
                              + after["compile_s"] + 1e-9)


def test_nested_build_spans_count_once():
    u = _Union()
    # Spans arrive as they end: an inner trace before the outer that holds it.
    for span in [(2.0, 5.0), (0.0, 10.0), (12.0, 13.0), (12.5, 14.0)]:
        u.add(*span)
    assert u.total == 12.0
    assert u.spans == [(0.0, 10.0), (12.0, 14.0)]
    assert _Union().total == 0.0
