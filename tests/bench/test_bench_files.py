"""The benchmark's data files load by name, the metric readers and the
rasterizer's work counts behave on known inputs, and ``BENCHMARK.json``
keeps the shape its contract asks for."""

import json
import os
import re

import pytest

from bench import counts, spec
from bench.tracing import Event, Trace

BENCH = spec.load_benchmark()
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")


@pytest.mark.parametrize("cell", [w["name"] for w in BENCH["workloads"]])
def test_every_cell_loads_by_name(cell):
    c = spec.load_cell(cell)
    assert c.config["name"] == c.config_name
    assert c.traffic["config"] == c.config_name
    assert callable(spec.load_module("systems", c.config["path"]).System)
    assert c.traffic["streams"]
    for scene in c.traffic["streams"]:
        assert callable(spec.load_module("scenes", scene.rstrip("0123456789")).points)
    assert callable(spec.load_module(
        "trajectories", c.traffic["trajectory"]["generator"]).poses)
    assert c.traffic["warmup_frames"] <= c.traffic["checked_frames"]
    assert {m["name"] for m in c.end_to_end} >= {"setup_s"}
    assert c.per_layer


@pytest.mark.parametrize("metric", [m["name"] for m in BENCH["per_layer"]])
def test_every_metric_reader_loads_and_reads(metric):
    read = spec.load_reader(metric)
    tr = Trace(devices=[[Event(0, 400, "fusion.0"),
                         Event(400, 300, "jvp_jit_tile_render_fwd_sched__.1"),
                         Event(700, 200,
                               "transpose_jvp_jit_tile_render_bwd_sched___.2")]],
               host=[Event(0, 1000, "bench.window"),
                     Event(0, 10, "bench.dispatch")])

    class Run:
        trace, frames, steps = tr, 2, 2
        work = {"fragments": 1000, "pixels": 4096}
        peaks = spec.peaks_for("TPU v5 lite")

    value = read(Run)
    assert value is not None and value >= 0


@pytest.mark.parametrize("metric", [m["name"] for m in BENCH["per_layer"]])
def test_a_reader_with_nothing_to_read_returns_nothing(metric):
    class Run:
        trace = Trace(devices=[[]], host=[Event(0, 1000, "bench.window")])
        frames, steps = 2, 2
        work = {"fragments": 0, "pixels": 0}
        peaks = spec.peaks_for("TPU v5 lite")

    assert spec.load_reader(metric)(Run) is None


def _modules(kind):
    return sorted(f[:-3] for f in os.listdir(os.path.join(spec.HERE, kind))
                  if f.endswith(".py") and not f.startswith("_"))


@pytest.mark.parametrize("kind", _modules("scenes"))
def test_every_scene_draws_its_points_from_the_key(kind):
    import jax

    mod = spec.load_module("scenes", kind)
    pts, cols = mod.points(jax.random.PRNGKey(3), 256)
    again, _ = mod.points(jax.random.PRNGKey(3), 256)
    assert pts.shape == (256, 3) and cols.shape == (256, 3)
    assert (pts == again).all()


@pytest.mark.parametrize("cell", [w["name"] for w in BENCH["workloads"]])
def test_every_trajectory_gives_one_period_of_rigid_poses(cell):
    import numpy as np

    from bench import traffic

    traj = spec.load_cell(cell).traffic["trajectory"]
    poses = traffic.trajectory(traj)
    assert poses.shape == (traj["period_frames"], 4, 4)
    rot = poses[:, :3, :3].astype(np.float64)
    assert np.allclose(rot @ rot.transpose(0, 2, 1), np.eye(3), atol=1e-5)


def test_unknown_module_is_refused():
    with pytest.raises(KeyError):
        spec.load_module("scenes", "no_such_scene")


def test_unknown_cell_is_refused():
    with pytest.raises(KeyError):
        spec.load_cell("no_such.cell")


def test_peaks_refuse_an_unknown_device_kind():
    assert spec.peaks_for("TPU v5 lite")["hbm_bytes_per_s"] == 819e9
    with pytest.raises(KeyError):
        spec.peaks_for("TPU v99")


def test_counts_on_known_shapes():
    # One tile of 10 fragments in a 16x16 frame.
    ops, nbytes = counts.forward(10, 256)
    assert ops == 10 * 256 * counts.FWD_OPS
    assert nbytes == 10 * 44 + 256 * 20
    ops, nbytes = counts.backward(10, 256)
    assert ops == 10 * 256 * counts.BWD_OPS
    assert nbytes == 10 * 84 + 256 * 40


def test_roofline_takes_the_larger_bound():
    peaks = {"flops_per_s": 1e12, "hbm_bytes_per_s": 1e9}
    # 1e9 ops -> 1 ms; 2e6 bytes -> 2 ms: memory bound; 4 ms taken.
    assert counts.roofline_pct(1e9, 2e6, 4e-3, peaks) == pytest.approx(50.0)
    assert counts.roofline_pct(0, 2e6, 4e-3, peaks) is None


def test_benchmark_json_shape():
    assert set(BENCH) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    root = spec.ROOT
    e2e = {m["name"] for m in BENCH["end_to_end"]}
    assert "setup_s" in e2e
    for m in BENCH["end_to_end"]:
        assert NAME.match(m["name"]) and UNIT.match(m["unit"])
        assert 0.01 <= m["bound"] <= 0.25
    for m in BENCH["per_layer"]:
        assert NAME.match(m["name"]) and UNIT.match(m["unit"])
        assert m["moves"] in e2e
        assert os.path.exists(os.path.join(root, "bench", "metrics",
                                           m["name"] + ".py"))
    for entry in BENCH["configs"] + BENCH["workloads"]:
        assert 1 <= len(entry["why"]) <= 200 and "\n" not in entry["why"]
    for m in BENCH["per_layer"]:
        assert 1 <= len(m["layer"]) <= 200
        assert set(m.get("workloads", [])) <= {w["name"] for w in BENCH["workloads"]}
    for c in BENCH["configs"]:
        assert 1 <= len(c["source"]) <= 200
        assert os.path.exists(os.path.join(root, c["file"]))
        with open(os.path.join(root, c["file"])) as f:
            assert json.load(f)["name"] == c["name"]
    pairs = [(w["config"], w["traffic"]) for w in BENCH["workloads"]]
    assert len(pairs) == len(set(pairs))
    for w in BENCH["workloads"]:
        assert NAME.match(w["name"]) and w["chips"] in (1, 4)
        assert w["name"] == f"{w['config']}.{w['traffic']}"
