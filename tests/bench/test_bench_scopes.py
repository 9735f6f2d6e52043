"""The scope reduction (``bench/scopes.py``) on small made-up traces:
innermost-scope attribution through ``jvp``/``transpose`` paths, the
source-file and enclosing-loop fallbacks, kernels and containers left out,
the partition of busy time, the program's host spans, and the metadata
read from a hand-encoded ``.xplane.pb``."""

import pytest

from bench import scopes
from bench.scopes import ScopedEvent
from bench.tracing import Event, Trace

MAP = "jit(solo)/jvp(slam.map)/cond/branch_1_fun"
MAP_T = "jit(solo)/transpose(jvp(slam.map))/cond/branch_1_fun"
TRACK = "jit(solo)/slam.track/while/body/closed_call"
SORTING = "/repo/src/repro/core/sorting.py:97"


def op(start, dur, name, scope="", source=""):
    return ScopedEvent(start, dur, name, scope, source)


def test_components_unwrap_transforms():
    path = MAP_T + "/while/body/raster/jit(_render_single)/raster/mul:"
    assert scopes.components(path) == [
        "solo", "slam.map", "cond", "branch_1_fun", "while", "body",
        "raster", "_render_single", "raster", "mul"]
    assert scopes.phase_of(path) == "slam.map"
    # Merged instructions: the first path is read.
    assert scopes.work_of("jit(s)/project/mul;jit(s)/loss/add") == "project"


@pytest.mark.parametrize("path, work", [
    (MAP + "/raster/project/mul:", "project"),
    (MAP_T + "/while/body/raster/project/add_any:", "project"),
    (TRACK + "/prune/cond/branch_1_fun/frag_build/jit(cumsum)/add:",
     "frag_build"),
    (MAP + "/densify/raster/frag_build/jit(argsort)/sort:", "frag_build"),
    (MAP + "/while/body/adam/mul:", "adam"),
    (MAP + "/while/body/raster/raster/jit(tile_render_fwd_sched)/pallas_call:",
     "raster"),
    (MAP + "/while:", None),
    ("reduce_window_sum:", None),
])
def test_innermost_work_scope(path, work):
    assert scopes.work_of(path) == work


def test_source_fallback_and_enclosing_loop():
    ops = [
        op(0, 100, "while.1", MAP + "/while"),
        # XLA kept only the primitive's name: the source file decides.
        op(0, 10, "fusion.1", "reduce_window_sum:", SORTING),
        # A loop's carry copy: the loop's scopes.
        op(10, 10, "copy.2", MAP + "/while:"),
        # A computation XLA shares with a tracking call site: its path
        # names that site, the loop that runs it names the phase.
        op(20, 10, "fusion.3", TRACK + "/frag_build/gather:"),
        op(200, 5, "fusion.4", "", "/repo/src/repro/slam/engine.py:40"),
        op(210, 5, "fusion.5", TRACK + "/loss/abs:"),
        # A conditional whose own path XLA dropped: its operations' paths
        # give it its phase, which holds for all it runs.
        op(300, 100, "conditional.6", ""),
        op(300, 60, "fusion.7", MAP + "/raster/add:"),
        op(360, 30, "fusion.8", TRACK + "/frag_build/gather:"),
        op(390, 10, "fusion.9", "reduce_window_sum:", SORTING),
    ]
    got = {e.name: (w, p) for e, w, p in scopes.attribute(ops)}
    assert got["fusion.1"] == ("frag_build", "slam.map")
    assert got["copy.2"] == (None, "slam.map")
    assert got["fusion.3"] == ("frag_build", "slam.map")
    assert got["fusion.4"] == (None, None)
    assert got["fusion.5"] == ("loss", "slam.track")
    assert got["conditional.6"] == (None, "slam.map")
    assert got["fusion.8"] == ("frag_build", "slam.map")
    assert got["fusion.9"] == ("frag_build", "slam.map")


def made_up_trace():
    host = [Event(0, 1000, "bench.window"),
            Event(0, 400, "bench.dispatch"), Event(10, 380, "slam.step"),
            Event(500, 400, "bench.dispatch"), Event(510, 370, "slam.step"),
            Event(950, 20, "slam.step")]                  # outside a dispatch
    ops = [
        op(0, 300, "while.7", TRACK[:-len("/while/body/closed_call")]
           + "/while"),
        op(0, 40, "fusion.10", TRACK + "/project/mul:"),
        op(40, 60, "fusion.11", "reduce_window_sum:", SORTING),
        op(100, 30, "fusion.12", TRACK + "/wsu_schedule/sort:"),
        op(130, 50, "tile_render_fwd_sched.30",
           TRACK + "/raster/jit(tile_render_fwd_sched)/pallas_call:"),
        op(180, 20, "fusion.13", TRACK + "/raster/add:"),
        op(200, 20, "fusion.14", TRACK + "/loss/abs:"),
        op(220, 30, "fusion.15", TRACK + "/adam/mul:"),
        op(250, 50, "copy.16", TRACK[:-len("/while/body/closed_call")]
           + "/while:"),                                   # lost its scope
        op(500, 300, "conditional.2", MAP),
        op(500, 100, "tile_render_bwd_sched.28",
           MAP_T + "/raster/jit(tile_render_bwd_sched)/pallas_call:"),
        op(600, 100, "fusion.20", MAP + "/densify/sort:"),
        op(700, 100, "fusion.21", MAP + "/prune/add:"),
        op(800, 100, "fusion.22", "jit(solo)/add:"),         # no phase
        op(990, 100, "fusion.23", TRACK + "/project/mul:"),  # past the window
    ]
    return Trace(devices=[ops], host=host)


def test_split_partitions_busy_time():
    d = scopes.device_split(made_up_trace(), frames=2)
    ms = 1e-6 / 2                          # ns in the window -> ms/frame
    assert d["busy"] == pytest.approx(710 * ms)  # [0,300) [500,900) [990,1000)
    assert d["kernels"] == pytest.approx(150 * ms)  # containers, kernels out
    w = d["work"]
    assert w["project"] == pytest.approx(50 * ms)   # 40 + the window's last 10
    assert w["frag_build"] == pytest.approx(60 * ms)   # source fallback
    assert w["wsu_schedule"] == pytest.approx(30 * ms)
    assert w["raster_glue"] == pytest.approx(40 * ms)  # raster + loss
    assert w["optim"] == pytest.approx(230 * ms)       # adam, prune, densify
    # The lost carry copy and the phase-less op: busy - kernels - the five.
    assert w["step_unscoped"] == pytest.approx((710 - 150 - 410) * ms)
    assert sum(w.values()) + d["kernels"] == pytest.approx(d["busy"])
    assert d["step_xla"] == pytest.approx(d["busy"] - d["kernels"])
    p = d["phases"]
    assert p["slam.track"] == pytest.approx(310 * ms)  # kernel included
    assert p["slam.map"] == pytest.approx(300 * ms)
    assert p["none"] == pytest.approx(100 * ms)
    assert d["phase_work"]["slam.map/kernels"] == pytest.approx(100 * ms)
    assert d["phase_work"]["slam.track/none"] == pytest.approx(50 * ms)
    assert [n for n, _, _ in d["unscoped_top"]] == ["fusion.22", "copy.16"]


def test_host_split_counts_program_spans_in_dispatch():
    h = scopes.host_split(made_up_trace())
    assert h["steps"] == 2 and h["slam_steps"] == 3
    assert h["slam_step_ms_per_step"] == pytest.approx(770e-6 / 2)
    assert h["dispatch_ms_per_step"] == pytest.approx(800e-6 / 2)
    assert h["slam_steps_outside_dispatch"] == 1


def test_no_device_plane_reduces_to_host_alone():
    tr = made_up_trace()
    tr.devices = []
    out = scopes.reduce(tr)
    assert out["device"] is None and out["host"]["steps"] == 2


# -- a hand-encoded XSpace ----------------------------------------------------

def _varint(n):
    out = bytearray()
    while True:
        b, n = n & 0x7F, n >> 7
        out.append(b | (0x80 if n else 0))
        if not n:
            return bytes(out)


def _int(field, n):
    return _varint(field << 3) + _varint(n)


def _msg(field, payload):
    if isinstance(payload, str):
        payload = payload.encode()
    return _varint(field << 3 | 2) + _varint(len(payload)) + payload


def _plane(name, stat_names, events):
    body = _int(1, 7) + _msg(2, name)
    for key, (full, stats) in events.items():
        meta = _int(1, key) + _msg(2, full) + _msg(4, full.split(" ")[0])
        for sid, kind, val in stats:
            meta += _msg(5, _int(1, sid) + (_int(7, val) if kind == "ref"
                                            else _msg(5, val)))
        body += _msg(4, _int(1, key) + _msg(2, meta))
    for sid, sname in stat_names.items():
        body += _msg(5, _int(1, sid) + _msg(2, _int(1, sid) + _msg(2, sname)))
    return _msg(1, body)


def test_op_metadata_reads_scope_and_source(tmp_path):
    full = "%fusion.1 = f32[8]{0} fusion(f32[8]{0} %p), kind=kLoop"
    names = {1: "tf_op", 2: "source", 3: "hlo_category", 4: "loop fusion"}
    space = (_plane("/host:CPU", names, {5: ("host op", [(1, "str", "x")])})
             + _plane("/device:TPU:0", names, {
                 9: (full, [(3, "ref", 4), (1, "str", MAP + "/project/mul:"),
                            (2, "str", SORTING)]),
                 10: ("%copy.2 = f32[8]{0} copy(f32[8]{0} %fusion.1)", [])}))
    path = tmp_path / "t.xplane.pb"
    path.write_bytes(space)
    meta = scopes.op_metadata(str(path))
    assert list(meta) == ["/device:TPU:0"]
    assert meta["/device:TPU:0"][full] == (MAP + "/project/mul:", SORTING)
    assert meta["/device:TPU:0"]["%copy.2 = f32[8]{0} copy(f32[8]{0} "
                                 "%fusion.1)"] == ("", "")


def test_traced_rehearsal_counts_builds_and_program_spans(tmp_path):
    """One traced run through the harness on the CPU at tiny sizes: the
    program's ``slam.step`` spans lie in the harness's dispatch spans, and
    the window builds no program (the CPU profile has no device plane)."""
    out = scopes.traced_run("monogs_tum.room0", 3_000_000_019, 0.5,
                            str(tmp_path), rehearse=True)
    assert out["result"]["correct"] is True
    assert out["split"]["device"] is None
    host = out["split"]["host"]
    assert host["slam_steps"] == host["steps"] >= 4
    assert host["slam_steps_outside_dispatch"] == 0
    assert 0 < host["slam_step_ms_per_step"] <= host["dispatch_ms_per_step"]
    setup, window = out["builds"]["setup"], out["builds"]["window"]
    assert setup["programs"] > 0 and setup["jit_s"] > 0
    assert window["programs"] == 0 and window["jit_s"] == 0
