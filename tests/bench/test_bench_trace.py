"""The trace reduction on small made-up traces: busy union, idle share,
kernel sums, gap attribution, top operations."""

import pytest

from bench import tracing
from bench.tracing import Event, Trace


def make_trace(devices=None, host=None):
    host = host if host is not None else [
        Event(0, 1000, "bench.window"),
        Event(0, 100, "bench.dispatch"),
        Event(100, 400, "bench.fetch"),
        Event(500, 100, "bench.dispatch"),
        Event(600, 400, "bench.fetch"),
    ]
    if devices is None:
        devices = [[
            Event(100, 200, "jvp_jit_tile_render_fwd_sched__.62"),
            Event(100, 250, "while.3"),                       # holds the kernel
            Event(600, 100, "transpose_jvp_jit_tile_render_bwd_sched___.28"),
            Event(700, 50, "fusion.1"),
            Event(950, 200, "fusion.4"),                      # past the window
            Event(-50, 70, "fusion.5"),                       # before it
        ]]
    return Trace(devices=devices, host=host)


def test_union_merges_overlaps_and_touching():
    assert tracing.union([(5, 7), (0, 2), (1, 3), (3, 4)]) == [[0, 4], [5, 7]]


def test_busy_and_idle_share():
    tr = make_trace()
    # [0,20) + [100,350) + [600,750) + [950,1000) = 20 + 250 + 150 + 50
    assert tracing.busy_ns(tr) == pytest.approx(470)
    assert tracing.idle_share(tr) == pytest.approx(53.0)


def test_busy_averages_over_chips():
    tr = make_trace()
    tr.devices.append([Event(0, 1000, "fusion.9")])
    assert tracing.busy_ns(tr) == pytest.approx((470 + 1000) / 2)


def test_idle_share_without_device_events_is_unknown():
    assert tracing.idle_share(make_trace(devices=[])) is None
    assert tracing.idle_share(make_trace(devices=[[]])) is None
    assert tracing.breakdown(make_trace(devices=[])) is None


def test_kernel_sums_match_the_operation_name():
    tr = make_trace()
    assert tracing.kernel_ns(tr, ("tile_render_fwd",)) == pytest.approx(200)
    assert tracing.kernel_ns(tr, ("tile_render_bwd",)) == pytest.approx(100)
    assert tracing.kernel_ns(tr, ("block_cumsum",)) == 0


def test_gaps_are_labelled_by_the_host_span_they_fall_in():
    gaps = tracing.gaps(make_trace())
    # holes: [20,100) dispatch, [350,600) fetch / dispatch boundary at 500:
    # midpoint 475 lies in the first fetch; [750,950) in the second fetch.
    assert [(g[0], round(g[1] * 1e9)) for g in gaps] == [
        ("bench.fetch", 250), ("bench.fetch", 200), ("bench.dispatch", 80)]


def test_gap_outside_every_span_is_host_other():
    host = [Event(0, 1000, "bench.window")]
    gaps = tracing.gaps(make_trace(host=host))
    assert {g[0] for g in gaps} == {"host:other"}


def test_top_ops_sum_by_name_within_the_window():
    tops = dict(tracing.top_ops(make_trace()))
    assert "while.3" not in tops                # it holds other operations
    assert tops["fusion.1"] == pytest.approx(50e-9)
    assert tops["fusion.4"] == pytest.approx(50e-9)
    assert tops["fusion.5"] == pytest.approx(20e-9)


def test_missing_window_span_is_an_error():
    with pytest.raises(ValueError):
        make_trace(host=[]).window()


def test_op_name_drops_the_operands():
    text = ("%transpose_jvp_jit_tile_render_bwd_sched___.28 = f32[1200,10,128] "
            "custom-call(s32[1200] %a, f32[1200,128,256] "
            "%jvp_jit_tile_render_fwd_sched__.59)")
    assert tracing.op_name(text) == "transpose_jvp_jit_tile_render_bwd_sched___.28"
    assert tracing.is_container(tracing.op_name("%while.50 = (s32[]) while(...)"))
    assert not tracing.is_container("fusion.2296")


def test_host_spans_are_summed_in_the_window():
    assert tracing.host_ns(make_trace(), "bench.dispatch") == pytest.approx(200)


# -- the loader on a hand-encoded XSpace --------------------------------------

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


def _plane(name, line, start_ns, events, stat_names=None):
    """One XPlane with one line: ``events`` are (full name, offset ns,
    duration ns, [(stat id, string value)]), one metadata entry each."""
    line_body = _int(1, 1) + _msg(2, line) + _int(3, start_ns)
    metas = b""
    for key, (full, off, dur, stats) in enumerate(events, 1):
        line_body += _msg(4, _int(1, key) + _int(2, off * 1000)
                          + _int(3, dur * 1000))
        meta = _int(1, key) + _msg(2, full)
        for sid, val in stats:
            meta += _msg(5, _int(1, sid) + _msg(5, val))
        metas += _msg(4, _int(1, key) + _msg(2, meta))
    for sid, sname in (stat_names or {}).items():
        metas += _msg(5, _int(1, sid) + _msg(2, _int(1, sid) + _msg(2, sname)))
    return _msg(1, _int(1, 1) + _msg(2, name) + _msg(3, line_body) + metas)


TRACK = "jit(solo)/slam.track/while/body"
MAP_T = "jit(solo)/transpose(jvp(slam.map))/cond/branch_1_fun"
STATS = {1: "tf_op", 2: "source", 3: "hlo_category"}
DEVICE_OPS = [
    ("%fusion.1 = f32[8]{0} fusion(f32[8]{0} %p), kind=kLoop", 0, 100,
     [(3, "loop fusion"), (1, TRACK + "/project/mul:"),
      (2, "/repo/src/repro/core/projection.py:88")]),
    ("%jvp_jit_tile_render_fwd_sched__.62 = f32[1200,5,256]{2,1,0} "
     "custom-call(s32[1200]{0} %a)", 100, 300,
     [(1, TRACK + "/raster/jit(tile_render_fwd_sched)/pallas_call:"),
      (2, "/repo/src/repro/kernels/ops.py:210")]),
    ("%transpose_jvp_jit_tile_render_bwd_sched___.28 = f32[1200,10,128]{2,1,0}"
     " custom-call(s32[1200]{0} %a)", 450, 200,
     [(1, MAP_T + "/raster/jit(tile_render_bwd_sched)/pallas_call:")]),
    ("%copy.2 = f32[8]{0} copy(f32[8]{0} %fusion.1)", 700, 50, []),
]
HOST_SPANS = [("bench.window", 0, 1000, []), ("bench.dispatch", 0, 40, []),
              ("slam.step", 5, 30, []), ("bench.fetch", 40, 700, []),
              ("bench.dispatch", 750, 20, []), ("slam.step", 752, 15, []),
              ("bench.fetch", 770, 200, []), ("PjitFunction(step)", 6, 20, [])]
T0 = 5_000_000


@pytest.fixture
def profile_dir(tmp_path):
    space = (_plane("/host:CPU", "python", T0, HOST_SPANS)
             + _plane("/device:TPU:0", "XLA Ops", T0, DEVICE_OPS, STATS))
    (tmp_path / "plugins" / "profile" / "run").mkdir(parents=True)
    (tmp_path / "plugins" / "profile" / "run" / "h.xplane.pb").write_bytes(space)
    return str(tmp_path)


def test_load_xplane_attaches_scope_and_source(profile_dir):
    tr = tracing.load_xplane(profile_dir)
    assert len(tr.devices) == 1
    got = {e.name: (e.start_ns - T0, e.dur_ns, e.scope, e.source)
           for e in tr.devices[0]}
    assert got == {
        "fusion.1": (0, 100, TRACK + "/project/mul:",
                     "/repo/src/repro/core/projection.py:88"),
        "jvp_jit_tile_render_fwd_sched__.62": (
            100, 300, TRACK + "/raster/jit(tile_render_fwd_sched)/pallas_call:",
            "/repo/src/repro/kernels/ops.py:210"),
        "transpose_jvp_jit_tile_render_bwd_sched___.28": (
            450, 200, MAP_T + "/raster/jit(tile_render_bwd_sched)/pallas_call:",
            ""),
        "copy.2": (700, 50, "", "")}


def test_load_xplane_keeps_the_program_host_spans(profile_dir):
    tr = tracing.load_xplane(profile_dir)
    names = [e.name for e in tr.host]
    assert names.count("slam.step") == 2
    assert "PjitFunction(step)" not in names
    assert {n for n in names if not n.startswith("slam.")} == {
        "bench.window", "bench.dispatch", "bench.fetch"}
    assert all(e.scope == e.source == "" for e in tr.host)


EXISTING = ("device.idle_pct", "raster_fwd.ms_per_frame", "raster_fwd_roofline",
            "raster_bwd.ms_per_frame", "raster_bwd_roofline",
            "step_xla.ms_per_frame", "host.ms_per_step")


def _run(trace):
    from bench import spec

    class Run:
        frames, steps = 2, 2
        work = {"fragments": 1_000_000, "pixels": 614_400}
        peaks = spec.peaks_for("TPU v5 lite")
        counters = {"setup": {"jit_s": 1.0, "programs": 1},
                    "window": {"jit_s": 0.0, "programs": 0}}

    Run.trace = trace
    return Run


@pytest.mark.parametrize("metric", EXISTING)
def test_existing_readers_read_the_same_without_scopes(metric, profile_dir):
    """What the loader now keeps besides (scope paths, sources, the
    program's host spans) moves none of the readers that match operations
    by name and host spans by ``bench.*`` name: the loader as it was gives
    the same value, bit for bit."""
    import dataclasses

    from bench import spec

    full = tracing.load_xplane(profile_dir)
    bare = Trace(devices=[[dataclasses.replace(e, scope="", source="")
                           for e in ops] for ops in full.devices],
                 host=[e for e in full.host if e.name.startswith("bench.")])
    read = spec.load_reader(metric)
    value = read(_run(full))
    assert value is not None
    assert value == read(_run(bare))
