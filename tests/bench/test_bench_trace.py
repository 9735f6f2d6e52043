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
