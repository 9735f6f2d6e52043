"""The harness's closed loop on a stand-in system (no JAX): the window
covers whole keyframe periods, and the check gets the run's first
``checked_frames`` frame-steps in order, from warm-up, window and the
steps carried on after the window closed."""

from types import SimpleNamespace

import pytest

from bench import harness


class Stub:
    """Frame-steps numbered from 1; every 4th is a keyframe."""

    def __init__(self, streams=1, offbeat=None):
        self.n, self.streams, self.offbeat = 0, streams, offbeat

    def dispatch(self):
        self.n += 1

    def fetch(self):
        kf = self.n % 4 == 0
        return [{"frame": self.n, "pose": [[0.0]],
                 "work": {"fragments": 10, "pixels": 256},
                 "is_kf": kf if i != self.offbeat else not kf}
                for i in range(self.streams)]


def frames(records):
    return [step[0]["frame"] for step in records]


@pytest.mark.parametrize("seconds,checked", [(0.0, 16), (0.0, 4), (0.05, 16)])
def test_checked_frames_run_on_in_order(seconds, checked):
    s = Stub()
    warm = harness.warm_up(s, 4)
    w = harness.measure(s, seconds, 16, annotate=False, keep=checked - 4)
    assert w.steps % 4 == 0 and w.periods == w.steps // 4
    assert w.frames == w.steps and w.failed == 0
    assert len(w.records) == min(checked - 4, w.steps)
    steps = harness.complete(s, warm + w.records, checked)
    assert frames(steps) == list(range(1, checked + 1))


def test_window_counts_every_stream_and_its_work():
    s = Stub(streams=2)
    harness.warm_up(s, 4)
    w = harness.measure(s, 0.0, 16, annotate=False)
    assert w.steps == 4 and w.frames == 8 and len(w.latencies_ms) == 8
    assert w.work == {"fragments": 80, "pixels": 2048}


def test_a_stream_off_its_keyframe_beat_fails_the_open_frames():
    s = Stub(streams=2, offbeat=1)
    harness.warm_up(s, 4)
    w = harness.measure(s, 10.0, 16, annotate=False)
    assert w.periods == 0 and w.steps == 1 and w.failed == w.frames == 2


class DeviceWorkStub(Stub):
    """The program's step result: each frame-step's ``DeviceWork`` holds
    its field's position plus the step number, so every counter sums to
    something of its own."""

    def fetch(self):
        import numpy as np

        from repro.slam.metrics import DeviceWork

        kf = self.n % 4 == 0
        out = SimpleNamespace(
            pose=np.eye(4, dtype=np.float32), is_kf=np.bool_(kf),
            track_losses=np.zeros(3, np.float32),
            map_losses=np.zeros(4, np.float32), psnr=np.float32(20.0),
            work=DeviceWork(*(np.int32(i + self.n)
                              for i in range(len(DeviceWork._fields)))))
        return [harness.record(harness.fetch(out))]


def test_every_device_work_counter_is_summed_over_the_window():
    from repro.slam.metrics import DeviceWork

    s = DeviceWorkStub()
    harness.warm_up(s, 4)                            # steps 1-4
    w = harness.measure(s, 0.0, 16, annotate=False)  # steps 5-8
    assert w.steps == 4
    assert set(w.work) == set(DeviceWork._fields)
    for i, name in enumerate(DeviceWork._fields):
        assert w.work[name] == sum(i + n for n in range(5, 9)), name
    # The two counters the rooflines read keep their names and meaning.
    assert w.work["fragments"] == sum(range(5, 9))       # field 0
    assert w.work["pixels"] == 4 + sum(range(5, 9))      # field 1
