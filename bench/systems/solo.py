"""System ``solo``: one stream through the program's
``session_init`` and ``session_step``, one dispatch per frame.

A system module is found by the configuration's ``path``
(``bench/systems/<path>.py``) and gives ``System(streams, cfg,
session_seed, max_frames)`` with ``dispatch()`` (submit one frame per
stream), ``fetch()`` (each stream's records, on the host) and ``close()``."""

from bench import harness


class System:
    """One stream through ``session_step``: one dispatch per frame."""

    def __init__(self, streams, cfg: dict, session_seed: int, max_frames: int):
        from repro.slam.session import session_init, session_step

        self.streams = streams
        self.step_fn = session_step
        self.sess = session_init(harness.first_frame(streams[0], cfg),
                                 harness.slam_config(cfg),
                                 max_frames=max_frames, seed=session_seed)
        self.frame_no = 1

    def dispatch(self):
        rgb, depth = self.streams[0].frame(self.frame_no)
        self.sess, self.out = self.step_fn(self.sess, (rgb, depth))
        self.frame_no += 1

    def fetch(self) -> list:
        return [harness.record(harness.fetch(self.out))]

    def close(self):
        self.sess = self.out = None
