"""The harness end to end on the CPU at tiny sizes (``--rehearse``):
through the same cell files, a sound run comes out correct, and a run with
the timed path broken underneath, or the lower-precision control in the
program's place, comes out not correct.  Without a TPU and without
``--rehearse`` the benchmark prints no result and exits with code 2."""

import argparse

import jax
import jax.numpy as jnp
import pytest

from bench import calibrate, spec
from bench import run as bench_run

SOLO = "monogs_tum.room0"


def args(cell, seed=7, trace=0):
    return argparse.Namespace(workload=cell, seed=seed, seconds=0.5,
                              trace=trace, rehearse=True, keep_trace=None)


def test_no_chip_no_result(capsys):
    rc = bench_run.main(["--workload", SOLO, "--seed", "1", "--seconds", "1",
                         "--trace", "0"])
    assert rc == 2
    assert capsys.readouterr().out == ""


def test_sound_solo_rehearsal_is_correct():
    r = bench_run.run(args(SOLO, seed=3_000_000_019))
    assert r["correct"] is True
    assert r["failed"] == 0 and r["attempted"] >= 4
    assert set(r["metrics"]) == {"frames_per_s", "frame_ms_p95", "setup_s"}
    assert list(r)[-1] == "compared"


def test_traced_rehearsal_reports_per_layer_metrics_only():
    r = bench_run.run(args(SOLO, seed=11, trace=1))
    assert r["correct"] is True
    names = {m["name"] for m in spec.load_benchmark()["per_layer"]}
    assert set(r["metrics"]) <= names
    assert "host.ms_per_step" in r["metrics"]   # no device plane on the CPU
    assert "busy_s" in r["device"] and "window_s" in r["device"]


def test_traced_rehearsal_hands_counters_and_work_to_the_readers():
    """``RunData.counters`` holds the build counters at the end of set-up
    and what the window added (nothing: every program is built in set-up),
    and ``RunData.work`` every ``DeviceWork`` counter of the window."""
    from repro.slam.metrics import DeviceWork

    r = bench_run.run(args(SOLO, seed=13, trace=1))
    assert r["correct"] is True
    setup, window = r["builds"]["setup"], r["builds"]["window"]
    assert setup["programs"] > 0 and setup["jit_s"] > 0
    assert window["programs"] == 0 and window["jit_s"] == 0
    assert r["metrics"]["setup.jit_s"]["value"] == setup["jit_s"]
    work = r["window"]["work"]
    assert set(work) == set(DeviceWork._fields)
    assert work["fragments"] > 0 and work["pixels"] > 0


def _stale_state(system):
    """Fault: the step returns its state unchanged."""
    real = system.step_fn

    def step(sess, obs):
        keep = jax.tree.map(jnp.copy, sess)
        _, out = real(sess, obs)
        return keep, out

    system.step_fn = step


def _altered_pose(system):
    """Fault: the answer is altered where it is produced."""
    real = system.step_fn

    def step(sess, obs):
        sess, out = real(sess, obs)
        return sess, out._replace(pose=out.pose.at[0, 3].add(0.1))

    system.step_fn = step


@pytest.mark.parametrize("cell,fault", [
    (SOLO, _stale_state), (SOLO, _altered_pose)])
def test_broken_timed_path_is_not_correct(cell, fault):
    assert bench_run.run(args(cell), hooks=fault)["correct"] is False


def test_lower_precision_control_reads_far_above_the_program():
    """The control (the reference at ``high``) in the program's place reads
    at least 3x what the program reads on every compared number, at the
    rehearsal's size: the comparison separates the two."""
    cell = spec.load_cell(SOLO)
    cfg, mix = bench_run.rehearsal_sizes(cell.config, cell.traffic)
    r = calibrate.readings(cell, cfg, mix, seed=5, control=True)
    for name in cell.config["correct"]["limits"]:
        assert r["control"][name] >= 3 * r["program"][name], name
