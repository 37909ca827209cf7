"""The trace reduction against a small recorded v5e trace (``data/v5e_window.xplane.pb``:
32 telemetry pushes and one scoring round on one chip, recorded by a chip run of PR 21,
the same file as tests/telemetry/data). Run by hand:

    JAX_PLATFORMS=cpu python -m pytest benchmark/tests -q
"""

import os
import sys

import pytest

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__)))))

from benchmark import xplane  # noqa: E402

TRACE = os.path.join(os.path.dirname(__file__), "data", "v5e_window.xplane.pb")


@pytest.fixture(scope="module")
def reduced():
    from jax.profiler import ProfileData

    return xplane.reduce(ProfileData.from_file(TRACE), window_s=0.01)


def test_programs_are_read_from_the_device_plane(reduced):
    assert reduced.planes == 1
    assert set(reduced.programs) == {"jit__push_impl", "jit__score_reset_impl"}
    assert len(reduced.programs["jit__push_impl"]) == 5
    assert all(1e-6 < s < 3e-6 for s in reduced.programs["jit__push_impl"])


def test_busy_is_the_union_of_op_intervals(reduced):
    # ops of one program abut or nest; the union can be no longer than the programs
    total_programs = sum(sum(v) for v in reduced.programs.values())
    assert 0 < reduced.busy_s <= total_programs * 1.001
    # the window is the extent of the device's events, never more than the host's
    assert reduced.busy_s < reduced.window_s <= 0.01


def test_ops_are_keyed_by_instruction_name(reduced):
    assert "add" in reduced.ops
    assert all("%" not in name and "=" not in name for name in reduced.ops)
    assert reduced.op_text["add"].startswith("%add")


def test_breakdown_is_bounded_and_sorted(reduced):
    b = reduced.breakdown()
    assert len(b["device_ops"]) <= 10 and len(b["idle_gaps"]) <= 10
    seconds = [s for _, s in b["device_ops"]]
    assert seconds == sorted(seconds, reverse=True)


def test_merge_and_attribute():
    assert xplane.merge([(0, 1), (0.5, 2), (3, 4), (3.5, 3.7)]) == [(0, 2), (3, 4)]
    spans = [(0.0, 1.0, "step"), (1.0, 5.0, "restore"), (1.5, 2.0, "hooks")]
    assert xplane.attribute((0.9, 3.0), spans) == "restore"
    assert xplane.attribute((6.0, 7.0), spans) == "unattributed"
    assert xplane.op_name("%fused_median_weights.1 = (f32[1,64]) custom-call(...)") \
        == "fused_median_weights"
    assert xplane.program_name("jit_train_step(123456)") == "jit_train_step"
