"""The restart engine protecting the framework's most complex workload: the
pipelined+expert-parallel MoE example survives an injected fault and resumes from
its local checkpoint (examples/moe_pipeline_training.py driven end to end)."""

import os
import subprocess
import sys

REPO = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def test_moe_pipeline_example_restarts_and_resumes(tmp_path):
    proc = subprocess.run(
        [
            sys.executable,
            os.path.join(REPO, "examples", "moe_pipeline_training.py"),
            "--cpu",
            "--steps", "8",
            "--fault-step", "3",
            "--ckpt-root", str(tmp_path),
        ],
        capture_output=True,
        text=True,
        timeout=560,
    )
    out = proc.stdout
    assert proc.returncode == 0, f"stdout:\n{out}\nstderr:\n{proc.stderr[-2000:]}"
    # Fault at step 3 after the step-2 checkpoint: the restart resumes at step 3.
    assert "RESUMED step=3" in out, out
    assert "DONE loss=" in out, out
