"""The end-to-end rehearsal on the CPU (about three minutes): the throw-away
cells of ``tests/rehearsal/`` run through the real harness, and the result can
never look like a pass."""

import os
import subprocess
import sys

HERE = os.path.dirname(os.path.abspath(__file__))


def test_rehearsal_runs_the_added_files_and_never_passes():
    r = subprocess.run([sys.executable, os.path.join(HERE, "rehearse.py"),
                        "--seconds", "3"], capture_output=True, text=True,
                       timeout=900)
    assert r.returncode == 2, r.stdout[-3000:] + r.stderr[-3000:]
    lines = [l for l in r.stdout.splitlines() if l.startswith("rehearsed ")]
    assert len(lines) == 6
    assert sum('"workload": "tiny-moe.drip"' in l for l in lines) == 2
    assert all('"correct": false' in l and '"rehearsal": true' in l
               for l in lines)
    assert "rehearsal passed" in r.stdout
