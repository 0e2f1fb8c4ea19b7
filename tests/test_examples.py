"""The example drivers double as integration tests — the reference's own
discipline (SURVEY.md §4: EXAMPLE drivers fabricate xtrue and check the
solve, .travis_tests.sh runs them as CI).  Each must exit 0."""

import os
import subprocess
import sys

import pytest

EXAMPLES = ["pddrive.py", "pddrive1.py", "pddrive2.py", "pddrive3.py",
            "pddrive4.py", "pzdrive.py", "pzdrive1.py", "pzdrive2.py",
            "pzdrive3.py", "pzdrive4.py", "pddrive_ABglobal.py",
            "pddrive_dist.py", "pddrive_df64.py", "pddrive_grid.py",
            "pddrive_refactor.py"]
ROOT = os.path.join(os.path.dirname(__file__), "..")


@pytest.mark.parametrize("script", EXAMPLES)
def test_example_runs_clean(script):
    env = dict(os.environ)
    # examples run in a fresh interpreter: pin the CPU backend the same
    # way the conftest does (a host with a chip would otherwise run
    # them there)
    r = subprocess.run(
        [sys.executable, os.path.join(ROOT, "examples", script),
         "--backend", "cpu"],
        capture_output=True, timeout=600, env=env, cwd=ROOT)
    assert r.returncode == 0, r.stdout.decode() + r.stderr.decode()
    assert b"residual" in r.stdout


import pytest  # noqa: E402

# slow tier: multi-process / native-build / at-scale — fast CI runs -m "not slow"
pytestmark = pytest.mark.slow
