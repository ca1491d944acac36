"""``tools/tpu_aot.py``'s rows for the tests that read them (not a test
file). A model's row sits in a file of that model that holds SEVERAL tests
(its ``test_remat_operands_<family>.py`` / ``test_keye_remat.py``, else its
``_role`` file): ``--dist loadfile`` hands files out by their number of
tests, most first, so a file of one 60-100 s test is handed out last, and
seven of them were the run's tail (PR 55: the wall 113 s over sum / 6)."""
import json
import os
import subprocess
import sys

import pytest

REPO = os.path.abspath(os.path.join(os.path.dirname(__file__), ".."))


def run(argv, env=None, cwd=None, timeout=120):
    return subprocess.run(
        [sys.executable, *argv], env=env, cwd=cwd, timeout=timeout,
        capture_output=True, text=True,
    )


def tpu_aot(*programs):
    """tools/tpu_aot.py's rows by program, compiled in a child (the real
    XLA:TPU and Mosaic compilers, through libtpu's compile-only client)."""
    out = run(
        [os.path.join(REPO, "tools", "tpu_aot.py"), *programs],
        # no persistent cache, as the tool runs by hand: an executable for an
        # absent chip is written (tens of MB) and can never be read back
        env={k: v for k, v in os.environ.items()
             if k != "JAX_COMPILATION_CACHE_DIR"},
        timeout=300,  # a pathological compile fails here, not after an hour
    )
    if out.returncode == 3:
        pytest.skip(out.stderr.strip().splitlines()[-1])
    assert out.returncode == 0, out.stderr[-3000:]
    return {
        row["program"]: row
        for row in map(json.loads, out.stdout.strip().splitlines())
    }
