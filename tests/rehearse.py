"""One cell's ``--rehearsal`` run as a test sees it (``run.py``'s, or a sweep
script's that takes the same flags): a subprocess on the CPU, its last line
parsed. One at a time across the suite's workers: the serve
plane's HTTP proxy binds ONE port (8000), and two rehearsals that start
together lose one of them to ``address already in use``."""

import fcntl
import json
import os
import subprocess
import sys
import tempfile

from benchmarks.lib import spec


def run_cell(cell: str, seed: int, seconds: int = 3,
             script: str = "benchmarks/run.py",
             extra: tuple = ("--trace", "0"), serves: bool = True) -> dict:
    """``serves=False``: the run starts no serve plane (a sweep script on a
    train cell), binds no port and does not queue behind the lock."""
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    lock = os.path.join(tempfile.gettempdir(), "ray_tpu_rehearsal.lock")
    with open(lock, "w") as held:
        if serves:
            fcntl.flock(held, fcntl.LOCK_EX)
        run = subprocess.run(
            [sys.executable, script, "--workload", cell,
             "--rehearsal", "--seed", str(seed), "--seconds", str(seconds),
             *extra], cwd=spec.ROOT, env=env, capture_output=True,
            text=True, timeout=120)
    assert run.returncode == 0, run.stderr[-2000:]
    return json.loads(run.stdout.strip().splitlines()[-1])
