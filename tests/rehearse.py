"""One cell's ``--rehearsal`` run as a test sees it: a subprocess on the CPU,
its last line parsed. One at a time across the suite's workers: the serve
plane's HTTP proxy binds ONE port (8000), and two rehearsals that start
together lose one of them to ``address already in use``."""

import fcntl
import json
import os
import subprocess
import sys
import tempfile

from benchmarks.lib import spec


def run_cell(cell: str, seed: int, seconds: int = 3) -> dict:
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    lock = os.path.join(tempfile.gettempdir(), "ray_tpu_rehearsal.lock")
    with open(lock, "w") as held:
        fcntl.flock(held, fcntl.LOCK_EX)
        run = subprocess.run(
            [sys.executable, "benchmarks/run.py", "--workload", cell,
             "--rehearsal", "--seed", str(seed), "--seconds", str(seconds),
             "--trace", "0"], cwd=spec.ROOT, env=env, capture_output=True,
            text=True, timeout=120)
    assert run.returncode == 0, run.stderr[-2000:]
    return json.loads(run.stdout.strip().splitlines()[-1])
