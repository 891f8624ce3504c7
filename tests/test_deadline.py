"""The per-test deadline of ``tests/conftest.py``, through that file itself:
made-up test files beside a copy of it, run as the driver runs tier-1
(``-p xdist -n 2 --dist loadfile``), each wedged test given one second by
the ``deadline`` marker."""

import os
import shutil
import subprocess
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

FILES = {
    "test_call_waits.py": """
import threading
import pytest

def bystander(stop):
    stop.wait(60)

@pytest.mark.deadline(1)
def test_waits_on_an_event_nobody_sets():
    stop = threading.Event()
    threading.Thread(target=bystander, args=(stop,), daemon=True).start()
    try:
        threading.Event().wait()
    finally:
        stop.set()

def test_after_the_wedged_call():
    pass
""",
    "test_fixture_waits.py": """
import threading
import pytest

@pytest.fixture(scope="module")
def cluster():
    threading.Event().wait()

@pytest.mark.deadline(1.5)  # not at the instant the other file's stacks print
def test_needs_the_cluster(cluster):
    pass

def test_after_the_wedged_fixture():
    pass
""",
    "test_signal_cannot_reach.py": """
import os
import signal
import threading
import pytest

@pytest.mark.deadline(1)
def test_waits_where_no_signal_is_delivered():
    # xdist runs the test a lost worker died in again: wedge only once
    if os.path.exists("wedged_once"):
        return
    open("wedged_once", "w").close()
    signal.pthread_sigmask(signal.SIG_BLOCK, {signal.SIGALRM})
    threading.Event().wait()

def test_after_the_worker_was_lost():
    pass
""",
}
QUIET = {"test_passes.py": "def test_passes():\n    pass\n"}


def run_tier1_style(tmp_path, files):
    shutil.copy(os.path.join(ROOT, "tests", "conftest.py"), tmp_path)
    for name, text in files.items():
        (tmp_path / name).write_text(text)
    return subprocess.run(
        [sys.executable, "-m", "pytest", ".", "-q", "-p", "no:cacheprovider",
         "-p", "xdist", "-n", "2", "--dist", "loadfile", "-p", "no:randomly"],
        cwd=tmp_path, env=dict(os.environ, PYTHONPATH=ROOT, COLUMNS="200"),
        text=True, capture_output=True, timeout=120)


@pytest.fixture(scope="module")
def wedged_run(tmp_path_factory):
    return run_tier1_style(tmp_path_factory.mktemp("wedged"), FILES)


def test_the_run_reaches_its_end_and_counts_the_rest(wedged_run):
    assert wedged_run.returncode == 1, wedged_run.stdout
    assert "2 failed, 4 passed, 1 error" in wedged_run.stdout


@pytest.mark.parametrize("line", [
    "FAILED test_call_waits.py::test_waits_on_an_event_nobody_sets - "
    "TimeoutError: test_call_waits.py::test_waits_on_an_event_nobody_sets "
    "passed its deadline of 1 s",
    "ERROR test_fixture_waits.py::test_needs_the_cluster - TimeoutError: "
    "test_fixture_waits.py::test_needs_the_cluster passed its deadline of "
    "1.5 s",
    "FAILED test_signal_cannot_reach.py::"
    "test_waits_where_no_signal_is_delivered"],
    ids=["a-call", "a-module-scoped-fixture", "a-wait-no-signal-breaks"])
def test_a_wedged_test_fails_by_name(wedged_run, line):
    assert line in wedged_run.stdout


def test_every_threads_stack_is_in_the_output_of_a_run_nobody_killed(
        wedged_run):
    err = wedged_run.stderr
    assert "test_call_waits.py::test_waits_on_an_event_nobody_sets passed " \
        "its deadline of 1 s" in err
    # the thread that waits and the one beside it
    assert " in test_waits_on_an_event_nobody_sets" in err
    assert " in bystander" in err
    assert " in cluster" in err
    # twice the deadline, for the wait the alarm could not end
    assert "Timeout (0:00:02)!" in err
    assert " in test_waits_where_no_signal_is_delivered" in err


def test_a_passing_test_prints_nothing(tmp_path):
    quiet = run_tier1_style(tmp_path, QUIET)
    assert quiet.returncode == 0, quiet.stdout
    assert "1 passed" in quiet.stdout
    assert quiet.stderr == ""
