"""The time limit ``tests/conftest.py`` gives every test, shown on inner
pytest runs so that the cases that must fail do so inside them. Each inner run
loads the repo's conftest with its two constants cut to a second."""

import os
import subprocess
import sys
import textwrap

TESTS_DIR = os.path.dirname(os.path.abspath(__file__))

INNER_CONFTEST = f"""
import importlib.util

_spec = importlib.util.spec_from_file_location(
    "repo_conftest", {os.path.join(TESTS_DIR, "conftest.py")!r})
_repo = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(_repo)
_repo.TEST_LIMIT_S = 1.0
_repo.HARD_GRACE_S = 1.0


def pytest_configure(config):
    config.pluginmanager.register(_repo, "repo_conftest")
"""


def _inner_run(tmp_path, body, *args):
    (tmp_path / "conftest.py").write_text(INNER_CONFTEST)
    with open(os.path.join(os.path.dirname(TESTS_DIR), "pytest.ini")) as f:
        (tmp_path / "pytest.ini").write_text(f.read())
    (tmp_path / "test_inner.py").write_text(textwrap.dedent(body))
    return subprocess.run(
        [sys.executable, "-m", "pytest", "test_inner.py", "-v", "-p", "no:cacheprovider",
         "-p", "no:randomly", *args],
        cwd=tmp_path, capture_output=True, text=True, timeout=240,
    )


def test_sleep_past_the_soft_limit_fails_by_name_and_the_run_goes_on(tmp_path):
    r = _inner_run(tmp_path, """
        import time

        def test_sleeps():
            time.sleep(60)

        def test_after():
            pass
    """, "-p", "no:xdist")
    assert r.returncode == 1, r.stdout + r.stderr
    assert "test_inner.py::test_sleeps FAILED" in r.stdout
    assert "test_inner.py::test_after PASSED" in r.stdout
    assert "test_sleeps ran past its time limit of 1 s" in r.stdout
    assert "time.sleep(60)" in r.stdout  # its own traceback
    assert "1 failed, 1 passed" in r.stdout


def test_marker_raises_the_limit_for_its_test_only(tmp_path):
    r = _inner_run(tmp_path, """
        import time

        import pytest

        @pytest.mark.time_limit(30)
        def test_marked():
            time.sleep(2.5)

        def test_unmarked():
            time.sleep(2.5)
    """, "-p", "no:xdist")
    assert r.returncode == 1, r.stdout + r.stderr
    assert "test_inner.py::test_marked PASSED" in r.stdout
    assert "test_inner.py::test_unmarked FAILED" in r.stdout
    assert "test_unmarked ran past its time limit of 1 s" in r.stdout


# a default pthread mutex locked twice by one thread: a wait in native code that
# no signal ends, so the soft limit's handler never gets to run
BLOCKS_IN_NATIVE_CODE = """
    import ctypes
    import threading
    import time

    def test_blocks():
        threading.Thread(target=time.sleep, args=(60,), daemon=True, name="bystander").start()
        libc = ctypes.CDLL(None)
        mutex = ctypes.create_string_buffer(64)
        libc.pthread_mutex_lock(mutex)
        libc.pthread_mutex_lock(mutex)

    def test_after():
        pass
"""


def test_main_thread_in_a_native_lock_ends_the_run_with_every_stack(tmp_path):
    r = _inner_run(tmp_path, BLOCKS_IN_NATIVE_CODE, "-p", "no:xdist")
    assert r.returncode == 1, r.stdout + r.stderr
    assert "passed" not in r.stdout and "failed" not in r.stdout  # the process was ended
    assert "Timeout (0:00:02)!" in r.stderr
    # the blocked main thread and the bystander, each with its stack
    assert r.stderr.count("most recent call first") >= 2
    assert "in test_blocks" in r.stderr


def test_under_xdist_the_worker_goes_down_and_the_rest_runs(tmp_path):
    r = _inner_run(tmp_path, BLOCKS_IN_NATIVE_CODE, "-p", "xdist", "-n", "1")
    assert r.returncode == 1, r.stdout + r.stderr
    assert "node down" in r.stdout
    assert "in test_blocks" in r.stderr
    assert "test_inner.py::test_after" in r.stdout and "PASSED" in r.stdout
    assert "1 failed, 1 passed" in r.stdout
