"""Tests for the child-process containment recipe
(utils/childproc.py): hard deadline, process-group kill, bounded
post-kill wait."""

import sys
import time

from skdist_tpu.utils.childproc import run_child_with_deadline


def _py(code):
    return [sys.executable, "-c", code]


def test_ok_captures_stdout():
    status, rc, out = run_child_with_deadline(
        _py("print('hello'); print('{\"x\": 1}')"), timeout=30
    )
    assert status == "ok" and rc == 0
    assert "hello" in out and '{"x": 1}' in out


def test_error_propagates_returncode():
    status, rc, out = run_child_with_deadline(
        _py("import sys; print('partial'); sys.exit(3)"), timeout=30
    )
    assert status == "error" and rc == 3
    assert "partial" in out


def test_timeout_kills_process_group():
    # child spawns a grandchild; both must die at the deadline (the
    # group kill), and the call must return promptly, not block on the
    # grandchild holding the stdout pipe open
    code = (
        "import subprocess, sys, time;"
        "p = subprocess.Popen([sys.executable, '-c', 'import time; time.sleep(60)']);"
        "print('spawned', flush=True);"
        "time.sleep(60)"
    )
    t0 = time.perf_counter()
    # timeout must comfortably cover interpreter cold-start so the
    # child reaches its print before the deadline fires
    status, rc, out = run_child_with_deadline(_py(code), timeout=5, kill_wait=10)
    wall = time.perf_counter() - t0
    assert status == "timeout"
    assert "spawned" in (out or "")
    assert wall < 25, f"did not return promptly after kill ({wall:.1f}s)"


def test_no_capture_mode():
    status, rc, out = run_child_with_deadline(
        _py("pass"), timeout=30, capture=False
    )
    assert status == "ok" and out is None


def test_stderr_captured_with_stdout():
    """A crashing child's traceback (stderr) must survive containment
    — capture merges stderr into the stdout pipe (the round-13
    satellite: tracebacks used to vanish)."""
    status, rc, out = run_child_with_deadline(
        _py("import sys; print('out-line'); "
            "sys.stderr.write('err-line\\n'); "
            "raise RuntimeError('child exploded')"),
        timeout=30,
    )
    assert status == "error" and rc == 1
    assert "out-line" in out
    assert "err-line" in out
    assert "child exploded" in out  # the traceback itself


def test_timeout_returncode_contract():
    """A killed-within-bounds child reports its signal returncode; the
    docstring pins the abandoned-unkillable case to an EXPLICIT None
    (no stale value)."""
    status, rc, out = run_child_with_deadline(
        _py("import time; print('alive', flush=True); time.sleep(60)"),
        timeout=3, kill_wait=10,
    )
    assert status == "timeout"
    # killed and reaped inside kill_wait: the SIGKILL returncode
    assert rc is not None and rc < 0
    assert "alive" in out
