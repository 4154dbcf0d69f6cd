"""
Child-process containment: run a command with a hard deadline, and kill
a child's whole process group.

A child that blocks inside native code cannot be stopped by an
in-process timeout, and one stuck in an uninterruptible state may not
even die on SIGKILL. The containment recipe is therefore: start the
child in its own session, enforce the deadline from the parent, kill
the whole process GROUP on expiry (so grandchildren die too), and bound
the post-kill wait so an unkillable child is abandoned rather than
inherited as a parent hang. ``serve/procfleet.py`` applies the
group-kill half (:func:`_kill_group`) to its worker processes;
:func:`run_child_with_deadline` is the whole recipe for a one-shot
command.

A child that needs an accelerator can only get it from a parent that
has not initialised a JAX backend: a device belongs to one process at a
time.
"""

import os
import signal
import subprocess
import sys


def run_child_with_deadline(cmd, timeout, kill_wait=10, capture=True):
    """Run ``cmd`` with a hard deadline; never block past
    ``timeout + kill_wait``.

    Returns ``(status, returncode, output_text)``:
      status: 'ok' (rc 0), 'error' (nonzero rc), or 'timeout'
      returncode: the child's exit code — or, explicitly, ``None``
        for the ABANDONED-UNKILLABLE case: the bounded post-kill wait
        expired before the child could be reaped, so no exit code
        exists yet (and whatever Popen might eventually learn is
        unknowable here; callers must treat None as "containment gave
        up", not as success).
      output_text: captured stdout AND stderr interleaved (stderr is
        merged into the stdout pipe so a crashing child's traceback
        survives containment instead of vanishing), '' when nothing
        landed, or None with ``capture=False`` (the child inherits
        the parent's streams).

    The child is started in its own session (process group) so the
    deadline kill reaches grandchildren as well.
    """
    popen_kw = {"start_new_session": True}
    if capture:
        popen_kw.update(stdout=subprocess.PIPE,
                        stderr=subprocess.STDOUT, text=True)
    proc = subprocess.Popen(cmd, **popen_kw)
    out = None
    try:
        out, _ = proc.communicate(timeout=timeout)
        rc = proc.returncode
        status = "ok" if rc == 0 else "error"
    except subprocess.TimeoutExpired:
        _kill_group(proc)
        try:
            out, _ = proc.communicate(timeout=kill_wait)
            rc = proc.returncode
        except subprocess.TimeoutExpired:
            # unkillable child: abandon, do not inherit its hang — and
            # return an EXPLICIT None (the process was never reaped;
            # there is no exit code), not whatever stale value the
            # Popen object happens to hold
            rc = None
        status = "timeout"
    return status, rc, (out if capture else None)


def _kill_group(proc, sig=signal.SIGKILL):
    """Signal a child's whole process group (grandchildren included);
    falls back to the process alone when the group is gone. THE one
    containment recipe — the procfleet supervisor imports it rather
    than growing a drifting copy."""
    try:
        os.killpg(os.getpgid(proc.pid), sig)
    except (ProcessLookupError, PermissionError, OSError):
        try:
            proc.send_signal(sig)
        except (ProcessLookupError, OSError):
            pass


def relay(out):
    """Forward a child's captured stdout to this process's stdout."""
    if out:
        sys.stdout.write(out)
        sys.stdout.flush()
