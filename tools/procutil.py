"""Process-group-bounded subprocess execution.

One home for the Popen(start_new_session) + killpg(SIGKILL) +
bounded-second-communicate pattern used wherever a child may spawn
grandchildren that inherit the stdout pipe (launcher workers):
`subprocess.run(timeout=...)` alone kills only the direct child and
then blocks in communicate() while a grandchild holds the pipe. Used
by tests/test_dist_launcher.py.
"""
from __future__ import annotations

import os
import signal
import subprocess


def run_group_bounded(argv, timeout, env=None, cwd=None):
    """Run argv in its own process group; SIGKILL the whole group on
    timeout. Returns (returncode_or_None, stdout, stderr, timed_out)
    — returncode is None when the deadline fired.
    """
    proc = subprocess.Popen(argv, env=env, cwd=cwd,
                            stdout=subprocess.PIPE,
                            stderr=subprocess.PIPE, text=True,
                            start_new_session=True)
    try:
        out, err = proc.communicate(timeout=timeout)
        return proc.returncode, out or "", err or "", False
    except subprocess.TimeoutExpired:
        try:
            os.killpg(proc.pid, signal.SIGKILL)
        except (ProcessLookupError, PermissionError):
            proc.kill()
        try:
            # bounded: a grandchild that escaped the session could
            # still hold the stdout pipe open
            out, err = proc.communicate(timeout=15)
        except (subprocess.TimeoutExpired, OSError):
            out, err = "", ""
        return None, out or "", err or "", True
