"""Child processes of the benchmark's parent, which never touches JAX.

One process owns a chip at a time, so every phase that needs the chip is a
child, started in its own process group and killed with the whole group
(`chip_smoke.py`'s pattern, PR 21).
"""
from __future__ import annotations

import os
import re
import signal
import subprocess
import sys
import threading
import time


class ChildFailed(Exception):
    pass


def kill_group(popen: subprocess.Popen, grace_s: float = 10.0) -> None:
    """Stop a child and everything it started (it leads its own group), and
    wait until it has ended."""
    for sig in (signal.SIGTERM, signal.SIGKILL):
        if popen.poll() is not None:
            break
        try:
            os.killpg(popen.pid, sig)
        except ProcessLookupError:
            break
        try:
            popen.wait(timeout=grace_s)
        except subprocess.TimeoutExpired:
            continue
    try:  # stragglers of the group that outlived its leader
        os.killpg(popen.pid, signal.SIGKILL)
    except (ProcessLookupError, PermissionError):
        pass
    popen.wait()


class Child:
    """A child in its own process group; its output is echoed to this
    process's stderr under a tag (stdout is kept for the result line) and
    kept, each line with the time it arrived."""

    def __init__(self, tag: str, cmd, cwd: str, env=None):
        self.tag = tag
        self.lines = []  # (monotonic seconds, text)
        self._lock = threading.Lock()
        print(f"[{tag}] $ {' '.join(cmd)}", file=sys.stderr, flush=True)
        self.t_spawn = time.monotonic()
        self.popen = subprocess.Popen(
            cmd, cwd=cwd, env=env, stdout=subprocess.PIPE,
            stderr=subprocess.STDOUT, text=True, bufsize=1,
            start_new_session=True)
        self._pump = threading.Thread(target=self._read, daemon=True)
        self._pump.start()

    def _read(self) -> None:
        for line in self.popen.stdout:
            line = line.rstrip("\n")
            with self._lock:
                self.lines.append((time.monotonic(), line))
            print(f"[{self.tag}] {line}", file=sys.stderr, flush=True)

    def snapshot(self):
        with self._lock:
            return list(self.lines)

    def wait(self, timeout_s: float) -> int:
        try:
            rc = self.popen.wait(timeout=timeout_s)
        except subprocess.TimeoutExpired:
            self.stop()
            raise ChildFailed(f"{self.tag} ran past {timeout_s:.0f}s")
        self.stop()
        return rc

    def wait_for_line(self, pattern: str, timeout_s: float):
        """(seconds since spawn, line) of the first output line matching
        `pattern`; fails if the child exits or the limit passes first."""
        rx = re.compile(pattern)
        deadline = time.monotonic() + timeout_s
        seen = 0
        while time.monotonic() < deadline:
            lines = self.snapshot()
            for t, line in lines[seen:]:
                if rx.search(line):
                    return t - self.t_spawn, line
            seen = len(lines)
            if self.popen.poll() is not None:
                raise ChildFailed(f"{self.tag} exited ({self.popen.returncode}) "
                                  f"before printing /{pattern}/")
            time.sleep(0.05)
        raise ChildFailed(f"{self.tag}: no /{pattern}/ within {timeout_s:.0f}s")

    def first_line(self, pattern: str):
        rx = re.compile(pattern)
        for t, line in self.snapshot():
            if rx.search(line):
                return t - self.t_spawn, line
        return None

    def stop(self) -> None:
        kill_group(self.popen)
        self._pump.join(timeout=10)


def fields(line: str) -> dict:
    """key=value tokens of a DEVICE:/READY line (values may be quoted)."""
    out = {}
    for m in re.finditer(r"(\w+)=('(?:[^']*)'|\S+)", line):
        v = m.group(2)
        out[m.group(1)] = v[1:-1] if v.startswith("'") else v
    return out
