"""Child processes of a run (copied from ``chip_smoke.py``, which
proved it on the chip): each in its own session with its output in a
log file, each stopped and waited for when the run ends."""

from __future__ import annotations

import os
import signal
import socket
import subprocess
import sys
import time
import urllib.error
import urllib.request


class RunFailure(Exception):
    pass


class Procs:
    def __init__(self, log_dir: str, cwd: str):
        self.log_dir = log_dir
        self.cwd = cwd
        self._procs = []

    def start(self, name: str, cmd: list, env: dict = None):
        log_path = os.path.join(self.log_dir, f"{name}.log")
        with open(log_path, "wb") as log:
            proc = subprocess.Popen(
                cmd, cwd=self.cwd, stdout=log, stderr=subprocess.STDOUT,
                start_new_session=True, env=env)
        self._procs.append((name, proc, log_path))
        return proc, log_path

    def stop_all(self) -> bool:
        """SIGTERM, then SIGKILL, newest first (the router's open
        connections would hold the engine's shutdown).  A TPU runtime
        can take a minute to let go of its chips."""
        stopped = True
        for name, proc, _ in reversed(self._procs):
            for sig, wait_s in ((signal.SIGTERM, 60), (signal.SIGKILL, 60)):
                if proc.poll() is not None:
                    break
                try:
                    os.killpg(proc.pid, sig)
                except ProcessLookupError:
                    pass
                try:
                    proc.wait(timeout=wait_s)
                except subprocess.TimeoutExpired:
                    pass
            if proc.poll() is None:
                print(f"[chipbench] {name} (pid {proc.pid}) survived "
                      "SIGKILL", file=sys.stderr)
                stopped = False
        return stopped

    def dump_tails(self, nbytes: int = 5000) -> None:
        for name, _, log_path in self._procs:
            with open(log_path, "rb") as f:
                tail = f.read()[-nbytes:].decode("utf-8", "replace")
            print(f"---- tail of {name} log ({log_path}) ----\n{tail}",
                  file=sys.stderr)


def free_port() -> int:
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


def http(url: str, timeout: float, data: bytes = None,
         headers: dict = None) -> str:
    """Body of a 2xx reply (urlopen raises on anything else); POST
    where ``data`` is given."""
    req = urllib.request.Request(url, data=data, headers=headers or {})
    with urllib.request.urlopen(req, timeout=timeout) as resp:
        return resp.read().decode()


def wait_http_ok(url: str, proc, name: str, limit: float) -> None:
    end = time.time() + limit
    while time.time() < end:
        if proc.poll() is not None:
            raise RunFailure(f"{name} exited with code {proc.returncode} "
                             f"before answering {url}")
        try:
            http(url, timeout=5)
            return
        except (urllib.error.URLError, OSError):
            time.sleep(0.5)
    raise RunFailure(f"{name} did not answer {url} within {limit:.0f}s")
