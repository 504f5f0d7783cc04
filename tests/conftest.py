"""Shared fixtures for the test suite.

``device_pool`` replaces the old per-test ``subprocess.run(python -c ...)``
harness used by test_launch / test_sharding / test_tpcomm. Multi-device
tests need ``XLA_FLAGS=--xla_force_host_platform_device_count=N`` set before
JAX's first init while the rest of the suite must keep the default single
host device, so multi-device work runs in a separate process — but one
persistent worker per session (tests/_device_worker.py), not one cold
interpreter per test: each test ships its script over a JSON-line pipe and
gets the parsed result back, sharing the worker's jax import and compilation
cache. The worker device count comes from ``REPRO_HOST_DEVICES`` (default 8,
see scripts/run_tests.sh).
"""

from __future__ import annotations

import json
import os
import select
import subprocess
import sys
import textwrap
import threading
import time

import pytest

_TESTS_DIR = os.path.dirname(os.path.abspath(__file__))
_REPO = os.path.dirname(_TESTS_DIR)
_WORKER = os.path.join(_TESTS_DIR, "_device_worker.py")

DEFAULT_TIMEOUT_S = 900


class DevicePoolError(AssertionError):
    """A script failed inside the device-pool worker."""


class DevicePool:
    """Client for the persistent multi-device worker process."""

    def __init__(self, num_devices: int = 8):
        self.num_devices = num_devices
        self.proc = None
        self._stderr_lines: list = []
        self._spawn()

    def _spawn(self) -> None:
        env = dict(os.environ)
        env["PYTHONPATH"] = os.path.join(_REPO, "src") + (
            os.pathsep + env["PYTHONPATH"] if env.get("PYTHONPATH") else ""
        )
        env["XLA_FLAGS"] = (
            f"--xla_force_host_platform_device_count={self.num_devices}"
        )
        # The worker's devices are virtual CPU devices; keep it off any
        # accelerator, which belongs to one process at a time.
        env["JAX_PLATFORMS"] = "cpu"
        self.proc = subprocess.Popen(
            [sys.executable, "-u", _WORKER],
            stdin=subprocess.PIPE,
            stdout=subprocess.PIPE,
            stderr=subprocess.PIPE,
            env=env,
        )
        self._stderr_lines = []
        t = threading.Thread(
            target=self._drain_stderr, args=(self.proc,), daemon=True
        )
        t.start()

    def _drain_stderr(self, proc) -> None:
        for raw in proc.stderr:
            self._stderr_lines.append(raw.decode("utf-8", "replace"))
            del self._stderr_lines[:-500]

    def stderr_tail(self, n: int = 60) -> str:
        return "".join(self._stderr_lines[-n:])

    def _read_line(self, timeout: float) -> bytes:
        """Read one protocol line from the worker with a deadline."""
        fd = self.proc.stdout.fileno()
        deadline = time.monotonic() + timeout
        chunks = []
        while True:
            remaining = deadline - time.monotonic()
            if remaining <= 0:
                self.close()
                raise DevicePoolError(
                    f"device-pool script timed out after {timeout}s; worker "
                    f"stderr tail:\n{self.stderr_tail()}"
                )
            ready, _, _ = select.select([fd], [], [], min(remaining, 1.0))
            if not ready:
                if self.proc.poll() is not None:
                    raise DevicePoolError(
                        "device-pool worker died "
                        f"(rc={self.proc.returncode}); stderr tail:\n"
                        f"{self.stderr_tail()}"
                    )
                continue
            chunk = os.read(fd, 1 << 20)
            if not chunk:
                raise DevicePoolError(
                    "device-pool worker closed stdout; stderr tail:\n"
                    f"{self.stderr_tail()}"
                )
            chunks.append(chunk)
            if b"\n" in chunk:
                return b"".join(chunks)

    def run(self, body: str, timeout: float = DEFAULT_TIMEOUT_S) -> dict:
        """Exec dedented `body` in the worker; parse its last printed line
        as JSON (the same contract the old subprocess harness had).

        If a previous script killed the worker (timeout, crash), a fresh
        one is spawned first so one bad test can't cascade into failures
        for every later multi-device test — the old per-test subprocess
        harness had that isolation, and we keep it."""
        if self.proc is None or self.proc.poll() is not None:
            self._spawn()
        payload = json.dumps({"src": textwrap.dedent(body)})
        self.proc.stdin.write(payload.encode() + b"\n")
        self.proc.stdin.flush()
        resp = json.loads(self._read_line(timeout).decode())
        if not resp["ok"]:
            raise DevicePoolError(
                "device-pool script failed:\n"
                f"{resp['error']}\ncaptured stdout:\n{resp['stdout'][-3000:]}"
            )
        out = resp["stdout"].strip()
        if not out:
            raise DevicePoolError("device-pool script printed no result line")
        return json.loads(out.splitlines()[-1])

    def close(self) -> None:
        if self.proc is not None and self.proc.poll() is None:
            try:
                self.proc.stdin.close()
                self.proc.wait(timeout=10)
            except Exception:  # noqa: BLE001
                self.proc.kill()
                self.proc.wait(timeout=10)


@pytest.fixture(scope="session")
def device_pool():
    n = int(os.environ.get("REPRO_HOST_DEVICES", "8"))
    if n not in (4, 8):
        raise pytest.UsageError(
            f"REPRO_HOST_DEVICES={n} unsupported: the multi-device tests "
            "derive their mesh shapes and logical-partition divisibility "
            "from the device count and require it to be 4 or 8"
        )
    pool = DevicePool(num_devices=n)
    yield pool
    pool.close()
