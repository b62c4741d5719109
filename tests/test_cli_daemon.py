"""Daemon-mode CLI e2e: start --head spawns a detached head daemon;
external CLI invocations in FRESH processes authenticate via the
token persisted in the address file (regression: the daemon minted a
random cluster token but never persisted it, so every external CLI
call — status, submit, stop — died with 'authentication failed' and
stop leaked the daemon).

Reference analogue: `ray start --head` + `ray status` from another
shell (python/ray/tests/test_cli.py).
"""
import json
import os
import subprocess
import sys
import time

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _cli(*args, timeout=120):
    env = dict(os.environ)
    env.setdefault("JAX_PLATFORMS", "cpu")
    return subprocess.run(
        [sys.executable, "-m", "ray_tpu", *args],
        capture_output=True, text=True, timeout=timeout, cwd=REPO,
        env=env)


@pytest.fixture
def daemon():
    from ray_tpu.scripts.head_daemon import address_file_path
    if os.path.exists(address_file_path()):
        pytest.skip("another head daemon is already running")
    res = _cli("start", "--head", "--num-workers", "1")
    assert res.returncode == 0, res.stdout + res.stderr
    try:
        yield
    finally:
        _cli("stop")
        deadline = time.time() + 15
        while time.time() < deadline and os.path.exists(
                address_file_path()):
            time.sleep(0.2)
        subprocess.run(["pkill", "-f", "ray_tpu.scripts.head_daemon"],
                       capture_output=True)


def test_daemon_cli_auth_roundtrip(daemon):
    from ray_tpu.scripts.head_daemon import (address_file_path,
                                             read_address_file)
    # token persisted, file private
    addr, token, pid = read_address_file()
    assert addr and token and pid
    assert os.stat(address_file_path()).st_mode & 0o777 == 0o600

    # status from a FRESH process authenticates via the file token
    res = _cli("status")
    assert res.returncode == 0, res.stdout + res.stderr
    assert "Workers (1)" in res.stdout

    # a job runs end-to-end through the daemon
    res = _cli("submit", "--", sys.executable, "-c",
               "print('daemon-job-ok')")
    assert res.returncode == 0, res.stdout + res.stderr
    assert "daemon-job-ok" in res.stdout

    # stop actually reaches the daemon (auth ok) and removes the file
    res = _cli("stop")
    assert res.returncode == 0, res.stdout + res.stderr
    deadline = time.time() + 15
    while time.time() < deadline:
        if not os.path.exists(address_file_path()):
            break
        time.sleep(0.2)
    probe = subprocess.run(
        ["pgrep", "-f", "ray_tpu.scripts.head_daemon"],
        capture_output=True, text=True)
    assert probe.returncode != 0, "daemon survived stop"
