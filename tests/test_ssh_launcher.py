"""ssh-launched workers, exercised through the REAL CommandLauncher.ssh
code path (reference ``YarnJobSubmission.cs:63-111`` remote process
groups).

Two tiers:

1. An ``ssh`` SHIM on PATH that behaves like a remote shell: it strips
   client options up to the host token, scrubs the environment
   (``env -i``), and re-parses the joined command line with ``bash -c``
   — exactly what sshd does on the remote side.  The gang must come up
   THROUGH the shim (quoted env-forwarding argv, routable 0.0.0.0
   bind), run a distributed job, and die when the launcher stops the
   ssh client.
2. The same flow over REAL ssh to localhost, skipped unless an sshd is
   reachable with agent/key auth (CI boxes without sshd skip).
"""

import os
import shutil
import socket
import stat
import subprocess
import time

import numpy as np
import pytest

from dryad_tpu import DryadContext
from dryad_tpu.cluster.localjob import CommandLauncher, LocalJobSubmission

SHIM = """#!/bin/bash
# fake sshd: log the client argv, skip client options up to the host
# token, then hand the space-joined command line to a login shell with
# a SCRUBBED environment — the remote-shell re-parse ssh really does.
echo "$@" >> "$SSH_SHIM_LOG"
args=("$@")
i=0
while [[ $i -lt ${#args[@]} && "${args[$i]}" == -* ]]; do i=$((i+1)); done
host="${args[$i]}"; i=$((i+1))
echo "HOST=$host" >> "$SSH_SHIM_LOG"
cmd="${args[@]:$i}"
exec env -i /bin/bash -c "$cmd"
"""


@pytest.fixture
def ssh_shim(tmp_path, monkeypatch):
    bindir = tmp_path / "bin"
    bindir.mkdir()
    shim = bindir / "ssh"
    shim.write_text(SHIM)
    shim.chmod(shim.stat().st_mode | stat.S_IEXEC)
    log = tmp_path / "ssh.log"
    log.write_text("")
    monkeypatch.setenv("PATH", f"{bindir}:{os.environ['PATH']}")
    monkeypatch.setenv("SSH_SHIM_LOG", str(log))
    return log


def test_ssh_launcher_gang_through_shim(ssh_shim):
    """Workers launched via CommandLauncher.ssh survive the remote-shell
    re-parse (scrubbed env + space-joined argv), join the gang on the
    routable bind, execute a distributed group_by, and die on stop."""
    launcher = CommandLauncher.ssh(["nodeA", "nodeB"])
    with LocalJobSubmission(
        num_workers=2, devices_per_worker=2, launcher=launcher,
        bind_host="0.0.0.0", advertise_host="127.0.0.1",
    ) as sub:
        rng = np.random.default_rng(3)
        tbl = {
            "k": rng.integers(0, 30, 500).astype(np.int32),
            "v": np.ones(500, np.float32),
        }
        ctx = DryadContext(num_partitions_=8)
        out = sub.submit(
            ctx.from_arrays(tbl).group_by("k", {"c": ("count", None)})
        )
        ref = np.bincount(tbl["k"], minlength=30)
        got = dict(zip(out["k"].tolist(), out["c"].tolist()))
        assert got == {int(k): int(c) for k, c in enumerate(ref) if c}

        handles = list(sub._handles.values())
    # context exit stops the launcher: the ssh client hang-up must take
    # the worker with it (the -tt kill semantics the preset documents)
    deadline = time.monotonic() + 10
    for h in handles:
        while h.poll() is None and time.monotonic() < deadline:
            time.sleep(0.1)
        assert h.poll() is not None, "worker outlived its ssh client"

    text = ssh_shim.read_text()
    assert "-tt" in text, "ssh preset must force a remote tty"
    assert "HOST=nodeA" in text and "HOST=nodeB" in text
    # env forwarding rode the argv as quoted tokens
    assert "PYTHONPATH=" in text and " env " in f" {text} "


def _sshd_reachable(host: str = "localhost", port: int = 22) -> bool:
    if shutil.which("ssh") is None:
        return False
    try:
        with socket.create_connection((host, port), timeout=2):
            pass
    except OSError:
        return False
    probe = subprocess.run(
        ["ssh", "-o", "BatchMode=yes", "-o", "StrictHostKeyChecking=no",
         "-o", "ConnectTimeout=3", host, "true"],
        capture_output=True, timeout=15,
    )
    return probe.returncode == 0


@pytest.mark.skipif(
    not _sshd_reachable(), reason="no sshd reachable at localhost:22"
)
def test_ssh_launcher_gang_real_sshd():
    """The real thing: workers started over ssh to localhost — env
    forwarding, gang join, distributed execution, remote-kill on stop
    (requires key/agent auth to localhost)."""
    launcher = CommandLauncher.ssh(
        ["localhost"],
        ssh_args=["-o", "BatchMode=yes", "-o", "StrictHostKeyChecking=no"],
    )
    with LocalJobSubmission(
        num_workers=2, devices_per_worker=1, launcher=launcher,
        bind_host="0.0.0.0", advertise_host="127.0.0.1",
    ) as sub:
        rng = np.random.default_rng(5)
        tbl = {"k": rng.integers(0, 10, 200).astype(np.int32)}
        ctx = DryadContext(num_partitions_=2)
        out = sub.submit(
            ctx.from_arrays(tbl).group_by("k", {"c": ("count", None)})
        )
        assert int(np.sum(out["c"])) == 200
        handles = list(sub._handles.values())
    deadline = time.monotonic() + 10
    for h in handles:
        while h.poll() is None and time.monotonic() < deadline:
            time.sleep(0.1)
        assert h.poll() is not None, "worker outlived its ssh client"
