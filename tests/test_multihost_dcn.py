"""Two-process DCN test: jax.distributed over a local coordinator.

parallel/multihost.py had only ever run with
jax.process_count() == 1. This spawns two real processes (4 virtual CPU
devices each), initializes the distributed runtime, and runs the
host_batches_to_global feed + sharded_count_scan across the 8-device
global mesh with cross-process collectives.
"""

import os
import socket
import subprocess
import sys
from pathlib import Path

import jax
import pytest

_WORKER = Path(__file__).with_name("_dcn_worker.py")
_REPO = Path(__file__).resolve().parent.parent


def _free_port() -> int:
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


@pytest.mark.skipif(
    jax.default_backend() == "cpu",
    reason="the CPU backend has no multiprocess collectives (XLA "
    "multiprocess runtime unimplemented for CPU): the 2-process DCN "
    "exchange cannot initialize on a CPU-only harness",
)
def test_two_process_scan_over_dcn():
    port = _free_port()
    env = {
        k: v
        for k, v in os.environ.items()
        if k not in ("JAX_PLATFORMS", "XLA_FLAGS", "JAX_NUM_CPU_DEVICES")
    }
    env["PYTHONPATH"] = str(_REPO)
    procs = [
        subprocess.Popen(
            [sys.executable, str(_WORKER), str(i), str(port)],
            stdout=subprocess.PIPE,
            stderr=subprocess.PIPE,
            text=True,
            cwd=str(_REPO),
            env=env,
        )
        for i in range(2)
    ]
    outs = []
    try:
        for p in procs:
            out, err = p.communicate(timeout=150)
            outs.append((p.returncode, out, err))
    except subprocess.TimeoutExpired:
        for p in procs:
            p.kill()
        pytest.fail(f"DCN workers hung; partial output: {outs}")
    for i, (rc, out, err) in enumerate(outs):
        assert rc == 0, f"proc{i} rc={rc}\nstdout:\n{out}\nstderr:\n{err}"
        assert f"proc{i} DCN scan OK" in out, (out, err)
    # both processes computed the same replicated global count
    c0 = outs[0][1].split("count=")[1].strip()
    c1 = outs[1][1].split("count=")[1].strip()
    assert c0 == c1
