"""Multi-process (multi-host-style) runtime: real ``jax.distributed``.

Launches TWO separate Python processes that bootstrap via
``jax.distributed.initialize`` (the framework's ``MPI_Init`` equivalent,
SURVEY §2's comm-backend mapping) and run cross-process collectives over
the Gloo CPU backend — the closest single-machine stand-in for a
multi-host DCN pod. Exercises the same multi-process runtime the
``--distributed`` CLI flag initialises (the flag's argless auto-detect
``initialize()`` needs a real pod environment; here the coordinator is
passed explicitly) and the non-fully-addressable ``collect()`` +
process-0-only snapshot write.
"""

import os
import socket
import subprocess
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
WORKER = os.path.join(HERE, "_dist_worker.py")


def _free_port() -> int:
    with socket.socket() as s:
        s.bind(("localhost", 0))
        return s.getsockname()[1]


def test_two_process_distributed_run():
    coord = f"localhost:{_free_port()}"
    env = {k: v for k, v in os.environ.items() if k != "XLA_FLAGS"}
    procs = [
        subprocess.Popen(
            [sys.executable, WORKER, str(i), "2", coord],
            stdout=subprocess.PIPE, stderr=subprocess.PIPE, env=env,
            text=True,
        )
        for i in range(2)
    ]
    try:
        outs = [p.communicate(timeout=180) for p in procs]
    finally:
        # A worker hung in a collective would otherwise outlive the test,
        # holding the coordinator port.
        for p in procs:
            if p.poll() is None:
                p.kill()
                p.communicate()
    for p, (out, err) in zip(procs, outs):
        assert p.returncode == 0, f"worker failed:\n{out}\n{err}"
    assert "DIST_OK" in outs[0][0]
