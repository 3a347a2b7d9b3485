"""Multi-host bootstrap: 2 real processes join the PJRT distributed
runtime over a 127.0.0.1 coordinator (the DCN story at test scale —
SURVEY §5 distributed-backend row; VERDICT r1 missing #1) and run one
sharded train step plus a sharded generation on the GLOBAL mesh.

The workers are separate interpreters (tests/_distributed_worker.py), so
this file only orchestrates: conftest's in-process jax config does not
leak into them.
"""

import os
import socket
import subprocess
import sys

from gofr_tpu.config import MapConfig
from gofr_tpu.parallel import distributed

_WORKER = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                       "_distributed_worker.py")


def _free_port() -> int:
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


def test_maybe_initialize_noop_without_coordinator():
    # No TPU_COORDINATOR => single-process mode, and no runtime join
    # happened inside THIS process (the test suite must stay single-proc).
    assert distributed.maybe_initialize(MapConfig({})) is False
    assert distributed.is_initialized() is False


def test_two_process_sharded_train_and_generate():
    port = _free_port()
    env = {**os.environ, "JAX_PLATFORMS": ""}
    procs = [
        subprocess.Popen(
            [sys.executable, _WORKER, str(rank), "2", str(port)],
            stdout=subprocess.PIPE, stderr=subprocess.PIPE, text=True,
            env=env)
        for rank in (0, 1)
    ]
    outs = []
    try:
        for p in procs:
            out, err = p.communicate(timeout=240)
            outs.append((p.returncode, out, err))
    finally:
        for p in procs:
            if p.poll() is None:
                p.kill()

    for rc, out, err in outs:
        assert rc == 0, f"worker failed rc={rc}\nstdout:\n{out}\nstderr:\n{err}"
        assert "WORKER OK" in out

    def field(out, prefix):
        return [ln for ln in out.splitlines() if ln.startswith(prefix)][0]

    # both processes saw the GLOBAL device view
    for _, out, _ in outs:
        assert field(out, "JOINED") == "JOINED devices=8 local=4"
    # SPMD agreement: identical loss and identical greedy tokens
    assert field(outs[0][1], "TRAIN") == field(outs[1][1], "TRAIN")
    assert field(outs[0][1], "GEN") == field(outs[1][1], "GEN")
    # the pipeline conveyor ran ACROSS the process boundary (stage 0 on
    # proc 0, stage 1 on proc 1; ppermutes over DCN) with agreeing loss
    assert field(outs[0][1], "PPTRAIN") == field(outs[1][1], "PPTRAIN")
