"""Driver-contract checks: entry() compiles, dryrun_multichip executes
inside the driver's wall-clock budget."""

import time

import jax

import __graft_entry__ as graft


def test_entry_jits():
    fn, args = graft.entry()
    out = jax.jit(fn)(*args)
    assert out.shape == (2, 64, graft._SMOKE.vocab_size)


def test_dryrun_multichip_8_within_budget():
    # The driver runs dryrun_multichip(8) with a hard timeout on a slow
    # virtual-CPU box (~1 core); the budget assertion keeps the dryrun
    # honest. The bound is
    # machine-dependent by nature — override GOFR_DRYRUN_BUDGET_S on
    # slower CI boxes (the driver's real cap is 120 s on its own box).
    import os
    budget = float(os.environ.get("GOFR_DRYRUN_BUDGET_S", "90"))
    t0 = time.time()
    graft.dryrun_multichip(8)
    took = time.time() - t0
    assert took < budget, f"dryrun_multichip(8) took {took:.0f}s > {budget:.0f}s"


def test_dryrun_plan_covers_all_axes_at_8():
    # with ring attention handling sp (seq_parallel="auto"), the dryrun
    # demonstrates all four mesh axes once enough devices exist
    for n in (2, 4, 8, 16):
        plan = graft._plan_for(n)
        assert plan.n_devices == n
    assert graft._plan_for(8).sp == 2
    assert graft._plan_for(4).sp == 1  # tp/fsdp first: the shipping axes


def test_dryrun_multichip_2():
    graft.dryrun_multichip(2)


def test_dryrun_self_provisions_in_driver_environment():
    # Simulate the driver: fresh interpreter, no conftest, no XLA_FLAGS,
    # one default device — dryrun_multichip(8) must self-provision its
    # own 8-device virtual CPU mesh via subprocess re-exec and exit 0.
    import os
    import subprocess
    import sys

    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env = {k: v for k, v in os.environ.items()
           if k not in ("XLA_FLAGS", "JAX_PLATFORMS", "JAX_NUM_CPU_DEVICES",
                        "_GOFR_DRYRUN_CHILD")}
    # generous margin over the in-process budget test (which owns the
    # honest timing contract): the child pays interpreter boot + imports
    # + the self-provision re-exec, and a loaded box (parallel suite,
    # CPU contention) stretches all three — this variant verifies the
    # SELF-PROVISIONING, not the speed
    budget = float(os.environ.get("GOFR_DRYRUN_BUDGET_S", "90")) + 210
    r = subprocess.run(
        [sys.executable, "-c",
         "import __graft_entry__; __graft_entry__.dryrun_multichip(8)"],
        cwd=repo, env=env, capture_output=True, text=True, timeout=budget)
    assert r.returncode == 0, (r.stdout[-1000:], r.stderr[-2000:])
    assert "OK" in r.stdout
    # The fsdp×sp×tp train step must partition WITHOUT involuntary full
    # rematerialization (a feature-sharded embedding
    # table once made GSPMD replicate the [B, S, D] token-embedding gather
    # every step). The warning is emitted by spmd_partitioner.cc on the
    # child's stderr, which passes through here — grep it like the driver
    # artifact's tail would show it.
    assert "full rematerialization" not in r.stderr, r.stderr[-3000:]
