"""Subprocess worker for tests/test_distributed.py: one PROCESS of an
N-process run over the PJRT distributed runtime (CPU backend, 4 local
devices each). Joins via the same TPU_COORDINATOR/TPU_PROCESS_ID config
keys production uses, then runs one sharded train step and a short
sharded greedy generation over the GLOBAL 8-device mesh, printing
machine-checkable lines the test asserts on.

Run: python _distributed_worker.py <process_id> <num_processes> <port>
"""

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

pid, nprocs, port = int(sys.argv[1]), int(sys.argv[2]), sys.argv[3]

# 4 local devices per process, set before backend init; a force-count
# inherited through XLA_FLAGS would fight it.
os.environ["XLA_FLAGS"] = " ".join(
    f for f in os.environ.get("XLA_FLAGS", "").split()
    if not f.startswith("--xla_force_host_platform_device_count"))

import jax  # noqa: E402

jax.config.update("jax_platforms", "cpu")
jax.config.update("jax_num_cpu_devices", 4)

import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from gofr_tpu import parallel  # noqa: E402
from gofr_tpu.config import MapConfig  # noqa: E402
from gofr_tpu.models import llama  # noqa: E402
from gofr_tpu.models.common import ModelConfig  # noqa: E402

cfg = MapConfig({
    "TPU_COORDINATOR": f"127.0.0.1:{port}",
    "TPU_PROCESS_ID": str(pid),
    "TPU_NUM_PROCESSES": str(nprocs),
})
assert parallel.maybe_initialize(cfg), "coordinator config must initialize"
assert parallel.is_initialized()
assert jax.process_index() == pid
print(f"JOINED devices={jax.device_count()} local={jax.local_device_count()}",
      flush=True)

MCFG = ModelConfig(name="dist-smoke", vocab_size=256, dim=64, n_layers=2,
                   n_heads=4, n_kv_heads=2, ffn_dim=128, max_seq=64,
                   dtype="float32")
mesh = parallel.make_mesh(parallel.MeshPlan(dp=2, fsdp=1, sp=1, tp=4))

# -- one sharded train step over DCN+ICI (dp crosses the process boundary)
opt = parallel.default_optimizer(lr=1e-3, warmup=1, total_steps=10)
state = parallel.init_train_state(MCFG, jax.random.PRNGKey(0), mesh, opt)
step = parallel.make_train_step(MCFG, opt, mesh, remat=False)
tokens = jax.random.randint(jax.random.PRNGKey(1), (8, 16), 0,
                            MCFG.vocab_size)
lengths = jnp.full((8,), 16, jnp.int32)
state, metrics = step(state, tokens, lengths)
loss = float(metrics["loss"])
assert np.isfinite(loss) and int(metrics["step"]) == 1
print(f"TRAIN loss={loss:.6f}", flush=True)

# -- sharded generation: prefill + greedy decode against the sharded cache
from jax.sharding import NamedSharding, PartitionSpec as P  # noqa: E402

p_sh = parallel.shardings_for(jax.eval_shape(
    lambda k: llama.init(MCFG, k), jax.random.PRNGKey(2)), mesh)
params = jax.jit(lambda k: llama.init(MCFG, k), out_shardings=p_sh)(
    jax.random.PRNGKey(2))

cache_shape = jax.eval_shape(lambda: llama.init_cache(MCFG, 2, 32))
cache_sh = parallel.kv_cache_specs(mesh, cache_shape)
rep = NamedSharding(mesh, P())
cache = jax.jit(lambda: llama.init_cache(MCFG, 2, 32),
                out_shardings=cache_sh)()

prompt = jnp.asarray([[3, 1, 4, 1, 5, 9, 2, 6]] * 2, jnp.int32)


@jax.jit
def prefill(params, tokens, cache):
    # flash stays off: Pallas calls do not partition under GSPMD
    logits, cache = llama.prefill(params, MCFG, tokens, cache, flash=False)
    return jnp.argmax(logits[:, -1], axis=-1).astype(jnp.int32), cache


@jax.jit
def decode(params, tokens, cache):
    logits, cache = llama.decode_step(params, MCFG, tokens, cache)
    return jnp.argmax(logits, axis=-1).astype(jnp.int32), cache


prefill = jax.jit(prefill, out_shardings=(rep, cache_sh))
decode = jax.jit(decode, out_shardings=(rep, cache_sh))

tok, cache = prefill(params, prompt, cache)
out = [int(tok[0])]
for _ in range(5):
    tok, cache = decode(params, tok, cache)
    out.append(int(tok[0]))
print(f"GEN tokens={out}", flush=True)

# -- pipeline conveyor ACROSS the process boundary: pp=2 puts stage 0 on
# process 0 and stage 1 on process 1, so every conveyor ppermute (and
# the loss psum) rides DCN — the multi-host story for the pp axis.
pp_mesh = parallel.make_mesh(parallel.MeshPlan(pp=2, dp=1, tp=4))
pp_state = parallel.init_train_state(MCFG, jax.random.PRNGKey(3), pp_mesh,
                                     opt)
pp_step = parallel.make_train_step(MCFG, opt, pp_mesh, remat=False,
                                   n_microbatches=2)
pp_state, pp_metrics = pp_step(pp_state, tokens, lengths)
pp_loss = float(pp_metrics["loss"])
assert np.isfinite(pp_loss)
print(f"PPTRAIN loss={pp_loss:.6f}", flush=True)
print("WORKER OK", flush=True)
