"""Fine-tuning as a framework CLI app (BASELINE training counterpart:
the serving framework's training half driven through the same App
surface as everything else — reference CLI precedent:
examples/sample-cmd, pkg/gofr/cmd.go:27-63).

    python main.py train -model=llama-1b -steps=100 -data=tokens.npz \
        -sharding=dp=2,fsdp=2,tp=2 -out=./ckpt
    python main.py resume -model=llama-1b -out=./ckpt -steps=50

Data: an .npz with ``tokens`` [N, S] int32 (and optional ``lengths``
[N]); omitted = synthetic random tokens (bringup mode, like
TPU_WEIGHTS-less serving). Meshes with sp>1 train through ring
attention automatically (seq_parallel="auto"); ``-sharding=pp=2,dp=4``
runs the GPipe pipeline conveyor, ``ep=...`` shards MoE experts —
every axis of gofr_tpu/parallel composes through this one flag.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

import os as _os
import sys as _sys

# appended (not prepended): an installed gofr_tpu always wins
_sys.path.append(_os.path.join(_os.path.dirname(_os.path.abspath(__file__)),
                               "..", ".."))

from gofr_tpu import new_cmd, parallel
from gofr_tpu.models import LLAMA_CONFIGS

app = new_cmd()


def _mesh(spec: str):
    if not spec:
        return parallel.single_device_mesh()
    axes = {}
    for part in spec.split(","):
        k, _, v = part.partition("=")
        axes[k.strip()] = int(v)
    return parallel.make_mesh(**axes)


def _data(ctx, cfg, batch: int, seq: int):
    path = ctx.param("data", "")
    if path:
        with np.load(path) as f:
            tokens = np.asarray(f["tokens"], np.int32)
            lengths = (np.asarray(f["lengths"], np.int32)
                       if "lengths" in f.files
                       else np.full((len(tokens),), tokens.shape[1],
                                    np.int32))
        return tokens, lengths
    rng = np.random.default_rng(0)  # bringup: synthetic tokens
    tokens = rng.integers(1, cfg.vocab_size,
                          (batch, seq)).astype(np.int32)
    return tokens, np.full((batch,), seq, np.int32)


def _run(ctx, resume: bool) -> str:
    # -platform=cpu -devices=8: force a virtual host mesh for local dev
    # BEFORE first backend use.
    platform = ctx.param("platform", "")
    if platform:
        jax.config.update("jax_platforms", platform)
        n = int(ctx.param("devices", "0"))
        if n and platform == "cpu":
            jax.config.update("jax_num_cpu_devices", n)
    cfg = LLAMA_CONFIGS[ctx.param("model", "tiny")]
    steps = int(ctx.param("steps", "10"))
    batch = int(ctx.param("batch", "8"))
    seq = min(int(ctx.param("seq", "128")), cfg.max_seq)
    out = ctx.param("out", "./ckpt")
    lr = float(ctx.param("lr", "3e-4"))
    mesh = _mesh(ctx.param("sharding", ""))

    def optimizer(total: int):
        return parallel.default_optimizer(lr=lr,
                                          warmup=max(1, total // 10),
                                          total_steps=max(total, 2))

    if resume:
        # restore FIRST (the optimizer only shapes the state skeleton —
        # schedule values don't affect structure), then rebuild the LR
        # schedule to cover restored_step + this run's steps: a schedule
        # sized to this run alone would put the restored adam count past
        # its decay horizon and train every step at lr = 0.
        state = parallel.restore_train_state(out, cfg, mesh, optimizer(2))
        start = int(state.step)
        opt = optimizer(start + steps)
        ctx.logger.info({"event": "resumed", "step": start})
    else:
        opt = optimizer(steps)
        state = parallel.init_train_state(cfg, jax.random.PRNGKey(0),
                                          mesh, opt)
    step_fn = parallel.make_train_step(cfg, opt, mesh)

    tokens, lengths = _data(ctx, cfg, batch, seq)
    if tokens.shape[1] > seq:  # honor -seq for file data too
        tokens, lengths = tokens[:, :seq], np.minimum(lengths, seq)
    n = len(tokens)
    metrics = {"loss": float("nan")}
    for i in range(steps):
        lo = (i * batch) % max(1, n - batch + 1)
        state, metrics = step_fn(state,
                                 jnp.asarray(tokens[lo:lo + batch]),
                                 jnp.asarray(lengths[lo:lo + batch]))
        if i % max(1, steps // 10) == 0:
            # float() forces a device sync — only on logging steps, so
            # the loop otherwise keeps the device queue full
            ctx.logger.info({"event": "train", "step": int(state.step),
                             "loss": round(float(metrics["loss"]), 4)})
    loss = float(metrics["loss"])
    parallel.save_train_state(out, state)
    return (f"trained to step {int(state.step)} loss {loss:.4f} "
            f"mesh[{'x'.join(f'{k}={v}' for k, v in mesh.shape.items())}] "
            f"-> {out}")


@app.sub_command("train", description="fine-tune a model, save the state")
def train(ctx):
    return _run(ctx, resume=False)


@app.sub_command("resume", description="continue training from -out")
def resume(ctx):
    return _run(ctx, resume=True)


if __name__ == "__main__":
    raise SystemExit(app.run_command())
