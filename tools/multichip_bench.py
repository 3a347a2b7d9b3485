"""Multi-chip tensor-parallel serving bench: tp scaling, token
exactness, and warm device-loss recovery.

The ROADMAP's multi-chip item gates on exactly this run: tensor-
parallel decode as a REAL serving configuration — sharded KV leased
per device from the HBM arbiter, mesh-aware paged attention, per-shard
T1 offload, and a mid-serving device loss that re-places the mesh and
resumes warm instead of dying (docs/advanced-guide/
multichip-serving.md).

Arms (each a fresh engine built from its TPU_* config rows, same keys
production serving reads):

  tp1          single-device contiguous engine — the reference stream
               every other arm must match token-for-token, and the
               scaling baseline.
  tp2 / tp4    mesh engines (``TPU_SHARDING=tp=N,dp=rest``): aggregate
               decode tok/s with every slot busy, token-exact vs tp1.
  tp2_paged    mesh-aware PAGED engine (block pool sharded over tp,
               dense-gather attention): token-exact vs tp1 — the
               paged+mesh composition this PR lifted the refusal on.
  device_loss  tp=2 engine with a prefix pool + T1 host tier: prime
               T0, spill to T1, then a seeded chaos ``GENERATOR_STEP``
               DeviceLost mid-serving. Gates: the in-flight stream
               fails TYPED (no process death), the mesh re-places
               (stats.mesh.replacements >= 1), the repeat prompt
               serves WARM from T1, post-recovery tokens are exact,
               and the arbiter's in-use figure re-settles to the
               pre-loss byte count (leases replaced, never
               double-counted).

STRUCTURAL gates are strict everywhere (exactness, recovery, per-shard
lease visibility, 0 deaths). The SCALING gate (aggregate tok/s up with
tp) is judged only on real multi-device hardware: on virtual CPU
devices (this container: 8-way ``jax_num_cpu_devices``) every "chip"
time-slices one host, so tp adds partitioning overhead with zero added
FLOPs — the ratio is recorded advisory, the same caveat class
slo_bench documents.

Conventions (tools/README.md): the LAST stdout line is the JSON
artifact; ``--smoke`` is the CI gate (smaller shapes, same structural
invariants); full runs commit ``MULTICHIP_SERVE_BENCH.json``. Exit is
non-zero only when a strict gate fails.
"""

from __future__ import annotations

import json
import os
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent.parent))


def log(*a) -> None:
    print(*a, file=sys.stderr, flush=True)


def _init_devices() -> int:
    """CPU: fan the host platform out to 8 virtual devices BEFORE
    first backend use (the tests/conftest.py recipe); TPU: use the
    slice as-is."""
    import jax

    if not os.environ.get("GOFR_BENCH_TPU"):
        jax.config.update("jax_platforms", "cpu")
        jax.config.update("jax_num_cpu_devices", 8)
        jax.config.update("jax_default_matmul_precision", "float32")
    return jax.device_count()


def _build(cfg, params, rows: dict):
    """Engine from TPU_* rows — bench.engine_from_rows, so an arm
    definition IS a deployable serving config."""
    import bench

    return bench.engine_from_rows(cfg, params, rows)


def _drive(engine, cfg, *, streams: int, new_tokens: int,
           prompt_len: int = 16) -> dict:
    """Two phases. THROUGHPUT: fill every slot, wall-clock all tokens
    out (aggregate decode tok/s through the full serving stack).
    EXACTNESS: fixed greedy prompts served ONE AT A TIME — the regime
    tests/test_sharded_serving.py proves bit-stable across tp
    factorizations (a fully-batched probe would gate on fp reduction
    order across different activation shardings, which no tp change
    preserves — a numerics artifact, not a sharding bug)."""
    import numpy as np

    rng = np.random.default_rng(11)
    prompts = [rng.integers(1, cfg.vocab_size, prompt_len).tolist()
               for _ in range(streams)]
    t0 = time.perf_counter()
    outs = [engine.generate(p, max_new_tokens=new_tokens) for p in prompts]
    total = sum(len(s.tokens()) for s in outs)
    dt = time.perf_counter() - t0
    probes = [[5, 17, 42, 7, 9, 3, 11, 2],
              list(range(2, 18)),
              [31, 4, 15, 9, 2, 6]]
    probe_toks = [engine.generate(p, max_new_tokens=new_tokens).tokens()
                  for p in probes]
    return {"tok_s": round(total / dt, 1), "tokens": total,
            "seconds": round(dt, 2), "streams": probe_toks}


def run(smoke: bool) -> dict:
    n_dev = _init_devices()
    import jax

    from gofr_tpu import chaos
    from gofr_tpu.models.common import LLAMA_CONFIGS
    from gofr_tpu.tpu import GenerationError, hbm
    from gofr_tpu.tpu.kvcache import KVCacheOptions
    import bench

    platform = jax.devices()[0].platform
    # full-precision weights + model-dtype cache: the exactness gate
    # judges the SHARDING machinery (specs, collectives, masked row
    # copies), and fp weights make greedy argmax invariant across tp
    # factorizations (the proven test_sharded_serving regime). int8
    # weight quantization re-orders the dequant psum reductions per tp
    # and can flip a borderline argmax — a numerics artifact the int8
    # config documents, not a sharding bug. The model must fit ONE
    # chip (the tp1 reference arm) and every tp arm must DIVIDE its
    # n_kv_heads — splitting a KV head on a multi-axis mesh is the
    # documented wrong-logits hazard this bench's bring-up found
    # (multichip-serving.md "known limits"), so the CPU config widens
    # tiny to 4 KV heads (MHA) to keep tp=4 in the clean regime.
    cfg = (LLAMA_CONFIGS["tiny"].with_(n_kv_heads=4)
           if platform == "cpu" else LLAMA_CONFIGS["llama-1b"])
    slots = 4 if smoke else 8
    new_tokens = 12 if smoke else 48
    from gofr_tpu.models import llama

    params = llama.init(cfg, jax.random.PRNGKey(0))
    base = {"TPU_SLOTS": str(slots), "TPU_MAX_SEQ": "128",
            "TPU_SEQ_BUCKETS": "32", "TPU_KV_DTYPE": "model",
            "TPU_DECODE_BLOCK": "4"}

    def mesh_spec(tp: int) -> str:
        dp = n_dev // tp
        return f"tp={tp}" + (f",dp={dp}" if dp > 1 else "")

    arm_rows = [("tp1", dict(base))]
    for tp in (2, 4):
        if n_dev >= tp and n_dev % tp == 0:
            arm_rows.append((f"tp{tp}",
                             {**base, "TPU_SHARDING": mesh_spec(tp)}))
    if n_dev >= 2 and n_dev % 2 == 0:
        arm_rows.append(("tp2_paged",
                         {**base, "TPU_SHARDING": mesh_spec(2),
                          "TPU_PAGED_BLOCKS": str(slots * 5 + 1),
                          "TPU_PAGED_BLOCK": "32"}))

    arms: dict[str, dict] = {}
    ref_streams = None
    sharded_lease_devices: set[str] = set()
    for name, rows in arm_rows:
        extra = {k: v for k, v in rows.items() if k not in base}
        log(f"arm {name}: rows={extra or 'base'}")
        engine = None
        try:
            engine = _build(cfg, params, rows)
            res = _drive(engine, cfg, streams=slots, new_tokens=new_tokens)
            streams = res.pop("streams")
            if name == "tp1":
                ref_streams = streams
            exact = streams == ref_streams
            arm = {"status": "ok", "token_exact_vs_tp1": exact, **res}
            if engine.mesh is not None:
                arm["mesh"] = engine.stats()["mesh"]
                for row in hbm.arbiter_stats()["leases"]:
                    if "device" in row:
                        sharded_lease_devices.add(row["device"])
            arms[name] = arm
            log(f"  {name}: {res['tok_s']} tok/s aggregate, "
                f"exact={exact}")
        except Exception as e:  # noqa: BLE001 — each arm reports its fate
            arms[name] = {"status": "error",
                          "error": f"{type(e).__name__}: {str(e)[:200]}"}
            log(f"  {name} FAILED: {arms[name]['error']}")
        finally:
            if engine is not None:
                engine.close()

    # -- the device-loss arm --------------------------------------------------
    loss = {"status": "error"}
    engine = None
    # built directly rather than via _build/engine_from_rows: the T1
    # host tier is a constructor option outside the perf-arm row set
    try:
        import jax.numpy as jnp

        from gofr_tpu.parallel import make_mesh, shard_params
        from gofr_tpu.tpu import GenerationEngine

        mesh = None
        mparams = params
        if n_dev >= 2 and n_dev % 2 == 0:
            mesh = make_mesh(tp=2, dp=n_dev // 2)
            mparams = shard_params(params, mesh)
        engine = GenerationEngine(
            cfg, mparams, mesh=mesh, slots=slots, max_seq=128,
            prompt_buckets=(32,), kv_dtype=jnp.int8, decode_block=4,
            prefix_cache_slots=1, prefix_store_min=16,
            kvcache=KVCacheOptions(host_mb=64))
        pA = list(range(1, 33))
        ref = engine.generate(pA + [1, 2], max_new_tokens=8).tokens()
        engine.generate(list(range(40, 72)) + [3, 4],
                        max_new_tokens=8).tokens()  # spill A's row to T1
        in_use_before = hbm.arbiter_stats()["in_use_bytes"]
        sched = chaos.ChaosSchedule(seed=7).on(
            chaos.GENERATOR_STEP, error=chaos.DeviceLost, every=1, limit=1)
        typed_failure = False
        with chaos.scope(sched):
            try:
                engine.generate([9, 8, 7, 6], max_new_tokens=8).tokens()
            except GenerationError:
                typed_failure = True  # the SHED contract: typed, not a death
        s2 = engine.generate(pA + [1, 2], max_new_tokens=8)
        got = s2.tokens()
        st = engine.stats()
        in_use_after = hbm.arbiter_stats()["in_use_bytes"]
        loss = {
            "status": "ok",
            "typed_failure": typed_failure,
            "replacements": (st.get("mesh", {}).get("replacements", 0)
                             if mesh is not None else engine._recoveries),
            "post_recovery_exact": got == ref,
            "warm_tier": s2.cache_tier,
            "engine_down": engine.down is not None,
            "in_use_before": in_use_before,
            "in_use_after": in_use_after,
            "leases_resettled": in_use_before == in_use_after,
        }
        log(f"  device_loss: typed={typed_failure} "
            f"replacements={loss['replacements']} warm={s2.cache_tier} "
            f"exact={loss['post_recovery_exact']} "
            f"resettled={loss['leases_resettled']}")
    except Exception as e:  # noqa: BLE001
        loss = {"status": "error",
                "error": f"{type(e).__name__}: {str(e)[:200]}"}
        log(f"  device_loss FAILED: {loss['error']}")
    finally:
        if engine is not None:
            engine.close()

    # -- gates ----------------------------------------------------------------
    mesh_arms = [n for n in arms if n != "tp1"]
    scaling = {}
    if "tp1" in arms and arms["tp1"].get("status") == "ok":
        for n in ("tp2", "tp4"):
            if arms.get(n, {}).get("status") == "ok":
                scaling[f"{n}_vs_tp1"] = round(
                    arms[n]["tok_s"] / arms["tp1"]["tok_s"], 3)
    scaling_gated = platform != "cpu" and n_dev > 1
    checks = {
        "all_arms_ok": all(a.get("status") == "ok" for a in arms.values()),
        "mesh_arms_present": len(mesh_arms) >= 2,
        "all_token_exact": all(a.get("token_exact_vs_tp1")
                               for a in arms.values()
                               if a.get("status") == "ok"),
        "per_shard_leases_visible": len(sharded_lease_devices) >= 2,
        "loss_arm_recovered_warm": (
            loss.get("status") == "ok" and loss.get("typed_failure")
            and loss.get("post_recovery_exact")
            and loss.get("warm_tier") == "t1"
            and not loss.get("engine_down")
            and loss.get("replacements", 0) >= 1
            and loss.get("leases_resettled")),
        "zero_deaths": True,  # we are here emitting the artifact
    }
    if scaling_gated:
        # real hardware: tp must buy aggregate throughput
        checks["scaling_up"] = all(v > 1.1 for v in scaling.values()) \
            and bool(scaling)
    ok = all(checks.values())
    return {
        "bench": "multichip_serve",
        "smoke": smoke,
        "ok": ok,
        "platform": platform,
        "devices": n_dev,
        "arms": arms,
        "device_loss": loss,
        "scaling": scaling,
        "scaling_gate": ("strict" if scaling_gated
                         else "advisory (virtual devices time-slice one "
                              "host: tp adds partitioning overhead with "
                              "zero added FLOPs)"),
        "checks": checks,
        "sharded_lease_devices": sorted(sharded_lease_devices),
    }


def main() -> None:
    smoke = "--smoke" in sys.argv[1:]
    out = run(smoke)
    print(json.dumps(out))
    sys.exit(0 if out["ok"] else 1)


if __name__ == "__main__":
    main()
