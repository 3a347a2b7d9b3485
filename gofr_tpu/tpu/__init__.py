"""TPU datasource: engine, continuous batching, checkpoint loading.

Wired into the container the way Redis/SQL are in the reference
(pkg/gofr/container/container.go:55-126 builds each datasource from
config): ``new_engine_from_config`` reads ``TPU_*`` config keys, builds the
engine, registers the model family's programs, and hands back a
health-checkable datasource reachable as ``ctx.tpu``. Unlike Redis/SQL, a
configured model that cannot be built is a start-up failure, not a
degraded start.

Config keys (reference config style, pkg/gofr/config/config.go:3):
  TPU_MODEL           model name: llama family (llama3-8b, llama-1b, tiny),
                      the latent-attention family (tiny-mla-moe; any
                      configuration with kv_lora_rank > 0: it refuses
                      TPU_SHARDING, TPU_PAGED_BLOCKS, the host and Redis
                      cache tiers, TPU_SPEC_DECODE, TPU_LORA_ADAPTERS,
                      P/D roles and an int8 cache at start-up),
                      the hybrid family (tiny-kda-moe; any configuration
                      whose layer_pattern names a "linear" layer: gated
                      delta-rule layers beside full ones, a recurrent
                      state beside KV rows; it refuses the same options
                      but the int8 cache, and TPU_MAX_SEQ must be whole
                      prefill chunks),
                      the window family (tiny-swa-moe; any configuration
                      whose layer_pattern names a "window" layer:
                      sliding-window layers on a ring of rows beside full
                      layers' rows in one cache; it refuses what the
                      latent family refuses, and TPU_MAX_SEQ must be
                      whole prefill chunks),
                      the conv family (tiny-conv-moe; any configuration
                      whose layer_pattern names a "conv" layer: gated
                      short-convolution layers that keep the last
                      conv_kernel - 1 inputs a slot and no rows, beside
                      full layers whose 64-wide KV heads share a cache
                      row two by two; it refuses what the latent family
                      refuses, and TPU_MAX_SEQ must be whole prefill
                      chunks),
                      the state-space family (tiny-ssm-moe; any
                      configuration whose layer_pattern names a "mamba"
                      layer, and then names every layer: Mamba-2 layers
                      that keep a float32 state and a convolution tail a
                      slot, attention layers' rows beside them, expert
                      layers of two-matrix relu2 experts in a latent; it
                      refuses what the hybrid family refuses, and
                      TPU_MAX_SEQ must be whole prefill chunks),
                      bert family (bert/bert-base, bert-tiny), or
                      vit family (vit/vit-l-14, vit-tiny)
  TPU_WEIGHTS         checkpoint path (.npz or orbax dir); absent = random
                      init from a seed, built leaf by leaf at the serving
                      dtype and sharding (smoke/serving-bringup mode)
  TPU_QUANT           "int8" to quantize projection weights on load
  TPU_KV_DTYPE        KV-cache dtype for generation: "int8" (default —
                      halves decode's cache HBM stream; quantize-on-write,
                      dequant fused into attention) or "bf16"/"model" for
                      the exact dense cache
  TPU_SLOTS           decode batch slots for generation (default 48 —
                      decode streams the full weight set per step, so
                      throughput scales with tokens per weight pass until
                      HBM runs out; shrink for small-HBM chips)
  TPU_MAX_SEQ         serving KV capacity (default min(model max, 2048))
  TPU_DECODE_BLOCK    decode steps fused per device dispatch (default 4 —
                      the stream sees K tokens per roundtrip; raise on
                      high-latency links, lower toward 1 for tightest
                      per-token latency)
  TPU_ADMIT_WINDOW_MS in-flight admission poll cadence in ms (default
                      2 — decode blocks dispatch async and new requests
                      are admitted while one runs, their prefill
                      queueing behind it on the device stream)
  TPU_STALL_MS        a phase of the generation loop other than its idle
                      park that lasts this long (default 1000) leaves a
                      stall record: the queue's readiness seen from
                      outside the blocked thread, per-thread CPU,
                      how late the watchdog's own ticks woke, stacks, a
                      cause (observe/stall.py, /debug/stalls; no
                      watchdog with TPU_TIMELINE=0)
  TPU_PREFILL_CHUNK   chunked-prefill interleave budget in tokens
                      (docs/advanced-guide/serving-scheduler.md):
                      prompts longer than the budget admit as bounded
                      chunk dispatches with one admission pass + one
                      decode block between chunks, so a long prefill
                      neither stalls active decode streams nor
                      head-of-line-blocks a newly arrived request.
                      Unset = the largest prompt bucket; other values
                      snap UP to a prompt bucket; 0 disables the
                      interleave (chunks dispatch back-to-back — the
                      bench contrast arm)
  TPU_SLO_THROUGHPUT_FACTOR  scale on every AdmissionGate bound for
                      throughput-class requests (default 0.5): batch
                      traffic sheds and brownouts FIRST as load rises;
                      1.0 restores class-blind gating
  TPU_SLO_THROUGHPUT_SHARE   generation pending-line share guaranteed
                      to throughput-class under latency saturation
                      (default 0.25 — one pick in four); 0 drains
                      throughput only on latency idle
  TPU_SLO_LATENCY_SLOTS      decode slots throughput-class admissions
                      may never occupy (default 1, clamped below the
                      slot count): a latency request under batch-driven
                      saturation finds a slot at its uncontended wait
                      instead of queueing behind admitted batch
                      streams. Costs idle capacity only while tagged
                      throughput traffic saturates; 0 disables
  TPU_SLO_BATCH_SHARE enable SLO-class scheduling in the predict
                      batchers with this throughput reserve share
                      (default 0 = off: class lines run the Python
                      dispatcher, giving up the native GIL-released
                      wait — a measured tradeoff, not a default)
  TPU_SLO_BATCH_DELAY throughput-class flush delay for the predict
                      batchers in seconds (default 4x
                      TPU_MAX_BATCH_DELAY — batch items wait longer
                      for fuller batches)
  TPU_PREFIX_CACHE    prefix-KV pool rows (default 0 = off): stored
                      prompt prefixes restore as one HBM row copy
                      instead of prefill compute. The pool is the T0
                      tier of the hierarchical kv cache (tpu/kvcache/,
                      docs/advanced-guide/kv-cache.md); the radix
                      index, host-DRAM offload and Redis-shared tiers
                      are tuned by the TPU_KVCACHE_* keys below
  TPU_PREFIX_MIN      min prompt length stored in the pool (default:
                      the largest prompt bucket)
  TPU_KVCACHE_BLOCK   radix/content-hash block size in tokens
                      (default 16); also the Redis tier's sharing
                      granularity
  TPU_KVCACHE_HOST_MB host-DRAM offload tier budget in MiB (default 0
                      = off): LRU-evicted pool rows spill to host
                      numpy and restore via device_put on hit —
                      cache capacity beyond HBM, survives device loss.
                      On mesh engines rows spill/restore PER SHARD
                      (each tp shard's head range reads off its own
                      device; promotion lands the assembled row with
                      one sharded write)
  TPU_KVCACHE_REDIS   "true" shares quantized int8 KV blocks through
                      the framework Redis client (REDIS_HOST/PORT) so
                      replicas warm each other (default off)
  TPU_KVCACHE_REDIS_TTL_S      shared-block TTL seconds (default 300)
  TPU_KVCACHE_REDIS_TIMEOUT_S  socket timeout for the tier's dedicated
                      client (default 0.25 — fail open fast; the
                      serving loop must never stall on Redis)
  TPU_KVCACHE_EPOCH_REFRESH_S  staleness bound on the adapter-epoch
                      invalidation key (default 5)
  TPU_SPEC_DECODE     prompt-lookup speculative decoding: K draft
                      tokens per verify pass (default 0 = off). One
                      weight stream emits 1..K+1 tokens per greedy slot
                      when its history's trailing n-gram repeats
  TPU_PAGED_BLOCKS    paged KV cache: pool blocks incl. the reserved
                      trash block (default 0 = contiguous rows). Slots
                      share fixed-size blocks via a block table, so HBM
                      sizes to expected LIVE tokens and decode batch
                      scales past what [slots, max_seq] rows fit
                      (models/paged_llama.py; long prompts chunk via a
                      dense scratch row; composes with TPU_SPEC_DECODE,
                      and with TPU_PREFIX_CACHE the prefix cache
                      becomes zero-copy block sharing). Composes with
                      TPU_SHARDING: the pool shards KV-heads over tp
                      and attention runs the dense-gather reference
                      (the Pallas kernel is single-device)
  TPU_PAGED_BLOCK     block size in tokens (default 128)
  TPU_LORA_ADAPTERS   multi-LoRA serving: adapter slots (default 0 =
                      off; slot 0 is the base no-op). Per-request
                      selection via generate(adapter=i); install
                      weights with engine.generator.load_adapter
  TPU_LORA_RANK       LoRA bottleneck rank (default 16)
  TPU_HBM_BUDGET_MB   HBM arbiter budget in MiB (docs/advanced-guide/
                      memory.md): one budget every subsystem leases
                      from, with demand-driven reclaim (T0 shrinks
                      toward the host tier, cold paged blocks release)
                      and an OOM-shed path (429/RESOURCE_EXHAUSTED +
                      Retry-After) instead of process death. Unset/0 =
                      resolve from the device's reported limit minus
                      the headroom fraction on accelerator backends;
                      on CPU the budget stays off unless set
  TPU_HBM_HEADROOM    fraction of the device limit the resolved budget
                      leaves free for XLA workspace the accounting
                      registry can't see (default 0.1)
  TPU_HBM_DEVICE_BUDGET_MB  PER-DEVICE arbiter budget in MiB for mesh
                      serving (docs/advanced-guide/
                      multichip-serving.md): sharded buffers settle
                      one lease per device, each checked against this
                      bound, and a hot shard's deficit reclaims only
                      that device's leases. Unset = resolved per
                      device on accelerator backends; inert for
                      single-device engines
  TPU_MAX_QUEUE_DEPTH admission control (resilience.AdmissionGate):
                      shed with 429/RESOURCE_EXHAUSTED once this many
                      requests wait in a queue (default 0 = off)
  TPU_MAX_QUEUE_DELAY shed once the observed queue-wait EWMA exceeds
                      this many seconds (default 0 = off)
  TPU_BROWNOUT_DELAY  brownout band: cap max_new_tokens while the
                      queue-wait EWMA exceeds this (default 0 = off)
  TPU_BROWNOUT_MAX_NEW token cap applied in brownout (default 32)
  TPU_BATCH_BUCKETS   csv of predict batch buckets (default 1,2,4,8)
  TPU_SEQ_BUCKETS     csv of token-length buckets  (default 32..512)
                      (a warmed engine bounds a bucket's padding by
                      the next bucket down plus the rest's, where its
                      measured table says two dispatches are cheaper:
                      docs/advanced-guide/serving-scheduler.md)
  TPU_MAX_BATCH_DELAY coalescing window in seconds (default 0.004)
  TPU_SHARDING        "tp=8" / "tp=4,dp=2" mesh axes for sharded serving
                      (axes from gofr_tpu.parallel; weights get
                      NamedShardings, XLA inserts the ICI collectives)
  TPU_SERVING_ROLE    disaggregated prefill/decode serving
                      (docs/advanced-guide/disaggregated-serving.md):
                      "fused" (default — one process serves both
                      phases), "prefill" (this worker computes prompt
                      KV and ships checksummed int8 block frames to
                      the decode pool, relaying its token stream), or
                      "decode" (this worker listens for shipped KV,
                      owns the slot lattice and the token stream).
                      Each pool draws its own TPU_HBM_BUDGET_MB with
                      its own reclaim policy. "gateway" is the APP
                      mode that fronts N replicas with prefix-affinity
                      routing + failover (gofr_tpu/gateway,
                      docs/advanced-guide/gateway.md, TPU_GATEWAY_*
                      rows in config-reference) — it holds no model,
                      so setting it alongside TPU_MODEL fails startup
  TPU_PD_LISTEN       decode role: host:port the KV-ingest listener
                      binds (default 127.0.0.1:9400)
  TPU_PD_PEER         prefill role: the decode worker's TPU_PD_LISTEN
                      address (required)
  TPU_PD_BLOCK        KV-ship frame granularity in tokens (default 16
                      — one frame per radix-sized block, streamed as
                      prefill chunks complete)
  TPU_PD_WINDOW_MB    KV-ship backpressure window in MiB (default 8):
                      unsent bytes past this block the shipper until
                      the peer drains (typed 502 when a wedged peer
                      stalls past the request deadline)
  TPU_WARMUP          "true" to precompile all buckets at startup
  TPU_TENANTS         multi-tenant serving plane (gofr_tpu/tenancy,
                      docs/advanced-guide/multi-tenancy.md): path to a
                      hot-reloadable JSON tenant registry mapping
                      tenant id -> LoRA adapter, SLO-class default,
                      fair-share queue weight, rps/concurrency quota
                      and cache-budget share. Unset AND no
                      TPU_TENANTS_INLINE = tenancy off (anonymous
                      single-tenant serving, zero overhead)
  TPU_TENANTS_INLINE  the same registry as a literal JSON string (for
                      tests/static fleets; TPU_TENANTS wins when both
                      are set)
  TPU_TENANTS_RELOAD_S  registry-file mtime poll throttle in seconds
                      (default 0.5)
  TPU_TENANT_HEADER   HTTP header carrying the tenant id (default
                      X-Tenant-Id; gRPC always reads x-tenant-id
                      metadata)
  TPU_TENANT_TOPIC    pub/sub topic the async inference lane consumes
                      (default inference-jobs); the lane is installed
                      by tenancy.install_async_lane(app)
  TPU_TENANT_CHECKPOINT_EVERY  async-lane resume-checkpoint cadence in
                      tokens (default 8)
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from .batcher import BatcherClosed, ClassPolicy, CoalescingBatcher, pad_bucket
from .checkpoint import (load_npz, load_orbax, load_params, maybe_quantize,
                         placed, random_params, save_npz, save_orbax)
from .engine import DEFAULT_BATCH_BUCKETS, DEFAULT_SEQ_BUCKETS, Program, TPUEngine
from .generator import GenerationEngine, GenerationError, GenStream

__all__ = [
    "BatcherClosed", "ClassPolicy", "CoalescingBatcher", "pad_bucket",
    "load_npz", "load_orbax", "load_params", "maybe_quantize", "placed",
    "random_params", "save_npz", "save_orbax",
    "DEFAULT_BATCH_BUCKETS", "DEFAULT_SEQ_BUCKETS", "Program", "TPUEngine",
    "GenerationEngine", "GenerationError", "GenStream",
    "new_engine_from_config", "parse_mesh",
]


def _opt_int(val: str | None) -> int | None:
    """Tri-state int key (unset -> None, which get_int's single default
    cannot express); malformed values fall back to None like every
    other config key degrades to its default instead of crashing
    startup."""
    if not val:
        return None
    try:
        return int(val)
    except (TypeError, ValueError):
        return None


def _csv_ints(val: str | None, default: tuple[int, ...]) -> tuple[int, ...]:
    if not val:
        return default
    return tuple(int(x) for x in val.split(",") if x.strip())


def parse_mesh(spec: str | None):
    """"tp=8" / "tp=4,dp=2" -> Mesh over the named parallel axes (the
    TPU_SHARDING row syntax). Public: tools/benches that accept the
    same rows must parse them identically to the production wiring."""
    if not spec:
        return None
    from ..parallel import make_mesh

    axes = {}
    for part in spec.split(","):
        k, _, v = part.partition("=")
        axes[k.strip()] = int(v)
    return make_mesh(**axes)


# the engine constructor's options in the configuration's names, for
# the refusals a model family answers with (errors.UnsupportedOptions)
_OPTION_KEYS = {
    "mesh": "TPU_SHARDING", "paged_blocks": "TPU_PAGED_BLOCKS",
    "kvcache": "TPU_KVCACHE_HOST_MB / TPU_KVCACHE_REDIS",
    "spec_decode_k": "TPU_SPEC_DECODE", "lora_adapters": "TPU_LORA_ADAPTERS",
    "kv_dtype": "TPU_KV_DTYPE", "serving_role": "TPU_SERVING_ROLE"}


def _generation_engine(name: str, mc, params, **options) -> GenerationEngine:
    """The engine, or what its family refuses said by configuration key."""
    from ..errors import UnsupportedOptions

    try:
        return GenerationEngine(mc, params, **options)
    except UnsupportedOptions as e:
        raise UnsupportedOptions(
            [(_OPTION_KEYS.get(opt, opt), why) for opt, why in e.refused],
            f"TPU_MODEL={name}") from None


def new_engine_from_config(cfg, logger=None, metrics=None,
                           observe=None) -> TPUEngine:
    from .. import compile_cache
    from ..models import BERT_CONFIGS, LLAMA_CONFIGS, VIT_CONFIGS
    from ..observe.startup import StartupAccount

    compile_cache.configure()
    # the account of this start-up (observe/startup.py): from here to the
    # ready line every second belongs to a phase. It is the container's
    # (``observe.startup``), which the engines take the same way
    startup = observe.startup if observe is not None else StartupAccount()
    startup.phase("configure")

    if (cfg.get("TPU_SERVING_ROLE") or "").strip().lower() == "gateway":
        # the gateway role (gofr_tpu/gateway) is an APP mode, not an
        # engine mode: it fronts replicas and holds no model. A config
        # naming both is two deployments in one file — refuse BEFORE
        # building anything rather than guess which one was meant.
        raise ValueError(
            "TPU_SERVING_ROLE=gateway builds no engine (the gateway "
            "routes to TPU_GATEWAY_REPLICAS); unset "
            f"TPU_MODEL={cfg.get('TPU_MODEL')!r} on the gateway "
            "process, or drop the gateway role on this serving "
            "replica (docs/advanced-guide/gateway.md)")
    name = (cfg.get("TPU_MODEL") or "tiny").strip()
    mesh = parse_mesh(cfg.get("TPU_SHARDING"))
    max_delay = cfg.get_float("TPU_MAX_BATCH_DELAY", 0.004)
    batch_buckets = _csv_ints(cfg.get("TPU_BATCH_BUCKETS"), DEFAULT_BATCH_BUCKETS)
    seq_buckets = _csv_ints(cfg.get("TPU_SEQ_BUCKETS"), DEFAULT_SEQ_BUCKETS)

    from ..resilience import gate_from_config
    from . import hbm

    # the HBM arbiter budget (one per process — subsystems of every
    # engine built after this lease from it; mesh engines additionally
    # settle PER-DEVICE leases checked against the per-device budget).
    # Sized to the chips THIS engine occupies: its mesh's, or one.
    hbm.configure(budget_mb=cfg.get_int("TPU_HBM_BUDGET_MB", 0) or None,
                  headroom=cfg.get_float("TPU_HBM_HEADROOM", 0.1),
                  device_budget_mb=cfg.get_int("TPU_HBM_DEVICE_BUDGET_MB",
                                               0) or None,
                  n_devices=len(mesh.local_devices) if mesh is not None
                  else 1)

    tracer = getattr(observe, "tracer", None)
    batch_share = cfg.get_float("TPU_SLO_BATCH_SHARE", 0.0)
    class_policy = None
    if batch_share > 0:
        class_policy = ClassPolicy(
            throughput_delay=cfg.get_float("TPU_SLO_BATCH_DELAY", 0.0)
            or None,
            throughput_share=batch_share)
    engine = TPUEngine(logger=logger, metrics=metrics, max_delay=max_delay,
                       mesh=mesh, model_name=name, observe=observe,
                       class_policy=class_policy,
                       gate=gate_from_config(cfg, "predict", metrics=metrics,
                                             tracer=tracer, logger=logger))

    weights = cfg.get("TPU_WEIGHTS")
    quant = (cfg.get("TPU_QUANT") or "").lower() == "int8"

    def params_for(model_cfg, init_fn):
        with startup.within("weights") as acct:
            if weights:
                params = placed(maybe_quantize(load_params(weights), quant),
                                mesh)
            else:
                params = random_params(init_fn, model_cfg, quant=quant,
                                       mesh=mesh)
            # dispatched, not waited for: the host's next second overlaps
            # the device's last leaf, as before the account
            leaves = jax.tree_util.tree_leaves(params)
            acct.note(leaves=len(leaves), bytes=hbm.tree_nbytes(leaves))
        return params

    if name.startswith("bert"):
        from ..models import bert

        key = {"bert": "bert-base", "bert-tiny": "tiny"}.get(name, name)
        mc = BERT_CONFIGS[key]
        params = params_for(mc, bert.init)
        seq_b = tuple(b for b in seq_buckets if b <= mc.max_seq) or (mc.max_seq,)

        def embed_fn(p, tokens, lengths):
            mask = jnp.arange(tokens.shape[1])[None, :] < lengths[:, None]
            return bert.embed(p, mc, tokens, mask)

        engine.register("embed", embed_fn, params, kind="tokens",
                        batch_buckets=batch_buckets, seq_buckets=seq_b)
    elif name.startswith("vit"):
        from ..models import vit

        key = {"vit": "vit-l-14", "vit-l14": "vit-l-14", "vit-tiny": "tiny"}.get(name, name)
        mc = VIT_CONFIGS[key]
        params = params_for(mc, vit.init)

        def classify_fn(p, images):
            return jax.nn.softmax(vit.forward(p, mc, images), axis=-1)

        import numpy as np

        engine.register("classify", classify_fn, params, kind="fixed",
                        batch_buckets=batch_buckets,
                        example_item=np.zeros(
                            (mc.image_size, mc.image_size, 3), np.float32))
    else:
        from ..models import family, llama

        mc = LLAMA_CONFIGS.get(name)
        if mc is None:
            raise KeyError(f"unknown TPU_MODEL {name!r}; known: "
                           f"{sorted(LLAMA_CONFIGS) + sorted(BERT_CONFIGS) + sorted(VIT_CONFIGS)}")
        # the decoder family (models.family: by what the configuration
        # says, e.g. a latent cache row)
        fam = family(mc)
        params = params_for(mc, fam.init)
        max_seq = cfg.get_int("TPU_MAX_SEQ", min(mc.max_seq, 2048))
        slots = cfg.get_int("TPU_SLOTS", 48)
        kv_choice = (cfg.get("TPU_KV_DTYPE") or "int8").lower()
        kv_dtype = jnp.int8 if kv_choice == "int8" else None
        prompt_b = tuple(b for b in seq_buckets if b < max_seq) or (max_seq // 2,)
        kv_opts = None
        if cfg.get_int("TPU_PREFIX_CACHE", 0) > 0 \
                and cfg.get_int("TPU_PAGED_BLOCKS", 0) == 0:
            # paged engines keep their zero-copy SharedPrefixIndex —
            # don't open a Redis connection the engine would
            # immediately discard. Mesh engines DO take the offload
            # tiers: T1/T2 spill/restore sharded rows per shard
            # (docs/advanced-guide/multichip-serving.md)
            from .kvcache import options_from_config

            kv_opts = options_from_config(cfg, logger=logger,
                                          metrics=metrics)
        engine.generator = _generation_engine(
            name, mc, params, slots=slots, max_seq=max_seq,
            prompt_buckets=prompt_b,
            logger=logger, metrics=metrics, observe=observe, mesh=mesh,
            gate=gate_from_config(cfg, "generate", metrics=metrics,
                                  tracer=tracer, logger=logger),
            kv_dtype=kv_dtype,
            decode_block=cfg.get_int("TPU_DECODE_BLOCK", 4),
            admit_window_ms=cfg.get_float("TPU_ADMIT_WINDOW_MS", 2.0),
            prefill_chunk=_opt_int(cfg.get("TPU_PREFILL_CHUNK")),
            slo_throughput_share=cfg.get_float("TPU_SLO_THROUGHPUT_SHARE",
                                               0.25),
            slo_latency_slots=cfg.get_int("TPU_SLO_LATENCY_SLOTS", 1),
            prefix_cache_slots=cfg.get_int("TPU_PREFIX_CACHE", 0),
            prefix_store_min=cfg.get_int("TPU_PREFIX_MIN", 0) or None,
            kvcache=kv_opts,
            spec_decode_k=cfg.get_int("TPU_SPEC_DECODE", 0),
            lora_adapters=cfg.get_int("TPU_LORA_ADAPTERS", 0),
            lora_rank=cfg.get_int("TPU_LORA_RANK", 16),
            paged_blocks=cfg.get_int("TPU_PAGED_BLOCKS", 0),
            paged_block_size=cfg.get_int("TPU_PAGED_BLOCK", 128),
            serving_role=(cfg.get("TPU_SERVING_ROLE") or "").strip().lower(),
            stall_ms=cfg.get_float("TPU_STALL_MS", 1000.0))

        # scoring program: next-token logits at the prompt end (the
        # non-streaming sibling of generate, e.g. for classification
        # heads). The batcher coalesces UNRELATED requests into one
        # [B, S] batch, so grouped MoE dispatch is forbidden here just
        # like at decode — request isolation (llama.py:
        # multi_request_serving_config).
        score_mc = llama.multi_request_serving_config(mc)

        def score_fn(p, tokens, lengths):
            # gather the prompt-end hidden state BEFORE lm_head: full
            # [B, S, V] f32 logits are 2.1 GB at (8, 512) x 128k vocab
            return fam.forward(p, score_mc, tokens, lengths,
                               logit_pos=jnp.maximum(lengths - 1, 0))[:, 0]

        seq_b = tuple(b for b in seq_buckets if b <= max_seq) or (max_seq,)
        engine.register("score", score_fn, params, kind="tokens",
                        batch_buckets=batch_buckets, seq_buckets=seq_b)

    # multi-tenant plane: registry + quotas + fair-share weights
    # (gofr_tpu/tenancy). Installed on the engine AND pushed into the
    # generator so the pending line fans into per-tenant DRR queues and
    # the kv cache learns its per-tenant budget shares.
    from ..tenancy import plane_from_config

    plane = plane_from_config(cfg, metrics=metrics, logger=logger)
    if plane is not None:
        engine.tenancy = plane
        if engine.generator is not None:
            engine.generator.install_tenancy(plane)

    role_key = cfg.get("TPU_SERVING_ROLE")
    if role_key:
        # disaggregated prefill/decode serving (gofr_tpu/pd/,
        # docs/advanced-guide/disaggregated-serving.md): non-fused
        # roles attach their PD half here — after the generator exists,
        # before warmup — so a misconfigured role fails startup loudly
        from ..pd import ROLE_FUSED, parse_role, wire_role

        role = parse_role(role_key)
        if role != ROLE_FUSED:
            wire_role(engine, role, cfg, logger=logger, metrics=metrics)

    if cfg.get_bool("TPU_WARMUP"):
        engine.warmup()
    took = startup.finish()
    if logger is not None:
        # seconds from the entry of this function, the three longest
        # phases, and the programs that missed the persistent cache
        logger.info({"event": "tpu engine ready", "model": name,
                     "platform": engine.platform, "devices": len(engine.devices),
                     "quant": "int8" if quant else "none",
                     "sharding": cfg.get("TPU_SHARDING") or "single",
                     **took})
    return engine
