"""Hierarchical prefix-KV cache: radix index + host offload + Redis.

The subsystem behind ``GenerationEngine``'s prefix reuse (the engine
owns device memory and every jitted copy; this package owns indexing,
host snapshots, and the shared tier):

  T0  HBM pool rows, block-hash radix indexed    (hbm.HBMTier)
  T1  host-DRAM spill of LRU-evicted rows        (host.HostTier)
  T2  Redis-shared int8 blocks across replicas   (redis_tier.RedisTier)

behind one facade (manager.CacheManager). See
docs/advanced-guide/kv-cache.md for the tier diagram and deployment
notes, and tools/kvcache_bench.py for the hit-vs-miss TTFT numbers.

Config (read by ``new_engine_from_config`` via options_from_config):

  TPU_KVCACHE_BLOCK        radix block size in tokens (default 16)
  TPU_KVCACHE_HOST_MB      T1 host-DRAM budget in MiB (default 0 = off)
  TPU_KVCACHE_REDIS        "true" enables the shared tier over the
                           framework Redis client (REDIS_HOST/PORT)
  TPU_KVCACHE_REDIS_TTL_S  shared-block TTL seconds (default 300)
  TPU_KVCACHE_REDIS_TIMEOUT_S  shared-tier socket timeout (default 0.25)
  TPU_KVCACHE_EPOCH_REFRESH_S  adapter-epoch staleness bound (default 5)
"""

from __future__ import annotations

import hashlib
from dataclasses import dataclass
from typing import Any

from .hbm import HBMTier
from .host import HostTier
from .manager import CacheManager, Match, clamp_restore_len
from .quant import (HostKV, KVLayout, ShardedHostKV, decode_block,
                    dense_hostkv, encode_block)
from .radix import Entry, RadixIndex, chain_hashes, first_block_hash
from .redis_tier import RedisTier

__all__ = [
    "CacheManager", "Match", "clamp_restore_len",
    "HBMTier", "HostTier", "RedisTier",
    "HostKV", "KVLayout", "ShardedHostKV", "dense_hostkv",
    "encode_block", "decode_block",
    "Entry", "RadixIndex", "chain_hashes", "first_block_hash",
    "KVCacheOptions", "options_from_config", "model_fingerprint",
]


@dataclass
class KVCacheOptions:
    """Tier wiring handed to the engine. ``redis`` is a live
    RedisClient (or anything with get/mget/set/incr/pipeline/close) —
    the engine takes ownership and closes it on engine.close() (or
    immediately when a mesh engine discards the offload tiers); None
    keeps the shared tier off."""

    block: int = 16
    host_mb: int = 0
    redis: Any = None
    redis_ttl_s: float = 300.0
    epoch_refresh_s: float = 5.0


def options_from_config(cfg, logger=None, metrics=None) -> KVCacheOptions:
    """TPU_KVCACHE_* -> options. The Redis tier is built on the
    framework's own datasource client and degrades gracefully: an
    unreachable Redis logs once and leaves the tier off (reference
    container style — a down datasource never blocks startup)."""
    redis = None
    if cfg.get_bool("TPU_KVCACHE_REDIS"):
        try:
            from ...datasource.redisclient import RedisClient

            # a DEDICATED short socket timeout, not the datasource
            # default 5 s: T2 consults run on the serving-loop thread,
            # and a merely-degraded Redis must trip the tier's
            # fail-open error path instead of freezing every active
            # decode stream for seconds per lookup
            redis = RedisClient(
                host=cfg.get_or_default("REDIS_HOST", "localhost"),
                port=cfg.get_int("REDIS_PORT", 6379),
                logger=logger, metrics=metrics,
                timeout=cfg.get_float("TPU_KVCACHE_REDIS_TIMEOUT_S", 0.25))
        except Exception as e:  # noqa: BLE001 — degrade, don't block boot
            if logger is not None:
                logger.warn({"event": "kvcache redis tier disabled "
                             "(connect failed)", "error": repr(e)})
    return KVCacheOptions(
        block=cfg.get_int("TPU_KVCACHE_BLOCK", 16),
        host_mb=cfg.get_int("TPU_KVCACHE_HOST_MB", 0),
        redis=redis,
        redis_ttl_s=cfg.get_float("TPU_KVCACHE_REDIS_TTL_S", 300.0),
        epoch_refresh_s=cfg.get_float("TPU_KVCACHE_EPOCH_REFRESH_S", 5.0))


def model_fingerprint(cfg, params=None, extra: str = "") -> str:
    """Short stable id for (architecture, weights, cache dtype): the T2
    key prefix that keeps replicas with different models from ever
    exchanging KV. Weights contribute tiny deterministic samples from
    leaves spread ACROSS the tree — one leaf is not enough (fine-tunes
    often share a frozen/tied embedding table, typically first in tree
    order) — still without hashing gigabytes; on any failure the
    config-only hash still isolates architectures."""
    from ...models import ModelConfig, family

    # the row tables a cached token has, not the depth: a row of a
    # one-pass model must never be restored into one that runs its stack
    # several times (the same number wherever a token passes a layer
    # once, and for a bare description of sizes that is no ModelConfig)
    tables = family(cfg).kv_tables(cfg) if isinstance(cfg, ModelConfig) \
        else cfg.n_layers
    h = hashlib.sha256()
    h.update(repr((cfg.name, cfg.vocab_size, cfg.dim, tables,
                   cfg.n_heads, cfg.n_kv_heads, cfg.head_dim,
                   cfg.rope_theta)).encode())
    h.update(extra.encode())
    if params is not None:
        try:
            import jax
            import numpy as np

            leaves = jax.tree_util.tree_leaves(params)
            picks = sorted({0, len(leaves) // 3, (2 * len(leaves)) // 3,
                            len(leaves) - 1})
            # ONE batched transfer for all sampled leaves (device_get
            # takes a pytree) — per-leaf gets would sync the host once
            # per pick
            samples = jax.device_get(
                [leaves[i].reshape(-1)[:8] for i in picks])
            for sample in samples:
                h.update(np.asarray(sample).astype(np.float32).tobytes())
        except Exception:
            pass
    return h.hexdigest()[:16]
