"""A prompt admitted as two dispatches against the same prompt admitted
in one padded bucket (GenerationEngine._split_prefill), on one engine at
a family's ``tiny`` preset: the case every family's test module runs.

The plan is forced (no table is measured): every length past a bucket
leaves for that bucket and the rest. With buckets (8, 16, 32) that is a
rest that overlaps or is padded (20 = 16 + 4 in 8, 27 = 16 + 11 in 16),
a rest that is a whole bucket (32 = 16 + 16) and the smallest first
part (12 = 8 + 4 in 8)."""

import time

import jax
import numpy as np

from gofr_tpu.tpu import GenerationEngine

BUCKETS = (8, 16, 32)
MAX_SEQ = 96          # no other axis of a tiny cache is 96 long
LENGTHS = (12, 20, 27, 32)
STEPS = 16
# every length in (8, 16] leaves for 8, every length in (16, 32] for 16
FORCED = [0] * 9 + [8] * 8 + [16] * 16


def _admit(engine, plan, prompt):
    """(served tokens and logprobs, the slot's cache arrays after them)
    for ``prompt`` admitted under ``plan``."""
    engine._split_first = plan
    stream = engine.generate(prompt, max_new_tokens=STEPS, logprobs=True)
    served = [(int(t), float(lp)) for t, lp in stream]
    deadline = time.monotonic() + 30
    while engine.stats()["active"] and time.monotonic() < deadline:
        time.sleep(0.01)
    slot = stream.trace["slot"]
    with engine._device_lock:
        rows = jax.tree_util.tree_map(
            lambda a: np.asarray(a[:, slot]),
            engine.cache._replace(lengths=None))
    return served, rows


def _written(a: np.ndarray, n: int) -> np.ndarray:
    """The first ``n`` positions of an array with a position axis (the
    rest holds what earlier tenants and padding left), else all of it."""
    axes = [i for i, d in enumerate(a.shape) if d == MAX_SEQ]
    assert len(axes) <= 1, a.shape
    return np.take(a, range(n), axis=axes[0]) if axes else a


def _values(rows, field: str, n: int) -> np.ndarray:
    """A cache array as float32 values: an int8 array times its scales."""
    a = _written(getattr(rows, field), n).astype(np.float32)
    scale = getattr(rows, field + "_scale", None)
    return a if scale is None else a * _written(scale, n)[..., None]


def check(cfg, params, *, tol: float, kv_dtype=None, lp_tol=None,
          **engine_kw) -> None:
    """Each of LENGTHS admitted split and in one bucket: the same greedy
    tokens for STEPS steps, logprobs within ``lp_tol`` (``tol`` unless
    given) and every cache array of the slot (rows up to the last one
    decoded, states, tails, rings) within ``tol`` of its largest value;
    and the engine's count of what ran."""
    lp_tol = tol if lp_tol is None else lp_tol
    engine = GenerationEngine(cfg, params, slots=2, max_seq=MAX_SEQ,
                              prompt_buckets=BUCKETS, kv_dtype=kv_dtype,
                              **engine_kw)
    try:
        for L in LENGTHS:
            prompt = np.random.default_rng(L).integers(
                1, cfg.vocab_size, L).tolist()
            before = dict(engine._prefill_n)
            split, rows_s = _admit(engine, FORCED, prompt)
            first = FORCED[L]
            rest = next(b for b in BUCKETS if b >= L - first)
            after = engine._prefill_n
            assert after["split"] == before["split"] + 1
            assert after["positions"] == before["positions"] + first + rest
            assert after["prompt_tokens"] == before["prompt_tokens"] + L
            one, rows_o = _admit(engine, None, prompt)
            assert engine._prefill_n["split"] == after["split"]
            assert [t for t, _ in split] == [t for t, _ in one], L
            assert max(abs(a[1] - b[1])
                       for a, b in zip(split, one)) < lp_tol, L
            n = L + STEPS - 1
            for field in rows_s._fields:
                if getattr(rows_s, field) is None \
                        or field.endswith("_scale"):
                    continue
                a, b = _values(rows_s, field, n), _values(rows_o, field, n)
                assert np.abs(a - b).max() <= tol * max(1.0, np.abs(b).max()), \
                    (L, field)
    finally:
        engine.close()
