"""ctypes loader for the native runtime (gofr_runtime.cc).

Build model: the shared library is compiled on first load (g++ -O2
-shared, ~1s) and cached next to the source under a name carrying the
source's content hash, so only a binary built from exactly this source
is ever loaded — a stale or copied-in ``.so`` has the wrong name.
Environments without a toolchain fall back to pure-Python equivalents —
every native consumer (batcher, metrics) keeps a fallback path — but not
silently: ``load_error()`` says why, and the container logs it at
startup.

Set GOFR_NATIVE=0 to force the Python paths (useful for debugging).
"""

from __future__ import annotations

import ctypes
import glob
import hashlib
import os
import subprocess
import threading

_DIR = os.path.dirname(os.path.abspath(__file__))
_SRC = os.path.join(_DIR, "gofr_runtime.cc")

_lib = None
_load_lock = threading.Lock()
_load_attempted = False
_load_error: str | None = None


def _so_path() -> str:
    with open(_SRC, "rb") as f:
        digest = hashlib.sha256(f.read()).hexdigest()[:16]
    return os.path.join(_DIR, f"libgofr_runtime.{digest}.so")


def _build(so: str) -> None:
    """Compile to a scratch name, then rename: a concurrent process never
    loads a half-written library. Raises with the compiler's stderr."""
    tmp = f"{so}.{os.getpid()}.tmp"
    try:
        subprocess.run(
            ["g++", "-O2", "-std=c++17", "-shared", "-fPIC", "-pthread",
             "-o", tmp, _SRC],
            check=True, capture_output=True, timeout=120)
        os.replace(tmp, so)
    except subprocess.CalledProcessError as e:
        raise OSError(f"g++ failed (rc={e.returncode}): "
                      f"{e.stderr.decode(errors='replace')[-400:]}") from e
    except subprocess.TimeoutExpired as e:
        raise OSError("g++ timed out after 120s") from e
    finally:
        if os.path.exists(tmp):
            os.unlink(tmp)
    for stale in glob.glob(os.path.join(_DIR, "libgofr_runtime*.so")):
        if stale != so:
            os.unlink(stale)


def _bind(lib: ctypes.CDLL) -> ctypes.CDLL:
    u64 = ctypes.c_uint64
    lib.gq_new.restype = ctypes.c_void_p
    lib.gq_new.argtypes = [ctypes.c_int, ctypes.c_double]
    lib.gq_free.argtypes = [ctypes.c_void_p]
    lib.gq_push.restype = ctypes.c_int
    lib.gq_push.argtypes = [ctypes.c_void_p, u64]
    lib.gq_pop_batch.restype = ctypes.c_int
    lib.gq_pop_batch.argtypes = [ctypes.c_void_p, ctypes.POINTER(u64),
                                 ctypes.c_int, ctypes.POINTER(ctypes.c_double)]
    lib.gq_close.argtypes = [ctypes.c_void_p]
    lib.gq_size.restype = ctypes.c_int
    lib.gq_size.argtypes = [ctypes.c_void_p]
    lib.hist_new.restype = ctypes.c_void_p
    lib.hist_new.argtypes = [ctypes.POINTER(ctypes.c_double), ctypes.c_int]
    lib.hist_free.argtypes = [ctypes.c_void_p]
    # hist_record is wait-free, a few relaxed atomics. Through CDLL the
    # call lets go of the interpreter lock for those nanoseconds, and a
    # thread that records in a loop (the generation loop delivering a
    # block's tokens, one sample a token) then queues for the lock
    # behind every consumer it has just woken: a delivery of 512 tokens
    # took 7-112 ms on the chip, longer than the decode block behind it
    # (PERF.md, Findings PR 29). PyDLL keeps the lock across the call.
    lib.hist_record = ctypes.PyDLL(lib._name).hist_record
    lib.hist_record.argtypes = [ctypes.c_void_p, ctypes.c_double]
    lib.hist_snapshot.argtypes = [ctypes.c_void_p, ctypes.POINTER(u64),
                                  ctypes.POINTER(ctypes.c_double),
                                  ctypes.POINTER(u64)]
    return lib


def load():
    """The native library, or None when unavailable (``load_error()``
    then says why, unless GOFR_NATIVE=0 asked for it)."""
    global _lib, _load_attempted, _load_error
    if _lib is not None or _load_attempted:
        return _lib
    with _load_lock:
        if _lib is not None or _load_attempted:
            return _lib
        _load_attempted = True
        if os.environ.get("GOFR_NATIVE", "1") == "0":
            return None
        try:
            so = _so_path()
            if not os.path.exists(so):
                _build(so)
            _lib = _bind(ctypes.CDLL(so))
        except OSError as e:  # no toolchain, failed build, unloadable .so
            _lib = None
            _load_error = f"{type(e).__name__}: {e}"
        return _lib


def available() -> bool:
    return load() is not None


def load_error() -> str | None:
    """Why the library failed to build or load; None when it loaded or
    was switched off with GOFR_NATIVE=0."""
    load()
    return _load_error


class NativeBatchQueue:
    """MPMC coalescing id queue; pop blocks in C with the GIL released."""

    def __init__(self, max_batch: int, max_delay: float):
        lib = load()
        if lib is None:
            raise RuntimeError("native runtime unavailable")
        self._lib = lib
        self._q = lib.gq_new(max_batch, max_delay)
        self.max_batch = max_batch
        self._out = (ctypes.c_uint64 * max_batch)()
        self._wait = ctypes.c_double()

    def push(self, item_id: int) -> bool:
        return self._lib.gq_push(self._q, item_id) == 0

    def pop_batch(self) -> tuple[list[int], float]:
        """Block until a batch is ready; ([], 0.0) means closed+drained."""
        n = self._lib.gq_pop_batch(self._q, self._out, self.max_batch,
                                   ctypes.byref(self._wait))
        return list(self._out[:n]), self._wait.value

    def close(self) -> None:
        self._lib.gq_close(self._q)

    def __len__(self) -> int:
        return self._lib.gq_size(self._q)

    def __del__(self):
        try:
            if self._q:
                self._lib.gq_close(self._q)
                self._lib.gq_free(self._q)
                self._q = None
        except Exception:
            pass


class NativeHistogram:
    """Wait-free fixed-bucket histogram (record is one C call, no lock)."""

    def __init__(self, bounds):
        lib = load()
        if lib is None:
            raise RuntimeError("native runtime unavailable")
        self._lib = lib
        self.bounds = tuple(bounds)
        arr = (ctypes.c_double * len(bounds))(*bounds)
        self._h = lib.hist_new(arr, len(bounds))

    def record(self, value: float) -> None:
        self._lib.hist_record(self._h, value)

    def snapshot(self) -> tuple[list[int], float, int]:
        """(per-bucket counts [len(bounds)+1], sum, count). Buffers are
        allocated per call: concurrent scrape threads must not share them."""
        counts = (ctypes.c_uint64 * (len(self.bounds) + 1))()
        total = ctypes.c_double()
        count = ctypes.c_uint64()
        self._lib.hist_snapshot(self._h, counts, ctypes.byref(total),
                                ctypes.byref(count))
        return list(counts), total.value, count.value

    def __del__(self):
        try:
            if self._h:
                self._lib.hist_free(self._h)
                self._h = None
        except Exception:
            pass
