"""ctypes bindings for the native (C++) microbatcher.

Builds ``microbatcher.cpp`` on demand with g++ (pybind11 is not in this
image; the C ABI + ctypes keeps the dependency surface at zero). The build
is cached next to the source under a name keyed on the source's CONTENT
(``_<stem>.<sha256[:16]>.so``, git-ignored), so a binary can only ever
serve the source it was built from — a copied tree, a fresh checkout and
an edited file all resolve correctly without trusting mtimes. Set
``RTFD_DISABLE_NATIVE=1`` to force the pure-Python assembler.
"""

from __future__ import annotations

import ctypes
import hashlib
import os
import subprocess
import threading
from pathlib import Path
from typing import List, Optional

_DIR = Path(__file__).resolve().parent
_SRC = _DIR / "microbatcher.cpp"

_lock = threading.Lock()
_lib: Optional[ctypes.CDLL] = None
_build_error: Optional[str] = None


def _compile_native(src: Path) -> tuple[Optional[ctypes.CDLL], Optional[str]]:
    """Shared on-demand g++ build: env-var gate, content-keyed cache, one
    compiler recipe for every native kernel in this package. Returns
    (lib, error)."""
    if os.environ.get("RTFD_DISABLE_NATIVE") == "1":
        return None, "disabled via RTFD_DISABLE_NATIVE"
    try:
        digest = hashlib.sha256(src.read_bytes()).hexdigest()[:16]
        lib_path = src.with_name(f"_{src.stem}.{digest}.so")
        if not lib_path.exists():
            # build to a private name, then rename: concurrent worker
            # processes never load a half-written library
            tmp = lib_path.with_suffix(f".{os.getpid()}.tmp")
            cmd = [
                "g++", "-O3", "-std=c++17", "-shared", "-fPIC", "-pthread",
                str(src), "-o", str(tmp),
            ]
            subprocess.run(cmd, check=True, capture_output=True, timeout=120)
            os.replace(tmp, lib_path)
            for stale in src.parent.glob(f"_{src.stem}.*.so"):
                if stale != lib_path:
                    stale.unlink(missing_ok=True)
        return ctypes.CDLL(str(lib_path)), None
    except (OSError, subprocess.SubprocessError) as e:
        return None, str(e)


def _build() -> Optional[ctypes.CDLL]:
    global _build_error
    lib, _build_error = _compile_native(_SRC)
    if lib is None:
        return None

    lib.mb_create.restype = ctypes.c_void_p
    lib.mb_create.argtypes = [ctypes.c_size_t, ctypes.c_size_t,
                              ctypes.c_size_t, ctypes.c_double]
    lib.mb_destroy.argtypes = [ctypes.c_void_p]
    lib.mb_push.restype = ctypes.c_int
    lib.mb_push.argtypes = [ctypes.c_void_p, ctypes.c_char_p, ctypes.c_uint32]
    lib.mb_pending.restype = ctypes.c_size_t
    lib.mb_pending.argtypes = [ctypes.c_void_p]
    lib.mb_next_batch.restype = ctypes.c_int
    lib.mb_next_batch.argtypes = [
        ctypes.c_void_p, ctypes.c_char_p, ctypes.c_size_t,
        ctypes.POINTER(ctypes.c_uint32), ctypes.c_int,
    ]
    for name in ("mb_stat_batches", "mb_stat_records", "mb_stat_dropped"):
        fn = getattr(lib, name)
        fn.restype = ctypes.c_uint64
        fn.argtypes = [ctypes.c_void_p]
    return lib


def native_available() -> bool:
    return _get_lib() is not None


def native_build_error() -> Optional[str]:
    _get_lib()
    return _build_error


def _get_lib() -> Optional[ctypes.CDLL]:
    global _lib
    with _lock:
        if _lib is None and _build_error is None:
            _lib = _build()
        return _lib


class NativeMicrobatchQueue:
    """Lock-free MPMC ingest queue + deadline microbatcher (C++ backed).

    Same close-condition contract as stream.microbatch.MicrobatchAssembler:
    a batch closes when it reaches ``max_batch`` or when ``max_delay_ms`` has
    passed since its oldest record was enqueued.
    """

    def __init__(self, capacity: int = 4096, slot_bytes: int = 4096,
                 max_batch: int = 256, max_delay_ms: float = 5.0):
        lib = _get_lib()
        if lib is None:
            raise RuntimeError(f"native microbatcher unavailable: {_build_error}")
        self._lib = lib
        self.slot_bytes = slot_bytes
        self.max_batch = max_batch
        self._q = ctypes.c_void_p(lib.mb_create(
            capacity, slot_bytes, max_batch, max_delay_ms
        ))
        self._out_buf = ctypes.create_string_buffer(slot_bytes * max_batch)
        self._out_lens = (ctypes.c_uint32 * max_batch)()

    def _handle(self) -> ctypes.c_void_p:
        if not self._q:
            raise ValueError("queue is closed")
        return self._q

    def push(self, payload: bytes) -> bool:
        """Enqueue one record; False when the ring is full (backpressure)."""
        rc = self._lib.mb_push(self._handle(), payload, len(payload))
        if rc == -2:
            raise ValueError(
                f"payload of {len(payload)} bytes exceeds slot size {self.slot_bytes}"
            )
        return rc == 0

    def next_batch(self, block_ms: int = 0) -> List[bytes]:
        n = self._lib.mb_next_batch(
            self._handle(), self._out_buf, len(self._out_buf), self._out_lens,
            block_ms,
        )
        if n <= 0:
            return []
        used = sum(self._out_lens[i] for i in range(n))
        raw = ctypes.string_at(self._out_buf, used)  # copy used prefix only
        out: List[bytes] = []
        off = 0
        for i in range(n):
            ln = self._out_lens[i]
            out.append(raw[off:off + ln])
            off += ln
        return out

    def pending(self) -> int:
        return int(self._lib.mb_pending(self._handle()))

    def stats(self) -> dict:
        h = self._handle()
        return {
            "batches": int(self._lib.mb_stat_batches(h)),
            "records": int(self._lib.mb_stat_records(h)),
            "dropped": int(self._lib.mb_stat_dropped(h)),
        }

    def close(self) -> None:
        if self._q:
            self._lib.mb_destroy(self._q)
            self._q = ctypes.c_void_p(None)

    def __del__(self):  # pragma: no cover
        try:
            self.close()
        except Exception:
            pass


# ------------------------------------------------------------------- trees
_TREES_SRC = _DIR / "trees.cpp"
_trees_lib: Optional[ctypes.CDLL] = None
_trees_error: Optional[str] = None


def _build_trees() -> Optional[ctypes.CDLL]:
    global _trees_error
    lib, _trees_error = _compile_native(_TREES_SRC)
    if lib is None:
        return None
    import numpy as np
    from numpy.ctypeslib import ndpointer

    lib.trees_score_mt.restype = None
    lib.trees_score_mt.argtypes = [
        ndpointer(np.int32, flags="C_CONTIGUOUS"),
        ndpointer(np.float32, flags="C_CONTIGUOUS"),
        ndpointer(np.float32, flags="C_CONTIGUOUS"),
        ctypes.c_float, ctypes.c_int32, ctypes.c_int32,
        ndpointer(np.float32, flags="C_CONTIGUOUS"),
        ctypes.c_int32, ctypes.c_int32,
        ndpointer(np.float32, flags="C_CONTIGUOUS"),
        ctypes.c_int32,
    ]
    return lib


def _get_trees_lib() -> Optional[ctypes.CDLL]:
    global _trees_lib
    with _lock:
        if _trees_lib is None and _trees_error is None:
            _trees_lib = _build_trees()
        return _trees_lib


def native_trees_available() -> bool:
    return _get_trees_lib() is not None


class NativeTreeScorer:
    """C++ boosted-tree inference over the framework's complete-binary-tree
    layout (models/trees.py TreeEnsemble) — the CPU-baseline scorer twin of
    the TPU tensorized traversal (SURVEY.md §2.9 component 2) and an
    independent numerics oracle for it.
    """

    def __init__(self, ensemble, n_threads: int = 0):
        import numpy as np

        lib = _get_trees_lib()
        if lib is None:
            raise RuntimeError(f"native tree scorer unavailable: {_trees_error}")
        self._lib = lib
        self.feature = np.ascontiguousarray(
            np.asarray(ensemble.feature), np.int32)
        self.threshold = np.ascontiguousarray(
            np.asarray(ensemble.threshold), np.float32)
        self.leaf = np.ascontiguousarray(np.asarray(ensemble.leaf), np.float32)
        self.base_score = float(np.asarray(ensemble.base_score))
        self.n_trees = self.feature.shape[0]
        self.depth = int(self.leaf.shape[1]).bit_length() - 1
        self.n_threads = n_threads or min(8, os.cpu_count() or 1)
        # widest feature index any split touches: inputs narrower than this
        # would make the C++ kernel read out of bounds
        self.min_features = int(self.feature.max()) + 1 if self.n_trees else 0

    def logits(self, x):
        import numpy as np

        x = np.ascontiguousarray(np.asarray(x), np.float32)
        if x.ndim != 2 or x.shape[1] < self.min_features:
            raise ValueError(
                f"need f32[B, >= {self.min_features}] features, got {x.shape}")
        out = np.empty((x.shape[0],), np.float32)
        self._lib.trees_score_mt(
            self.feature, self.threshold, self.leaf, self.base_score,
            self.n_trees, self.depth, x, x.shape[0], x.shape[1], out,
            self.n_threads)
        return out

    def predict(self, x):
        """Fraud probability: sigmoid(logits), matching
        models.trees.tree_ensemble_predict."""
        import numpy as np

        return 1.0 / (1.0 + np.exp(-self.logits(x)))
