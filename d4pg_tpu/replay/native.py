"""ctypes bindings for the native C++ segment trees (``native/sumtree.cpp``).

Compiled on first use with g++ into a repo-local build dir (pybind11 is not
available in the image; the C ABI + ctypes keeps the binding dependency-free).
API-compatible with :class:`d4pg_tpu.replay.SumTree` / ``MinTree`` so
:class:`~d4pg_tpu.replay.PrioritizedReplayBuffer` swaps backends via its
``tree_backend`` argument ("auto" prefers native, falls back to NumPy).
"""

from __future__ import annotations

import ctypes
import hashlib
import os
import subprocess
import threading

import numpy as np

_LIB = None
_LIB_LOCK = threading.Lock()


def _source_path() -> str:
    here = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
    return os.path.join(here, "native", "sumtree.cpp")


def _build_dir() -> str:
    d = os.path.join(os.path.dirname(os.path.abspath(__file__)), "_native_build")
    os.makedirs(d, exist_ok=True)
    return d


def library_path() -> str:
    """Where the binary for the CURRENT ``native/sumtree.cpp`` lives: the
    file name carries a hash of the source, so a binary left on disk by
    another checkout, another commit or a tree copy with scrambled mtimes
    is never mistaken for this one (the build dir is gitignored and
    travels with directory copies)."""
    with open(_source_path(), "rb") as f:
        digest = hashlib.sha256(f.read()).hexdigest()[:16]
    return os.path.join(_build_dir(), f"libsumtree-{digest}.so")


def load_library() -> ctypes.CDLL:
    """Compile (if absent for this source) and load the shared library.
    Raises on any failure; callers with ``tree_backend='auto'`` catch and
    fall back to NumPy."""
    global _LIB
    with _LIB_LOCK:
        if _LIB is not None:
            return _LIB
        so = library_path()
        if not os.path.exists(so):
            # one-time compile; serializing concurrent first-users on the
            # lock is the point. Built under a private name and renamed
            # into place so another PROCESS never loads a half-written
            # file. No -march=native: the build dir travels with copies.
            tmp = f"{so}.{os.getpid()}.tmp"
            try:
                subprocess.run(  # d4pglint: disable=lock-blocking-call
                    ["g++", "-O3", "-shared", "-fPIC", "-o", tmp,
                     _source_path()],
                    check=True,
                    capture_output=True,
                )
                os.replace(tmp, so)
            finally:
                if os.path.exists(tmp):
                    os.unlink(tmp)
        lib = ctypes.CDLL(so)
        lib.st_create.restype = ctypes.c_void_p
        lib.st_create.argtypes = [ctypes.c_int64, ctypes.c_int]
        lib.st_destroy.argtypes = [ctypes.c_void_p]
        lib.st_capacity.restype = ctypes.c_int64
        lib.st_capacity.argtypes = [ctypes.c_void_p]
        lib.st_set.argtypes = [
            ctypes.c_void_p,
            ctypes.POINTER(ctypes.c_int64),
            ctypes.POINTER(ctypes.c_double),
            ctypes.c_int64,
        ]
        lib.st_get.argtypes = [
            ctypes.c_void_p,
            ctypes.POINTER(ctypes.c_int64),
            ctypes.POINTER(ctypes.c_double),
            ctypes.c_int64,
        ]
        lib.st_root.restype = ctypes.c_double
        lib.st_root.argtypes = [ctypes.c_void_p]
        lib.st_find_prefix.argtypes = [
            ctypes.c_void_p,
            ctypes.POINTER(ctypes.c_double),
            ctypes.POINTER(ctypes.c_int64),
            ctypes.c_int64,
        ]
        lib.st_sample_gather.argtypes = [
            ctypes.c_void_p,                   # sum tree
            ctypes.c_void_p,                   # min tree
            ctypes.POINTER(ctypes.c_double),   # prefixes [n]
            ctypes.c_int64,                    # n = K*B
            ctypes.c_int64,                    # deal_k
            ctypes.c_int64,                    # size (live rows)
            ctypes.c_double,                   # beta
            ctypes.c_void_p,                   # obs ring (f32 or u8)
            ctypes.POINTER(ctypes.c_float),    # action ring
            ctypes.POINTER(ctypes.c_float),    # reward ring
            ctypes.c_void_p,                   # next_obs ring
            ctypes.POINTER(ctypes.c_float),    # discount ring
            ctypes.POINTER(ctypes.c_int64),    # generation ring
            ctypes.c_int64,                    # obs_dim
            ctypes.c_int64,                    # act_dim
            ctypes.c_int,                      # obs_mode
            ctypes.POINTER(ctypes.c_int64),    # idx out
            ctypes.POINTER(ctypes.c_int64),    # gen out
            ctypes.POINTER(ctypes.c_float),    # weights out
            ctypes.c_void_p,                   # obs out
            ctypes.POINTER(ctypes.c_float),    # action out
            ctypes.POINTER(ctypes.c_float),    # reward out
            ctypes.c_void_p,                   # next_obs out
            ctypes.POINTER(ctypes.c_float),    # discount out
        ]
        lib.st_update_priorities.restype = ctypes.c_double
        lib.st_update_priorities.argtypes = [
            ctypes.c_void_p,                   # sum tree
            ctypes.c_void_p,                   # min tree
            ctypes.POINTER(ctypes.c_int64),    # idx [n]
            ctypes.POINTER(ctypes.c_double),   # priorities [n] (|td|+eps)
            ctypes.c_int64,                    # n
            ctypes.POINTER(ctypes.c_int64),    # sample_gen [n] or None
            ctypes.POINTER(ctypes.c_int64),    # current generation ring
            ctypes.c_double,                   # alpha
        ]
        _LIB = lib
        return _LIB


def _i64(a: np.ndarray):
    return a.ctypes.data_as(ctypes.POINTER(ctypes.c_int64))


def _f64(a: np.ndarray):
    return a.ctypes.data_as(ctypes.POINTER(ctypes.c_double))


def _f32(a: np.ndarray):
    return a.ctypes.data_as(ctypes.POINTER(ctypes.c_float))


def _vp(a: np.ndarray):
    return a.ctypes.data_as(ctypes.c_void_p)


# obs_mode values for st_sample_gather (must match native/sumtree.cpp)
OBS_F32 = 0      # float32 rows copied as-is
OBS_U8_DECODE = 1  # uint8 rows decoded to float32/255 at gather time
OBS_U8_RAW = 2   # uint8 rows copied raw (uint8 wire format)


class SampleGatherCall:
    """Precomputed ``st_sample_gather`` argument block for one (ring,
    staging-slot) pair.

    Pointer marshaling (``ndarray.ctypes.data_as``) costs ~1-2 µs per
    argument and the call takes 24 of them — at batch 256 that rivals the
    gather itself. The ring arrays and staging buffers are stable
    allocations (that stability is the point of the preallocated staging),
    so every pointer except the per-call ``prefixes`` is computed ONCE here
    and the hot path marshals exactly one array.
    """

    def __init__(
        self,
        sum_tree: "NativeSumTree",
        min_tree: "NativeMinTree",
        obs: np.ndarray,
        action: np.ndarray,
        reward: np.ndarray,
        next_obs: np.ndarray,
        discount: np.ndarray,
        gen: np.ndarray,
        obs_mode: int,
        out: dict,
    ):
        assert out["obs"].dtype == (
            np.float32 if obs_mode != OBS_U8_RAW else np.uint8
        )
        for a in (obs, action, reward, next_obs, discount, gen):
            assert a.flags.c_contiguous
        self._fn = load_library().st_sample_gather
        self._trees = (sum_tree._h, min_tree._h)
        self._ring = (
            _vp(obs), _f32(action), _f32(reward), _vp(next_obs),
            _f32(discount), _i64(gen), obs.shape[1], action.shape[1],
            int(obs_mode),
        )
        self._out = (
            _i64(out["idx"]), _i64(out["gen"]), _f32(out["weights"]),
            _vp(out["obs"]), _f32(out["action"]), _f32(out["reward"]),
            _vp(out["next_obs"]), _f32(out["discount"]),
        )

    def __call__(
        self, prefixes: np.ndarray, deal_k: int, size: int, beta: float
    ) -> None:
        """Run the fused descent+weights+gen-capture+gather. ``prefixes``
        [n] are caller-generated from the NumPy Generator so the seeded
        draw stream matches the NumPy oracle byte-for-byte."""
        self._fn(
            *self._trees, _f64(prefixes), prefixes.size, deal_k, size,
            float(beta), *self._ring, *self._out,
        )


def update_priorities(
    sum_tree: "NativeSumTree",
    min_tree: "NativeMinTree",
    idx: np.ndarray,
    priorities: np.ndarray,
    sample_gen: np.ndarray | None,
    cur_gen: np.ndarray,
    alpha: float,
) -> float:
    """Batched gen-filtered priority write-back; returns the max applied
    pre-α priority (0.0 when every entry was dropped as recycled)."""
    lib = load_library()
    assert idx.flags.c_contiguous and priorities.flags.c_contiguous
    assert idx.size == priorities.size
    sg = _i64(sample_gen) if sample_gen is not None else None
    return lib.st_update_priorities(
        sum_tree._h, min_tree._h, _i64(idx), _f64(priorities), idx.size,
        sg, _i64(cur_gen), float(alpha),
    )


class _NativeTreeBase:
    def __init__(self, capacity: int, is_min: bool):
        self._lib = load_library()
        self._h = self._lib.st_create(capacity, 1 if is_min else 0)
        self.capacity = self._lib.st_capacity(self._h)

    def __del__(self):
        if getattr(self, "_h", None):
            self._lib.st_destroy(self._h)
            self._h = None

    def set(self, indices, values) -> None:
        idx = np.ascontiguousarray(np.atleast_1d(indices), np.int64)
        vals = np.ascontiguousarray(np.atleast_1d(values), np.float64)
        self._lib.st_set(self._h, _i64(idx), _f64(vals), idx.size)

    def get(self, indices) -> np.ndarray:
        idx = np.ascontiguousarray(np.atleast_1d(indices), np.int64)
        out = np.empty(idx.size, np.float64)
        self._lib.st_get(self._h, _i64(idx), _f64(out), idx.size)
        return out

    @property
    def root(self) -> float:
        return self._lib.st_root(self._h)


class NativeSumTree(_NativeTreeBase):
    def __init__(self, capacity: int):
        super().__init__(capacity, is_min=False)

    def sum(self) -> float:
        return self.root

    def find_prefixsum_idx(self, prefixes) -> np.ndarray:
        p = np.ascontiguousarray(np.atleast_1d(prefixes), np.float64)
        out = np.empty(p.size, np.int64)
        self._lib.st_find_prefix(self._h, _f64(p), _i64(out), p.size)
        return out


class NativeMinTree(_NativeTreeBase):
    def __init__(self, capacity: int):
        super().__init__(capacity, is_min=True)

    def min(self) -> float:
        return self.root
