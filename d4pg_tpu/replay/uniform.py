"""Uniform ring-buffer replay on preallocated NumPy arrays.

Capability parity with reference ``replay_memory.py:4-80`` (``Replay.add`` /
``sample``) — but columnar storage with O(1) vectorized batched writes and
gather-based sampling: a sampled batch is a set of contiguous-dtype arrays
ready for a single ``jax.device_put`` (the host→TPU boundary), not a Python
list of tuples re-stacked per sample (``replay_memory.py:61-80``).

Transitions carry an explicit per-sample ``discount`` = γ^m·(1−terminal) so
the device-side projection needs no gamma/n plumbing — this is how the build
fixes the reference's dead/inconsistent n-step path (SURVEY.md quirk #3/#5).
"""

from __future__ import annotations

import os
import threading
from typing import Mapping, NamedTuple

import numpy as np
from d4pg_tpu.analysis import lockwitness


class Transition(NamedTuple):
    """One (possibly n-step-collapsed) transition."""

    obs: np.ndarray        # s_t
    action: np.ndarray     # a_t
    reward: np.ndarray     # R_t = sum_{k<m} gamma^k r_{t+k}
    next_obs: np.ndarray   # s_{t+m}
    discount: np.ndarray   # gamma^m * (1 - terminal)


class ReplayBuffer:
    """Thread-safe columnar ring buffer.

    Writes (actor threads) and reads (learner thread) take a lock; the
    critical sections are pure NumPy slice ops so contention stays tiny.
    The reference's equivalent races (per-process buffers, SURVEY.md §5
    'race detection') are structurally removed.
    """

    def __init__(self, capacity: int, obs_dim: int, action_dim: int,
                 obs_dtype=np.float32, obs_scale: float | None = None,
                 decode_on_sample: bool = True):
        """``obs_dtype=np.uint8`` quantizes observations to bytes in storage
        — 4× less host RAM for pixel envs, the standard pixel-replay layout.
        ``obs_scale`` is the fixed store-time multiplier, declared once at
        construction (guessing the convention per frame mis-encodes dark
        frames). Only 255.0 ([0,1]-float envs, the default) is accepted:
        decoded batches are always [0,1] floats, so an env emitting raw
        [0,255] bytes would act on a different input range than it trains
        on — byte envs must normalize at the env boundary instead. Flat
        envs keep f32 and ignore ``obs_scale``."""
        self.capacity = int(capacity)
        self.obs_dtype = np.dtype(obs_dtype)
        self._quantized = self.obs_dtype == np.uint8
        # decode_on_sample=False (quantized buffers only) keeps sampled obs
        # rows in their stored uint8 form so the TRAINER can ship them
        # host→device at 1 byte/element and dequantize in-jit — a pixel
        # batch in f32 is 4× the bytes (302 MB per K=32 batch-256 48×48×2
        # dispatch). Consumers must divide by 255 before use.
        self._decode_on_sample = bool(decode_on_sample)
        self._obs_scale = float(obs_scale) if obs_scale is not None else 255.0
        if self._quantized and self._obs_scale != 255.0:
            # With scale≠255 the stored rows decode to [0,1] while acting/eval
            # feed the RAW env range to the same actor — a train/act input
            # mismatch. Byte envs must normalize at the env boundary (emit
            # [0,1] floats) instead of relying on store-time scale.
            raise ValueError(
                "obs_scale must be 255.0 (env emits [0,1] floats); byte-image "
                "envs should normalize observations at the env boundary"
            )
        self.obs = np.zeros((capacity, obs_dim), self.obs_dtype)
        self.action = np.zeros((capacity, action_dim), np.float32)
        self.reward = np.zeros((capacity,), np.float32)
        self.next_obs = np.zeros((capacity, obs_dim), self.obs_dtype)
        self.discount = np.zeros((capacity,), np.float32)
        # Per-slot write generation: bumped on every overwrite so async
        # priority write-backs can detect that a sampled slot was recycled
        # (new transition) before the flush landed, and drop the update
        # instead of stamping a fresh max-priority insert with another
        # transition's TD priority.
        self._gen = np.zeros((capacity,), np.int64)
        self._pos = 0
        self._size = 0
        # Monotone lifetime write counter (never wraps): the device-ring
        # mirror (replay/device_ring.py) diffs it to find which slots
        # changed since its last flush. Plain-int reads are safe off-lock
        # (readers tolerate one-batch staleness: unmirrored rows simply
        # ship on the next flush).
        self._total_added = 0
        # Witnessed under --debug-guards (static node id, see lockwitness)
        self._lock = lockwitness.named_lock("ReplayBuffer._lock")

    def _encode_obs(self, obs: np.ndarray) -> np.ndarray:
        obs = np.atleast_2d(np.asarray(obs, np.float32))
        if self._quantized:
            return np.clip(np.rint(obs * self._obs_scale), 0.0, 255.0).astype(
                np.uint8
            )
        return obs

    def _decode_obs(self, stored: np.ndarray) -> np.ndarray:
        if self._quantized:
            return stored.astype(np.float32) / 255.0
        return stored

    def __len__(self) -> int:
        return self._size

    @property
    def total_added(self) -> int:
        """Monotone count of rows ever written (including overwrites)."""
        return self._total_added

    def add_batch(self, t: Transition) -> np.ndarray:
        """Insert a batch of transitions; returns the slot indices written."""
        obs = self._encode_obs(t.obs)
        n = obs.shape[0]
        with self._lock:
            idx = (self._pos + np.arange(n)) % self.capacity
            self.obs[idx] = obs
            self.action[idx] = np.atleast_2d(np.asarray(t.action, np.float32))
            self.reward[idx] = np.asarray(t.reward, np.float32).reshape(n)
            self.next_obs[idx] = self._encode_obs(t.next_obs)
            self.discount[idx] = np.asarray(t.discount, np.float32).reshape(n)
            self._gen[idx] += 1
            self._pos = int((self._pos + n) % self.capacity)
            self._size = int(min(self._size + n, self.capacity))
            self._total_added += n
        return idx

    def add(self, obs, action, reward, next_obs, discount) -> np.ndarray:
        return self.add_batch(
            Transition(
                np.asarray(obs)[None],
                np.asarray(action)[None],
                np.asarray([reward]),
                np.asarray(next_obs)[None],
                np.asarray([discount]),
            )
        )

    def gather(self, idx: np.ndarray) -> Mapping[str, np.ndarray]:
        decode = (
            self._decode_obs if self._decode_on_sample else (lambda x: x)
        )
        with self._lock:
            return {
                "obs": decode(self.obs[idx]),
                "action": self.action[idx],
                "reward": self.reward[idx],
                "next_obs": decode(self.next_obs[idx]),
                "discount": self.discount[idx],
            }

    def sample(self, batch_size: int, rng: np.random.Generator):
        """Uniform sample of stacked arrays (reference ``replay_memory.py:61-80``)."""
        idx = rng.integers(0, self._size, size=batch_size)
        return self.gather(idx)

    # ------------------------------------------------------------- snapshot
    def _snapshot_arrays(self) -> dict:
        """Stored rows in ring order [0, size) as LIVE VIEWS. The caller
        MUST hold self._lock and copy every value before releasing it."""
        n = self._size
        return {
            "obs": self.obs[:n],
            "action": self.action[:n],
            "reward": self.reward[:n],
            "next_obs": self.next_obs[:n],
            "discount": self.discount[:n],
            "pos": np.asarray(self._pos),
            "size": np.asarray(n),
        }

    def snapshot(self, path: str) -> None:
        """Write the buffer contents to ``path`` (.npz, atomic via rename).

        The reference checkpoints nothing but network weights (SURVEY.md §5
        'checkpoint/resume'); without this, --resume restarts with an empty
        replay and repays the whole warmup in fresh interaction.
        """
        with self._lock:
            # Real copies: collector threads keep mutating the live arrays
            # while the (seconds-long) compression below runs unlocked.
            data = {k: np.array(v, copy=True) for k, v in self._snapshot_arrays().items()}
        tmp = f"{path}.tmp.npz"  # savez appends .npz unless present
        # Uncompressed: replay rows are high-entropy floats (deflate gains
        # ~10%) and compression stalls the learner for minutes at 1M rows.
        np.savez(tmp, **data)
        os.replace(tmp, path)

    def _restore_arrays(self, data) -> int:
        n = int(np.asarray(data["size"]).item())
        if n > self.capacity:
            raise ValueError(
                f"snapshot holds {n} rows > capacity {self.capacity}; "
                "raise --rmsize to restore it"
            )
        if data["obs"].shape[1] != self.obs.shape[1]:
            raise ValueError("snapshot obs_dim does not match this buffer")
        self.obs[:n] = data["obs"]
        self.action[:n] = data["action"]
        self.reward[:n] = data["reward"]
        self.next_obs[:n] = data["next_obs"]
        self.discount[:n] = data["discount"]
        # Every row changed identity: invalidate any generation stamps
        # captured by samples taken before the restore.
        self._gen += 1
        self._size = n
        # Same capacity → resume the saved write head so FIFO eviction order
        # survives a wrapped ring; different capacity → data sits at [0, n).
        saved_pos = int(np.asarray(data["pos"]).item())
        self._pos = saved_pos if n == self.capacity else n % self.capacity
        # Re-derive the lifetime counter so (total_added % capacity) ==
        # _pos and min(total_added, capacity) == _size keep holding — the
        # two invariants the device-ring mirror's slot math rests on. A
        # fresh mirror (synced=0) then resyncs the whole restored buffer.
        self._total_added = (
            self._pos + self.capacity if n == self.capacity else n
        )
        return n

    def restore(self, path: str) -> int:
        """Load a :meth:`snapshot`; returns the number of rows restored."""
        with np.load(path, allow_pickle=False) as data:
            with self._lock:
                return self._restore_arrays(data)
