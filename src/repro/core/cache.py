"""Patch-level cache manager (paper §5).

One ``PatchCache`` per diffusion block. The control plane (uid<->slot map,
Common/New/Expired set partition, paper Fig. 11) is host-side — it mirrors
the paper's CPU-side coalescing and runs concurrently with device compute in
the engine. The data plane (reuse-mask computation, batched store
update/query) is one gather/scatter per block step, jitted.

Semantics (paper Fig. 10):
  (1) the Cache Reuse Predictor compares the incoming input against the
      cached input from the previous *compute* and emits a per-patch mask;
  (2) masked (reusable) patches take the cached output;
  (3) unmasked patches are recomputed and their (input, output) re-cached;
  (4) uids seen in the cache but not in the batch have exited -> Expired,
      their slots are freed (no preemption, so exit is final).
``update_input_on_reuse=False`` keeps the cached input anchored at the last
actual compute so the drift test bounds the *cumulative* error (the paper's
"cumulative errors" note on Fig. 19).
"""
from __future__ import annotations

import functools
from dataclasses import dataclass
from typing import Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np


@functools.partial(jax.jit, static_argnames=())
def _rel_delta(x: jax.Array, cached: jax.Array) -> jax.Array:
    """Per-patch relative MSE between input and cached input. (P,...)->(P,)"""
    ax = tuple(range(1, x.ndim))
    num = jnp.mean(jnp.square(x.astype(jnp.float32)
                              - cached.astype(jnp.float32)), axis=ax)
    den = jnp.mean(jnp.square(cached.astype(jnp.float32)), axis=ax) + 1e-8
    return num / den


@jax.jit
def _gather(store: jax.Array, slots: jax.Array) -> jax.Array:
    return store[slots]


@jax.jit
def _scatter_where(store: jax.Array, slots: jax.Array, values: jax.Array,
                   mask: jax.Array) -> jax.Array:
    """store[slots] = values where mask; single batched scatter."""
    prev = store[slots]
    sel = jnp.where(mask.reshape((-1,) + (1,) * (values.ndim - 1)),
                    values, prev)
    return store.at[slots].set(sel)


@dataclass
class SyncResult:
    slots: np.ndarray          # (P,) int32 slot per uid
    is_new: np.ndarray         # (P,) bool — no cached entry (must compute)
    n_common: int
    n_new: int
    n_expired: int


class PatchCache:
    """Fixed-capacity device cache for one block: cached inputs + outputs.

    Stores are allocated lazily on first update — a block's output shape may
    differ from its input shape (channel/spatial-changing blocks)."""

    def __init__(self, capacity: int, item_shape: Tuple[int, ...] = None,
                 dtype=jnp.float32, update_input_on_reuse: bool = False):
        self.capacity = capacity
        self.store_in: Optional[jax.Array] = None
        self.store_out: Optional[jax.Array] = None
        if item_shape is not None:
            self.store_in = jnp.zeros((capacity,) + tuple(item_shape), dtype)
            self.store_out = jnp.zeros((capacity,) + tuple(item_shape), dtype)
        self.uid_to_slot: Dict[int, int] = {}
        self._free: List[int] = list(range(capacity - 1, -1, -1))
        self.update_input_on_reuse = update_input_on_reuse

    # ---------------- control plane (host) ----------------

    def sync(self, uids: Sequence[int]) -> SyncResult:
        """Partition into Common/New/Expired and resolve slots (Fig. 11)."""
        uids = list(int(u) for u in uids)
        current = set(uids)
        expired = [u for u in self.uid_to_slot if u not in current]
        for u in expired:                       # (4) delete
            self._free.append(self.uid_to_slot.pop(u))
        slots = np.empty(len(uids), np.int32)
        is_new = np.zeros(len(uids), bool)
        n_new = 0
        for j, u in enumerate(uids):
            s = self.uid_to_slot.get(u)
            if s is None:                       # (3) insert
                if not self._free:
                    raise RuntimeError("patch cache capacity exceeded")
                s = self._free.pop()
                self.uid_to_slot[u] = s
                is_new[j] = True
                n_new += 1
            slots[j] = s
        return SyncResult(slots=slots, is_new=is_new,
                          n_common=len(uids) - n_new, n_new=n_new,
                          n_expired=len(expired))

    # ---------------- data plane (device) ----------------

    def reuse_mask(self, x: jax.Array, sync: SyncResult, predictor) -> jax.Array:
        """(1) per-patch reuse decision; new entries always compute."""
        if self.store_in is None or self.store_out is None:
            return jnp.zeros((len(sync.slots),), bool)
        slots = jnp.asarray(sync.slots)
        delta = _rel_delta(x, _gather(self.store_in, slots))
        mask = predictor(delta)
        return mask & ~jnp.asarray(sync.is_new)

    def cached_outputs(self, sync: SyncResult) -> jax.Array:
        return _gather(self.store_out, jnp.asarray(sync.slots))

    def cached_inputs(self, sync: SyncResult) -> jax.Array:
        return _gather(self.store_in, jnp.asarray(sync.slots))

    def update(self, sync: SyncResult, x: jax.Array, y: jax.Array,
               computed: jax.Array) -> None:
        """(5) re-cache computed entries (one scatter per store)."""
        if self.store_in is None:
            self.store_in = jnp.zeros((self.capacity,) + x.shape[1:], x.dtype)
        if self.store_out is None:
            self.store_out = jnp.zeros((self.capacity,) + y.shape[1:], y.dtype)
        slots = jnp.asarray(sync.slots)
        in_mask = computed | bool(self.update_input_on_reuse)
        self.store_in = _scatter_where(self.store_in, slots, x,
                                       jnp.asarray(in_mask))
        self.store_out = _scatter_where(self.store_out, slots, y,
                                        jnp.asarray(computed))


def bucket_size(n: int, ladder: Sequence[int] = (0, 8, 16, 32, 64, 128, 256,
                                                 512, 1024, 2048, 4096)) -> int:
    """Pad dynamic unmasked-counts to a small static ladder (bounded compile
    set — the JAX-serving adaptation, docs/ARCHITECTURE.md §4)."""
    for b in ladder:
        if n <= b:
            return b
    return int(2 ** np.ceil(np.log2(max(n, 1))))


def masked_block_apply(block_fn, patches: jax.Array, reuse: np.ndarray,
                       cached_out: jax.Array,
                       fill_inputs: Optional[jax.Array] = None) -> Tuple[jax.Array, int]:
    """Run block_fn only on non-reused patches, bucket-padded.

    block_fn must be pixel-wise (shape-preserving, per-patch independent).
    Context-dependent blocks instead run dense with cache-filled inputs
    (paper §5.1) — handled by the engine, not here.
    Returns (outputs (P,...), bucket) where reused rows take cached_out.
    """
    reuse = np.asarray(reuse)
    idx = np.nonzero(~reuse)[0]
    n = len(idx)
    if n == 0:
        return cached_out, 0
    b = bucket_size(n)
    pad_idx = np.concatenate([idx, np.zeros(b - n, np.int64)])
    sub = patches[jnp.asarray(pad_idx)]
    if fill_inputs is not None:
        sub = sub  # pixel-wise blocks need no context fill
    out_sub = block_fn(sub)[:n]
    out = cached_out.at[jnp.asarray(idx)].set(out_sub)
    return out, b
