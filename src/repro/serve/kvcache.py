"""Paged/slotted KV pool for continuous batching.

The pool is the model's decode-state pytree with the batch axis
reinterpreted as ``n_slots`` fixed-size *pages*: one page = one request's
entire cache — KV runs for attention layers, ring buffers bounded by the
window for sliding-window layers, so ``gemma3``'s 5:1 local:global
pattern never holds more than ``window`` positions per local layer.  A
per-slot ``pos`` vector (``[n_slots] int32``) replaces the legacy scalar
position so every page advances independently.  Each attention
segment's pages are stacked as ``[n_layers, n_slots, W, n_kv, hd]``;
``decode_slots`` updates them in place, one row a slot per layer (see
``models.transformer.decode_step``), and leaves that layout as it is.
(The pool pytree carries whatever ``init_decode_state`` defines —
recurrent SSM/xLSTM states included — but only attention-only archs can
be *served* through it; see ``engine.pool_supported`` for why MoE and
recurrent blocks are gated to the legacy fixed-batch path.)

Device-side primitives (pure, jit-friendly, slot index traced so one
compile covers the pool's whole lifetime):

  * :func:`init_pool_state`  — the zeroed pool pytree;
  * :func:`write_slot`       — copy a single-request (B=1) state into a page;
  * :func:`reset_slot`       — retire a page (position back to 0).

Host-side bookkeeping lives in :class:`SlotAllocator`: a FIFO free list
plus occupancy accounting, deliberately free of any jax dependency so the
scheduler's admission logic is unit-testable without a device.
"""

from __future__ import annotations

from dataclasses import dataclass, field
from typing import List, Optional

import jax
import jax.numpy as jnp
from jax import lax

from repro.models import transformer as T


def init_pool_state(model_cfg, n_slots: int, max_seq_len: int) -> dict:
    """Zeroed pool: per-segment stacked caches + per-slot positions."""
    state = T.init_decode_state(model_cfg, n_slots, max_seq_len)
    state["pos"] = jnp.zeros((n_slots,), jnp.int32)
    return state


def write_slot(pool: dict, one: dict, slot) -> dict:
    """Install a single-request decode state (batch 1) into page ``slot``.

    ``one`` is a ``prefill``/``init_decode_state`` pytree with B=1 and a
    scalar ``pos``; cache leaves are ``[n_layers, 1, ...]`` and land at
    ``pool_leaf[:, slot]``.  ``slot`` may be a traced int32 scalar.
    """
    def put(dst, src):
        return lax.dynamic_update_slice_in_dim(
            dst, src.astype(dst.dtype), slot, axis=1)

    segments = [jax.tree.map(put, dseg, sseg)
                for dseg, sseg in zip(pool["segments"], one["segments"])]
    pos = pool["pos"].at[slot].set(jnp.asarray(one["pos"], jnp.int32))
    return {"segments": segments, "pos": pos}


def reset_slot(pool: dict, slot) -> dict:
    """Retire page ``slot``: position back to 0 (cache bytes are left in
    place — ``write_slot`` overwrites the whole page on reuse)."""
    return {"segments": pool["segments"],
            "pos": pool["pos"].at[slot].set(0)}


# ---------------------------------------------------------------------------
# Host-side slot accounting (no jax)
# ---------------------------------------------------------------------------

@dataclass
class SlotAllocator:
    """FIFO page allocator + occupancy counters for the scheduler."""

    n_slots: int
    free: List[int] = field(default_factory=list)
    #: cumulative (occupied slots summed over every decode step) — divide
    #: by ``decode_steps`` for mean occupancy
    occupancy_sum: int = 0
    decode_steps: int = 0
    peak_occupancy: int = 0
    total_inserts: int = 0

    def __post_init__(self):
        if not self.free:
            self.free = list(range(self.n_slots))

    @property
    def n_occupied(self) -> int:
        return self.n_slots - len(self.free)

    def acquire(self) -> Optional[int]:
        """Pop the oldest free page, or None when the pool is full."""
        if not self.free:
            return None
        self.total_inserts += 1
        slot = self.free.pop(0)
        self.peak_occupancy = max(self.peak_occupancy, self.n_occupied)
        return slot

    def release(self, slot: int) -> None:
        if not 0 <= slot < self.n_slots:
            raise ValueError(f"slot {slot} out of range [0, {self.n_slots})")
        if slot in self.free:
            raise ValueError(f"slot {slot} double-freed")
        self.free.append(slot)

    def tick(self) -> None:
        """Record one decode step's occupancy."""
        self.occupancy_sum += self.n_occupied
        self.decode_steps += 1

    @property
    def mean_occupancy(self) -> float:
        return self.occupancy_sum / max(self.decode_steps, 1)
