"""Fixed-width bit packing of model states into uint32 lanes.

A model checker dedups states by identity, so the tensor encoding of a state
must be *canonical*: one TLA+ state <-> exactly one bit pattern.  The models
guarantee canonical field values (e.g. `TruncateTo` Nil-fills truncated log
slots, /root/reference/FiniteReplicatedLog.tla:105-109, so unwritten slots are
always Nil); this module guarantees a canonical bit layout.

Each field is an integer tensor with a known inclusive value range
[lo, hi].  Values are stored biased (v - lo) in ceil(log2(hi-lo+1)) bits.
Elements never straddle a lane boundary (the packer pads instead), which keeps
pack/unpack shifts, masks and static slices — friendly to XLA fusion on TPU.
"""

from __future__ import annotations

import math
from dataclasses import dataclass
from typing import Sequence

import jax.numpy as jnp
import numpy as np


@dataclass(frozen=True)
class Field:
    """One state variable: an integer tensor of `shape` with values in [lo, hi]."""

    name: str
    shape: tuple[int, ...]
    lo: int
    hi: int

    def __post_init__(self):
        assert self.hi >= self.lo, (self.name, self.lo, self.hi)

    @property
    def width(self) -> int:
        span = self.hi - self.lo + 1
        return max(1, math.ceil(math.log2(span)))

    @property
    def num_elements(self) -> int:
        return int(np.prod(self.shape, dtype=np.int64)) if self.shape else 1


class StateSpec:
    """Bit-layout codec for a tuple of Fields -> uint32[num_lanes].

    pack/unpack are vectorizable (jax.vmap) and jit-friendly: the layout is
    computed once in Python; at trace time packing is a sum a lane over the
    static slice of shifted values the lane holds, and unpacking a broadcast
    of each lane over its elements + shift + mask.  `unpack_rows` is
    `unpack`'s numpy twin over a batch of host rows.
    """

    def __init__(self, fields: Sequence[Field], force_hashed: bool = False):
        self.fields = tuple(fields)
        self._force_hashed = force_hashed
        names = [f.name for f in self.fields]
        assert len(set(names)) == len(names), "duplicate field names"

        lane_ids, shifts, widths, los = [], [], [], []
        lane, bit = 0, 0
        lane_bits = {}
        for f in self.fields:
            w = f.width
            assert w <= 32, f"field {f.name} needs {w} bits > 32"
            for _ in range(f.num_elements):
                if bit + w > 32:  # never straddle a lane
                    lane, bit = lane + 1, 0
                lane_ids.append(lane)
                shifts.append(bit)
                widths.append(w)
                los.append(f.lo)
                bit += w
                lane_bits[lane] = bit
        self.num_lanes = lane + 1 if bit > 0 else lane
        # a state can only pack to the all-ones sentinel pair (the dedup
        # empty-slot marker, ops/dedup.SENT == ops/hashset.SENT) if every
        # lane is completely full of field bits (pad bits are always 0) AND
        # every field's biased span actually reaches its all-ones bit
        # pattern (a span < 2^width leaves the top pattern unrepresentable);
        # with a single lane the exact fingerprint's hi word is constant 0,
        # so the sentinel pair is unreachable regardless
        spans_full = all(
            f.hi - f.lo + 1 == (1 << f.width) for f in self.fields
        )
        self._may_hit_sentinel = (
            self.num_lanes == 2
            and all(lane_bits.get(i, 0) == 32 for i in range(self.num_lanes))
            and spans_full
        )
        self.total_bits = sum(widths)
        self._lane_ids = np.asarray(lane_ids, np.int32)
        self._shifts = np.asarray(shifts, np.uint32)
        self._masks = np.asarray([(1 << w) - 1 for w in widths], np.uint32)
        self._los = np.asarray(los, np.int32)
        self._num_elements = len(lane_ids)
        # the flat elements of lane k are [lane_starts[k], lane_starts[k+1])
        self._lane_starts = np.searchsorted(
            self._lane_ids, np.arange(self.num_lanes + 1)
        )
        # per-field slices into the flat element vector
        self._field_slices = {}
        ofs = 0
        for f in self.fields:
            self._field_slices[f.name] = (ofs, ofs + f.num_elements, f.shape)
            ofs += f.num_elements
        # True iff the whole state fits in 64 bits -> fingerprints can be
        # exact (collision-free dedup).  Demoted to hashed when a state could
        # pack to the all-ones dedup sentinel (only if every lane is exactly
        # full — never the case for the corpus encodings).  force_hashed
        # exists so tests can exercise the hashed mode on small states.
        self.exact64 = (
            self.num_lanes <= 2 and not force_hashed and not self._may_hit_sentinel
        )

    # -- flat <-> struct -------------------------------------------------------

    def _flatten(self, state: dict) -> jnp.ndarray:
        parts = []
        for f in self.fields:
            v = jnp.asarray(state[f.name], jnp.int32).reshape(-1)
            parts.append(v)
        return jnp.concatenate(parts) if len(parts) > 1 else parts[0]

    def _unflatten(self, flat: jnp.ndarray) -> dict:
        out = {}
        for f in self.fields:
            a, b, shape = self._field_slices[f.name]
            v = flat[a:b].reshape(shape) if shape else flat[a]
            out[f.name] = v
        return out

    # -- public API ------------------------------------------------------------

    def pack(self, state: dict) -> jnp.ndarray:
        """dict of int32 tensors -> uint32[num_lanes]. vmap over leading axes."""
        flat = self._flatten(state)
        biased = (flat - self._los).astype(jnp.uint32) & self._masks
        shifted = biased << self._shifts
        # widths don't overlap within a lane, so sum == bitwise-or; the
        # lane table is a constant, so each lane sums its own static slice
        # (a scatter-add by lane id is a scatter a row under vmap)
        starts = self._lane_starts
        return jnp.stack(
            [
                jnp.sum(shifted[starts[k]: starts[k + 1]], dtype=jnp.uint32)
                for k in range(self.num_lanes)
            ]
        )

    def unpack(self, lanes: jnp.ndarray) -> dict:
        """uint32[num_lanes] -> dict of int32 tensors. vmap over leading axes."""
        starts = self._lane_starts
        # each lane broadcast over its own elements (static slices of the
        # constant lane table; indexing by it is a gather a row under vmap)
        spread = jnp.concatenate(
            [
                jnp.broadcast_to(lanes[k], (int(starts[k + 1] - starts[k]),))
                for k in range(self.num_lanes)
            ]
        )
        vals = (spread >> self._shifts) & self._masks
        flat = vals.astype(jnp.int32) + self._los
        return self._unflatten(flat)

    def unpack_rows(self, rows: np.ndarray) -> dict:
        """uint32[n, num_lanes] ON THE HOST -> dict of int32 numpy arrays
        with a leading n: `unpack`'s integers for any bit pattern, in numpy
        (the verdict path decodes a trace's rows with it; no device work)."""
        rows = np.asarray(rows, np.uint32)
        vals = (rows[:, self._lane_ids] >> self._shifts) & self._masks
        flat = vals.astype(np.int32) + self._los
        return {
            name: flat[:, a:b].reshape((len(rows),) + shape)
            for name, (a, b, shape) in self._field_slices.items()
        }

    def validate(self, state: dict) -> jnp.ndarray:
        """True iff every element is within its declared [lo, hi] range."""
        ok = jnp.bool_(True)
        for f in self.fields:
            v = jnp.asarray(state[f.name])
            ok = ok & jnp.all(v >= f.lo) & jnp.all(v <= f.hi)
        return ok
