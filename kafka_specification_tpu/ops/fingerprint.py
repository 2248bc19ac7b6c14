"""64-bit state fingerprints as (hi, lo) uint32 pairs.

TPUs have no native 64-bit integer ALU, so fingerprints are carried as two
uint32 lanes everywhere (sorting via lexsort on the pair, membership via a
pairwise binary search — see ops.dedup).  This replaces TLC's FP64 fingerprint
set (the external Java engine the reference corpus relies on).

Two modes:
- exact: when the packed state fits in <= 64 bits, the fingerprint IS the
  state — dedup is collision-free and distinct-state counts are exact by
  construction (used by the small configs the golden tests pin down).
- hashed: murmur3-style mixing of the uint32 lanes with two different seeds.
  Collision risk for n states is ~n^2/2^65, the same regime TLC accepts.
"""

from __future__ import annotations

import jax.numpy as jnp

# plain ints (not jnp scalars): a module-level jnp constant would initialize
# the default JAX backend at import time
_C1 = 0xCC9E2D51
_C2 = 0x1B873593
SEED_HI = 0x9747B28C
SEED_LO = 0x3C6EF372


def _rotl32(x, r):
    return (x << r) | (x >> (32 - r))


def _fmix32(h):
    h = h ^ (h >> 16)
    h = h * jnp.uint32(0x85EBCA6B)
    h = h ^ (h >> 13)
    h = h * jnp.uint32(0xC2B2AE35)
    h = h ^ (h >> 16)
    return h


def _murmur3_lanes(lanes: jnp.ndarray, seed: int) -> jnp.ndarray:
    """murmur3_x86_32 over the trailing lane axis. lanes: uint32[..., K]."""
    k = lanes.shape[-1]
    c1, c2 = jnp.uint32(_C1), jnp.uint32(_C2)
    h = jnp.full(lanes.shape[:-1], seed, jnp.uint32)
    for i in range(k):
        kx = lanes[..., i] * c1
        kx = _rotl32(kx, 15) * c2
        h = h ^ kx
        h = _rotl32(h, 13) * jnp.uint32(5) + jnp.uint32(0xE6546B64)
    return _fmix32(h ^ jnp.uint32(4 * k))


def hash_pair(lanes: jnp.ndarray) -> tuple[jnp.ndarray, jnp.ndarray]:
    """Hashed-mode fingerprint pair.

    The all-ones pair is the dedup padding sentinel: a valid state hashing
    to it would be indistinguishable from padding and silently *dropped*
    (worse than an ordinary collision, which merely conflates two states),
    so it is remapped to a reserved neighbour (~n*2^-64 probability per
    state; costs at most one extra ordinary collision).
    """
    hi = _murmur3_lanes(lanes, SEED_HI)
    lo = _murmur3_lanes(lanes, SEED_LO)
    sent = jnp.uint32(0xFFFFFFFF)
    is_sent = (hi == sent) & (lo == sent)
    lo = jnp.where(is_sent, jnp.uint32(0xFFFFFFFE), lo)
    return hi, lo


def fingerprint_lanes(lanes: jnp.ndarray, exact: bool) -> tuple[jnp.ndarray, jnp.ndarray]:
    """uint32[..., K] packed states -> (hi, lo) uint32 fingerprints."""
    if exact:
        k = lanes.shape[-1]
        lo = lanes[..., 0]
        hi = lanes[..., 1] if k > 1 else jnp.zeros_like(lo)
        return hi, lo
    return hash_pair(lanes)
