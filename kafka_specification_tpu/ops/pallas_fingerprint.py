"""Pallas TPU kernel: fused murmur3 fingerprinting of packed state rows.

The per-candidate hot path of a BFS level hashes M ~ 10^6-10^7 rows of K
uint32 lanes twice (hi/lo seeds).  XLA already fuses the jnp implementation
(ops/fingerprint.py) well; this Pallas version exists to (a) keep both hash
streams and the sentinel masking in one VMEM-resident pass over the
candidate matrix, and (b) serve as the template for further Pallas work on
the dedup pipeline.  It is opt-in (`use_pallas=True` / KSPEC_USE_PALLAS=1)
and bit-identical to the jnp path — the test suite runs it in interpret
mode on CPU and compares exactly.

Grid: 1-D over row blocks of `block_rows`; each program hashes its block's
K lanes with both seeds and applies the invalid->sentinel mask.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from .fingerprint import hash_pair
from . import dedup


def _kernel(lanes_ref, valid_ref, hi_ref, lo_ref):
    # one authoritative hash implementation: the kernel body is plain jnp
    # over the VMEM-resident block, so it reuses ops.fingerprint directly
    # (including the sentinel-collision remap)
    lanes = lanes_ref[...]  # [block, K] uint32
    valid = valid_ref[...]  # [block] bool
    sent = jnp.uint32(dedup.SENT)
    hi, lo = hash_pair(lanes)
    hi_ref[...] = jnp.where(valid, hi, sent)
    lo_ref[...] = jnp.where(valid, lo, sent)


@functools.partial(jax.jit, static_argnames=("block_rows", "interpret"))
def fingerprint_pallas(lanes, valid, block_rows: int = 1024, interpret: bool = False):
    """uint32[M, K] x bool[M] -> (hi, lo) uint32[M] with invalid -> sentinel.

    M must be a multiple of block_rows (the engine's buffers are powers of
    two).  interpret=True runs the kernel in Pallas interpret mode (CPU CI).
    """
    m, k = lanes.shape
    assert m % block_rows == 0, (m, block_rows)
    grid = (m // block_rows,)
    return pl.pallas_call(
        _kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((block_rows, k), lambda i: (i, 0)),
            pl.BlockSpec((block_rows,), lambda i: (i,)),
        ],
        out_specs=[
            pl.BlockSpec((block_rows,), lambda i: (i,)),
            pl.BlockSpec((block_rows,), lambda i: (i,)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((m,), jnp.uint32),
            jax.ShapeDtypeStruct((m,), jnp.uint32),
        ],
        interpret=interpret,
        name="kspec_fingerprint",
    )(lanes, valid)
