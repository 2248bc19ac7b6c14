"""Orbit keys: a state's identity under a model's SYMMETRY.

TLC's ``SYMMETRY`` over ``Permutations(S)`` makes two states one iff some
permutation of the constant set ``S`` maps one to the other.  The engine
keys every candidate by its ORBIT instead of by itself: the key is the
64-bit fingerprint of the orbit's canonical image, the LEAST packed image
over ALL ``|S|!`` permutations (lanes compared as unsigned words, the last
lane the most significant).  An exact function of the orbit, so no orbit
is ever split: no signature, no pruning, every image is formed.  The same
pass counts the images equal to the least one, which is the order of the
state's stabiliser, so the orbit's size ``|S|! / stabiliser`` comes free.
Rows themselves flow on unchanged (the stored member of an orbit is the
successor as generated, so every stored row is a true successor of its
parent row and a counterexample is a behaviour of the unreduced spec).

How one image is formed (:class:`Canon`, tables built once per model from
``Model.symmetry``'s field roles, ``models/base.py``):

- the packed row is unpacked to its flat element vector, biased values,
  one element a sublane and a block of rows along the lanes (``[E, B]``);
- ``axis`` roles move elements: output element ``e`` is source element
  ``src[g][e]`` (one row gather by the ``[|G|, E]`` table);
- ``member`` values map ``v -> g[v]`` (``N`` compare-selects; sentinels
  below zero are fixed), ``mask`` values move bit ``j`` to bit ``g[j]``
  (``N`` shift-ands); ``g`` is a row of the ``[|G|, N]`` table;
- the image is packed with the spec's own shifts.

``G`` is a DATA axis: one ``fori_loop`` over the tables' rows, its body
traced once.  The stage costs per LIVE candidate: the valid lanes are
numbered, and a rolled loop over fixed blocks of them, whose trip count is
a device value, gathers a block's rows, reduces its images and scatters
the keys back (the idiom of ``pipeline.novel_stage``); whole WIDE blocks
first where the width holds them (:data:`CANON_WIDE`).

The oracle twin of this reduction (models/kafka_replication.py
``o_canonical``) is written on the TLA-level state and shares nothing with
this module; tests/test_symmetry.py holds the two to each other.
"""

from __future__ import annotations

from itertools import permutations

import jax
import jax.numpy as jnp
import numpy as np

from .dedup import even_block
from .fingerprint import fingerprint_lanes

SENT = 0xFFFFFFFF  # ops/dedup.SENT (the masked lanes' fingerprint pair)

#: The most rows one iteration of the block loop canonicalises (the block
#: is a shape, :func:`canon_block`; how many blocks run is a device value).
#: Timed on a TPU v5e, the stage alone (PERF.md section 6, PR 38), 5
#: brokers (120 images of 56 elements in 5 lanes), 131,072 lanes, us a
#: live row at blocks of 1,024 / 2,048 / 4,096 / 8,192: a third of the
#: lanes live 0.556 / 0.348 / 0.239 / 0.192; every lane live 0.522 / 0.307
#: / 0.205 / 0.149.
CANON_BLOCK = 8192

#: A WIDE block is this many blocks' rows.  While a whole wide block of
#: live rows is left the loop takes one; the blocks of :func:`canon_block`
#: finish the rest, so a width that holds few live rows pays for a block
#: and one that holds hundreds of thousands runs a quarter of the
#: iterations: a block of B rows costs a + b B (a = ~0.37 ms, the 120
#: launches of the group loop's operations; b = ~0.097 us a row: the stage
#: alone at 3,702,784 lanes, 330,000 live: 48.2 ms in 41 blocks of 8,192,
#: 37.1 ms in 11 of 32,768; PERF.md section 6, PR 47).  A width under two
#: wide blocks compiles no wide loop and lowers as it did without one.
CANON_WIDE = 4


def canon_block(T: int) -> int:
    """The block of a width: ``dedup.even_block`` at :data:`CANON_BLOCK`."""
    return even_block(T, CANON_BLOCK)


def canon_wide_block(T: int) -> int:
    """The wide block of a width, 0 where the width holds fewer than two."""
    W = CANON_WIDE * canon_block(T)
    return W if CANON_WIDE > 1 and T >= 2 * W else 0


class Canon:
    """The canonicalisation kernel of one (spec, symmetry)."""

    def __init__(self, spec, symmetry):
        self.spec = spec
        self.symmetry = symmetry
        n = self.n = symmetry.n
        unknown = set(symmetry.roles) - {f.name for f in spec.fields}
        assert not unknown, f"symmetry roles for unknown fields {unknown}"
        # the spec's flat element vector, field by field in pack order
        kinds, los = [], []  # per element
        src_of = []  # per element: (axis position or None, key of its group)
        for f in spec.fields:
            role = symmetry.roles.get(f.name)
            shape = f.shape or ()
            for flat in range(f.num_elements):
                idx = np.unravel_index(flat, shape) if shape else ()
                kinds.append(role.value if role else None)
                los.append(f.lo)
                if role is not None and role.axis is not None:
                    assert shape[role.axis] == n, (f.name, shape, n)
                    rest = tuple(x for a, x in enumerate(idx)
                                 if a != role.axis)
                    src_of.append((int(idx[role.axis]), (f.name, rest)))
                else:
                    src_of.append((None, None))
            if role is not None and role.value == "member":
                # members are 0..n-1; whatever lies below is a sentinel
                assert f.hi == n - 1 and f.lo <= 0, (f.name, f.lo, f.hi)
            if role is not None and role.value == "mask":
                assert f.lo == 0 and f.hi == (1 << n) - 1, (f.name, f.lo, f.hi)
        E = self.E = len(kinds)
        # internal element order: plain, then member-valued, then masks, so
        # the two value maps each read and write one contiguous slab
        order = ([e for e in range(E) if kinds[e] is None]
                 + [e for e in range(E) if kinds[e] == "member"]
                 + [e for e in range(E) if kinds[e] == "mask"])
        self.n_member = sum(k == "member" for k in kinds)
        self.n_mask = sum(k == "mask" for k in kinds)
        self.n_plain = E - self.n_member - self.n_mask
        order = np.asarray(order, np.int32)
        where = np.empty(E, np.int32)  # spec element -> internal position
        where[order] = np.arange(E, dtype=np.int32)
        self._lane_ids = spec._lane_ids[order]
        self._shifts = spec._shifts[order].astype(np.uint32)
        self._masks = spec._masks[order].astype(np.uint32)
        self._member_lo = np.asarray(
            [los[e] for e in order[self.n_plain:self.n_plain + self.n_member]],
            np.int32)
        group = {}
        for e, (pos, key) in enumerate(src_of):
            if key is not None:
                group[key + (pos,)] = e
        perms = np.asarray(list(permutations(range(n))), np.int32)  # g[i]
        G = self.G = perms.shape[0]
        src = np.tile(np.arange(E, dtype=np.int32), (G, 1))
        for gi, g in enumerate(perms):
            inv = np.argsort(g)
            for e, (pos, key) in enumerate(src_of):
                if key is not None:
                    # slot g(i) of the image holds slot i of the state
                    src[gi, where[e]] = where[group[key + (int(inv[pos]),)]]
        self.perms = perms
        self.src = src

    # -- one block -----------------------------------------------------------

    def _unpack(self, rows):  # kspec: traced
        """rows u32[B, K] -> biased elements u32[E, B], internal order."""
        lanes = rows.T  # [K, B]
        return ((lanes[self._lane_ids] >> self._shifts[:, None])
                & self._masks[:, None])

    def _image(self, x, g, src):  # kspec: traced
        """The packed image of a block under one permutation: elements
        x u32[E, B], g i32[N] (``g[i]`` the image of member ``i``), src
        i32[E] -> K lanes, each u32[B]."""
        n, a, b = self.n, self.n_plain, self.n_plain + self.n_member
        y = jnp.take(x, src, axis=0)
        parts = [y[:a]]
        if self.n_member:
            v = y[a:b]
            lo = jnp.asarray(-self._member_lo, jnp.uint32)[:, None]  # bias
            out = v
            for j in range(n):
                out = jnp.where(v == lo + jnp.uint32(j),
                                lo + g[j].astype(jnp.uint32), out)
            parts.append(out)
        if self.n_mask:
            m = y[b:]
            out = jnp.zeros_like(m)
            for j in range(n):
                out = out | (((m >> jnp.uint32(j)) & jnp.uint32(1))
                             << g[j].astype(jnp.uint32))
            parts.append(out)
        w = jnp.concatenate(parts, axis=0) << self._shifts[:, None]
        lanes = []
        for k in range(self.spec.num_lanes):
            rows_k = np.flatnonzero(self._lane_ids == k)
            lane = w[int(rows_k[0])]
            for e in rows_k[1:]:
                lane = lane | w[int(e)]
            lanes.append(lane)
        return lanes

    def images(self, rows):  # kspec: traced
        """Every image of every row: u32[B, K] -> u32[|G|, B, K], in the
        order of :attr:`perms` (what the tests hold to the oracle's)."""
        x = self._unpack(rows)
        perms, src = jnp.asarray(self.perms), jnp.asarray(self.src)
        return jax.lax.map(
            lambda gs: jnp.stack(self._image(x, gs[0], gs[1]), axis=-1),
            (perms, src),
        )

    def least(self, rows):  # kspec: traced
        """rows u32[B, K] -> (the least image of each row u32[B, K], the
        images equal to it i32[B]: the stabiliser's order)."""
        K = self.spec.num_lanes
        x = self._unpack(rows)
        perms, src = jnp.asarray(self.perms), jnp.asarray(self.src)

        def body(gi, carry):
            best, hits = carry
            img = self._image(x, perms[gi], src[gi])
            lt = jnp.zeros(hits.shape, bool)
            eq = jnp.ones(hits.shape, bool)
            for k in reversed(range(K)):
                lt = lt | (eq & (img[k] < best[k]))
                eq = eq & (img[k] == best[k])
            best = tuple(jnp.where(lt, img[k], best[k]) for k in range(K))
            hits = jnp.where(lt, 1, hits + eq.astype(jnp.int32))
            return best, hits

        start = (tuple(rows[:, k] for k in range(K)),
                 jnp.zeros((rows.shape[0],), jnp.int32))
        best, hits = jax.lax.fori_loop(0, self.G, body, start)
        return jnp.stack(best, axis=-1), hits

    # -- the stage -----------------------------------------------------------

    def keys(self, cand, valid):  # kspec: traced
        """The body of the ``canon`` stage: candidates u32[T, K], their
        mask -> (hi u32[T], lo u32[T], orbit i32[T], rows i32): the orbit's
        fingerprint pair of every valid lane (the sentinel pair elsewhere),
        its orbit's size (0 elsewhere), and the rows whose images the stage
        formed (blocks run x block size: a device count)."""
        T, K = cand.shape
        B, W = canon_block(T), canon_wide_block(T)
        sent = jnp.uint32(SENT)
        n_live = jnp.sum(valid, dtype=jnp.int32)
        pos = jnp.cumsum(valid, dtype=jnp.int32) - 1
        lane_of = jnp.zeros((T,), jnp.int32).at[
            jnp.where(valid, pos, T)].set(
                jnp.arange(T, dtype=jnp.int32), mode="drop")

        def run(size, first, count, outs):
            """`count` blocks of `size` live rows, from live row `first`
            (None: from the first one)."""

            def block(k, outs):
                hi, lo, orbit = outs
                s = k * size if first is None else first + k * size
                s = jnp.minimum(s, T - size)
                at = jax.lax.dynamic_slice(lane_of, (s,), (size,))
                keep = (s + jnp.arange(size, dtype=jnp.int32)) < n_live
                best, hits = self.least(cand[at])
                b_hi, b_lo = fingerprint_lanes(best, self.spec.exact64)
                # the overlap writes the same lanes again; dead rows drop
                to = jnp.where(keep, at, T)
                return (hi.at[to].set(b_hi, mode="drop"),
                        lo.at[to].set(b_lo, mode="drop"),
                        orbit.at[to].set(self.G // jnp.maximum(hits, 1),
                                         mode="drop"))

            return jax.lax.fori_loop(0, count, block, outs)

        # whole wide blocks first, then the rest in blocks of B
        done = (n_live // W) * W if W else None
        blocks = ((n_live if done is None else n_live - done) + (B - 1)) // B
        outs = (jnp.full((T,), sent), jnp.full((T,), sent),
                jnp.zeros((T,), jnp.int32))
        if done is not None:
            outs = run(W, None, done // W, outs)
        hi, lo, orbit = run(B, done, blocks, outs)
        rows = blocks * B
        return hi, lo, orbit, rows if done is None else done + rows


def canon_of(model) -> Canon:
    """The model's kernel, built once and kept on the model object beside
    its step cache."""
    got = getattr(model, "_canon", None)
    if got is None or got.symmetry is not model.symmetry:
        got = Canon(model.spec, model.symmetry)
        try:
            model._canon = got
        except AttributeError:
            pass
    return got
