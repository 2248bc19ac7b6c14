"""End-to-end state-integrity defense: level digest chains + typed exits.

The resilience arc (crash resume, resource governance, fleet supervision)
defends the checker against failures that ANNOUNCE themselves.  Nothing
before this module defended the *verdict* against silent corruption: a
flipped bit in a frontier buffer, a torn spill run, or a garbled exchange
payload yields a confidently wrong "no violation" with no trace.  This
module is the detection layer:

- :class:`LevelDigestChain` — an always-on, order-invariant digest over
  each BFS level's new-state fingerprint multiset.  Per level it keeps
  ``(count, xor, sum)`` accumulators over the 64-bit fingerprints (XOR
  and wrapping sum are commutative, so chunk order, shard order, and
  pipeline choice cannot change the digest — the multiset is the
  engine-invariant object the bit-identity contract already pins), plus
  a splitmix64 hash-chain value linking every level to its predecessor.
  The chain is stamped into checkpoints and run manifests; resume and
  ``cli verify-checkpoint`` re-verify it offline, so a resumed run
  provably continues the *same* exploration and a CRC-consistent
  corrupted generation (one whose per-array checksums were recomputed
  after the corruption, or whose corruption happened before the write)
  is still flagged.

- :func:`fingerprint_rows` / :func:`digest_rows` — a bit-exact HOST twin
  of the engines' jax fingerprint kernel (``ops.fingerprint``), so host
  code (the digest fold over arena-assembled rows, the frontier verify
  at each level boundary, the tiny-chunk shadow oracle, the offline
  verifier) can recompute fingerprints without touching an accelerator.
  One native pass over the rows (``native/fpset.cpp`` ``rows_digest``)
  where the library loaded, the same arithmetic in numpy over blocks
  where it did not.  ``tests/test_fingerprint_native.py`` pins native ==
  numpy == jax on random rows.

- :class:`IntegrityError` + :data:`EXIT_INTEGRITY` (76) — the typed
  terminal.  The engines stamp the run manifest ``integrity-violation``
  and re-raise; the CLI maps it to exit 76 (one past the resource exit
  75, same sysexits-adjacent convention); the supervisor classifies it
  as restartable — the load path's chain validator skips corrupted
  generations, so a restart resumes from the newest *chain-verified*
  checkpoint generation automatically.

- :func:`checkpoint_chain_errors` — the jax-free validator shared by
  the resume path (``CheckpointStore(validators=...)``) and the offline
  ``cli verify-checkpoint``: chain linkage, per-level count agreement
  with the ``levels`` array, and (when the generation carries the full
  fingerprint set: ``host_fps`` dumps, ``vhi``/``vlo`` prefixes,
  ``hash_hi``/``hash_lo`` live slots) the cumulative multiset digest of
  the stored visited set against the chain's running total.

Must stay jax-free at import (the offline verifier and the supervisor
parent both run on boxes whose accelerator stack may be wedged).
"""

from __future__ import annotations

import os
from typing import Optional

import numpy as np

#: one past EXIT_RESOURCE_EXHAUSTED (75): "the run's state failed an
#: integrity check" — distinct from crashes (restart blindly) and from
#: resource exits (do NOT restart), because the correct supervisor policy
#: is its own: restart from the newest chain-verified generation.
EXIT_INTEGRITY = 76

ENV_DISABLE = "KSPEC_INTEGRITY"  # "0" disables every always-on check
ENV_SHADOW = "KSPEC_INTEGRITY_SHADOW"  # sampled shadow re-execution rate

_U64 = np.uint64
_MASK64 = _U64(0xFFFFFFFFFFFFFFFF)


class IntegrityError(RuntimeError):
    """Typed terminal: a state-integrity check failed — the run's data
    (not its progress) can no longer be trusted.  The engines convert it
    into an ``integrity-violation`` manifest stamp; the CLI maps it to
    :data:`EXIT_INTEGRITY`; the supervisor restarts from the newest
    chain-verified checkpoint generation (corrupted generations are
    skipped by the load-time chain validator)."""

    def __init__(self, site: str, detail: str = "", depth=None):
        self.site = site  # frontier | fpset | exchange | spill | ckpt |
        # shadow | storage | chain
        self.detail = detail
        self.depth = depth
        super().__init__(
            f"INTEGRITY_VIOLATION[{site}]"
            + (f" at level {depth}" if depth is not None else "")
            + (f": {detail}" if detail else "")
        )


def enabled() -> bool:
    """Always-on unless explicitly disabled (bench baselines, escape
    hatch); the kill switch is an env var so a production operator can
    flip it without a redeploy."""
    return os.environ.get(ENV_DISABLE, "1") != "0"


def shadow_rate(arg: Optional[float] = None) -> float:
    """Resolve the shadow re-execution sample rate: explicit arg >
    $KSPEC_INTEGRITY_SHADOW > 0 (off)."""
    if arg is not None:
        rate = float(arg)
    else:
        rate = float(os.environ.get(ENV_SHADOW) or "0")
    if not (0.0 <= rate <= 1.0):
        raise ValueError(f"integrity shadow rate must be in [0, 1], got {rate}")
    return rate


def sample_chunk(depth: int, start: int, rate: float) -> bool:
    """Deterministic chunk sampler: the same (depth, chunk-start) is
    sampled identically on every run and after every resume, so shadow
    re-execution never perturbs bit-identity contracts."""
    if rate <= 0.0:
        return False
    if rate >= 1.0:
        return True
    h = ((depth * 1000003 ^ start * 2654435761) * 0x9E3779B9) & 0xFFFFFFFF
    return h < rate * 4294967296.0


# --------------------------------------------------------------------------
# host twins of ops.fingerprint (bit-exact; pinned by tests)
# --------------------------------------------------------------------------

_C1 = np.uint32(0xCC9E2D51)
_C2 = np.uint32(0x1B873593)
_SEED_HI = np.uint32(0x9747B28C)
_SEED_LO = np.uint32(0x3C6EF372)
_SENT = np.uint32(0xFFFFFFFF)

#: rows a block of the numpy twin: its scratch arrays then stay in the
#: cache (1,075,905 rows x 15 lanes on the sandbox's CPU, PR 46: 0.07-0.08 s
#: at 16,384, 0.12 s at 4,096, 0.10 s at 65,536, 0.31-0.37 s over whole
#: columns as it was written first; the native pass 0.027 s)
_BLOCK = 16384


def _rotl32(x: np.ndarray, r: int, tmp: np.ndarray) -> None:
    """x <- rotl(x, r), in place, through the scratch `tmp`."""
    np.right_shift(x, np.uint32(32 - r), out=tmp)
    np.left_shift(x, np.uint32(r), out=x)
    np.bitwise_or(x, tmp, out=x)


def _xorshift32(x: np.ndarray, r: int, tmp: np.ndarray) -> None:
    """x <- x ^ (x >> r), in place, through the scratch `tmp`."""
    np.right_shift(x, np.uint32(r), out=tmp)
    np.bitwise_xor(x, tmp, out=x)


def _hashed_blocks(rows: np.ndarray, want_fps: bool):
    """The numpy twin of the hashed mode, ``uint32[n, K]`` -> ``(fps or
    None, (count, xor, sum))``: `_BLOCK` rows at a time, the lanes
    transposed once a block, each lane's ``kx`` computed once for both
    seeds, every operation ``out=`` into scratch."""
    n, k = rows.shape
    fps = np.empty(n, _U64) if want_fps else None
    b = min(n, _BLOCK)
    kx_s = np.empty((k, b), np.uint32)  # a lane a row, premixed
    tmp_s = np.empty((k, b), np.uint32)
    h_s = np.empty((2, b), np.uint32)  # [seed hi, seed lo]
    t_s = np.empty((2, b), np.uint32)
    fp_s = np.empty((2, b), _U64)
    x = s = 0
    with np.errstate(over="ignore"):
        for i in range(0, n, _BLOCK):
            m = min(_BLOCK, n - i)
            kx, h, t, fp = kx_s[:, :m], h_s[:, :m], t_s[:, :m], fp_s[:, :m]
            np.multiply(rows[i:i + m].T, _C1, out=kx)
            _rotl32(kx, 15, tmp_s[:, :m])
            np.multiply(kx, _C2, out=kx)
            h[0] = _SEED_HI
            h[1] = _SEED_LO
            for j in range(k):
                np.bitwise_xor(h, kx[j], out=h)
                _rotl32(h, 13, t)
                np.multiply(h, np.uint32(5), out=h)
                np.add(h, np.uint32(0xE6546B64), out=h)
            np.bitwise_xor(h, np.uint32(4 * k), out=h)
            _xorshift32(h, 16, t)
            np.multiply(h, np.uint32(0x85EBCA6B), out=h)
            _xorshift32(h, 13, t)
            np.multiply(h, np.uint32(0xC2B2AE35), out=h)
            _xorshift32(h, 16, t)
            hi, lo = h
            # the all-ones pair is the dedup padding sentinel (hash_pair)
            lo[(hi == _SENT) & (lo == _SENT)] = np.uint32(0xFFFFFFFE)
            fp[0] = hi
            fp[1] = lo
            np.left_shift(fp[0], _U64(32), out=fp[0])
            np.bitwise_or(fp[0], fp[1], out=fp[0])
            if want_fps:
                fps[i:i + m] = fp[0]
            x ^= int(np.bitwise_xor.reduce(fp[0]))
            s += int(np.sum(fp[0], dtype=_U64))
    return fps, (n, x, s & 0xFFFFFFFFFFFFFFFF)


def _hashed(rows: np.ndarray, want_fps: bool):
    """Hashed-mode fingerprints and their digest in one pass over the
    rows: the native pass (native/fpset.cpp ``rows_digest``) where the
    library loaded, the blocked numpy twin where it did not (no
    toolchain: ``cli verify-checkpoint`` on a bare box)."""
    from .. import native  # lazy: builds the library on first use

    rows = np.ascontiguousarray(rows, np.uint32)
    rows = rows.reshape(-1, rows.shape[-1])
    out = native.rows_digest(rows, int(_SEED_HI), int(_SEED_LO), want_fps)
    return _hashed_blocks(rows, want_fps) if out is None else out


def native_twin(exact: bool) -> bool:
    """Whether :func:`fingerprint_rows` / :func:`digest_rows` take the
    native pass (what the ``frontier-verify`` span says of itself)."""
    from .. import native

    return not exact and native.native_available()


def _exact_rows(rows: np.ndarray) -> np.ndarray:
    lo = rows[..., 0]
    hi = rows[..., 1] if rows.shape[-1] > 1 else np.zeros_like(lo)
    return pair_u64(hi, lo)


def fingerprint_rows(rows: np.ndarray, exact: bool) -> np.ndarray:
    """uint32[n, K] packed states -> uint64[n] fingerprints, bit-exact
    with ``ops.fingerprint.fingerprint_lanes`` (incl. the all-ones
    sentinel remap in hashed mode)."""
    rows = np.asarray(rows, np.uint32)
    if exact:
        return _exact_rows(rows)
    return _hashed(rows, True)[0].reshape(rows.shape[:-1])


def digest_rows(rows: np.ndarray, exact: bool) -> tuple:
    """uint32[n, K] packed states -> the ``(count, xor, sum)`` digest of
    their fingerprint multiset: ``digest_fps(fingerprint_rows(rows,
    exact))`` without materialising the fingerprints, for the callers
    that only compare or fold a digest (the level boundary's frontier
    verify, the host backends' chain folds)."""
    rows = np.asarray(rows, np.uint32)
    if exact:
        return digest_fps(_exact_rows(rows))
    return _hashed(rows, False)[1]


def pair_u64(hi, lo) -> np.ndarray:
    """(hi, lo) uint32 fingerprint lanes -> uint64 values."""
    return (np.asarray(hi).astype(_U64) << _U64(32)) | np.asarray(lo).astype(
        _U64
    )


# --------------------------------------------------------------------------
# multiset digests + the level chain
# --------------------------------------------------------------------------


def digest_fps(fps: np.ndarray) -> tuple:
    """-> (count, xor, sum) over a uint64 fingerprint multiset.  XOR and
    wrapping sum are commutative and associative, so the digest is
    invariant to chunking, shard order, and pipeline choice — and two
    digests combine by (count+count, xor^xor, sum+sum)."""
    fps = np.asarray(fps, _U64)
    if fps.size == 0:
        return 0, 0, 0
    with np.errstate(over="ignore"):
        x = int(np.bitwise_xor.reduce(fps))
        s = int(np.sum(fps, dtype=_U64))
    return int(fps.size), x, s


def combine_digests(digests) -> tuple:
    """The digest of the union of disjoint multisets from their digests:
    (count+count, xor^xor, sum+sum wrapping)."""
    c = x = s = 0
    for dc, dx, ds in digests:
        c += dc
        x ^= dx
        s = (s + ds) & 0xFFFFFFFFFFFFFFFF
    return c, x, s


def _splitmix64(x: int) -> int:
    x = (x + 0x9E3779B97F4A7C15) & 0xFFFFFFFFFFFFFFFF
    z = x
    z = ((z ^ (z >> 30)) * 0xBF58476D1CE4E5B9) & 0xFFFFFFFFFFFFFFFF
    z = ((z ^ (z >> 27)) * 0x94D049BB133111EB) & 0xFFFFFFFFFFFFFFFF
    return z ^ (z >> 31)


def chain_link(prev: int, count: int, xor: int, total: int) -> int:
    """One hash-chain step: the level-d chain value commits to the whole
    exploration prefix (every earlier level's digest), so two runs with
    equal chain values at depth d provably explored the same multiset
    sequence — the "a resumed run continues the SAME exploration" stamp."""
    h = _splitmix64(prev ^ _splitmix64(count))
    h = _splitmix64(h ^ xor)
    return _splitmix64(h ^ total)


class LevelDigestChain:
    """Per-level (count, xor, sum) digests + the linking hash chain.

    One instance per run; both engines drive the same protocol:

        chain.fold(fps_u64)      # any number of times per level, any order
        chain.seal(depth, n)     # at the level boundary (n = new states)

    ``entries[d] = (count, xor, sum, chain)`` as python ints;
    ``to_array()``/``from_array()`` round-trip through the uint64[L, 4]
    checkpoint stamp.  ``anchored`` is False when the chain was rebuilt
    from a pre-integrity checkpoint (counts known from ``levels``, digests
    unknown) — digest-dependent checks then skip, linkage-dependent ones
    still run from the resume point on.
    """

    COLS = 4  # count, xor, sum, chain

    def __init__(self):
        self.entries: list[tuple] = []
        self.anchored = True
        self._fold_count = 0
        self._fold_xor = 0
        self._fold_sum = 0

    # --- build ----------------------------------------------------------
    def fold(self, fps) -> None:
        self.fold_digest(*digest_fps(fps))

    def fold_digest(self, count: int, xor: int, total: int) -> None:
        """Fold a PRE-COMPUTED (count, xor, sum) multiset digest — the
        device-resident pipeline's per-level accumulator, computed
        in-jit (ops/devlevel.py) bit-exactly with :func:`digest_fps`
        over the same fingerprints.  Digests combine by (c+c, x^x, s+s)
        (see digest_fps), so this is exactly fold() minus the host
        recomputation."""
        self._fold_count += int(count)
        self._fold_xor ^= int(xor)
        self._fold_sum = (self._fold_sum + int(total)) & 0xFFFFFFFFFFFFFFFF

    def seal(self, depth: int, count: int) -> None:
        """Close level `depth` (must be len(entries)): the folded digest
        becomes the level's entry.  A count disagreement between the
        engine's accounting and the folded multiset is itself an
        integrity violation (it means novelty masks and emitted rows
        diverged somewhere between the kernel and the host)."""
        assert depth == len(self.entries), (depth, len(self.entries))
        if self._fold_count != int(count):
            raise IntegrityError(
                "chain",
                f"level {depth}: folded {self._fold_count} fingerprints "
                f"but the engine accounted {int(count)} new states",
                depth=depth,
            )
        prev = self.entries[-1][3] if self.entries else 0
        link = chain_link(prev, self._fold_count, self._fold_xor,
                          self._fold_sum)
        self.entries.append(
            (self._fold_count, self._fold_xor, self._fold_sum, link)
        )
        self._fold_count = self._fold_xor = self._fold_sum = 0

    def reset_fold(self) -> None:
        self._fold_count = self._fold_xor = self._fold_sum = 0

    # --- verify ---------------------------------------------------------
    def verify_level(self, depth: int, fps) -> None:
        """The level-boundary frontier check: the multiset about to be
        expanded must be exactly the multiset sealed when the level was
        discovered — a bit flipped in the frontier buffer (or a frontier
        loaded from a CRC-consistent corrupted checkpoint) lands here.
        `fps`: the frontier's fingerprints, or their ``(count, xor,
        sum)`` digest (:func:`digest_rows`)."""
        if not self.anchored or depth >= len(self.entries):
            return
        c, x, s = fps if isinstance(fps, tuple) else digest_fps(fps)
        want = self.entries[depth]
        if (c, x, s) != want[:3]:
            raise IntegrityError(
                "frontier",
                f"level {depth} frontier digest (n={c}, xor={x:#x}) does "
                f"not match the sealed chain entry (n={want[0]}, "
                f"xor={want[1]:#x}) — the frontier buffer was corrupted "
                f"after the level was discovered",
                depth=depth,
            )

    def cumulative(self) -> tuple:
        """(count, xor, sum) over EVERY sealed level — the digest of the
        whole visited set (levels are disjoint by construction)."""
        return combine_digests(e[:3] for e in self.entries)

    def verify_visited(self, fps, depth=None, what: str = "fpset") -> None:
        """The save-time self-check: the visited-set dump about to be
        checkpointed must digest to the chain's running total.  Runs
        BEFORE the write, so detected corruption never enters a
        checkpoint."""
        if not self.anchored:
            return
        c, x, s = digest_fps(fps)
        wc, wx, ws = self.cumulative()
        if (c, x, s) != (wc, wx, ws):
            raise IntegrityError(
                what,
                f"visited-set dump digest (n={c}, xor={x:#x}) does not "
                f"match the chain's cumulative digest (n={wc}, "
                f"xor={wx:#x}) — the fingerprint set was corrupted in "
                f"memory",
                depth=depth,
            )

    # --- (de)serialization ---------------------------------------------
    def to_array(self) -> np.ndarray:
        return np.asarray(
            [[c, x, s, h] for c, x, s, h in self.entries], _U64
        ).reshape(len(self.entries), self.COLS)

    @classmethod
    def from_array(cls, arr) -> "LevelDigestChain":
        chain = cls()
        for row in np.asarray(arr, _U64).reshape(-1, cls.COLS):
            chain.entries.append(tuple(int(v) for v in row))
        return chain

    @classmethod
    def from_levels(cls, levels) -> "LevelDigestChain":
        """Rebuild from a pre-integrity checkpoint: counts only, digests
        unknown — the chain keeps extending but is unanchored below the
        resume point."""
        chain = cls()
        chain.anchored = False
        prev = 0
        for n in levels:
            prev = chain_link(prev, int(n), 0, 0)
            chain.entries.append((int(n), 0, 0, prev))
        return chain


# --------------------------------------------------------------------------
# checkpoint-side validation (shared: resume fallback + offline verifier)
# --------------------------------------------------------------------------


def chain_array_errors(arr, levels=None) -> list:
    """Validate a stamped ``digest_chain`` array: internal hash-chain
    linkage, and per-level count agreement with the checkpoint's own
    ``levels`` array.  -> list of error strings (empty = ok)."""
    errors = []
    try:
        rows = np.asarray(arr, _U64).reshape(-1, LevelDigestChain.COLS)
    except (ValueError, TypeError) as e:
        return [f"digest chain unparseable: {e}"]
    prev = 0
    for d, (c, x, s, h) in enumerate(rows.tolist()):
        want = chain_link(prev, int(c), int(x), int(s))
        if int(h) != want:
            errors.append(
                f"digest chain broken at level {d}: stored link "
                f"{int(h):#x} != recomputed {want:#x}"
            )
            break
        prev = int(h)
    if levels is not None:
        lv = [int(v) for v in np.asarray(levels).ravel().tolist()]
        cc = [int(c) for c in rows[:, 0].tolist()]
        if lv != cc:
            errors.append(
                f"digest chain counts {cc[:8]}{'...' if len(cc) > 8 else ''} "
                f"disagree with the levels array "
                f"{lv[:8]}{'...' if len(lv) > 8 else ''}"
            )
    return errors


def _visited_fps_of(arrays: dict):
    """The full visited-set uint64 multiset stored in a (single-device)
    checkpoint, or None when the generation doesn't carry one (disk-tier
    hot dumps are a budget-bounded subset; sharded mains may hold only
    per-shard concatenations, which still digest identically)."""
    if "spill_manifest" in arrays:
        return None  # hot dump only; the runs carry their own CRCs
    if "host_fps" in arrays:
        return np.asarray(arrays["host_fps"], _U64)
    if "hash_hi" in arrays:
        return pair_u64(arrays["hash_hi"], arrays["hash_lo"])
    if "vhi" in arrays and "vn" in arrays:
        vhi = np.asarray(arrays["vhi"], np.uint32)
        vlo = np.asarray(arrays["vlo"], np.uint32)
        if vhi.ndim == 1:
            return pair_u64(vhi, vlo)
        # sharded device backend: [D, w] per-shard prefixes of vn[d] rows
        vn = np.asarray(arrays["vn"]).ravel()
        parts = [
            pair_u64(vhi[d, : int(n)], vlo[d, : int(n)])
            for d, n in enumerate(vn.tolist())
        ]
        return np.concatenate(parts) if parts else np.empty(0, _U64)
    return None


def checkpoint_chain_errors(arrays: dict) -> list:
    """THE digest-chain validator for one checkpoint generation's arrays:
    linkage + levels agreement + (when the generation carries the full
    fingerprint set) cumulative visited digest.  Shared by the resume
    fallback (``CheckpointStore(validators=[...])``) and the offline
    ``cli verify-checkpoint`` — this is what flags a corrupted generation
    whose per-array CRCs still pass (the CRC faithfully checksums
    corrupted content; the chain does not).  Pre-integrity generations
    (no ``digest_chain``) validate vacuously."""
    if "digest_chain" not in arrays:
        return []
    errors = chain_array_errors(
        arrays["digest_chain"], levels=arrays.get("levels")
    )
    if "total" in arrays and not errors:
        rows = np.asarray(arrays["digest_chain"], _U64).reshape(
            -1, LevelDigestChain.COLS
        )
        tot = int(np.sum(rows[:, 0], dtype=_U64))
        if tot != int(arrays["total"]):
            errors.append(
                f"digest chain total {tot} != checkpoint total "
                f"{int(arrays['total'])}"
            )
    fps = _visited_fps_of(arrays) if not errors else None
    if fps is not None:
        chain = LevelDigestChain.from_array(arrays["digest_chain"])
        chain.anchored = True
        c, x, s = digest_fps(fps)
        wc, wx, ws = chain.cumulative()
        if (c, x, s) != (wc, wx, ws):
            errors.append(
                f"visited fingerprint set digest (n={c}, xor={x:#x}) does "
                f"not match the digest chain's cumulative (n={wc}, "
                f"xor={wx:#x}) — CRC-consistent content corruption"
            )
    return errors


def spill_run_errors(directory: str, metas) -> list:
    """CRC-verify every spill run a checkpoint generation REFERENCES —
    the shared core of both engines' disk-tier load validators (one
    implementation, like readback_chain: the accept/reject contract for
    generations must not drift between engines).  -> error strings."""
    from ..storage.runs import RunCorrupt, SortedRun

    errs = []
    for meta in metas:
        try:
            SortedRun(directory, meta, verify=True)
        except RunCorrupt as e:
            errs.append(f"referenced spill run corrupt: {e}")
    return errs


def readback_chain(path: str, depth=None) -> None:
    """Cheap post-save verification of a freshly promoted checkpoint's
    chain members only (digest_chain / levels / total — the big arrays
    were self-checked BEFORE the write).  A CRC-consistent corruption
    inside the writer (flip@ckpt rehearses it: the manifest checksums
    corrupt content faithfully) is caught here, typed, before the run
    sails on trusting a poisoned newest generation.  ONE implementation
    for both engines — the read-back contract must not drift between
    them."""
    for _attempt in range(3):
        try:
            with np.load(path, allow_pickle=False) as z:
                small = {
                    k: z[k]
                    for k in ("digest_chain", "levels", "total", "depth")
                    if k in z.files
                }
            break
        except FileNotFoundError:
            # the NEXT save's keep-K rotation window: generation 0 is
            # briefly renamed to .1 before its replacement promotes
            # (checkpoints.CheckpointStore.save).  The promote that
            # triggered THIS readback already succeeded, so the path can
            # only be missing because a newer generation superseded it
            # mid-rotate — wait out the window, and if it stays gone the
            # superseding save's own readback verifies the new newest.
            import time

            time.sleep(0.02)
    else:
        return
    count_check()
    errs = checkpoint_chain_errors(small)
    if errs:
        raise IntegrityError(
            "ckpt",
            f"post-save chain read-back of {path} failed: "
            + "; ".join(errs),
            depth=depth,
        )


def record_violation(err: "IntegrityError") -> None:
    """THE record-a-violation protocol (obs event + metric), shared by
    both engines' terminal handlers so the telemetry cannot drift."""
    from ..obs import metrics as _met  # lazy: cycle hygiene
    from ..obs import tracer as _obs

    _obs.event(
        "integrity-violation",
        site=err.site,
        depth=err.depth,
        detail=str(err)[:300],
    )
    _met.inc("kspec_integrity_violations_total")


def flip_bit(arr: np.ndarray) -> None:
    """In-place single-bit corruption of a (writable) numpy buffer — the
    injected SDC the flip@ faults rehearse.  Flips one bit in the middle
    element so interval gates and shape checks still pass (the corruption
    must be detectable only by content checks)."""
    if arr.size == 0:
        return
    flat = arr.reshape(-1).view(np.uint8)
    flat[flat.shape[0] // 2] ^= 0x10


def count_check(n: int = 1) -> None:
    """Bump the integrity-check counter (the obs beat's numerator)."""
    from ..obs import metrics as _met

    _met.inc("kspec_integrity_checks_total", n)


def fold_shard_device_digests(chain: "LevelDigestChain", counts,
                              xors_hi, xors_lo, limbs) -> None:
    """Fold per-SHARD device-computed level digests into a chain — the
    sharded device-resident level path's twin of the single-device
    fold_digest call.  `counts`/`xors_hi`/`xors_lo` are the fetched [D]
    accumulator lanes and `limbs` the [D, 4] 16-bit wrapping-sum limbs
    (ops/devlevel.masked_digest's accumulator, one per shard).  Digests
    combine commutatively, so folding shard by shard lands the exact
    value the per-chunk path's per-shard host folds produce over the
    same rows — chains stay comparable across pipelines, engines and
    elastic reshards."""
    from ..ops import devlevel

    for d in range(len(counts)):
        chain.fold_digest(
            *devlevel.digest_ints(
                (counts[d], xors_hi[d], xors_lo[d], limbs[d])
            )
        )
