"""KafkaReplication — the shared protocol core (L3 of SURVEY.md §1).

Reference: /root/reference/KafkaReplication.tla. This module provides, for a
given constant valuation (Replicas=N, LogSize=L, MaxRecords=R,
MaxLeaderEpoch=E):

- the canonical tensor encoding of the 6 state variables (:45-75), per
  SURVEY.md §2.2. The grow-only `leaderAndIsrRequests` message set is encoded
  as an epoch-indexed array: every request is created by ControllerUpdateIsr,
  which consumes a fresh leader epoch (:138-145), so requests are uniquely
  keyed by epoch — append-only and canonical, no set machinery needed.
- vmappable successor kernels for the shared actions (:138-310),
- predicate kernels for TypeOk/WeakIsr/StrongIsr/LeaderInIsr (:101,320,334,345),
- a 1:1 set-semantics oracle transcription of the same definitions, used as
  the golden cross-check (stock TLC is unavailable in this environment),
- `decode` from tensor state to the oracle's canonical Python state, so
  engine and oracle runs can be compared as state *sets*.

Value conventions (shared by tensors and oracle): replicas are 0..N-1,
`None == "NONE"` is -1 (:38), `Nil` is -1 (:39), ISRs are bitmasks in tensor
form and frozensets in oracle form.

Note on LeaderInIsr (:345): taken literally, `quorumState.leader \\in
quorumState.isr` is False whenever leader = None — including the initial
state (:117-119), so the literal invariant is violated at depth 0 despite the
THEOREM at Kip320.tla:169. We expose both the literal predicate
(`LeaderInIsrLiteral`) and the evident intent (`LeaderInIsr`: leader # None
=> leader in ISR), and the known-answer tests pin down both behaviors.
"""

from __future__ import annotations

import dataclasses
import math
from dataclasses import dataclass
from itertools import permutations, product

import jax.numpy as jnp
import numpy as np

from ..ops.packing import Field, StateSpec
from ..oracle.interp import (
    OracleAction,
    OracleModel,
    OracleSymmetry,
    reduce_by_symmetry,
)
from .base import Action, FieldRole, Invariant, Symmetry, read, write

NONE = -1  # KafkaReplication.tla:38
NIL = -1  # KafkaReplication.tla:39
ABSENT = -2  # epoch slot with no LeaderAndIsr request yet


@dataclass(frozen=True)
class Config:
    """Constant valuation: Replicas/LogSize/MaxRecords/MaxLeaderEpoch
    (KafkaReplication.tla:32-36)."""

    n_replicas: int
    log_size: int
    max_records: int
    max_leader_epoch: int

    @property
    def n(self):
        return self.n_replicas

    @property
    def l(self):
        return self.log_size

    @property
    def r(self):
        return self.max_records

    @property
    def e(self):
        return self.max_leader_epoch

    @property
    def full_isr(self):
        return (1 << self.n_replicas) - 1


def make_spec(cfg: Config) -> StateSpec:
    """Tensor encoding of the 6 state variables (SURVEY.md §2.2)."""
    N, L, R, E = cfg.n, cfg.l, cfg.r, cfg.e
    return StateSpec(
        [
            # replicaLog (:47; FiniteReplicatedLog.tla:41-44)
            Field("end", (N,), 0, L),
            Field("rid", (N, L), NIL, R - 1),
            Field("repoch", (N, L), NIL, E),
            # replicaState (:49-51, :96-99)
            Field("hw", (N,), 0, L),
            Field("ep", (N,), NIL, E),
            Field("ldr", (N,), NONE, N - 1),
            Field("isr", (N,), 0, cfg.full_isr),
            # id sequences (:55,:59; IdSequence.tla:43)
            Field("nrid", (), 0, R),
            Field("nep", (), 0, E + 1),
            # quorumState (:73, :87-89)
            Field("qep", (), NIL, E),
            Field("qldr", (), NONE, N - 1),
            Field("qisr", (), 0, cfg.full_isr),
            # leaderAndIsrRequests, epoch-indexed (:66, :107; see module doc)
            Field("req_ldr", (E + 1,), ABSENT, N - 1),
            Field("req_isr", (E + 1,), 0, cfg.full_isr),
        ]
    )


def symmetry(cfg: Config) -> Symmetry:
    """How :func:`make_spec`'s fields move under a permutation of
    `Replicas` (TLC: ``SYMMETRY Symm``, ``Symm == Permutations(Replicas)``
    in the conventional ``MC`` wrapper module).  The spec is symmetric in
    its replicas: Init treats them alike, every action quantifies over
    `Replicas`, the only CHOOSEs are Min / Max over offsets, and the
    invariants quantify over replicas (tests/test_symmetry.py holds the
    equivariance to the oracle).  Record ids, epochs and offsets are not
    replicas and do not move."""
    by_replica = FieldRole(axis=0)
    return Symmetry(
        set_name="Replicas",
        n=cfg.n,
        roles={
            "end": by_replica,
            "rid": by_replica,
            "repoch": by_replica,
            "hw": by_replica,
            "ep": by_replica,
            "ldr": FieldRole(axis=0, value="member"),
            "isr": FieldRole(axis=0, value="mask"),
            "qldr": FieldRole(value="member"),
            "qisr": FieldRole(value="mask"),
            "req_ldr": FieldRole(value="member"),
            "req_isr": FieldRole(value="mask"),
        },
    )


def init_state(cfg: Config) -> dict:
    """Init (KafkaReplication.tla:109-120)."""
    N, L, E = cfg.n, cfg.l, cfg.e
    return {
        "end": [0] * N,
        "rid": [[NIL] * L for _ in range(N)],
        "repoch": [[NIL] * L for _ in range(N)],
        "hw": [0] * N,  # ReplicaLog!StartOffset (:113)
        "ep": [NIL] * N,
        "ldr": [NONE] * N,
        "isr": [0] * N,  # local ISR starts empty (:116)
        "nrid": 0,
        "nep": 0,
        "qep": NIL,
        "qldr": NONE,
        "qisr": cfg.full_isr,  # quorum ISR starts as all replicas (:119)
        "req_ldr": [ABSENT] * (E + 1),
        "req_isr": [0] * (E + 1),
    }


# --------------------------------------------------------------------------
# kernel helpers
# --------------------------------------------------------------------------


# declared write sets (frame conditions) for the analyzer's
# frame-condition pass (analysis/encoding.py): the variables each
# action's TLA+ disjunct primes, in tensor-lane terms
_CTRL_WRITES = frozenset({"nep", "qep", "qldr", "qisr", "req_ldr", "req_isr"})
_QUORUM_WRITES = frozenset({"qisr", "isr"})
_BECOME_FOLLOWER_WRITES = frozenset(
    {"rid", "repoch", "end", "ep", "ldr", "isr", "hw"}
)
_REPLICATE_WRITES = frozenset({"rid", "repoch", "end", "hw"})


def _bit(r):
    return jnp.int32(1) << r


def _member(mask, r):
    return ((mask >> r) & 1) == 1


def _is_true_leader(s, l):
    # IsTrueLeader (:128-131)
    return (
        (s["qldr"] == l)
        & (read(s["ldr"], l) == l)
        & (read(s["ep"], l) == s["qep"])
    )


def _caught_up(s, l, f, end_offset):
    # IsFollowerCaughtUp(leader, follower, endOffset) (:219-225):
    # following /\ (endOffset = 0 \/ (leader has a record at endOffset-1
    # /\ follower HasOffset(endOffset-1)))
    following = read(s["ldr"], f) == l
    nonzero = (
        (end_offset > 0)
        & (end_offset <= read(s["end"], l))
        & (read(s["end"], f) >= end_offset)
    )
    return following & ((end_offset == 0) | nonzero)


def _forall_isr(cfg, isr_mask, cond_vec):
    """\\A follower \\in isr : cond[follower] — masked reduction over N."""
    members = ((isr_mask >> jnp.arange(cfg.n)) & 1) == 1
    return jnp.all(jnp.where(members, cond_vec, True))


def _truncate_log(s, r, new_end):
    """ReplicaLog!TruncateTo Nil-fill (FiniteReplicatedLog.tla:105-109);
    caller must guard new_end <= end[r]."""
    offs = jnp.arange(s["rid"].shape[1])
    keep = offs < new_end
    rid = write(s["rid"], r, jnp.where(keep, read(s["rid"], r), NIL))
    repoch = write(s["repoch"], r, jnp.where(keep, read(s["repoch"], r), NIL))
    end = write(s["end"], r, new_end)
    return rid, repoch, end


def _ctrl_update_isr(cfg, s, new_leader, new_isr):
    """ControllerUpdateIsr(newLeader, newIsr) (:138-145): consume a fresh
    epoch via LeaderEpochSeq!NextId (forced existential), write quorumState,
    append the LeaderAndIsr request. Returns (enabled, next_state)."""
    e = s["nep"]
    ok = e <= cfg.e  # IdSequence.tla:31 — disabled once epochs exhausted
    ec = jnp.minimum(e, cfg.e)
    return ok, {
        **s,
        "nep": jnp.minimum(e + 1, cfg.e + 1),
        "qep": ec,
        "qldr": new_leader,
        "qisr": new_isr,
        "req_ldr": write(s["req_ldr"], ec, new_leader),
        "req_isr": write(s["req_isr"], ec, new_isr),
    }


# --------------------------------------------------------------------------
# shared action kernels (KafkaReplication.tla:138-310)
# --------------------------------------------------------------------------


def controller_shrink_isr(cfg: Config):
    # ControllerShrinkIsr (:158-168), choice = replica
    def kernel(s, r):
        is_ldr = s["qldr"] == r
        sole = s["qisr"] == _bit(r)
        case1 = is_ldr & sole  # leader is the sole ISR member: keep ISR (:159-161)
        case2 = is_ldr & ~sole  # leader leaves: None, ISR - {r} (:162-164)
        case3 = (~is_ldr) & _member(s["qisr"], r)  # follower leaves (:165-167)
        enabled = case1 | case2 | case3
        new_leader = jnp.where(case3, s["qldr"], NONE)
        new_isr = jnp.where(case1, s["qisr"], s["qisr"] & ~_bit(r))
        ok, nxt = _ctrl_update_isr(cfg, s, new_leader, new_isr)
        return enabled & ok, nxt

    return Action("ControllerShrinkIsr", cfg.n, kernel,
                  writes=_CTRL_WRITES)


def controller_elect_leader(cfg: Config):
    # ControllerElectLeader (:176-179), choice = newLeader \in quorum ISR
    def kernel(s, r):
        enabled = _member(s["qisr"], r) & (s["qldr"] != r)
        ok, nxt = _ctrl_update_isr(cfg, s, r, s["qisr"])
        return enabled & ok, nxt

    return Action("ControllerElectLeader", cfg.n, kernel,
                  writes=_CTRL_WRITES)


def become_leader(cfg: Config):
    # BecomeLeader (:186-195), choice = request (keyed by its unique epoch)
    def kernel(s, e):
        l = read(s["req_ldr"], e)
        lc = jnp.clip(l, 0, cfg.n - 1)
        enabled = (l >= 0) & (e > read(s["ep"], lc))  # leader # None /\ epoch newer
        return enabled, {
            **s,
            "ep": write(s["ep"], lc, e),
            "ldr": write(s["ldr"], lc, lc),
            "isr": write(s["isr"], lc, read(s["req_isr"], e)),
            # hw unchanged — the stale-HW subtlety (:183-185, :191)
        }

    return Action("BecomeLeader", cfg.e + 1, kernel,
                  writes=frozenset({"ep", "ldr", "isr"}))


def leader_write(cfg: Config):
    # LeaderWrite (:202-207), choice = replica; id/offset are forced
    def kernel(s, r):
        end = read(s["end"], r)
        enabled = (read(s["ldr"], r) == r) & (s["nrid"] < cfg.r) & (end < cfg.l)
        off = jnp.minimum(end, cfg.l - 1)
        return enabled, {
            **s,
            "rid": write(
                s["rid"], (r, off),
                jnp.where(enabled, s["nrid"], read(s["rid"], r, off)),
            ),
            "repoch": write(
                s["repoch"], (r, off),
                jnp.where(enabled, read(s["ep"], r), read(s["repoch"], r, off)),
            ),
            "end": write(s["end"], r, jnp.where(enabled, end + 1, end)),
            "nrid": jnp.minimum(s["nrid"] + 1, cfg.r),
        }

    return Action("LeaderWrite", cfg.n, kernel,
                  writes=frozenset({"rid", "repoch", "end", "nrid"}))


def _quorum_update(s, l, new_isr):
    """QuorumUpdateLeaderAndIsr (:213-217): quorum-fenced ISR write; sets the
    quorum ISR and the leader's cached ISR. Returns (enabled, next)."""
    enabled = _is_true_leader(s, l)
    return enabled, {
        **s,
        "qisr": new_isr,
        "isr": write(s["isr"], l, new_isr),
    }


def leader_shrink_isr(cfg: Config):
    # LeaderShrinkIsr (:233-239), choice = (leader, replica in isr \ {leader})
    def kernel(s, c):
        l, f = c // cfg.n, c % cfg.n
        in_isr = (f != l) & _member(read(s["isr"], l), f)
        lagging = ~_caught_up(s, l, f, read(s["end"], l))
        ok, nxt = _quorum_update(s, l, read(s["isr"], l) & ~_bit(f))
        return in_isr & lagging & ok, nxt

    return Action("LeaderShrinkIsr", cfg.n * cfg.n, kernel,
                  writes=_QUORUM_WRITES)


def leader_expand_isr(cfg: Config):
    # LeaderExpandIsr (:248-254), choice = (leader, replica not in isr)
    def kernel(s, c):
        l, f = c // cfg.n, c % cfg.n
        outside = ~_member(read(s["isr"], l), f)
        caught = _caught_up(s, l, f, read(s["hw"], l))
        ok, nxt = _quorum_update(s, l, read(s["isr"], l) | _bit(f))
        return outside & caught & ok, nxt

    return Action("LeaderExpandIsr", cfg.n * cfg.n, kernel,
                  writes=_QUORUM_WRITES)


def leader_inc_high_watermark(cfg: Config):
    # LeaderIncHighWatermark (:264-271), choice = leader; offset forced = hw.
    # No epoch verification — the pre-KIP-320 hole (:256-263).
    def kernel(s, l):
        hw = read(s["hw"], l)
        presumes = read(s["ldr"], l) == l
        in_offsets = hw < cfg.l  # \E offset \in Offsets (:264)
        follows = (s["ldr"] == l) & (s["end"] > hw)  # HasOffset(f, hw) (:267-269)
        all_isr = _forall_isr(cfg, read(s["isr"], l), follows)
        enabled = presumes & in_offsets & all_isr
        return enabled, {**s, "hw": write(s["hw"], l, jnp.minimum(hw + 1, cfg.l))}

    return Action("LeaderIncHighWatermark", cfg.n, kernel,
                  writes=frozenset({"hw"}))


def become_follower_and_truncate_to(cfg: Config, name: str, trunc_offset_fn):
    """BecomeFollowerAndTruncateTo(leader, replica, truncationOffset)
    (:281-294), choice = (replica, request-epoch); leader = request.leader.

    trunc_offset_fn(s, l, r) -> truncation offset computed on the *old* state;
    this is the only thing the historical variants change (:274-277).
    The `leader = None` branch (:285-286) is unreachable from every variant's
    Next (each quantifies leader over Replicas), so leaders here are real
    replicas; requests with leader = None are never consumed.
    """

    def kernel(s, c):
        r, e = c // (cfg.e + 1), c % (cfg.e + 1)
        l = read(s["req_ldr"], e)
        lc = jnp.clip(l, 0, cfg.n - 1)
        enabled = (l >= 0) & (lc != r) & (e > read(s["ep"], r))
        toff = trunc_offset_fn(s, lc, r)
        enabled = enabled & (toff <= read(s["end"], r))  # TruncateTo guard (FRL:106)
        toff = jnp.clip(toff, 0, cfg.l)
        rid, repoch, end = _truncate_log(s, r, toff)
        return enabled, {
            **s,
            "rid": rid,
            "repoch": repoch,
            "end": end,
            "ep": write(s["ep"], r, e),
            "ldr": write(s["ldr"], r, lc),
            "isr": write(s["isr"], r, read(s["req_isr"], e)),
            "hw": write(s["hw"], r, jnp.minimum(toff, read(s["hw"], r))),  # (:293)
        }

    return Action(name, cfg.n * (cfg.e + 1), kernel,
                  writes=_BECOME_FOLLOWER_WRITES)


def follower_replicate(cfg: Config):
    # FollowerReplicate (:302-310), choice = (follower, leader); the fetched
    # record/offset are forced (ReplicateTo copies the follower's next slot).
    # Unfenced: no epoch check (:297-301).
    def kernel(s, c):
        f, l = c // cfg.n, c % cfg.n
        off = read(s["end"], f)
        enabled = (
            (read(s["ldr"], l) == l)
            & (read(s["ldr"], f) == l)
            & (off < cfg.l)
            & (off < read(s["end"], l))
        )
        offc = jnp.minimum(off, cfg.l - 1)
        new_hw = jnp.minimum(read(s["hw"], l), off + 1)  # (:306-309)
        return enabled, {
            **s,
            "rid": write(
                s["rid"], (f, offc),
                jnp.where(
                    enabled, read(s["rid"], l, offc), read(s["rid"], f, offc)
                ),
            ),
            "repoch": write(
                s["repoch"], (f, offc),
                jnp.where(
                    enabled,
                    read(s["repoch"], l, offc),
                    read(s["repoch"], f, offc),
                ),
            ),
            "end": write(s["end"], f, jnp.where(enabled, off + 1, off)),
            "hw": write(s["hw"], f, jnp.where(enabled, new_hw, read(s["hw"], f))),
        }

    return Action("FollowerReplicate", cfg.n * cfg.n, kernel,
                  writes=_REPLICATE_WRITES)


# --------------------------------------------------------------------------
# variant truncation offsets (Kip101.tla / Kip279.tla)
# --------------------------------------------------------------------------


def truncate_to_hw_offset(cfg: Config):
    # BecomeFollowerTruncateToHighWatermark: truncate to own HW
    # (KafkaTruncateToHighWatermark.tla:29-31)
    def fn(s, l, r):
        return read(s["hw"], r)

    return fn


def kip101_offset(cfg: Config):
    """LookupOffsetForEpoch (Kip101.tla:31-39) applied per
    BecomeFollowerTruncateKip101 (Kip101.tla:41-47): empty follower log
    truncates to 0 (disjunct 1); otherwise look up by the epoch of the
    follower's latest record (disjunct 2 — the record is forced)."""

    def fn(s, l, r):
        offs = jnp.arange(cfg.l)
        r_end = read(s["end"], r)
        # latest record's epoch
        epoch = read(s["repoch"], r, jnp.clip(r_end - 1, 0, cfg.l - 1))
        l_end = read(s["end"], l)
        # OffsetsWithLargerEpochs(leader, epoch) (Kip101.tla:27-29)
        larger = (offs < l_end) & (read(s["repoch"], l) > epoch)
        any_larger = jnp.any(larger)
        min_larger = jnp.min(jnp.where(larger, offs, cfg.l))
        latest_match = (
            read(s["repoch"], l, jnp.clip(l_end - 1, 0, cfg.l - 1)) == epoch
        )
        lookup = jnp.where(
            l_end == 0,
            read(s["hw"], r),  # leader empty -> follower hw (Kip101.tla:32-33)
            jnp.where(
                latest_match,
                l_end,  # latest epoch match -> leader end offset (:34-35)
                jnp.where(any_larger, min_larger, read(s["hw"], r)),  # (:36-39)
            ),
        )
        return jnp.where(r_end == 0, 0, lookup)  # Kip101.tla:42-43

    return fn


def kip279_offset(cfg: Config):
    """FirstNonMatchingOffsetFromTail (Kip279.tla:39-45):
    Max(MatchingOffsets(follower, leader)) + 1, else 0.  MatchingOffsets
    (Kip279.tla:27-30) = offsets whose (id, epoch) entry in the follower's
    log exists identically in the leader's.  The empty-follower disjunct of
    BecomeFollowerTruncateKip279 (Kip279.tla:48-49) yields offset 0, which
    this formula already produces (no matching offsets)."""

    def fn(s, l, r):
        offs = jnp.arange(cfg.l)
        match = (
            (offs < read(s["end"], r))
            & (offs < read(s["end"], l))
            & (read(s["rid"], r) == read(s["rid"], l))
            & (read(s["repoch"], r) == read(s["repoch"], l))
        )
        any_match = jnp.any(match)
        max_match = jnp.max(jnp.where(match, offs, -1))
        return jnp.where(
            (read(s["end"], l) == 0) | ~any_match, 0, max_match + 1
        )

    return fn


# --------------------------------------------------------------------------
# invariants (KafkaReplication.tla:101-107, 320-345)
# --------------------------------------------------------------------------


def _isr_property(cfg: Config, s, isr_of_r1):
    """Common body of WeakIsr/StrongIsr (:320-340): for every presumed leader
    r1, every member r2 of `isr_of_r1(r1)` has an identical log below r1's hw."""
    N, L = cfg.n, cfg.l
    offs = jnp.arange(L)
    # pair_ok[r1, r2, off]: both logs hold the same record at off
    has1 = offs[None, None, :] < s["end"][:, None, None]  # r1 axis
    has2 = offs[None, None, :] < s["end"][None, :, None]  # r2 axis
    same = (s["rid"][:, None, :] == s["rid"][None, :, :]) & (
        s["repoch"][:, None, :] == s["repoch"][None, :, :]
    )
    pair_ok = has1 & has2 & same
    below_hw = offs[None, None, :] < s["hw"][:, None, None]
    r2_in = ((isr_of_r1 >> jnp.arange(N)[None, :]) & 1) == 1  # [r1, r2]
    relevant = below_hw & r2_in[:, :, None]
    ok_r1 = jnp.all(jnp.where(relevant, pair_ok, True), axis=(1, 2))
    presumes = s["ldr"] == jnp.arange(N)
    return jnp.all(jnp.where(presumes, ok_r1, True))


def weak_isr(cfg: Config):
    # WeakIsr (:320-326): r2 ranges over the presumed leader's *local* ISR
    def pred(s):
        return _isr_property(cfg, s, s["isr"][:, None])

    return Invariant("WeakIsr", pred)


def strong_isr(cfg: Config):
    # StrongIsr (:334-340): r2 ranges over the *quorum* ISR
    def pred(s):
        qisr = jnp.broadcast_to(s["qisr"], (cfg.n,))[:, None]
        return _isr_property(cfg, s, qisr)

    return Invariant("StrongIsr", pred)


def leader_in_isr_literal(cfg: Config):
    # LeaderInIsr (:345) taken literally: False whenever leader = None,
    # including Init (see module docstring).
    def pred(s):
        lc = jnp.clip(s["qldr"], 0, cfg.n - 1)
        return (s["qldr"] >= 0) & _member(s["qisr"], lc)

    return Invariant("LeaderInIsrLiteral", pred)


def leader_in_isr(cfg: Config):
    # Evident intent of (:345): a real leader is always in the quorum ISR.
    def pred(s):
        lc = jnp.clip(s["qldr"], 0, cfg.n - 1)
        return (s["qldr"] < 0) | _member(s["qisr"], lc)

    return Invariant("LeaderInIsr", pred)


def type_ok(cfg: Config):
    """TypeOk (:101-107): sequence bounds, record well-formedness, canonical
    Nil padding (FiniteReplicatedLog.tla:90-95), state ranges."""

    def pred(s):
        offs = jnp.arange(cfg.l)[None, :]
        written = offs < s["end"][:, None]
        recs_ok = jnp.all(
            jnp.where(
                written,
                (s["rid"] >= 0) & (s["rid"] < cfg.r) & (s["repoch"] >= 0) & (s["repoch"] <= cfg.e),
                (s["rid"] == NIL) & (s["repoch"] == NIL),
            )
        )
        seq_ok = (s["nrid"] >= 0) & (s["nrid"] <= cfg.r) & (s["nep"] >= 0) & (s["nep"] <= cfg.e + 1)
        rs_ok = (
            jnp.all((s["hw"] >= 0) & (s["hw"] <= cfg.l))
            & jnp.all((s["ep"] >= NIL) & (s["ep"] <= cfg.e))
            & jnp.all((s["ldr"] >= NONE) & (s["ldr"] < cfg.n))
            & jnp.all((s["isr"] >= 0) & (s["isr"] <= cfg.full_isr))
        )
        q_ok = (
            (s["qep"] >= NIL)
            & (s["qep"] <= cfg.e)
            & (s["qldr"] >= NONE)
            & (s["qldr"] < cfg.n)
            & (s["qisr"] >= 0)
            & (s["qisr"] <= cfg.full_isr)
        )
        return recs_ok & seq_ok & rs_ok & q_ok

    return Invariant("TypeOk", pred)


# --------------------------------------------------------------------------
# decode: tensor state -> canonical oracle state
# --------------------------------------------------------------------------


def make_decode(cfg: Config):
    """Canonical Python state:
    (logs, rstates, nrid, nep, reqs, quorum) with
      logs    = tuple_N of tuple of (id, epoch)
      rstates = tuple_N of (hw, epoch, leader, isr_frozenset)
      reqs    = frozenset of (epoch, leader, isr_frozenset)
      quorum  = (epoch, leader, isr_frozenset)
    """

    def iset(mask):
        return frozenset(r for r in range(cfg.n) if (int(mask) >> r) & 1)

    def decode(s):
        logs = tuple(
            tuple(
                (int(s["rid"][r][o]), int(s["repoch"][r][o]))
                for o in range(int(s["end"][r]))
            )
            for r in range(cfg.n)
        )
        rstates = tuple(
            (int(s["hw"][r]), int(s["ep"][r]), int(s["ldr"][r]), iset(s["isr"][r]))
            for r in range(cfg.n)
        )
        reqs = frozenset(
            (e, int(s["req_ldr"][e]), iset(s["req_isr"][e]))
            for e in range(cfg.e + 1)
            if int(s["req_ldr"][e]) != ABSENT
        )
        quorum = (int(s["qep"]), int(s["qldr"]), iset(s["qisr"]))
        return (logs, rstates, int(s["nrid"]), int(s["nep"]), reqs, quorum)

    return decode


# ==========================================================================
# oracle transcription (independent set semantics; the golden source)
# ==========================================================================
#
# Oracle state mirrors make_decode's canonical form exactly.  Indices below
# cite /root/reference/KafkaReplication.tla.


def o_init(cfg: Config):
    # Init (:109-120)
    logs = tuple(() for _ in range(cfg.n))
    rstates = tuple((0, NIL, NONE, frozenset()) for _ in range(cfg.n))
    quorum = (NIL, NONE, frozenset(range(cfg.n)))
    return (logs, rstates, 0, 0, frozenset(), quorum)


def _o_ctrl_update(cfg, s, new_leader, new_isr):
    # ControllerUpdateIsr (:138-145); None if epochs exhausted
    logs, rstates, nrid, nep, reqs, quorum = s
    if nep > cfg.e:
        return None
    req = (nep, new_leader, frozenset(new_isr))
    return (logs, rstates, nrid, nep + 1, reqs | {req}, req)


def o_controller_shrink_isr(cfg: Config):
    # ControllerShrinkIsr (:158-168)
    def successors(s):
        _, _, _, _, _, (qep, qldr, qisr) = s
        for r in range(cfg.n):
            if qldr == r and qisr == {r}:
                t = _o_ctrl_update(cfg, s, NONE, qisr)
            elif qldr == r and qisr != {r}:
                t = _o_ctrl_update(cfg, s, NONE, qisr - {r})
            elif qldr != r and r in qisr:
                t = _o_ctrl_update(cfg, s, qldr, qisr - {r})
            else:
                continue
            if t is not None:
                yield t

    return OracleAction("ControllerShrinkIsr", successors)


def o_controller_elect_leader(cfg: Config):
    # ControllerElectLeader (:176-179)
    def successors(s):
        _, _, _, _, _, (qep, qldr, qisr) = s
        for n in sorted(qisr):
            if qldr != n:
                t = _o_ctrl_update(cfg, s, n, qisr)
                if t is not None:
                    yield t

    return OracleAction("ControllerElectLeader", successors)


def o_become_leader(cfg: Config):
    # BecomeLeader (:186-195)
    def successors(s):
        logs, rstates, nrid, nep, reqs, quorum = s
        for (e, l, risr) in reqs:
            if l != NONE and e > rstates[l][1]:
                hw = rstates[l][0]
                new_rs = rstates[:l] + ((hw, e, l, risr),) + rstates[l + 1 :]
                yield (logs, new_rs, nrid, nep, reqs, quorum)

    return OracleAction("BecomeLeader", successors)


def o_leader_write(cfg: Config):
    # LeaderWrite (:202-207): presumed leader appends [id |-> nextRecordId,
    # epoch |-> own epoch]; RecordSeq!NextId bumps the counter.
    def successors(s):
        logs, rstates, nrid, nep, reqs, quorum = s
        if nrid >= cfg.r:
            return
        for r in range(cfg.n):
            if rstates[r][2] == r and len(logs[r]) < cfg.l:
                rec = (nrid, rstates[r][1])
                new_logs = logs[:r] + (logs[r] + (rec,),) + logs[r + 1 :]
                yield (new_logs, rstates, nrid + 1, nep, reqs, quorum)

    return OracleAction("LeaderWrite", successors)


def _o_is_true_leader(s, l):
    # IsTrueLeader (:128-131)
    _, rstates, _, _, _, (qep, qldr, _) = s
    return qldr == l and rstates[l][2] == l and rstates[l][1] == qep


def _o_quorum_update(s, l, new_isr):
    # QuorumUpdateLeaderAndIsr (:213-217)
    if not _o_is_true_leader(s, l):
        return None
    logs, rstates, nrid, nep, reqs, (qep, qldr, qisr) = s
    fs = frozenset(new_isr)
    hw, ep, ldr, _ = rstates[l]
    new_rs = rstates[:l] + ((hw, ep, ldr, fs),) + rstates[l + 1 :]
    return (logs, new_rs, nrid, nep, reqs, (qep, qldr, fs))


def _o_caught_up(s, l, f, end_offset):
    # IsFollowerCaughtUp (:219-225)
    logs, rstates, _, _, _, _ = s
    if rstates[f][2] != l:
        return False
    if end_offset == 0:
        return True
    return end_offset <= len(logs[l]) and len(logs[f]) >= end_offset


def o_leader_shrink_isr(cfg: Config):
    # LeaderShrinkIsr (:233-239)
    def successors(s):
        _, rstates, _, _, _, _ = s
        logs = s[0]
        for l in range(cfg.n):
            isr = rstates[l][3]
            for f in sorted(isr - {l}):
                if not _o_caught_up(s, l, f, len(logs[l])):
                    t = _o_quorum_update(s, l, isr - {f})
                    if t is not None:
                        yield t

    return OracleAction("LeaderShrinkIsr", successors)


def o_leader_expand_isr(cfg: Config):
    # LeaderExpandIsr (:248-254)
    def successors(s):
        _, rstates, _, _, _, _ = s
        for l in range(cfg.n):
            isr = rstates[l][3]
            hw = rstates[l][0]
            for f in range(cfg.n):
                if f not in isr and _o_caught_up(s, l, f, hw):
                    t = _o_quorum_update(s, l, isr | {f})
                    if t is not None:
                        yield t

    return OracleAction("LeaderExpandIsr", successors)


def o_leader_inc_high_watermark(cfg: Config):
    # LeaderIncHighWatermark (:264-271)
    def successors(s):
        logs, rstates, nrid, nep, reqs, quorum = s
        for l in range(cfg.n):
            hw, ep, ldr, isr = rstates[l]
            if ldr != l or hw >= cfg.l:
                continue
            if all(rstates[f][2] == l and len(logs[f]) > hw for f in isr):
                new_rs = rstates[:l] + ((hw + 1, ep, ldr, isr),) + rstates[l + 1 :]
                yield (logs, new_rs, nrid, nep, reqs, quorum)

    return OracleAction("LeaderIncHighWatermark", successors)


def o_become_follower_and_truncate_to(cfg: Config, name: str, trunc_offset_fn):
    # BecomeFollowerAndTruncateTo (:281-294) composed per-variant; leader
    # ranges over Replicas in every variant, so the None branch is dead.
    def successors(s):
        logs, rstates, nrid, nep, reqs, quorum = s
        for (e, l, risr) in reqs:
            if l == NONE:
                continue
            for r in range(cfg.n):
                if r == l or e <= rstates[r][1]:
                    continue
                toff = trunc_offset_fn(cfg, s, l, r)
                if toff > len(logs[r]):  # TruncateTo guard (FRL:106)
                    continue
                new_logs = logs[:r] + (logs[r][:toff],) + logs[r + 1 :]
                new_hw = min(toff, rstates[r][0])
                new_rs = rstates[:r] + ((new_hw, e, l, risr),) + rstates[r + 1 :]
                yield (new_logs, new_rs, nrid, nep, reqs, quorum)

    return OracleAction(name, successors)


def o_follower_replicate(cfg: Config):
    # FollowerReplicate (:302-310)
    def successors(s):
        logs, rstates, nrid, nep, reqs, quorum = s
        for f in range(cfg.n):
            for l in range(cfg.n):
                if rstates[l][2] != l or rstates[f][2] != l:
                    continue
                off = len(logs[f])
                if off >= cfg.l or off >= len(logs[l]):
                    continue
                new_logs = logs[:f] + (logs[f] + (logs[l][off],),) + logs[f + 1 :]
                new_hw = min(rstates[l][0], off + 1)
                hwf, epf, ldrf, isrf = rstates[f]
                new_rs = rstates[:f] + ((new_hw, epf, ldrf, isrf),) + rstates[f + 1 :]
                yield (new_logs, new_rs, nrid, nep, reqs, quorum)

    return OracleAction("FollowerReplicate", successors)


# variant truncation offsets, oracle side ---------------------------------


def o_truncate_to_hw_offset(cfg, s, l, r):
    # KafkaTruncateToHighWatermark.tla:29-31
    return s[1][r][0]


def o_kip101_offset(cfg, s, l, r):
    # Kip101.tla:27-47
    logs, rstates, *_ = s
    if len(logs[r]) == 0:
        return 0
    epoch = logs[r][-1][1]
    if len(logs[l]) == 0:
        return rstates[r][0]
    if logs[l][-1][1] == epoch:
        return len(logs[l])
    larger = [o for o, (_, ep) in enumerate(logs[l]) if ep > epoch]
    return min(larger) if larger else rstates[r][0]


def o_kip279_offset(cfg, s, l, r):
    # Kip279.tla:27-45
    logs = s[0]
    if len(logs[l]) == 0:
        return 0
    matching = [
        o
        for o, rec in enumerate(logs[r])
        if o < len(logs[l]) and logs[l][o] == rec
    ]
    return (max(matching) + 1) if matching else 0


# oracle invariants --------------------------------------------------------


def o_weak_isr(cfg: Config):
    # WeakIsr (:320-326)
    def pred(s):
        logs, rstates, *_ = s
        for r1 in range(cfg.n):
            hw, _, ldr, isr = rstates[r1]
            if ldr != r1:
                continue
            for r2 in isr:
                for off in range(hw):
                    if off >= len(logs[r1]) or off >= len(logs[r2]):
                        return False
                    if logs[r1][off] != logs[r2][off]:
                        return False
        return True

    return ("WeakIsr", pred)


def o_strong_isr(cfg: Config):
    # StrongIsr (:334-340)
    def pred(s):
        logs, rstates, _, _, _, (_, _, qisr) = s
        for r1 in range(cfg.n):
            hw, _, ldr, _ = rstates[r1]
            if ldr != r1:
                continue
            for r2 in qisr:
                for off in range(hw):
                    if off >= len(logs[r1]) or off >= len(logs[r2]):
                        return False
                    if logs[r1][off] != logs[r2][off]:
                        return False
        return True

    return ("StrongIsr", pred)


def o_leader_in_isr_literal(cfg: Config):
    # LeaderInIsr (:345), literal
    def pred(s):
        _, _, _, _, _, (_, qldr, qisr) = s
        return qldr in qisr

    return ("LeaderInIsrLiteral", pred)


def o_leader_in_isr(cfg: Config):
    def pred(s):
        _, _, _, _, _, (_, qldr, qisr) = s
        return qldr == NONE or qldr in qisr

    return ("LeaderInIsr", pred)


def o_type_ok(cfg: Config):
    # TypeOk (:101-107) on the canonical representation
    def pred(s):
        logs, rstates, nrid, nep, reqs, (qep, qldr, qisr) = s
        if not (0 <= nrid <= cfg.r and 0 <= nep <= cfg.e + 1):
            return False
        for log in logs:
            if len(log) > cfg.l:
                return False
            if any(not (0 <= i < cfg.r and 0 <= e <= cfg.e) for i, e in log):
                return False
        for hw, ep, ldr, isr in rstates:
            if not (0 <= hw <= cfg.l and NIL <= ep <= cfg.e and NONE <= ldr < cfg.n):
                return False
            if not isr <= set(range(cfg.n)):
                return False
        return NIL <= qep <= cfg.e and NONE <= qldr < cfg.n

    return ("TypeOk", pred)


# oracle symmetry (TLC's SYMMETRY over Permutations(Replicas)) --------------
#
# Written on the canonical Python state above, sharing nothing with
# ops/canon.py: the engine's orbit counts are held to these.


def o_permute(cfg: Config, s, g):
    """The image of state `s` under the replica permutation `g` (``g[i]``
    is the image of replica ``i``; None and Nil are fixed)."""
    logs, rstates, nrid, nep, reqs, (qep, qldr, qisr) = s
    n = cfg.n
    inv = [0] * n
    for i in range(n):
        inv[g[i]] = i

    def member(v):
        return g[v] if v >= 0 else v

    def subset(xs):
        return frozenset(g[x] for x in xs)

    new_rs = []
    for j in range(n):
        hw, ep, ldr, isr = rstates[inv[j]]
        new_rs.append((hw, ep, member(ldr), subset(isr)))
    return (
        tuple(logs[inv[j]] for j in range(n)),
        tuple(new_rs),
        nrid,
        nep,
        frozenset((e, member(l), subset(risr)) for (e, l, risr) in reqs),
        (qep, member(qldr), subset(qisr)),
    )


def _o_order_key(s):
    """A total order on states (frozensets compare by inclusion, so they
    become sorted tuples): the least image under it is the orbit's
    canonical member."""
    logs, rstates, nrid, nep, reqs, (qep, qldr, qisr) = s
    return (
        logs,
        tuple((hw, ep, ldr, tuple(sorted(isr))) for hw, ep, ldr, isr in rstates),
        nrid,
        nep,
        tuple(sorted((e, l, tuple(sorted(risr))) for e, l, risr in reqs)),
        (qep, qldr, tuple(sorted(qisr))),
    )


def _o_replica_signature(cfg: Config, s, i):
    """What can be said of replica `i` without naming a replica: equal for
    `i` in `s` and ``g(i)`` in ``g(s)``, whatever `g`."""
    logs, rstates, _, _, reqs, (_, qldr, qisr) = s
    hw, ep, ldr, isr = rstates[i]
    return (
        logs[i],
        hw,
        ep,
        0 if ldr == NONE else (1 if ldr == i else 2),
        len(isr),
        i in isr,
        qldr == i,
        i in qisr,
        tuple(sorted((e, l == i, i in risr) for e, l, risr in reqs)),
        sum(1 for r in rstates if r[2] == i),
        sum(1 for r in rstates if i in r[3]),
    )


def _o_tie_orders(groups):
    """Every way to lay the tie groups out in order, group by group."""
    return product(*(permutations(grp) for grp in groups))


def o_canonical(cfg: Config, s):
    """-> (canonical member of `s`'s orbit, the orbit's size).

    Exact, and short of all N! images: replicas are ordered by a
    replica-free signature, and only the orders inside a tie are tried.
    A permutation that sorts the signatures of ``h(s)`` is one that sorts
    those of `s`, composed with `h`, so every member of an orbit tries the
    same set of images and takes the same least one; and the sorting
    permutations that reach it are one coset of the stabiliser, so their
    count is its order (tests/test_symmetry.py holds both to the brute
    force over all N!)."""
    n = cfg.n
    sigs = [_o_replica_signature(cfg, s, i) for i in range(n)]
    order = sorted(range(n), key=lambda i: sigs[i])
    groups, last = [], None
    for i in order:
        if sigs[i] == last:
            groups[-1].append(i)
        else:
            groups.append([i])
            last = sigs[i]
    best = best_key = None
    hits = 0
    for laid in _o_tie_orders(groups):
        g = [0] * n
        pos = 0
        for grp in laid:
            for i in grp:
                g[i] = pos
                pos += 1
        t = o_permute(cfg, s, g)
        k = _o_order_key(t)
        if best_key is None or k < best_key:
            best, best_key, hits = t, k, 1
        elif k == best_key:
            hits += 1
    return best, math.factorial(n) // hits


def o_canonical_brute(cfg: Config, s):
    """:func:`o_canonical` by the definition: the least of all N! images,
    and N! over the images equal to `s` itself."""
    images = [o_permute(cfg, s, g) for g in permutations(range(cfg.n))]
    best = min(images, key=_o_order_key)
    return best, len(images) // sum(1 for t in images if t == s)


def reduced(built, cfg: Config, symmetric: bool):
    """A family model (or its oracle twin) as built, or with the replica
    symmetry switched on: the one place the family's factories apply it."""
    if not symmetric:
        return built
    if isinstance(built, OracleModel):
        return reduce_by_symmetry(built, o_symmetry(cfg))
    sym = symmetry(cfg)
    return dataclasses.replace(
        built, name=f"{built.name}/SYMMETRY({sym.set_name})", symmetry=sym
    )


def o_symmetry(cfg: Config) -> OracleSymmetry:
    sym = symmetry(cfg)
    return OracleSymmetry(
        set_name=sym.set_name,
        n=cfg.n,
        order=sym.order,
        permute=lambda s, g: o_permute(cfg, s, g),
        canonical=lambda s: o_canonical(cfg, s),
    )
