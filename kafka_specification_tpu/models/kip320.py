"""Kip320 — the final, correct fenced replication protocol (the flagship
model), and Kip320FirstTry — the rejected truncate-on-fetch-error design.

References: /root/reference/Kip320.tla and /root/reference/Kip320FirstTry.tla
(both EXTEND Kip279, which supplies FirstNonMatchingOffsetFromTail,
Kip279.tla:39-45).

Kip320's Next (Kip320.tla:150-159) keeps the controller actions, BecomeLeader
and LeaderWrite from the core and replaces the five replica-side actions with
fenced versions (:49-148).  Its four THEOREMs (:168-171) are the corpus's
headline correctness claims: TypeOk / LeaderInIsr / WeakIsr / StrongIsr all
hold (for LeaderInIsr see the literal-vs-intent note in kafka_replication.py).

Kip320FirstTry's Next (Kip320FirstTry.tla:159-169) instead lets followers
fetch immediately and truncate on epoch mismatch at any time (:75-82); it
fails StrongIsr because the leader can advance the HW with a follower on an
older epoch (:27-39).
"""

from __future__ import annotations

from typing import Sequence

import jax.numpy as jnp

from ..oracle.interp import OracleAction, OracleModel
from .base import Action, Model, read, write
from . import kafka_replication as kr
from .kafka_replication import NONE, Config, _bit, _member, _forall_isr
from .variants import _invariant_kernels, _invariant_oracles, DEFAULT_INVARIANTS


# --------------------------------------------------------------------------
# Kip320 kernels (Kip320.tla:39-148)
# --------------------------------------------------------------------------


def _following_epoch(s, l, f):
    # IsFollowingLeaderEpoch (Kip320.tla:39-42): leader presumes leadership,
    # follower follows it, and epochs match.
    return (
        (read(s["ldr"], l) == l)
        & (read(s["ldr"], f) == l)
        & (read(s["ep"], f) == read(s["ep"], l))
    )


def fenced_follower_fetch(cfg: Config):
    # FencedFollowerFetch (Kip320.tla:49-56): FollowerReplicate, fenced on
    # the follower having the leader's epoch.
    def kernel(s, c):
        f, l = c // cfg.n, c % cfg.n
        off = read(s["end"], f)
        enabled = (
            _following_epoch(s, l, f) & (off < cfg.l) & (off < read(s["end"], l))
        )
        offc = jnp.minimum(off, cfg.l - 1)
        new_hw = jnp.minimum(read(s["hw"], l), off + 1)
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

    return Action("FencedFollowerFetch", cfg.n * cfg.n, kernel,
                  writes=kr._REPLICATE_WRITES)


def fenced_leader_inc_high_watermark(cfg: Config):
    # FencedLeaderIncHighWatermark (Kip320.tla:63-70): every ISR member must
    # be on the leader's epoch and past the HW; the leader itself must hold a
    # record at the HW.  (Quantifies leader over Replicas without a presumes
    # guard of its own — with an empty local ISR the \A is vacuous and only
    # HasOffset(leader, hw) gates; kept literal.)
    def kernel(s, l):
        hw = read(s["hw"], l)
        has_off = hw < read(s["end"], l)
        cond = _following_epoch_vec(cfg, s, l) & (s["end"] > hw)
        enabled = has_off & _forall_isr(cfg, read(s["isr"], l), cond)
        return enabled, {**s, "hw": write(s["hw"], l, jnp.minimum(hw + 1, cfg.l))}

    return Action("FencedLeaderIncHighWatermark", cfg.n, kernel,
                  writes=frozenset({"hw"}))


def _following_epoch_vec(cfg, s, l):
    """IsFollowingLeaderEpoch(l, f) for all f as a vector over f."""
    return (read(s["ldr"], l) == l) & (s["ldr"] == l) & (s["ep"] == read(s["ep"], l))


def fenced_leader_shrink_isr(cfg: Config):
    # FencedLeaderShrinkIsr (Kip320.tla:78-85): drop an ISR member that is
    # not following the current epoch or whose end offset lags.
    def kernel(s, c):
        l, f = c // cfg.n, c % cfg.n
        in_isr = (f != l) & _member(read(s["isr"], l), f)
        stale = ~_following_epoch(s, l, f) | (read(s["end"], f) < read(s["end"], l))
        ok, nxt = kr._quorum_update(s, l, read(s["isr"], l) & ~_bit(f))
        return in_isr & stale & ok, nxt

    return Action("FencedLeaderShrinkIsr", cfg.n * cfg.n, kernel,
                  writes=kr._QUORUM_WRITES)


def fenced_leader_expand_isr(cfg: Config):
    # FencedLeaderExpandIsr (Kip320.tla:110-117), guarded by
    # HasFollowerReachedHighWatermark (:94-98) and
    # HasHighWatermarkReachedCurrentEpoch (:87-92).
    def kernel(s, c):
        l, f = c // cfg.n, c % cfg.n
        outside = ~_member(read(s["isr"], l), f)
        hw = read(s["hw"], l)
        follower_at_hw = (hw == 0) | (read(s["end"], f) >= hw)  # :94-98
        hw_at_epoch = (hw == read(s["end"], l)) | (
            (hw < read(s["end"], l))
            & (read(s["repoch"], l, jnp.minimum(hw, cfg.l - 1)) == read(s["ep"], l))
        )  # :87-92
        ok, nxt = kr._quorum_update(s, l, read(s["isr"], l) | _bit(f))
        return (
            outside & _following_epoch(s, l, f) & follower_at_hw & hw_at_epoch & ok
        ), nxt

    return Action("FencedLeaderExpandIsr", cfg.n * cfg.n, kernel,
                  writes=kr._QUORUM_WRITES)


def fenced_become_follower_and_truncate(cfg: Config):
    # FencedBecomeFollowerAndTruncate (Kip320.tla:134-148): truncation is
    # fenced on the target leader being active in the request's epoch
    # (:142-143); truncation point = FirstNonMatchingOffsetFromTail.  The
    # leader = None branch (:138-140) is dead (leader ranges over Replicas).
    trunc = kr.kip279_offset(cfg)

    def kernel(s, c):
        r, e = c // (cfg.e + 1), c % (cfg.e + 1)
        l = read(s["req_ldr"], e)
        lc = jnp.clip(l, 0, cfg.n - 1)
        enabled = (
            (l >= 0)
            & (lc != r)
            & (e > read(s["ep"], r))
            & (read(s["ldr"], lc) == lc)  # ReplicaPresumesLeadership(leader) (:142)
            & (read(s["ep"], lc) == e)  # leader on the request's epoch (:143)
        )
        toff = trunc(s, lc, r)
        enabled = enabled & (toff <= read(s["end"], r))
        toff = jnp.clip(toff, 0, cfg.l)
        rid, repoch, end = kr._truncate_log(s, r, toff)
        return enabled, {
            **s,
            "rid": rid,
            "repoch": repoch,
            "end": end,
            "ep": write(s["ep"], r, e),
            "ldr": write(s["ldr"], r, lc),
            "isr": write(s["isr"], r, read(s["req_isr"], e)),
            "hw": write(s["hw"], r, jnp.minimum(toff, read(s["hw"], r))),  # (:145)
        }

    return Action("FencedBecomeFollowerAndTruncate", cfg.n * (cfg.e + 1),
                  kernel, writes=kr._BECOME_FOLLOWER_WRITES)


# --------------------------------------------------------------------------
# Kip320FirstTry kernels (Kip320FirstTry.tla:49-157)
# --------------------------------------------------------------------------


def _caught_up_to_epoch(cfg, s, l, f, end_offset):
    # IsFollowerCaughtUpToLeaderEpoch (Kip320FirstTry.tla:49-57): presumed
    # leadership + following + the records at endOffset-1 carry the same
    # epoch on both logs (ids need not match).
    base = (read(s["ldr"], l) == l) & (read(s["ldr"], f) == l)
    off = jnp.clip(end_offset - 1, 0, cfg.l - 1)
    nonzero = (
        (end_offset > 0)
        & (end_offset <= read(s["end"], l))
        & (end_offset <= read(s["end"], f))
        & (read(s["repoch"], f, off) == read(s["repoch"], l, off))
    )
    return base & ((end_offset == 0) | nonzero)


def ft_follower_truncate(cfg: Config):
    # FollowerTruncate (Kip320FirstTry.tla:75-82), guarded by
    # FollowerNeedsTruncation (:64-69).
    trunc = kr.kip279_offset(cfg)

    def kernel(s, c):
        l, f = c // cfg.n, c % cfg.n
        base = (read(s["ldr"], l) == l) & (read(s["ldr"], f) == l)
        f_end = read(s["end"], f)
        last = jnp.clip(f_end - 1, 0, cfg.l - 1)
        epoch_mismatch = (
            (f_end > 0)
            & (f_end <= read(s["end"], l))  # HasOffset(leader, f_end - 1)
            & (read(s["repoch"], l, last) != read(s["repoch"], f, last))
        )
        needs = (f_end > read(s["end"], l)) | epoch_mismatch
        toff = trunc(s, l, f)
        enabled = base & needs & (toff <= f_end)
        toff = jnp.clip(toff, 0, cfg.l)
        rid, repoch, end = kr._truncate_log(s, f, toff)
        return enabled, {
            **s,
            "rid": rid,
            "repoch": repoch,
            "end": end,
            "hw": write(s["hw"], f, jnp.minimum(toff, read(s["hw"], f))),  # (:81)
        }

    return Action("FollowerTruncate", cfg.n * cfg.n, kernel,
                  writes=kr._REPLICATE_WRITES)


def ft_improved_leader_inc_high_watermark(cfg: Config):
    # ImprovedLeaderIncHighWatermark (Kip320FirstTry.tla:90-97): every ISR
    # member caught up (by epoch) to hw+1.
    def kernel(s, l):
        hw = read(s["hw"], l)
        presumes = read(s["ldr"], l) == l
        has_entry = hw < read(s["end"], l)
        off = jnp.minimum(hw, cfg.l - 1)
        cond = (
            (s["ldr"] == l)
            & (hw + 1 <= read(s["end"], l))
            & (hw + 1 <= s["end"])
            & (read(s["repoch"], slice(None), off) == read(s["repoch"], l, off))
        )
        enabled = presumes & has_entry & _forall_isr(cfg, read(s["isr"], l), cond)
        return enabled, {**s, "hw": write(s["hw"], l, jnp.minimum(hw + 1, cfg.l))}

    return Action("ImprovedLeaderIncHighWatermark", cfg.n, kernel,
                  writes=frozenset({"hw"}))


def ft_follower_fetch(cfg: Config):
    # FollowerFetch (Kip320FirstTry.tla:103-111): replicate only when caught
    # up (by epoch) to own end offset.
    def kernel(s, c):
        f, l = c // cfg.n, c % cfg.n
        off = read(s["end"], f)
        enabled = (
            _caught_up_to_epoch(cfg, s, l, f, off)
            & (off < cfg.l)
            & (off < read(s["end"], l))
        )
        offc = jnp.minimum(off, cfg.l - 1)
        new_hw = jnp.minimum(read(s["hw"], l), off + 1)
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

    return Action("FollowerFetch", cfg.n * cfg.n, kernel,
                  writes=kr._REPLICATE_WRITES)


def ft_leader_shrink_isr(cfg: Config):
    # LeaderShrinkIsrBetterFencing (Kip320FirstTry.tla:114-120)
    def kernel(s, c):
        l, f = c // cfg.n, c % cfg.n
        in_isr = (f != l) & _member(read(s["isr"], l), f)
        lagging = ~_caught_up_to_epoch(cfg, s, l, f, read(s["end"], l))
        ok, nxt = kr._quorum_update(s, l, read(s["isr"], l) & ~_bit(f))
        return in_isr & lagging & ok, nxt

    return Action("LeaderShrinkIsrBetterFencing", cfg.n * cfg.n, kernel,
                  writes=kr._QUORUM_WRITES)


def ft_leader_expand_isr(cfg: Config):
    # LeaderExpandIsrBetterFencing (Kip320FirstTry.tla:134-141), with the
    # HasHighWatermarkReachedCurrentEpoch guard (:122-127).
    def kernel(s, c):
        l, f = c // cfg.n, c % cfg.n
        outside = ~_member(read(s["isr"], l), f)
        hw = read(s["hw"], l)
        caught = _caught_up_to_epoch(cfg, s, l, f, hw)
        hw_at_epoch = (hw == read(s["end"], l)) | (
            (hw < read(s["end"], l))
            & (read(s["repoch"], l, jnp.minimum(hw, cfg.l - 1)) == read(s["ep"], l))
        )
        ok, nxt = kr._quorum_update(s, l, read(s["isr"], l) | _bit(f))
        return outside & caught & hw_at_epoch & ok, nxt

    return Action("LeaderExpandIsrBetterFencing", cfg.n * cfg.n, kernel,
                  writes=kr._QUORUM_WRITES)


def ft_become_follower(cfg: Config):
    # BecomeFollower (Kip320FirstTry.tla:148-157): adopt the request's state,
    # keep the log and hw (no truncation on leader change in this design).
    def kernel(s, c):
        r, e = c // (cfg.e + 1), c % (cfg.e + 1)
        l = read(s["req_ldr"], e)
        lc = jnp.clip(l, 0, cfg.n - 1)
        enabled = (l >= 0) & (lc != r) & (e > read(s["ep"], r))
        return enabled, {
            **s,
            "ep": write(s["ep"], r, e),
            "ldr": write(s["ldr"], r, lc),
            "isr": write(s["isr"], r, read(s["req_isr"], e)),
        }

    return Action("BecomeFollower", cfg.n * (cfg.e + 1), kernel,
                  writes=frozenset({"ep", "ldr", "isr"}))


# --------------------------------------------------------------------------
# model factories
# --------------------------------------------------------------------------


def make_model(cfg: Config, invariants: Sequence[str] = DEFAULT_INVARIANTS,
               symmetric: bool = False) -> Model:
    """Kip320!Next (Kip320.tla:150-159)."""
    actions = [
        kr.controller_elect_leader(cfg),
        kr.controller_shrink_isr(cfg),
        kr.become_leader(cfg),
        fenced_leader_expand_isr(cfg),
        fenced_leader_shrink_isr(cfg),
        kr.leader_write(cfg),
        fenced_leader_inc_high_watermark(cfg),
        fenced_become_follower_and_truncate(cfg),
        fenced_follower_fetch(cfg),
    ]
    return kr.reduced(Model(
        name=f"Kip320({cfg.n}r,L{cfg.l},R{cfg.r},E{cfg.e})",
        spec=kr.make_spec(cfg),
        init_states=lambda: [kr.init_state(cfg)],
        actions=actions,
        invariants=_invariant_kernels(cfg, invariants),
        decode=kr.make_decode(cfg),
        meta={"variant": "Kip320", "cfg": cfg},
    ), cfg, symmetric)


def make_first_try_model(
    cfg: Config, invariants: Sequence[str] = DEFAULT_INVARIANTS,
    symmetric: bool = False,
) -> Model:
    """Kip320FirstTry!Next (Kip320FirstTry.tla:159-169)."""
    actions = [
        kr.controller_elect_leader(cfg),
        kr.controller_shrink_isr(cfg),
        kr.become_leader(cfg),
        ft_leader_expand_isr(cfg),
        ft_leader_shrink_isr(cfg),
        kr.leader_write(cfg),
        ft_improved_leader_inc_high_watermark(cfg),
        ft_become_follower(cfg),
        ft_follower_fetch(cfg),
        ft_follower_truncate(cfg),
    ]
    return kr.reduced(Model(
        name=f"Kip320FirstTry({cfg.n}r,L{cfg.l},R{cfg.r},E{cfg.e})",
        spec=kr.make_spec(cfg),
        init_states=lambda: [kr.init_state(cfg)],
        actions=actions,
        invariants=_invariant_kernels(cfg, invariants),
        decode=kr.make_decode(cfg),
        meta={"variant": "Kip320FirstTry", "cfg": cfg},
    ), cfg, symmetric)


# ==========================================================================
# oracle transcriptions
# ==========================================================================


def _o_following_epoch(s, l, f):
    # IsFollowingLeaderEpoch (Kip320.tla:39-42)
    _, rstates, *_ = s
    return (
        rstates[l][2] == l and rstates[f][2] == l and rstates[f][1] == rstates[l][1]
    )


def o_fenced_follower_fetch(cfg: Config):
    # Kip320.tla:49-56
    def successors(s):
        logs, rstates, nrid, nep, reqs, quorum = s
        for f in range(cfg.n):
            for l in range(cfg.n):
                if not _o_following_epoch(s, l, f):
                    continue
                off = len(logs[f])
                if off >= cfg.l or off >= len(logs[l]):
                    continue
                new_logs = logs[:f] + (logs[f] + (logs[l][off],),) + logs[f + 1 :]
                hwf = min(rstates[l][0], off + 1)
                _, epf, ldrf, isrf = rstates[f]
                new_rs = rstates[:f] + ((hwf, epf, ldrf, isrf),) + rstates[f + 1 :]
                yield (new_logs, new_rs, nrid, nep, reqs, quorum)

    return OracleAction("FencedFollowerFetch", successors)


def o_fenced_leader_inc_hw(cfg: Config):
    # Kip320.tla:63-70
    def successors(s):
        logs, rstates, nrid, nep, reqs, quorum = s
        for l in range(cfg.n):
            hw, ep, ldr, isr = rstates[l]
            if hw >= len(logs[l]):
                continue
            if all(
                _o_following_epoch(s, l, f) and len(logs[f]) > hw for f in isr
            ):
                new_rs = rstates[:l] + ((hw + 1, ep, ldr, isr),) + rstates[l + 1 :]
                yield (logs, new_rs, nrid, nep, reqs, quorum)

    return OracleAction("FencedLeaderIncHighWatermark", successors)


def o_fenced_leader_shrink_isr(cfg: Config):
    # Kip320.tla:78-85
    def successors(s):
        logs, rstates, *_ = s
        for l in range(cfg.n):
            isr = rstates[l][3]
            for f in sorted(isr - {l}):
                if (not _o_following_epoch(s, l, f)) or len(logs[f]) < len(logs[l]):
                    t = kr._o_quorum_update(s, l, isr - {f})
                    if t is not None:
                        yield t

    return OracleAction("FencedLeaderShrinkIsr", successors)


def _o_hw_reached_epoch(s, l):
    # HasHighWatermarkReachedCurrentEpoch (Kip320.tla:87-92)
    logs, rstates, *_ = s
    hw = rstates[l][0]
    if hw == len(logs[l]):
        return True
    return hw < len(logs[l]) and logs[l][hw][1] == rstates[l][1]


def o_fenced_leader_expand_isr(cfg: Config):
    # Kip320.tla:110-117
    def successors(s):
        logs, rstates, *_ = s
        for l in range(cfg.n):
            hw, _, _, isr = rstates[l]
            for f in range(cfg.n):
                if f in isr:
                    continue
                if not _o_following_epoch(s, l, f):
                    continue
                if not (hw == 0 or len(logs[f]) >= hw):  # :94-98
                    continue
                if not _o_hw_reached_epoch(s, l):  # :87-92
                    continue
                t = kr._o_quorum_update(s, l, isr | {f})
                if t is not None:
                    yield t

    return OracleAction("FencedLeaderExpandIsr", successors)


def o_fenced_become_follower_and_truncate(cfg: Config):
    # Kip320.tla:134-148
    def successors(s):
        logs, rstates, nrid, nep, reqs, quorum = s
        for (e, l, risr) in reqs:
            if l == NONE:
                continue
            for r in range(cfg.n):
                if r == l or e <= rstates[r][1]:
                    continue
                if rstates[l][2] != l or rstates[l][1] != e:  # :142-143
                    continue
                toff = kr.o_kip279_offset(cfg, s, l, r)
                if toff > len(logs[r]):
                    continue
                new_hw = min(toff, rstates[r][0])
                new_logs = logs[:r] + (logs[r][:toff],) + logs[r + 1 :]
                new_rs = rstates[:r] + ((new_hw, e, l, risr),) + rstates[r + 1 :]
                yield (new_logs, new_rs, nrid, nep, reqs, quorum)

    return OracleAction("FencedBecomeFollowerAndTruncate", successors)


def _o_caught_up_to_epoch(cfg, s, l, f, end_offset):
    # Kip320FirstTry.tla:49-57
    logs, rstates, *_ = s
    if rstates[l][2] != l or rstates[f][2] != l:
        return False
    if end_offset == 0:
        return True
    off = end_offset - 1
    return (
        end_offset <= len(logs[l])
        and end_offset <= len(logs[f])
        and logs[f][off][1] == logs[l][off][1]
    )


def o_ft_follower_truncate(cfg: Config):
    # Kip320FirstTry.tla:64-82
    def successors(s):
        logs, rstates, nrid, nep, reqs, quorum = s
        for l in range(cfg.n):
            for f in range(cfg.n):
                if rstates[l][2] != l or rstates[f][2] != l:
                    continue
                f_end = len(logs[f])
                mismatch = (
                    f_end > 0
                    and f_end <= len(logs[l])
                    and logs[l][f_end - 1][1] != logs[f][f_end - 1][1]
                )
                if not (f_end > len(logs[l]) or mismatch):
                    continue
                toff = kr.o_kip279_offset(cfg, s, l, f)
                if toff > f_end:
                    continue
                new_logs = logs[:f] + (logs[f][:toff],) + logs[f + 1 :]
                hwf, epf, ldrf, isrf = rstates[f]
                new_rs = (
                    rstates[:f] + ((min(toff, hwf), epf, ldrf, isrf),) + rstates[f + 1 :]
                )
                yield (new_logs, new_rs, nrid, nep, reqs, quorum)

    return OracleAction("FollowerTruncate", successors)


def o_ft_improved_inc_hw(cfg: Config):
    # Kip320FirstTry.tla:90-97
    def successors(s):
        logs, rstates, nrid, nep, reqs, quorum = s
        for l in range(cfg.n):
            hw, ep, ldr, isr = rstates[l]
            if ldr != l or hw >= len(logs[l]):
                continue
            if all(_o_caught_up_to_epoch(cfg, s, l, f, hw + 1) for f in isr):
                new_rs = rstates[:l] + ((hw + 1, ep, ldr, isr),) + rstates[l + 1 :]
                yield (logs, new_rs, nrid, nep, reqs, quorum)

    return OracleAction("ImprovedLeaderIncHighWatermark", successors)


def o_ft_follower_fetch(cfg: Config):
    # Kip320FirstTry.tla:103-111
    def successors(s):
        logs, rstates, nrid, nep, reqs, quorum = s
        for f in range(cfg.n):
            for l in range(cfg.n):
                off = len(logs[f])
                if not _o_caught_up_to_epoch(cfg, s, l, f, off):
                    continue
                if off >= cfg.l or off >= len(logs[l]):
                    continue
                new_logs = logs[:f] + (logs[f] + (logs[l][off],),) + logs[f + 1 :]
                hwf = min(rstates[l][0], off + 1)
                _, epf, ldrf, isrf = rstates[f]
                new_rs = rstates[:f] + ((hwf, epf, ldrf, isrf),) + rstates[f + 1 :]
                yield (new_logs, new_rs, nrid, nep, reqs, quorum)

    return OracleAction("FollowerFetch", successors)


def o_ft_leader_shrink(cfg: Config):
    # Kip320FirstTry.tla:114-120
    def successors(s):
        logs, rstates, *_ = s
        for l in range(cfg.n):
            isr = rstates[l][3]
            for f in sorted(isr - {l}):
                if not _o_caught_up_to_epoch(cfg, s, l, f, len(logs[l])):
                    t = kr._o_quorum_update(s, l, isr - {f})
                    if t is not None:
                        yield t

    return OracleAction("LeaderShrinkIsrBetterFencing", successors)


def o_ft_leader_expand(cfg: Config):
    # Kip320FirstTry.tla:122-141
    def successors(s):
        logs, rstates, *_ = s
        for l in range(cfg.n):
            hw, _, _, isr = rstates[l]
            for f in range(cfg.n):
                if f in isr:
                    continue
                if not _o_caught_up_to_epoch(cfg, s, l, f, hw):
                    continue
                if not _o_hw_reached_epoch(s, l):
                    continue
                t = kr._o_quorum_update(s, l, isr | {f})
                if t is not None:
                    yield t

    return OracleAction("LeaderExpandIsrBetterFencing", successors)


def o_ft_become_follower(cfg: Config):
    # Kip320FirstTry.tla:148-157
    def successors(s):
        logs, rstates, nrid, nep, reqs, quorum = s
        for (e, l, risr) in reqs:
            if l == NONE:
                continue
            for r in range(cfg.n):
                if r == l or e <= rstates[r][1]:
                    continue
                hwf = rstates[r][0]
                new_rs = rstates[:r] + ((hwf, e, l, risr),) + rstates[r + 1 :]
                yield (logs, new_rs, nrid, nep, reqs, quorum)

    return OracleAction("BecomeFollower", successors)


def make_oracle(cfg: Config, invariants: Sequence[str] = DEFAULT_INVARIANTS,
                symmetric: bool = False) -> OracleModel:
    actions = [
        kr.o_controller_elect_leader(cfg),
        kr.o_controller_shrink_isr(cfg),
        kr.o_become_leader(cfg),
        o_fenced_leader_expand_isr(cfg),
        o_fenced_leader_shrink_isr(cfg),
        kr.o_leader_write(cfg),
        o_fenced_leader_inc_hw(cfg),
        o_fenced_become_follower_and_truncate(cfg),
        o_fenced_follower_fetch(cfg),
    ]
    return kr.reduced(OracleModel(
        name="Kip320-oracle",
        init_states=lambda: [kr.o_init(cfg)],
        actions=actions,
        invariants=_invariant_oracles(cfg, invariants),
        meta={"variant": "Kip320", "cfg": cfg},
    ), cfg, symmetric)


def make_first_try_oracle(
    cfg: Config, invariants: Sequence[str] = DEFAULT_INVARIANTS,
    symmetric: bool = False,
) -> OracleModel:
    actions = [
        kr.o_controller_elect_leader(cfg),
        kr.o_controller_shrink_isr(cfg),
        kr.o_become_leader(cfg),
        o_ft_leader_expand(cfg),
        o_ft_leader_shrink(cfg),
        kr.o_leader_write(cfg),
        o_ft_improved_inc_hw(cfg),
        o_ft_become_follower(cfg),
        o_ft_follower_fetch(cfg),
        o_ft_follower_truncate(cfg),
    ]
    return kr.reduced(OracleModel(
        name="Kip320FirstTry-oracle",
        init_states=lambda: [kr.o_init(cfg)],
        actions=actions,
        invariants=_invariant_oracles(cfg, invariants),
        meta={"variant": "Kip320FirstTry", "cfg": cfg},
    ), cfg, symmetric)
