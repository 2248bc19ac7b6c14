"""AsyncIsr — the KIP-497-style AlterIsr model (standalone state machine).

Reference: /root/reference/AsyncIsr.tla.  A fixed leader (no elections,
:24-29) proposes ISR changes to the controller asynchronously; the key safety
idea is that the high watermark counts *pending* ISR members too
(`HighWatermark == Min(offsets over isr \\union pendingIsr)`, :58-60), so a
member can be added to the ISR before the controller acknowledges without
exposing unreplicated data.  Invariant: `ValidHighWatermark` (:161-162).

As written the model is infinite-state: `LeaderWrite` has no MaxOffset guard
(:117-119) and controller versions grow without bound, so a TLC run needs a
state CONSTRAINT.  Here the bounds are explicit constants (max_offset,
max_version) enforced as constraint-pruning at successor generation:
out-of-bound successors are discarded — not counted, not invariant-checked —
and the oracle applies the identical rule, so engine and oracle agree exactly.

Encoding notes (SURVEY.md §2.2): every `updates` element is created by
`ControllerWriteIsr`, which CASes version to controllerVersion+1 (:68-70), so
updates are uniquely keyed by version -> version-indexed array.  `requests`
(leader -> controller) reuse the leader's *current* version (:92-99,:107-114),
so several distinct ISRs can share a version -> encoded as a per-version
bitset over ISR subsets (`req_bits[v]` bit s <=> request (isr=s, version=v)
present); N <= 4 keeps the 2^N-bit subset lattice within one signed int32
element (the packing dtype).

WLOG the fixed `Leader` constant is replica 0.

Sizes at the encoding's limit of 4 replicas (plain oracle, PR 32; the
engine's counts are equal level by level): MaxOffset 2 / MaxVersion 2:
165,312 states, diameter 21 (`oracle_bfs` over `make_oracle`, equal to the
engine's count of ISSUE 32, `cli check --cpu --visited-backend host`);
MaxOffset 3 / MaxVersion 3 (configs/AsyncIsrFourBroker.cfg, the benchmark's
`asyncisr-4b`): 8,134,400 states, diameter 30, ValidHighWatermark holds on
every state, all 31 levels oracle-derived
(perfbench/golden/asyncisr-4b.derived.json).  Static fanout
there is 37 choice slots (4 + 16 + 4 + 4 + 1 + 4 + 4), the packed state 4
lanes, and three enabled candidates in four are duplicates by depth 14.
"""

from __future__ import annotations

from dataclasses import dataclass

import jax.numpy as jnp

from ..ops.packing import Field, StateSpec
from ..oracle.interp import OracleAction, OracleModel
from .base import Action, Invariant, Model, read, write

NIL = -1  # AsyncIsr.tla:38
LEADER = 0  # WLOG (Leader \in Replicas, :29)


@dataclass(frozen=True)
class AsyncIsrConfig:
    n_replicas: int
    max_offset: int  # CONSTANT MaxOffset (:25) — enforced as a constraint
    max_version: int  # state CONSTRAINT bound on controller/leader versions

    @property
    def n(self):
        return self.n_replicas

    @property
    def full_isr(self):
        return (1 << self.n_replicas) - 1


def check_encoding_bounds(cfg: AsyncIsrConfig) -> None:
    """The N <= 4 encoding cliff, checked wherever a config enters
    (engine spec, model, oracle): the request set is encoded as a
    per-version 2^N-bit ISR-subset bitset (`req_bits`) that must fit ONE
    signed int32 lane — 2^5 = 32 bits already overflows it.

    The DETECTOR is now the general spec-width pass
    (analysis/encoding.spec_fits_errors — every field of every model is
    held to the packed int32 element dtype at Model construction); this
    wrapper keeps the AsyncIsr-specific actionable message, and the
    oracle keeps calling it because a config the engine cannot encode
    must not be silently accepted by the cross-check path either.
    Spreading the bitset over multiple lanes is the documented extension
    path (ROADMAP.md R9)."""
    from ..analysis.encoding import EncodingUnsound, spec_fits_errors

    # bitset width 2^N, with N capped BEFORE the shift so a wild config
    # (a typo'd N of 10^12) can't make the probe allocate an N-bit
    # integer — past the cap the bound already exceeds the int32 element
    # range by construction, which is all the detector needs
    probe = Field(
        "req_bits", (cfg.max_version + 1,), 0,
        (1 << (1 << min(cfg.n, 6))) - 1,
    )
    findings = spec_fits_errors([probe], context="AsyncIsr")
    if findings:
        raise EncodingUnsound(
            f"AsyncIsr supports at most 4 replicas, got {cfg.n_replicas}: "
            "the request set is encoded as a per-version 2^N-bit subset "
            "bitset (req_bits) that must fit one signed int32 element "
            f"(2^{cfg.n_replicas} bits > 31); "
            "reduce the replica count or extend the encoding to multiple "
            "lanes",
            findings=findings,
        )


def make_spec(cfg: AsyncIsrConfig) -> StateSpec:
    N, M, V = cfg.n, cfg.max_offset, cfg.max_version
    # the per-version request bitset has 2^N bits and lives in int32 fields
    check_encoding_bounds(cfg)
    return StateSpec(
        [
            # controllerState (:48-51)
            Field("c_isr", (), 0, cfg.full_isr),
            Field("c_ver", (), 0, V),
            # leaderState (:40-46)
            Field("l_isr", (), 0, cfg.full_isr),
            Field("l_ver", (), 0, V),
            Field("l_pend", (), 0, cfg.full_isr),
            Field("l_pver", (), NIL, V),
            Field("offs", (N,), 0, M),
            # updates: version-indexed (unique by CAS, :68-70); -1 = absent
            Field("upd_isr", (V + 1,), -1, cfg.full_isr),
            # requests: per-version bitset over ISR subsets (:92-95,:107-110)
            Field("req_bits", (V + 1,), 0, (1 << (1 << N)) - 1),
        ]
    )


def init_state(cfg: AsyncIsrConfig) -> dict:
    # Init (:137-150)
    return {
        "c_isr": cfg.full_isr,
        "c_ver": 0,
        "l_isr": cfg.full_isr,
        "l_ver": 0,
        "l_pend": 0,
        "l_pver": NIL,
        "offs": [0] * cfg.n,
        "upd_isr": [-1] * (cfg.max_version + 1),
        "req_bits": [0] * (cfg.max_version + 1),
    }


def _hw(cfg, s):
    # HighWatermark (:58-60): Min of offsets over isr \union pendingIsr.
    # The union always contains the Leader (shrink never removes it, :73,:89),
    # so it is never empty.
    potential = s["l_isr"] | s["l_pend"]
    members = ((potential >> jnp.arange(cfg.n)) & 1) == 1
    return jnp.min(jnp.where(members, s["offs"], cfg.max_offset + 1))


def _bit(r):
    return jnp.int32(1) << r


def controller_shrink_isr(cfg: AsyncIsrConfig):
    # ControllerShrinkIsr (:72-79); version bound = constraint pruning
    def kernel(s, r):
        enabled = (r != LEADER) & (((s["c_isr"] >> r) & 1) == 1) & (s["c_ver"] < cfg.max_version)
        ver = jnp.minimum(s["c_ver"] + 1, cfg.max_version)
        isr = s["c_isr"] & ~_bit(r)
        return enabled, {
            **s,
            "c_isr": isr,
            "c_ver": ver,
            "upd_isr": write(s["upd_isr"], ver, isr),
        }

    return Action("ControllerShrinkIsr", cfg.n, kernel,
                  writes=frozenset({"c_isr", "c_ver", "upd_isr"}))


def controller_handle_request(cfg: AsyncIsrConfig):
    # ControllerHandleRequest (:81-86): pick any pending request whose version
    # CASes against the controller's; choice = the request's ISR subset.
    def kernel(s, subset):
        pending = ((read(s["req_bits"], s["c_ver"]) >> subset) & 1) == 1
        enabled = pending & (s["c_ver"] < cfg.max_version)
        ver = jnp.minimum(s["c_ver"] + 1, cfg.max_version)
        return enabled, {
            **s,
            "c_isr": subset,
            "c_ver": ver,
            "upd_isr": write(s["upd_isr"], ver, subset),
        }

    return Action("ControllerHandleRequest", 1 << cfg.n, kernel,
                  writes=frozenset({"c_isr", "c_ver", "upd_isr"}))


def leader_request_shrink_isr(cfg: AsyncIsrConfig):
    # LeaderRequestShrinkIsr (:88-100): request (isr \ {r}, current version);
    # pendingIsr accumulates by union (:97)
    def kernel(s, r):
        enabled = (r != LEADER) & (((s["l_isr"] >> r) & 1) == 1)
        isr = s["l_isr"] & ~_bit(r)
        return enabled, {
            **s,
            "req_bits": write(
                s["req_bits"], s["l_ver"],
                read(s["req_bits"], s["l_ver"]) | (jnp.int32(1) << isr),
            ),
            "l_pend": s["l_pend"] | isr,
            "l_pver": s["l_ver"],
        }

    return Action("LeaderRequestShrinkIsr", cfg.n, kernel,
                  writes=frozenset({"req_bits", "l_pend", "l_pver"}))


def leader_request_expand_isr(cfg: AsyncIsrConfig):
    # LeaderRequestExpandIsr (:102-115): candidate must have reached the HW
    def kernel(s, r):
        enabled = (((s["l_isr"] >> r) & 1) == 0) & (
            read(s["offs"], r) >= _hw(cfg, s)
        )
        isr = s["l_isr"] | _bit(r)
        return enabled, {
            **s,
            "req_bits": write(
                s["req_bits"], s["l_ver"],
                read(s["req_bits"], s["l_ver"]) | (jnp.int32(1) << isr),
            ),
            "l_pend": s["l_pend"] | isr,
            "l_pver": s["l_ver"],
        }

    return Action("LeaderRequestExpandIsr", cfg.n, kernel,
                  writes=frozenset({"req_bits", "l_pend", "l_pver"}))


def leader_write(cfg: AsyncIsrConfig):
    # LeaderWrite (:117-119); MaxOffset bound = constraint pruning (the TLA+
    # action itself is unguarded — see module docstring)
    def kernel(s, _):
        enabled = s["offs"][LEADER] < cfg.max_offset
        return enabled, {
            **s,
            "offs": write(
                s["offs"], LEADER,
                jnp.minimum(s["offs"][LEADER] + 1, cfg.max_offset),
            ),
        }

    return Action("LeaderWrite", 1, kernel, writes=frozenset({"offs"}))


def leader_handle_update(cfg: AsyncIsrConfig):
    # LeaderHandleUpdate (:121-129): adopt any newer update, clear pending
    def kernel(s, v):
        enabled = (read(s["upd_isr"], v) >= 0) & (v > s["l_ver"])
        return enabled, {
            **s,
            "l_isr": jnp.maximum(read(s["upd_isr"], v), 0),
            "l_ver": v,
            "l_pend": jnp.int32(0),
            "l_pver": jnp.int32(NIL),
        }

    return Action("LeaderHandleUpdate", cfg.max_version + 1, kernel,
                  writes=frozenset({"l_isr", "l_ver", "l_pend", "l_pver"}))


def follower_replicate(cfg: AsyncIsrConfig):
    # FollowerReplicate (:131-135)
    def kernel(s, r):
        enabled = (r != LEADER) & (read(s["offs"], r) < s["offs"][LEADER])
        return enabled, {
            **s,
            "offs": write(
                s["offs"], r, jnp.minimum(read(s["offs"], r) + 1, cfg.max_offset)
            ),
        }

    return Action("FollowerReplicate", cfg.n, kernel,
                  writes=frozenset({"offs"}))


def valid_high_watermark(cfg: AsyncIsrConfig):
    # ValidHighWatermark (:161-162)
    def pred(s):
        hw = _hw(cfg, s)
        members = ((s["c_isr"] >> jnp.arange(cfg.n)) & 1) == 1
        return jnp.all(jnp.where(members, s["offs"] >= hw, True))

    return Invariant("ValidHighWatermark", pred)


def type_ok(cfg: AsyncIsrConfig):
    # TypeOk (:62-66) within the constraint bounds
    def pred(s):
        return (
            (s["c_ver"] >= 0)
            & (s["c_ver"] <= cfg.max_version)
            & (s["l_ver"] >= 0)
            & (s["l_ver"] <= cfg.max_version)
            & (s["l_pver"] >= NIL)
            & (s["l_pver"] <= cfg.max_version)
            & jnp.all((s["offs"] >= 0) & (s["offs"] <= cfg.max_offset))
        )

    return Invariant("TypeOk", pred)


def make_decode(cfg: AsyncIsrConfig):
    def iset(mask):
        return frozenset(r for r in range(cfg.n) if (int(mask) >> r) & 1)

    def decode(s):
        reqs = frozenset(
            (iset(subset), v)
            for v in range(cfg.max_version + 1)
            for subset in range(1 << cfg.n)
            if (int(s["req_bits"][v]) >> subset) & 1
        )
        upds = frozenset(
            (iset(s["upd_isr"][v]), v)
            for v in range(cfg.max_version + 1)
            if int(s["upd_isr"][v]) >= 0
        )
        return (
            (iset(s["c_isr"]), int(s["c_ver"])),
            (
                iset(s["l_isr"]),
                int(s["l_ver"]),
                iset(s["l_pend"]),
                int(s["l_pver"]),
                tuple(int(x) for x in s["offs"]),
            ),
            reqs,
            upds,
        )

    return decode


def make_model(cfg: AsyncIsrConfig, invariants=("TypeOk", "ValidHighWatermark")) -> Model:
    table = {"TypeOk": type_ok, "ValidHighWatermark": valid_high_watermark}
    return Model(
        name=f"AsyncIsr({cfg.n}r,M{cfg.max_offset},V{cfg.max_version})",
        spec=make_spec(cfg),
        init_states=lambda: [init_state(cfg)],
        actions=[
            controller_shrink_isr(cfg),
            controller_handle_request(cfg),
            leader_request_shrink_isr(cfg),
            leader_request_expand_isr(cfg),
            leader_write(cfg),
            leader_handle_update(cfg),
            follower_replicate(cfg),
        ],
        invariants=[table[n](cfg) for n in invariants],
        decode=make_decode(cfg),
        meta={"variant": "AsyncIsr", "cfg": cfg},
    )


# ==========================================================================
# oracle transcription
# ==========================================================================
# state = ((c_isr, c_ver), (l_isr, l_ver, pend, pver, offs), reqs, upds)
# with isr values as frozensets, reqs/upds as frozensets of (isr, version).


def o_init(cfg: AsyncIsrConfig):
    # Init (:137-150)
    full = frozenset(range(cfg.n))
    return (
        (full, 0),
        (full, 0, frozenset(), NIL, tuple([0] * cfg.n)),
        frozenset(),
        frozenset(),
    )


def _o_hw(s):
    # HighWatermark (:58-60)
    (_, _), (l_isr, _, pend, _, offs), _, _ = s
    return min(offs[r] for r in (l_isr | pend))


def make_oracle(cfg: AsyncIsrConfig, invariants=("TypeOk", "ValidHighWatermark")) -> OracleModel:
    # the oracle itself has no bitset (frozensets), but it exists to
    # cross-check the engine — accepting a config the engine cannot
    # encode would just diverge later, so the cliff check is shared
    check_encoding_bounds(cfg)
    V, M = cfg.max_version, cfg.max_offset

    def ctrl_shrink(s):
        # :72-79 (+ version constraint)
        (c_isr, c_ver), lstate, reqs, upds = s
        if c_ver >= V:
            return
        for r in range(cfg.n):
            if r != LEADER and r in c_isr:
                isr = c_isr - {r}
                yield ((isr, c_ver + 1), lstate, reqs, upds | {(isr, c_ver + 1)})

    def ctrl_handle(s):
        # :81-86 (+ version constraint)
        (c_isr, c_ver), lstate, reqs, upds = s
        if c_ver >= V:
            return
        for (isr, ver) in reqs:
            if ver == c_ver:
                yield ((isr, c_ver + 1), lstate, reqs, upds | {(isr, c_ver + 1)})

    def leader_req_shrink(s):
        # :88-100
        cstate, (l_isr, l_ver, pend, pver, offs), reqs, upds = s
        for r in sorted(l_isr):
            if r == LEADER:
                continue
            isr = l_isr - {r}
            yield (
                cstate,
                (l_isr, l_ver, pend | isr, l_ver, offs),
                reqs | {(isr, l_ver)},
                upds,
            )

    def leader_req_expand(s):
        # :102-115
        cstate, (l_isr, l_ver, pend, pver, offs), reqs, upds = s
        hw = _o_hw(s)
        for r in range(cfg.n):
            if r in l_isr or offs[r] < hw:
                continue
            isr = l_isr | {r}
            yield (
                cstate,
                (l_isr, l_ver, pend | isr, l_ver, offs),
                reqs | {(isr, l_ver)},
                upds,
            )

    def leader_write(s):
        # :117-119 (+ MaxOffset constraint)
        cstate, (l_isr, l_ver, pend, pver, offs), reqs, upds = s
        if offs[LEADER] >= M:
            return
        offs2 = offs[:LEADER] + (offs[LEADER] + 1,) + offs[LEADER + 1 :]
        yield (cstate, (l_isr, l_ver, pend, pver, offs2), reqs, upds)

    def leader_handle_update(s):
        # :121-129
        cstate, (l_isr, l_ver, pend, pver, offs), reqs, upds = s
        for (isr, ver) in upds:
            if ver > l_ver:
                yield (cstate, (isr, ver, frozenset(), NIL, offs), reqs, upds)

    def follower_replicate(s):
        # :131-135
        cstate, (l_isr, l_ver, pend, pver, offs), reqs, upds = s
        for r in range(cfg.n):
            if r != LEADER and offs[r] < offs[LEADER]:
                offs2 = offs[:r] + (offs[r] + 1,) + offs[r + 1 :]
                yield (cstate, (l_isr, l_ver, pend, pver, offs2), reqs, upds)

    def valid_hw(s):
        # :161-162
        (c_isr, _), (_, _, _, _, offs), _, _ = s
        hw = _o_hw(s)
        return all(offs[r] >= hw for r in c_isr)

    def o_type_ok(s):
        (c_isr, c_ver), (l_isr, l_ver, pend, pver, offs), reqs, upds = s
        return (
            0 <= c_ver <= V
            and 0 <= l_ver <= V
            and NIL <= pver <= V
            and all(0 <= o <= M for o in offs)
        )

    table = {"TypeOk": o_type_ok, "ValidHighWatermark": valid_hw}
    return OracleModel(
        name="AsyncIsr-oracle",
        init_states=lambda: [o_init(cfg)],
        actions=[
            OracleAction("ControllerShrinkIsr", ctrl_shrink),
            OracleAction("ControllerHandleRequest", ctrl_handle),
            OracleAction("LeaderRequestShrinkIsr", leader_req_shrink),
            OracleAction("LeaderRequestExpandIsr", leader_req_expand),
            OracleAction("LeaderWrite", leader_write),
            OracleAction("LeaderHandleUpdate", leader_handle_update),
            OracleAction("FollowerReplicate", follower_replicate),
        ],
        invariants=[(n, table[n]) for n in invariants],
        meta={"variant": "AsyncIsr", "cfg": cfg},
    )
