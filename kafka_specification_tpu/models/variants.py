"""L4 spec variants over the KafkaReplication core.

Each variant is a `Next` composition: the 9 disjuncts listed in its reference
module, differing only in the become-follower truncation logic
(KafkaReplication.tla:274-277):

- KafkaTruncateToHighWatermark (KafkaTruncateToHighWatermark.tla:33-42):
  truncate to own HW — known-unsafe pre-KIP-101 behavior (:23-27); expected
  to violate WeakIsr/StrongIsr.
- Kip101 (Kip101.tla:49-58): epoch-based truncation via the
  OffsetsForLeaderEpoch lookup (:27-39); still violates StrongIsr under
  consecutive fast leader changes (Kip279.tla:21-23).
- Kip279 (Kip279.tla:53-62): tail-matching truncation (:27-45); truncation is
  correct but fetch is unfenced, so StrongIsr still fails (Kip320.tla:21-35).

Fairness conjuncts in each Spec (SF/WF) concern liveness only; no liveness
property is stated anywhere in the corpus, so a safety-only BFS checker
ignores them (SURVEY.md §2.4).

Invariant selection mirrors TLC's .cfg INVARIANT list: pass the names to
check (default: all four).
"""

from __future__ import annotations

from typing import Sequence

from ..oracle.interp import OracleModel
from .base import Model
from . import kafka_replication as kr

DEFAULT_INVARIANTS = ("TypeOk", "LeaderInIsr", "WeakIsr", "StrongIsr")


def _invariant_kernels(cfg, names):
    table = {
        "TypeOk": kr.type_ok,
        "LeaderInIsr": kr.leader_in_isr,
        "LeaderInIsrLiteral": kr.leader_in_isr_literal,
        "WeakIsr": kr.weak_isr,
        "StrongIsr": kr.strong_isr,
    }
    return [table[n](cfg) for n in names]


def _invariant_oracles(cfg, names):
    table = {
        "TypeOk": kr.o_type_ok,
        "LeaderInIsr": kr.o_leader_in_isr,
        "LeaderInIsrLiteral": kr.o_leader_in_isr_literal,
        "WeakIsr": kr.o_weak_isr,
        "StrongIsr": kr.o_strong_isr,
    }
    return [table[n](cfg) for n in names]


_VARIANTS = {
    # name -> (kernel truncation offset, oracle truncation offset, citation)
    "KafkaTruncateToHighWatermark": (
        kr.truncate_to_hw_offset,
        lambda cfg: kr.o_truncate_to_hw_offset,
        "BecomeFollowerTruncateToHighWatermark",
    ),
    "Kip101": (kr.kip101_offset, lambda cfg: kr.o_kip101_offset, "BecomeFollowerTruncateKip101"),
    "Kip279": (kr.kip279_offset, lambda cfg: kr.o_kip279_offset, "BecomeFollowerTruncateKip279"),
}


def make_model(
    variant: str, cfg: kr.Config, invariants: Sequence[str] = DEFAULT_INVARIANTS,
    symmetric: bool = False,
) -> Model:
    trunc_fn, _, action_name = _VARIANTS[variant]
    spec = kr.make_spec(cfg)
    # Next (KafkaTruncateToHighWatermark.tla:33-42 / Kip101.tla:49-58 /
    # Kip279.tla:53-62): identical 9 disjuncts modulo the truncation action.
    actions = [
        kr.controller_elect_leader(cfg),
        kr.controller_shrink_isr(cfg),
        kr.become_leader(cfg),
        kr.leader_expand_isr(cfg),
        kr.leader_shrink_isr(cfg),
        kr.leader_write(cfg),
        kr.leader_inc_high_watermark(cfg),
        kr.become_follower_and_truncate_to(cfg, action_name, trunc_fn(cfg)),
        kr.follower_replicate(cfg),
    ]
    return kr.reduced(Model(
        name=f"{variant}({cfg.n}r,L{cfg.l},R{cfg.r},E{cfg.e})",
        spec=spec,
        init_states=lambda: [kr.init_state(cfg)],
        actions=actions,
        invariants=_invariant_kernels(cfg, invariants),
        decode=kr.make_decode(cfg),
        meta={"variant": variant, "cfg": cfg},
    ), cfg, symmetric)


def make_oracle(
    variant: str, cfg: kr.Config, invariants: Sequence[str] = DEFAULT_INVARIANTS,
    symmetric: bool = False,
) -> OracleModel:
    _, o_trunc_fn, action_name = _VARIANTS[variant]
    actions = [
        kr.o_controller_elect_leader(cfg),
        kr.o_controller_shrink_isr(cfg),
        kr.o_become_leader(cfg),
        kr.o_leader_expand_isr(cfg),
        kr.o_leader_shrink_isr(cfg),
        kr.o_leader_write(cfg),
        kr.o_leader_inc_high_watermark(cfg),
        kr.o_become_follower_and_truncate_to(cfg, action_name, o_trunc_fn(cfg)),
        kr.o_follower_replicate(cfg),
    ]
    return kr.reduced(OracleModel(
        name=f"{variant}-oracle",
        init_states=lambda: [kr.o_init(cfg)],
        actions=actions,
        invariants=_invariant_oracles(cfg, invariants),
        meta={"variant": variant, "cfg": cfg},
    ), cfg, symmetric)
