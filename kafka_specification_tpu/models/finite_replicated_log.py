"""FiniteReplicatedLog — standalone bounded per-replica log state machine.

Reference: /root/reference/FiniteReplicatedLog.tla
  State: logs[replica] = [endOffset: 0..LogSize,
                          records: Offsets -> LogRecords \\union {Nil}]  (:41-44)
  Next == \\E replica :                                              (:115-118)
      \\/ \\E record, offset : Append(replica, record, offset)
      \\/ \\E offset : TruncateTo(replica, offset)
      \\/ \\E other # replica : ReplicateTo(replica, other)
  THEOREM Spec => []TypeOk                                           (:122)

Tensor encoding (SURVEY.md §2.2): end[N] in 0..L; rec[N, L] in {-1} + 0..R-1
(Nil = -1).  TruncateTo Nil-fills truncated slots (:108), so the dense array
is canonical by construction and bitwise fingerprinting is sound.

Choice spaces:
  Append      (replica, record): offset is forced to endOffset (:101)
  TruncateTo  (replica, offset): offset in 0..LogSize-1 (Offsets, :37)
  ReplicateTo (from, to): offset/record forced to to's endOffset / from's
              record there (:111-113)
"""

from __future__ import annotations

import jax.numpy as jnp

from ..ops.packing import Field, StateSpec
from ..oracle.interp import OracleAction, OracleModel
from .base import Action, Invariant, Model, read, write

NIL = -1


def make_model(
    n_replicas: int, log_size: int, n_records: int, force_hashed: bool = False
) -> Model:
    N, L, R = n_replicas, log_size, n_records
    spec = StateSpec(
        [
            Field("end", (N,), 0, L),
            Field("rec", (N, L), NIL, R - 1),
        ],
        force_hashed=force_hashed,
    )

    def init():
        # Init == logs = [replica |-> EmptyLog]  (FiniteReplicatedLog.tla:97,43-44)
        return [{"end": [0] * N, "rec": [[NIL] * L for _ in range(N)]}]

    def append(state, choice):
        # Append(replica, record, offset), offset = endOffset, ~IsFull (:99-103)
        r = choice // R
        record = choice % R
        end = read(state["end"], r)
        enabled = end < L
        off = jnp.minimum(end, L - 1)
        rec = write(
            state["rec"], (r, off),
            jnp.where(enabled, record, read(state["rec"], r, off)),
        )
        new_end = write(state["end"], r, jnp.where(enabled, end + 1, end))
        return enabled, {"end": new_end, "rec": rec}

    def truncate_to(state, choice):
        # TruncateTo(replica, newEndOffset <= endOffset); Nil-fill (:105-109)
        r = choice // L
        new_end = choice % L
        end = read(state["end"], r)
        enabled = new_end <= end
        offs = jnp.arange(L)
        row = jnp.where(offs < new_end, read(state["rec"], r), NIL)
        rec = write(state["rec"], r, jnp.where(enabled, row, read(state["rec"], r)))
        ends = write(state["end"], r, jnp.where(enabled, new_end, end))
        return enabled, {"end": ends, "rec": rec}

    def replicate_to(state, choice):
        # ReplicateTo(from, to) == \E offset, record : HasEntry(from, record, offset)
        #                          /\ Append(to, record, offset)   (:111-113)
        # offset forced to to's endOffset; record forced to from's entry there.
        src = choice // (N - 1)
        dst_i = choice % (N - 1)
        dst = jnp.where(dst_i >= src, dst_i + 1, dst_i)  # Replicas \ {src}
        off = read(state["end"], dst)
        enabled = (off < L) & (off < read(state["end"], src))
        offc = jnp.minimum(off, L - 1)
        record = read(state["rec"], src, offc)
        rec = write(
            state["rec"], (dst, offc),
            jnp.where(enabled, record, read(state["rec"], dst, offc)),
        )
        ends = write(state["end"], dst, jnp.where(enabled, off + 1, off))
        return enabled, {"end": ends, "rec": rec}

    def type_ok(state):
        # TypeOk (:90-95): written slots hold records, unwritten slots Nil.
        offs = jnp.arange(L)[None, :]
        written = offs < state["end"][:, None]
        rec = state["rec"]
        ok_written = jnp.all(jnp.where(written, (rec >= 0) & (rec < R), True))
        ok_unwritten = jnp.all(jnp.where(~written, rec == NIL, True))
        ok_end = jnp.all((state["end"] >= 0) & (state["end"] <= L))
        return ok_written & ok_unwritten & ok_end

    def decode(s):
        return tuple(
            tuple(int(x) for x in s["rec"][r][: int(s["end"][r])]) for r in range(N)
        )

    return Model(
        name=f"FiniteReplicatedLog(N={N},L={L},R={R})",
        spec=spec,
        init_states=init,
        actions=[
            Action("Append", N * R, append,
                   writes=frozenset({"end", "rec"})),
            Action("TruncateTo", N * L, truncate_to,
                   writes=frozenset({"end", "rec"})),
            Action("ReplicateTo", N * (N - 1), replicate_to,
                   writes=frozenset({"end", "rec"})),
        ],
        invariants=[Invariant("TypeOk", type_ok)],
        decode=decode,
    )


def make_oracle(n_replicas: int, log_size: int, n_records: int) -> OracleModel:
    """Set-semantics transcription. State = tuple over replicas of the written
    record tuple (endOffset is its length; unwritten slots are implicit Nil,
    canonical per FiniteReplicatedLog.tla:105-109)."""
    N, L, R = n_replicas, log_size, n_records

    def append(s):
        # :99-103
        for r in range(N):
            if len(s[r]) < L:
                for record in range(R):
                    yield s[:r] + (s[r] + (record,),) + s[r + 1 :]

    def truncate(s):
        # :105-109; newEndOffset in Offsets = 0..L-1 (:37,117) and <= endOffset
        for r in range(N):
            for new_end in range(min(len(s[r]), L - 1) + 1):
                yield s[:r] + (s[r][:new_end],) + s[r + 1 :]

    def replicate(s):
        # :111-113, 118
        for src in range(N):
            for dst in range(N):
                if dst == src:
                    continue
                off = len(s[dst])
                if off < L and off < len(s[src]):
                    yield s[:dst] + (s[dst] + (s[src][off],),) + s[dst + 1 :]

    return OracleModel(
        name=f"FiniteReplicatedLog(N={N},L={L},R={R})",
        init_states=lambda: [tuple(() for _ in range(N))],  # :97
        actions=[
            OracleAction("Append", append),
            OracleAction("TruncateTo", truncate),
            OracleAction("ReplicateTo", replicate),
        ],
        # TypeOk (:90-95): endOffset bounded; written slots hold LogRecords
        # (unwritten slots are implicitly Nil in this representation, which is
        # the canonical form TruncateTo maintains, :108)
        invariants=[
            (
                "TypeOk",
                lambda s: all(
                    len(log) <= L and all(0 <= rec < R for rec in log) for log in s
                ),
            )
        ],
    )
