"""Per-stage profile of the BFS step on the bench config (CPU).

Times, at the bench's peak chunk shape, each pipeline stage in isolation:
expand (guards+updates+pack), fingerprint, lexsort, probe+merge, and the
full step; plus the host-side bookkeeping per level. Prints a table.
"""

import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
from kafka_specification_tpu.utils.platform_guard import (  # noqa: E402
    enable_compile_cache,
    pin_cpu_in_process,
)

pin_cpu_in_process()
import jax  # noqa: E402
enable_compile_cache()
import jax.numpy as jnp
import numpy as np

from kafka_specification_tpu.engine.bfs import _Step, _next_pow2, _pad_rows
from kafka_specification_tpu.models import kip320
from kafka_specification_tpu.models.kafka_replication import Config
from kafka_specification_tpu.ops.fingerprint import fingerprint_lanes
from kafka_specification_tpu.ops import dedup
from kafka_specification_tpu.engine import check


def timeit(fn, *args, n=5):
    fn(*args)  # warm/compile
    jax.block_until_ready(fn(*args))
    t0 = time.perf_counter()
    for _ in range(n):
        out = fn(*args)
        jax.block_until_ready(out)
    return (time.perf_counter() - t0) / n


def main():
    cfg = Config(3, 2, 2, 2)
    model = kip320.make_model(cfg)
    sb = _Step(model)
    spec = model.spec
    K, C = spec.num_lanes, sb.C
    print(f"lanes={K} fanout={C} exact64={spec.exact64}")

    # build a realistic mid-run frontier: run bounded BFS to get a frontier
    levels = []
    res = check(model, max_depth=10, store_trace=False, collect_levels=levels,
                chunk_size=32768, min_bucket=4096)
    frontier = levels[-1]
    print(f"frontier at depth 10: {frontier.shape[0]} rows; totals={res.total}")

    bucket = 32768
    piece = frontier[:bucket]
    fp_n = piece.shape[0]
    bucket = _next_pow2(max(fp_n, 4096))
    vcap = _next_pow2(800_000 + bucket * C)
    # fill visited with res fingerprints
    vhi = jnp.full(vcap, 0xFFFFFFFF, jnp.uint32)
    vlo = jnp.full(vcap, 0xFFFFFFFF, jnp.uint32)
    vn = jnp.int32(0)

    shift = 2
    expand = sb.make_expand(bucket, shift)
    T_exp = sb.expand_width(bucket, shift)
    T = max(256, T_exp >> 1)
    print(f"bucket={bucket} M={bucket*C} T_exp={T_exp} T={T}")

    fr = jnp.asarray(_pad_rows(piece, bucket))
    fv = jnp.arange(bucket) < fp_n

    unpack = jax.jit(lambda f: jax.vmap(spec.unpack)(f))
    states = unpack(fr)

    t_unpack = timeit(unpack, fr)

    exp_j = jax.jit(lambda s, v: expand(s, v))
    t_expand = timeit(exp_j, states, fv)
    en_pre, cand, valid, parent, actid, act_en, act_guard, ovf = exp_j(states, fv)
    print(f"enabled={int(valid.sum())} of {valid.shape[0]}")

    # guards-only timing: build expand with shift but measure phase A alone
    def guards_only(states):
        parts = []
        for a in model.actions:
            choices = jnp.arange(a.n_choices, dtype=jnp.int32)
            ok = jax.vmap(lambda s: jax.vmap(lambda c, s=s: a.kernel(s, c)[0])(choices))(states)
            parts.append(ok)
        return jnp.concatenate(parts, axis=1)
    g_j = jax.jit(guards_only)
    t_guards = timeit(g_j, states)

    # squeeze stage
    def squeeze(cand, valid, parent, actid):
        n_en = jnp.sum(valid, dtype=jnp.int32)
        spos = jnp.where(valid, jnp.cumsum(valid) - 1, T)
        c2 = jnp.zeros((T, K), jnp.uint32).at[spos].set(cand)
        p2 = jnp.full((T,), -1, jnp.int32).at[spos].set(parent)
        a2 = jnp.full((T,), -1, jnp.int32).at[spos].set(actid)
        return c2, p2, a2, jnp.arange(T) < n_en
    sq_j = jax.jit(squeeze)
    t_squeeze = timeit(sq_j, cand, valid, parent, actid)
    cand2, parent2, actid2, valid2 = sq_j(cand, valid, parent, actid)

    # fingerprint
    sent = jnp.uint32(dedup.SENT)
    def fprint(cand, valid):
        hi, lo = fingerprint_lanes(cand, spec.exact64)
        return jnp.where(valid, hi, sent), jnp.where(valid, lo, sent)
    fp_j = jax.jit(fprint)
    t_fp = timeit(fp_j, cand2, valid2)
    hi, lo = fp_j(cand2, valid2)

    # sort
    sort_j = jax.jit(lambda hi, lo: jnp.lexsort((lo, hi)))
    t_sort = timeit(sort_j, hi, lo)
    order = sort_j(hi, lo)

    # probe + first-occurrence
    def probe(hi, lo, order, vhi, vlo, vn):
        hi_s, lo_s = hi[order], lo[order]
        invalid_s = (hi_s == sent) & (lo_s == sent)
        first = dedup.first_occurrence_mask(hi_s, lo_s, invalid_s)
        seen, rank = dedup.rank_sorted(vhi, vlo, vn, hi_s, lo_s)
        return first & ~seen, rank
    probe_j = jax.jit(probe)
    t_probe = timeit(probe_j, hi, lo, order, vhi, vlo, vn)
    is_new, rank = probe_j(hi, lo, order, vhi, vlo, vn)

    # compact + merge
    def compact_merge(is_new, rank, cand, parent, actid, order, hi, lo, vhi, vlo, vn):
        hi_s, lo_s = hi[order], lo[order]
        pos = jnp.where(is_new, jnp.cumsum(is_new) - 1, T)
        out = jnp.zeros((T, K), jnp.uint32).at[pos].set(cand[order])
        out_parent = jnp.full((T,), -1, jnp.int32).at[pos].set(parent[order])
        out_act = jnp.full((T,), -1, jnp.int32).at[pos].set(actid[order])
        out_hi = jnp.full((T,), sent).at[pos].set(hi_s)
        out_lo = jnp.full((T,), sent).at[pos].set(lo_s)
        out_rank = jnp.zeros((T,), jnp.int32).at[pos].set(rank)
        new_n = jnp.sum(is_new, dtype=jnp.int32)
        vhi2, vlo2, vn2 = dedup.merge_ranked(vhi, vlo, vn, out_hi, out_lo, out_rank, new_n, vcap)
        return out, out_parent, out_act, new_n, vhi2, vlo2, vn2
    cm_j = jax.jit(compact_merge)
    t_cm = timeit(cm_j, is_new, rank, cand2, parent2, actid2, order, hi, lo, vhi, vlo, vn)

    # invariants
    def invs(states, fv):
        outs = []
        for inv in model.invariants:
            ok = jax.vmap(inv.pred)(states)
            bad = fv & ~ok
            outs.append(jnp.any(bad))
        return jnp.stack(outs)
    inv_j = jax.jit(invs)
    t_inv = timeit(inv_j, states, fv)

    # full step for comparison
    step = sb.get(bucket, vcap, True, True, 2)
    t_step = timeit(step, fr, fv, vhi, vlo, vn)

    total = t_unpack + t_expand + t_squeeze + t_fp + t_sort + t_probe + t_cm + t_inv
    rows = [
        ("unpack", t_unpack), ("expand(2phase)", t_expand), ("  guards only", t_guards),
        ("squeeze", t_squeeze), ("fingerprint", t_fp), ("lexsort", t_sort),
        ("probe", t_probe), ("compact+merge", t_cm), ("invariants", t_inv),
        ("SUM stages", total), ("FULL STEP", t_step),
    ]
    for name, t in rows:
        print(f"{name:>16}: {t*1e3:8.2f} ms")
    nn = int(is_new.sum())
    print(f"new states this step: {nn}; step states/sec={fp_n/t_step:.0f}")


if __name__ == "__main__":
    main()
