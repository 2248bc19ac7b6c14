"""`dedup.probe_sorted` alone on the chip: the blocked live-prefix search
against the full-width form (PERF.md section 6, PR 41), and the search of a
bounded window of the set against the search of its whole pinned capacity
(`--table window`; PERF.md section 6, PR 45).

    chiprun -- python scripts/probe_block_bench.py            # time on the chip
    chiprun -- python scripts/probe_block_bench.py --table window
    JAX_PLATFORMS=cpu python scripts/probe_block_bench.py --hlo  # compile only

`--table block`: for every (capacity, set size, query lanes, live share) it
times the present form (`q_n=None`, every lane) and the blocked form at each
block size.  `--table window`: the product cell's widest chunk (a set of
1,189,826 and 1,203,489 query lanes, 62% live) at capacities 2^23, 2^24 and
2^25, the whole capacity searched (`whole`) and its first 2^23 / 2^22 slots
(`window <n>`: `dedup.PROBE_WINDOW`), and `merge_counted` of a tenth of the
live lanes at the same capacities.  Both print where the optimised HLO
places the `u32[2, n]` pairs buffers (`S(1)` or not).  `--hlo` compiles for
a described v5e and runs nothing: no time comes out of it.  Writes
`chiprun_out/probe_block_bench/<table>.json` (`hlo_<table>.json`).
"""

from __future__ import annotations

import argparse
import json
import os
import re
import statistics
import sys
import time

os.environ.setdefault("TPU_LOG_DIR", "disabled")
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from kafka_specification_tpu.ops import dedup  # noqa: E402

SENT = np.uint32(dedup.SENT)
#: (capacity, set_n, query lanes): the widest chunk of `kip320-3b-*`,
#: `asyncisr-4b-constraint` and `kip279-4b-cex`, and two narrow widths
CASES = (
    (4_194_304, 1_200_000, 278_528),
    (4_194_304, 1_200_000, 475_136),
    (8_388_608, 3_100_000, 639_000),
)
LIVE = (0.31, 0.44, 1.0)
NARROW = ((4_194_304, 1_200_000, 8_192), (4_194_304, 1_200_000, 19_661))
BLOCKS = (2_048, 4_096, 8_192, 16_384, 32_768, 65_536)
#: `kip320-5b-3p-notrace`'s level 5: the set at the end of the pass, a
#: fused chunk's pooled width (147 blocks of 8,187), `probe_live_share`
WINDOW_CASES = tuple((cap, 1_189_826, 1_203_489)
                     for cap in (8_388_608, 16_777_216, 33_554_432))
WINDOW_LIVE = 0.62
WINDOWS = (8_388_608, 4_194_304)
#: no capacity reaches it: the search of the whole capacity
NO_WINDOW = 1 << 31


def make(cap, set_n, T, live, seed):
    """A sorted hashed set and a sorted query list: 70% of the live lanes
    drawn from the set (duplicates among them), the rest fresh."""
    rng = np.random.default_rng(seed)
    v = np.unique(rng.integers(0, 2**64 - 2**33, size=set_n, dtype=np.uint64))
    s_hi = np.full(cap, SENT)
    s_lo = np.full(cap, SENT)
    s_hi[:len(v)] = (v >> np.uint64(32)).astype(np.uint32)
    s_lo[:len(v)] = (v & np.uint64(0xFFFFFFFF)).astype(np.uint32)
    n = int(T * live)
    old = rng.choice(v, size=int(n * 0.7))
    q = np.sort(np.concatenate([
        old, rng.integers(0, 2**64 - 2**33, size=n - len(old),
                          dtype=np.uint64)]))
    q_hi = np.full(T, SENT)
    q_lo = np.full(T, SENT)
    q_hi[:n] = (q >> np.uint64(32)).astype(np.uint32)
    q_lo[:n] = (q & np.uint64(0xFFFFFFFF)).astype(np.uint32)
    return s_hi, s_lo, np.int32(len(v)), q_hi, q_lo, np.int32(n)


def rounds_outside(set_hi, set_lo, set_n, q_hi, q_lo, q_n):
    """The other loop order, for the table only: the rounds outside, the
    blocks of the live prefix inside, the intervals carried T wide."""
    cap, T = set_hi.shape[0], q_hi.shape[0]
    pairs = jnp.stack([set_hi, set_lo])
    k = dedup.directory_bits(T)
    nb = 1 << k
    start = jnp.concatenate([
        dedup._search(pairs, jnp.zeros((nb,), jnp.int32),
                      jnp.broadcast_to(set_n, (nb,)),
                      jnp.arange(nb, dtype=jnp.uint32) << (32 - k),
                      jnp.zeros((nb,), jnp.uint32), dedup._bit_length(set_n)),
        set_n[None]])
    rounds = dedup._bit_length(jnp.max(start[1:] - start[:-1]))
    B = dedup.even_block(T, dedup.PROBE_BLOCK)
    blocks = (q_n + (B - 1)) // B
    b = (q_hi >> (32 - k)).astype(jnp.int32)

    def one_round(_, carry):
        def block(i, carry):
            lo_i, hi_i = carry
            s = jnp.minimum(i * B, T - B)
            sl = lambda x: jax.lax.dynamic_slice(x, (s,), (B,))  # noqa: E731
            lo_b, hi_b, qh, ql = sl(lo_i), sl(hi_i), sl(q_hi), sl(q_lo)
            active = lo_b < hi_b
            mid = (lo_b + hi_b) // 2
            m = pairs[:, jnp.minimum(mid, cap - 1)]
            less = (m[0] < qh) | ((m[0] == qh) & (m[1] < ql))
            return (
                jax.lax.dynamic_update_slice(
                    lo_i, jnp.where(active & less, mid + 1, lo_b), (s,)),
                jax.lax.dynamic_update_slice(
                    hi_i, jnp.where(active & ~less, mid, hi_b), (s,)))
        return jax.lax.fori_loop(0, blocks, block, carry)

    rank, _ = jax.lax.fori_loop(0, rounds, one_round,
                                (start[b], start[b + 1]))
    live = jnp.arange(T, dtype=jnp.int32) < q_n
    at = pairs[:, jnp.minimum(rank, cap - 1)]
    found = live & (rank < set_n) & (at[0] == q_hi) & (at[1] == q_lo)
    return found, jnp.where(live, rank, 0), rounds


def variants():
    """name -> (function of the six arrays, block or None)."""
    out = {"present": (lambda sh, sl, sn, qh, ql, qn:
                       dedup.probe_sorted(sh, sl, sn, qh, ql), None)}
    for B in BLOCKS:
        out[f"block {B}"] = (dedup.probe_sorted, B)
    out["rounds outside 8192"] = (rounds_outside, 8_192)
    return out


def window_variants():
    """name -> (function of the six arrays, `dedup.PROBE_WINDOW`)."""
    out = {"whole": (dedup.probe_sorted, NO_WINDOW)}
    for W in WINDOWS:
        out[f"window {W}"] = (dedup.probe_sorted, W)

    def merge(sh, sl, sn, qh, ql, qn):
        # a tenth of the live lanes as new entries, at ranks spread over
        # the set (the probe's own answer is left out of the time)
        M = qh.shape[0]
        new_n = qn // 10
        rank = (jnp.arange(M, dtype=jnp.float32)
                * (sn.astype(jnp.float32) / M)).astype(jnp.int32)
        return dedup.merge_counted(sh, sl, sn, qh, ql, rank, new_n,
                                   sh.shape[0])

    out["merge"] = (merge, NO_WINDOW)
    return out


def pairs_layouts(text, *sizes):
    """The layouts the optimised HLO gives `u32[2, n]` (the probe's pairs
    buffers) and `u32[n]` / `s32[n]` (the merge's outputs, histogram and
    prefix sum), with counts."""
    found = [x for n in dict.fromkeys(sizes)
             for x in re.findall(r"(?:u32\[2,|[us]32\[)%d\]\{[^}]*\}" % n,
                                 text)]
    return {x: found.count(x) for x in sorted(set(found))}


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--hlo", action="store_true",
                    help="compile for a described v5e, run nothing")
    ap.add_argument("--table", choices=("block", "window"), default="block")
    ap.add_argument("--reps", type=int, default=15)
    ap.add_argument("--seed", type=int, default=2147486101)
    args = ap.parse_args()
    if args.hlo:
        from jax.experimental import topologies
        from jax.sharding import SingleDeviceSharding
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
        where = SingleDeviceSharding(topo.devices[0])
    else:
        dev = jax.devices()[0]
        print("device", dev.platform, dev.device_kind, flush=True)
        assert dev.platform == "tpu", "a time comes from the chip alone"
    rows = []
    window = args.table == "window"
    if window:
        todo = [(c, WINDOW_LIVE) for c in WINDOW_CASES]
    else:
        todo = [(c, live) for c in CASES for live in LIVE]
        todo += [(c, 1.0) for c in NARROW]
    for (cap, set_n, T), live in todo:
        names = window_variants() if window else variants()
        if not window and T < 100_000:  # one or three blocks: the shell's cost alone
            names = {k: v for k, v in names.items()
                     if k in ("present", "block 4096", "block 8192")}
        data = None if args.hlo else [
            jax.device_put(x) for x in make(cap, set_n, T, live, args.seed)]
        want = None
        for name, (fn, B) in names.items():
            if window:
                dedup.PROBE_WINDOW = B
            elif B is not None:
                dedup.PROBE_BLOCK = B
            # a function object of its own: jit keys its trace on the
            # function, and the two constants are read while tracing
            jitted = jax.jit(lambda *a, fn=fn: fn(*a))
            row = {"cap": cap, "set_n": set_n, "T": T, "live": live,
                   "variant": name}
            if args.hlo:
                u = lambda n: jax.ShapeDtypeStruct(  # noqa: E731
                    (n,), jnp.uint32, sharding=where)
                i = jax.ShapeDtypeStruct((), jnp.int32, sharding=where)
                text = jitted.lower(u(cap), u(cap), i, u(T), u(T),
                                    i).compile().as_text()
            else:
                compiled = jitted.lower(*data).compile()
                text = compiled.as_text()
                out = compiled(*data)
                jax.block_until_ready(out)
                n = int(data[5])
                got = (np.asarray(out[0])[:n], np.asarray(out[1])[:n])
                if want is None:
                    want = got
                    row["found"] = int(got[0].sum())
                elif name != "merge":  # every probe answers as the first
                    assert (got[0] == want[0]).all(), name
                    assert (got[1] == want[1]).all(), name
                    assert not np.asarray(out[0])[n:].any(), name
                ms = []
                for _ in range(args.reps):
                    t0 = time.perf_counter()
                    jax.block_until_ready(compiled(*data))
                    ms.append((time.perf_counter() - t0) * 1e3)
                row["ms_median"] = statistics.median(ms)
                row["ms_min"] = min(ms)
                row["work"] = [int(x) for x in np.atleast_1d(out[-1])]
            row["pairs"] = pairs_layouts(text, cap,
                                         *([min(cap, B)] if window else []))
            rows.append(row)
            print(json.dumps(row), flush=True)
    os.makedirs("chiprun_out/probe_block_bench", exist_ok=True)
    name = ("hlo_" if args.hlo else "") + args.table + ".json"
    with open(f"chiprun_out/probe_block_bench/{name}", "w") as f:
        json.dump(rows, f, indent=1)


if __name__ == "__main__":
    main()
