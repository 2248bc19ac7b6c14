"""Mosaic lowering ladder: which Pallas constructs, and which of the
repo's own Pallas kernels, compile and run on the chip.

The first rungs are single-construct kernels, from pure vector ops down
to one dynamic (1,)-slice access — the constructs a hash probe is made
of (data-dependent VMEM addressing).  The last rungs are the four opt-in
kernels themselves, `interpret=False`, each held to its jnp reference:
the fingerprint kernel at the flagship's lane width, and the three probe
kernels against the shared dedup fixture (ops/probe_fixture).

One JSON record lands in chiprun_out/mosaic_ladder.json (rewritten after
every rung, so a rung that kills the process loses nothing banked before
it).  Exits non-zero if any rung failed.

Usage:  python scripts/tpu_mosaic_ladder.py   (on a machine with a TPU)
"""

import json
import os
import sys
import time

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, _REPO)


def main():
    import jax

    from kafka_specification_tpu.utils.platform_guard import (
        device_stamp,
        enable_compile_cache,
    )

    enable_compile_cache()
    import jax.numpy as jnp
    import numpy as np
    from jax.experimental import pallas as pl

    def k_vec(x_ref, o_ref):  # pure vector op
        o_ref[:] = x_ref[:] * 3 + 7

    def k_loop_vec(x_ref, o_ref):  # fori_loop, vector body
        def body(i, acc):
            return acc + x_ref[:]

        o_ref[:] = jax.lax.fori_loop(0, 4, body, jnp.zeros_like(x_ref))

    def k_static_scalar(x_ref, o_ref):  # static scalar index
        o_ref[:] = x_ref[:]
        o_ref[pl.ds(0, 1)] = (x_ref[0] + 1)[None]

    def k_dyn_read(x_ref, o_ref):  # dynamic (1,)-slice READ only
        pos = (x_ref[0] % 7).astype(jnp.int32)
        o_ref[:] = x_ref[:] + x_ref[pl.ds(pos, 1)][0]

    def k_dyn_slice(x_ref, o_ref):  # dynamic (1,)-slice read+write
        pos = (x_ref[0] % 7).astype(jnp.int32)
        o_ref[:] = x_ref[:]
        o_ref[pl.ds(pos, 1)] = x_ref[pl.ds(pos, 1)] + 1

    def k_scalar_loop(x_ref, o_ref):  # the probe shape: scalar loop
        def body(i, c):
            v = x_ref[i]
            o_ref[pl.ds(i, 1)] = (v + 1)[None]
            return c

        jax.lax.fori_loop(0, x_ref.shape[0], body, 0)

    rungs = [
        ("vec", k_vec),
        ("loop_vec", k_loop_vec),
        ("static_scalar", k_static_scalar),
        ("dyn_read", k_dyn_read),
        ("dyn_slice", k_dyn_slice),
        ("scalar_loop", k_scalar_loop),
    ]
    record = {"started": time.time(), **device_stamp(), "rungs": {}}
    print(f"# platform: {record['platform']}", flush=True)
    if record["platform"] == "cpu":
        raise SystemExit("the ladder asks what Mosaic compiles: it needs a "
                         "TPU (the CPU runs these kernels in interpret mode "
                         "in tests/test_pallas.py)")
    out_path = os.path.join(_REPO, "chiprun_out", "mosaic_ladder.json")
    os.makedirs(os.path.dirname(out_path), exist_ok=True)

    def run(name, fn):
        t0 = time.perf_counter()
        try:
            fn()
            record["rungs"][name] = {
                "ok": True,
                "seconds": round(time.perf_counter() - t0, 2),
            }
        except Exception as e:  # noqa: BLE001 — banking the failure mode
            record["rungs"][name] = {
                "ok": False,
                "error": f"{type(e).__name__}: {str(e)[:1500]}",
            }
        print(f"# {name}: {record['rungs'][name]}", flush=True)
        with open(out_path, "w") as f:
            json.dump(record, f, indent=1)

    x = jnp.arange(256, dtype=jnp.uint32)
    for name, k in rungs:
        run(name, lambda k=k: pl.pallas_call(
            k, out_shape=jax.ShapeDtypeStruct((256,), jnp.uint32)
        )(x).block_until_ready())

    # the repo's own kernels, compiled for the chip (interpret=False)
    from kafka_specification_tpu.models import kip320
    from kafka_specification_tpu.models.kafka_replication import Config
    from kafka_specification_tpu.ops import dedup
    from kafka_specification_tpu.ops.fingerprint import fingerprint_lanes
    from kafka_specification_tpu.ops.pallas_fingerprint import (
        fingerprint_pallas,
    )
    from kafka_specification_tpu.ops.pallas_hashset import (
        MAX_VMEM_CAP,
        probe_insert_pallas,
        probe_insert_pallas_hbm,
    )
    from kafka_specification_tpu.ops.probe_fixture import (
        assert_same_winners,
        make_probe_case,
    )

    def fingerprint():
        spec = kip320.make_model(Config(3, 2, 2, 2)).spec
        rng = np.random.default_rng(3)
        lanes = jnp.asarray(rng.integers(
            0, 2**32, size=(8192, spec.num_lanes), dtype=np.uint32))
        valid = jnp.asarray(rng.random(8192) < 0.9)
        hi, lo = fingerprint_pallas(lanes, valid, block_rows=1024)
        ref_hi, ref_lo = fingerprint_lanes(lanes, spec.exact64)
        sent = jnp.uint32(dedup.SENT)
        assert np.array_equal(hi, jnp.where(valid, ref_hi, sent))
        assert np.array_equal(lo, jnp.where(valid, ref_lo, sent))

    def probe(fn, cap=1 << 12, **kw):
        case = make_probe_case(seed=11, cap=cap)
        th, tl, p_new, p_n, _ovf = fn(
            case["t_hi0"], case["t_lo0"], case["q_hi"], case["q_lo"],
            case["valid"], block_rows=256, **kw)
        assert_same_winners(case, th, tl, p_new, p_n)

    run("fingerprint_pallas", fingerprint)
    run("probe_insert_pallas_group1",
        lambda: probe(probe_insert_pallas, group=1))
    run("probe_insert_pallas_group8",
        lambda: probe(probe_insert_pallas, group=8))
    # the largest table the engine lets the VMEM-staged kernel take
    run("probe_insert_pallas_group8_max_vmem_cap",
        lambda: probe(probe_insert_pallas, cap=MAX_VMEM_CAP, group=8))
    run("probe_insert_pallas_hbm", lambda: probe(probe_insert_pallas_hbm))
    return 0 if all(r["ok"] for r in record["rungs"].values()) else 1


if __name__ == "__main__":
    raise SystemExit(main())
