"""Benchmark: distinct states/sec, exhaustive check of Kip320 (the flagship).

Runs the TPU engine on the default platform, which must be a TPU, over
Kip320 at 3 brokers (737,794 distinct states, all four invariants on — the
THEOREM workload of Kip320.tla:168-171; count pinned by the oracle), and
prints ONE JSON line.  On any other platform the headline exits non-zero:
a timing from the CPU is never reported under a device metric's name.
(The control-plane modes --serve/--fleet/--router/--sweep measure the
jax-free service layers and stay pinned to the CPU, saying so in their
"platform" field.)

vs_baseline: the reference corpus publishes no numbers (BASELINE.md) and its
external engine (TLC, Java) is not installable in this zero-egress image, so
the recorded baseline is this machine's Python oracle interpreter on the
SAME model and constants, Config(3,2,2,2) — an explicit-state BFS in
CPython, the same algorithmic role TLC's worker loop plays.  The oracle runs
the FULL 737,794-state pass (~25s), not a prefix: deep states carry longer
logs and more in-flight requests, so a shallow-prefix rate overstates the
oracle and made vs_baseline swing between rounds on identical code
(BENCH_r01 26k vs BENCH_r02 45k states/sec).

The whole benchmark runs in ONE child process on the default platform; the
parent never imports jax (the chip belongs to one process at a time, and
the child's CPU-mesh sub-benches are children of their own).
"""

import json
import os
import subprocess
import sys
import time

_CHILD_ENV = "KSPEC_BENCH_CHILD"
# child budget: compiles + TWO measured 25-level passes (emitted default +
# the hand cross-check, each with a warmup) and the cross-check legs
_TPU_TIMEOUT = int(os.environ.get("KSPEC_BENCH_TPU_TIMEOUT", "2400"))


def _child_main():
    import jax

    from kafka_specification_tpu.utils.platform_guard import (
        enable_compile_cache,
    )

    enable_compile_cache()
    platform = jax.devices()[0].platform
    print(f"# platform: {platform}", file=sys.stderr)
    if platform != "tpu":
        raise SystemExit(
            f"bench.py headline needs a TPU; the default platform is "
            f"{platform!r} (a CPU timing is never a device number)"
        )

    from kafka_specification_tpu.engine import check
    from kafka_specification_tpu.models import kip320
    from kafka_specification_tpu.models.kafka_replication import Config
    from kafka_specification_tpu.oracle.interp import oracle_bfs

    # baseline: Python-oracle BFS throughput (TLC stand-in) on the SAME
    # model + constants as the engine run below — the FULL 737,794-state
    # pass, not a prefix (deep states carry longer logs and more requests,
    # so a prefix rate overstates the oracle and made vs_baseline noisy
    # across rounds: 26k vs 45k/s on identical code, BENCH_r01 vs r02)
    cfg = Config(3, 2, 2, 2)
    t0 = time.perf_counter()
    ores = oracle_bfs(kip320.make_oracle(cfg), keep_level_sets=False)
    oracle_sps = ores.total / (time.perf_counter() - t0)
    assert ores.total == 737_794, ores.total

    # THE measured model is the path users actually get: `cli check`
    # defaults to the mechanically emitted kernels (utils/tla_emit) when
    # the reference corpus is on disk, AND to the fused level-pipeline
    # (engine/pipeline.py) — so the headline is the emitted flagship on
    # the fused successor mega-kernels.  The hand kernels and the legacy
    # per-action pipeline are both timed as cross-checks: the bench JSON
    # records the emitted-vs-hand gap and the fused-vs-legacy gap as
    # measured artifacts, plus the per-level successor-launch counts.
    # Without a reference checkout (this container ships none) the
    # emitted builders cannot run at all; the bench then measures the
    # hand kernels and says so ("reference_absent": true) instead of
    # failing the whole benchmark.
    invs = ("TypeOk", "LeaderInIsr", "WeakIsr", "StrongIsr")
    hand_model = kip320.make_model(cfg)
    model = None
    reference_absent = True
    try:
        from kafka_specification_tpu.models.emitted import make_emitted_model

        model = make_emitted_model("Kip320", cfg, invariants=invs)
        reference_absent = False
    except FileNotFoundError as e:
        print(f"# no reference checkout ({e}); measuring the hand "
              "kernels as the headline", file=sys.stderr)
        model = hand_model
    # Backend: the open-addressing HBM hash table (ops/hashset — O(batch)
    # dedup per level, device-resident)
    kwargs = dict(
        store_trace=False,
        min_bucket=32768,
        chunk_size=32768,
        visited_capacity_hint=800_000,
        visited_backend="device-hash",
        stats_path=os.devnull,  # per-level stats carry the launch counts
    )

    def run(m, pipeline):
        # One warmup pass populates the jit caches (tracing + XLA
        # compiles are a one-time cost per shape, amortized away in any
        # real checking session); the measured run is steady-state.
        check(m, pipeline=pipeline, **kwargs)
        r = check(m, pipeline=pipeline, **kwargs)
        assert r.ok, r.violation
        assert r.total == 737_794, r.total  # oracle-pinned golden count
        return r

    res = run(model, "fused")  # the headline: the CLI-default path
    lres = run(model, "legacy")  # pipeline cross-check, same kernels
    hres = res if reference_absent else run(hand_model, "fused")

    # Integrity overhead (resilience.integrity): the headline above runs
    # with the ALWAYS-ON digest path (level digest chain + per-chunk
    # folds — the production default); measure the kill-switch baseline
    # to bank the overhead honestly.  The venue is CPU-share-throttled
    # (PR 7's caveat), so single on/off runs are noise-dominated —
    # alternate on/off three times and compare best-of wall (standard
    # throttled-venue practice; everything is warm by this point).
    on_s, off_s = [], []
    for _ in range(3):
        os.environ["KSPEC_INTEGRITY"] = "0"
        r = check(model, pipeline="fused", **kwargs)
        assert r.ok and r.total == 737_794
        off_s.append(r.seconds)
        del os.environ["KSPEC_INTEGRITY"]
        r = check(model, pipeline="fused", **kwargs)
        assert r.ok and r.total == 737_794
        on_s.append(r.seconds)
    digest_overhead = 100.0 * (min(on_s) / min(off_s) - 1.0)
    # shadow re-execution per sample rate: each sampled chunk re-executes
    # through the legacy pipeline + the host fingerprint oracle, so cost
    # scales with the rate (vs the best always-on wall)
    shadow = {}
    for rate in (0.1, 0.5):
        r = check(model, pipeline="fused", integrity_shadow=rate, **kwargs)
        assert r.ok and r.total == 737_794, (r.total, r.violation)
        shadow[str(rate)] = {
            "sps": round(r.states_per_sec, 1),
            "cost_vs_always_on_pct": round(
                100.0 * (r.seconds / min(on_s) - 1.0), 1
            ),
        }
    integrity_rec = {
        "digest_on_best_s": round(min(on_s), 2),
        "digest_off_best_s": round(min(off_s), 2),
        "digest_on_walls_s": [round(s, 2) for s in on_s],
        "digest_off_walls_s": [round(s, 2) for s in off_s],
        "digest_overhead_pct": round(digest_overhead, 1),
        "shadow": shadow,
    }

    # Async overlap (PR 10, overlap.py): alternate overlap on/off on the
    # FORCED-SPILL + CHECKPOINT-CADENCE config — the configuration whose
    # storage/checkpoint wall the overlap layer exists to hide (the plain
    # headline config above has no storage I/O to overlap, so measuring
    # it there would just bank noise).  Best-of-3 alternating, same
    # throttled-venue practice as the integrity measurement.  The
    # per-level wall decomposition (compute vs exposed-I/O vs hidden-I/O)
    # comes from the engine's own per-level attribution
    # (result.stats["levels"][*]["io_hidden_ms"/"io_exposed_ms"]).
    import shutil
    import tempfile

    ov_cfg = dict(
        store_trace=False,
        min_bucket=4096,
        chunk_size=16384,
        store="disk",
        mem_budget=1 << 20,  # ~65k fps/spill -> ~11 spills + merges
        checkpoint_every=3,
        stats_path=os.devnull,
    )
    ov_on_w, ov_off_w = [], []
    ov_on_stats = ov_off_stats = None
    for _ in range(3):
        for flag in ("0", "1"):
            os.environ["KSPEC_OVERLAP"] = flag
            sd = tempfile.mkdtemp(prefix="kspec-bench-ov-")
            try:
                r = check(
                    model,
                    spill_dir=os.path.join(sd, "spill"),
                    checkpoint_dir=os.path.join(sd, "ck"),
                    **ov_cfg,
                )
            finally:
                shutil.rmtree(sd, ignore_errors=True)
            assert r.ok and r.total == 737_794, (r.total, r.violation)
            if flag == "1":
                ov_on_w.append(r.seconds)
                ov_on_stats = r.stats
            else:
                ov_off_w.append(r.seconds)
                ov_off_stats = r.stats
    del os.environ["KSPEC_OVERLAP"]

    def _decompose(stats):
        lv = stats.get("levels") or []
        wall = sum(l.get("level_ms", 0.0) for l in lv)
        step = sum(l.get("step_ms", 0.0) for l in lv)
        hid = sum(l.get("io_hidden_ms", 0.0) for l in lv)
        exp = sum(l.get("io_exposed_ms", 0.0) for l in lv)
        return {
            "wall_ms": round(wall, 1),
            "compute_ms": round(step, 1),
            "exposed_io_ms": round(exp, 1),
            "hidden_io_ms": round(hid, 1),
            "overlap_efficiency": round(
                hid / (hid + exp), 4
            ) if (hid + exp) > 0 else None,
        }

    overlap_rec = {
        "config": "forced-spill disk tier (mem_budget 1M) + "
        "checkpoint cadence 3 (the storage-heavy configuration)",
        "on_best_s": round(min(ov_on_w), 2),
        "off_best_s": round(min(ov_off_w), 2),
        "on_walls_s": [round(s, 2) for s in ov_on_w],
        "off_walls_s": [round(s, 2) for s in ov_off_w],
        "speedup": round(min(ov_off_w) / min(ov_on_w), 3),
        "speedup_target": 1.15,
        "staged_chunks_peak": ov_on_stats["overlap"]["staged_chunks_peak"],
        "decomposition_on": _decompose(ov_on_stats),
        "decomposition_off": _decompose(ov_off_stats),
        # venue honesty (the PR 7 precedent): the wall win is bounded by
        # the venue's concurrency and storage latency.  On a 1-core
        # page-cached container the hideable I/O share is the
        # decomposition's hidden+exposed over wall (~5% here), so even
        # PERFECT hiding cannot reach the 1.15x target — the mechanism
        # is proven by the decomposition (exposed ~0 with overlap on)
        # and the span-overlap tests; the wall target needs a venue
        # with >=2 cores or real storage latency.
        "venue": {
            "cores": os.cpu_count(),
            "note": "1-core CPU-share-throttled container, page-cached "
            "disk: speedup bounded by the hideable-I/O share "
            "(Amdahl), absolute walls not comparable across rounds",
        }
        if (os.cpu_count() or 1) <= 2
        else {"cores": os.cpu_count()},
    }

    # Device-resident level pipeline (PR 12, engine/pipeline.py
    # DevicePipeline): device vs fused on the SORTED-SET device visited
    # backend — the workload the device pipeline exists for (the
    # per-chunk O(capacity) merge is the measured 74% of the
    # device-backend level step; the device path pays it once per
    # LEVEL, and a whole level is one dispatched while_loop program).
    # Best-of-3 alternating, same throttled-venue practice as above.
    # chunk_size 4096 (= the compact gate) keeps per-chunk device
    # memory bounded and gives multi-chunk levels — the shape the
    # chunk-loop collapse targets.
    dv_kwargs = dict(
        store_trace=False,
        min_bucket=4096,
        chunk_size=4096,
        visited_backend="device",
        visited_capacity_hint=800_000,
        stats_path=os.devnull,
    )
    dv_w, df_w = [], []
    dv_stats = df_stats = None
    for m_, p_ in ((model, "device"), (model, "fused")):
        check(m_, pipeline=p_, max_states=60_000, **dv_kwargs)  # warm
    for _ in range(3):
        for p_ in ("device", "fused"):
            r = check(model, pipeline=p_, **dv_kwargs)
            assert r.ok and r.total == 737_794, (p_, r.total)
            if p_ == "device":
                dv_w.append(r.seconds)
                dv_stats = r.stats
            else:
                df_w.append(r.seconds)
                df_stats = r.stats
    assert dv_stats["device"]["levels"] > 0, dv_stats["device"]

    def _launch_rec(stats):
        lv = stats["levels"]
        return {
            "per_level_max": max(l["successor_launches"] for l in lv),
            "per_level_mean": round(
                sum(l["successor_launches"] for l in lv) / len(lv), 2
            ),
        }

    device_rec = {
        "config": "sorted-set device visited backend, chunk 4096 "
        "(multi-chunk levels; the per-chunk-merge-bound workload)",
        "device_sps": round(
            737_794 / min(dv_w), 1
        ),
        "fused_sps": round(737_794 / min(df_w), 1),
        "device_walls_s": [round(s, 2) for s in dv_w],
        "fused_walls_s": [round(s, 2) for s in df_w],
        "device_vs_fused": round(min(df_w) / min(dv_w), 3),
        "target": 2.0,
        "launches_per_level": {
            "device": _launch_rec(dv_stats),
            "fused": _launch_rec(df_stats),
        },
        "device_levels": dv_stats["device"]["levels"],
        "device_fallback": dv_stats["device"]["fallback"],
        # venue honesty: on this 1-core CPU container the win is the
        # per-level (vs per-chunk) visited merge + the removed per-chunk
        # host round trips; on a real accelerator the removed launch
        # round trips (2/chunk -> <=2/level) are the additional lever
        # this venue cannot price.  Same box, same config, alternating
        # runs — the ratio is the venue-independent signal.
        "venue": {"cores": os.cpu_count()},
    }

    # Device-resident levels for the HOST-FpSet backend (PR 15,
    # deferred once-per-level batched host dedup): device vs fused on
    # the backend every production-scale run to date actually used (the
    # 195.5M and 463.8M runs ride the host FpSet / disk tier — the
    # device backend needs the whole fingerprint set in HBM).  The
    # fused path pays one host sync + one FpSet insert per CHUNK; the
    # device path runs the level as one dispatched while_loop with
    # intra-level dedup on device and probes the host set ONCE per
    # level.  Best-of-3 alternating; chunk 4096 (= the compact gate)
    # gives multi-chunk levels — the O(chunks)-host-sync shape the
    # deferred probe collapses.
    dh_kwargs = dict(
        store_trace=False,
        min_bucket=4096,
        chunk_size=4096,
        visited_backend="host",
        stats_path=os.devnull,
    )
    dh_w, fh_w = [], []
    dh_stats = fh_stats = None
    for p_ in ("device", "fused"):
        check(model, pipeline=p_, max_states=60_000, **dh_kwargs)  # warm
    for _ in range(3):
        for p_ in ("device", "fused"):
            r = check(model, pipeline=p_, **dh_kwargs)
            assert r.ok and r.total == 737_794, (p_, r.total)
            if p_ == "device":
                dh_w.append(r.seconds)
                dh_stats = r.stats
            else:
                fh_w.append(r.seconds)
                fh_stats = r.stats
    assert dh_stats["device"]["levels"] > 0, dh_stats["device"]
    # only levels that actually ran the deferred probe carry the key —
    # averaging the others in as 0.0 would dilute the per-probe figure
    probe_ms = [
        l["host_probe_ms"] for l in dh_stats["levels"]
        if "host_probe_ms" in l
    ]
    # forced-spill disk tier, single alternating pass (the tier rides
    # the same deferred probe; the signal here is that the batched
    # sorted run probe keeps the disk tier AT LEAST at parity — full
    # best-of-3 would double the bench wall for a secondary signal)
    dsk = {}
    for p_ in ("device", "fused"):
        sd = tempfile.mkdtemp(prefix="kspec-bench-dh-")
        try:
            r = check(
                model,
                pipeline=p_,
                store="disk",
                mem_budget=1 << 20,
                spill_dir=os.path.join(sd, "spill"),
                **{k: v for k, v in dh_kwargs.items()
                   if k != "visited_backend"},
            )
        finally:
            shutil.rmtree(sd, ignore_errors=True)
        assert r.ok and r.total == 737_794, (p_, r.total)
        dsk[p_] = r
    assert dsk["device"].stats["device"]["levels"] > 0
    device_host_rec = {
        "config": "host-FpSet backend (C arena), chunk 4096 "
        "(multi-chunk levels; the O(chunks)-host-sync workload)",
        "device_sps": round(737_794 / min(dh_w), 1),
        "fused_sps": round(737_794 / min(fh_w), 1),
        "device_walls_s": [round(s, 2) for s in dh_w],
        "fused_walls_s": [round(s, 2) for s in fh_w],
        "device_vs_fused": round(min(fh_w) / min(dh_w), 3),
        "target": 1.5,
        "launches_per_level": {
            "device": _launch_rec(dh_stats),
            "fused": _launch_rec(fh_stats),
        },
        "host_probe_ms_mean": round(
            sum(probe_ms) / max(len(probe_ms), 1), 2
        ),
        "device_levels": dh_stats["device"]["levels"],
        "device_fallback": dh_stats["device"]["fallback"],
        "disk_tier": {
            "config": "forced-spill disk tier (mem_budget 1M), chunk "
            "4096, single alternating pass",
            "device_s": round(dsk["device"].seconds, 2),
            "fused_s": round(dsk["fused"].seconds, 2),
            "device_vs_fused": round(
                dsk["fused"].seconds / dsk["device"].seconds, 3
            ),
            "spills": dsk["device"].stats["spill"]["spills"],
        },
        # venue honesty (the PR 10 Amdahl-note / PR 13 multiprocess
        # precedent): on this 1-core CPU container the ratio INVERTS —
        # the deferred path's in-jit per-chunk lexsort + level-new
        # merge compete for the SAME core that runs the C hash insert
        # they replace, and a C open-addressing insert is far cheaper
        # than an XLA:CPU sort, so the fused per-chunk path (no device
        # dedup at all on this backend) wins the wall here.  What this
        # venue CANNOT price is the lever the path exists for: host
        # syncs 1/level vs O(chunks) and successor launches <=2/level
        # vs 2/chunk, each a device->host round trip on a real
        # accelerator (~1.2s/level on the pre-PR-1 engine,
        # TPU_PROFILE.jsonl).  The venue-independent signals banked
        # here: launches/level max 2 vs 42, ONE batched probe per
        # level at ~4ms (the engine's measured host_ms drops ~4x), and
        # bit-identity across the whole matrix.  The >=1.5x wall
        # target needs an accelerator venue where device compute and
        # host FpSet run on different silicon.
        "venue": {
            "cores": os.cpu_count(),
            "note": "1-core CPU venue: the in-jit sort/dedup and the "
            "C FpSet share one core, so removing host syncs cannot "
            "pay; ratio meaningful only on a real accelerator "
            "(see launches/probe structural signals)",
        },
    }

    # Exchange compression on the 8-device CI mesh (ROADMAP item 5's
    # measure): run in a sub-child — the virtual 8-device platform must
    # be configured before jax initializes, which this process already
    # did.  Failure degrades to exchange=null, never the whole bench.
    exchange_rec = None
    try:
        env = dict(os.environ)
        env["KSPEC_BENCH_EXCHANGE"] = "1"
        env["KSPEC_EXCHANGE_COMPRESS"] = "1"  # measuring the codec IS the point
        env["JAX_PLATFORMS"] = "cpu"
        env["XLA_FLAGS"] = (
            env.get("XLA_FLAGS", "")
            + " --xla_force_host_platform_device_count=8"
        ).strip()
        p = subprocess.run(
            [sys.executable, os.path.abspath(__file__)],
            env=env,
            timeout=int(os.environ.get("KSPEC_BENCH_EXCH_TIMEOUT", "1500")),
            capture_output=True,
            text=True,
        )
        if p.returncode == 0:
            exchange_rec = json.loads(p.stdout.strip().splitlines()[-1])
        else:
            print(
                "# exchange sub-bench failed (rc="
                f"{p.returncode}): {p.stderr[-300:]}",
                file=sys.stderr,
            )
    except Exception as e:  # noqa: BLE001 — degrade, never fail the bench
        print(f"# exchange sub-bench error: {e}", file=sys.stderr)

    # Sharded device-resident level pipeline + the multi-process
    # wall-breaker attempt (PR 13): same sub-child pattern as the
    # exchange leg — the 4-device virtual platform must be configured
    # before jax initializes.  Failure degrades to sharded_device=null,
    # never the whole bench.
    sharded_device_rec = None
    try:
        env = dict(os.environ)
        env["KSPEC_BENCH_SHARDED_DEVICE"] = "1"
        env["JAX_PLATFORMS"] = "cpu"
        env["XLA_FLAGS"] = (
            env.get("XLA_FLAGS", "")
            + " --xla_force_host_platform_device_count=4"
        ).strip()
        p = subprocess.run(
            [sys.executable, os.path.abspath(__file__)],
            env=env,
            timeout=int(
                os.environ.get("KSPEC_BENCH_SDEV_TIMEOUT", "2400")
            ),
            capture_output=True,
            text=True,
        )
        if p.returncode == 0:
            sharded_device_rec = json.loads(
                p.stdout.strip().splitlines()[-1]
            )
        else:
            print(
                "# sharded-device sub-bench failed (rc="
                f"{p.returncode}): {p.stderr[-300:]}",
                file=sys.stderr,
            )
    except Exception as e:  # noqa: BLE001 — degrade, never fail the bench
        print(f"# sharded-device sub-bench error: {e}", file=sys.stderr)

    def launches(r):
        lv = r.stats["levels"]
        return {
            "per_chunk_max": max(l["launches_per_chunk_max"] for l in lv),
            "per_level_max": max(l["successor_launches"] for l in lv),
        }

    kernel_source = "hand" if reference_absent else "emitted"
    print(
        json.dumps(
            {
                "metric": "Kip320 3-broker exhaustive check (737,794 "
                f"states, 4 invariants), {kernel_source.upper()} kernels, "
                "FUSED successor-mega-kernel pipeline (the cli default "
                "path), distinct states/sec",
                "value": round(res.states_per_sec, 1),
                "unit": "states/sec",
                "vs_baseline": round(res.states_per_sec / oracle_sps, 2),
                "platform": platform,
                "kernel_source": kernel_source,
                "reference_absent": reference_absent,
                "pipeline": {
                    "fused_sps": round(res.states_per_sec, 1),
                    "legacy_sps": round(lres.states_per_sec, 1),
                    "fused_vs_legacy": round(
                        res.states_per_sec / lres.states_per_sec, 2
                    ),
                    "fallback": res.stats.get("pipeline_fallback", False),
                },
                "kernel_launches": {
                    "fused": launches(res),
                    "legacy": launches(lres),
                },
                "emitted_vs_hand": (
                    None if reference_absent
                    else round(res.states_per_sec / hres.states_per_sec, 2)
                ),
                "hand_sps": round(hres.states_per_sec, 1),
                "integrity": integrity_rec,
                "overlap": overlap_rec,
                "device_resident": device_rec,
                "device_host_backend": device_host_rec,
                "exchange": exchange_rec,
                "sharded_device": sharded_device_rec,
            }
        )
    )
    print(
        f"# device-resident pipeline (sorted-set device backend, "
        f"chunk 4096): device {device_rec['device_sps']:,.0f} vs fused "
        f"{device_rec['fused_sps']:,.0f} states/sec = "
        f"{device_rec['device_vs_fused']}x (target >=2x); launches/"
        f"level max {device_rec['launches_per_level']['device']['per_level_max']}"
        f" device vs {device_rec['launches_per_level']['fused']['per_level_max']}"
        f" fused",
        file=sys.stderr,
    )
    dh = device_host_rec
    print(
        f"# device-resident HOST backend (C-arena FpSet, chunk 4096): "
        f"device {dh['device_sps']:,.0f} vs fused "
        f"{dh['fused_sps']:,.0f} states/sec = {dh['device_vs_fused']}x "
        f"(target >=1.5x); launches/level max "
        f"{dh['launches_per_level']['device']['per_level_max']} device "
        f"vs {dh['launches_per_level']['fused']['per_level_max']} "
        f"fused; batched probe {dh['host_probe_ms_mean']}ms/level; "
        f"disk tier {dh['disk_tier']['device_vs_fused']}x "
        f"({dh['disk_tier']['spills']} spills)",
        file=sys.stderr,
    )
    print(
        f"# overlap (forced-spill + ckpt cadence): on "
        f"{overlap_rec['on_best_s']}s vs off {overlap_rec['off_best_s']}s "
        f"= {overlap_rec['speedup']}x; hidden/exposed io "
        f"{overlap_rec['decomposition_on']['hidden_io_ms']:.0f}/"
        f"{overlap_rec['decomposition_on']['exposed_io_ms']:.0f} ms",
        file=sys.stderr,
    )
    if exchange_rec:
        print(
            f"# exchange (8-device CI mesh): "
            f"{exchange_rec['bytes_per_level_compressed']:,} B/level "
            f"compressed vs {exchange_rec['bytes_per_level_raw']:,} raw = "
            f"{exchange_rec['ratio']}x fewer bytes",
            file=sys.stderr,
        )
    if sharded_device_rec:
        sd, mp = sharded_device_rec, sharded_device_rec["multiprocess"]
        print(
            f"# sharded device (4-device mesh, chunk 1024): device "
            f"{sd['device_sps']:,.0f} vs per-chunk "
            f"{sd['perchunk_sps']:,.0f} states/sec = "
            f"{sd['device_vs_perchunk']}x; launches/level/shard max "
            f"{sd['launches_per_level']['device']['per_level_per_shard_max']}"
            f" device vs "
            f"{sd['launches_per_level']['perchunk']['per_level_per_shard_max']}"
            f" per-chunk; multiprocess P={mp['procs']}: "
            + ("supported" if mp.get("supported")
               else f"NOT runnable here ({mp.get('reason', '?')[:120]})"),
            file=sys.stderr,
        )
    print(
        f"# {kernel_source} fused (default path): {res.seconds:.1f}s wall "
        f"on {platform}, diameter {res.diameter}; legacy pipeline same "
        f"kernels: {lres.states_per_sec:,.0f} states/sec "
        f"({lres.seconds:.1f}s); hand fused: {hres.states_per_sec:,.0f} "
        f"states/sec; oracle baseline {oracle_sps:.0f} states/sec",
        file=sys.stderr,
    )
    print(
        f"# integrity: always-on digest path "
        f"{integrity_rec['digest_overhead_pct']:+.1f}% wall vs "
        f"kill-switch baseline (best-of-3 alternating, "
        f"{min(on_s):.2f}s vs {min(off_s):.2f}s); shadow "
        + ", ".join(
            f"rate {k}: {v['cost_vs_always_on_pct']:+.1f}%"
            for k, v in shadow.items()
        ),
        file=sys.stderr,
    )


# Backend noise the child's stderr can carry into the banked BENCH tail:
# XLA:CPU's "Compile machine features ... vs host machine features ... This
# could lead to execution errors such as SIGILL" advisory (one huge line,
# BENCH_r05.json), absl/TF-style log-prefix lines, and the pre-absl-init
# warning.  Filtered before re-emission so the tail the bench driver banks
# holds only the benchmark lines (the '# ...' side-notes and the JSON).
_NOISE_MARKERS = (
    "machine features:",
    "execution errors such as SIGILL",
    "WARNING: All log messages before absl::InitializeLog",
    "TF-TRT Warning",
)
_NOISE_PREFIXES = ("E0000", "W0000", "I0000", "F0000")


def _filter_backend_noise(text: str) -> str:
    """Drop known backend-noise lines from child stderr; keep everything
    else (benchmark side-notes, tracebacks, real warnings)."""
    kept = []
    for line in text.splitlines():
        s = line.strip()
        if any(m in s for m in _NOISE_MARKERS):
            continue
        if s.split(" ", 1)[0][:5] in _NOISE_PREFIXES:
            continue
        kept.append(line)
    return "\n".join(kept) + ("\n" if kept else "")


def _run_child(timeout: int) -> int:
    """Run this script's headline as a child on the default platform;
    relays its output and returns its exit code."""
    env = dict(os.environ)
    env[_CHILD_ENV] = "1"
    try:
        p = subprocess.run(
            [sys.executable, os.path.abspath(__file__)],
            env=env,
            timeout=timeout,
            capture_output=True,
            text=True,
        )
    except subprocess.TimeoutExpired as e:
        err = e.stderr or ""
        if isinstance(err, bytes):
            err = err.decode()
        print(
            f"# bench child timed out after {timeout}s; "
            f"stderr tail: {_filter_backend_noise(err)[-300:]}",
            file=sys.stderr,
        )
        return 124
    sys.stderr.write(_filter_backend_noise(p.stderr))
    sys.stdout.write(p.stdout)
    return p.returncode


def _serve_bench():
    """`bench.py --serve`: checking-as-a-service latency benchmark.

    Spawns one `cli serve` daemon (pinned to CPU — the deterministic CI
    venue the acceptance bar names), warms each toy schema shape once,
    then submits a burst of concurrent jobs and measures the
    submit->verdict latency distribution plus the compile-cache hit rate.
    Prints ONE JSON line (banked as BENCH_SERVE_r06.json).  The parent
    never imports jax (the tenant-side contract under test)."""
    import tempfile
    import threading

    from kafka_specification_tpu.service.queue import JobQueue
    from kafka_specification_tpu.utils.platform_guard import cpu_env

    shapes = {
        "IdSequence": (
            "IdSequence",
            "SPECIFICATION Spec\nCONSTANTS\n    MaxId = 10\n"
            "INVARIANTS TypeOk\nCHECK_DEADLOCK FALSE\n",
        ),
        "FiniteReplicatedLog": (
            "FiniteReplicatedLog",
            "SPECIFICATION Spec\nCONSTANTS\n    Replicas = {r1, r2}\n"
            "    LogSize = 2\n    LogRecords = {a, b}\n    Nil = Nil\n"
            "INVARIANTS TypeOk\nCHECK_DEADLOCK FALSE\n",
        ),
        "TruncateTiny": (
            "KafkaTruncateToHighWatermark",
            "SPECIFICATION Spec\nCONSTANTS\n    Replicas = {b1, b2}\n"
            "    LogSize = 2\n    MaxRecords = 1\n    MaxLeaderEpoch = 1\n"
            "INVARIANTS TypeOk WeakIsr\nCHECK_DEADLOCK FALSE\n",
        ),
    }
    jobs_per_shape = int(os.environ.get("KSPEC_SERVE_BENCH_JOBS", "10"))
    svc = tempfile.mkdtemp(prefix="kspec-serve-bench-")
    q = JobQueue(svc)
    env = cpu_env()
    daemon_log = open(os.path.join(svc, "daemon-stderr.log"), "w")
    daemon = subprocess.Popen(
        [
            sys.executable, "-m", "kafka_specification_tpu.utils.cli",
            "serve", svc, "--idle-exit", "900", "--min-bucket", "32",
            # venue-matched backend, same choice the headline bench makes
            # for its CPU fallback: the native host FpSet is the fastest
            # dedup when the "device" IS the host, and it keeps the warm
            # path free of device visited-set capacity management
            "--visited-backend", "host",
        ],
        env=env,
        stdout=subprocess.DEVNULL,
        stderr=daemon_log,
    )

    def wait_verdict(jid, timeout=900.0):
        """wait_result + daemon liveness: a daemon that died at startup
        must fail the bench in seconds with its stderr, not burn the
        full timeout per job with no diagnostic."""
        deadline = time.time() + timeout
        while time.time() < deadline:
            rec = q.result(jid)
            if rec is not None:
                return rec
            if daemon.poll() is not None:
                daemon_log.flush()
                with open(daemon_log.name) as fh:
                    tail = fh.read()[-2000:]
                raise SystemExit(
                    f"serve bench: daemon exited rc={daemon.returncode} "
                    f"before verdict for {jid}; stderr tail:\n{tail}"
                )
            time.sleep(0.05)
        return None
    try:
        # warm pass: pays model build + compiles once per shape, for BOTH
        # engine paths a burst can hit — a singleton group runs real solo
        # check() (invariant-checking step variants) while groups >= 2 run
        # the shared batched exploration (invariant-free variants), so
        # each shape warms with one solo job, then a coalescing pair
        t_warm = time.time()
        warm = [
            q.submit(text, module, tenant="bench", kernel_source="hand")
            for module, text in shapes.values()
        ]
        for spec in list(warm):
            if wait_verdict(spec["job_id"]) is None:
                raise SystemExit("serve bench: warmup verdict never arrived")
        warm += [
            q.submit(text, module, tenant="bench", kernel_source="hand")
            for module, text in shapes.values()
            for _ in range(2)
        ]
        for spec in warm:
            rec = wait_verdict(spec["job_id"])
            if rec is None:
                raise SystemExit("serve bench: warmup verdict never arrived")
            if rec["exit_code"] not in (0, 1):
                raise SystemExit(f"serve bench: warmup failed: {rec}")
        warm_s = time.time() - t_warm

        # measured burst: concurrent submitters across the warmed shapes
        ids = []
        lock = threading.Lock()

        def submit(module, text):
            spec = q.submit(text, module, tenant="bench",
                            kernel_source="hand")
            with lock:
                ids.append(spec["job_id"])

        threads = [
            threading.Thread(target=submit, args=shapes[name])
            for name in shapes
            for _ in range(jobs_per_shape)
        ]
        t_burst = time.time()
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        lat = []
        for jid in ids:
            rec = wait_verdict(jid)
            if rec is None:
                raise SystemExit(f"serve bench: no verdict for {jid}")
            if rec["exit_code"] not in (0, 1):
                raise SystemExit(f"serve bench: job failed: {rec}")
            lat.append(rec["timing"]["latency_s"])
        burst_s = time.time() - t_burst
    finally:
        daemon.terminate()
        try:
            daemon.wait(timeout=30)
        except subprocess.TimeoutExpired:
            daemon.kill()
            daemon.wait()
        daemon_log.close()

    lat.sort()

    def pct(p):
        return round(lat[min(len(lat) - 1, int(p * len(lat)))], 3)

    # cache + batching accounting from the daemon's own metrics export
    hits = misses = batched = groups = 0
    try:
        with open(os.path.join(svc, "service", "metrics.jsonl")) as fh:
            last = json.loads(fh.read().splitlines()[-1])
        c = last.get("counters", {})
        hits = c.get("kspec_svc_cache_hits_total", 0)
        misses = c.get("kspec_svc_cache_misses_total", 0)
        batched = c.get("kspec_svc_batched_jobs_total", 0)
        groups = c.get("kspec_svc_groups_total", 0)
    except (OSError, ValueError, IndexError):
        pass
    n = len(lat)
    rec = {
        "bench": "serve",
        "platform": "cpu",
        "schema_shapes": len(shapes),
        "warmup_s": round(warm_s, 3),
        "concurrent_jobs": n,
        "burst_wall_s": round(burst_s, 3),
        "p50_s": pct(0.50),
        "p95_s": pct(0.95),
        "max_s": round(lat[-1], 3),
        "jobs_per_sec": round(n / max(burst_s, 1e-9), 2),
        "compile_cache": {
            "hits": hits,
            "misses": misses,
            "hit_rate": round(hits / max(1, hits + misses), 4),
        },
        "batched_jobs": batched,
        "engine_runs": groups,
        "target": {"p50_s": 2.0, "concurrent_jobs": 25},
        "pass": bool(pct(0.50) < 2.0 and n >= 25),
    }
    print(json.dumps(rec))


def _fleet_bench():
    """`bench.py --fleet`: the serving-fleet + state-space-cache bench
    (ROADMAP item 3 acceptance; banked as the `fleet` section of
    BENCH_r14.json).

    Phase A — 100+ concurrent jobs across a 2-daemon fleet with the
    state cache OFF (the honest engine-serving measurement: with the
    cache on, a burst of identical configs is mostly O(verify) hits).
    Phase B — cache economics on a fresh fleet with the cache ON: cold
    submit->verdict latency vs repeat-check (chain-verified hit)
    latency for the same config, plus a config-delta (boundary-seeded)
    check.  The parent never imports jax.

    VENUE-HONEST: this container exposes ONE schedulable core, so two
    daemons time-share it — burst p50/p95 measures queueing + batching
    economics, not hardware parallelism; the venue-independent signals
    are exactly-once verdicts under the fleet and the cold/hit latency
    ratio."""
    import tempfile
    import threading

    from kafka_specification_tpu.service.fleet import (
        FleetManager,
        FleetServeConfig,
    )
    from kafka_specification_tpu.service.queue import JobQueue
    from kafka_specification_tpu.utils.platform_guard import cpu_env

    shapes = {
        "IdSequence": (
            "IdSequence",
            "SPECIFICATION Spec\nCONSTANTS\n    MaxId = 10\n"
            "INVARIANTS TypeOk\nCHECK_DEADLOCK FALSE\n",
        ),
        "FiniteReplicatedLog": (
            "FiniteReplicatedLog",
            "SPECIFICATION Spec\nCONSTANTS\n    Replicas = {r1, r2}\n"
            "    LogSize = 2\n    LogRecords = {a, b}\n    Nil = Nil\n"
            "INVARIANTS TypeOk\nCHECK_DEADLOCK FALSE\n",
        ),
        "TruncateTiny": (
            "KafkaTruncateToHighWatermark",
            "SPECIFICATION Spec\nCONSTANTS\n    Replicas = {b1, b2}\n"
            "    LogSize = 2\n    MaxRecords = 1\n    MaxLeaderEpoch = 1\n"
            "INVARIANTS TypeOk WeakIsr\nCHECK_DEADLOCK FALSE\n",
        ),
    }
    jobs_per_shape = int(os.environ.get("KSPEC_FLEET_BENCH_JOBS", "36"))
    n_daemons = int(os.environ.get("KSPEC_FLEET_BENCH_DAEMONS", "2"))

    def start_fleet(svc, extra_serve_args=()):
        cfg = FleetServeConfig(
            service_dir=svc,
            daemons=n_daemons,
            min_daemons=n_daemons,
            max_daemons=n_daemons,
            poll_s=0.2,
            stall_timeout=300.0,  # a cold compile must not read as a wedge
            serve_args=("--min-bucket", "32", "--visited-backend", "host")
            + tuple(extra_serve_args),
            env=cpu_env(),
        )
        mgr = FleetManager(cfg)
        t = threading.Thread(target=mgr.run, daemon=True)
        t.start()
        return mgr, t

    def wait_verdict(q, mgr, jid, timeout=900.0):
        deadline = time.time() + timeout
        while time.time() < deadline:
            rec = q.result(jid)
            if rec is not None:
                return rec
            if all(s.state == "halted" for s in mgr.slots):
                raise SystemExit(
                    f"fleet bench: every daemon halted before {jid}; "
                    f"see {mgr.events_path} and {mgr.log_dir}"
                )
            time.sleep(0.05)
        raise SystemExit(f"fleet bench: no verdict for {jid}")

    # ---- phase A: 100+ concurrent, state cache OFF -----------------------
    svc_a = tempfile.mkdtemp(prefix="kspec-fleet-bench-")
    qa = JobQueue(svc_a)
    mgr_a, t_a = start_fleet(svc_a, ("--no-state-cache",))
    try:
        warm = [
            qa.submit(text, module, tenant="bench", kernel_source="hand")
            for module, text in shapes.values()
        ]
        for spec in list(warm):
            wait_verdict(qa, mgr_a, spec["job_id"])
        warm += [
            qa.submit(text, module, tenant="bench", kernel_source="hand")
            for module, text in shapes.values()
            for _ in range(2)
        ]
        for spec in warm:
            rec = wait_verdict(qa, mgr_a, spec["job_id"])
            if rec["exit_code"] not in (0, 1):
                raise SystemExit(f"fleet bench: warmup failed: {rec}")

        ids = []
        submit_errors = []
        lock = threading.Lock()

        def submit(module, text):
            # a failed submit must FAIL the bench, not silently shrink
            # the measured set (percentiles over fewer jobs would still
            # "pass")
            try:
                spec = qa.submit(text, module, tenant="bench",
                                 kernel_source="hand")
            except Exception as e:  # noqa: BLE001 — re-raised after join
                with lock:
                    submit_errors.append(e)
                return
            with lock:
                ids.append(spec["job_id"])

        threads = [
            threading.Thread(target=submit, args=shapes[name])
            for name in shapes
            for _ in range(jobs_per_shape)
        ]
        t_burst = time.time()
        for th in threads:
            th.start()
        for th in threads:
            th.join()
        if submit_errors:
            raise SystemExit(
                f"fleet bench: {len(submit_errors)} submits failed "
                f"(first: {submit_errors[0]!r})"
            )
        lat = []
        for jid in ids:
            rec = wait_verdict(qa, mgr_a, jid)
            if rec["exit_code"] not in (0, 1):
                raise SystemExit(f"fleet bench: job failed: {rec}")
            lat.append(rec["timing"]["latency_s"])
        burst_s = time.time() - t_burst
        # exactly-once visibility across the fleet
        ov = qa.overview()
        if ov["counts"]["pending"] or ov["counts"]["claimed"]:
            raise SystemExit(f"fleet bench: jobs left behind: {ov}")
    finally:
        mgr_a.request_stop()
        t_a.join(timeout=30)

    # ---- phase B: cache economics (cold vs chain-verified hit) -----------
    svc_b = tempfile.mkdtemp(prefix="kspec-fleet-bench-cache-")
    qb = JobQueue(svc_b)
    mgr_b, t_b = start_fleet(svc_b)
    module, text = shapes["TruncateTiny"]
    repeats = 10
    try:
        # cold (includes the shape's compile; measured as a tenant sees it)
        t0 = time.time()
        spec = qb.submit(text, module, tenant="bench", kernel_source="hand")
        wait_verdict(qb, mgr_b, spec["job_id"])
        cold_s = time.time() - t0
        # warm-engine cold-cache reference: second shape submit would hit
        # the cache, so measure repeat checks (hits) directly
        hits = []
        for _ in range(repeats):
            t0 = time.time()
            spec = qb.submit(text, module, tenant="bench",
                             kernel_source="hand")
            rec = wait_verdict(qb, mgr_b, spec["job_id"])
            hits.append(time.time() - t0)
            if (rec.get("cache") or {}).get("state_cache") != "hit":
                raise SystemExit(f"fleet bench: expected cache hit: {rec}")
        # config-delta: bounded first, then the unbounded check seeds
        bounded = text  # same schema, depth-bounded
        spec = qb.submit(bounded, module, tenant="bench",
                         kernel_source="hand", max_depth=4)
        wait_verdict(qb, mgr_b, spec["job_id"])
        t0 = time.time()
        spec = qb.submit(bounded, module, tenant="bench",
                         kernel_source="hand", max_depth=6)
        rec = wait_verdict(qb, mgr_b, spec["job_id"])
        delta_s = time.time() - t0
        delta_seeded = (rec.get("cache") or {}).get("state_cache") == "seed"
    finally:
        mgr_b.request_stop()
        t_b.join(timeout=30)

    lat.sort()
    hits.sort()

    def pct(vals, p):
        return round(vals[min(len(vals) - 1, int(p * len(vals)))], 3)

    n = len(lat)
    hit_p50 = pct(hits, 0.50)
    rec = {
        "bench": "fleet",
        "platform": "cpu",
        "daemons": n_daemons,
        "concurrent_jobs": n,
        "burst_wall_s": round(burst_s, 3),
        "p50_s": pct(lat, 0.50),
        "p95_s": pct(lat, 0.95),
        "max_s": round(lat[-1], 3),
        "jobs_per_sec": round(n / max(burst_s, 1e-9), 2),
        "state_cache": {
            "cold_s": round(cold_s, 3),
            "hit_p50_s": hit_p50,
            "hit_p95_s": pct(hits, 0.95),
            "repeats": repeats,
            "cold_over_hit": round(cold_s / max(hit_p50, 1e-9), 1),
            "delta_seeded": delta_seeded,
            "delta_s": round(delta_s, 3),
        },
        "venue": {
            "cores": 1,
            "caveat": (
                "1-core CPU-share-throttled container: the daemons "
                "time-share one core, so burst p50/p95 measures queueing "
                "+ batching economics, not hardware parallelism (the PR "
                "10/13 venue-honesty precedent).  Venue-independent "
                "signals: exactly-once verdicts across the fleet and the "
                "cold/hit latency ratio"
            ),
        },
        "target": {"p50_s": 2.0, "concurrent_jobs": 100, "daemons": 2},
        "pass": bool(pct(lat, 0.50) < 2.0 and n >= 100
                     and n_daemons >= 2),
    }
    print(json.dumps(rec))


def _router_bench():
    """`bench.py --router`: the two-host routed-fleet bench (ISSUE 16
    acceptance; banked as BENCH_r16.json).

    Phase A — 100+ concurrent jobs submitted through the jax-free
    router fronting TWO single-daemon hosts (separate queue dirs,
    separate daemon processes), state cache OFF: the honest routed
    engine-serving measurement, plus the placement spread the router
    actually chose.
    Phase B — federation economics on a fresh host pair sharing ONE
    cache namespace: host 0 publishes a verdict cold, then host 1
    serves the SAME config as a cross-host chain-verified hit (the
    entry it never wrote).  The parent never imports jax.

    VENUE-HONEST: one schedulable core, so the two "hosts" time-share
    it — burst p50/p95 measures routing + queueing + batching
    economics, not hardware parallelism; the venue-independent signals
    are exactly-once verdicts across hosts and the cold vs cross-host
    hit ratio."""
    import tempfile
    import threading

    from kafka_specification_tpu.service.fleet import (
        FleetManager,
        FleetServeConfig,
    )
    from kafka_specification_tpu.service.queue import JobQueue
    from kafka_specification_tpu.service.router import Router
    from kafka_specification_tpu.utils.platform_guard import cpu_env

    shapes = {
        "IdSequence": (
            "IdSequence",
            "SPECIFICATION Spec\nCONSTANTS\n    MaxId = 10\n"
            "INVARIANTS TypeOk\nCHECK_DEADLOCK FALSE\n",
        ),
        "FiniteReplicatedLog": (
            "FiniteReplicatedLog",
            "SPECIFICATION Spec\nCONSTANTS\n    Replicas = {r1, r2}\n"
            "    LogSize = 2\n    LogRecords = {a, b}\n    Nil = Nil\n"
            "INVARIANTS TypeOk\nCHECK_DEADLOCK FALSE\n",
        ),
        "TruncateTiny": (
            "KafkaTruncateToHighWatermark",
            "SPECIFICATION Spec\nCONSTANTS\n    Replicas = {b1, b2}\n"
            "    LogSize = 2\n    MaxRecords = 1\n    MaxLeaderEpoch = 1\n"
            "INVARIANTS TypeOk WeakIsr\nCHECK_DEADLOCK FALSE\n",
        ),
    }
    jobs_per_shape = int(os.environ.get("KSPEC_ROUTER_BENCH_JOBS", "36"))

    def start_host(svc, extra_serve_args=()):
        cfg = FleetServeConfig(
            service_dir=svc,
            daemons=1,
            min_daemons=1,
            max_daemons=1,
            poll_s=0.2,
            stall_timeout=300.0,  # a cold compile must not read as a wedge
            serve_args=("--min-bucket", "32", "--visited-backend", "host")
            + tuple(extra_serve_args),
            env=cpu_env(),
        )
        mgr = FleetManager(cfg)
        t = threading.Thread(target=mgr.run, daemon=True)
        t.start()
        return mgr, t

    def wait_verdict(router, mgrs, jid, timeout=900.0):
        deadline = time.time() + timeout
        while time.time() < deadline:
            rec = router.result(jid)
            if rec is not None:
                return rec
            if all(s.state == "halted" for m in mgrs for s in m.slots):
                raise SystemExit(
                    f"router bench: every daemon halted before {jid}"
                )
            time.sleep(0.05)
        raise SystemExit(f"router bench: no verdict for {jid}")

    def stop_hosts(pairs):
        for mgr, _ in pairs:
            mgr.request_stop()
        for _, t in pairs:
            t.join(timeout=30)

    # ---- phase A: 100+ concurrent through the router, cache OFF ----------
    root_a = tempfile.mkdtemp(prefix="kspec-router-bench-")
    h0 = os.path.join(root_a, "h0")
    h1 = os.path.join(root_a, "h1")
    q0, q1 = JobQueue(h0), JobQueue(h1)
    router = Router(os.path.join(root_a, "rt"), hosts=[h0, h1],
                    dead_after_s=30.0)
    hosts_a = [start_host(h0, ("--no-state-cache",)),
               start_host(h1, ("--no-state-cache",))]
    mgrs_a = [m for m, _ in hosts_a]
    try:
        deadline = time.time() + 120.0
        while time.time() < deadline:
            if all(h["state"] == "ok" for h in router.healths()):
                break
            time.sleep(0.2)
        else:
            raise SystemExit(
                f"router bench: hosts never alive: {router.healths()}"
            )
        # warm BOTH hosts' compile caches on every shape (pinned submits:
        # the burst then measures routed serving, not cold compiles)
        warm = [
            router.submit(text, module, tenant="bench",
                          kernel_source="hand", host=i)
            for i in (0, 1)
            for module, text in shapes.values()
        ]
        for spec in warm:
            rec = wait_verdict(router, mgrs_a, spec["job_id"])
            if rec["exit_code"] not in (0, 1):
                raise SystemExit(f"router bench: warmup failed: {rec}")

        ids = []
        submit_errors = []
        lock = threading.Lock()

        def submit(module, text):
            # a failed submit must FAIL the bench, not silently shrink
            # the measured set
            try:
                spec = router.submit(text, module, tenant="bench",
                                     kernel_source="hand")
            except Exception as e:  # noqa: BLE001 — re-raised after join
                with lock:
                    submit_errors.append(e)
                return
            with lock:
                ids.append(spec["job_id"])

        threads = [
            threading.Thread(target=submit, args=shapes[name])
            for name in shapes
            for _ in range(jobs_per_shape)
        ]
        t_burst = time.time()
        for th in threads:
            th.start()
        for th in threads:
            th.join()
        if submit_errors:
            raise SystemExit(
                f"router bench: {len(submit_errors)} submits failed "
                f"(first: {submit_errors[0]!r})"
            )
        lat = []
        placement = {0: 0, 1: 0}
        for jid in ids:
            rec = wait_verdict(router, mgrs_a, jid)
            if rec["exit_code"] not in (0, 1):
                raise SystemExit(f"router bench: job failed: {rec}")
            lat.append(rec["timing"]["latency_s"])
            placement[router.locate(jid)] += 1
        burst_s = time.time() - t_burst
        # exactly-once visibility across BOTH host queues
        for q in (q0, q1):
            ov = q.overview()
            if ov["counts"]["pending"] or ov["counts"]["claimed"]:
                raise SystemExit(f"router bench: jobs left behind: {ov}")
    finally:
        stop_hosts(hosts_a)

    # ---- phase B: federation (cold publish vs cross-host verified hit) ---
    root_b = tempfile.mkdtemp(prefix="kspec-router-bench-fed-")
    f0 = os.path.join(root_b, "h0")
    f1 = os.path.join(root_b, "h1")
    cache_dir = os.path.join(root_b, "shared-cache")
    fed = Router(os.path.join(root_b, "rt"), hosts=[f0, f1],
                 dead_after_s=30.0)
    cache_args = ("--state-cache-dir", cache_dir)
    hosts_b = [start_host(f0, cache_args), start_host(f1, cache_args)]
    mgrs_b = [m for m, _ in hosts_b]
    module, text = shapes["TruncateTiny"]
    repeats = 5
    try:
        # cold on host 0 (includes the shape's compile; publishes the
        # entry host 1 will verify)
        t0 = time.time()
        spec = fed.submit(text, module, tenant="bench",
                          kernel_source="hand", host=0)
        wait_verdict(fed, mgrs_b, spec["job_id"])
        cold_s = time.time() - t0
        # cross-host: host 1 serves host 0's publish, chain-verified
        hits = []
        for _ in range(repeats):
            t0 = time.time()
            spec = fed.submit(text, module, tenant="bench",
                              kernel_source="hand", host=1)
            rec = wait_verdict(fed, mgrs_b, spec["job_id"])
            hits.append(time.time() - t0)
            if (rec.get("cache") or {}).get("state_cache") != "hit":
                raise SystemExit(
                    f"router bench: expected cross-host hit: {rec}"
                )
    finally:
        stop_hosts(hosts_b)

    lat.sort()
    hits.sort()

    def pct(vals, p):
        return round(vals[min(len(vals) - 1, int(p * len(vals)))], 3)

    n = len(lat)
    hit_p50 = pct(hits, 0.50)
    rec = {
        "bench": "router",
        "platform": "cpu",
        "hosts": 2,
        "daemons_per_host": 1,
        "concurrent_jobs": n,
        "burst_wall_s": round(burst_s, 3),
        "p50_s": pct(lat, 0.50),
        "p95_s": pct(lat, 0.95),
        "max_s": round(lat[-1], 3),
        "jobs_per_sec": round(n / max(burst_s, 1e-9), 2),
        "placement": {"host0": placement[0], "host1": placement[1]},
        "federation": {
            "cold_s": round(cold_s, 3),
            "cross_host_hit_p50_s": hit_p50,
            "cross_host_hit_p95_s": pct(hits, 0.95),
            "repeats": repeats,
            "cold_over_hit": round(cold_s / max(hit_p50, 1e-9), 1),
        },
        "venue": {
            "cores": 1,
            "caveat": (
                "1-core CPU-share-throttled container: the two hosts "
                "time-share one core, so burst p50/p95 measures routing "
                "+ queueing + batching economics, not hardware "
                "parallelism (the PR 10/13/14 venue-honesty precedent). "
                "Venue-independent signals: exactly-once verdicts across "
                "both host queues and the cold vs cross-host "
                "chain-verified hit ratio"
            ),
        },
        "target": {"p50_s": 2.0, "concurrent_jobs": 100, "hosts": 2},
        "pass": bool(pct(lat, 0.50) < 2.0 and n >= 100),
    }
    print(json.dumps(rec))


def _sweep_bench():
    """`bench.py --sweep`: coverage-sweep economics (ISSUE 17
    acceptance; banked as BENCH_r17.json).

    A 200+ point lattice over (brokers x log size x MaxId x depth
    bounds) — few distinct CONSTANTS shapes, many bounds per shape, so
    the daemon's group planner coalesces each shape's points into ONE
    batched engine run — swept COLD through the portfolio against one
    `cli serve` daemon, then REPEATED into a fresh sweep dir against the
    same service: the repeat's points are state-cache O(verify) hits
    (batched members publish verdict-only entries), which is the
    cache-incremental win the subsystem exists for.  Finally the same
    lattice runs through a SECOND daemon with the state cache disabled
    and every point forced solo (`solo_threshold_states=0`) — the
    ground-truth leg — and every cold verdict must be bit-identical to
    its solo verdict (model, distinct_states, diameter, violation,
    exit_code).  The parent is a pure queue client and never imports
    the real jax (the sweep package's jax-free contract; the vacuity
    analyzer installs its own stub).

    VENUE-HONEST: one schedulable core, so cold wall is dominated by
    XLA compiles + engine exploration time-shared with the daemon; the
    venue-independent signals are the point count, verdict completeness
    and the cold/repeat ratio."""
    import tempfile

    from kafka_specification_tpu.sweep import (
        SweepConfig,
        enumerate_points,
        load_lattice,
        run_sweep,
    )
    from kafka_specification_tpu.utils.platform_guard import cpu_env

    frl = (
        "SPECIFICATION Spec\nCONSTANTS\n    Replicas = {r1, r2}\n"
        "    LogSize = 2\n    LogRecords = {a, b}\n    Nil = Nil\n"
        "INVARIANTS TypeOk\nCHECK_DEADLOCK FALSE\n"
    )
    idc = (
        "SPECIFICATION Spec\nCONSTANTS\n    MaxId = 6\n"
        "INVARIANTS TypeOk\nCHECK_DEADLOCK FALSE\n"
    )
    lattice = load_lattice({
        "schema": "kspec-sweep-lattice/1",
        "name": "bench-lattice",
        "sheets": [
            {"module": "FiniteReplicatedLog", "cfg_text": frl,
             "axes": [
                 {"name": "Replicas", "values": [1, 2]},
                 {"name": "LogSize", "values": [1, 2]},
                 {"name": "max_depth", "kind": "bound",
                  "values": [2, 4, 6, 8, 10, 12, 14, 16, 24, 32, None]},
             ]},
            {"module": "IdSequence", "cfg_text": idc,
             "axes": [
                 {"name": "MaxId", "values": list(range(2, 13))},
                 {"name": "max_depth", "kind": "bound",
                  "values": [2, 3, 4, 6, 8, 10, 12, 16, 24, 32, 48, 64,
                             96, 128, None]},
             ]},
        ],
    })
    points = enumerate_points(lattice)
    shapes = len({p.key.base_digest() for p in points})

    root = tempfile.mkdtemp(prefix="kspec-sweep-bench-")

    def start_daemon(svc, *extra):
        log = open(os.path.join(root, os.path.basename(svc) + "-stderr.log"),
                   "w")
        proc = subprocess.Popen(
            [
                sys.executable, "-m", "kafka_specification_tpu.utils.cli",
                "serve", svc, "--idle-exit", "900", "--min-bucket", "32",
                "--visited-backend", "host", *extra,
            ],
            env=cpu_env(),
            stdout=subprocess.DEVNULL,
            stderr=log,
        )
        return proc, log

    def stop_daemon(proc, log):
        proc.terminate()
        try:
            proc.wait(timeout=30)
        except subprocess.TimeoutExpired:
            proc.kill()
        log.close()

    def sweep_into(name, svc, proc, log, **cfg_kw):
        t0 = time.time()
        rec = run_sweep(lattice, SweepConfig(
            sweep_dir=os.path.join(root, name),
            service_dir=svc,
            tenant="bench",
            wait_timeout_s=850.0,
            **cfg_kw,
        ))
        wall = time.time() - t0
        if proc.poll() is not None:
            log.flush()
            with open(log.name) as fh:
                raise SystemExit(
                    f"daemon died rc={proc.returncode}:\n"
                    + fh.read()[-4000:]
                )
        done = sum(1 for r in rec["points"].values()
                   if r["status"] == "done")
        hits = sum(
            1 for r in rec["points"].values()
            if (r.get("cache") or {}).get("state_cache") == "hit"
        )
        return rec, wall, done, hits

    svc = os.path.join(root, "svc")
    daemon, daemon_log = start_daemon(svc)
    try:
        rec1, cold_s, cold_done, cold_hits = sweep_into(
            "cold", svc, daemon, daemon_log)
        rec2, rep_s, rep_done, rep_hits = sweep_into(
            "repeat", svc, daemon, daemon_log)
    finally:
        stop_daemon(daemon, daemon_log)

    # ground truth: a cache-less daemon, every point solo — the sweep's
    # batched/cache-served verdicts must be bit-identical to this
    svc2 = os.path.join(root, "svc-solo")
    daemon2, daemon2_log = start_daemon(svc2, "--no-state-cache")
    try:
        rec3, solo_s, solo_done, _ = sweep_into(
            "solo", svc2, daemon2, daemon2_log, solo_threshold_states=0)
    finally:
        stop_daemon(daemon2, daemon2_log)

    _CMP = ("model", "distinct_states", "diameter", "violation",
            "exit_code")
    mismatches = []
    for pid, row in rec1["points"].items():
        a = {k: (row.get("verdict") or {}).get(k) for k in _CMP}
        b = {k: (rec3["points"][pid].get("verdict") or {}).get(k)
             for k in _CMP}
        if a != b:
            mismatches.append({"point_id": pid, "sweep": a, "solo": b})
    if mismatches:
        raise SystemExit(
            f"sweep vs solo verdict mismatch on {len(mismatches)} "
            f"points, first: {json.dumps(mismatches[0])}"
        )

    n = len(points)
    ratio = cold_s / max(rep_s, 1e-9)
    out = {
        "bench": "sweep",
        "platform": "cpu",
        "points": n,
        "shapes": shapes,
        "cold": {
            "wall_s": round(cold_s, 3),
            "done": cold_done,
            "cache_hits": cold_hits,
            "points_per_sec": round(n / max(cold_s, 1e-9), 2),
        },
        "repeat": {
            "wall_s": round(rep_s, 3),
            "done": rep_done,
            "cache_hits": rep_hits,
            "points_per_sec": round(n / max(rep_s, 1e-9), 2),
        },
        "cold_over_repeat": round(ratio, 1),
        "solo_ground_truth": {
            "wall_s": round(solo_s, 3),
            "done": solo_done,
            "verdicts_bit_identical": True,
            "compared_fields": list(_CMP),
        },
        "cost_model": {
            "n_records": (rec2.get("cost_model") or {}).get("n_records"),
            "residual_shift": (rec2.get("cost_model") or {}).get(
                "residual_shift"
            ),
        },
        "venue": {
            "cores": 1,
            "caveat": (
                "1-core CPU-share-throttled container: the sweep client "
                "and the serving daemon time-share one core, so cold "
                "wall is XLA compiles + engine exploration, not "
                "portfolio overhead, and repeat wall is dominated by "
                "chain-verify + queue round-trips (the PR 10/13/14 "
                "venue-honesty precedent). Venue-independent signals: "
                "the 200+ point count, verdict completeness, and the "
                "cold vs all-cache-hit repeat ratio"
            ),
        },
        "target": {"points": 200, "repeat_speedup": 5.0},
        "pass": bool(
            n >= 200 and cold_done == n and rep_done == n
            and rep_hits == n and solo_done == n and ratio >= 5.0
        ),
    }
    print(json.dumps(out))


def _exchange_child_main():
    """8-device CI-mesh exchange measurement (ROADMAP item 5): the same
    sharded workload with the compressed exchange on vs off — verdicts
    must be identical (a runtime bit-identity assert), and the record
    banks the measured bytes/level both ways."""
    import jax

    from kafka_specification_tpu.utils.platform_guard import (
        enable_compile_cache,
    )

    jax.config.update("jax_platforms", "cpu")
    enable_compile_cache()
    import numpy as np
    from jax.sharding import Mesh

    from kafka_specification_tpu.models import kip320
    from kafka_specification_tpu.models.kafka_replication import Config
    from kafka_specification_tpu.parallel.sharded import check_sharded

    devs = jax.devices("cpu")
    assert len(devs) >= 8, f"expected 8 virtual devices, got {len(devs)}"
    mesh = Mesh(np.array(devs[:8]), ("d",))
    model = kip320.make_model(
        Config(2, 2, 2, 2), ("TypeOk", "LeaderInIsr", "WeakIsr")
    )
    kwargs = dict(
        mesh=mesh,
        store_trace=False,
        min_bucket=512,
        stats_path=os.devnull,
    )
    os.environ["KSPEC_OVERLAP"] = "0"
    off = check_sharded(model, **kwargs)
    os.environ["KSPEC_OVERLAP"] = "1"
    os.environ["KSPEC_EXCHANGE_COMPRESS"] = "1"
    on = check_sharded(model, **kwargs)
    assert on.stats["exchange_compressed"], "codec not engaged"
    assert (on.total, on.levels, on.ok) == (off.total, off.levels, off.ok), (
        "compressed exchange diverged from the raw oracle"
    )
    n_levels = max(1, len(on.levels) - 1)
    sent = on.stats["exchange_bytes_total"]
    raw = on.stats["exchange_raw_bytes_total"]
    print(
        json.dumps(
            {
                "devices": 8,
                "model": "Kip320 Config(2,2,2,2) sharded all_to_all",
                "total_states": on.total,
                "bit_identical_to_raw": True,
                "bytes_per_level_compressed": int(sent / n_levels),
                "bytes_per_level_raw": int(raw / n_levels),
                "ratio": round(raw / max(sent, 1), 2),
                "wall_on_s": round(on.seconds, 2),
                "wall_off_s": round(off.seconds, 2),
            }
        )
    )


_MP_WORKER = r"""
import json, os, sys, time
import jax
jax.config.update("jax_platforms", "cpu")
from kafka_specification_tpu.utils.platform_guard import enable_compile_cache
enable_compile_cache()
from kafka_specification_tpu.parallel.multihost import init_distributed
init_distributed()
from kafka_specification_tpu.models import finite_replicated_log as frl
from kafka_specification_tpu.parallel.sharded import check_sharded
m = frl.make_model(3, 4, 1)
t0 = time.perf_counter()
r = check_sharded(m, pipeline="device", store_trace=False,
                  stats_path=os.devnull, min_bucket=8, compact_gate=8)
print("RESULT " + json.dumps({
    "pid": jax.process_index(), "total": r.total, "ok": bool(r.ok),
    "wall_s": round(time.perf_counter() - t0, 2),
}))
"""


def _attempt_multiprocess(procs: int) -> dict:
    """The wall-breaker ATTEMPT: a P-process jax.distributed sharded
    run on localhost (the ROADMAP item 2 configuration — P-way sharding
    across real cores is the lever that breaks the single-core compute
    wall the 195.5M/464M runs are pinned to).  Banked HONESTLY either
    way: some jaxlib builds ship an XLA:CPU without cross-process
    collectives ("Multiprocess computations aren't implemented" — the
    PR 4 environment gap, also skipped in tests/test_multiprocess.py),
    and a 1-schedulable-core container time-slices P processes onto one
    core, so the record says what the venue could and could not run
    instead of silently dropping the leg."""
    import socket

    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        port = s.getsockname()[1]
    children = []
    for pid in range(procs):
        env = dict(os.environ)
        env["JAX_PLATFORMS"] = "cpu"
        env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=1"
        env["JAX_COORDINATOR_ADDRESS"] = f"127.0.0.1:{port}"
        env["JAX_NUM_PROCESSES"] = str(procs)
        env["JAX_PROCESS_ID"] = str(pid)
        children.append(
            subprocess.Popen(
                [sys.executable, "-c", _MP_WORKER],
                env=env,
                cwd=os.path.dirname(os.path.abspath(__file__)),
                stdout=subprocess.PIPE,
                stderr=subprocess.PIPE,
                text=True,
            )
        )
    rec = {"attempted": True, "procs": procs,
           "cores": len(os.sched_getaffinity(0))
           if hasattr(os, "sched_getaffinity") else os.cpu_count()}
    outs = []
    for p in children:
        try:
            out, err = p.communicate(
                timeout=int(os.environ.get("KSPEC_BENCH_MP_TIMEOUT", "600"))
            )
        except subprocess.TimeoutExpired:
            for q in children:
                q.kill()
            rec.update(supported=False, reason="worker timeout")
            return rec
        if p.returncode != 0:
            for q in children:
                q.kill()
            gap = "Multiprocess computations aren't implemented" in err
            rec.update(
                supported=False,
                reason=(
                    "this jaxlib's XLA:CPU backend cannot run "
                    "multiprocess collectives (the PR 4 environment "
                    "gap; tests/test_multiprocess.py skips on it too)"
                    if gap
                    else f"worker rc={p.returncode}: {err[-200:]}"
                ),
            )
            return rec
        line = [l for l in out.splitlines() if l.startswith("RESULT ")]
        outs.append(json.loads(line[-1][len("RESULT "):]) if line else None)
    ok = all(o and o["ok"] and o["total"] == 125 for o in outs)
    rec.update(
        supported=bool(ok),
        results=outs,
        **({} if ok else {"reason": "wrong worker results"}),
    )
    return rec


def _sharded_device_child_main():
    """Sharded device-resident level pipeline measurement (ROADMAP
    items 1+2): per-shard one-dispatch level programs vs the per-chunk
    sharded step on the 4-device virtual mesh, per-shard launches/level
    and exchange bytes/level banked, the single-device 1-core baseline
    alongside, and the multi-process wall-breaker ATTEMPT recorded
    venue-honestly (this container exposes ONE schedulable core and its
    XLA:CPU lacks cross-process collectives — the P>=4 multi-core run
    needs a venue that has both; the device-vs-per-chunk ratio on the
    same box is the venue-independent signal, the PR 7/10/12 bench
    precedent)."""
    import jax

    from kafka_specification_tpu.utils.platform_guard import (
        enable_compile_cache,
    )

    jax.config.update("jax_platforms", "cpu")
    enable_compile_cache()
    import numpy as np
    from jax.sharding import Mesh

    from kafka_specification_tpu.engine import check
    from kafka_specification_tpu.models import kip320
    from kafka_specification_tpu.models.kafka_replication import Config
    from kafka_specification_tpu.parallel.sharded import check_sharded

    devs = jax.devices("cpu")
    assert len(devs) >= 4, f"expected 4 virtual devices, got {len(devs)}"
    mesh = Mesh(np.array(devs[:4]), ("d",))
    model = lambda: kip320.make_model(Config(3, 2, 2, 2))  # noqa: E731
    GOLD = 737_794
    # THE flagship workload (oracle-pinned golden count; same model as
    # the headline and the PR 12 device leg), chunk 1024 = this
    # engine's historical compact gate (the PR 12 bench sized the same
    # way: chunk at the gate): the waist levels are ~20k rows PER SHARD
    # on the 4-device mesh, so every shard runs ~20 gated chunks there
    # — the many-chunks-per-level shape of HBM-bounded chunks at the
    # 2-5B scale, whose per-chunk collective dispatches and per-chunk
    # O(capacity) visited merges the level program collapses (one
    # dispatch + one merge per LEVEL per shard).  Best-of-3 alternating
    # (the throttled-venue practice; round 1 pays any cold compiles and
    # best-of picks a warm round).
    kwargs = dict(
        mesh=mesh,
        store_trace=False,
        min_bucket=1024,
        chunk_size=1024,
        stats_path=os.devnull,
    )
    os.environ["KSPEC_OVERLAP"] = "0"  # device backend: no staging
    dv_w, pc_w = [], []
    dv_stats = pc_stats = None
    for _ in range(3):
        for pipe in ("device", "legacy"):
            r = check_sharded(model(), pipeline=pipe, **kwargs)
            assert r.ok and r.total == GOLD, (pipe, r.total)
            if pipe == "device":
                dv_w.append(r.seconds)
                dv_stats = r.stats
            else:
                pc_w.append(r.seconds)
                pc_stats = r.stats
    assert dv_stats["device"]["levels"] > 0, dv_stats["device"]
    assert dv_stats["device"]["fallback"] is None, dv_stats["device"]

    def _launches(stats):
        lv = stats["levels"]
        return {
            "per_level_per_shard_max": max(
                l["shard_launches"] for l in lv
            ),
            "per_level_per_shard_mean": round(
                sum(l["shard_launches"] for l in lv) / len(lv), 2
            ),
        }

    n_levels = max(1, len(dv_stats["levels"]) - 1)
    # single-device 1-core baseline, same model/invariants: the box's
    # FASTEST single-device configuration (fused pipeline + host FpSet,
    # the CPU-venue default — RESULTS.md) — what a P-way multi-core run
    # must beat for the wall-breaker claim
    base_kw = dict(
        store_trace=False,
        min_bucket=4096,
        chunk_size=32768,
        visited_backend="host",
        stats_path=os.devnull,
    )
    check(model(), pipeline="fused", **base_kw)  # warm
    bres = check(model(), pipeline="fused", **base_kw)
    assert bres.ok and bres.total == GOLD, bres.total

    mp_rec = _attempt_multiprocess(4)
    print(
        json.dumps(
            {
                "config": "Kip320 Config(3,2,2,2) flagship (737,794 "
                "states, 4 invariants), 4-device virtual mesh, "
                "all_to_all, chunk 1024 = the sharded compact gate "
                "(~20 gated chunks/shard at the waist)",
                "devices": 4,
                "total_states": GOLD,
                "device_sps": round(GOLD / min(dv_w), 1),
                "perchunk_sps": round(GOLD / min(pc_w), 1),
                "device_walls_s": [round(s, 2) for s in dv_w],
                "perchunk_walls_s": [round(s, 2) for s in pc_w],
                "device_vs_perchunk": round(min(pc_w) / min(dv_w), 3),
                "device_levels": dv_stats["device"]["levels"],
                "device_fallback": dv_stats["device"]["fallback"],
                "launches_per_level": {
                    "device": _launches(dv_stats),
                    "perchunk": _launches(pc_stats),
                },
                "exchange_bytes_per_level": int(
                    dv_stats["exchange_raw_bytes_total"] / n_levels
                ),
                "mesh_layouts": dv_stats["mesh_layouts"],
                "single_device_1core_sps": round(
                    bres.states_per_sec, 1
                ),
                "multiprocess": mp_rec,
                # venue honesty (the PR 10 Amdahl-note precedent): with
                # ONE schedulable core, D=4 shard programs time-slice
                # one core, so sharded absolute sps trails the
                # single-device baseline and a P>=4 multi-process run
                # cannot demonstrate multi-core scaling AT ALL here —
                # on this box the venue-independent signals are the
                # device-vs-per-chunk ratio (the collective-launch +
                # per-level-merge win this PR adds) and the O(1)
                # launches/level/shard contract; the >=2x-vs-1-core
                # wall-breaker run needs >=4 schedulable cores AND an
                # XLA build with cross-process collectives
                "venue": {
                    "cores": len(os.sched_getaffinity(0))
                    if hasattr(os, "sched_getaffinity")
                    else os.cpu_count(),
                    "note": "1-schedulable-core CPU-share-throttled "
                    "container without multiprocess XLA:CPU "
                    "collectives; see 'multiprocess' for the attempt "
                    "record",
                },
            }
        )
    )


def main():
    if "--serve" in sys.argv[1:]:
        _serve_bench()
        return
    if "--fleet" in sys.argv[1:]:
        _fleet_bench()
        return
    if "--router" in sys.argv[1:]:
        _router_bench()
        return
    if "--sweep" in sys.argv[1:]:
        _sweep_bench()
        return
    if os.environ.get("KSPEC_BENCH_EXCHANGE"):
        _exchange_child_main()
        return
    if os.environ.get("KSPEC_BENCH_SHARDED_DEVICE"):
        _sharded_device_child_main()
        return
    if os.environ.get(_CHILD_ENV):
        _child_main()
        return
    raise SystemExit(_run_child(_TPU_TIMEOUT))


if __name__ == "__main__":
    main()
