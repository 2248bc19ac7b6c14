"""Shape-keyed compile cache: the warm heart of the serving daemon.

A cold ``cli check`` pays ~2 minutes of jax import + reference parse +
model build + trace/XLA-compile for seconds of actual checking.
The daemon pays each of those exactly once per *schema shape* and then
serves every later job of that shape warm, following the compiler-first
portable-cache design of arXiv:2603.09555 (PAPERS.md): make compilation a
keyed artifact, look it up in O(1).

Two layers (the model-layer/invariant-overlay split, ROADMAP item 3):

**Model layer** — keyed by ``(module, kernel source, canonical CONSTANTS,
constraints)``: the expensive artifact.  One entry holds the built
:class:`~..models.base.Model` (reference parse, symbolic emit, schema,
action kernels) constructed with the sorted UNION of every invariant any
overlay of this shape has asked for, plus the model-lifetime jitted-step
cache (``_step_cache``).

**Invariant overlay** — keyed by the full shape key (ordered invariants +
deadlock flag): a cheap view over its base model.  The invariant
selection adds/removes predicate kernels AND fixes the first-violation
order, so it must key — ORDERED — but it does not need a second model
build: the overlay reorders the base's Invariant objects (and
column-permutes the base's fused invariant evaluator) and SHARES the
base's step cache.  Step-cache keys carry the ordered invariant names
(engine.bfs._Step.inv_sig), so invariant-free step programs — the whole
batched-exploration path — are shared across every overlay of a shape,
while each ordering's invariant-bearing programs compile once per order.
This is what retires the old "mixed solo/batched traffic of one schema
shape holds two cache lines" note: solo (cfg-order invariants) and
batched (sorted-union invariants) traffic now share one model build and
one step cache, and the solo order only adds its own thin overlay.

Two .cfg files with the same semantic content — regardless of path,
comments, or formatting — therefore hit the same overlay.  Engine knobs
(bucket floor, chunk size, visited backend) select among the per-model
compiled step variants and ride in the GROUP key (scheduler), not here.

A hit skips model build AND every step trace/compile — the engine then
emits zero ``compile`` spans into the job's trace, which is the warm
path's observable proof (docs/service.md).

Not jax-free (building models touches jax): imported only by the daemon,
never by the client commands.
"""

from __future__ import annotations

import time
from typing import Optional

from ..obs.ledger import PROCESS as _LEDGER
from ..utils.cfg import (
    TlcConfig,
    build_model,
    parse_cfg,
    resolved_invariants,
)


def canonical_constants(constants: dict) -> tuple:
    """Hashable canonical form of a .cfg's CONSTANTS block."""
    return tuple(
        (k, tuple(v) if isinstance(v, list) else v)
        for k, v in sorted(constants.items())
    )


def resolve_kernel_source(kernel_source: str, module: str) -> bool:
    """'auto'|'emitted'|'hand' -> emitted? — same resolution as the CLI
    (`auto` = emitted iff the reference checkout has the module)."""
    if kernel_source == "emitted":
        return True
    if kernel_source == "hand":
        return False
    from ..models.emitted import ref_path

    return (ref_path() / f"{module}.tla").exists()


def shape_key(module: str, cfg: TlcConfig, emitted: bool,
              invariants: tuple) -> tuple:
    """The overlay key (ordered invariants fix the first-violation rule,
    so they key verbatim; see module docstring)."""
    return (
        module,
        bool(emitted),
        canonical_constants(cfg.constants),
        tuple(invariants),
        tuple(cfg.constraints),
        bool(cfg.check_deadlock),
        cfg.symmetry,  # a reduced job never shares an unreduced one's kernels
    )


def model_key(module: str, cfg: TlcConfig, emitted: bool) -> tuple:
    """The model-layer key: everything that shapes the built Model except
    the invariant selection (overlaid) and the deadlock flag (a pure
    engine knob — the step programs compute deadlock info either way)."""
    return (
        module,
        bool(emitted),
        canonical_constants(cfg.constants),
        tuple(cfg.constraints),
        cfg.symmetry,
    )


def _overlay_model(base, invariants: tuple):
    """A cheap Model view selecting `invariants` (ordered) from `base`.

    Shares the base's spec/actions/decode AND its step cache (the
    expensive compiled artifacts); the fused invariant evaluator is a
    column permutation of the base's, so the shared predicate core
    compiles once per base, not once per ordering."""
    base_names = [i.name for i in base.invariants]
    if tuple(base_names) == tuple(invariants):
        return base
    import dataclasses

    import jax.numpy as jnp

    idx = tuple(base_names.index(n) for n in invariants)
    fused = None
    if base.invariants_fused is not None:
        def fused(s, _f=base.invariants_fused, _ix=idx):
            return _f(s)[jnp.asarray(_ix)]

    view = dataclasses.replace(
        base,
        invariants=[base.invariant(n) for n in invariants],
        invariants_fused=fused,
    )
    # one step cache per BASE: overlays share compiled programs; the
    # ordered-invariant component of each step key (engine.bfs._Step)
    # keeps invariant-bearing programs per-order while everything
    # invariant-free is shared
    for attr in ("_step_cache", "_step_compiled_log"):
        store = getattr(base, attr, None)
        if store is None:
            store = {} if attr == "_step_cache" else set()
            setattr(base, attr, store)
        setattr(view, attr, store)
    return view


class KernelCache:
    """In-process two-layer cache of built models + prepared engine
    kernels.  Bounded LRU over the overlays (``max_entries``): compiled
    programs are tens of MB of host memory each on big models, and a
    long-lived daemon must not grow without bound across every shape it
    has ever seen.  Base models are dropped when their last overlay is
    evicted."""

    def __init__(self, max_entries: int = 32):
        self.max_entries = max_entries
        self._entries: dict = {}  # overlay key -> entry dict
        self._models: dict = {}  # model key -> {key, model, names}
        self.hits = 0
        self.misses = 0
        self.evictions = 0
        self.model_builds = 0
        self.overlay_derives = 0

    def __len__(self) -> int:
        return len(self._entries)

    def _base(self, module: str, cfg: TlcConfig, emitted: bool,
              invariants: tuple) -> dict:
        """The model-layer entry covering `invariants`, building (or
        rebuilding with a grown union) when needed."""
        bkey = model_key(module, cfg, emitted)
        base = self._models.get(bkey)
        if base is not None and set(invariants) <= set(base["names"]):
            return base
        union = sorted(set(invariants) | set(base["names"] if base else ()))
        build_cfg = TlcConfig(
            constants=dict(cfg.constants),
            invariants=list(union),
            constraints=list(cfg.constraints),
            specification=cfg.specification,
            check_deadlock=cfg.check_deadlock,
        )
        model = build_model(module, build_cfg, emitted=emitted)
        self.model_builds += 1
        if base is not None:
            # a grown union replaced the base: overlays derived from the
            # OLD base would otherwise pin a second full model + step
            # cache for this shape (the exact cost this split retires) —
            # drop them so their next request re-derives from the new
            # base (in-flight callers keep their own references)
            for k in [
                k for k, e in self._entries.items()
                if e.get("base_key") == bkey
            ]:
                del self._entries[k]
        base = {
            "key": bkey,
            "model": model,
            # the names actually RESOLVED into the model (builders may
            # apply defaults), so coverage checks match reality
            "names": tuple(i.name for i in model.invariants),
        }
        self._models[bkey] = base
        return base

    def get(self, module: str, cfg: TlcConfig, emitted: bool,
            invariants: tuple) -> dict:
        """-> {model, prepared, key, hit, build_s}; builds on miss.
        A miss that lands on a warm model layer derives an invariant
        overlay (no model build, no step compiles for the shared
        invariant-free programs) — ``overlay`` is True on such entries."""
        from ..engine.bfs import prepare

        key = shape_key(module, cfg, emitted, invariants)
        entry = self._entries.get(key)
        if entry is not None:
            self.hits += 1
            entry["last_used"] = time.time()
            entry["uses"] += 1
            return {**entry, "hit": True}
        self.misses += 1
        t0 = time.perf_counter()
        # (one build in the process ledger's `model_s`, the overlay's
        # derivation included: `build_model` and `prepare` nest in it)
        with _LEDGER.model():
            prior = self._models.get(model_key(module, cfg, emitted))
            base = self._base(module, cfg, emitted, invariants)
            overlay = prior is not None and prior is base  # warm base
            model = _overlay_model(base["model"], tuple(invariants))
            if model is not base["model"]:
                self.overlay_derives += 1
            prepared = prepare(model)
        entry = {
            "key": key,
            "base_key": base["key"],
            "model": model,
            "prepared": prepared,
            "build_s": round(time.perf_counter() - t0, 3),
            "overlay": bool(overlay),
            "last_used": time.time(),
            "uses": 1,
        }
        self._entries[key] = entry
        if len(self._entries) > self.max_entries:
            lru = min(self._entries.values(), key=lambda e: e["last_used"])
            del self._entries[lru["key"]]
            self.evictions += 1
            # drop the base model once no overlay references it
            bk = lru.get("base_key")
            if bk is not None and not any(
                e.get("base_key") == bk for e in self._entries.values()
            ):
                self._models.pop(bk, None)
        return {**entry, "hit": False}

    def stats(self) -> dict:
        return {
            "entries": len(self._entries),
            "hits": self.hits,
            "misses": self.misses,
            "evictions": self.evictions,
            "hit_rate": round(
                self.hits / max(1, self.hits + self.misses), 4
            ),
            "model_layer": {
                "entries": len(self._models),
                "builds": self.model_builds,
                "overlay_derives": self.overlay_derives,
            },
        }


def job_cfg(spec: dict) -> TlcConfig:
    """Parse a job spec's inline .cfg text."""
    cfg = parse_cfg(spec["cfg_text"])
    return cfg


def job_invariants(module: str, cfg: TlcConfig) -> tuple:
    """The invariant names, in model order, that a solo ``cli check`` of
    this job would build and check.  Delegates to build_model's own
    resolution (utils.cfg.resolved_invariants) so the batched replay
    (service/batch.py) can never drift from the solo path; an unknown
    module raises KeyError loudly, same as build_model."""
    return resolved_invariants(module, cfg)
