"""The expansion side indexes by gather once: a chunk's parent rows, packed
(ISSUE 39).  Pins the ``gather`` / ``scatter*`` equation counts of the guard
body (``fgd``), the pooled successor body (``fsc``) and one `_expand_compact`
body (``step`` / ``dvl`` / ``dvh`` / the sharded programs) of every hand model
at its cell's constants, by stage (the `kspec.<stage>` scope of the equation),
and holds the ``compile`` span's ``gathers`` / ``scatters`` to the same count.

What the parent of PR 39 traced to (same bodies, same constants; `x[traced]`
and `x.at[traced].set(v)` in the kernels, `lanes[lane_ids]` /
`lanes.at[lane_ids].add` in the codec, the unpacked tree gathered a field):

    model                     fgd guard    fsc expand    _expand_compact
                              gather+scat  gather+scat   guard      expand
    Kip320 / 3 and / 5        66 + 24      80 + 33       65 + 24    191 + 33
    MCKip320 / 5              66 + 24      80 + 33       65 + 24    191 + 33
    Kip320FirstTry / 3        76 + 24      90 + 34       75 + 24    215 + 34
    AsyncIsr / 4               9 +  6      18 + 13        8 +  6     71 + 13
    Kip101, Kip279 / 3        52 + 24      66 + 33       51 + 24    177 + 33
    TruncateToHighWatermark   46 + 24      60 + 33       45 + 24    171 + 33

Now: none in ``guard``; one gather in a ``fsc``'s ``expand`` and one an action
in `_expand_compact`'s; the scatter an action in its ``compact`` (the index
compaction, `part.select`) is that stage's own and stays."""

import ast
import collections
import functools
import json
import os

import jax
import jax.numpy as jnp
import pytest

from helpers import hand_models

from kafka_specification_tpu.engine import check
from kafka_specification_tpu.engine import pipeline as pl
from kafka_specification_tpu.engine.bfs import _Step, indexing_equations
from kafka_specification_tpu.obs.runctx import RunContext

MODELS = hand_models()
B, VCAP = 256, 1024


@functools.lru_cache(maxsize=None)
def _model(name):
    return MODELS[name]()


def by_stage(jaxpr, counts=None):
    """{"<stage>:gather" | "<stage>:scatter": equations}, sub-jaxprs
    included; <stage> is the equation's `kspec.` scope."""
    counts = collections.Counter() if counts is None else counts
    for eqn in jaxpr.eqns:
        name = eqn.primitive.name
        if name == "gather" or name.startswith("scatter"):
            scope = str(eqn.source_info.name_stack)
            stage = next(
                (s for s in pl.STAGES if f"{pl.STAGE_PREFIX}{s}" in scope),
                "unnamed")
            counts[f"{stage}:{'gather' if name == 'gather' else 'scatter'}"] += 1
        for sub in jax.core.jaxprs_in_params(eqn.params):
            by_stage(sub, counts)
    return counts


def _fused(model):
    return pl.FusedPipeline(_Step(model), model, None, None, None, True,
                            "device", None, 2, 1)


def _succ_args(model, widths, vcap=VCAP, bucket=B):
    W, K = sum(widths), model.spec.num_lanes
    return (jnp.zeros((bucket, K), jnp.uint32), jnp.zeros((W,), jnp.int32),
            jnp.zeros((W,), jnp.int32), jnp.ones((W,), bool),
            jnp.zeros((vcap,), jnp.uint32), jnp.zeros((vcap,), jnp.uint32),
            jnp.int32(0))


def _trace(name, body):
    model = _model(name)
    K = model.spec.num_lanes
    frontier, fvalid = jnp.zeros((B, K), jnp.uint32), jnp.ones((B,), bool)
    widths = tuple(256 for _ in model.actions)
    if body == "fgd":
        return jax.make_jaxpr(_fused(model)._build_guard(B))(frontier, fvalid)
    if body == "fsc":
        return jax.make_jaxpr(
            _fused(model)._build_succ(B, widths, VCAP, True, True)
        )(*_succ_args(model, widths))
    expand = _Step(model).make_expand(B, widths)
    return jax.make_jaxpr(
        lambda f, v: expand(f, jax.vmap(model.spec.unpack)(f), v)
    )(frontier, fvalid)


def _expansion_side(counts):
    return {k: v for k, v in counts.items()
            if k.split(":")[0] in ("guard", "expand", "compact")}


@pytest.mark.parametrize("body", ["fgd", "fsc", "expand_compact"])
@pytest.mark.parametrize("name", sorted(MODELS))
def test_the_expansion_side_gathers_the_packed_rows_and_nothing_else(
        name, body):
    n_actions = len(_model(name).actions)
    got = _expansion_side(by_stage(_trace(name, body).jaxpr))
    want = {
        "fgd": {},
        "fsc": {"expand:gather": 1},
        "expand_compact": {"expand:gather": n_actions,
                           "compact:scatter": n_actions},
    }[body]
    assert got == want


@pytest.mark.parametrize("name", sorted(MODELS))
def test_no_kernel_or_predicate_indexes_by_gather(name):
    model = _model(name)
    rows = jnp.zeros((4, model.spec.num_lanes), jnp.uint32)
    states = jax.vmap(model.spec.unpack)(rows)
    choice = jnp.zeros((4,), jnp.int32)
    for a in model.actions:
        jaxpr = jax.make_jaxpr(jax.vmap(a.kernel))(states, choice)
        assert indexing_equations(jaxpr.jaxpr) == (0, 0), a.name
    preds = [(i.name, i.pred) for i in model.invariants]
    if model.constraint is not None:
        preds.append(("CONSTRAINT", model.constraint))
    for pname, pred in preds:
        jaxpr = jax.make_jaxpr(jax.vmap(pred))(states)
        assert indexing_equations(jaxpr.jaxpr) == (0, 0), pname


@pytest.mark.parametrize("name", ["Kip320/3", "AsyncIsr/4"])
def test_compile_spans_carry_the_count_of_the_program_they_traced(
        name, tmp_path):
    """A cold run's ``compile`` spans say which programs index by gather:
    every one has both attributes, and those of the fused pair equal a
    fresh trace of the same body at the span's own shapes."""
    model = MODELS[name]()  # a cold model: nothing of it compiled yet
    run = RunContext(str(tmp_path))
    res = check(model, pipeline="fused", max_depth=4, min_bucket=32,
                chunk_size=256, compact_gate=32, run=run,
                stats_path=os.devnull)
    run.deactivate()
    assert res.stats["pipeline"] == "fused"
    with open(os.path.join(run.dir, "spans.jsonl")) as fh:
        spans = [json.loads(line) for line in fh]
    compiled = [s for s in spans if s.get("span") == "compile"]
    assert compiled
    fused = _fused(model)
    seen = set()
    for s in compiled:
        assert isinstance(s["gathers"], int) and isinstance(s["scatters"], int)
        K = model.spec.num_lanes
        if s["program"] == "fused-guards":
            jaxpr = jax.make_jaxpr(fused._build_guard(s["bucket"]))(
                jnp.zeros((s["bucket"], K), jnp.uint32),
                jnp.ones((s["bucket"],), bool))
            assert (s["gathers"], s["scatters"]) == (0, 0)
        elif s["program"] == "fused-successors":
            widths = ast.literal_eval(s["widths"])
            jaxpr = jax.make_jaxpr(
                fused._build_succ(s["bucket"], widths, s["vcap"], True, True)
            )(*_succ_args(model, widths, s["vcap"], s["bucket"]))
        else:
            continue
        seen.add(s["program"])
        assert (s["gathers"], s["scatters"]) == indexing_equations(jaxpr.jaxpr)
    assert seen == {"fused-guards", "fused-successors"}
