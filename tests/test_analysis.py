"""kspec analyze — the spec & engine static-analysis subsystem.

Pins the PR's acceptance matrix (docs/analysis.md):

- the tier-1 STATIC GATE as a test (compileall + pyflakes when present),
  so the gate runs on every pytest invocation, not only via
  scripts/check_tier1.sh;
- every shipped model (TruncateToHW / Kip101 / Kip279 / Kip320 /
  Kip320FirstTry / AsyncIsr / IdSequence / FRL + a product config)
  analyzes CLEAN;
- the seeded-mutant matrix: out-of-range update, vacuous clause, frame
  write, read-of-unwritten field, cross-thread mutation (static AND
  runtime) — each class DETECTED with a machine-readable finding;
- an encoding-unsound (config, schema) pair is REFUSED by the engine at
  build time with the interval counterexample (and KSPEC_ANALYZE=0
  documented as the override);
- the AsyncIsr N=5 regression: the general spec-width pass produces the
  same actionable ValueError class the hand-written check did;
- `cli analyze` is jax-free (runs with jax poisoned), emits the
  schema-versioned kspec-analysis/1 record, and exits non-zero on HIGH
  findings;
- a KSPEC_TSAN-armed overlap fault-matrix run passes with zero
  ownership violations (the fault tests double as a race harness).
"""

import compileall
import json
import os
import subprocess
import sys
import textwrap

import pytest

import jax.numpy as jnp

from kafka_specification_tpu.analysis import (
    ANALYSIS_SCHEMA,
    Finding,
    analysis_record,
    analyze_engine_sources,
    require_encoding_sound,
)
from kafka_specification_tpu.analysis.encoding import (
    EncodingUnsound,
    analyze_model,
    spec_fits_errors,
    verify_model_encoding,
)
from kafka_specification_tpu.analysis.ownership import (
    OwnershipViolation,
    arm_all,
    check_module_contract,
    disarm_all,
    lint_purity,
)
from kafka_specification_tpu.engine.bfs import check
from kafka_specification_tpu.models import async_isr
from kafka_specification_tpu.models import finite_replicated_log as frl
from kafka_specification_tpu.models import id_sequence, kip320, product, variants
from kafka_specification_tpu.models.base import Action, Invariant, Model
from kafka_specification_tpu.models.kafka_replication import Config
from kafka_specification_tpu.ops.packing import Field, StateSpec

pytestmark = pytest.mark.analysis

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _nontrivial(findings):
    return [f for f in findings if f.severity != "INFO"]


# --------------------------------------------------------------------------
# satellite: the static gate as a tier-1 test
# --------------------------------------------------------------------------


def test_static_gate():
    """compileall (+ pyflakes when installed) over the package — the
    scripts/check_tier1.sh stage 1 gate, now running on every pytest
    invocation."""
    ok = compileall.compile_dir(
        os.path.join(_REPO, "kafka_specification_tpu"),
        quiet=2, force=False,
    )
    assert ok, "compileall found syntax errors in the package"
    try:
        import pyflakes  # noqa: F401
    except ImportError:
        return  # advisory layer absent: compileall already ran
    out = subprocess.run(
        [sys.executable, "-m", "pyflakes",
         "kafka_specification_tpu", "scripts", "chip_smoke.py"],
        cwd=_REPO, capture_output=True, text=True, timeout=120,
    )
    assert out.returncode == 0, out.stdout + out.stderr


# --------------------------------------------------------------------------
# the shipped-model matrix analyzes clean
# --------------------------------------------------------------------------

_CFG = Config(3, 2, 2, 2)


def _shipped_models():
    return [
        variants.make_model("KafkaTruncateToHighWatermark", _CFG),
        variants.make_model("Kip101", _CFG),
        variants.make_model("Kip279", _CFG),
        kip320.make_model(_CFG),
        kip320.make_first_try_model(_CFG),
        id_sequence.make_model(3),
        frl.make_model(2, 2, 2),
        async_isr.make_model(async_isr.AsyncIsrConfig(3, 2, 2)),
        # the product config (BASELINE stretch shape at tiny constants)
        product.product_model(kip320.make_model(_CFG), 2),
    ]


def test_shipped_models_analyze_clean():
    for m in _shipped_models():
        findings = _nontrivial(analyze_model(m))
        assert not findings, (
            m.name, [(f.kind, f.message) for f in findings]
        )
        # every action carries a declared write set -> the frame pass
        # actually ran (not vacuously skipped)
        assert all(a.writes is not None for a in m.actions), m.name


def test_engine_sources_analyze_clean():
    """Self-application: ownership contracts verify and the purity/order
    lint over engine/pipeline.py + parallel/sharded.py +
    ops/devlevel.py (the device pipeline's in-jit helpers) is clean."""
    assert analyze_engine_sources() == []


def test_purity_lint_covers_device_level_helpers():
    """The device pipeline's traced helpers are IN the self-application
    sweep (a host-side np.*/.item() call inside the while_loop body
    must fail CI, not ship): the module is registered, its traced
    functions are marked, and a seeded host-materialization mutant of
    it is detected."""
    import kafka_specification_tpu.analysis as an
    from kafka_specification_tpu.analysis.ownership import lint_purity

    rel = "kafka_specification_tpu/ops/devlevel.py"
    assert rel in an.PURITY_MODULES
    path = os.path.join(an.repo_root(), rel)
    src = open(path).read()
    assert "# kspec: traced" in src
    # seeded mutant: a .item() materialization inside a traced helper
    mutated = src.replace(
        "count = jnp.sum(valid, dtype=jnp.int32)",
        "count = jnp.sum(valid, dtype=jnp.int32)\n"
        "    _bad = count.item()",
    )
    assert mutated != src
    import tempfile

    with tempfile.NamedTemporaryFile(
        "w", suffix=".py", delete=False
    ) as fh:
        fh.write(mutated)
        tmp = fh.name
    try:
        findings = lint_purity(tmp, rel)
        assert any(f.kind == "host-materialization" for f in findings), \
            [(f.kind, f.message) for f in findings]
    finally:
        os.unlink(tmp)


def test_purity_lint_covers_sharded_level_body():
    """The SHARDED device-resident level program's while-loop body is in
    the self-application sweep (parallel/sharded.py is a registered
    PURITY_MODULE, the level helpers are `# kspec: traced`-marked), and
    a seeded host-materialization mutant INSIDE the loop body is
    detected — a .item() between collectives would deadlock a real mesh,
    so it must fail CI, not ship."""
    import kafka_specification_tpu.analysis as an
    from kafka_specification_tpu.analysis.ownership import lint_purity

    rel = "kafka_specification_tpu/parallel/sharded.py"
    assert rel in an.PURITY_MODULES
    path = os.path.join(an.repo_root(), rel)
    src = open(path).read()
    # BOTH level bodies (device backend + the host deferred-probe twin)
    # and their conds are traced-marked
    assert src.count("def level_body(fbuf, flen, ncs, vhi, vlo, vn):  "
                     "# kspec: traced") == 1
    assert src.count("def level_body(fbuf, flen, ncs):  "
                     "# kspec: traced") == 1
    # seeded mutant: a .item() materialization inside the while-loop
    # body — the needle now occurs in both level programs' loop bodies,
    # so the mutant seeds into both (the lint must flag either)
    needle = "            ovf = ovf | this_ovf | ln_ovf\n"
    assert src.count(needle) == 2
    mutated = src.replace(
        needle, needle + "            _bad = int(ovf.item())\n"
    )
    assert mutated != src
    import tempfile

    with tempfile.NamedTemporaryFile(
        "w", suffix=".py", delete=False
    ) as fh:
        fh.write(mutated)
        tmp = fh.name
    try:
        findings = lint_purity(tmp, rel)
        assert any(f.kind == "host-materialization" for f in findings), \
            [(f.kind, f.message) for f in findings]
    finally:
        os.unlink(tmp)


def test_field_hulls_pin_against_packing_widths():
    """The stable analysis.field_hulls export (the device pipeline's
    pack-width precondition): on every shipped model the per-field
    reachable-value hull sits INSIDE the declared packed range, so the
    hull-derived pack width never exceeds ops/packing.Field.width — the
    proof that the engine's shipped bit layout is wide enough for
    everything the kernels can write (the general AsyncIsr N<=4 cliff,
    now a queryable artifact)."""
    from kafka_specification_tpu.analysis import field_hulls
    from kafka_specification_tpu.analysis.encoding import (
        hull_pack_widths,
    )

    checked = 0
    for m in _shipped_models():
        hulls = field_hulls(m)
        widths = hull_pack_widths(hulls)
        for f in m.spec.fields:
            lo, hi = hulls[f.name]
            assert f.lo <= lo <= hi <= f.hi, (m.name, f.name, hulls)
            assert widths[f.name] <= f.width, (m.name, f.name)
            checked += 1
    assert checked > 50  # the matrix really swept


def test_field_hulls_strict_raises_on_opaque_kernels():
    """strict=True (the device pipeline's entry ticket) refuses to
    guess: a kernel outside the interval domain raises
    AnalysisUnsupported instead of returning a widened hull — while the
    non-strict form widens honestly to the declared range."""
    from kafka_specification_tpu.analysis import field_hulls
    from kafka_specification_tpu.analysis.interval import (
        AnalysisUnsupported,
    )

    def opaque(s, c):
        raise RuntimeError("not abstractly executable")

    m = _mutant_model(
        "Opaque", [Action("Op", 1, opaque, writes=("x",))]
    )
    with pytest.raises(AnalysisUnsupported):
        field_hulls(m, strict=True)
    hulls = field_hulls(m)  # non-strict: declared-range widening
    f = m.spec.fields[0]
    assert hulls[f.name] == (f.lo, f.hi)


# --------------------------------------------------------------------------
# seeded-mutant matrix: every class must be DETECTED
# --------------------------------------------------------------------------


def _tiny_spec():
    return StateSpec([Field("x", (), 0, 3), Field("y", (2,), 0, 3)])


def _mutant_model(name, actions, spec=None):
    return Model(
        name=name,
        spec=spec or _tiny_spec(),
        init_states=lambda: [{"x": 0, "y": [0, 0]}],
        actions=actions,
        invariants=[Invariant("True", lambda s: s["x"] >= 0)],
    )


def test_mutant_out_of_range_update_detected():
    def kernel(s, c):
        # guard admits x == 3, update is neither clamped nor pruned
        return s["x"] <= 3, {**s, "x": s["x"] + 1}

    m = _mutant_model("mutant-overflow",
                      [Action("Bump", 1, kernel,
                              writes=frozenset({"x"}))])
    fs = [f for f in analyze_model(m) if f.kind == "encoding-overflow"]
    assert fs, "out-of-range update not detected"
    # the machine-readable interval counterexample
    d = fs[0].data
    assert d["field"] == "x" and d["declared"] == [0, 3]
    assert d["interval"][1] > 3 and d["action"] == "Bump"


def test_mutant_vacuous_clause_detected():
    def kernel(s, c):
        # x > 3 is unsatisfiable under the declared bound x <= 3
        return (s["x"] > 3) & (s["x"] >= 0), {**s, "x": s["x"]}

    m = _mutant_model("mutant-vacuous",
                      [Action("Never", 2, kernel, writes=frozenset())])
    fs = [f for f in analyze_model(m) if f.kind == "vacuous-action"]
    assert fs and fs[0].data["action"] == "Never"


def test_mutant_frame_write_detected():
    def kernel(s, c):
        ok = s["x"] <= 2
        # writes y but only declares x
        return ok, {**s, "x": jnp.minimum(s["x"] + 1, 3),
                    "y": s["y"].at[0].set(0)}

    m = _mutant_model("mutant-frame",
                      [Action("Sneaky", 1, kernel,
                              writes=frozenset({"x"}))])
    fs = [f for f in analyze_model(m) if f.kind == "frame-violation"]
    assert fs and fs[0].data["extra_writes"] == ["y"]


def test_mutant_read_of_unwritten_field_detected():
    def kernel(s, c):
        # guard reads y; no action ever writes y
        return (s["y"][0] <= 3) & (s["x"] <= 2), \
            {**s, "x": jnp.minimum(s["x"] + 1, 3)}

    m = _mutant_model("mutant-unwritten",
                      [Action("ReadsY", 1, kernel,
                              writes=frozenset({"x"}))])
    kinds = {f.kind for f in analyze_model(m)}
    assert "read-of-unwritten-field" in kinds


def test_skipped_action_suppresses_dead_field_guessing():
    """Honesty rule: a kernel outside the abstract domain contributes
    UNKNOWN writes — with no declared write set the dead-field pass must
    not guess; with one, the declared set counts as written."""
    def opaque(s, c):
        raise RuntimeError("not abstractly executable")

    m = _mutant_model("mutant-skip-undeclared",
                      [Action("Opaque", 1, opaque)])
    kinds = [f.kind for f in analyze_model(m)]
    assert "analysis-skip" in kinds
    assert "dead-field" not in kinds and \
        "read-of-unwritten-field" not in kinds
    # declared writes on the skipped action keep the pass precise: x is
    # covered by the declaration, y is genuinely dead
    m2 = _mutant_model("mutant-skip-declared",
                       [Action("Opaque", 1, opaque,
                               writes=frozenset({"x"}))])
    dead = [f.data["field"] for f in analyze_model(m2)
            if f.kind == "dead-field"]
    assert dead == ["y"]


def test_mutant_spec_width_rejected_at_model_construction():
    # hi > int32: Model.__post_init__ must refuse (the generalized
    # AsyncIsr cliff — no hand-written inequality anywhere)
    with pytest.raises(EncodingUnsound, match="int32"):
        _mutant_model(
            "mutant-width", [],
            # width 32 passes the lane assert; the VALUE range exceeds
            # the int32 element dtype — exactly the silent-wrap class
            spec=StateSpec([Field("wide", (), 0, (1 << 31) + 7)]),
        )


def test_engine_refuses_unsound_model_at_build_time(monkeypatch):
    """check() must refuse an encoding-unsound model BEFORE exploring —
    the wrong-verdict prevention contract — and KSPEC_ANALYZE=0 is the
    documented override."""
    def kernel(s, c):
        return s["x"] <= 3, {**s, "x": s["x"] + 1}

    m = _mutant_model("mutant-refused",
                      [Action("Bump", 1, kernel,
                              writes=frozenset({"x"}))])
    with pytest.raises(EncodingUnsound) as ei:
        check(m, max_depth=1, min_bucket=32)
    # the interval counterexample rides the typed error
    assert ei.value.findings and \
        ei.value.findings[0].data["field"] == "x"
    # the override knob (and a fresh name so the memo can't mask it)
    monkeypatch.setenv("KSPEC_ANALYZE", "0")
    m2 = _mutant_model("mutant-overridden",
                       [Action("Bump", 1, kernel,
                               writes=frozenset({"x"}))])
    res = check(m2, max_depth=1, min_bucket=32)
    assert res.total >= 1  # explored (at the operator's own risk)


def test_require_encoding_sound_memoizes_structural_identity():
    m = kip320.make_model(_CFG)
    require_encoding_sound(m)
    from kafka_specification_tpu.analysis import (
        _VERIFIED_MODELS,
        _model_memo_key,
    )

    assert _model_memo_key(m) in _VERIFIED_MODELS
    # a SAME-NAMED model with different field bounds must NOT ride the
    # memo (emitted names drop constants; the key is structural)
    import dataclasses

    m2 = kip320.make_model(Config(3, 3, 2, 2))
    m2 = dataclasses.replace(m2, name=m.name)
    assert _model_memo_key(m2) not in _VERIFIED_MODELS


# --------------------------------------------------------------------------
# satellite: AsyncIsr N=5 — same actionable error class, general detector
# --------------------------------------------------------------------------


def test_async_isr_n5_regression_same_error_class():
    """The hand-written N<=4 inequality is gone; the general spec-width
    pass is the detector — and the old actionable message class is
    preserved at every entry point (the PR 4 contract)."""
    cfg = async_isr.AsyncIsrConfig(5, 1, 1)
    for entry in (async_isr.make_spec, async_isr.make_model,
                  async_isr.make_oracle, async_isr.check_encoding_bounds):
        with pytest.raises(ValueError, match="at most 4 replicas"):
            entry(cfg)
    # the general pass's machine-readable counterexample rides along
    with pytest.raises(EncodingUnsound) as ei:
        async_isr.check_encoding_bounds(cfg)
    f = ei.value.findings[0]
    assert f.kind == "spec-width" and f.data["field"] == "req_bits"
    assert f.data["declared"][1] == (1 << 32) - 1
    # N = 4 keeps building (the documented edge)
    async_isr.make_spec(async_isr.AsyncIsrConfig(4, 1, 1))


def test_spec_fits_errors_boundary():
    assert spec_fits_errors([Field("ok", (), -(1 << 31), (1 << 31) - 1)]) \
        == []
    assert spec_fits_errors([Field("bad", (), 0, 1 << 31)])[0].kind == \
        "spec-width"


# --------------------------------------------------------------------------
# ownership: static mutants + runtime TSAN
# --------------------------------------------------------------------------

_SYNTHETIC = textwrap.dedent('''
    THREAD_CONTRACT = {
        "schema": "kspec-ownership/1",
        "classes": {
            "W": {
                "lock": "_cv",
                "shared_locked": ["q"],
                "engine_only": ["state"],
                "immutable_after_init": ["name"],
                "worker_methods": ["_run"],
            },
        },
    }
    class W:
        def __init__(self):
            self.q = []
            self.state = 0
            self.name = "w"
        def _run(self):
            self.state = 1
            self.q.append(1)
            self.mystery = 2
        def engine_step(self):
            self.q.append(2)
            self.name = "x"
''')


def test_ownership_checker_detects_mutants(tmp_path):
    p = tmp_path / "synthetic.py"
    p.write_text(_SYNTHETIC)
    kinds = [f.kind for f in check_module_contract(str(p), "synthetic.py")]
    assert kinds.count("ownership-breach") == 2  # state@worker, name rebound
    assert kinds.count("unlocked-shared-write") == 2
    assert "unannotated-attribute" in kinds


def test_ownership_allow_comment_suppresses(tmp_path):
    src = _SYNTHETIC.replace(
        "        self.state = 1",
        "        self.state = 1  # kspec: allow(ownership-breach) test",
    ).replace(
        "        self.q.append(2)",
        "        self.q.append(2)  "
        "# kspec: allow(unlocked-shared-write) test",
    ).replace(
        '        self.name = "x"',
        '        self.name = "x"  # kspec: allow(ownership) category-wide',
    )
    assert src.count("kspec: allow") == 3
    p = tmp_path / "synthetic.py"
    p.write_text(src)
    kinds = [f.kind for f in check_module_contract(str(p), "synthetic.py")]
    # every documented suppression form works for its own kind; the
    # worker-side unlocked write and unannotated mutation remain
    assert kinds.count("ownership-breach") == 0
    assert kinds.count("unlocked-shared-write") == 1  # the worker one
    assert "unannotated-attribute" in kinds


def test_ownership_nested_callback_inherits_context(tmp_path):
    """A nested function NOT handed to submit()/AsyncJob() inherits its
    enclosing method's context — its mutations must not be invisible."""
    src = textwrap.dedent('''
        THREAD_CONTRACT = {
            "schema": "kspec-ownership/1",
            "classes": {
                "W": {
                    "lock": "_cv",
                    "shared_locked": ["q"],
                    "engine_only": ["state"],
                    "worker_methods": ["_run"],
                },
            },
        }
        class W:
            def engine_step(self):
                def cb():
                    self.q.append(1)      # unlocked shared write
                register(cb)
            def _run(self):
                f = lambda: self.q.append(2)  # unlocked, worker ctx
                f()
    ''')
    p = tmp_path / "nested.py"
    p.write_text(src)
    kinds = [f.kind for f in check_module_contract(str(p), "nested.py")]
    assert kinds.count("unlocked-shared-write") == 2


def test_where_truthiness_is_sound():
    """jnp truthiness: a raw-int condition whose interval excludes zero
    is definitely TRUE even when negative — the `where` hull must not
    hide the taken branch from the overflow check."""
    from kafka_specification_tpu.analysis.interval import (
        ABSTRACT_JNP,
        IVal,
        definitely_disabled,
    )

    out = ABSTRACT_JNP.where(IVal(-5, -1), 100, 0)
    assert (out.lo.item(), out.hi.item()) == (100, 100)
    assert ABSTRACT_JNP.all(IVal(-2, -1)).lo.item() == 1
    assert definitely_disabled(IVal(0, 0))
    assert not definitely_disabled(IVal(-2, -1))


def test_ownership_sees_chained_container_mutation(tmp_path):
    """`self.deleter.pending.append(...)` from worker context must charge
    the root attribute — interior mutations are not invisible."""
    src = textwrap.dedent('''
        THREAD_CONTRACT = {
            "schema": "kspec-ownership/1",
            "classes": {
                "W": {
                    "engine_only": ["deleter"],
                    "worker_methods": ["_run"],
                },
            },
        }
        class W:
            def _run(self):
                self.deleter.pending.append(1)
    ''')
    p = tmp_path / "chain.py"
    p.write_text(src)
    fs = check_module_contract(str(p), "chain.py")
    assert any(f.kind == "ownership-breach" and
               f.data["attr"] == "deleter" for f in fs)


def test_partial_skip_keeps_frame_checking():
    """A choice outside the abstract domain must not gate frame findings
    observed in the analyzable choices (observed changes understate)."""
    def kernel(s, c):
        if c == 1:
            raise RuntimeError("opaque choice")
        return s["x"] <= 2, {**s, "x": jnp.minimum(s["x"] + 1, 3),
                             "y": s["y"].at[0].set(0)}

    m = _mutant_model("mutant-partial-skip",
                      [Action("Sneaky", 2, kernel,
                              writes=frozenset({"x"}))])
    fs = analyze_model(m)
    assert any(f.kind == "frame-violation" and
               f.data.get("extra_writes") == ["y"] for f in fs)
    assert any(f.kind == "analysis-skip" for f in fs)


def test_tsan_catches_cross_thread_mutation():
    """Runtime mutant: a worker job mutating engine-only state must trip
    the sanitizer, and the violation propagates through wait() like any
    worker error."""
    from kafka_specification_tpu.overlap import AsyncWorker

    assert arm_all() > 0
    try:
        w = AsyncWorker("tsan-test")
        try:
            assert w.wait(w.submit("ok", lambda: 41)) == 41

            def evil():
                w.blocked_s = 1.0  # engine-only, from the worker

            with pytest.raises(OwnershipViolation, match="engine-thread"):
                w.wait(w.submit("evil", evil))
            with pytest.raises(OwnershipViolation, match="without holding"):
                w.jobs_done = 7  # shared, lock not held
        finally:
            w.close()
    finally:
        disarm_all()


@pytest.mark.fault
def test_tsan_overlap_fault_matrix_clean(tmp_path, monkeypatch):
    """The acceptance run: a KSPEC_TSAN-armed engine run exercising the
    async paths (forced spills + background merges + async checkpoint
    writes + an injected mid-merge crash and resume) produces ZERO
    ownership violations — the fault matrix doubles as a race harness."""
    assert arm_all() > 0
    try:
        tiny = Config(2, 2, 1, 1)

        def mk():
            return variants.make_model(
                "KafkaTruncateToHighWatermark", tiny, ("TypeOk",)
            )

        ck = str(tmp_path / "ck")
        monkeypatch.setenv("KSPEC_FAULT", "crash@merge:1")
        from kafka_specification_tpu.resilience.faults import InjectedCrash

        with pytest.raises(InjectedCrash):
            check(mk(), min_bucket=32, checkpoint_dir=ck, mem_budget=300)
        monkeypatch.delenv("KSPEC_FAULT")
        res = check(mk(), min_bucket=32, checkpoint_dir=ck,
                    mem_budget=300)
        ref = check(mk(), min_bucket=32, visited_backend="host")
        assert res.total == ref.total and res.diameter == ref.diameter
    finally:
        disarm_all()


# --------------------------------------------------------------------------
# purity / iteration-order lint mutants
# --------------------------------------------------------------------------


def test_purity_lint_detects_and_suppresses(tmp_path):
    src = textwrap.dedent('''
        import numpy as np

        def stage(x):  # kspec: traced
            n = int(x)
            return np.asarray(x)

        def ok_stage(x):  # kspec: traced
            # kspec: allow(host-materialization) static shape
            n = int(x)
            return n

        def host_side():
            for k in set(["a", "b"]):
                pass
            for k in sorted(set(["a", "b"])):
                pass
    ''')
    p = tmp_path / "mod.py"
    p.write_text(src)
    fs = lint_purity(str(p), "mod.py")
    kinds = [f.kind for f in fs]
    assert kinds.count("host-materialization") == 2  # int(x) + np.asarray
    assert kinds.count("set-iteration-order") == 1  # sorted() exempt


# --------------------------------------------------------------------------
# the record + CLI front door
# --------------------------------------------------------------------------


def test_analysis_record_schema():
    rec = analysis_record(
        [Finding(kind="encoding-overflow", severity="HIGH",
                 target="action:X", message="m", data={"a": 1})],
        targets=["t"],
    )
    assert rec["schema"] == ANALYSIS_SCHEMA
    assert rec["counts"]["HIGH"] == 1 and rec["ok"] is False
    assert rec["findings"][0]["data"] == {"a": 1}


def test_suppression_downgrades_with_justification():
    def kernel(s, c):
        return s["x"] <= 3, {**s, "x": s["x"] + 1}

    m = _mutant_model("mutant-suppressed",
                      [Action("Bump", 1, kernel,
                              writes=frozenset({"x"}))])
    m.meta["analysis_suppress"] = [
        {"kind": "encoding-overflow", "target": "Bump",
         "reason": "known-unsound test fixture"},
    ]
    fs = [f for f in analyze_model(m) if f.kind == "encoding-overflow"]
    assert fs and fs[0].severity == "INFO"
    assert fs[0].suppressed == "known-unsound test fixture"
    # suppressed findings do not trip the build gate
    verify_model_encoding(m)


def test_cli_analyze_is_jax_free_and_versioned(tmp_path):
    """`cli analyze --json` runs with jax poisoned (the operator/CI
    case), emits kspec-analysis/1, and exits 0 on the clean shipped
    matrix."""
    out = subprocess.run(
        [
            sys.executable, "-c",
            "import sys; sys.modules['jax'] = None\n"
            "from kafka_specification_tpu.utils.cli import main\n"
            "sys.exit(main(['analyze', '--json']))",
        ],
        cwd=_REPO, capture_output=True, text=True, timeout=120,
    )
    assert out.returncode == 0, out.stderr[-2000:]
    rec = json.loads(out.stdout)
    assert rec["schema"] == ANALYSIS_SCHEMA and rec["ok"] is True
    assert rec["counts"]["HIGH"] == 0
    assert any("Kip320" in t for t in rec["targets"])
    assert any("engine sources" in t for t in rec["targets"])


def test_cli_analyze_exits_nonzero_on_high(tmp_path):
    """A config whose schema cannot be packed soundly must exit non-zero
    with the HIGH finding in the record (AsyncIsr at 5 replicas)."""
    cfg = tmp_path / "AsyncIsr.cfg"
    cfg.write_text(
        "SPECIFICATION Spec\nCONSTANTS\n"
        "    Replicas = {r1, r2, r3, r4, r5}\n"
        "    MaxOffset = 1\n    MaxVersion = 1\n"
        "INVARIANTS TypeOk ValidHighWatermark\n"
    )
    out = subprocess.run(
        [sys.executable, "-m", "kafka_specification_tpu.utils.cli",
         "analyze", str(cfg), "--json", "--no-engine"],
        cwd=_REPO, capture_output=True, text=True, timeout=120,
    )
    assert out.returncode == 1, out.stderr[-2000:]
    rec = json.loads(out.stdout)
    assert rec["ok"] is False
    kinds = {f["kind"] for f in rec["findings"]}
    assert "spec-width" in kinds


def test_cli_check_refuses_unsound_cfg(tmp_path):
    """`cli check` at build time: the unsound (config, schema) pair is
    refused with exit 2 and the actionable message — it never explores."""
    cfg = tmp_path / "AsyncIsr.cfg"
    cfg.write_text(
        "SPECIFICATION Spec\nCONSTANTS\n"
        "    Replicas = {r1, r2, r3, r4, r5}\n"
        "    MaxOffset = 1\n    MaxVersion = 1\n"
        "INVARIANTS TypeOk ValidHighWatermark\n"
    )
    out = subprocess.run(
        [sys.executable, "-m", "kafka_specification_tpu.utils.cli",
         "check", str(cfg), "--cpu"],
        cwd=_REPO, capture_output=True, text=True, timeout=180,
        env={**os.environ, "JAX_PLATFORMS": "cpu"},
    )
    assert out.returncode == 2, (out.returncode, out.stderr[-1500:])
    assert "at most 4 replicas" in out.stderr
