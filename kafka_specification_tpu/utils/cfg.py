"""TLC-compatible .cfg parsing and model instantiation.

The reference corpus shipped no TLC configs (`*.toolbox` is gitignored,
/root/reference/.gitignore:1), so this framework authors its own (configs/)
in stock TLC .cfg syntax — the north-star requirement is that existing .cfg
files drive the TPU engine unchanged (BASELINE.json "north_star").

Supported subset (what TLC configs for this corpus need):
  CONSTANT / CONSTANTS   name = value   (ints, model-value sets {a, b, c})
  INVARIANT / INVARIANTS name...
  CONSTRAINT name                        (AsyncIsr's bound; see below)
  SPECIFICATION                          (parsed, informational — each module
                                          has exactly one Spec shape)
  INIT / NEXT                            (each module has exactly one Init and
                                          one Next; the names are not resolved,
                                          and a logged line says so)
  SYMMETRY name                          (TLC's symmetry reduction: `name` must
                                          be the operator the module declares,
                                          `Symm == Permutations(Replicas)` in
                                          the MC wrapper modules below; the
                                          single-device engine then stores one
                                          state an orbit, docs/engine.md)
  CHECK_DEADLOCK TRUE|FALSE              (default FALSE: the bounded models
                                          deadlock by design, SURVEY.md §2.4)
  \\* and (* ... *) comments

Replica sets are given as model-value sets ({r1, r2, r3}); the engine maps
them to indices 0..N-1.  AsyncIsr's CONSTRAINT references bounds that TLC
would read from the constraint's definition in a .tla override; here the
bounds come from the MaxVersion constant (an authored extension, documented
in configs/AsyncIsr.cfg).
"""

from __future__ import annotations

import logging
import re
from dataclasses import dataclass, field
from pathlib import Path

_log = logging.getLogger(__name__)


@dataclass
class TlcConfig:
    constants: dict = field(default_factory=dict)  # name -> int | list[str]
    invariants: list = field(default_factory=list)
    constraints: list = field(default_factory=list)
    specification: str | None = None
    check_deadlock: bool = False
    symmetry: str | None = None  # the SYMMETRY stanza's operator name


_SECTIONS = {
    "CONSTANT": "constants",
    "CONSTANTS": "constants",
    "INVARIANT": "invariants",
    "INVARIANTS": "invariants",
    "CONSTRAINT": "constraints",
    "CONSTRAINTS": "constraints",
    "SPECIFICATION": "specification",
    "INIT": "init",
    "NEXT": "next",
    "CHECK_DEADLOCK": "check_deadlock",
    "SYMMETRY": "symmetry",
}


def _strip_comments(text: str) -> str:
    text = re.sub(r"\(\*.*?\*\)", " ", text, flags=re.S)
    return "\n".join(line.split("\\*")[0] for line in text.splitlines())


def parse_cfg(path_or_text) -> TlcConfig:
    if isinstance(path_or_text, Path):
        text = path_or_text.read_text()
    elif "\n" not in str(path_or_text) and Path(str(path_or_text)).exists():
        text = Path(str(path_or_text)).read_text()
    elif "\n" not in str(path_or_text) and str(path_or_text).endswith(".cfg"):
        # one line that ends in `.cfg` is a path, never a cfg's text: say
        # so, and not `KeyError: 'Replicas'` from whoever builds the model
        raise FileNotFoundError(f"no cfg file at {str(path_or_text)!r}")
    else:
        text = str(path_or_text)
    cfg = TlcConfig()
    section = None
    for raw in _strip_comments(text).splitlines():
        line = raw.strip()
        if not line:
            continue
        head = line.split()[0].upper()
        if head in _SECTIONS:
            section = _SECTIONS[head]
            rest = line[len(line.split()[0]) :].strip()
            if not rest:
                continue
            line = rest
        if section == "constants":
            m = re.match(r"(\w+)\s*(?:=|<-)\s*(.+)", line)
            if not m:
                raise ValueError(f"cannot parse constant assignment: {line!r}")
            name, val = m.group(1), m.group(2).strip()
            if val.startswith("{"):
                cfg.constants[name] = [
                    v.strip() for v in val.strip("{} ").split(",") if v.strip()
                ]
            elif re.fullmatch(r"-?\d+", val):
                cfg.constants[name] = int(val)
            else:
                cfg.constants[name] = val  # model value (e.g. Leader = r1)
        elif section == "invariants":
            cfg.invariants.extend(line.split())
        elif section == "constraints":
            cfg.constraints.extend(line.split())
        elif section == "specification":
            cfg.specification = line.split()[0]
        elif section == "check_deadlock":
            cfg.check_deadlock = line.strip().upper() == "TRUE"
        elif section == "symmetry":
            if cfg.symmetry is not None or len(line.split()) != 1:
                raise ValueError(
                    f"SYMMETRY takes one operator name, got {line!r}"
                    + (f" after {cfg.symmetry!r}" if cfg.symmetry else "")
                )
            cfg.symmetry = line
        elif section in ("init", "next"):
            # not resolved: every module here has one Init and one Next
            _log.warning(
                "%s %s: ignored (each module's own %s is checked)",
                section.upper(), line, section.capitalize(),
            )
    return cfg


# --------------------------------------------------------------------------
# module registry: .cfg + module name -> Model / OracleModel factories
# --------------------------------------------------------------------------

KAFKA_VARIANTS = ("KafkaTruncateToHighWatermark", "Kip101", "Kip279")

# shipped .cfg files whose stem is not a module name (TLC pairs Model.cfg
# with Model.tla; these document their explicit `--module`)
CFG_MODULE_ALIASES = {"Kip320Stretch": "Kip320", "Kip320FiveBroker": "Kip320",
                      "AsyncIsrFourBroker": "AsyncIsr",
                      "MCKip320FiveBroker": "MCKip320",
                      "Kip279FourBroker": "Kip279",
                      "MCKip279FiveBroker": "MCKip279"}

# The wrapper modules a TLC user writes to run a spec under SYMMETRY: the
# corpus's modules define no symmetry set, so the conventional
# `MC<Spec>.tla` does (`EXTENDS <Spec>, TLC` and
# `Symm == Permutations(Replicas)`), and the .cfg says `SYMMETRY Symm`.
# wrapper -> (the module it extends, the operator it defines).
MC_MODULES = {
    "MCKip320": ("Kip320", "Symm"),
    "MCKip320FirstTry": ("Kip320FirstTry", "Symm"),
    **{f"MC{v}": (v, "Symm") for v in KAFKA_VARIANTS},
}


def resolve_symmetry(module: str, cfg: TlcConfig) -> tuple:
    """-> (the module whose Spec is checked, whether the SYMMETRY stanza
    switches its reduction on).  A wrapper module without the stanza is the
    module it extends; a stanza naming an operator the module does not
    define is TLC's "unknown operator", an error here too (it was parsed
    and ignored until PR 38: unreduced counts with no word said)."""
    base, operator = MC_MODULES.get(module, (module, None))
    if cfg.symmetry is None:
        return base, False
    if operator is None:
        hint = next((w for w, (b, _) in MC_MODULES.items() if b == module), None)
        raise ValueError(
            f"SYMMETRY {cfg.symmetry}: module {module!r} defines no such "
            "operator (unknown operator)"
            + (f"; its wrapper {hint!r} defines `Symm == "
               f"Permutations(Replicas)`: pass --module {hint}" if hint else
               "; no symmetry set is declared for it")
        )
    if cfg.symmetry != operator:
        raise ValueError(
            f"SYMMETRY {cfg.symmetry}: module {module!r} defines "
            f"{operator!r}, not {cfg.symmetry!r} (unknown operator)"
        )
    return base, True


def _setlen(v) -> int:
    return len(v) if isinstance(v, list) else int(v)


def resolved_invariants(module: str, cfg) -> tuple:
    """The invariant names, in order, the model built by :func:`build_model`
    for this module+cfg will check — the .cfg order, per-module defaults
    when the .cfg names none, and the fixed built-in TypeOk for the small
    models whose builders take no invariant selection.  The serving path's
    batched verdict replay (service/batch.py) keys on exactly this set, so
    it lives here next to build_model's own resolution rather than as a
    second table that could drift.  Unknown modules raise KeyError, the
    same loud failure build_model gives them."""
    module = MC_MODULES.get(module, (module,))[0]
    if module in ("IdSequence", "FiniteReplicatedLog"):
        return ("TypeOk",)  # fixed by the builders; cfg selection ignored
    if module in KAFKA_VARIANTS or module in ("Kip320", "Kip320FirstTry"):
        return tuple(cfg.invariants) or ("TypeOk",)
    if module == "AsyncIsr":
        return tuple(cfg.invariants) or ("TypeOk", "ValidHighWatermark")
    raise KeyError(f"unknown module {module!r}")


def _with_names(built, constants):
    """Record the .cfg's replica model-value names (`Replicas = {b1, b2,
    b3}`) in the model's meta so counterexample traces render with the
    config's own vocabulary (utils/pretty), the way TLC echoes the model
    values it was given."""
    names = constants.get("Replicas")
    if isinstance(names, list) and hasattr(built, "meta"):
        built.meta.setdefault("replica_names", list(names))
    return built


def build_model(
    module: str,
    cfg: TlcConfig,
    oracle: bool = False,
    emitted: bool = False,
    reference=None,
    analysis_gate: bool = True,
):
    """Instantiate the tensor model (or its oracle twin) for a TLA+ module
    name under a parsed TLC config.

    reference: explicit reference-checkout path for the emitted builders
    (default: KSPEC_REFERENCE env var, resolved lazily — models/emitted
    .ref_path); `cli validate --reference` threads through here so one
    knob controls both resolutions.

    CONSTRAINT is only meaningful for AsyncIsr in this corpus (its bound is
    driven by the MaxOffset/MaxVersion constants); naming one for any other
    module is rejected rather than silently ignored.

    emitted=True builds the model mechanically from the reference TLA+ text
    (models/emitted — no hand-translated kernels).  Invariant names resolve
    to the corpus-wide intent readings on both paths (LeaderInIsr guarded
    on leader # None, AsyncIsr TypeOk admitting pendingVersion = Nil); the
    literal reference predicates — False at Init — remain available as
    LeaderInIsrLiteral / TypeOkLiteral (PARITY.md)."""
    from ..obs.ledger import PROCESS

    # (runs before any run context is open, in the CLI, the daemon and the
    # benchmark alike: the process ledger's `model_s` is its record)
    PROCESS.mark_backend(start=False)
    with PROCESS.model():
        return _build_model(module, cfg, oracle, emitted, reference,
                            analysis_gate)


def _build_model(module, cfg, oracle, emitted, reference, analysis_gate):
    if emitted and oracle:
        raise ValueError("emitted models have no oracle twin (the oracle IS "
                         "an independent path; use oracle=False)")
    module, symmetric = resolve_symmetry(module, cfg)
    if symmetric and emitted:
        raise ValueError(
            f"SYMMETRY {cfg.symmetry}: the emitted kernel source declares "
            "no field roles; build the hand model (--hand)"
        )

    def _sound(built):
        # build-time encoding-soundness gate (analysis; KSPEC_ANALYZE=0
        # disables): an unsound (config, schema) pair refuses to build —
        # `cli check` then exits 2 with the interval counterexample
        # instead of exploring to a wrong verdict (docs/analysis.md).
        # Oracle twins carry no tensor schema and are exempt (their
        # entry points share the AsyncIsr cliff check directly).
        # analysis_gate=False is for callers that run the FULL analysis
        # themselves (`cli analyze` wants the finding list, not the
        # first-HIGH refusal).
        if analysis_gate and not oracle:
            from ..analysis import require_encoding_sound

            require_encoding_sound(built)
        return built

    if cfg.constraints and module != "AsyncIsr":
        raise ValueError(
            f"CONSTRAINT {cfg.constraints} is not supported for module "
            f"{module!r} (only AsyncIsr's bound is defined in this corpus)"
        )
    c = cfg.constants
    if module == "IdSequence":
        if emitted:
            return _sound(_emitted_id_sequence(int(c["MaxId"]), reference))
        from ..models import id_sequence as m

        return _sound((m.make_oracle if oracle else m.make_model)(int(c["MaxId"])))
    if module == "FiniteReplicatedLog":
        if emitted:
            return _sound(_emitted_frl(
                _setlen(c["Replicas"]),
                int(c["LogSize"]),
                _setlen(c["LogRecords"]),
                reference,
            ))
        from ..models import finite_replicated_log as m

        return _sound((m.make_oracle if oracle else m.make_model)(
            _setlen(c["Replicas"]), int(c["LogSize"]), _setlen(c["LogRecords"])
        ))
    if module in KAFKA_VARIANTS or module in ("Kip320", "Kip320FirstTry"):
        from ..models.kafka_replication import Config

        kcfg = Config(
            n_replicas=_setlen(c["Replicas"]),
            log_size=int(c["LogSize"]),
            max_records=int(c["MaxRecords"]),
            max_leader_epoch=int(c["MaxLeaderEpoch"]),
        )
        invs = resolved_invariants(module, cfg)
        if emitted:
            from ..models.emitted import make_emitted_model

            built = make_emitted_model(
                module, kcfg, invariants=invs, reference=reference
            )
        elif module in KAFKA_VARIANTS:
            from ..models import variants as m

            built = (m.make_oracle if oracle else m.make_model)(
                module, kcfg, invs, symmetric=symmetric)
        else:
            from ..models import kip320 as m

            if module == "Kip320":
                built = (m.make_oracle if oracle else m.make_model)(
                    kcfg, invs, symmetric=symmetric)
            else:
                built = (
                    m.make_first_try_oracle if oracle else m.make_first_try_model
                )(kcfg, invs, symmetric=symmetric)
        # Partitions = K (authored constant, not in the reference): the
        # K-partition product space — the reading of the "5 brokers /
        # 3 partitions" stretch workload (BASELINE.md note; models/product.py)
        built = _with_names(built, c)
        k = _setlen(c.get("Partitions", 1))
        if k > 1:
            # (a model under SYMMETRY is refused there, by name)
            from ..models.product import product_model, product_oracle

            built = (product_oracle if oracle else product_model)(built, k)
        return _sound(built)
    if module == "AsyncIsr":
        from ..models import async_isr as m

        acfg = m.AsyncIsrConfig(
            n_replicas=_setlen(c["Replicas"]),
            max_offset=int(c["MaxOffset"]),
            max_version=int(c.get("MaxVersion", c["MaxOffset"])),
        )
        invs = resolved_invariants(module, cfg)
        if emitted:
            from ..models.emitted import make_emitted_async_isr

            return _sound(_with_names(
                make_emitted_async_isr(
                    acfg, invariants=invs, reference=reference
                ),
                c,
            ))
        return _sound(
            _with_names((m.make_oracle if oracle else m.make_model)(acfg, invs), c)
        )
    raise KeyError(f"unknown module {module!r}")


def _emitted_inferred(module: str, consts: dict, name: str, reference=None):
    """Emit a module whose tensor schema is INFERRED from its TypeOk
    (utils/schema_infer) — no per-module mapping code (round-5 verdict
    item 7).  Modules whose state needs a representation choice beyond
    bounds (the message-set encodings of L3/AsyncIsr, PARITY.md) keep
    their curated schemas in models/emitted — the documented override
    hook, not this path."""
    from ..models.emitted import ref_path
    from .schema_infer import infer_schemas, spec_from_schemas
    from .tla_emit import build_model as emit, load_defs
    from .tla_frontend import parse_tla

    ref = ref_path(reference)
    mod = parse_tla(ref / f"{module}.tla")
    defs = load_defs(ref, module)
    schemas = infer_schemas(defs, consts, mod.variables)
    return emit(
        mod, consts, schemas, spec_from_schemas(schemas), name=name
    )


def _emitted_id_sequence(max_id: int, reference=None):
    return _emitted_inferred(
        "IdSequence",
        {"MaxId": max_id},
        f"IdSequence(emitted,{max_id})",
        reference,
    )


def _emitted_frl(n: int, log_size: int, n_records: int, reference=None):
    return _emitted_inferred(
        "FiniteReplicatedLog",
        {
            "Replicas": (0, n - 1),
            "LogRecords": (0, n_records - 1),
            "Nil": -1,
            "LogSize": log_size,
        },
        f"FiniteReplicatedLog(emitted,{n}x{log_size})",
        reference,
    )
