"""Generic tensor-schema inference from TypeOk (utils/schema_infer).

Round-5 verdict item 7: `validate --emitted` / `check --emitted` for the
plain-state modules must need no hand-authored schema mapping — the
(variable -> tensor schema) map and the packed StateSpec both derive from
the reference module's own TypeOk conjuncts."""

from pathlib import Path

import pytest
from conftest import needs_reference

from kafka_specification_tpu.engine import check
from kafka_specification_tpu.models.emitted import ref_path
from kafka_specification_tpu.utils.schema_infer import (
    SchemaInferenceError,
    infer_schemas,
    spec_from_schemas,
)
from kafka_specification_tpu.utils.tla_emit import (
    SBitset,
    SFun,
    SInt,
    SRec,
    build_model as emit,
    load_defs,
)
from kafka_specification_tpu.utils.tla_frontend import parse_tla


@needs_reference
def test_id_sequence_schema_inferred_from_typeok():
    """nextId \\in IdSet \\union {MaxId+1} (IdSequence.tla:28,43) infers
    the exact scalar bounds the hand mapping used."""
    defs = load_defs(ref_path(), "IdSequence")
    sch = infer_schemas(defs, {"MaxId": 5}, ["nextId"])
    assert sch == {"nextId": SInt("nextId", 0, 6)}


@needs_reference
def test_frl_schema_inferred_from_typeok():
    """FiniteReplicatedLog's \\A replica quantified record type
    (FiniteReplicatedLog.tla:90-95) infers the full nested schema:
    per-replica record of endOffset scalar + records function."""
    defs = load_defs(ref_path(), "FiniteReplicatedLog")
    consts = {"Replicas": (0, 2), "LogRecords": (0, 1), "Nil": -1, "LogSize": 4}
    sch = infer_schemas(defs, consts, ["logs"])
    logs = sch["logs"]
    assert isinstance(logs, SFun) and logs.size == 3
    rec = logs.elem
    assert isinstance(rec, SRec)
    assert rec.fields["endOffset"] == SInt("logs_endOffset", 0, 4)
    inner = rec.fields["records"]
    assert isinstance(inner, SFun) and inner.size == 4
    assert inner.elem == SInt("logs_records", -1, 1)
    spec = spec_from_schemas(sch)
    assert [(f.name, f.shape) for f in spec.fields] == [
        ("logs_endOffset", (3,)),
        ("logs_records", (3, 4)),
    ]


@needs_reference
@pytest.mark.slow
def test_inferred_emitted_models_reach_golden_counts():
    """The inferred schemas drive the emitted models to the exact golden
    state counts (the same counts as hand models / oracle / TLC)."""
    ref = ref_path()
    mod = parse_tla(ref / "IdSequence.tla")
    defs = load_defs(ref, "IdSequence")
    sch = infer_schemas(defs, {"MaxId": 5}, mod.variables)
    m = emit(mod, {"MaxId": 5}, sch, spec_from_schemas(sch), name="ids-inf")
    r = check(m, min_bucket=32)
    assert r.total == 7 and r.diameter == 6

    mod = parse_tla(ref / "FiniteReplicatedLog.tla")
    defs = load_defs(ref, "FiniteReplicatedLog")
    consts = {"Replicas": (0, 2), "LogRecords": (0, 1), "Nil": -1, "LogSize": 4}
    sch = infer_schemas(defs, consts, mod.variables)
    m = emit(mod, consts, sch, spec_from_schemas(sch), name="frl-inf")
    r = check(m, min_bucket=64)
    assert r.total == 29791  # 31^3


@needs_reference
def test_unsupported_shapes_fail_loudly():
    """L3's message-set state (SUBSET of a record set) is a representation
    choice, not an inferable bound — the inferencer must refuse it (the
    curated schema in models/emitted is the documented override hook)."""
    defs = load_defs(ref_path(), "KafkaReplication")
    consts = {
        "Replicas": (0, 2),
        "LogSize": 2,
        "MaxRecords": 2,
        "MaxLeaderEpoch": 2,
        "None": -1,
    }
    with pytest.raises(SchemaInferenceError):
        infer_schemas(
            defs,
            consts,
            [
                "replicaLog",
                "replicaState",
                "nextRecordId",
                "nextLeaderEpoch",
                "leaderAndIsrRequests",
                "quorumState",
            ],
        )
