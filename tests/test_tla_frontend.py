"""Structural front-end: parse the reference corpus and mechanically verify
the hand-translated models' action inventories against each module's Next."""

import pytest
from conftest import REFERENCE as REF, needs_reference

from kafka_specification_tpu.models import async_isr, finite_replicated_log, kip320, variants
from kafka_specification_tpu.models.kafka_replication import Config
from kafka_specification_tpu.utils import tla_frontend as tf


TINY = Config(2, 2, 1, 1)


def test_parse_minimal_module():
    mod = tf.parse_tla(
        """
---- MODULE Demo ----
EXTENDS Integers, FiniteSets
CONSTANTS A, B
VARIABLES x, y
Foo == x + 1
Bar(z) == z \\* trailing
Seq == INSTANCE IdSequence WITH MaxId <- A, nextId <- x
Next ==
    \\/ Foo
    \\/ Bar
====
"""
    )
    assert mod.name == "Demo"
    assert mod.extends == ["Integers", "FiniteSets"]
    assert mod.constants == ["A", "B"]
    assert mod.variables == ["x", "y"]
    assert "Foo" in mod.definitions and "Bar" in mod.definitions
    assert mod.instances["Seq"] == ("IdSequence", {"MaxId": "A", "nextId": "x"})
    assert tf.next_disjuncts(mod) == ["Foo", "Bar"]


@needs_reference
def test_reference_chain_structure():
    chain = tf.load_chain(REF, "Kip320")
    assert set(chain) >= {"Kip320", "Kip279", "KafkaReplication", "Util"}
    kr = chain["KafkaReplication"]
    assert set(kr.variables) == {
        "replicaLog",
        "replicaState",
        "nextRecordId",
        "nextLeaderEpoch",
        "leaderAndIsrRequests",
        "quorumState",
    }
    assert set(kr.instances) == {"LeaderEpochSeq", "RecordSeq", "ReplicaLog"}


@needs_reference
@pytest.mark.parametrize(
    "module,model",
    [
        ("KafkaTruncateToHighWatermark", variants.make_model("KafkaTruncateToHighWatermark", TINY)),
        ("Kip101", variants.make_model("Kip101", TINY)),
        ("Kip279", variants.make_model("Kip279", TINY)),
        ("Kip320", kip320.make_model(TINY)),
        ("Kip320FirstTry", kip320.make_first_try_model(TINY)),
        ("AsyncIsr", async_isr.make_model(async_isr.AsyncIsrConfig(2, 1, 1))),
    ],
    ids=lambda m: m if isinstance(m, str) else "",
)
def test_model_actions_match_reference_next(module, model):
    problems = tf.validate_model(model, REF, module)
    assert not problems, problems


@needs_reference
def test_frl_standalone_next_actions():
    """FiniteReplicatedLog's Next nests its existentials, so disjunct names
    are the three mutators; our model matches them by construction."""
    chain = tf.load_chain(REF, "FiniteReplicatedLog")
    mod = chain["FiniteReplicatedLog"]
    assert {"Append", "TruncateTo", "ReplicateTo"} <= set(mod.definitions)
    model = finite_replicated_log.make_model(2, 2, 1)
    assert [a.name for a in model.actions] == ["Append", "TruncateTo", "ReplicateTo"]

def test_next_disjuncts_mixed_plain_and_quantified():
    mod = tf.parse_tla(
        """
---- MODULE Mixed ----
VARIABLES x
Simple == x' = x
Quantified(r) == x' = r
Next ==
    \\/ Simple
    \\/ \\E r \\in {1, 2} : Quantified(r)
====
"""
    )
    assert tf.next_disjuncts(mod) == ["Simple", "Quantified"]


@needs_reference
def test_validate_cfg_constants():
    from kafka_specification_tpu.utils.cfg import parse_cfg

    # every shipped config assigns the full constant set of its module
    import pathlib

    from kafka_specification_tpu.utils.cfg import CFG_MODULE_ALIASES

    for cfg_file in pathlib.Path("configs").glob("*.cfg"):
        module = CFG_MODULE_ALIASES.get(cfg_file.stem, cfg_file.stem)
        problems = tf.validate_cfg_constants(parse_cfg(cfg_file), REF, module)
        assert not problems, (cfg_file, problems)

    # missing + typo'd constants are reported
    bad = parse_cfg("CONSTANTS\n Replicas = {a, b}\n LogSizee = 2\n")
    problems = tf.validate_cfg_constants(bad, REF, "Kip320")
    assert any("LogSize is declared" in p for p in problems)
    assert any("LogSizee" in p and "no module" in p for p in problems)
