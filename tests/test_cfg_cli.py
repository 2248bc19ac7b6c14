"""TLC .cfg parsing, module registry, CLI, and checkpoint/resume."""

import numpy as np
import pytest
from conftest import needs_reference

from kafka_specification_tpu.utils.cfg import (
    CFG_MODULE_ALIASES,
    build_model,
    parse_cfg,
)
from kafka_specification_tpu.utils.cli import main as cli_main
from kafka_specification_tpu.engine.bfs import check


def test_parse_cfg_full_syntax(tmp_path):
    text = """
\\* comment line
SPECIFICATION Spec
CONSTANTS
    Replicas = {b1, b2, b3}
    LogSize = 2   \\* trailing comment
    MaxRecords = 2
    MaxLeaderEpoch = 2
(* block
   comment *)
INVARIANTS TypeOk WeakIsr
INVARIANT StrongIsr
CONSTRAINT Bounded
CHECK_DEADLOCK FALSE
"""
    cfg = parse_cfg(text)
    assert cfg.constants["Replicas"] == ["b1", "b2", "b3"]
    assert cfg.constants["LogSize"] == 2
    assert cfg.invariants == ["TypeOk", "WeakIsr", "StrongIsr"]
    assert cfg.constraints == ["Bounded"]
    assert cfg.specification == "Spec"
    assert cfg.check_deadlock is False


@pytest.mark.parametrize("path", [
    "configs/NoSuchJob.cfg", "Kip320FiveBrokerThreePartitions.cfg"])
def test_parse_cfg_names_a_cfg_path_that_is_no_file(path):
    """One line ending in `.cfg` is a path a user mistyped, not a cfg's
    text: the error names it (it was `KeyError: 'Replicas'` from
    `build_model`, one call later)."""
    with pytest.raises(FileNotFoundError, match=path.replace(".", r"\.")):
        parse_cfg(path)


def test_parse_cfg_still_takes_one_line_of_text():
    assert parse_cfg("INVARIANTS TypeOk").invariants == ["TypeOk"]
    assert parse_cfg("INVARIANTS TypeOk\n").invariants == ["TypeOk"]


def test_build_model_registry_covers_all_modules():
    import pathlib

    for cfg_file in pathlib.Path("configs").glob("*.cfg"):
        module = CFG_MODULE_ALIASES.get(cfg_file.stem, cfg_file.stem)
        cfg = parse_cfg(cfg_file)
        model = build_model(module, cfg)
        oracle = build_model(module, cfg, oracle=True)
        assert model.actions and oracle.actions
        # invariant names listed in the .cfg drive the model's predicates
        if cfg.invariants:
            assert [i.name for i in model.invariants] == cfg.invariants


def test_cli_check_and_exit_codes(tmp_path, capsys):
    # IdSequence exhaustive pass -> exit 0
    rc = cli_main(["check", "configs/IdSequence.cfg", "--json"])
    out = capsys.readouterr().out
    assert rc == 0
    assert '"distinct_states": 12' in out


def test_cli_check_of_the_five_broker_kip279_cfg_under_its_wrapper_module(
        capsys):
    """`configs/MCKip279FiveBroker.cfg` is no module's stem: the alias names
    the wrapper module for whoever walks `configs/` (`cli analyze`, the
    registry test above), `cli check` takes it as the header's line gives
    it, and the stanza counts orbits (1, 2, 7, 36 for the unreduced 1, 10,
    110, 1,220)."""
    import json

    assert CFG_MODULE_ALIASES["MCKip279FiveBroker"] == "MCKip279"
    with open("configs/MCKip279FiveBroker.cfg") as fh:
        assert ("check configs/MCKip279FiveBroker.cfg \\\n"
                "\\*       --module MCKip279\n") in fh.read()
    rc = cli_main(["check", "configs/MCKip279FiveBroker.cfg", "--module",
                   "MCKip279", "--hand", "--max-depth", "3", "--json"])
    out = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert rc == 0 and out["levels"] == [1, 2, 7, 36]
    assert out["distinct_states"] == 46


@needs_reference
def test_cli_simulate_emitted(capsys):
    # random walks over the mechanically emitted IdSequence model; TypeOk
    # holds on every walk -> exit 0
    rc = cli_main(
        ["simulate", "configs/IdSequence.cfg", "--emitted", "--walks", "4",
         "--depth", "6", "--seed", "3"]
    )
    out = capsys.readouterr().out
    assert rc == 0
    assert "no violations" in out


def test_checkpoint_resume(tmp_path):
    from kafka_specification_tpu.models import finite_replicated_log as frl

    ckdir = str(tmp_path / "ck")
    model = frl.make_model(2, 2, 2)
    # run 3 levels, "crash", resume to completion
    partial = check(model, max_depth=3, min_bucket=32, checkpoint_dir=ckdir)
    assert partial.total < 49
    resumed = check(model, min_bucket=32, checkpoint_dir=ckdir)
    assert resumed.total == 49  # 7^2, same as the uncheckpointed golden run
    assert resumed.ok


@pytest.mark.slow  # round-5 fast-suite budget (<=300s): cheaper siblings keep the
# fast-path coverage; this full variant runs in the slow set
def test_stretch_config_builds_product_model():
    """The 5-broker/3-partition stretch workload is expressible via the
    authored Partitions constant and explores correctly under a bound."""
    cfg = parse_cfg("configs/Kip320Stretch.cfg")
    model = build_model("Kip320", cfg)
    assert model.meta["partitions"] == 3
    assert model.spec.num_lanes >= 3 * 9 // 2  # 3 partitions of 5-broker state
    res = check(model, max_states=700, max_depth=2, store_trace=False, min_bucket=64)
    assert res.levels[:3] == [1, 30, 570]  # 3 partitions x 10 controller moves, etc.


@needs_reference
def test_validate_emitted_covers_reference_next():
    """`validate --emitted`: the mechanically emitted model's `Name~k` DNF
    branches map back to their source disjuncts and cover the reference
    Next exactly (VERDICT r2 item 7 — the two halves of the fidelity story
    compose).  One module here (emission is ~20s/module); all six L4
    configs are exercised by the CLI run recorded in RESULTS.md."""
    rc = cli_main(["validate", "configs/Kip320.cfg", "--emitted"])
    assert rc == 0
