"""The harness finds a configuration, a mix, a metric and a cell from
files and entries alone, and the manifest in the repository resolves."""

import json
from pathlib import Path

import pytest

from harness import manifest, model

BENCH = Path(__file__).resolve().parent


def test_every_cell_of_the_manifest_resolves():
    bench = manifest.load(BENCH.parent)
    for w in bench["workloads"]:
        cell = manifest.cell(w["name"], BENCH.parent, bench)
        assert any(m["name"] == "setup_s" for m in cell.end_to_end)
        assert len(cell.end_to_end) >= 2 and cell.per_layer
        assert cell.limits["logit_gap"] > 0
        for m in cell.end_to_end + cell.per_layer:
            assert callable(manifest.reader(m["name"]))
        assert manifest.reference(cell.config["reference"]).logits
        assert cell.config["name"] == w["config"]
        # every size not in ``reduced`` is the registry's published one
        model.program_config(cell.config)


def test_a_new_config_mix_metric_and_cell_from_files_alone(tmp_path):
    root = tmp_path
    (root / "bench" / "configs").mkdir(parents=True)
    (root / "bench" / "traffic").mkdir()
    (root / "bench" / "cells").mkdir()
    (root / "bench" / "metrics").mkdir()
    (root / "bench" / "configs" / "toy.json").write_text(
        json.dumps({"name": "toy", "reference": "decoder", "hidden_size": 8}))
    (root / "bench" / "traffic" / "trickle.json").write_text(
        json.dumps({"generator": "poisson", "rate_per_s": 0.1}))
    (root / "bench" / "cells" / "toy-trickle.json").write_text(
        json.dumps({"limits": {"logit_gap": 0.5}}))
    (root / "bench" / "metrics" / "queue_len.py").write_text(
        "def read(ctx):\n    return 7.0\n")
    (root / "BENCHMARK.json").write_text(json.dumps({
        "configs": [{"name": "toy", "file": "bench/configs/toy.json"}],
        "workloads": [{"name": "toy-trickle", "config": "toy",
                       "traffic": "trickle", "chips": 1}],
        "end_to_end": [
            {"name": "setup_s", "unit": "s"},
            {"name": "tokens_per_s", "unit": "tokens/s",
             "workloads": ["toy-trickle"]},
            {"name": "ttft_p90_s", "unit": "s", "workloads": ["other"]}],
        "per_layer": [
            {"name": "queue_len.trickle", "unit": "1", "moves": "tokens_per_s",
             "workloads": ["toy-trickle"]},
            {"name": "mfu.other", "unit": "%", "moves": "ttft_p90_s"}],
    }))
    cell = manifest.cell("toy-trickle", root)
    assert cell.config["hidden_size"] == 8
    assert cell.traffic["rate_per_s"] == 0.1
    assert cell.limits == {"logit_gap": 0.5}
    assert [m["name"] for m in cell.end_to_end] == ["setup_s", "tokens_per_s"]
    assert [m["name"] for m in cell.per_layer] == ["queue_len.trickle"]
    # a metric's reader: its own file, or the file of its base name
    assert manifest.reader("queue_len.trickle", root / "bench")(None) == 7.0
    assert manifest.reader("mfu.anything") is manifest.reader("mfu.decode")
    with pytest.raises(FileNotFoundError):
        manifest.reader("no_such_metric.x", root / "bench")
    with pytest.raises(KeyError):
        manifest.cell("absent", root)


def test_manifest_keys_and_names_keep_to_the_contract():
    bench = manifest.load(BENCH.parent)
    assert set(bench) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    import re

    name = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
    for group in ("configs", "workloads", "end_to_end", "per_layer"):
        names = [e["name"] for e in bench[group]]
        assert len(set(names)) == len(names)
        assert all(name.match(n) for n in names)
    for e in bench["end_to_end"] + bench["per_layer"]:
        assert re.match(r"^[A-Za-z0-9_/%.\-]{1,16}$", e["unit"])
        assert e["better"] in ("lower", "higher")
    e2e = {e["name"]: e for e in bench["end_to_end"]}
    assert e2e["setup_s"]["bound"] <= 0.25
    for m in bench["per_layer"]:
        assert m["moves"] in e2e
        for w in m["workloads"]:
            assert w in e2e[m["moves"]].get("workloads", [w])


def test_without_a_chip_a_run_exits_non_zero_and_prints_no_result(capsys):
    import run

    assert run.main(["--workload", "nemotron8-chat-decode", "--seed",
                     str(2**40), "--seconds", "1"]) != 0
    out = capsys.readouterr()
    assert "{" not in out.out
    assert "not a TPU" in out.err
