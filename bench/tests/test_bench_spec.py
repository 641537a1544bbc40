"""BENCHMARK.json keeps to its contract, and every cell's configuration,
traffic mix, cell file and metric reader resolves by name."""
import json
import re

import pytest

import run

ROOT = run.ROOT
SPEC = json.loads((ROOT / "BENCHMARK.json").read_text())
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
CELLS = [w["name"] for w in SPEC["workloads"]]


def test_top_level_keys_and_command():
    assert set(SPEC) == {"command", "paths", "run_seconds", "configs",
                         "workloads", "end_to_end", "per_layer"}
    assert SPEC["command"] == ["python3", "bench/run.py"]
    assert SPEC["paths"] == ["bench"]
    assert 1 <= SPEC["run_seconds"] <= 51
    assert len(json.dumps(SPEC)) < 64 * 1024


def test_entries_have_just_their_keys_and_valid_names():
    for c in SPEC["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert NAME.match(c["name"]) and all(NAME.match(k)
                                             for k in c["reduced"])
    for w in SPEC["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert w["chips"] in (1, 4) and len(w["why"]) <= 200
        for key in ("name", "config", "traffic"):
            assert NAME.match(w[key])
    metrics = SPEC["end_to_end"] + SPEC["per_layer"]
    for m in SPEC["end_to_end"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "bound",
                                          "source"}
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.25
    for m in SPEC["per_layer"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "source",
                                          "layer", "moves"}
        assert m["source"] in ("device_trace", "program_span",
                               "program_counter", "host_clock")
    for m in metrics:
        assert NAME.match(m["name"]) and UNIT.match(m["unit"])
        assert m["better"] in ("lower", "higher")
        assert set(m.get("workloads", CELLS)) <= set(CELLS)
    names = [m["name"] for m in metrics]
    assert len(names) == len(set(names))
    assert len(CELLS) == len(set(CELLS))


@pytest.mark.parametrize("name", CELLS)
def test_cell_files_resolve(name):
    cell = run.Cell(SPEC, name)
    cfg = cell.cfg
    for key in ("hidden_size", "num_hidden_layers", "page_tokens",
                "kv_pool_bytes", "max_batch_seqs", "prefill_chunk_tokens",
                "max_len", "source", "reduced", "assumed", "program_arch"):
        assert key in cfg
    assert cell.mix["arrival"] in ("poisson", "backlog")
    if cell.mix["arrival"] != "backlog":
        assert cell.params["rate_rps"] > 0
    assert cell.params["gap_limit"] > 0
    assert any(m["name"] == "setup_s" for m in cell.end_to_end)
    assert len(cell.end_to_end) >= 2 and cell.per_layer
    for m in cell.end_to_end + cell.per_layer:
        if m["name"] != "setup_s":
            assert callable(run.load_reader(m["name"]))
    for m in cell.per_layer:
        assert m["moves"] in {e["name"] for e in cell.end_to_end}


def test_every_config_is_used_and_its_file_is_its_own():
    used = {w["config"] for w in SPEC["workloads"]}
    assert used == {c["name"] for c in SPEC["configs"]}
    files = [c["file"] for c in SPEC["configs"]]
    assert len(files) == len(set(files))
    for c in SPEC["configs"]:
        assert c["file"].startswith("bench/")
        cfg = json.loads((ROOT / c["file"]).read_text())
        assert cfg["source"] == c["source"] and cfg["reduced"] == c["reduced"]


def test_layers_named_alike():
    by_layer = {}
    for m in SPEC["per_layer"]:
        by_layer.setdefault(m["name"].split(".")[0], set()).add(m["layer"])
    assert all(len(v) == 1 for v in by_layer.values())


def test_unknown_device_kind_is_an_error():
    assert run.load_peaks("TPU v5 lite")["bf16_flops_per_s"] == 197e12
    with pytest.raises(KeyError):
        run.load_peaks("TPU v9 imaginary")
