"""Cells, configurations, traffic mixes and metrics are found by name:
adding one adds files and edits none that is there."""
import hashlib
import json
import shutil

import pytest

import catalog

BENCH = catalog.BENCH


def snapshot(root):
    return {p.relative_to(root): hashlib.sha256(p.read_bytes()).hexdigest()
            for p in root.rglob("*") if p.is_file()}


@pytest.fixture
def copy_root(tmp_path):
    shutil.copy(catalog.ROOT / "BENCHMARK.json", tmp_path / "BENCHMARK.json")
    shutil.copytree(BENCH, tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    return tmp_path


def add_cell(root):
    """A new configuration, traffic mix, cell and metric, as files; the
    one line that names them goes into a rewritten BENCHMARK.json."""
    cfg = json.loads((root / "bench/configs/rwkv6-1.6b-3l.json").read_text())
    cfg["name"] = "rwkv6-1.6b-2l"
    cfg["num_hidden_layers"] = 2
    (root / "bench/configs/rwkv6-1.6b-2l.json").write_text(json.dumps(cfg))
    mix = json.loads((root / "bench/traffic/iot-s64.json").read_text())
    mix["name"] = "iot-s32"
    mix["seq"] = 32
    (root / "bench/traffic/iot-s32.json").write_text(json.dumps(mix))
    (root / "bench/workloads/rwkv6-1.6b-2l.iot-s32.json").write_text(
        json.dumps({"name": "rwkv6-1.6b-2l.iot-s32", "loss_chunk": 32,
                    "limits": {"loss_gap": 1}}))
    (root / "bench/metrics/tokens_seen.py").write_text(
        "def read(ctx):\n    return float(ctx['rounds'])\n")
    bench = json.loads((root / "BENCHMARK.json").read_text())
    bench["configs"].append({"name": "rwkv6-1.6b-2l", "source": "x",
                             "file": "bench/configs/rwkv6-1.6b-2l.json",
                             "reduced": ["num_hidden_layers"], "why": "x"})
    bench["workloads"].append({"name": "rwkv6-1.6b-2l.iot-s32",
                               "config": "rwkv6-1.6b-2l",
                               "traffic": "iot-s32", "chips": 1, "why": "x"})
    bench["per_layer"].append({"name": "tokens_seen", "unit": "rounds",
                               "better": "higher", "source": "host_clock",
                               "layer": "x", "moves": "train_tokens_per_s",
                               "workloads": ["rwkv6-1.6b-2l.iot-s32"]})
    (root / "BENCHMARK.json").write_text(json.dumps(bench))


def test_new_cell_config_traffic_and_metric_are_found(copy_root):
    before = snapshot(copy_root / "bench")
    add_cell(copy_root)
    after = snapshot(copy_root / "bench")
    assert all(after[p] == h for p, h in before.items())
    cell = catalog.cell("rwkv6-1.6b-2l.iot-s32", root=copy_root)
    assert cell.config["num_hidden_layers"] == 2
    assert cell.traffic["seq"] == 32
    assert [m["name"] for m in cell.per_layer] == ["tokens_seen"]
    assert catalog.metric_reader("tokens_seen", root=copy_root)(
        {"rounds": 7}) == 7.0


def test_every_cell_of_the_benchmark_resolves():
    bench = catalog.benchmark()
    for w in bench["workloads"]:
        cell = catalog.cell(w["name"])
        assert set(cell.workload["limits"]) >= {
            "loss_gap", "gnorm_gap", "update_gap", "change_gap", "eps_gap"}
        assert cell.per_layer, w["name"]
        for m in cell.per_layer:
            assert callable(catalog.metric_reader(m["name"]))
    with pytest.raises(KeyError):
        catalog.cell("no-such.cell")


def test_config_files_agree_with_what_the_program_runs():
    """The published-style keys of each configuration file and the
    program's ``ArchConfig`` overrides state the same sizes."""
    for c in catalog.benchmark()["configs"]:
        cfg = catalog.load_json(catalog.ROOT / c["file"])
        prog = cfg["program"]
        assert prog["n_layers"] == cfg["num_hidden_layers"]
        assert prog["tie_head"] == cfg["tie_word_embeddings"]
        assert set(c["reduced"]) == set(cfg["reduced"])
        for key in ("source", "assumed", "deployment", "reduced"):
            assert cfg[key], key
        if cfg["family"] == "mistral":
            assert prog["n_heads"] == cfg["num_attention_heads"]
            assert prog["n_kv_heads"] == cfg["num_key_value_heads"]
            assert prog["vocab"] == cfg["vocab_size"]
            assert prog["d_ff"] * cfg["tensor_parallel"] == cfg[
                "intermediate_size"]
