"""Cells, configurations, mixes and metrics are found by name: a new cell
is new files only."""
import hashlib
import json
import shutil

from harness import spec, traffic
from conftest import BENCH, ROOT, make_root


def digest(root):
    h = hashlib.sha256()
    for p in sorted((root / "bench").rglob("*")):
        if p.is_file() and "__pycache__" not in p.parts:
            h.update(str(p.relative_to(root)).encode() + p.read_bytes())
    return h.hexdigest()


def test_every_cell_resolves():
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    for w in bench["workloads"]:
        cell = spec.load_cell(w["name"])
        assert cell.config["name"] == w["config"]
        assert any(m["name"] == "setup_s" for m in cell.end_to_end)
        assert len(cell.end_to_end) >= 2 and cell.per_layer
        for m in cell.per_layer:
            assert m["moves"] in {e["name"] for e in cell.end_to_end}


def test_metric_readers_agree_with_benchmark_json():
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    for m in bench["per_layer"]:
        mod = spec.load_metric(m["name"])
        assert (mod.LAYER, mod.UNIT, mod.MOVES) == (m["layer"], m["unit"],
                                                    m["moves"])
    assert spec.peaks("TPU v5 lite")["hbm_bytes_per_s"] == 819e9


def test_unknown_device_kind_is_an_error():
    import pytest
    with pytest.raises(KeyError):
        spec.peaks("cpu")


def test_adding_a_traffic_file_adds_a_cell(tmp_path):
    root = make_root(tmp_path)
    before = digest(root)
    mix = json.loads((BENCH / "traffic/decode-8k.json").read_text())
    mix["clients"] = 4
    (root / "bench/traffic/decode-8k-small.json").write_text(json.dumps(mix))
    bench = json.loads((root / "BENCHMARK.json").read_text())
    bench["workloads"].append({"name": "smollm2-decode-8k-small",
                               "config": "smollm2-135m",
                               "traffic": "decode-8k-small", "chips": 1,
                               "why": "added by data alone"})
    (root / "BENCHMARK.json").write_text(json.dumps(bench))
    cell = spec.load_cell("smollm2-decode-8k-small", root=root)
    assert cell.traffic["clients"] == 4
    m = traffic.Mix(cell.traffic, 5, cell.config["vocab_size"])
    assert m.closed and len(m.next_item().prompt) >= 2048
    # nothing that was there changed: the new mix is the only new file
    (root / "bench/traffic/decode-8k-small.json").unlink()
    assert digest(root) == before
