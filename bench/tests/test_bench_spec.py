"""BENCHMARK.json keeps to its limits, and every cell's files resolve."""
import json
import shutil

import pytest

from bench import spec

B = spec.load_benchmark()
CELLS = [w["name"] for w in B["workloads"]]
TOP = {"command", "paths", "run_seconds", "configs", "workloads",
       "end_to_end", "per_layer"}


def test_names_units_and_keys():
    assert set(B) == TOP
    assert 1 <= B["run_seconds"] <= 51 and isinstance(B["run_seconds"], int)
    names = []
    for c in B["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert c["file"].startswith(tuple(p + "/" for p in B["paths"]))
        names += [c["name"], *c["reduced"]]
    for w in B["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert w["chips"] in (1, 4) and len(w["why"]) <= 200
        names += [w["name"], w["config"], w["traffic"]]
    e2e = {m["name"] for m in B["end_to_end"]}
    assert "setup_s" in e2e
    for m in B["end_to_end"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "bound",
                                          "source"}
        assert 0.01 <= m["bound"] <= 0.25
        assert m["source"] in ("host_clock", "device_trace")
    for m in B["per_layer"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "source",
                                          "layer", "moves"}
        assert m["moves"] in e2e and "\n" not in m["layer"]
        assert m["source"] in ("device_trace", "program_span",
                               "program_counter", "host_clock")
    for m in B["end_to_end"] + B["per_layer"]:
        names.append(m["name"])
        assert spec.UNIT.match(m["unit"]), m["unit"]
        assert m["better"] in ("lower", "higher")
        if m["name"].endswith("_roofline") or "mfu" in m["name"]:
            assert m["unit"] == "%"
    for n in names:
        assert spec.NAME.match(n), n
    groups = [B["configs"], B["workloads"], B["end_to_end"] + B["per_layer"]]
    for g in groups:
        assert len({x["name"] for x in g}) == len(g)
    assert len(json.dumps(B)) < 64 * 1024


@pytest.mark.parametrize("name", CELLS)
def test_cell_files_resolve(name):
    cell = spec.cell(name)
    assert callable(cell.generator.make) and callable(cell.generator.drive)
    for core in cell.config["cores"]:
        assert (spec.ROOT / core["weights"]).exists()
        assert (spec.ROOT / core["solution"]).exists()
    e2e = {m["name"] for m in cell.end_to_end}
    assert "setup_s" in e2e and len(e2e) >= 2 and cell.per_layer
    for m in cell.per_layer:
        assert m["moves"] in e2e
        assert callable(spec.reader(m["name"]))


def test_a_new_mix_and_metric_are_only_files(tmp_path):
    """A cell with a new traffic mix and a new per-layer metric needs new
    files and new entries, and no edit of any file that is there."""
    shutil.copytree(spec.BENCH, tmp_path / "bench",
                    ignore=shutil.ignore_patterns("out", "__pycache__"))
    bench = dict(B)
    mix = json.loads((spec.BENCH / "traffic" / "zipf_open.json").read_text())
    mix["rate_per_s"] = mix["rate_per_s"] / 2
    (tmp_path / "bench" / "traffic" / "zipf_half.json").write_text(
        json.dumps(mix))
    (tmp_path / "bench" / "metrics" / "draws_seen.half.py").write_text(
        "def read(obs):\n    return float(obs['draws'])\n")
    bench["workloads"] = B["workloads"] + [
        {"name": "farm5.zipf_half", "config": "farm5", "traffic": "zipf_half",
         "chips": 1, "why": "half the rate"}]
    bench["per_layer"] = B["per_layer"] + [
        {"name": "draws_seen.half", "unit": "draws", "better": "higher",
         "source": "host_clock", "layer": "load generator",
         "moves": "draw_p50_ms", "workloads": ["farm5.zipf_half"]}]
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(bench))
    cell = spec.cell("farm5.zipf_half", root=tmp_path)
    assert cell.mix["rate_per_s"] == mix["rate_per_s"]
    assert [m["name"] for m in cell.per_layer] == ["draws_seen.half"]
    assert spec.reader("draws_seen.half", root=tmp_path)({"draws": 3}) == 3.0
