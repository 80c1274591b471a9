"""A configuration is its file: a new one, a new core kind and a mesh
need new files and entries, and no edit of any file that is there."""
import json
import os
import shutil
import subprocess
import sys

import pytest
from tiny import run_tiny, tiny_cell

from bench import run as bench_run, serve, spec

B = spec.load_benchmark()
FARM5 = spec.read_json(spec.BENCH / "configs" / "farm5.json")


def _bench_with(tmp_path, config: dict, chips: int = 1) -> str:
    """A copy of the benchmark with ``config`` added as a file and one
    bulk cell on it; returns the cell's name."""
    shutil.copytree(spec.BENCH, tmp_path / "bench",
                    ignore=shutil.ignore_patterns("out", "__pycache__"))
    name = config["name"]
    (tmp_path / "bench" / "configs" / f"{name}.json").write_text(
        json.dumps(config))
    bench = dict(B)
    bench["configs"] = B["configs"] + [
        {"name": name, "source": FARM5["source"],
         "file": f"bench/configs/{name}.json", "reduced": [], "why": "test"}]
    bench["workloads"] = B["workloads"] + [
        {"name": f"{name}.bulk", "config": name, "traffic": "bulk",
         "chips": chips, "why": "test"}]
    bench["end_to_end"] = [dict(m, workloads=m["workloads"] + [f"{name}.bulk"])
                           if "workloads" in m else m
                           for m in B["end_to_end"]]
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(bench))
    return f"{name}.bulk"


def _pair(**kw) -> dict:
    """Two of farm5's cores, the second of a kind added as a file.  Both
    are 3-8-3, so the planner stacks them: a core launched alone on a
    mesh compiles anew at every flush (PERF.md, Open questions)."""
    cores = [dict(c) for c in FARM5["cores"] if c["name"] in ("chen", "chua")]
    cores[1]["kind"] = "ann_copy"
    return dict(FARM5, **dict(dict(name="pair", cores=cores), **kw))


def test_a_new_configuration_and_core_kind_are_only_files(tmp_path):
    name = _bench_with(tmp_path, _pair(mesh={"axis": "data"}))
    shutil.copy(spec.BENCH / "cores" / "ann.py",
                tmp_path / "bench" / "cores" / "ann_copy.py")
    cell = tiny_cell(name, root=tmp_path)
    assert [m.__name__ for m in cell.kinds.values()] == [
        "bench_core_ann", "bench_core_ann_copy"]
    devs, _ = bench_run.device_info(1, require_tpu=False)
    farm = serve.build_farm(cell, profile=False, devs=devs)
    assert list(farm.services) == ["chen", "chua"]
    for svc in farm.services.values():
        assert svc.mesh is not None and svc.mesh_axis == "data"
        assert list(svc.mesh.devices.reshape(-1)) == devs
    result, checks, numbers = run_tiny(cell, 2 ** 31 + 91, 4.0)
    assert result["correct"], checks
    assert numbers["launches_audited"] > 0


@pytest.mark.parametrize("config,chips,why", [
    (dict(chips=4), 1, "runs on 4 chips"),
    (dict(chips=4), 4, "with no mesh"),
])
def test_a_configuration_unlike_its_cell_is_refused(tmp_path, config, chips,
                                                    why):
    name = _bench_with(tmp_path, dict(FARM5, name="wide", **config), chips)
    with pytest.raises(spec.Refused, match=why):
        spec.cell(name, root=tmp_path)


MESH4 = """
import pathlib
import sys
sys.path[:0] = [{root!r}, {root!r} + "/src", {root!r} + "/bench/tests"]
from tiny import run_tiny, tiny_cell
cell = tiny_cell("pair4.bulk", root=pathlib.Path({tmp!r}))
result, checks, _ = run_tiny(cell, 2 ** 31 + 97, 4.0)
assert result["correct"], checks
assert result["device"]["count"] == 4, result["device"]
print("ok")
"""


def test_a_mesh_configuration_runs_on_the_cells_four_devices(tmp_path):
    """Four forced host devices stand for four chips: the pools shard
    over them and the audit holds."""
    _bench_with(tmp_path, _pair(name="pair4", chips=4,
                                mesh={"axis": "data"}), chips=4)
    shutil.copy(spec.BENCH / "cores" / "ann.py",
                tmp_path / "bench" / "cores" / "ann_copy.py")
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    p = subprocess.run(
        [sys.executable, "-c", MESH4.format(root=str(spec.ROOT),
                                            tmp=str(tmp_path))],
        env=env, capture_output=True, text=True, timeout=600)
    assert p.returncode == 0 and p.stdout.strip().endswith("ok"), (
        p.stdout[-2000:] + p.stderr[-4000:])
