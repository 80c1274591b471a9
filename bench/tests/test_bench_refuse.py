"""The benchmark refuses what is not a chip it knows."""
import json
import os
import shutil
import subprocess
import sys
import types

import pytest

from bench import run as bench_run, spec


def test_a_cpu_is_refused(capsys):
    rc = bench_run.main(["--workload", "farm5.bulk", "--seed", "1",
                         "--seconds", "1", "--trace", "0"])
    out = capsys.readouterr()
    assert rc == 1 and out.out == "" and "no TPU" in out.err


def _fake_devices(monkeypatch, kind, n):
    import jax
    devs = [types.SimpleNamespace(platform="tpu", device_kind=kind, id=i)
            for i in range(n)]
    monkeypatch.setattr(jax, "devices", lambda *a: devs)


def test_an_unknown_device_kind_is_refused(monkeypatch):
    _fake_devices(monkeypatch, "TPU v99", 1)
    with pytest.raises(bench_run.Refused, match="no peaks"):
        bench_run.device_info(1, require_tpu=True)


def test_too_few_chips_are_refused(monkeypatch):
    _fake_devices(monkeypatch, "TPU v5 lite", 1)
    with pytest.raises(bench_run.Refused, match="asks for 4"):
        bench_run.device_info(4, require_tpu=True)
    devs, peaks = bench_run.device_info(1, require_tpu=True)
    assert peaks["flops_per_s"] == 197e12


def test_the_benchmark_alone_prints_no_result(tmp_path):
    """A directory that holds only BENCHMARK.json and the benchmark."""
    shutil.copy(spec.ROOT / "BENCHMARK.json", tmp_path)
    shutil.copytree(spec.BENCH, tmp_path / "bench",
                    ignore=shutil.ignore_patterns("out", "__pycache__"))
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run([sys.executable, "bench/run.py", "--workload",
                        "farm5.bulk", "--seed", "3", "--seconds", "1",
                        "--trace", "0"], cwd=tmp_path, env=env,
                       capture_output=True, text=True, timeout=300)
    assert p.returncode != 0 and p.stdout == ""


def test_the_compile_watch_names_what_the_window_requests():
    import jax
    import jax.numpy as jnp

    from bench import serve
    watch = serve.CompileWatch()

    @jax.jit
    def outside_window(x):
        return x + 1

    @jax.jit
    def inside_window(x):
        return x * 3

    outside_window(jnp.ones(7))
    watch.on = True
    inside_window(jnp.ones(7))
    watch.on = False
    assert watch.window["requests"] == 1
    assert list(watch.window_programs) == ["jit(inside_window)"]
    assert watch.total["requests"] >= 2
