"""A cell cut to a size the CPU's Pallas interpreter runs in seconds."""
import json

from bench import run as bench_run, spec


def tiny_cell(name: str, root=spec.ROOT) -> spec.Cell:
    cell = spec.cell(name, root=root)
    cell.mix.update(tenants_per_core=2, warm_max_rows=4, warm_active=[1, 2],
                    warmup_s=1.0,
                    audit_tenants_per_core=1)
    if "rate_per_s" in cell.mix:
        cell.mix["rate_per_s"] = 20
    if "draw_words" in cell.mix:
        cell.mix.update(draw_words=256, outstanding=1, warmup_s=0.5)
    return cell


def run_tiny(cell, seed: int, seconds: float = 2.0, control=None):
    devs, _ = bench_run.device_info(cell.chips, require_tpu=False)
    peaks = json.loads((spec.BENCH / "peaks.json").read_text())["TPU v5 lite"]
    return bench_run.run(cell, seed, seconds, False, devs, peaks,
                         control=control)
