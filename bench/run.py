#!/usr/bin/env python3
"""Benchmark of the served oscillator farm: one run of one cell.

    python3 bench/run.py --workload farm5.zipf_open --seed 7 --seconds 20 --trace 0

Run from the root of a checkout, in one process that owns the chips.  The
cell (``BENCHMARK.json``) names a configuration, a traffic mix and the
metrics it reports.  The run builds the configuration's farm, registers
its tenants with seeds drawn from ``--seed``, warms up every launch shape
the traffic uses, then serves ``--seconds`` of traffic through
``AsyncOscillatorFarm.submit`` and audits a seeded sample of the launches
the window made against the plain reference of each core's kind
(``bench/cores/<kind>.py``).

``--trace 0`` reports the cell's end-to-end metrics; ``--trace 1`` builds
the farm with its stage timers on, records a profiler trace of the window
and reports the per-layer metrics.  The last line of standard output is one
JSON object; the last lines of standard error are the audit's numbers,
each beside its limit.  Exit code 1, and no result line, when JAX finds no
TPU, fewer chips than the cell asks for, or a device kind with no peaks in
``bench/peaks.json``.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import asyncio  # noqa: E402
import json  # noqa: E402
import pathlib  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402

ROOT = pathlib.Path(__file__).resolve().parents[1]
sys.path[:0] = [str(ROOT / "src"), str(ROOT)]

TRACE_DIR = ROOT / "bench" / "out" / "trace"

from bench.spec import Refused  # noqa: E402


def device_info(chips: int, require_tpu: bool):
    import jax
    devs = jax.devices()
    if require_tpu and devs[0].platform != "tpu":
        raise Refused(f"no TPU: JAX found {devs[0].platform}")
    if len(devs) < chips:
        raise Refused(f"the cell asks for {chips} chips, JAX found "
                      f"{len(devs)}")
    peaks = json.loads((ROOT / "bench" / "peaks.json").read_text())
    kind = devs[0].device_kind
    if kind not in peaks and require_tpu:
        raise Refused(f"no peaks for device kind {kind!r} in "
                      f"bench/peaks.json")
    return devs[:chips], peaks.get(kind)


def memory_peak(devs) -> int:
    peaks = []
    for d in devs:
        stats = d.memory_stats() or {}
        peaks.append(int(stats.get("peak_bytes_in_use", 0)))
    return max(peaks)


def run(cell, seed: int, seconds: float, traced: bool, devs, peaks,
        control=None):
    """One run; returns (result dict, checks, audit numbers)."""
    import numpy as np
    from bench import serve, spec, trace as tr
    watch = serve.CompileWatch()
    sess = serve.Session(cell, seed, seconds,
                         TRACE_DIR if traced else None)
    farm = serve.build_farm(cell, profile=traced, devs=devs)
    if devs[0].platform == "tpu":
        for core, svc in farm.services.items():
            if svc.backend != "pallas":
                raise Refused(f"{core} resolves to {svc.backend!r}")
    serve.say(f"farm built at {time.perf_counter() - T_START:.3f} s")
    for t, (core, client) in enumerate(sess.tenants):
        farm.register(core, client, seed=sess.tenant_seed[t])
    serve.say(f"{len(sess.tenants)} tenants registered at "
              f"{time.perf_counter() - T_START:.3f} s")
    watched = {}
    for core, client in sess.audited:
        watched.setdefault(core, set()).add(client)
    audit = serve.Audit(farm, watched, cell.mix["audit_records"], seed, sess)
    sess.warm_shapes(farm)
    serve.say(f"launch shapes warmed at {time.perf_counter() - T_START:.3f} s;"
              f" {watch.total}")
    plan = cell.generator.make(cell.mix, len(sess.cores), seed, seconds)
    if traced:
        shutil.rmtree(TRACE_DIR, ignore_errors=True)
    stats = asyncio.run(sess.serve(farm, plan, watch, audit))
    t_open = sess.t_base + sess.t0
    setup_s = t_open - T_START
    window_s = sess.t1 - sess.t0
    mem = memory_peak(devs)
    serve.say(f"{cell.name}: {len(sess.cores)} cores x "
              f"{cell.mix['tenants_per_core']} tenants x "
              f"{cell.config['lanes_per_client']} lanes; "
              f"plans {farm.plan_decisions} layouts {farm.layout_launches} "
              f"launches {farm.launches} (gang {farm.gang_launches}); "
              f"health {stats['health']}; flush errors {len(stats['errors'])}")
    serve.say(f"compiles: {watch.compiles(watch.total)} and "
              f"{watch.total['cache_loads']} cache loads in all "
              f"({watch.seconds:.3f} s); inside the window: "
              f"{watch.compiles(watch.window)} compiles, "
              f"{watch.window['cache_loads']} cache loads; programs "
              f"requested: {watch.window_programs}")

    # the window's draws; one never answered waited until the collection
    idx = sess.window_draws(plan)
    due = np.asarray(sess.due)[idx]
    done = np.asarray(sess.done)[idx]
    ok = ~np.isnan(done)
    lat_ms = (np.where(ok, done, sess.t_collected) - due) * 1e3
    all_done = np.asarray(sess.done)
    all_words = np.asarray(sess.words)
    in_win = (all_done >= sess.t0) & (all_done < sess.t1)
    words_in_window = int(all_words[in_win].sum())
    lag_ms = (np.asarray(sess.sent)[idx] - due) * 1e3
    by_core: dict = {}
    for t, n in zip(np.asarray(sess.tid)[in_win], all_words[in_win]):
        core = sess.tenants[int(t)][0]
        by_core[core] = by_core.get(core, 0) + int(n)

    # the audit, once the program's state is freed
    records = audit.records()
    lanes = int(cell.config["lanes_per_client"])
    del farm, audit
    refcores = {c["name"]: cell.kinds[c["name"]].load(ROOT, c)
                for c in cell.config["cores"]}
    precision = {c["name"]: c["precision"] for c in cell.config["cores"]}
    t_ref = time.perf_counter()
    numbers = serve.compare(records, cell.kinds, refcores, lanes, precision)
    numbers["unresolved_draws"] = int((~ok).sum())
    numbers["window_draws"] = int(len(idx))
    serve.say(f"audit: {numbers} in {time.perf_counter() - t_ref:.3f} s")
    checks = serve.verdict(numbers)
    if control is not None:
        ctl = serve.compare(records, cell.kinds, refcores, lanes, precision,
                            control=control)
        ctl.update(unresolved_draws=numbers["unresolved_draws"],
                   window_draws=numbers["window_draws"])
        numbers["control"] = ctl
        numbers["control_checks"] = serve.verdict(ctl)

    e2e = {"words_per_s": words_in_window / window_s,
           "draw_p50_ms": serve.percentile(lat_ms, 50),
           "draw_p99_ms": serve.percentile(lat_ms, 99),
           "setup_s": setup_s}
    device = {"platform": devs[0].platform, "kind": devs[0].device_kind,
              "count": len(devs), "memory_peak_bytes": mem}
    numbers["latency_ms"] = lat_ms
    numbers["due"] = due
    result = {"correct": serve.passed(checks), "attempted": int(len(idx)),
              "failed": int((~ok).sum())}
    serve.say(f"window {window_s:.6f} s: {len(idx)} draws, "
              f"{words_in_window} words resolved in it; set-up "
              f"{setup_s:.3f} s")
    if not traced:
        result["metrics"] = {m["name"]: {"value": e2e[m["name"]],
                                         "unit": m["unit"]}
                             for m in cell.end_to_end}
        result["device"] = device
        return result, checks, numbers

    trace = tr.load(sorted(TRACE_DIR.rglob("*.xplane.pb"))[-1])
    classes = {c["name"]: cell.kinds[c["name"]].work_class(
        refcores[c["name"]], c) for c in cell.config["cores"]}
    # what the per-layer readers (bench/metrics/<name>.py) read
    obs = {"window_s": window_s, "draws": len(idx), "latency_ms": lat_ms,
           "draws_resolved": int(in_win.sum()),
           "gen_lag_ms": lag_ms if hasattr(plan, "due") else None,
           "flushes": stats["flushes"], "stages": stats["stages"],
           "words": words_in_window, "words_by_core": by_core,
           "classes": classes, "peaks": peaks, "chips": len(devs),
           "trace": trace}
    metrics = {}
    for m in cell.per_layer:
        v = spec.reader(m["name"])(obs)
        if v is not None:
            metrics[m["name"]] = {"value": v, "unit": m["unit"]}
    result["metrics"] = metrics
    device["busy_s"] = tr.busy_s(trace)
    t0_ns, t1_ns = trace.window()
    device["window_s"] = (t1_ns - t0_ns) / 1e9
    result["device"] = device
    result["breakdown"] = {"device_ops": [list(x) for x in tr.op_time(trace)],
                           "idle_gaps": [list(x) for x in tr.idle_gaps(trace)]}
    return result, checks, numbers


def emit(result, checks) -> None:
    for name, c in checks.items():
        op = ">=" if c.get("at_least") else "<="
        print(f"check {name} = {c['value']} (limit {op} {c['limit']})",
              file=sys.stderr)
    result["checks"] = {k: {"value": c["value"], "limit": c["limit"]}
                        for k, c in checks.items()}
    print(json.dumps(result))


def main(argv=None, require_tpu: bool = True) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    try:
        from bench import spec
        cell = spec.cell(args.workload)
        devs, peaks = device_info(cell.chips, require_tpu)
        if require_tpu:
            from repro.compile_cache import enable_compile_cache
            enable_compile_cache()
        result, checks, _ = run(cell, args.seed, args.seconds,
                                bool(args.trace), devs, peaks)
    except (Refused, ImportError, FileNotFoundError, KeyError) as e:
        print(f"bench: FAIL: {type(e).__name__}: {e}", file=sys.stderr)
        return 1
    emit(result, checks)
    return 0


if __name__ == "__main__":
    sys.exit(main())
