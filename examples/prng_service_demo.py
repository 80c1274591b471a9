"""Streaming chaotic-PRNG service demo — the HENNC engine as a serving
system.

Eight named clients share ONE fused-kernel launch per flush: each owns a
block of lanes on the stream axis, carries its own Weyl word counter, and
the DSE autotuner (paper Eqs. 8-9) picks the kernel microarchitecture.
Shows (1) batched serving, (2) bit-exact determinism across service
instances, (3) snapshot/restore resumability.

Run:  PYTHONPATH=src python examples/prng_service_demo.py
"""
import pathlib
import sys

import numpy as np

sys.path.insert(0, str(pathlib.Path(__file__).parent.parent / "src"))

from repro.prng.stream import default_params
from repro.serve.prng_service import PRNGService

N_CLIENTS = 8


def build_service(params):
    svc = PRNGService(params, lanes_per_client=128)
    for i in range(N_CLIENTS):
        svc.register(f"client{i}", seed=1000 + i)
    return svc


def main():
    print("=== train the Chen oscillator (cached per process) ===")
    params = default_params()

    svc = build_service(params)
    print(f"DSE-selected kernel config: {svc.config}")

    print(f"\n=== {N_CLIENTS} clients, one batched launch ===")
    for i in range(N_CLIENTS):
        svc.request(f"client{i}", 1000 + 100 * i)
    out = svc.flush()
    assert svc.launches == 1
    for name in sorted(out):
        w = out[name]
        ones = np.unpackbits(w.view(np.uint8)).mean()
        print(f"  {name}: {w.size:5d} words in launch #1, "
              f"monobit={ones:.4f}, head={w[:3]}")

    print("\n=== determinism: a fresh service replays identical streams ===")
    svc2 = build_service(params)
    replay = svc2.draw("client3", 1300)
    assert np.array_equal(replay, out["client3"]), "determinism broken!"
    print("  client3 replay: bit-identical")

    print("\n=== resumability: snapshot -> draw -> restore -> draw ===")
    snap = svc.snapshot()
    a = svc.draw("client5", 2000)
    svc3 = PRNGService(params, lanes_per_client=128)
    svc3.restore(snap)
    b = svc3.draw("client5", 2000)
    assert np.array_equal(a, b), "resume broken!"
    print(f"  client5 resumed mid-stream: bit-identical "
          f"({a.size} words, head={a[:3]})")

    print(f"\n{svc.launches + svc2.launches + svc3.launches} total kernel "
          f"launches served {N_CLIENTS + 2} draws for {N_CLIENTS} clients.")
    print("demo complete.")


if __name__ == "__main__":
    from repro.compile_cache import enable_compile_cache
    enable_compile_cache()
    main()
