"""Sharded basecall scaling: decoded windows/s vs dp device count.

The paper's throughput story is scale-out — PIM arrays basecall many
signal windows concurrently — and the repo's counterpart is the
dp-sharded ``BasecallPipeline`` path: the window batch splits over a
``dist.sharding`` mesh's data-parallel devices, the serving artifact is
replicated, and per-window reads are all-gathered before the stitch.
This benchmark times the same long read through meshes of growing device
count and reports windows/s per count (plus the speedup over 1 device).

    XLA_FLAGS=--xla_force_host_platform_device_count=4 \
        PYTHONPATH=src python -m benchmarks.run --only shard_scaling
    PYTHONPATH=src python benchmarks/fig_shard_scaling.py --devices 4

Standalone invocation forces the host device count itself (before jax
loads); through ``benchmarks.run`` it sweeps whatever devices the already
initialized process has (real accelerators included).  On CPU the fake
host devices share the same cores, so windows/s is a plumbing check —
the scaling *shape* is only meaningful on real parallel hardware.
"""
import argparse
import time

import numpy as np


def _pipeline(backend: str):
    import jax

    from repro.core.quant import QuantConfig
    from repro.pipeline import BasecallPipeline

    pipe = BasecallPipeline.from_preset(
        "guppy", scale="tiny",
        quant=QuantConfig(enabled=True, bits_w=5, bits_a=5),
        backend=backend, beam_width=3)
    pipe.init_params(jax.random.PRNGKey(0))
    return pipe


def _device_counts(limit: int):
    import jax

    n = len(jax.devices())
    counts = [c for c in (1, 2, 4, 8, 16) if c <= min(n, limit)]
    return counts or [1]


def run(smoke: bool = False, backend: str = "auto", max_devices: int = 16,
        repeats: int = None):
    """windows/s through ``pipe.basecall`` per dp device count."""
    import jax

    from repro.dist import sharding as shd
    from repro.pipeline import chunking

    pipe = _pipeline(backend)
    repeats = repeats or (2 if smoke else 5)
    n_win = 16 if smoke else 64
    rng = np.random.default_rng(0)
    sig = rng.standard_normal(
        pipe.mcfg.input_len + (n_win - 1) * pipe.chunk.hop
    ).astype(np.float32)
    n_windows = chunking.n_windows(sig.shape[0], pipe.chunk)

    rows = []
    base = None
    for c in _device_counts(max_devices):
        mesh = shd.make_mesh((c,), ("data",), devices=jax.devices()[:c])
        with shd.use_mesh(mesh):
            pipe.basecall(sig)                       # compile + place
            t0 = time.perf_counter()
            for _ in range(repeats):
                res = pipe.basecall(sig)
            dt = (time.perf_counter() - t0) / repeats
        assert res.window_reads.shape[0] == n_windows
        wps = n_windows / dt
        base = base or wps
        rows.append((f"shard_scaling/dp{c}/windows_per_s", f"{wps:.1f}",
                     f"{n_windows} windows, {c} device(s), "
                     f"speedup x{wps / base:.2f}"))
    return rows


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--devices", type=int, default=4,
                    help="host devices to force (standalone runs only; "
                         "must be set before jax initializes)")
    ap.add_argument("--smoke", action="store_true",
                    help="short read / few repeats (CI)")
    ap.add_argument("--backend", default="auto")
    args = ap.parse_args()
    # must precede the first jax import (run() imports it lazily)
    from repro.hostdev import force_host_devices
    force_host_devices(args.devices)
    print("name,us_per_call,derived")
    for name, val, derived in run(smoke=args.smoke, backend=args.backend,
                                  max_devices=args.devices):
        print(f"{name},{val},{derived}")


if __name__ == "__main__":
    main()
