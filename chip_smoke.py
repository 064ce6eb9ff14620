#!/usr/bin/env python3
"""Bring-up smoke run of the Helix serving path on a TPU.

    python chip_smoke.py              # one chip
    python chip_smoke.py --chips 4    # the dp-sharded path on four chips

One chip: the full-width, 5-bit guppy pipeline (``models.basecaller.
GUPPY``: 300-sample windows, conv 11x96 stride 2, five alternating GRU
layers at H = 96, beam width 5, decode strip 8) on ``backend="pallas"``
serves seeded synthetic reads of mixed lengths through ``serve.Server``
over ``BasecallEngine``.  Every read must come back ok, the compiled
decode step must hold the Pallas kernels as ``tpu_custom_call``s, and the
same reads on ``backend="ref"``, on the same chip, must agree within the
tolerances below.

Four chips (``--chips 4``): the same requests through ``BasecallEngine``
under a 4-chip ``("data",)`` mesh and through the same engine on one of
those chips.  The consensus reads must be bitwise equal, and every
step's window batch must land as four shards, one per chip.  No other
phase runs.

Weights and reads come from ``--seed``; nothing is downloaded.  The lines
before the last are a smoke run's notes, not a benchmark.  The last line
of stdout is ``{"ok": true, "device": {...}}``, printed only when every
phase passed: any failure, or a platform other than TPU, exits non-zero
without it.  JAX's persistent compile cache goes to
``JAX_COMPILATION_CACHE_DIR`` when that is set, else to ``.jax_cache``
in this checkout.
"""
from __future__ import annotations

import argparse
import contextlib
import json
import os
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parent
sys.path.insert(0, str(ROOT / "src"))
# JAX reads this when imported; a fixed path, as the path is part of the
# cache key
os.environ.setdefault("JAX_COMPILATION_CACHE_DIR", str(ROOT / ".jax_cache"))

import jax  # noqa: E402
import numpy as np  # noqa: E402

from repro.core import metrics  # noqa: E402
from repro.core.quant import QuantConfig  # noqa: E402
from repro.data import genome  # noqa: E402
from repro.dist import sharding as shd  # noqa: E402
from repro.models import basecaller as bc  # noqa: E402
from repro.pipeline import BasecallPipeline, chunking  # noqa: E402
from repro.serve import BasecallRequest, Server  # noqa: E402
from repro.serve.basecall_engine import BasecallEngine  # noqa: E402

QUANT = QuantConfig(enabled=True, bits_w=5, bits_a=5)
N_READS = 8
READ_SAMPLES = (4_000, 20_000)
BATCH_SLOTS = 32
LP_WINDOWS = 64            # windows whose log-probs are compared
# pallas vs ref on the chip.  The GRU's f32 dots run at full precision
# in the kernels and in their oracle alike, so the two programs differ
# only in rounding (Mosaic vs XLA transcendentals and summation order).
# Through five 5-bit activation quantizers a last-bit difference can
# still move a value across a quantization step now and then, so the
# comparison is statistical, not bitwise:
LP_MEAN_ABS_MAX = 1e-3      # mean |log p_pallas - log p_ref|, valid frames
LP_ARGMAX_AGREE_MIN = 0.99  # share of valid frames with the same argmax
READ_IDENTITY_MIN = 0.98    # mean over reads of 1 - edit distance / length


class SmokeFailure(RuntimeError):
    pass


def check(cond: bool, msg: str) -> None:
    if not cond:
        raise SmokeFailure(msg)


def note(msg: str) -> None:
    print(f"smoke: {msg}", flush=True)


def make_pipeline(backend: str, params=None) -> BasecallPipeline:
    pipe = BasecallPipeline.from_preset("guppy", scale="full", quant=QUANT,
                                        backend=backend, params=params)
    check((pipe.beam_width, pipe.decode_strip) == (5, 8),
          "guppy serving defaults changed")
    return pipe


def make_reads(pipe: BasecallPipeline, seed: int) -> list:
    """N_READS seeded reads, uniform in READ_SAMPLES, through the
    synthetic pore channel (``data.genome.render_signal``)."""
    dcfg = pipe.data_config()
    rng = np.random.default_rng(seed)
    reads = []
    for i in range(N_READS):
        n = int(rng.integers(*READ_SAMPLES))
        seq = rng.integers(0, genome.N_BASES, int(n / dcfg.mean_dwell))
        sig, _ = genome.render_signal(seq, dcfg,
                                      jax.random.PRNGKey(seed + 1 + i))
        reads.append(np.asarray(sig)[:n])
    return reads


def serve(pipe: BasecallPipeline, signals, *, batch_slots: int,
          mesh=None) -> tuple:
    """Every read through ``Server`` over ``BasecallEngine`` (under
    ``mesh`` when given).  Returns (engine, results, first-step seconds,
    total seconds)."""
    ctx = shd.use_mesh(mesh) if mesh is not None else contextlib.nullcontext()
    with ctx:
        engine = BasecallEngine(pipe, batch_slots=batch_slots)
    srv = Server(engine, max_queue=len(signals))
    futs = [srv.submit(BasecallRequest(signal=s)) for s in signals]
    t0 = time.perf_counter()
    srv.step()                       # admits, compiles and runs step 1
    t_first = time.perf_counter() - t0
    srv.run_until_idle()
    results = [f.result() for f in futs]
    t_total = time.perf_counter() - t0
    bad = [(r.rid, r.status, r.error) for r in results if not r.ok]
    check(not bad, f"requests not ok: {bad}")
    return engine, results, t_first, t_total


def windows_of(results) -> int:
    return sum(len(r.value.window_lengths) for r in results)


def custom_calls(pipe: BasecallPipeline, engine: BasecallEngine) -> int:
    """``tpu_custom_call``s in the compiled decode step the engine ran."""
    step = pipe._decode_windows.cache[None]
    mcfg = pipe.mcfg
    batch = np.zeros((engine.B, mcfg.input_len, mcfg.in_channels),
                     np.float32)
    frames = np.full((engine.B,), mcfg.output_len, np.int32)
    hlo = step.lower(engine.params, batch, frames).compile().as_text()
    return hlo.count("tpu_custom_call")


def logprob_agreement(pipe, ref_pipe, signals) -> tuple:
    """(mean |diff|, max |diff|, argmax agreement) of the DNN log-probs on
    LP_WINDOWS windows of the reads, pallas vs ref."""
    wins = np.concatenate([chunking.chunk_signal(s, pipe.chunk)
                           for s in signals])[:LP_WINDOWS]
    frames = np.concatenate([pipe.window_logit_lengths(len(s))
                             for s in signals])[:LP_WINDOWS]
    lps = []
    for p in (pipe, ref_pipe):
        fwd = jax.jit(lambda prm, w, p=p: bc.apply_basecaller(
            prm, w, p.mcfg, p.backend))
        lps.append(np.asarray(fwd(pipe.serving_params(), wins)))
    valid = np.arange(lps[0].shape[1])[None, :] < frames[:, None]
    diff = np.abs(lps[0] - lps[1])[valid]
    agree = (lps[0].argmax(-1) == lps[1].argmax(-1))[valid]
    return float(diff.mean()), float(diff.max()), float(agree.mean())


def read_identity(a, b) -> float:
    ra, rb = a.read[:a.length], b.read[:b.length]
    return 1.0 - metrics.edit_distance(ra, rb) / max(len(ra), len(rb), 1)


def one_chip(seed: int) -> None:
    t0 = time.perf_counter()
    pipe = make_pipeline("pallas")
    pipe.init_params(jax.random.PRNGKey(seed))
    signals = make_reads(pipe, seed)
    note(f"{N_READS} reads of {min(map(len, signals))}-"
         f"{max(map(len, signals))} samples, set-up "
         f"{time.perf_counter() - t0:.1f} s")

    engine, got, t_first, t_total = serve(pipe, signals,
                                          batch_slots=BATCH_SLOTS)
    n_calls = custom_calls(pipe, engine)
    note(f"pallas: {len(got)} reads ok, {windows_of(got)} windows in "
         f"{engine.steps} steps of {engine.B} lanes; first step (compile "
         f"+ run) {t_first:.1f} s, whole pass {t_total:.1f} s (cold: it "
         f"also compiles each read's stitch/vote shapes); decode step "
         f"holds {n_calls} tpu_custom_call")
    check(n_calls >= 3, "decode step lost its Pallas kernels "
          "(quant_matmul, gru_seq, beam_merge_multiframe)")

    ref_pipe = make_pipeline("ref", params=pipe.params)
    _, want, t_first, t_total = serve(ref_pipe, signals,
                                      batch_slots=BATCH_SLOTS)
    note(f"ref: {len(want)} reads ok; first step {t_first:.1f} s, whole "
         f"pass {t_total:.1f} s (reuses the stitch/vote compiles)")
    check([windows_of([r]) for r in got] == [windows_of([r]) for r in want],
          "pallas and ref decoded different window counts")

    mean_abs, max_abs, argmax_agree = logprob_agreement(pipe, ref_pipe,
                                                        signals)
    ident = [read_identity(g.value, w.value) for g, w in zip(got, want)]
    note(f"log-probs pallas vs ref on {LP_WINDOWS} windows: mean |diff| "
         f"{mean_abs:.3g} (limit {LP_MEAN_ABS_MAX}), max |diff| "
         f"{max_abs:.3g}, argmax agreement {argmax_agree:.4f} (limit "
         f"{LP_ARGMAX_AGREE_MIN})")
    note(f"consensus reads pallas vs ref: mean identity "
         f"{np.mean(ident):.4f} (limit {READ_IDENTITY_MIN}), per read "
         f"{[round(x, 4) for x in ident]}, "
         f"{sum(g.value.length == w.value.length for g, w in zip(got, want))}"
         f"/{len(got)} lengths equal")
    check(mean_abs <= LP_MEAN_ABS_MAX, "log-probs beyond tolerance")
    check(argmax_agree >= LP_ARGMAX_AGREE_MIN, "frame argmax disagrees")
    check(np.mean(ident) >= READ_IDENTITY_MIN, "decoded reads disagree")


def four_chips(seed: int) -> None:
    devices = jax.devices()
    check(len(devices) >= 4, f"--chips 4 needs 4 chips, found {len(devices)}")
    mesh = shd.make_mesh((4,), ("data",), devices=devices[:4])
    pipe = make_pipeline("pallas")
    pipe.init_params(jax.random.PRNGKey(seed))
    signals = make_reads(pipe, seed)

    _, single, _, t_single = serve(pipe, signals, batch_slots=BATCH_SLOTS)

    # observe where each step's window batch lives
    decode, layouts = pipe._decode_windows, []

    def probe(params, batch, frames):
        layouts.append(sorted((s.device.id, s.data.shape)
                              for s in batch.addressable_shards))
        return decode(params, batch, frames)

    pipe.__dict__["_decode_windows"] = probe
    engine, sharded, t_first, t_total = serve(
        pipe, signals, batch_slots=BATCH_SLOTS // 4, mesh=mesh)
    pipe.__dict__["_decode_windows"] = decode

    shard = (BATCH_SLOTS // 4, pipe.mcfg.input_len, pipe.mcfg.in_channels)
    want = sorted((d.id, shard) for d in devices[:4])
    check(layouts and all(lay == want for lay in layouts),
          f"window batch not split one shard per chip: {layouts[:2]}")
    for a, b in zip(single, sharded):
        check(a.value.length == b.value.length
              and np.array_equal(a.value.read, b.value.read)
              and np.array_equal(a.value.window_reads, b.value.window_reads),
              f"read {a.rid}: 4-chip consensus differs from 1-chip")
    note(f"4 chips: {len(sharded)} reads ok, {windows_of(sharded)} windows "
         f"in {engine.steps} steps of {engine.B} lanes, every step split "
         f"{BATCH_SLOTS // 4} windows per chip; first step {t_first:.1f} s,"
         f" whole pass {t_total:.1f} s (1 chip, cold: {t_single:.1f} s); "
         f"consensus reads bitwise equal to the 1-chip engine")


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()

    print(f"jax {jax.__version__}; devices {jax.devices()}", flush=True)
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: JAX runs on {dev.platform!r}, not a TPU; "
              "nothing to smoke-test", file=sys.stderr)
        return 2
    try:
        (four_chips if args.chips == 4 else one_chip)(args.seed)
    except SmokeFailure as e:
        print(f"chip_smoke: FAILED: {e}", file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": 4 if args.chips == 4 else len(jax.devices())}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
