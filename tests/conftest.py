"""Shared test config.

When the real ``hypothesis`` package is unavailable (the CI/container image
does not ship it and installing deps is out of scope), install a minimal
deterministic stand-in BEFORE test modules import it: ``@given`` runs the
test body over a fixed pseudo-random sample of the strategy space
(``max_examples`` draws, seeded per test name), which keeps the property
tests meaningful — just without shrinking or adaptive search.
"""
from __future__ import annotations

import functools
import importlib.util
import os
import random
import sys
import types

# ---------------------------------------------------------------------------
# multi-device host platform: the dist-pipeline tests exercise the dp-sharded
# basecall path on 4 fake host devices.  XLA locks the device count at first
# backend init, so the flag must land BEFORE any test imports jax — conftest
# import time is the one place pytest guarantees runs first (repro.hostdev
# is jax-free, so this import initializes nothing).  Single-device tests are
# unaffected: unsharded arrays still live on device 0, and
# sharding.constrain is a no-op without an ambient mesh.
# ---------------------------------------------------------------------------
from repro.hostdev import force_host_devices  # noqa: E402

force_host_devices(4)

# The tests, and every child process they start (children copy this
# environment), run on the CPU: a TPU belongs to one process at a time,
# so a test run on a machine with a chip must never take it.  The chip is
# driven by ``chip_smoke.py``, alone.
os.environ["JAX_PLATFORMS"] = "cpu"


def _install_hypothesis_fallback() -> None:
    mod = types.ModuleType("hypothesis")
    strategies = types.ModuleType("hypothesis.strategies")

    class _Integers:
        def __init__(self, lo: int, hi: int):
            self.lo, self.hi = lo, hi

        def sample(self, rng: random.Random) -> int:
            return rng.randint(self.lo, self.hi)

    class _SampledFrom:
        def __init__(self, options):
            self.options = list(options)

        def sample(self, rng: random.Random):
            return rng.choice(self.options)

    def integers(min_value: int, max_value: int) -> _Integers:
        return _Integers(min_value, max_value)

    def sampled_from(options) -> _SampledFrom:
        return _SampledFrom(options)

    def given(**strategy_kwargs):
        def deco(fn):
            def wrapper(*args, **kwargs):
                n = getattr(wrapper, "_max_examples", 10)
                rng = random.Random(fn.__qualname__)
                for _ in range(n):
                    drawn = {k: s.sample(rng)
                             for k, s in strategy_kwargs.items()}
                    fn(*args, **drawn, **kwargs)
            # keep the test's name/docs but NOT its signature — pytest
            # must not mistake the strategy params for fixtures
            wrapper.__name__ = fn.__name__
            wrapper.__doc__ = fn.__doc__
            wrapper.__module__ = fn.__module__
            return wrapper
        return deco

    def settings(max_examples=None, deadline=None, **_ignored):
        def deco(fn):
            if max_examples is not None:
                fn._max_examples = max_examples
            return fn
        return deco

    strategies.integers = integers
    strategies.sampled_from = sampled_from
    mod.strategies = strategies
    mod.given = given
    mod.settings = settings
    mod.HealthCheck = types.SimpleNamespace(all=lambda: [])
    sys.modules["hypothesis"] = mod
    sys.modules["hypothesis.strategies"] = strategies


if importlib.util.find_spec("hypothesis") is None:
    _install_hypothesis_fallback()


# ---------------------------------------------------------------------------
# golden-read fixture: deterministic genome -> signal -> basecall round-trip
# ---------------------------------------------------------------------------
#
# One session-scoped trained pipeline (the quickstart recipe: demo-scale
# Guppy, 5-bit quant, warm-up + SEAT, fixed seeds end to end) plus a known
# genome rendered through the synthetic pore channel.  Tests pin consensus
# read identity against thresholds comfortably below the deterministic
# achieved values, so decoder/voting changes cannot silently degrade
# accuracy.  Built lazily — only sessions running the golden tests pay the
# ~30 s training cost.

import pytest

GOLDEN_SEED = 42
GOLDEN_GENOME_LEN = 60
GOLDEN_TRAIN_STEPS = 300


@pytest.fixture(scope="session")
def host_mesh4():
    """A 4-device data-parallel host mesh (dp = 4, no model axis).

    Skips when the process has fewer than 4 devices — e.g. when something
    imported jax before this conftest's XLA_FLAGS append could take."""
    import jax
    from repro.dist.sharding import make_mesh
    if len(jax.devices()) < 4:
        pytest.skip("needs 4 host devices "
                    "(XLA_FLAGS=--xla_force_host_platform_device_count=4)")
    return make_mesh((4,), ("data",))


@pytest.fixture(scope="session")
def golden_pipeline():
    """(pipe, params, data_config) trained on the fixed golden recipe."""
    import jax
    from repro.core.quant import QuantConfig
    from repro.data import genome
    from repro.pipeline import BasecallPipeline, TrainPolicy

    pipe = BasecallPipeline.from_preset(
        "guppy", scale="demo",
        quant=QuantConfig(enabled=True, bits_w=5, bits_a=5),
        backend="ref", beam_width=5)
    dcfg = pipe.data_config(kmer=1, mean_dwell=6.0, max_label_len=40)
    params = pipe.init_params(jax.random.PRNGKey(0))
    warm = int(GOLDEN_TRAIN_STEPS * 0.73)          # quickstart's 220/80 split
    policy = TrainPolicy(warmup_steps=warm,
                         seat_steps=GOLDEN_TRAIN_STEPS - warm)
    trainer = pipe.trainer(policy)
    state = trainer.init(params)
    for step in range(policy.total_steps):
        batch = genome.batch_for_step(step, 8, dcfg)
        params, state, _, _ = pipe.train_step(params, state, batch, step)
    pipe.params = params
    return pipe, params, dcfg


@pytest.fixture(scope="session")
def golden_read(golden_pipeline):
    """(sequence (60,), signal) — a known genome through the pore model."""
    import jax
    import numpy as np
    from repro.data import genome

    _, _, dcfg = golden_pipeline
    rng = np.random.default_rng(GOLDEN_SEED)
    seq = rng.integers(0, 4, GOLDEN_GENOME_LEN).astype(np.int32)
    sig, _ = genome.render_signal(seq, dcfg, jax.random.PRNGKey(99))
    return seq, np.asarray(sig)
