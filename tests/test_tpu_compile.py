"""Compile the serving path's kernels for a TPU v5e without a chip.

The TPU compiler is installed with JAX and compiles for a chip that is
described, not attached (``jax.experimental.topologies``).  Interpret
mode runs the kernel bodies on the CPU and cannot see what Mosaic
refuses — unaligned lane slices, blocks that break the (8, 128) tiling
rule, VMEM overflow — so these tests compile the hot kernels at guppy's
published widths (``models.basecaller.GUPPY``: 300-sample windows, conv
11x96 stride 2 -> 150 frames, GRU H = 96, beam width 5 over A = 5
classes) and the whole jitted decode step, and check that the compiled
HLO holds the Pallas kernels as ``tpu_custom_call``s.  Nothing runs.

The topology is described inside a module-scoped fixture, never while a
module is imported: only one process at a time may load the TPU
library, so under several pytest workers only the worker given this file
loads it.  Arguments are shapes (``ShapeDtypeStruct``) on the described
device, never arrays.
"""
from __future__ import annotations

import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.core.quant import QuantConfig
from repro.kernels import registry

B, T, H = 64, 150, 96          # windows per step, frames, GRU width
W, A, F = 5, 5, 8              # beam width, classes, decode strip


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler in this installation
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    # compiles for the described chip are written to the persistent cache
    # but cannot be read back without one: keep the cache out of it
    from jax.experimental.compilation_cache import compilation_cache
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", prev)


@pytest.fixture
def on_tpu(monkeypatch):
    """The registry asks JAX for the platform, which here is the CPU;
    steer it to the described chip so backend="pallas" resolves."""
    monkeypatch.setattr(registry, "_platform", lambda: "tpu")


def _shape(sharding, shape, dtype=jnp.float32):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _compiled_text(fn, *shapes) -> str:
    return jax.jit(fn).lower(*shapes).compile().as_text()


def test_gru_seq_compiles_at_guppy_width(one_chip):
    from repro.kernels.gru_seq.ops import _impl_pallas
    hlo = _compiled_text(
        _impl_pallas, _shape(one_chip, (T, B, 3 * H)),
        _shape(one_chip, (B, H)), _shape(one_chip, (H, 3 * H)),
        _shape(one_chip, (3 * H,)))
    assert "tpu_custom_call" in hlo


def test_beam_merge_multiframe_compiles_at_guppy_width(one_chip):
    from repro.kernels.beam_strip.ops import _impl_pallas

    def strip(*args):
        return _impl_pallas(*args, blank=A - 1, L=T)

    i32 = jnp.int32
    hlo = _compiled_text(
        strip, _shape(one_chip, (B, F, A)), _shape(one_chip, (B, F), i32),
        _shape(one_chip, (B, W), i32), _shape(one_chip, (B, W)),
        _shape(one_chip, (B, W)), _shape(one_chip, (B, W), i32),
        _shape(one_chip, (B, W), i32))
    assert "tpu_custom_call" in hlo


def test_quant_matmul_compiles_at_guppy_width(one_chip):
    """The GRU input projection of one step: (B*T, H) x (H, 3H)."""
    from repro.kernels.quant_matmul.ops import _impl_pallas
    hlo = _compiled_text(
        _impl_pallas, _shape(one_chip, (B * T, H), jnp.int8),
        _shape(one_chip, (H, 3 * H), jnp.int8), _shape(one_chip, (1, 1)),
        _shape(one_chip, (1, 3 * H)))
    assert "tpu_custom_call" in hlo


def _guppy_step_hlo(windows, lengths, params_sharding, mesh=None) -> str:
    """Compiled HLO of the full-width, 5-bit guppy pipeline's jitted
    ``_decode_windows`` step on backend="pallas" — quantized DNN + strip
    beam decode, as ``BasecallEngine.step`` runs it."""
    from repro.dist import sharding as shd
    from repro.models import basecaller as bc
    from repro.pipeline import BasecallPipeline

    pipe = BasecallPipeline.from_preset(
        "guppy", scale="full", backend="pallas",
        quant=QuantConfig(enabled=True, bits_w=5, bits_a=5))
    assert (pipe.beam_width, pipe.decode_strip) == (W, F)
    packed = bc.pack_basecaller(
        bc.init_basecaller(jax.random.PRNGKey(0), pipe.mcfg), pipe.mcfg)
    params = jax.tree_util.tree_map(
        lambda x: _shape(params_sharding, x.shape, x.dtype), packed)
    with shd.use_mesh(mesh):
        step = pipe._build_decode_windows()
        return step.lower(params, windows, lengths).compile().as_text()


def test_full_guppy_decode_step_compiles(one_chip, on_tpu):
    hlo = _guppy_step_hlo(_shape(one_chip, (B, 300, 1)),
                          _shape(one_chip, (B,), jnp.int32), one_chip)
    assert hlo.count("tpu_custom_call") >= 3   # quant_matmul, gru_seq, strip


def test_dp_sharded_guppy_decode_step_compiles_on_four_chips(topo, one_chip,
                                                             on_tpu):
    """The same step under a 4-chip ("data",) mesh: XLA cannot partition
    a Mosaic kernel, so each kernel must run per dp shard."""
    from jax.sharding import NamedSharding, PartitionSpec as P
    from repro.dist import sharding as shd

    mesh = shd.make_mesh((4,), ("data",), devices=topo.devices)
    hlo = _guppy_step_hlo(
        _shape(shd.batch_sharding(mesh, 3), (B, 300, 1)),
        _shape(shd.batch_sharding(mesh, 1), (B,), jnp.int32),
        NamedSharding(mesh, P()), mesh)
    assert hlo.count("tpu_custom_call") >= 3
