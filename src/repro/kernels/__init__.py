"""Pallas TPU kernels for the compute hot-spots Helix optimizes.

Each subpackage ships kernel.py (pl.pallas_call + explicit BlockSpec VMEM
tiling), ops.py (jit'd wrapper: padding, registry dispatch), and
ref.py (pure-jnp oracle; tests assert allclose across shape sweeps).

  quant_matmul — int8-container low-bit matmul + fused dequant epilogue
                 (the NVM dot-product engine on the MXU, §4.2)
  vote_cmp     — XOR-popcount substring comparator (the SOT-MRAM binary
                 comparator array as one int8 matmul, §4.3/Fig 20)
  ctc_merge    — CTC beam-merge masked logsumexp (the BL-merge transistors
                 of Fig 18 as a crossbar-shaped VPU reduction)
  gru_cell     — fused GRU step, U stationary in VMEM (the base-caller's
                 recurrent hot loop, Table 3)
  decode_attn  — online-softmax single-token attention over a KV cache
                 (the serving memory-roofline hot-spot, EXPERIMENTS §Perf)

Dispatch is centralized in ``repro.kernels.registry``: every op registers a
(ref, pallas) pair and callers resolve concrete callables with
``registry.get_op(name, backend)`` where backend is one of
auto | pallas | interpret | ref.  The ``registry.Backend`` dataclass is the
switch models and the pipeline thread through their call stacks.
"""
from repro.kernels import registry  # noqa: F401
from repro.kernels.registry import Backend, get_op, register_op  # noqa: F401

__all__ = ["registry", "Backend", "get_op", "register_op"]

