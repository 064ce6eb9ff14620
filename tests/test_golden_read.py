"""Golden-read regression: deterministic genome -> signal -> basecall.

The session-scoped ``golden_pipeline`` fixture (conftest.py) trains the
quickstart recipe with fixed seeds, and ``golden_read`` renders a known
60-base genome through the synthetic pore channel.  Every threshold here
is pinned comfortably below the deterministically achieved value, so a
decoder / merge-kernel / voting change that silently degrades accuracy
trips the gate while numerics-level jitter does not.

Achieved values at pin time (jax CPU, seed-fixed):
  window read accuracy  0.543
  consensus identity    0.467
"""
import numpy as np
import pytest

from repro.core import metrics

WINDOW_ACC_FLOOR = 0.45
CONSENSUS_IDENTITY_FLOOR = 0.35


def _identity(read, length, truth) -> float:
    return 1.0 - metrics.edit_distance(read[: int(length)], truth) / len(truth)


def _edit_distance_loop(a, b) -> int:
    """The textbook row-by-row Levenshtein recurrence, kept as the
    reference for the vectorized ``metrics.edit_distance``."""
    prev = list(range(len(b) + 1))
    for i, ca in enumerate(a, 1):
        cur = [i]
        for j, cb in enumerate(b, 1):
            cur.append(min(prev[j] + 1, cur[j - 1] + 1,
                           prev[j - 1] + (ca != cb)))
        prev = cur
    return prev[-1]


@pytest.mark.parametrize("n1,n2", [(0, 0), (0, 7), (9, 0), (1, 1), (13, 40),
                                   (64, 57), (120, 120)])
def test_edit_distance_matches_loop_reference(n1, n2):
    rng = np.random.default_rng(n1 * 1000 + n2)
    for _ in range(5):
        a = rng.integers(0, 4, n1)
        b = a[:n2].copy() if n2 <= n1 else rng.integers(0, 4, n2)
        b[rng.random(len(b)) < 0.3] = rng.integers(0, 4)     # mutate some
        assert metrics.edit_distance(a, b) == _edit_distance_loop(a, b)
        assert metrics.edit_distance(b, a) == _edit_distance_loop(b, a)


def test_golden_window_read_accuracy(golden_pipeline):
    """Fixed-window serving path: beam-decoded reads vs training labels."""
    from repro.data import genome

    pipe, params, dcfg = golden_pipeline
    batch = genome.batch_for_step(9999, 8, dcfg)          # held-out step
    _, _, top, top_len, _ = pipe.basecall_windows(batch["signal"], params)
    acc = metrics.accuracy(np.asarray(top), np.asarray(top_len),
                           np.asarray(batch["labels"]),
                           np.asarray(batch["label_length"]))
    assert acc >= WINDOW_ACC_FLOOR, f"window read accuracy {acc:.3f}"


def test_golden_consensus_identity(golden_pipeline, golden_read):
    """Long-read path: chunk -> hash-merge beam decode -> vote, vs truth."""
    pipe, params, _ = golden_pipeline
    seq, sig = golden_read
    res = pipe.basecall(sig, params)
    ident = _identity(res.read, res.length, seq)
    assert ident >= CONSENSUS_IDENTITY_FLOOR, (
        f"consensus identity {ident:.3f} (len {res.length} vs {len(seq)})")


def test_golden_packed_bitwise_equals_repack(golden_pipeline, golden_read):
    """PR 3 acceptance: the quantize-once PackedParams serving path is
    bitwise identical to the pre-refactor repack-per-call path on the
    golden read — window reads, lengths AND voted consensus."""
    from repro.pipeline import BasecallPipeline

    pipe, params, _ = golden_pipeline
    _, sig = golden_read
    unpacked = BasecallPipeline(pipe.mcfg, backend=pipe.backend,
                                scfg=pipe.scfg, chunk=pipe.chunk,
                                beam_width=pipe.beam_width, packed=False,
                                params=params)
    want = unpacked.basecall(sig)            # per-call weight repacking
    got = pipe.basecall(sig, params)         # packed artifact (default)
    np.testing.assert_array_equal(got.window_reads, want.window_reads)
    np.testing.assert_array_equal(got.window_lengths, want.window_lengths)
    assert got.length == want.length
    np.testing.assert_array_equal(got.read, want.read)


def test_golden_consensus_matches_engine(golden_pipeline, golden_read):
    """The continuous-batching engine (behind the serving API) must
    reproduce the pipeline's golden consensus exactly (same windows, same
    logit_lengths, same decoder)."""
    from repro.serve import BasecallRequest, Server
    from repro.serve.basecall_engine import BasecallEngine

    pipe, params, _ = golden_pipeline
    seq, sig = golden_read
    want = pipe.basecall(sig, params)
    srv = Server(BasecallEngine(pipe, params=params, batch_slots=2))
    got = srv.submit(BasecallRequest(signal=sig)).result().value
    assert got.length == want.length
    np.testing.assert_array_equal(got.read[: got.length],
                                  want.read[: want.length])
