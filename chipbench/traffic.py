"""The one traffic generator: reads, their signal, and their timing.

Every traffic mix is a data file ``traffic/<mix>.json`` of parameters;
this module turns those parameters and ``--seed`` into inputs.  The
program under test receives only the generated signal.

The pore channel is the synthetic one the repository's own generator
models (``data.genome.render_signal``), kept here as plain numpy so that
no later change to the program can change the yardstick:

    bases --(6-mer pore table)--> current level per base
          --(dwell: 1 + Poisson(samples_per_base - 1), clipped)--> samples
          --(+ Gaussian noise)--> raw current

Unlike the program's helper, the signal is scaled by one constant (the
table is standardized, so ``level + noise`` has standard deviation
``sqrt(1 + noise_std**2)``) instead of by the whole read's statistics:
a live pore has no whole read to standardize over.

Read ``i`` of stream ``s`` draws its bases, dwells and noise from
``numpy.random.default_rng([seed, s, i])``, so the same seed gives the
same reads whatever order a run asks for them in.

The sizes that set how much work a run holds are stratified, so that
every seed gives the same set of them in another order: a read's length
in bases is the length law's quantile at ``(j + 0.5) / block`` for its
place ``j`` in a seeded permutation of its block of ``block`` reads, and
its length in samples is that times ``samples_per_base`` (the dwell law
shapes the signal, not its length).  Only which read gets which size,
and the signal itself, change with the seed.

A run that starts in steady state (``in_flight``) begins with the reads
the lanes already hold: each is the rest of a read in flight at a
random moment.  Such a rest follows the equilibrium law of the length
law (a read in flight is drawn in proportion to its length, the moment
uniformly within it), whose density at ``r`` is ``P(L > r) / E[L]``;
the first ``in_flight`` reads take its quantiles, stratified as above.
"""
from __future__ import annotations

import math
import statistics
from typing import Optional

import numpy as np

N_BASES = 4
BLOCK_BASES = 4096          # bases rendered per extension of a read
AUX = 2 ** 32 - 1           # stream/index of draws that are not reads


def pore_table(kmer: int, table_seed: int) -> np.ndarray:
    """Fixed pseudo-random k-mer -> current level table, standardized."""
    tbl = np.random.default_rng(table_seed).standard_normal(N_BASES ** kmer)
    return ((tbl - tbl.mean()) / tbl.std()).astype(np.float32)


class Channel:
    """The pore channel of one traffic mix (its ``pore`` parameters)."""

    def __init__(self, pore: dict, samples_per_base: float):
        self.kmer = int(pore["kmer"])
        self.noise_std = float(pore["noise_std"])
        self.table = pore_table(self.kmer, int(pore["table_seed"]))
        self.samples_per_base = float(samples_per_base)
        self.max_dwell = int(4 * samples_per_base)
        self.scale = np.float32(1.0 / math.sqrt(1.0 + self.noise_std ** 2))


def _lognormal(law: dict) -> tuple:
    if law["law"] != "lognormal":
        raise ValueError(f"unknown read-length law {law['law']!r}")
    return math.log(float(law["median"])), float(law["sigma"])


def read_bases_at(q: float, law: dict) -> float:
    """Read length in bases at quantile ``q`` of the mix's length law."""
    m, s = _lognormal(law)
    return math.exp(m + s * statistics.NormalDist().inv_cdf(q))


def rest_bases_at(q: float, law: dict) -> float:
    """Bases left of a read in flight at quantile ``q`` of the equilibrium
    law: ``F(r) = E[min(L, r)] / E[L]``, which for a log-normal ``L`` is
    ``Phi((ln r - m) / s - s) + r P(L > r) / E[L]``, solved by bisection
    in ``ln r``."""
    m, s = _lognormal(law)
    phi = statistics.NormalDist().cdf
    mean = math.exp(m + s * s / 2)

    def cdf(x):                                  # x = ln r
        z = (x - m) / s
        return phi(z - s) + math.exp(x) * (1.0 - phi(z)) / mean

    lo, hi = m - 12 * s, m + 12 * s
    for _ in range(100):
        mid = (lo + hi) / 2
        lo, hi = (mid, hi) if cdf(mid) < q else (lo, mid)
    return math.exp((lo + hi) / 2)


class ReadSignal:
    """One read's raw signal of ``n_samples`` samples, rendered lazily
    block by block: ``take(a, b)`` returns samples ``[a, b)``, ``full()``
    all of them."""

    def __init__(self, channel: Channel, rng: np.random.Generator,
                 n_samples: int):
        self.ch = channel
        self.rng = rng
        self.length = int(n_samples)
        self._ctx = np.zeros((channel.kmer - 1,), np.int64)
        self._parts = []
        self._n = 0
        self._buf: Optional[np.ndarray] = None

    def _render_block(self) -> None:
        ch, rng = self.ch, self.rng
        seq = rng.integers(0, N_BASES, BLOCK_BASES)
        full = np.concatenate([self._ctx, seq])
        ids = np.zeros((BLOCK_BASES,), np.int64)
        for i in range(ch.kmer):             # base-4 rolling k-mer id
            ids += full[i: i + BLOCK_BASES] * (N_BASES ** i)
        self._ctx = full[-(ch.kmer - 1):]
        levels = ch.table[ids]
        dwell = 1 + np.clip(rng.poisson(ch.samples_per_base - 1.0,
                                        BLOCK_BASES), 0, ch.max_dwell)
        raw = np.repeat(levels, dwell)
        raw += np.float32(ch.noise_std) * rng.standard_normal(
            raw.shape[0], dtype=np.float32)
        self._parts.append(raw * ch.scale)
        self._n += raw.shape[0]
        self._buf = None

    def _samples(self) -> np.ndarray:
        if self._buf is None:
            self._buf = (np.concatenate(self._parts) if self._parts
                         else np.zeros((0,), np.float32))
            self._parts = [self._buf]
        return self._buf

    def take(self, a: int, b: int) -> np.ndarray:
        b = min(b, self.length)
        while self._n < b:
            self._render_block()
        return self._samples()[a:b]

    def full(self) -> np.ndarray:
        return self.take(0, self.length)


class Traffic:
    """Reads and draws of one mix under one seed.  The first
    ``in_flight`` reads of stream 0 are the rests of reads in flight."""

    def __init__(self, mix: dict, samples_per_base: float, seed: int,
                 in_flight: int = 0):
        self.mix = mix
        self.seed = int(seed)
        self.in_flight = int(in_flight)
        self.channel = Channel(mix["pore"], samples_per_base)

    def rng(self, stream: int, i: int) -> np.random.Generator:
        return np.random.default_rng([self.seed, stream, i])

    def quantile(self, stream: int, i: int, block: int, what: int) -> float:
        """The stratified quantile of item ``i`` of ``stream``: its place
        in a seeded permutation of its block, at the place's midpoint."""
        b, j = divmod(i, block)
        perm = np.random.default_rng(
            [self.seed, stream, AUX, what, b]).permutation(block)
        return (perm[j] + 0.5) / block

    def bases(self, stream: int, i: int) -> float:
        """Length in bases of read ``i`` of ``stream``."""
        law = self.mix["read_bases"]
        if stream == 0 and i < self.in_flight:
            return rest_bases_at(self.quantile(0, i, self.in_flight, 1), law)
        k = i - self.in_flight if stream == 0 else i
        return read_bases_at(self.quantile(stream, k, int(law["block"]), 0),
                             law)

    def read(self, stream: int, i: int) -> ReadSignal:
        """Read ``i`` of ``stream``."""
        n = round(self.bases(stream, i) * self.channel.samples_per_base)
        return ReadSignal(self.channel, self.rng(stream, i),
                          min(max(1, n), int(self.mix["max_samples"])))

    def sample_times(self, n: int, seconds: float) -> np.ndarray:
        """``n`` sorted times in [0, seconds) from which steps are sampled
        for the output check: the window's first step, and ``n - 1``
        drawn from the seed."""
        if n <= 0:
            return np.zeros((0,))
        return np.sort(np.concatenate(
            [[0.0], self.rng(AUX, 0).uniform(0.0, seconds, n - 1)]))
