"""The comparison that decides ``correct``.

After the window has closed, ``memory_peak_bytes`` has been read and the
program's state is freed, the plain reference (``references/<family>``)
is run over what the timed path produced:

* every lane of the steps ``DecodeTap`` sampled from the seed: the
  reference cuts the lane's window from the benchmark's own signal,
  computes its log-probs and its top beam, and the program's window read
  and score for that lane are held against them:

  - ``reads_differ_pct``: the share of those windows whose served read
    differs from the reference's top beam (%);
  - ``score_gap_p99``: the 99th percentile over the windows of
    ``|program top-beam score - reference top-beam score|`` (nats);

  With random weights the 5-bit network amplifies any rounding
  difference in a few windows, so the widest gaps (``read_gap``,
  ``score_gap``: ``log p(ref read) - log p(program read)`` and the score
  difference, widest over the windows) swing from seed to seed and are
  printed, not compared;

* finished reads: ``stored_read_mismatch`` counts the sampled lanes
  whose read has finished and whose stored window read (the one the
  consensus was voted from, at that window) differs from what the decode
  step gave that lane (exact), so outputs attached to the wrong lane or
  window fail; and the reference votes each read's window reads as the
  program stored them, and ``consensus_mismatch`` counts reads whose
  consensus differs (exact).

Each number has its limit in ``limits/<workload>.json``, set from the
readings that ``PERF.md`` gives.
"""
from __future__ import annotations

import importlib.util
from pathlib import Path

import numpy as np

HERE = Path(__file__).resolve().parent
BLOCK = 512                       # windows per reference call
VOTE_WINDOWS = 10_000             # window reads voted by the reference


_REFERENCES: dict = {}


def reference(family: str):
    """The plain reference ``references/<family>.py``, loaded once per
    process (its jitted functions then compile once)."""
    if family not in _REFERENCES:
        path = HERE / "references" / f"{family}.py"
        spec = importlib.util.spec_from_file_location(f"ref_{family}", path)
        mod = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(mod)
        _REFERENCES[family] = mod
    return _REFERENCES[family]


def lane_items(cfg: dict, captured: list, signal_of) -> dict:
    """Windows, valid lengths and the program's outputs of every sampled
    lane, stacked."""
    window, hop, C = cfg["input_len"], cfg["hop"], cfg["in_channels"]
    wins, valid, reads, lens, scores = [], [], [], [], []
    cache = {}
    for lanes, r, n, s in captured:
        for slot, lane in enumerate(lanes):
            if lane is None:
                continue
            rid, c, v = lane
            if rid not in cache:
                cache[rid] = np.asarray(signal_of(rid), np.float32)
            sig = cache[rid]
            w = np.zeros((window, C), np.float32)
            piece = sig[c * hop: c * hop + v]
            w[: piece.shape[0]] = piece.reshape(piece.shape[0], -1)
            wins.append(w)
            valid.append(v)
            reads.append(r[slot])
            lens.append(n[slot])
            scores.append(s[slot])
    if not wins:
        return {"n": 0}
    return {"n": len(wins), "windows": np.stack(wins),
            "valid": np.asarray(valid), "reads": np.stack(reads),
            "lens": np.asarray(lens), "scores": np.asarray(scores)}


def decode_ref(ref, params, cfg: dict, windows, valid, bits: int,
               precision: str):
    """Reference log-probs' top beam for every window, in blocks."""
    n = windows.shape[0]
    pad = -n % BLOCK                 # one block shape: one compile
    windows = np.concatenate(
        [windows, np.zeros((pad,) + windows.shape[1:], windows.dtype)])
    frames = ref.out_frames(cfg, np.concatenate(
        [valid, np.ones((pad,), valid.dtype)])).astype(np.int32)
    out = {"reads": [], "lens": [], "scores": []}
    lps_all = []
    for a in range(0, windows.shape[0], BLOCK):
        lps = ref.forward(params, windows[a: a + BLOCK], cfg, bits,
                          precision)
        r, k, s = ref.beam_search(lps, frames[a: a + BLOCK],
                                  cfg["beam_width"], cfg["max_read_len"])
        out["reads"].append(np.asarray(r))
        out["lens"].append(np.asarray(k))
        out["scores"].append(np.asarray(s))
        lps_all.append(lps)
    return ({k: np.concatenate(v)[:n] for k, v in out.items()}, lps_all,
            frames[:n])


def gaps(ref, want: dict, got: dict, lps_blocks, frames) -> tuple:
    """(read gaps, score gaps) of ``got`` against the reference ``want``."""
    n = frames.shape[0]

    def block(x, a):
        x = x[a: a + BLOCK]
        return np.concatenate([x, np.zeros((BLOCK - x.shape[0],)
                                           + x.shape[1:], x.dtype)])

    read_gap = []
    for i, lps in enumerate(lps_blocks):
        a = i * BLOCK
        f = block(frames, a)
        ll_want = np.asarray(ref.ctc_loglik(
            lps, f, block(want["reads"], a), block(want["lens"], a)))
        ll_got = np.asarray(ref.ctc_loglik(
            lps, f, block(got["reads"], a), block(got["lens"], a)))
        read_gap.append(ll_want - ll_got)
    score_gap = np.abs(got["scores"] - want["scores"])
    return np.concatenate(read_gap)[:n], score_gap


def vote_sample(finished: list, seed: int) -> list:
    """Finished reads whose consensus the reference re-votes: the longest,
    then others drawn from the seed, up to VOTE_WINDOWS window reads."""
    if not finished:
        return []
    sizes = [len(v.window_lengths) for _, v in finished]
    order = list(np.random.default_rng([seed, 7]).permutation(len(finished)))
    longest = int(np.argmax(sizes))
    order.remove(longest)
    pick, total = [], 0
    for i in [longest] + order:
        if pick and total + sizes[i] > VOTE_WINDOWS:
            continue
        pick.append(finished[i])
        total += sizes[i]
    return pick


def stored_mismatches(captured: list, finished: list) -> tuple:
    """(lanes tied, lanes whose finished read stores another window read
    than the decode step gave the lane)."""
    done = dict(finished)
    tied = bad = 0
    for lanes, r, n, _ in captured:
        for slot, lane in enumerate(lanes):
            if lane is None or lane[0] not in done:
                continue
            v, c = done[lane[0]], lane[1]
            k = int(n[slot])
            tied += 1
            if (c >= len(v.window_lengths) or int(v.window_lengths[c]) != k
                    or not np.array_equal(v.window_reads[c][:k],
                                          r[slot][:k])):
                bad += 1
    return tied, bad


def consensus_mismatches(ref, finished: list) -> int:
    bad = 0
    for _, v in finished:
        want = ref.vote(v.window_reads, v.window_lengths)
        got = np.asarray(v.read[: v.length])
        if want.shape != got.shape or not np.array_equal(want, got):
            bad += 1
    return bad


def compare(cfg: dict, params, run: dict, limits: dict, seed: int,
            control=None) -> dict:
    """The compared numbers, each with its limit.  ``control`` (``bits``
    and/or ``precision``, e.g. ``{"precision": {"matmul": "high"}}``)
    puts the reference, computed so, in the program's place."""
    ref = reference(cfg["reference"])
    bits = cfg["quant"]["bits_a"]
    prec = {k: cfg["precision"][k] for k in ("conv", "matmul")}
    items = lane_items(cfg, run["captured"], run["signal_of"])
    nums = {}
    if items["n"]:
        want, lps, frames = decode_ref(ref, params, cfg, items["windows"],
                                       items["valid"], bits, prec)
        if control is None:
            got = {k: items[k] for k in ("reads", "lens", "scores")}
        else:
            got, _, _ = decode_ref(ref, params, cfg, items["windows"],
                                   items["valid"],
                                   control.get("bits", bits),
                                   dict(prec, **control.get("precision", {})))
        rg, sg = gaps(ref, want, got, lps, frames)
        differ = (np.any(want["reads"] != got["reads"], axis=1)
                  | (want["lens"] != got["lens"]))
        nums["reads_differ_pct"] = 100.0 * float(np.mean(differ))
        nums["score_gap_p99"] = float(np.percentile(sg, 99))
        nums["read_gap"] = float(np.max(rg))
        nums["score_gap"] = float(np.max(sg))
        nums["read_gap_p99"] = float(np.percentile(rg, 99))
        nums["windows_compared"] = items["n"]
    voted = vote_sample(run["finished"], seed)
    if control is None:
        nums["lanes_tied"], nums["stored_read_mismatch"] = stored_mismatches(
            run["captured"], run["finished"])
        nums["consensus_mismatch"] = consensus_mismatches(ref, voted)
    nums["reads_voted"] = len(voted)
    checks = {}
    for name, lim in limits.items():
        v = nums.get(name)
        checks[name] = {"value": v, "limit": lim["limit"]}
    # a run in which no sampled lane's read finished has checked no
    # delivered read: not correct
    correct = (items["n"] > 0 and nums.get("lanes_tied", 1) > 0 and all(
        c["value"] is not None and c["value"] <= c["limit"]
        for c in checks.values()))
    return {"correct": correct, "checks": checks, "numbers": nums}
