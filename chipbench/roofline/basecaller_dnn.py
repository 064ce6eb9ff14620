"""Operations of the basecaller DNN per window, as the configuration's
model family counts them (``families/<reference>.py``:
``macs_by_precision``, one window's multiply-accumulates split by the
precision the program runs each product at, and ``PEAK_OF``, the key of
``peaks.json`` each precision runs at).  Two operations a
multiply-accumulate."""
import check
import harness


def frames(cfg: dict) -> int:
    """Frames the model emits for one window (the reference's count)."""
    return int(check.reference(cfg["reference"]).out_frames(
        cfg, cfg["input_len"]))


def macs_by_precision(cfg: dict) -> dict:
    return harness.family_of(cfg).macs_by_precision(cfg)


def macs_per_window(cfg: dict) -> int:
    return sum(macs_by_precision(cfg).values())


def ops_per_window(cfg: dict) -> int:
    return 2 * macs_per_window(cfg)


def seconds_at_peak(cfg: dict, peaks: dict) -> float:
    """Seconds one window's products take at the chip's peak for the
    precision each runs at."""
    fam = harness.family_of(cfg)
    return sum(2 * m / peaks[fam.PEAK_OF[k]]
               for k, m in fam.macs_by_precision(cfg).items())
