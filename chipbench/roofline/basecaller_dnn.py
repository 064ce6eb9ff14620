"""Operations of the basecaller DNN per window, from the config's shapes,
split by the precision the program runs each product at.

Counts multiply-accumulates as the published structure needs them, two
operations each:

* ``conv``: every conv output frame's ``kernel x Cin x Cout`` products,
  run as a float convolution at the default precision (one bfloat16
  pass on a TPU);
* ``int8``: per GRU layer and frame the three gates' input products
  ``3 F H``, and the FC head's ``H x classes``: the packed projections,
  int8 codes on the MXU (``quant_matmul``);
* ``f32_highest``: per GRU layer and frame the three gates' hidden
  products ``3 H H``, float32 at ``Precision.HIGHEST`` (``gru_seq``).

Activation functions, quantization and the decode are not counted.  At
guppy's widths this is 41.7 M MACs per window.
"""

PEAK_OF = {"conv": "bf16_flops", "int8": "int8_ops",
           "f32_highest": "f32_highest_flops"}


def frames(cfg: dict) -> int:
    t = cfg["input_len"]
    for c in cfg["conv"]:
        t = -(-t // c["stride"])
    return t


def macs_by_precision(cfg: dict) -> dict:
    t, cin, conv = cfg["input_len"], cfg["in_channels"], 0
    for c in cfg["conv"]:
        t = -(-t // c["stride"])
        conv += t * c["kernel"] * cin * c["channels"]
        cin = c["channels"]
    g = 3 if cfg["rnn_type"] == "gru" else 4
    H = cfg["rnn_hidden"]
    proj = sum(t * g * (cin if i == 0 else H) * H
               for i in range(cfg["rnn_layers"]))
    hidden = cfg["rnn_layers"] * t * g * H * H
    fc = t * H * cfg["n_classes"]
    return {"conv": conv, "int8": proj + fc, "f32_highest": hidden}


def macs_per_window(cfg: dict) -> int:
    return sum(macs_by_precision(cfg).values())


def ops_per_window(cfg: dict) -> int:
    return 2 * macs_per_window(cfg)


def seconds_at_peak(cfg: dict, peaks: dict) -> float:
    """Seconds one window's products take at the chip's peak for the
    precision each runs at."""
    return sum(2 * m / peaks[PEAK_OF[k]]
               for k, m in macs_by_precision(cfg).items())
