"""Program side of the quantized Conv -> GRU -> FC -> CTC family.

The family of ``guppy`` and ``scrappie`` (Helix, arXiv 2008.03107,
Table 3).  Its plain side is ``references/conv_gru_ctc.py``, which
imports nothing of the program; this file is what the harness asks of
the program for a configuration whose ``reference`` is
``conv_gru_ctc``: its weights, its pipeline, the operations of one
window and its cut to test size.

What the reference covers, and so all this family takes: a chain of
plain convs, each ``{kernel, channels, stride}``, then GRU layers of one
width ``rnn_hidden`` walking forwards (``rnn_direction`` ``uni``) or in
alternating directions (``alt``), each fed by the one before, then the
FC head.  ``validate`` refuses any other configuration.

Operations are counted as multiply-accumulates as the published
structure needs them, two operations each, split by the precision the
program runs each product at (``PEAK_OF`` maps them to keys of
``peaks.json``):

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
from __future__ import annotations

from harness import seed32

PEAK_OF = {"conv": "bf16_flops", "int8": "int8_ops",
           "f32_highest": "f32_highest_flops"}
GATES = 3                          # z, r, n
CONV_KEYS = {"kernel", "channels", "stride"}
DIRECTIONS = ("uni", "alt")


def validate(cfg: dict) -> None:
    """Raise ``ValueError`` unless the reference covers ``cfg``."""
    why = []
    if cfg["rnn_type"] != "gru":
        why.append(f"rnn_type {cfg['rnn_type']!r} (only 'gru')")
    if cfg["rnn_direction"] not in DIRECTIONS:
        why.append(f"rnn_direction {cfg['rnn_direction']!r} (only "
                   f"{' or '.join(map(repr, DIRECTIONS))})")
    for i, c in enumerate(cfg["conv"]):
        if set(c) != CONV_KEYS:
            why.append(f"conv[{i}] with keys {sorted(c)} (only a plain "
                       f"conv {sorted(CONV_KEYS)})")
    if why:
        raise ValueError(
            f"configuration {cfg['name']!r}: the family conv_gru_ctc cannot "
            f"describe {'; '.join(why)}.  A model of another shape is a "
            "family of its own: add chipbench/families/<name>.py (the "
            "program side) and chipbench/references/<name>.py (the plain "
            "side), and name it in the configuration's 'reference'")


def make_params(cfg: dict, seed: int):
    """Float weights of the configuration, drawn on the device from the
    seed in one jitted call (the program packs them to its 5-bit
    artifact itself)."""
    import jax
    import jax.numpy as jnp

    def init(key):
        shapes = []
        cin = cfg["in_channels"]
        for c in cfg["conv"]:
            shapes.append(("conv", (c["kernel"], cin, c["channels"]),
                           c["kernel"] * cin))
            cin = c["channels"]
        H = cfg["rnn_hidden"]
        for i in range(cfg["rnn_layers"]):
            fin = cin if i == 0 else H
            shapes.append(("rnn", (fin, GATES * H), fin))
        keys = iter(jax.random.split(key, 4 * len(shapes) + 4))

        def normal(shape, fan):
            return jax.random.normal(next(keys), shape, jnp.float32) \
                / jnp.sqrt(float(fan))

        def bias(n):
            return 0.1 * jax.random.normal(next(keys), (n,), jnp.float32)

        p = {"conv": [], "rnn": [], "fc": None}
        for kind, shape, fan in shapes:
            if kind == "conv":
                p["conv"].append({"w": normal(shape, fan),
                                  "b": bias(shape[-1])})
            else:
                p["rnn"].append({"w": normal(shape, fan),
                                 "u": normal((H, GATES * H), H),
                                 "b": bias(GATES * H)})
        p["fc"] = {"w": normal((H, cfg["n_classes"]), H),
                   "b": bias(cfg["n_classes"])}
        return p

    return jax.jit(init)(jax.random.PRNGKey(seed32(seed)))


def build_pipeline(cfg: dict, backend: str):
    """The program's pipeline at exactly the configuration's sizes."""
    from repro.core.quant import QuantConfig
    from repro.models import basecaller as bc
    from repro.pipeline import BasecallPipeline, chunking

    q = cfg["quant"]
    mcfg = bc.BasecallerConfig(
        name=cfg["name"], input_len=cfg["input_len"],
        in_channels=cfg["in_channels"],
        conv=tuple(bc.ConvSpec(c["kernel"], c["channels"], c["stride"])
                   for c in cfg["conv"]),
        rnn_type=cfg["rnn_type"], rnn_layers=cfg["rnn_layers"],
        rnn_hidden=cfg["rnn_hidden"], rnn_direction=cfg["rnn_direction"],
        n_classes=cfg["n_classes"],
        quant=QuantConfig(enabled=True, bits_w=q["bits_w"],
                          bits_a=q["bits_a"], per_channel=True))
    return BasecallPipeline(
        mcfg, backend=backend,
        chunk=chunking.ChunkConfig(window=cfg["input_len"], hop=cfg["hop"]),
        beam_width=cfg["beam_width"], max_read_len=cfg["max_read_len"],
        decode_strip=cfg["decode_strip"])


def macs_by_precision(cfg: dict) -> dict:
    """One window's multiply-accumulates, by the precision each runs at."""
    t, cin, conv = cfg["input_len"], cfg["in_channels"], 0
    for c in cfg["conv"]:
        t = -(-t // c["stride"])
        conv += t * c["kernel"] * cin * c["channels"]
        cin = c["channels"]
    H = cfg["rnn_hidden"]
    proj = sum(t * GATES * (cin if i == 0 else H) * H
               for i in range(cfg["rnn_layers"]))
    hidden = cfg["rnn_layers"] * t * GATES * H * H
    fc = t * H * cfg["n_classes"]
    return {"conv": conv, "int8": proj + fc, "f32_highest": hidden}


def tiny(cfg: dict) -> dict:
    """The configuration at a size a CPU test run can hold: one stride-2
    conv and two GRU layers at H=8 over 120-sample windows, with every
    kind of layer the family has."""
    return dict(cfg, input_len=120, hop=60,
                conv=[{"kernel": 11, "channels": 8, "stride": 2}],
                rnn_layers=2, rnn_hidden=8, max_read_len=60)
