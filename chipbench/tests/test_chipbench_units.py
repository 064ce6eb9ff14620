"""CPU tests of the benchmark's yardstick: traffic laws, operation and
byte counts, the trace reduction, and the refusal off a TPU."""
import collections
import hashlib
import json
import math
import os
import subprocess
import sys
from pathlib import Path

import numpy as np
import pytest

import chipbench_tiny  # noqa: F401  (puts chipbench/ on the path)
import harness
import xplane
from roofline import basecaller_dnn, beam_strip, gru_seq
import traffic
from traffic import Traffic

ROOT = chipbench_tiny.ROOT
DATA = Path(__file__).resolve().parent / "data"


def cfg(name):
    return harness.load_json(harness.HERE / "configs" / f"{name}.json")


def mix(name):
    return harness.load_json(harness.HERE / "traffic" / f"{name}.json")


# -- operations and bytes ----------------------------------------------------

def test_dnn_macs_match_hand_counts():
    # guppy: 150 frames; conv 150*11*1*96; five GRU layers of
    # 150*3*(96*96 + 96*96); FC 150*96*5
    g = 150 * 11 * 96 + 5 * 150 * 3 * (96 * 96 + 96 * 96) + 150 * 96 * 5
    assert g == 41_702_400
    assert basecaller_dnn.macs_per_window(cfg("guppy")) == g
    assert basecaller_dnn.ops_per_window(cfg("guppy")) == 2 * g
    by = basecaller_dnn.macs_by_precision(cfg("guppy"))
    assert by == {"conv": 150 * 11 * 96,
                  "int8": 5 * 150 * 3 * 96 * 96 + 150 * 96 * 5,
                  "f32_highest": 5 * 150 * 3 * 96 * 96}
    # scrappie's widths: stride 5 gives 60 frames, H = 64, the first layer
    # fed by 96 conv channels
    scrappie = dict(cfg("guppy"), rnn_hidden=64,
                    conv=[{"kernel": 11, "channels": 96, "stride": 5}])
    s = (60 * 11 * 96 + 60 * 3 * (96 * 64 + 64 * 64)
         + 4 * 60 * 3 * (64 * 64 + 64 * 64) + 60 * 64 * 5)
    assert basecaller_dnn.macs_per_window(scrappie) == s
    assert basecaller_dnn.frames(scrappie) == 60


def test_seconds_at_peak_by_precision():
    peaks = {"bf16_flops": 2.0, "int8_ops": 4.0, "f32_highest_flops": 1.0}
    c = cfg("guppy")
    by = basecaller_dnn.macs_by_precision(c)
    want = 2 * by["conv"] / 2.0 + 2 * by["int8"] / 4.0 + 2 * by["f32_highest"]
    assert math.isclose(basecaller_dnn.seconds_at_peak(c, peaks), want)


# -- model families -----------------------------------------------------------

def leaf_digest(tree) -> str:
    import jax
    h = hashlib.sha256()
    for path, leaf in jax.tree_util.tree_flatten_with_path(tree)[0]:
        a = np.asarray(leaf)
        for part in (jax.tree_util.keystr(path), str(a.shape), a.dtype.str):
            h.update(part.encode())
        h.update(a.tobytes())
    return h.hexdigest()


@pytest.mark.parametrize("seed,digest", [
    (7, "4341bd3312018457ffd9bfe353ecfdc01ebbf0f8619b5d1a77c7b776305067de"),
    (2 ** 33 + 12345,
     "1f632e854803f79c41c94faecdeababce3921785311706a085ac338befd9d1c0"),
])
def test_guppy_weights_are_drawn_as_before(seed, digest):
    """Digests of guppy's weights on the CPU, recorded when the harness
    itself still drew them: its family draws the same bits."""
    assert leaf_digest(harness.make_params(cfg("guppy"), seed)) == digest


def test_guppy_window_counts_as_before():
    c = cfg("guppy")
    assert harness.family_of(c).macs_by_precision(c) == {
        "conv": 158_400, "int8": 20_808_000, "f32_highest": 20_736_000}
    v5e = harness.load_json(harness.HERE / "peaks.json")["TPU v5 lite"]
    assert math.isclose(basecaller_dnn.seconds_at_peak(c, v5e),
                        1.3706206741306121e-06, rel_tol=1e-12)


def cell_with_config(monkeypatch, **changes):
    """``guppy.flowcell`` with its configuration changed as it is read."""
    real = harness.load_json

    def load(path):
        d = real(path)
        return dict(d, **changes) if Path(path).parent.name == "configs" \
            else d
    monkeypatch.setattr(harness, "load_json", load)
    return harness.Cell(json.loads((ROOT / "BENCHMARK.json").read_text()),
                        "guppy.flowcell")


@pytest.mark.parametrize("changes,what", [
    ({"rnn_direction": "bidi"}, "rnn_direction 'bidi'"),
    ({"rnn_type": "lstm"}, "rnn_type 'lstm'"),
    ({"conv": [{"kernel": 11, "channels": 96, "stride": 2,
                "shortcut": 1}]}, "conv[0]"),
])
def test_conv_gru_ctc_refuses_what_its_reference_cannot_describe(
        monkeypatch, changes, what):
    with pytest.raises(ValueError) as e:
        cell_with_config(monkeypatch, **changes)
    msg = str(e.value)
    assert what in msg
    assert "families/<name>.py" in msg and "references/<name>.py" in msg


def test_cell_refuses_a_config_whose_family_is_missing(monkeypatch):
    with pytest.raises(ValueError) as e:
        cell_with_config(monkeypatch, reference="chiron")
    msg = str(e.value)
    assert "chipbench/families/chiron.py" in msg
    assert "chipbench/references/chiron.py" in msg


def test_tiny_cut_of_guppy_is_the_one_the_tiny_runs_had():
    real = cfg("guppy")
    assert harness.family_of(real).tiny(real) == dict(
        real, input_len=120, hop=60,
        conv=[{"kernel": 11, "channels": 8, "stride": 2}],
        rnn_layers=2, rnn_hidden=8, max_read_len=60)
    assert chipbench_tiny.TinyCell("guppy", "flowcell").cfg \
        == harness.family_of(real).tiny(real)


def test_gru_seq_cost_by_hand():
    ops, nbytes = gru_seq.cost(150, 256, 96)
    assert ops == 2 * 150 * 256 * 3 * 96 * 96
    assert nbytes == 4 * (150 * 256 * 288 + 150 * 256 * 96 + 96 * 288
                          + 288 + 256 * 96)


def test_beam_strip_cost_by_hand():
    ops, nbytes = beam_strip.cost(8, 256, 5, 5)
    assert ops == 8 * 256 * 25 * 25
    assert nbytes == 4 * (8 * 256 * 5 + 8 * 256 + 8 * 256 * 5 + 10 * 256 * 5)


# -- traffic -----------------------------------------------------------------

def test_reads_are_deterministic_in_the_seed():
    m = mix("flowcell")
    big = 2 ** 33 + 12345
    a = Traffic(m, 8.9, big).read(0, 3).full()
    b = Traffic(m, 8.9, big).read(0, 3).full()
    c = Traffic(m, 8.9, big + 1).read(0, 3).full()
    assert np.array_equal(a, b)
    assert a.shape != c.shape or not np.array_equal(a, c)
    # rendering in pieces gives the same samples as rendering whole
    sig = Traffic(m, 8.9, big).read(0, 3)
    parts = [sig.take(i, i + 1000) for i in range(0, 5000, 1000)]
    assert np.array_equal(np.concatenate(parts), a[:5000])


def test_read_length_and_dwell_laws():
    m = mix("flowcell")
    t = Traffic(m, 8.9, 99)
    n = np.array([t.bases(0, i) for i in range(3 * 256)])
    assert abs(np.median(n) / 6000 - 1) < 0.01
    assert abs(np.std(np.log(n)) / 0.9 - 1) < 0.05
    # every seed gets the same lengths, block by block, in another order
    blocks = n.reshape(3, 256)
    assert all(np.array_equal(np.sort(b), np.sort(blocks[0])) for b in blocks)
    other = np.array([Traffic(m, 8.9, 98).bases(0, i) for i in range(256)])
    assert np.array_equal(np.sort(other), np.sort(blocks[0]))
    assert not np.array_equal(other, blocks[0])
    # a read's samples are its bases times samples_per_base, capped
    assert t.read(0, 5).full().shape[0] == min(round(n[5] * 8.9),
                                               m["max_samples"])
    sig = t.read(0, 0)
    sig._render_block()                     # one block of bases: the dwell
    assert abs(sig._n / 4096 / 8.9 - 1) < 0.03
    assert abs(float(np.std(sig.take(0, sig._n))) - 1.0) < 0.05


def test_rests_of_reads_in_flight_follow_the_equilibrium_law():
    m = mix("flowcell")
    law = m["read_bases"]
    # Monte Carlo of the law: a read in flight is drawn in proportion to
    # its length (a log-normal with its log-mean raised by sigma**2), the
    # moment uniformly within it
    rng = np.random.default_rng(3)
    s = law["sigma"]
    full = np.exp(np.log(law["median"]) + s * s + s * rng.standard_normal(
        1_000_000))
    rest = full * rng.uniform(size=full.shape)
    for q in (0.02, 0.1, 0.5, 0.9):
        want = np.quantile(rest, q)
        assert abs(traffic.rest_bases_at(q, law) / want - 1) < 0.02
    # the first 256 reads of a steady start are those rests, the same set
    # on every seed; the reads after them follow the length law
    a = Traffic(m, 8.9, 2 ** 33 + 1, in_flight=256)
    b = Traffic(m, 8.9, 5, in_flight=256)
    ra = [a.bases(0, i) for i in range(256)]
    rb = [b.bases(0, i) for i in range(256)]
    assert sorted(ra) == sorted(rb) and ra != rb
    assert math.isclose(min(ra), traffic.rest_bases_at(0.5 / 256, law))
    after = sorted(a.bases(0, 256 + i) for i in range(256))
    assert after == sorted(Traffic(m, 8.9, 1).bases(0, i) for i in range(256))


# -- the trace reduction -------------------------------------------------------

def test_reduce_busy_idle_and_labels():
    ms = 1_000_000
    devices = {"/device:TPU:0": [(0, 2 * ms, "fusion.1"),
                                 (1 * ms, 3 * ms, "gru_seq"),
                                 (5 * ms, 6 * ms, "gru_seq")],
               "/device:TPU:1": [(0, 1 * ms, "fusion.1")]}
    spans = [(3 * ms, 5 * ms, "engine_step"), (3.5 * ms, 4.5 * ms, "vote")]
    s = xplane.reduce(devices, spans, window_s=0.010)
    assert s["devices"] == 2
    assert math.isclose(s["busy_s"], (0.004 + 0.001) / 2)
    assert s["idle_gaps"] == [["vote", 0.002]]
    assert s["op_n"]["gru_seq"] == 1.0
    assert math.isclose(s["op_s"]["gru_seq"], 0.0015)


def test_reduce_recorded_chip_trace():
    """One decode step of scrappie at 8 lanes, traced on a TPU v5e by
    ``data/record_tiny_trace.py``."""
    path = str(DATA / "tiny_trace.xplane.pb.gz")
    assert os.path.getsize(path) < 200_000
    devices, spans, modules = xplane.read_planes(path)
    s = xplane.reduce(devices, spans, window_s=1.0, modules=modules)
    assert s["devices"] == 1
    assert 0 < s["busy_s"] < 1.0
    assert {name for _, _, name in spans} == {"engine_step", "vote"}
    gru, strip = collections.Counter(), collections.Counter()
    for op, n in s["op_n"].items():
        if gru_seq.parse(op):
            gru[gru_seq.parse(op)] += n
        if beam_strip.parse(op):
            strip[beam_strip.parse(op)] += n
    # five layers, the 8 lanes padded to the kernel's 128-row tile
    assert gru == {(60, 128, 64): 5.0}
    assert strip == {(8, 8, 5, 5): 8.0}          # 60 frames in strips of 8
    assert all(lab in ("engine_step", "vote", "host")
               for lab, _ in s["idle_gaps"])
    assert all(len(name) < 200 for name, _ in s["device_ops"])
    # the decode step is the one program in the trace
    (name, n), = s["module_n"].items()
    assert name.startswith("jit_fn(") and n == 1.0
    # its span on the device holds its ops and the gaps between them
    assert s["busy_s"] <= s["module_s"][name] < 1.1 * s["busy_s"]


PEAKS = {"bf16_flops": 1e12, "int8_ops": 2e12, "f32_highest_flops": 1e11}


def readings(windows=2560, steps=10, window_s=2.0, chips=1, module_n=None,
             module_s=None):
    class Rd:
        pass
    Rd.cfg, Rd.peaks, Rd.chips = cfg("guppy"), PEAKS, chips
    Rd.run = {"windows": windows, "steps": steps, "window_s": window_s}
    Rd.trace = {"module_n": module_n or {}, "module_s": module_s or {}}
    return Rd


def test_decode_mfu_reads_the_decode_program():
    mfu = harness.load_reader("decode_mfu.batch")
    rd = readings(module_n={"jit_decode_windows(1)": 10.0,
                            "jit_scan(2)": 3.0},
                  module_s={"jit_decode_windows(1)": 0.5,
                            "jit_scan(2)": 2.0})
    # an execution for every step: the run's windows at peak over the
    # module's whole device time
    ideal = 2560 * basecaller_dnn.seconds_at_peak(rd.cfg, PEAKS)
    assert math.isclose(mfu(rd), 100 * ideal / 0.5)
    # a step more than the trace holds executions of: still a reading,
    # a step's windows at peak over an execution's mean time
    rd.run = dict(rd.run, steps=11)
    assert math.isclose(mfu(rd), 100 * ideal / 11 / (0.5 / 10))


@pytest.mark.parametrize("traced", [9, 10, 11])
def test_decode_mfu_reads_per_execution(traced):
    """One execution fewer or more in the trace than the run's steps
    reads as when they are equal: 50 ms an execution throughout."""
    mfu = harness.load_reader("decode_mfu.batch")
    rd = readings(module_n={"jit_decode_windows(7)": float(traced)},
                  module_s={"jit_decode_windows(7)": 0.05 * traced})
    per_step = 2560 / 10 * basecaller_dnn.seconds_at_peak(rd.cfg, PEAKS)
    assert math.isclose(mfu(rd), 100 * per_step / 0.05)
    # the same per device on four chips sharing the windows
    rd.chips = 4
    assert math.isclose(mfu(rd), 100 * per_step / 4 / 0.05)


def test_decode_mfu_finds_the_decode_program_by_name():
    mfu = harness.load_reader("decode_mfu.batch")
    # another program that runs every step and holds more device time is
    # not the decode program
    rd = readings(module_n={"jit_decode_windows(3)": 10.0,
                            "jit_other_step(4)": 10.0},
                  module_s={"jit_decode_windows(3)": 0.5,
                            "jit_other_step(4)": 5.0})
    ideal = 2560 * basecaller_dnn.seconds_at_peak(rd.cfg, PEAKS)
    assert math.isclose(mfu(rd), 100 * ideal / 0.5)
    # nothing to read: no decode program in the trace, or no window
    assert mfu(readings(module_n={"jit_other_step(4)": 10.0},
                        module_s={"jit_other_step(4)": 5.0})) is None
    rd.run = dict(rd.run, windows=0)
    assert mfu(rd) is None


def test_step_mfu_reads_the_window_on_the_host_clock():
    step = harness.load_reader("step_mfu.batch")
    rd = readings(windows=2560, window_s=2.0)
    at_peak = basecaller_dnn.seconds_at_peak(rd.cfg, PEAKS)
    assert math.isclose(step(rd), 100 * 2560 * at_peak / 2.0)
    rd.chips = 4                             # four chips' seconds
    assert math.isclose(step(rd), 100 * 2560 * at_peak / (2.0 * 4))
    # it needs no trace, and reads below the decode program's share
    rd.trace = None
    assert step(rd) > 0
    rd.trace = {"module_n": {"jit_decode_windows(1)": 10.0},
                "module_s": {"jit_decode_windows(1)": 1.5}}
    assert step(rd) < harness.load_reader("decode_mfu.batch")(rd)
    assert step(readings(windows=0)) is None


# -- the command ---------------------------------------------------------------

def test_run_refuses_a_cpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run(
        [sys.executable, "chipbench/run.py", "--workload", "guppy.flowcell",
         "--seed", "1", "--seconds", "1", "--trace", "0"],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=300)
    assert p.returncode != 0
    assert p.stdout.strip() == ""
    assert "TPU" in p.stderr


def test_benchmark_names_every_file():
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    for w in bench["workloads"]:
        cell = harness.Cell(bench, w["name"])
        assert cell.end_to_end and cell.per_layer
        assert "setup_s" in [m["name"] for m in cell.end_to_end]
    for m in bench["per_layer"]:
        assert callable(harness.load_reader(m["name"]))
    # every cell has a share of the chip's peak that no trace can drop
    for w in bench["workloads"]:
        assert any("mfu" in m["name"] and m["source"] == "host_clock"
                   for m in harness.Cell(bench, w["name"]).per_layer)
    # every configuration names a model family with both of its files
    for c in bench["configs"]:
        assert (ROOT / c["file"]).exists()
        family = json.loads((ROOT / c["file"]).read_text())["reference"]
        for side in ("references", "families"):
            assert (harness.HERE / side / f"{family}.py").exists()
