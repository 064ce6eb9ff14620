#!/usr/bin/env python3
"""Record the small chip trace the trace-reduction test reads.

    python3 chipbench/tests/data/record_tiny_trace.py   # on a TPU

One decode step at scrappie's widths (guppy's configuration with conv
stride 5, GRU width 64, reads of at most 60 bases) at 8 lanes, inside a
``cb:engine_step`` annotation and followed by a 2 ms ``cb:vote`` one,
traced with the profiler options ``harness.run_cell`` uses; writes
``tiny_trace.xplane.pb.gz`` beside this file.
"""
import gzip
import shutil
import sys
import tempfile
import time
from pathlib import Path

HERE = Path(__file__).resolve().parent
BENCH = HERE.parents[1]
sys.path.insert(0, str(BENCH))
sys.path.insert(0, str(BENCH.parent / "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

import harness  # noqa: E402
import xplane  # noqa: E402


def main() -> None:
    cfg = dict(harness.load_json(BENCH / "configs" / "guppy.json"),
               name="scrappie", rnn_hidden=64, max_read_len=60,
               conv=[{"kernel": 11, "channels": 96, "stride": 5}])
    pipe = harness.build_pipeline(cfg, "pallas")
    params = pipe.serving_params(harness.make_params(cfg, 5))
    rng = np.random.default_rng(0)
    batch = jnp.asarray(rng.standard_normal((8, 300, 1)).astype(np.float32))
    frames = jnp.full((8,), 60, jnp.int32)
    for _ in range(2):                                  # compile, warm
        [np.asarray(x) for x in pipe._decode_windows(params, batch, frames)]
    (BENCH / "_work").mkdir(exist_ok=True)
    out = Path(tempfile.mkdtemp(dir=BENCH / "_work"))
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 1
    jax.profiler.start_trace(str(out), profiler_options=opts)
    with jax.profiler.TraceAnnotation("cb:engine_step"):
        [np.asarray(x) for x in pipe._decode_windows(params, batch, frames)]
    with jax.profiler.TraceAnnotation("cb:vote"):
        time.sleep(0.002)
    jax.profiler.stop_trace()
    with open(xplane.find_xplane(str(out)), "rb") as f, \
            gzip.open(HERE / "tiny_trace.xplane.pb.gz", "wb") as g:
        g.write(f.read())
    shutil.rmtree(out)


if __name__ == "__main__":
    main()
