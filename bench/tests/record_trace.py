#!/usr/bin/env python3
"""Record ``bench/tests/data/tiny.xplane.pb``, the small trace that
``test_trace.py`` reads (run once, on one TPU chip):

    python3 bench/tests/record_trace.py

Three steps of a tiny program under the harness's host spans: a stage
executable named ``fn`` (a matmul), a Pallas kernel (a ``custom-call``),
and an update named ``adam_update``, with host sleeps between them so the
chip sits idle inside the ``batch`` and ``loss_read`` spans.
"""
import glob
import os
import pathlib
import shutil
import tempfile
import time

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

OUT = pathlib.Path(__file__).resolve().parent / "data" / "tiny.xplane.pb"


def _double(x_ref, o_ref):
    o_ref[...] = x_ref[...] * 2.0


@jax.jit
def fn(x):
    y = jnp.tanh(x @ x)
    return pl.pallas_call(_double, out_shape=jax.ShapeDtypeStruct(
        y.shape, y.dtype), interpret=jax.default_backend() == "cpu")(y)


@jax.jit
def adam_update(x):
    return x * 0.5 + 1.0


def main():
    x = jnp.ones((512, 512), jnp.float32)
    x = adam_update(fn(x)).block_until_ready()
    span = jax.profiler.TraceAnnotation
    d = tempfile.mkdtemp()
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(d, profiler_options=opts)
    with span("window"):
        for _ in range(3):
            with span("batch"):
                time.sleep(0.002)
            with span("executor.step"):
                x = fn(x)
            with span("adam.update"):
                x = adam_update(x)
            x.block_until_ready()
        with span("loss_read"):
            time.sleep(0.001)
            x.block_until_ready()
    jax.profiler.stop_trace()
    (path,) = glob.glob(os.path.join(d, "**", "*.xplane.pb"), recursive=True)
    OUT.parent.mkdir(exist_ok=True)
    shutil.copy(path, OUT)
    shutil.rmtree(d)
    print(f"wrote {OUT} ({OUT.stat().st_size} bytes)")


if __name__ == "__main__":
    main()
