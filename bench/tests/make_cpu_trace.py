"""Record the small CPU trace the trace-reduction tests read.

    JAX_PLATFORMS=cpu python bench/tests/make_cpu_trace.py

Writes ``bench/tests/data/cpu_trace.xplane.pb``: a ``bench/window`` host span
around two jitted calls, one under a ``proj/reduce0_inf`` named scope, with
a ``bench/sleep`` host span between them that leaves the device idle.
"""

import glob
import pathlib
import shutil
import tempfile
import time

import jax
import jax.numpy as jnp

OUT = pathlib.Path(__file__).resolve().parent / "data" / "cpu_trace.xplane.pb"


def main():
    x = jnp.ones((512, 512), jnp.float32)

    @jax.jit
    def proj_step(x):
        with jax.named_scope("proj/reduce0_inf"):
            v = jnp.max(jnp.abs(x), axis=0)
        return jnp.clip(x, -v, v)

    mm = jax.jit(lambda x: jnp.sin(x @ x))
    jax.block_until_ready((proj_step(x), mm(x)))
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    with tempfile.TemporaryDirectory() as d:
        with jax.profiler.trace(d, profiler_options=opts):
            with jax.profiler.TraceAnnotation("bench/window"):
                jax.block_until_ready(proj_step(x))
                with jax.profiler.TraceAnnotation("bench/sleep"):
                    time.sleep(0.05)
                jax.block_until_ready(mm(x))
        src = glob.glob(f"{d}/**/*.xplane.pb", recursive=True)[0]
        OUT.parent.mkdir(exist_ok=True)
        shutil.copy(src, OUT)
    print(OUT, OUT.stat().st_size)


if __name__ == "__main__":
    main()
