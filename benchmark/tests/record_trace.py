#!/usr/bin/env python3
"""Record the small chip trace the trace_reduce self-test reads:

    python3 benchmark/tests/record_trace.py <out.xplane.pb>

A few device programs, some of them behind host sleeps (idle gaps the
benchmark's host spans `create` and `feed` cover), inside a `traced` span.
Run on the chip once; the file is committed under benchmark/testdata/.
"""

import glob
import shutil
import sys
import tempfile
import time


def main(out: str) -> int:
    import jax
    import jax.numpy as jnp
    from jax.profiler import TraceAnnotation

    f = jax.jit(lambda x: jnp.tanh(x @ x).sum())
    g = jax.jit(lambda x: (x * 2.0 + 1.0).max())
    x = jnp.ones((1024, 1024), jnp.float32)
    f(x).block_until_ready()
    g(x).block_until_ready()
    tmp = tempfile.mkdtemp()
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 1
    jax.profiler.start_trace(tmp, profiler_options=opts)
    with TraceAnnotation("traced"):
        for i in range(4):
            with TraceAnnotation("create"):
                time.sleep(0.004)
            f(x).block_until_ready()
            with TraceAnnotation("feed"):
                time.sleep(0.002)
            g(x).block_until_ready()
    jax.profiler.stop_trace()
    path = glob.glob(f"{tmp}/**/*.xplane.pb", recursive=True)[0]
    shutil.copy(path, out)
    shutil.rmtree(tmp, ignore_errors=True)
    print(f"wrote {out}")
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1]))
