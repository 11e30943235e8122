"""Record the small device trace that `test_trace.py` reduces.

    python3 chipbench/tests/record_trace.py <out.xplane.pb>

On a TPU: a jitted matmul chain under a `window` span, three calls under
`train_call` spans with host sleeps between them, so the trace holds
device operations, spans and idle gaps of known length.
"""
import glob
import os
import shutil
import sys
import tempfile
import time

import jax
import jax.numpy as jnp


def main(out: str) -> int:
    f = jax.jit(lambda x: jnp.tanh(x @ x) @ x)
    x = jnp.ones((2048, 2048), jnp.bfloat16)
    f(x).block_until_ready()
    d = tempfile.mkdtemp()
    jax.profiler.start_trace(d)
    with jax.profiler.TraceAnnotation("window"):
        for _ in range(3):
            with jax.profiler.TraceAnnotation("train_call"):
                for _ in range(4):
                    f(x).block_until_ready()
            time.sleep(0.05)
    jax.profiler.stop_trace()
    path, = glob.glob(os.path.join(d, "**", "*.xplane.pb"), recursive=True)
    shutil.copy(path, out)
    shutil.rmtree(d)
    from jax.profiler import ProfileData
    for plane in ProfileData.from_file(out).planes:
        lines = [(ln.name, sum(1 for _ in ln.events)) for ln in plane.lines]
        print(plane.name, lines[:12])
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1]))
