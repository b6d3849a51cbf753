"""Record the small chip trace that test_trace.py reduces.

    python3 chipbench/tests/record_trace.py OUT.xplane.pb

On a TPU: three ``engine.step`` spans, each running a jitted matmul chain
on the device, with a ``client.readback`` and an ``idle.wait`` span between
them, all inside a ``window`` span. Prints the plane and line names and
the reduction, for the test's expectations.
"""

import shutil
import sys
import tempfile
import time
from pathlib import Path

import jax
import jax.numpy as jnp

sys.path.insert(0, str(Path(__file__).resolve().parents[2]))
from chipbench.trace import find_trace, reduce_trace  # noqa: E402


def main(out: str) -> int:
    f = jax.jit(lambda x: jnp.tanh(x @ x) @ x)
    x = jnp.ones((2048, 2048), jnp.bfloat16)
    f(x).block_until_ready()
    tmp = Path(tempfile.mkdtemp(dir=Path(out).parent))
    jax.profiler.start_trace(str(tmp))
    with jax.profiler.TraceAnnotation("window"):
        for _ in range(3):
            with jax.profiler.TraceAnnotation("engine.step"):
                y = f(x)
                y.block_until_ready()
            with jax.profiler.TraceAnnotation("client.readback"):
                float(y[0, 0])
            with jax.profiler.TraceAnnotation("idle.wait"):
                time.sleep(0.005)
    jax.profiler.stop_trace()
    path = find_trace(tmp)
    shutil.copy(path, out)
    shutil.rmtree(tmp)
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(out)
    for plane in pd.planes:
        print(plane.name, [(l.name, sum(1 for _ in l.events))
                           for l in plane.lines])
    print(reduce_trace(Path(out)))
    return 0


if __name__ == "__main__":
    raise SystemExit(main(sys.argv[1]))
