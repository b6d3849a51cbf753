"""Record the small chip trace with named scopes that test_layers.py reduces.

    python3 chipbench/tests/record_scopes.py OUT.xplane.pb

On a TPU: three ``engine.step`` spans inside a ``window`` span. Each runs
the program's host phases as ``repro.serving.tracing`` spans: a jitted
``decode_step`` (an ``embed`` scope, then a ``lax.scan`` over four layers
with an ``attention`` and a ``moe/ffn`` scope, then ``unembed``) launched
in ``step.launch`` and waited for in ``step.sync``, then 4 ms of host work
in ``step.observe`` around a 1 ms ``step.migrate``, and 1 ms each in
``step.schedule`` and ``step.finish``; a ``client.readback`` and a 5 ms
``idle.wait`` follow. Prints the reduction, for the test's expectations.
"""

import shutil
import sys
import tempfile
import time
from pathlib import Path

import jax
import jax.numpy as jnp

ROOT = Path(__file__).resolve().parents[2]
for p in (str(ROOT / "src"), str(ROOT)):
    sys.path.insert(0, p)
from chipbench.layers import reduce_layers  # noqa: E402
from chipbench.trace import find_trace, reduce_trace  # noqa: E402
from repro.serving import tracing  # noqa: E402


def decode_step(x, w):
    def layer(h, wl):
        with jax.named_scope("attention"):
            h = h + jnp.tanh(h @ wl)
        with jax.named_scope("moe"), jax.named_scope("ffn"):
            h = h + jax.nn.silu(h @ wl.T)
        return h, None

    with jax.named_scope("embed"):
        x = x * 2
    x, _ = jax.lax.scan(layer, x, w)
    with jax.named_scope("unembed"):
        return (x @ w[0]).astype(jnp.float32)


def main(out: str) -> int:
    f = jax.jit(decode_step)
    x = jnp.ones((512, 1024), jnp.bfloat16)
    w = jnp.full((4, 1024, 1024), 1e-3, jnp.bfloat16)
    f(x, w).block_until_ready()
    tmp = Path(tempfile.mkdtemp(dir=Path(out).parent))
    jax.profiler.start_trace(str(tmp))
    tracing.enable(True)
    with jax.profiler.TraceAnnotation("window"):
        for _ in range(3):
            with jax.profiler.TraceAnnotation("engine.step"):
                with tracing.span("step.schedule", kind="decode"):
                    time.sleep(0.001)
                with tracing.span("step.launch", kind="decode"):
                    y = f(x, w)
                with tracing.span("step.sync", kind="decode"):
                    y.block_until_ready()
                with tracing.span("step.observe", kind="decode"):
                    time.sleep(0.002)
                    with tracing.span("step.migrate", kind="decode",
                                      slots=2, bytes=4096):
                        time.sleep(0.001)
                    time.sleep(0.001)
                with tracing.span("step.finish", kind="decode"):
                    time.sleep(0.001)
            with jax.profiler.TraceAnnotation("client.readback"):
                float(y[0, 0])
            with jax.profiler.TraceAnnotation("idle.wait"):
                time.sleep(0.005)
    tracing.enable(False)
    jax.profiler.stop_trace()
    path = find_trace(tmp)
    shutil.copy(path, out)
    shutil.rmtree(tmp)
    print(reduce_trace(Path(out)))
    print(reduce_layers(Path(out)))
    return 0


if __name__ == "__main__":
    raise SystemExit(main(sys.argv[1]))
