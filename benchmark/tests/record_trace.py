"""Record the small profiler trace that tests/test_trace_reduce.py reads.

    python3 benchmark/tests/record_trace.py <out_dir>

On the chip: three rounds of host work with the device idle (a 50 ms sleep
inside a ``rhs_gen`` span), then a chain of 4096² float32 matmuls inside a
``gssvx`` span, all inside a ``window`` span, traced with the harness's
profiler options.  The newest ``.xplane.pb`` under <out_dir> is the
fixture."""

import sys
import time

import jax
import jax.numpy as jnp


def main(out_dir: str) -> None:
    if jax.devices()[0].platform != "tpu":
        raise SystemExit("the fixture is recorded on a TPU")
    step = jax.jit(lambda a: jnp.tanh(a @ a))
    a = jnp.ones((4096, 4096), jnp.float32) * 1e-3
    step(a).block_until_ready()
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(out_dir, profiler_options=opts)
    with jax.profiler.TraceAnnotation("window"):
        for _ in range(3):
            with jax.profiler.TraceAnnotation("rhs_gen"):
                time.sleep(0.05)
            with jax.profiler.TraceAnnotation("gssvx"):
                x = a
                for _ in range(20):
                    x = step(x)
                x.block_until_ready()
    jax.profiler.stop_trace()


if __name__ == "__main__":
    main(sys.argv[1])
