"""Record the small v5e trace that ``test_chipbench_trace.py`` reads.

    python3 benchmarks/chip/tests/data/record_trace.py <out_dir>

On the chip: three ``bench:batch`` spans of five matmuls each, each batch
followed by a 50 ms ``bench:sleep`` span in which the device idles, all
inside one ``bench:window`` span.  Copy the ``.xplane.pb`` it writes to
``v5e_small.xplane.pb`` beside this file.
"""

import sys
import time

import jax
import jax.numpy as jnp
from jax.profiler import TraceAnnotation


def main(out: str) -> None:
    if jax.devices()[0].platform != "tpu":
        sys.exit("record_trace.py: no TPU")
    step = jax.jit(lambda x: jnp.tanh(x @ x) * 0.5)
    x = jnp.ones((2048, 2048), jnp.float32)
    step(x).block_until_ready()
    jax.profiler.start_trace(out)
    with TraceAnnotation("bench:window"):
        for _ in range(3):
            with TraceAnnotation("bench:batch"):
                for _ in range(5):
                    x = step(x)
                x.block_until_ready()
            with TraceAnnotation("bench:sleep"):
                time.sleep(0.05)
    jax.profiler.stop_trace()


if __name__ == "__main__":
    main(sys.argv[1])
