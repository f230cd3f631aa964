"""Record the small device trace that ``test_trace.py`` reduces.

    python3 bench/tests/record_trace_fixture.py bench/tests/fixtures/trace

Run on the chip. The traced program is the serving decode kernel at a toy
shape, four times inside a jitted loop, called twice with a host
``bench.sleep`` span of 50 ms between the calls, so the trace holds a
while loop with the kernel's events in it, device ops outside the loop,
and an idle gap the host span covers. What the test expects of it is
written beside it in ``expect.json``.
"""
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

from repro.kernels.decode_step import decode_linear_attention  # noqa: E402

LOOP, CALLS, SLEEP_S = 4, 2, 0.05


@jax.jit
def prog(qf, kf, v, s, z, active):
    def body(_, c):
        s, z, acc = c
        y, s, z = decode_linear_attention(qf, kf, v, s, z, active)
        return s, z, acc + jnp.sum(y.astype(jnp.float32))
    s, z, acc = jax.lax.fori_loop(0, LOOP, body, (s, z, 0.0))
    return s * 0.5, z, acc


def main(out_dir: str):
    if jax.devices()[0].platform != "tpu":
        sys.exit("record the fixture on the chip")
    k = jax.random.split(jax.random.PRNGKey(0), 5)
    rows, m, dv = 16, 128, 128
    args = (jax.random.uniform(k[0], (rows, m), jnp.float32).astype(
                jnp.bfloat16),
            jax.random.uniform(k[1], (rows, m), jnp.float32).astype(
                jnp.bfloat16),
            jax.random.normal(k[2], (rows, dv), jnp.bfloat16),
            jnp.zeros((rows, m, dv), jnp.float32),
            jnp.zeros((rows, m), jnp.float32),
            (jnp.arange(rows) % 2).astype(jnp.int32))
    jax.block_until_ready(prog(*args))
    jax.profiler.start_trace(out_dir)
    t0 = time.perf_counter()
    for i in range(CALLS):
        if i:
            with jax.profiler.TraceAnnotation("bench.sleep"):
                time.sleep(SLEEP_S)
        with jax.profiler.TraceAnnotation("bench.step"):
            jax.block_until_ready(prog(*args))
    window = time.perf_counter() - t0
    jax.profiler.stop_trace()
    with open(os.path.join(out_dir, "expect.json"), "w") as f:
        json.dump({"window_s": window, "kernel_calls": LOOP * CALLS,
                   "sleep_s": SLEEP_S}, f)


if __name__ == "__main__":
    main(sys.argv[1])
