"""Record the small TPU trace with names in it that ``test_scopes.py``
reads.

    python3 chipbench/tests/record_scopes.py <out dir>     (on the chip)

One jitted program, ``_scoped_program``, run three times with a host
pause between: a matrix product under ``jax.named_scope("scope_matmul")``,
a ``lax.fori_loop`` of ``LOOP_TRIPS`` trips whose body multiplies under
``jax.named_scope("scope_loop_body")``, a Pallas kernel given the
``name`` ``named_scale_kernel`` under ``jax.named_scope("scope_kernel")``,
and a reduction under no scope of its own.  The kernel is an
elementwise pass over ``KERNEL_SHAPE`` float32 (4 GiB in, 4 GiB out),
sized to take some 12 ms so that a dispatch (0.7 ms, measured here too)
is small beside it.

Before the trace the same kernel is timed alone, jitted by itself:
``ALONE_CALLS`` calls, each ended by ``block_until_ready``, on the host
clock; and in the same way a jitted addition of eight numbers, which
is the price of a dispatch and its wait.  The numbers go to ``small_tpu_scopes.json`` beside the trace
(device kind, the kernel's seconds alone, its shape), which the test
compares with the kernel's seconds in the trace.
"""

import glob
import json
import os
import shutil
import sys
import time

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

KERNEL_SHAPE = (65536, 16384)
KERNEL_BLOCK = (256, 2048)
KERNEL_NAME = "named_scale_kernel"
MATMUL_N = 2048
LOOP_TRIPS = 8
RUNS = 3
ALONE_CALLS = 10


def _scale_kernel(x_ref, o_ref):
    o_ref[...] = x_ref[...] * 2.0 + 1.0


def named_kernel(x, interpret=False):
    rows, cols = KERNEL_BLOCK
    spec = pl.BlockSpec(KERNEL_BLOCK, lambda i, j: (i, j))
    return pl.pallas_call(
        _scale_kernel, out_shape=jax.ShapeDtypeStruct(x.shape, x.dtype),
        grid=(x.shape[0] // rows, x.shape[1] // cols),
        in_specs=[spec], out_specs=spec, name=KERNEL_NAME,
        interpret=interpret)(x)


def scoped_program(a, big, interpret=False):
    with jax.named_scope("scope_matmul"):
        b = a @ a

    def body(_, c):
        with jax.named_scope("scope_loop_body"):
            return (c @ a) * 0.01

    c = jax.lax.fori_loop(0, LOOP_TRIPS, body, b)
    with jax.named_scope("scope_kernel"):
        scaled = named_kernel(big, interpret)
    return jnp.sum(c.astype(jnp.float32)) + jnp.sum(scaled[:8])


_scoped_program = jax.jit(scoped_program)
_kernel_alone = jax.jit(named_kernel)
_nothing_alone = jax.jit(lambda x: x + 1.0)


def timed_alone(fn, x) -> list:
    fn(x).block_until_ready()
    seconds = []
    for _ in range(ALONE_CALLS):
        t = time.perf_counter()
        fn(x).block_until_ready()
        seconds.append(time.perf_counter() - t)
    return sorted(seconds)


def main(out: str) -> None:
    a = jnp.full((MATMUL_N, MATMUL_N), 0.01, jnp.bfloat16)
    big = jnp.ones(KERNEL_SHAPE, jnp.float32)
    _scoped_program(a, big).block_until_ready()
    alone = timed_alone(_kernel_alone, big)
    dispatch = timed_alone(_nothing_alone, jnp.ones(8, jnp.float32))
    tmp = os.path.join(out, "tmp_profile")
    jax.profiler.start_trace(tmp)
    for _ in range(RUNS):
        _scoped_program(a, big).block_until_ready()
        time.sleep(0.01)
    jax.profiler.stop_trace()
    path = glob.glob(os.path.join(tmp, "plugins", "profile", "*",
                                  "*.xplane.pb"))[0]
    trace = os.path.join(out, "small_tpu_scopes.xplane.pb")
    shutil.copy(path, trace)
    shutil.rmtree(tmp)
    facts = {"device_kind": jax.devices()[0].device_kind,
             "jax": jax.__version__,
             "kernel_alone_s": alone, "dispatch_alone_s": dispatch,
             "kernel_shape": list(KERNEL_SHAPE), "kernel_dtype": "float32",
             "runs": RUNS, "loop_trips": LOOP_TRIPS,
             "trace_bytes": os.path.getsize(trace)}
    with open(os.path.join(out, "small_tpu_scopes.json"), "w") as f:
        json.dump(facts, f, indent=1)
    print(json.dumps(facts))


if __name__ == "__main__":
    os.makedirs(sys.argv[1], exist_ok=True)
    main(sys.argv[1])
