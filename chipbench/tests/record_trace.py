"""Record the small TPU trace that ``test_reduce.py`` reads.

    python3 chipbench/tests/record_trace.py <out dir>     (on the chip)

Two jitted programs with names of their own, a few executions each with
a host-side pause between them, so that the reduction has busy time,
idle gaps and two programs to tell apart."""

import glob
import os
import shutil
import sys
import time

import jax
import jax.numpy as jnp


@jax.jit
def _small_matmul(x):
    return x @ x


@jax.jit
def _small_sum(x):
    return jnp.sum(x * 2.0, axis=0)


def main(out: str) -> None:
    x = jnp.ones((1024, 1024), jnp.bfloat16)
    _small_matmul(x).block_until_ready()
    _small_sum(x).block_until_ready()
    tmp = os.path.join(out, "tmp_profile")
    jax.profiler.start_trace(tmp)
    for _ in range(3):
        _small_matmul(x).block_until_ready()
        time.sleep(0.01)
        _small_sum(x).block_until_ready()
    jax.profiler.stop_trace()
    path = glob.glob(os.path.join(tmp, "plugins", "profile", "*",
                                  "*.xplane.pb"))[0]
    shutil.copy(path, os.path.join(out, "small_tpu.xplane.pb"))
    shutil.rmtree(tmp)
    print(jax.devices()[0].device_kind, os.path.getsize(
        os.path.join(out, "small_tpu.xplane.pb")))


if __name__ == "__main__":
    os.makedirs(sys.argv[1], exist_ok=True)
    main(sys.argv[1])
