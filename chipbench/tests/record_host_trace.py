"""Record the small TPU trace that ``tests/test_turn_phases.py`` gives
to ``host_phases.py``, and the turn records that go with it.

    python3 chipbench/tests/record_host_trace.py <out dir>     (on the chip)

The engine's own tracer (``production_stack_tpu/engine/tracing.py``)
walks one thread through four turns of named phases around a jitted
program, with the device left idle under ``build``, ``commit`` and
``emit``, while a second thread delivers ``server.stream_token`` events
as the server's event loop does.  Writes ``small_tpu_host.xplane.pb``
and ``small_tpu_host.steps.json``."""

import glob
import json
import os
import shutil
import sys
import threading
import time

import jax
import jax.numpy as jnp

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))

from production_stack_tpu.engine.tracing import EngineTracer  # noqa: E402


@jax.jit
def _small_matmul(x):
    for _ in range(8):
        x = (x @ x) * 1e-3
    return x


def main(out: str) -> None:
    x = jnp.ones((2048, 2048), jnp.bfloat16)
    _small_matmul(x).block_until_ready()
    tracer = EngineTracer(annotate=jax.profiler.TraceAnnotation)
    stop = threading.Event()

    def stream():
        while not stop.is_set():
            with jax.profiler.TraceAnnotation("server.stream_token"):
                time.sleep(0.001)
            time.sleep(0.001)

    tmp = os.path.join(out, "tmp_profile")
    jax.profiler.start_trace(tmp)
    streamer = threading.Thread(target=stream)
    streamer.start()
    tracer.start_turns()
    for _ in range(4):
        tracer.phase("build")
        time.sleep(0.004)
        tracer.phase("dispatch")
        y = _small_matmul(x)
        tracer.phase("wait")
        y.block_until_ready()
        tracer.phase("commit")
        time.sleep(0.002)
        tracer.on_step(kind="decode", window=8)
        tracer.phase("emit")
        time.sleep(0.006)
        tracer.end_turn(emitted=8)
    stop.set()
    streamer.join()
    jax.profiler.stop_trace()
    path = glob.glob(os.path.join(tmp, "plugins", "profile", "*",
                                  "*.xplane.pb"))[0]
    shutil.copy(path, os.path.join(out, "small_tpu_host.xplane.pb"))
    shutil.rmtree(tmp)
    with open(os.path.join(out, "small_tpu_host.steps.json"), "w") as f:
        json.dump(tracer.recent_steps(), f)
    print(jax.devices()[0].device_kind, os.path.getsize(
        os.path.join(out, "small_tpu_host.xplane.pb")))


if __name__ == "__main__":
    os.makedirs(sys.argv[1], exist_ok=True)
    main(sys.argv[1])
