"""Record the small TPU trace that ``tests/test_front_phases.py`` gives
to ``front_phases.py``, and the turn records that go with it.

    python3 chipbench/tests/record_front_trace.py <out dir>     (on the chip)

As ``record_host_trace.py``: the engine's own tracer
(``production_stack_tpu/engine/tracing.py``) walks one thread through
four turns of named phases around a jitted program, with the device
left idle under ``build``, ``commit`` and ``emit``.  A second thread
stands in for the server's event loop and uses the tracer's own
``FrontClock`` as the server does inside a slice: it binds it, and
then delivers (``server.stream_token``), consumes (``server.consume``)
and writes (``server.write``) in turns, working in each and parked
between them,
so that part of the device's idle falls beside a busy front and part
beside a parked one.  The slice is taken with the server's own options
(no Python frames).  Writes ``small_tpu_front.xplane.pb`` and
``small_tpu_front.steps.json``."""

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


def _work(seconds: float) -> None:
    """Holds the interpreter as a consumer's Python does."""
    until = time.perf_counter() + seconds
    while time.perf_counter() < until:
        pass


async def _socket_write() -> None:
    _work(0.0005)


def main(out: str) -> None:
    x = jnp.ones((2048, 2048), jnp.bfloat16)
    _small_matmul(x).block_until_ready()
    annotate = jax.profiler.TraceAnnotation
    tracer = EngineTracer(annotate=annotate)
    front = tracer.front
    bound, stop = threading.Event(), threading.Event()

    def event_loop():
        front.bind()
        bound.set()
        while not stop.is_set():
            front.annotate = annotate
            with annotate("server.stream_token"):
                _work(0.0003)
            front.consume_begin()
            _work(0.001)
            front.wake_done(8)
            try:
                front.write(_socket_write()).send(None)
            except StopIteration:
                pass
            time.sleep(0.002)

    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    tmp = os.path.join(out, "tmp_profile")
    jax.profiler.start_trace(tmp, profiler_options=options)
    loop = threading.Thread(target=event_loop)
    loop.start()
    bound.wait()
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
    loop.join()
    jax.profiler.stop_trace()
    path = glob.glob(os.path.join(tmp, "plugins", "profile", "*",
                                  "*.xplane.pb"))[0]
    shutil.copy(path, os.path.join(out, "small_tpu_front.xplane.pb"))
    shutil.rmtree(tmp)
    with open(os.path.join(out, "small_tpu_front.steps.json"), "w") as f:
        json.dump(tracer.recent_steps(), f)
    print(jax.devices()[0].device_kind, os.path.getsize(
        os.path.join(out, "small_tpu_front.xplane.pb")))


if __name__ == "__main__":
    os.makedirs(sys.argv[1], exist_ok=True)
    main(sys.argv[1])
