"""Where the persistent XLA compilation cache lives.

One rule for every entry point that touches JAX (engine server,
chip_smoke.py children, the test session): call
:func:`configure_compile_cache` before the first compile.

- ``JAX_COMPILATION_CACHE_DIR`` set: JAX reads it itself; this module
  sets no directory in code, so whoever placed the cache from outside
  (an operator, a CI machine that keeps it between runs) finds every
  executable there and nowhere else.
- unset: a fixed path — the directory is part of what makes a cache
  entry findable again, so it is never built from a temp name, a pid
  or the time. ``operator_dir`` is the engine server's
  ``--compilation-cache-dir`` (the Helm chart points it at the PVC);
  without it the cache is ``<checkout>/.jax_cache`` (gitignored).
"""

from __future__ import annotations

import os
from typing import Optional

from production_stack_tpu.utils.log import init_logger

logger = init_logger(__name__)

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"
REPO_CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__)))), ".jax_cache")


def configure_compile_cache(operator_dir: Optional[str] = None) -> str:
    """Place the persistent compile cache; returns the directory in
    use."""
    env_dir = os.environ.get(ENV_VAR)
    if env_dir:
        if operator_dir and operator_dir != env_dir:
            logger.info("%s=%s is set; ignoring --compilation-cache-dir "
                        "%s", ENV_VAR, env_dir, operator_dir)
        return env_dir
    import jax
    path = operator_dir or REPO_CACHE_DIR
    jax.config.update("jax_compilation_cache_dir", path)
    return path
