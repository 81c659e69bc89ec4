"""Placement of JAX's persistent compilation cache for the entry points.

A cold CLI job is mostly compile (the first gram kernel costs ~50 s for the
TPU's compiler whatever the row count), and a server recompiles every
(model, bucket) program at every start — so ``python -m avenir_tpu``,
``python -m avenir_tpu.pipeline`` and ``python -m avenir_tpu.serving`` all
call :func:`configure` before their first use of JAX.

Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it itself and nothing
is set in code.  Otherwise the cache lives at ONE fixed path inside the
checkout (git-ignored): the path is part of the cache's key, so a directory
built from a temporary name, a pid or the time would never hit.
"""

from __future__ import annotations

import os
from typing import Optional

CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__)))), ".jax_cache")


def configure() -> Optional[str]:
    """Point JAX at the in-checkout cache unless the environment already
    placed it; returns the directory set in code (None = the env's)."""
    if os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        return None
    import jax

    jax.config.update("jax_compilation_cache_dir", CACHE_DIR)
    return CACHE_DIR
