"""The one place that decides which JAX backend this process serves on.

Two outcomes, decided once per process before anything compiles:

  * ``JAX_PLATFORMS`` is exactly ``cpu`` — the CPU is intended (the test
    suite's virtual 8-device mesh, ``run-aios.sh --cpu``, the CPU smokes).
    The ``jnp`` reference implementations serve and no Pallas kernel is
    traced.
  * anything else — the default backend must be ``tpu``. A TPU that fails
    to initialise makes JAX fall back to the CPU with a warning; serving a
    7B model from the host and reporting ``ready`` is the failure this
    module exists to prevent, so that case raises :class:`BackendError`.

``ops.use_pallas()``, the model manager's default weight mode and the
engine's kernel selection all read :func:`on_tpu`; the runtime service,
``bench.py`` and ``chip_smoke.py`` call :func:`decide` / :func:`require_tpu`
first so a missing chip stops the process at start-up.

The persistent compilation cache is placed here too: where
``JAX_COMPILATION_CACHE_DIR`` is set JAX reads it itself and this module
sets no directory; where it is not, the cache lives at one fixed path
inside the checkout (the path is part of the cache key, so it must never
carry a pid, a timestamp or a temp name).
"""

from __future__ import annotations

import functools
import os
from pathlib import Path

# One fixed in-repo location (gitignored): <checkout>/.jax_cache
DEFAULT_COMPILE_CACHE_DIR = Path(__file__).resolve().parent.parent / ".jax_cache"


class BackendError(RuntimeError):
    """The process was not told to run on the CPU and JAX found no TPU."""


def compile_cache_dir() -> str:
    """Where compiled executables persist: the operator's
    ``JAX_COMPILATION_CACHE_DIR`` when set, else the fixed in-repo path."""
    return os.environ.get("JAX_COMPILATION_CACHE_DIR") or str(
        DEFAULT_COMPILE_CACHE_DIR
    )


def _configure_compile_cache() -> None:
    import jax

    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update(
            "jax_compilation_cache_dir", str(DEFAULT_COMPILE_CACHE_DIR)
        )
    # warmup AOT-compiles tens of graphs behind LoadModel and a fresh
    # machine pays all of them again; store every one, not only the slow
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)


@functools.lru_cache(maxsize=None)
def decide() -> str:
    """``"cpu"`` or ``"tpu"`` for this process; raises :class:`BackendError`
    when the CPU was not asked for and the default backend is not a TPU.
    Cached: the backend of a JAX process never changes once initialised."""
    if os.environ.get("JAX_PLATFORMS", "").strip().lower() == "cpu":
        return "cpu"
    import jax

    try:
        found = jax.default_backend()
    except RuntimeError as exc:  # JAX_PLATFORMS names a backend that failed
        raise BackendError(f"JAX backend failed to initialise: {exc}") from exc
    if found != "tpu":
        raise BackendError(
            f"default JAX backend is {found!r}, not 'tpu' "
            f"(JAX_PLATFORMS={os.environ.get('JAX_PLATFORMS', '')!r}). "
            "Serving needs the chip; set JAX_PLATFORMS=cpu only for tests "
            "and CPU smokes."
        )
    _configure_compile_cache()
    return "tpu"


def on_tpu() -> bool:
    return decide() == "tpu"


def require_tpu():
    """The TPU devices, for entry points that measure or prove the chip
    (``chip_smoke.py``, ``bench.py``'s default mode): an intended CPU run
    is refused as well."""
    import jax

    if jax.devices()[0].platform != "tpu":
        raise BackendError(
            f"no TPU: JAX reports {jax.devices()[0].platform!r} devices "
            f"(JAX_PLATFORMS={os.environ.get('JAX_PLATFORMS', '')!r})"
        )
    decide()
    return jax.devices()
