"""The one rule for JAX's persistent compilation cache.

If ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it itself and this
module sets no directory in code.  Otherwise the cache lives at the
fixed path ``<checkout>/.jax_cache`` (gitignored) — the path is part of
the cache key, so it is never derived from a temp name, a pid or the
clock.  Every entry point that compiles device programs (``cli.py bn``,
``chip_smoke.py``, the test suite, the dry-run worker) calls
:func:`configure` once before its first compile.

It is also the one place where the data plane meets jax before anything
runs, so it installs ``jax.profiler.TraceAnnotation`` as the annotator of
``common.tracing``: from then on every program span lands on the
``/host:CPU`` plane of any live profiler trace (a no-op while no profiler
session is live), and the host runtime's probes beside it
(``tracing.install_host_probes``: the collector's hook and the process's
fault counts).
"""

from __future__ import annotations

import os

_CHECKOUT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
#: programs cheaper than this recompile faster than they deserialize
MIN_COMPILE_SECS = 5


def configure() -> str:
    """Apply the rule; returns the directory the cache uses."""
    import jax

    from lighthouse_tpu.common import tracing

    tracing.set_annotator(jax.profiler.TraceAnnotation)
    tracing.install_host_probes()
    cache_dir = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not cache_dir:
        cache_dir = os.path.join(_CHECKOUT, ".jax_cache")
        jax.config.update("jax_compilation_cache_dir", cache_dir)
    jax.config.update("jax_persistent_cache_min_compile_time_secs",
                      MIN_COMPILE_SECS)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return cache_dir
