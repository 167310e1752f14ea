"""Where JAX's persistent compilation cache lives.

Every entry point that touches JAX calls `enable()` first thing, so a
second start of the same program finds what the first one compiled
(server boot on the tiny CPU model alone is 60-90 s of compiles). The
directory is part of what a cached program is found by, so it never
carries a temporary name, a pid or a time: it is what the environment
says, or one fixed place inside the checkout.

`enable()` also installs the process's compile ledger
(`obs.compile_ledger()`): from then on every trace, lowering and
compile, or read of this cache, is booked to its program by JAX's own
events, so that a slow start can say where it went
(docs/observability.md, "Compile ledger and start-up spans").

This module imports JAX only inside `enable()`: `chip_smoke.py`'s parent
reads `cache_dir()` to count entries and must stay off JAX.
"""

from __future__ import annotations

import os
import sys

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"
_CHECKOUT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def cache_dir() -> str:
    """`JAX_COMPILATION_CACHE_DIR` where set (JAX reads it itself),
    otherwise `<checkout>/.jax_cache`, found from this package's own
    location — the same answer from any working directory."""
    return os.environ.get(ENV_VAR) or os.path.join(_CHECKOUT, ".jax_cache")


def enable() -> str:
    """Turn the persistent cache on and return its directory. Where the
    environment names the directory it also owns the policy and this
    sets nothing. Otherwise every program is kept, however fast it
    compiled: at JAX's default 1 s floor a program near the floor is
    written by one run and not the next, and a warm start would still
    add entries."""
    path = cache_dir()
    _install_ledger()
    if not os.environ.get(ENV_VAR):
        import jax

        jax.config.update("jax_compilation_cache_dir", path)
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    return path


def _install_ledger():
    """Hand the process's compile ledger JAX's monitoring events.
    Harmless twice. What precedes the first
    program is counted from here; a process that has not imported JAX
    yet pays for the import under the span `startup.import_jax`."""
    from kubeflow_tpu import obs

    ledger = obs.compile_ledger()
    ledger.begin()
    if "jax" in sys.modules:
        import jax
    else:
        with ledger.span("startup.import_jax"):
            import jax
    ledger.install(jax.monitoring, annotate=jax.profiler.TraceAnnotation)
