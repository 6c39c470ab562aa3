"""Process-level jax set-up shared by every entry point that is about to
use the device: the persistent compilation cache and the one backend
question the kernel gates ask.

Importing this module never imports jax (data-plane processes import
`utils` freely); each function imports it on first use.
"""

from __future__ import annotations

import os

#: the checkout (or install root) that holds the package — the fallback
#: cache lives beside it at a FIXED path: the cache key includes the
#: directory, so a temp name, pid or timestamp would never hit
_ROOT = os.path.dirname(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
)
DEFAULT_CACHE_DIR = os.path.join(_ROOT, ".jax_cache")


def ensure_compile_cache() -> str:
    """Point jax's persistent compilation cache somewhere stable and
    return the directory in use. Call once per process BEFORE its first
    compile (train, deploy, the scheduler's train worker).

    Where JAX_COMPILATION_CACHE_DIR is set jax reads it itself and this
    sets nothing — the directory is the operator's to place (e.g. in an
    output directory that outlives the machine). Otherwise the cache
    goes to `<checkout>/.jax_cache` (git-ignored)."""
    placed = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if placed:
        return placed
    import jax

    jax.config.update("jax_compilation_cache_dir", DEFAULT_CACHE_DIR)
    return DEFAULT_CACHE_DIR


def on_tpu() -> bool:
    """True when jax's default backend is a TPU — the gate for every
    Mosaic (Pallas TPU) kernel. Backend initialisation errors (a chip
    another process holds, a broken plug-in) RAISE: a kernel gate that
    swallowed them would turn a broken device into a silent XLA run."""
    import jax

    return jax.default_backend() == "tpu"
