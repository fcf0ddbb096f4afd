"""JAX's persistent compilation cache for the entry points.

Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it itself and the
cache lives there.  Otherwise the cache lives at one fixed path inside
the checkout, ``<repo>/.jax_cache`` (ignored by git): the directory is
part of what a cache entry is found by, so it must not move between
runs.

A Pallas kernel reaches the compiler as a serialized Mosaic module that
carries its source locations, and the cache key hashes it whole.  Those
locations are made relative to the checkout, so the same commit finds
its entries from any checkout path; a line moved in a source file the
kernel is traced through still misses.
"""

from __future__ import annotations

import os
import re
from pathlib import Path

import jax

ROOT = Path(__file__).resolve().parents[3]
DEFAULT_DIR = ROOT / ".jax_cache"


def enable() -> str:
    """Turn the persistent cache on; returns the directory it uses."""
    jax.config.update("jax_hlo_source_file_canonicalization_regex",
                      "^" + re.escape(f"{ROOT}{os.sep}"))
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(DEFAULT_DIR))
    return str(DEFAULT_DIR)
