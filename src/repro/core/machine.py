"""Machine fingerprint for benchmark provenance, and the compile-cache rule.

Benchmark baselines in the BENCH_*.json files are machine-relative: CI
regenerates them from scratch before guarding, but the committed snapshots
are also read by humans, and a re-baseline is only auditable if the file
says WHERE its numbers came from. Every bench writer embeds this fingerprint
so a large swing between two committed snapshots can be attributed (same
machine -> investigate the code; different machine -> runner variance is a
plausible cause and a same-machine bisect is the next step).

Deliberately excludes anything volatile (load averages, timestamps beyond
the date) so regenerating on the same box yields a stable fingerprint.
"""

from __future__ import annotations

import os
import platform
from pathlib import Path

# <repo>/.jax_cache: a fixed path, because the path is part of what JAX's
# persistent cache keys on — a directory that moves between runs never hits.
_DEFAULT_CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def use_compile_cache() -> str:
    """Turn on JAX's persistent compilation cache; returns its directory.

    ``JAX_COMPILATION_CACHE_DIR``, when set, is JAX's own setting and is used
    as given: nothing is changed. Otherwise the cache goes to ``.jax_cache``
    at the repository root. Entry points call this before their first
    compile; library code never does.
    """
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    import jax

    jax.config.update("jax_compilation_cache_dir", str(_DEFAULT_CACHE_DIR))
    return str(_DEFAULT_CACHE_DIR)


def machine_fingerprint() -> dict:
    """Stable description of the host + JAX stack a benchmark ran on."""
    import jax

    return {
        "platform": platform.platform(),
        "machine": platform.machine(),
        "cpu_count": os.cpu_count(),
        "python": platform.python_version(),
        "jax": jax.__version__,
        "jax_backend": jax.default_backend(),
        "devices": [str(d) for d in jax.devices()],
    }
