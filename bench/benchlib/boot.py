"""Process set-up shared by the benchmark's entry points (`run.py`,
`sweep.py`, `control.py`). Call `prepare` before anything imports JAX."""
from __future__ import annotations

import os
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]


class Refused(RuntimeError):
    """The run cannot measure what it should: say why, print no result."""


def prepare(rehearse: bool) -> str:
    """Environment, the program on `sys.path`, and JAX's persistent
    compilation cache inside the checkout. Returns the cache directory."""
    forced = os.environ.get("REPRO_KERNEL_MODE", "")
    if forced not in ("", "pallas"):
        raise Refused(f"REPRO_KERNEL_MODE={forced!r} would swap the Pallas "
                      "kernels for their reference twins")
    if rehearse:
        os.environ["JAX_PLATFORMS"] = "cpu"
        os.environ["REPRO_PALLAS_INTERPRET"] = "1"
    # libtpu's own logs would go to a fixed path outside the checkout
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    src = ROOT / "src"
    if not (src / "repro").is_dir():
        raise Refused(f"no program to measure: {src / 'repro'} is missing")
    sys.path.insert(0, str(src))
    from repro.launch.compile_cache import enable_compile_cache
    cache = enable_compile_cache()
    import jax
    # every program, however quick to compile, is found again by the next
    # run in this checkout
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return cache
