"""Process-level device policy shared by the entry points.

- ``ensure_compile_cache``: JAX's persistent compilation cache. Where
  ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it itself and nothing
  is overridden; otherwise the cache lives at a fixed ``.jax_cache/``
  at the checkout root (derived from this package's path, never from a
  temporary name, pid or time: the path is part of the cache key, so a
  moving directory never hits). Called first by ``serve.main``,
  ``chip_smoke.py``, ``benchmarks/run.main``, ``launch/train.py`` and
  the standalone fabric worker (``serve --connect``).
- ``refuse_chip_children``: a TPU belongs to one process at a time. The
  process and loopback-fabric runtimes spawn children that each build a
  full engine (jitted route step included); under a parent that holds
  the chip they would fail or hang, so their spawn sites call this and
  fail fast instead.
"""
from __future__ import annotations

import os
import pathlib

import jax

CACHE_ENV = "JAX_COMPILATION_CACHE_DIR"
#: the checkout root: src/repro/device.py -> parents[2]
CHECKOUT_ROOT = pathlib.Path(__file__).resolve().parents[2]
DEFAULT_CACHE_DIR = CHECKOUT_ROOT / ".jax_cache"


def ensure_compile_cache() -> str:
    """Point JAX's persistent compile cache at its one directory and
    return that directory."""
    env = os.environ.get(CACHE_ENV)
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(DEFAULT_CACHE_DIR))
    return str(DEFAULT_CACHE_DIR)


class ChipOwnedError(RuntimeError):
    """A runtime would spawn children that need the chip this process
    holds."""


def refuse_chip_children(runtime: str) -> None:
    """Raise ``ChipOwnedError`` when this process's JAX backend is a TPU
    and ``runtime`` is about to spawn engine-building children."""
    if jax.default_backend() != "tpu":
        return
    raise ChipOwnedError(
        f"the {runtime} runtime spawns worker processes that each build "
        f"an engine on the chip, but this process already holds the TPU "
        f"(one process per chip). Run the campaign in-process (--nodes N "
        f"instead of --workers/--fabric-workers), or start one standalone "
        f"'serve --connect HOST:PORT' worker per chip host. A device-owner "
        f"role that lets host workers share one chip-owning process is "
        f"ROADMAP queue 2 item 1.")
