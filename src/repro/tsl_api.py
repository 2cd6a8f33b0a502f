"""Framework-facing TSL access point.

Every higher layer (nn/, train/, serve/) calls vector primitives ONLY through
this module, so switching execution dialect = regenerating the library
(``REPRO_TSL_TARGET=pallas_interpret`` etc.) — the paper's portability claim,
upheld structurally.

``load_library`` is backed by the content-addressed artifact cache: with an
unchanged UPD fingerprint + probed hardware flags the warm path imports the
cached package without re-running a single GPO. ``warmup()`` pre-generates
several targets off one validated corpus (zero re-validation per target).
"""

from __future__ import annotations

import os
from pathlib import Path
from types import ModuleType

import jax

from repro.core import generate_all, load_library

_lib: ModuleType | None = None


def lib(force: bool = False) -> ModuleType:
    global _lib
    if _lib is None or force:
        _lib = load_library(os.environ.get("REPRO_TSL_TARGET", "auto"))
    return _lib


def use(target: str, *, force: bool = False,
        build_root: Path | None = None) -> ModuleType:
    """Route every later primitive call through the library generated for
    ``target`` (``force`` regenerates it from the UPD, under ``build_root``
    if given). JAX's trace caches are dropped too, so no function traced
    against the previous library is reused."""
    global _lib
    _lib = load_library(target, force=force, build_root=build_root)
    jax.clear_caches()
    return _lib


def warmup(targets: tuple[str, ...] | None = None) -> dict[str, Path]:
    """Populate the artifact cache for ``targets`` (default: every corpus
    target) so later ``load_library`` calls are pure cache hits."""
    return generate_all(targets)


class _OpsProxy:
    """Late-bound proxy so `from repro.tsl_api import ops` works before the
    library is generated (first attribute access triggers generation)."""

    def __getattr__(self, name: str):
        return getattr(lib().ops, name)


ops = _OpsProxy()


def target_name() -> str:
    return lib().TARGET_NAME


def cost(primitive: str, term: str, **shapes) -> float:
    return lib().cost(primitive, term, **shapes)
