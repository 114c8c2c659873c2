"""Finding the benchmark's parts by name.

Whatever belongs to one name is a module of its own,
``bench/<kind>/<name>.py``, and a later cell adds such a file instead of
editing one:

``recipes``
    field content: ``make(key, shape) -> jnp.ndarray``;
``loops``
    traffic shapes: ``run(entry, op, items, seconds, rng, traffic,
    sample, span) -> List[Request]``;
``entries``
    ways into the program: ``start(program)``, an object with ``call``,
    ``warm`` and ``close`` (and ``submit`` where requests may overlap);
``metrics``
    readers: ``read(run) -> float | None``.
"""
from __future__ import annotations

import functools
import importlib.util
from pathlib import Path
from types import ModuleType
from typing import List

BENCH = Path(__file__).resolve().parent


def known(kind: str, base: Path = BENCH) -> List[str]:
    """The names of ``kind`` that ``base`` holds."""
    return sorted(p.stem for p in (Path(base) / kind).glob("*.py")
                  if p.stem != "__init__")


def find(kind: str, *names: str, base: Path = BENCH) -> ModuleType:
    """The module ``base/<kind>/<name>.py`` of the first of ``names``
    that has one; a ``LookupError`` listing the known names if none has."""
    for name in names:
        path = Path(base) / kind / f"{name}.py"
        if path.is_file():
            return _load(str(path.resolve()))
    raise LookupError(f"no {kind} file for {names[0]!r} under {base}; "
                      f"known: {known(kind, base)}")


@functools.lru_cache(maxsize=None)
def _load(path: str) -> ModuleType:
    # one module object per file, so that a recipe handed to jit as a
    # static argument is the same function on every call
    p = Path(path)
    tag = f"{p.parent.name}_{p.stem}".replace(".", "_").replace("-", "_")
    spec = importlib.util.spec_from_file_location(f"bench_{tag}", p)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod
