"""The benchmark's traffic, driven by the mix's data file.

A mix file (``bench/traffic/<name>.json``) names its ``kind`` and its
parameters.  Each kind is a module of its own, ``bench/kinds/<kind>.py``,
found by that name, which gives

* ``pipeline(mix, cfg, seed)`` — the input pipeline the service runs on its
  workers, built with the program's ``Dataset`` API, and
* ``reference(mix, cfg, seed)`` — the benchmark's own plain implementation
  of every element that pipeline can produce, as a :class:`Reference`: a map
  from a row's identity (a digest of its ``tokens`` and ``labels``) to the
  element's index, and a function that rebuilds element ``i``.  It imports
  nothing of the program.

A new mix of a known kind is a data file alone; a new kind is a new module
beside the others.
"""
from __future__ import annotations

import hashlib
import importlib.util
import sys
from pathlib import Path
from types import ModuleType
from typing import Any, Callable, Dict, Tuple

import numpy as np

ROOT = Path(__file__).resolve().parent.parent
PAD_ID = 0


def row_digest(tokens: np.ndarray, labels: np.ndarray) -> str:
    return hashlib.sha1(
        np.ascontiguousarray(tokens, np.int32).tobytes()
        + np.ascontiguousarray(labels, np.int32).tobytes()
    ).hexdigest()


class Reference:
    """Every element the mix can produce, rebuilt by the benchmark."""

    def __init__(self, size: int, make: Callable[[int], Dict[str, np.ndarray]],
                 ident: Callable[[int], Tuple[np.ndarray, np.ndarray]]):
        self.make = make
        self.index: Dict[str, int] = {}
        for i in range(size):
            self.index.setdefault(row_digest(*ident(i)), i)


def load_kind(kind: str, root: Path = ROOT) -> ModuleType:
    """``bench/kinds/<kind>.py`` under ``root``.  It is registered as
    ``bench.kinds.<kind>`` so that the functions it maps on the workers
    pickle by name into the service's pool children."""
    path = root / "bench" / "kinds" / f"{kind}.py"
    name = f"bench.kinds.{kind}"
    mod = sys.modules.get(name)
    if mod is not None and Path(mod.__file__).resolve() == path.resolve():
        return mod
    spec = importlib.util.spec_from_file_location(name, path)
    if spec is None or not path.is_file():
        raise KeyError(f"no traffic kind {kind!r}: {path} is missing")
    mod = importlib.util.module_from_spec(spec)
    sys.modules[name] = mod
    spec.loader.exec_module(mod)
    return mod


def pipeline(mix: Dict[str, Any], cfg: Dict[str, Any], seed: int, root: Path = ROOT):
    return load_kind(mix["kind"], root).pipeline(mix, cfg, seed)


def reference(mix: Dict[str, Any], cfg: Dict[str, Any], seed: int,
              root: Path = ROOT) -> Reference:
    return load_kind(mix["kind"], root).reference(mix, cfg, seed)
