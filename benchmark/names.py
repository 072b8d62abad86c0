"""Names in data files that stand for code: ``package.module`` is a module,
``package.module:attribute`` something in it. A configuration's adapter and
counts, and a reader kind that a later PR brings, are found this way, so that
they arrive as new files and no file that is there needs an edit."""

from __future__ import annotations

import importlib
import importlib.util
from typing import Any

from .server import HarnessFailure

#: the adapter of a configuration whose ``correctness`` block names none
DEFAULT_ADAPTER = "benchmark.adapters.llama"


def adapter_of(conf: dict) -> str:
    return conf.get("correctness", {}).get("adapter", DEFAULT_ADAPTER)


def load(spec: str) -> Any:
    module, _, attr = spec.partition(":")
    try:
        found = importlib.import_module(module)
        return getattr(found, attr) if attr else found
    except (ImportError, AttributeError) as e:
        raise HarnessFailure(f"{spec!r} names nothing that can be loaded: {e}")


def find(module: str) -> None:
    """The module is there; it is not imported (an adapter imports JAX, and
    the harness's parent process never does)."""
    try:
        found = importlib.util.find_spec(module)
    except (ImportError, ValueError) as e:
        found, why = None, f": {e}"
    else:
        why = ""
    if found is None:
        raise HarnessFailure(f"{module!r} names no module{why}")
