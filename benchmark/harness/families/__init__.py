"""One module per architecture family, found by the configuration's ``family``
key as the references are: a new architecture is new files only
(``families/<family>.py``, ``references/<family>.py``, a configuration file)."""

from __future__ import annotations

import importlib
from typing import Any, Dict


def of(arch: Dict[str, Any]) -> Any:
    """The family module of a configuration: ``dims``, ``layer_params``,
    ``other_params``, ``layer``, ``globals_``, ``program_layer``,
    ``program_tree`` and ``model_kwargs``."""
    return importlib.import_module(f"harness.families.{arch['family']}")
