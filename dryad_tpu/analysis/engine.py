"""Repo-level graftlint runner.

Locates the working tree from the installed package (the repo root is
the parent of the ``dryad_tpu`` package directory), builds a
:class:`~dryad_tpu.analysis.core.Project` over ``dryad_tpu/`` +
``tests/``, and runs the registry.  This is what the CLI and the
tier-1 test both call.
"""

from __future__ import annotations

from pathlib import Path
from typing import Iterable, Optional

import dryad_tpu
from dryad_tpu.analysis.core import Project, Report, run


def repo_root() -> Path:
    return Path(dryad_tpu.__file__).resolve().parent.parent


def load_project(root: Optional[Path] = None) -> Project:
    return Project.from_root(Path(root) if root else repo_root())


def run_repo(
    rules: Optional[Iterable[str]] = None,
    root: Optional[Path] = None,
) -> Report:
    return run(load_project(root), rules=rules)
