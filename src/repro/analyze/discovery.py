"""Single source of truth for "what counts as repro source" (ISSUE-6).

``tools/measure_cov.py`` (the stdlib settrace coverage tool) and the
analyzers in this package both need to enumerate / filter repro source
files; before this module each re-walked the tree with its own filter and
the two could silently disagree.  Both now resolve through here.

Keep this module importable WITHOUT the repro package: measure_cov loads
this FILE directly via importlib (spec_from_file_location) so that tracing
can start before anything imports ``repro`` (the repro modules pull in
jax, whose module-level lines would then execute untraced and depress the
measured coverage).  Stdlib imports only.
"""

from __future__ import annotations

import os
import pathlib

SRC_ROOT = pathlib.Path(__file__).resolve().parent.parent  # .../src/repro
REPO_ROOT = SRC_ROOT.parent.parent


def repro_source_files(subdir: str | None = None) -> list[pathlib.Path]:
    """Every repro source file, sorted; ``subdir`` narrows to one package."""
    base = SRC_ROOT / subdir if subdir else SRC_ROOT
    return sorted(base.rglob("*.py"))


def repro_frame_prefix() -> str:
    """Filename prefix identifying a stack frame as repro source."""
    return str(SRC_ROOT) + os.sep


def canon_frame_filename(filename: str) -> str:
    """Canonical form of a code object's filename.

    ``tests/conftest.py`` prepends ``<repo>/tests/../src`` to ``sys.path``,
    and CPython does NOT collapse the ``..`` when it absolutizes module
    ``__file__``s -- so under pytest every repro frame's ``co_filename``
    carries the unnormalized prefix and a naive ``startswith`` filter sees
    NOTHING (the bug that silently zeroed tools/measure_cov.py's counts).
    Every frame filter must compare through this normalization.
    """
    return os.path.normpath(filename)


def is_repro_frame(filename: str) -> bool:
    return canon_frame_filename(filename).startswith(repro_frame_prefix())
