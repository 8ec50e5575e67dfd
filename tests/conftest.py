"""Shared test bootstrap.

1. Puts ``src/`` on ``sys.path`` so the suite runs without PYTHONPATH.
2. Guards the optional ``hypothesis`` dependency: prefer the real package
   (installed via ``requirements-dev.txt``); fall back to the deterministic
   shim in ``_hypothesis_fallback.py``; and if even the shim cannot load,
   ``collect_ignore`` the hypothesis-based modules so collection never
   hard-errors on a missing optional dep (importorskip semantics).
"""

from __future__ import annotations

import importlib.util
import pathlib
import sys

_ROOT = pathlib.Path(__file__).resolve().parents[1]
_SRC = _ROOT / "src"
if str(_SRC) not in sys.path:
    sys.path.insert(0, str(_SRC))

# modules that import `hypothesis` at module scope
_HYPOTHESIS_MODULES = ["test_core_properties.py", "test_dist.py",
                       "test_fleet.py", "test_xlstm_vjp.py"]

collect_ignore: list = []


def _install_hypothesis_fallback() -> None:
    path = pathlib.Path(__file__).with_name("_hypothesis_fallback.py")
    spec = importlib.util.spec_from_file_location("_hypothesis_fallback", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    mod.install()


try:
    import hypothesis  # noqa: F401
except ImportError:
    try:
        _install_hypothesis_fallback()
    except Exception:  # last resort: skip, never a collection error
        collect_ignore += _HYPOTHESIS_MODULES
