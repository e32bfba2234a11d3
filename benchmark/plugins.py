"""Files found by the name ``BENCHMARK.json`` or a configuration gives them:
``benchmark/<kind>/<name>.py`` (a metric's name may hold dots, so these are
loaded by path, not imported)."""

from __future__ import annotations

import importlib.util
import pathlib

HERE = pathlib.Path(__file__).resolve().parent


def load(kind: str, name: str):
    spec = importlib.util.spec_from_file_location(f"benchmark.{kind}.{name.replace('.', '_')}",
                                                  HERE / kind / f"{name}.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod
