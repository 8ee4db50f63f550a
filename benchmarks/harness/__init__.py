"""The benchmark's own yardstick: data set, front-door wiring, checks,
trace reduction, peaks and operation counts.  Nothing here is imported
by the program; later PRs add files beside these and edit none."""

import importlib.util
import os

_HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def load_by_name(kind: str, name: str):
    """Import ``benchmarks/<kind>/<name>.py`` (names may hold '-' and '.')."""
    path = os.path.join(_HERE, kind, name + ".py")
    if not os.path.exists(path):
        raise FileNotFoundError(f"no {kind} file for {name!r}: {path}")
    spec = importlib.util.spec_from_file_location(
        f"benchmarks.{kind}.{name.replace('-', '_').replace('.', '_')}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod
