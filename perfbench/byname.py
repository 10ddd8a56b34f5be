"""Files of the benchmark found by name: ``perfbench/<kind>/<name>.py`` as a
module. A table (``tables/``), a query's plain reference (``queries/``) and a
metric's reader (``metrics/``) are each such a file, so adding one edits no
file that is there."""

from __future__ import annotations

import importlib.util
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
_loaded = {}


def load(kind: str, name: str):
    key = (kind, name)
    if key not in _loaded:
        folder = os.path.join(HERE, kind)
        path = os.path.join(folder, name + ".py")
        if not os.path.exists(path):
            raise FileNotFoundError(
                f"no {kind}/{name}.py under {HERE}: a {kind[:-1]} is a file "
                "of that name (see README.md)")
        if folder not in sys.path:  # files of a kind may share a helper
            sys.path.append(folder)
        spec = importlib.util.spec_from_file_location(
            f"perfbench_{kind}_" + name.replace(".", "_").replace("-", "_"),
            path)
        module = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(module)
        _loaded[key] = module
    return _loaded[key]
