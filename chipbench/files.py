"""How the benchmark finds its files: by path under a checkout's root, from
the names in ``BENCHMARK.json`` and in the files those name."""
from __future__ import annotations

import importlib.util
import json
import os


def load_json(root, *parts):
    with open(os.path.join(root, *parts)) as f:
        return json.load(f)


def load_module(root, *parts):
    """A Python file by its path under ``root``.  The file's name may hold
    dots, as a metric's name does, so this is not an import by name."""
    path = os.path.join(root, *parts)
    name = "chipbench_file_" + "_".join(parts).replace(".", "_")
    spec = importlib.util.spec_from_file_location(name, path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module
