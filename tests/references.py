"""The plain references live ONCE, under `benchmark/` (which reads nothing
outside its own directory): a suite loads the one it is held to by path.
They import nothing of `paddle_tpu`."""

import importlib.util
import os
import sys

_BENCHMARK = os.path.join(os.path.dirname(os.path.abspath(__file__)), "..",
                          "benchmark")


def by_path(name):
    """`benchmark/<name>.py` as a module, e.g. "reference_olmoe"."""
    spec = importlib.util.spec_from_file_location(
        name, os.path.join(_BENCHMARK, name + ".py"))
    module = importlib.util.module_from_spec(spec)
    sys.modules[name] = module
    spec.loader.exec_module(module)
    return module
