"""What every cell shares: the loop, the tables' writer, the comparison,
the probes and the trace reducer.  Whatever belongs to one cell, query,
table, entry point or metric is a file of its own beside this directory,
found by name through :func:`load_module`."""
import importlib.util
import os

BENCHMARK_DIR = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def load_module(kind, name):
    """``benchmark/<kind>/<name>.py``, by file: a later PR adds a file
    and edits none."""
    path = os.path.join(BENCHMARK_DIR, kind, f"{name}.py")
    if not os.path.isfile(path):
        raise FileNotFoundError(
            f"{name!r} is named but there is no benchmark/{kind}/{name}.py")
    spec = importlib.util.spec_from_file_location(
        f"benchmark_{kind}_{name}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod
