"""The benchmark's own tests, collected by tier-1.

``benchmark/tests/`` holds the tests of the measurement itself: the
trace reducer on two recorded v5e traces, the layer account, the kernel
readers, the contract of ``BENCHMARK.json`` and of a cell's last line.
They lie outside ``tests/``, so a renamed scope or kernel used to break
a per-layer reader with nothing failing until a chip run printed
``null``. This module loads those files by path and re-exports each
test as ``test_<file>__<name>``, so ``pytest tests/`` runs every one as
its own case. Nothing under ``benchmark/`` knows of it.

Not loaded: ``test_cells_rehearse.py``, whose subprocess rehearsals
``tests/test_benchmark_rehearse.py`` already runs here (the rest of it
by hand: ``python3 -m pytest benchmark/tests -q``).
"""

import importlib.util
import inspect
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH_DIR = os.path.join(ROOT, "benchmark")
# as benchmark/tests/conftest.py does: the files import ``lib``,
# ``reduce`` and ``kernels`` from the benchmark's own directory
for _p in (BENCH_DIR, ROOT):
    if _p not in sys.path:
        sys.path.insert(0, _p)

FILES = ("test_contract", "test_generators", "test_indexer_kernel_reader",
         "test_layer_account", "test_setup_readers", "test_stats",
         "test_table_kernel_reader", "test_xplane")


def _is_fixture(obj) -> bool:
    return (type(obj).__name__ == "FixtureFunctionDefinition"
            or hasattr(obj, "_pytestfixturefunction"))


def _load(stem: str):
    path = os.path.join(BENCH_DIR, "tests", stem + ".py")
    spec = importlib.util.spec_from_file_location(
        "benchmark_own_" + stem, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _export():
    """Each file's tests under a name that carries the file's; its
    fixtures under their own (tests ask for them by parameter name), so
    two files defining one fixture name is an error, not a shadowing."""
    fixtures = {}
    for stem in FILES:
        mod = _load(stem)
        for name, obj in vars(mod).items():
            if _is_fixture(obj):
                if name in fixtures:
                    raise RuntimeError(
                        f"fixture {name!r} is defined by both "
                        f"{fixtures[name]} and {stem}")
                fixtures[name] = stem
                globals()[name] = obj
            elif name.startswith("test_") and inspect.isfunction(obj) \
                    and obj.__module__ == mod.__name__:
                globals()[f"{stem}__{name[len('test_'):]}"] = obj


_export()
