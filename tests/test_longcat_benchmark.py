"""``benchmark/tests/test_longcat_benchmark.py`` under ``pytest tests/``: the
tier-1 command collects nothing under ``benchmark/tests/``, so the ``longcat``
cell's configuration, driver, metric files and work functions are imported
here to be counted with the rest."""

import importlib.util
import pathlib

_PATH = (pathlib.Path(__file__).resolve().parent.parent / "benchmark" / "tests"
         / "test_longcat_benchmark.py")
_spec = importlib.util.spec_from_file_location(
    "benchmark_tests_test_longcat_benchmark", _PATH)
_module = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(_module)

globals().update({name: value for name, value in vars(_module).items()
                  if name.startswith("test_") or name == "native"})
