"""Tier-1 runs ``tests/`` only; the concurrency family's own tests live
beside the benchmark (``cellbench/tests/test_concurrent.py``). This file
brings that module into tier-1 as it stands, every case counting, in a file
of its own so that it runs beside the other bridges and not behind them: the
family ``concurrent`` at a tiny size (one cell end to end with the session
live, the probe's eight checks, the three controls caught, the plain
reference in the program's place, sound and broken). CPU, tiny sizes, about
two minutes.
"""

import importlib.util
import os
import sys

_CELLBENCH_TESTS = os.path.join(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
    "cellbench", "tests")
if _CELLBENCH_TESTS not in sys.path:
    # the module imports its helpers by name
    sys.path.insert(0, _CELLBENCH_TESTS)

# loaded by its path under a name of its own: ``tests/test_concurrent.py``
# is a ``test_concurrent`` too, and whichever was imported first would win
_spec = importlib.util.spec_from_file_location(
    "cellbench_tests_test_concurrent",
    os.path.join(_CELLBENCH_TESTS, "test_concurrent.py"))
_module = importlib.util.module_from_spec(_spec)
sys.modules[_spec.name] = _module
_spec.loader.exec_module(_module)
globals().update({k: v for k, v in vars(_module).items()
                  if not k.startswith("__")})
