"""Tier-1 runs ``tests/`` only; the breaker family's own tests live beside
the benchmark (``cellbench/tests/test_breaker.py``). This file brings that
module into tier-1 as it stands, every case counting, in a file of its own
so that it runs beside ``test_cellbench_bridge.py`` and not behind it: the
family ``breaker`` at a tiny size (one cell end to end with the breaker arm
live and an outcome step behind every frame, the probe's twelve checks, the
three controls caught, the plain reference in the program's place, sound and
under both controls). CPU, tiny sizes, about two minutes.
"""

import os
import sys

_CELLBENCH_TESTS = os.path.join(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
    "cellbench", "tests")
if _CELLBENCH_TESTS not in sys.path:
    # the module imports its helpers by name
    sys.path.insert(0, _CELLBENCH_TESTS)

from test_breaker import *  # noqa: E402,F401,F403
