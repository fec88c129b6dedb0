"""Tier-1 runs ``tests/`` only; the benchmark's own tests of its rule
families live beside the benchmark (``cellbench/tests``). This file brings
three of those modules into tier-1 as they stand, every case counting: the
family seam with the fixture family ``paramflow`` (single PARAM_FLOW frames,
now decided a drained queue at a time), the family ``hotparam`` at a tiny
size (one cell end to end through the native door's data plane, the probe's
checks, the control caught), and the family ``shaped`` at a tiny size (one
cell with every arm of the step live, the probe's nine checks, both controls
caught, the plain reference in the program's place). CPU, tiny sizes, about
three minutes.
"""

import os
import sys

_CELLBENCH_TESTS = os.path.join(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
    "cellbench", "tests")
if _CELLBENCH_TESTS not in sys.path:
    # the modules import their helpers (frame_digest, fake_door) by name
    sys.path.insert(0, _CELLBENCH_TESTS)

from test_families import *  # noqa: E402,F401,F403
from test_hotparam import *  # noqa: E402,F401,F403
from test_shaped import *  # noqa: E402,F401,F403
