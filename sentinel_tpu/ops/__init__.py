"""Pallas TPU kernels for the engine's hot ops.

These are the "native" compute components of the framework (SURVEY.md §2:
the reference is 100% Java, so its JVM-concurrency hot paths — LongAdder
arrays, CAS window loops — map to device kernels here, not to C/C++):

- :mod:`sentinel_tpu.ops.prefix_pallas` — tiled in-batch segment prefix sums
  (the admission primitive) that never materializes the [N, N] mask in HBM.
- :mod:`sentinel_tpu.ops.cms_pallas` — the count-min-sketch decide+update
  kernel: whole sketch resident in VMEM, gathers/scatters expressed as
  one-hot MXU matmuls.

Every kernel has a pure-jax reference implementation elsewhere in the tree
(`engine/prefix.py`, `engine/param.py`). A kernel a config selects is
compiled by Mosaic or raises: nothing here derives ``interpret=`` from the
backend. The CPU parity tests ask for the interpreter themselves
(``interpret=True`` on the kernel entry points, or the ``pallas_interpret``
fixture in ``tests/conftest.py`` around a config-selected step).
"""

import jax
from jax._src.pallas.mosaic.lowering import LoweringException

from sentinel_tpu.ops.prefix_pallas import segment_prefix_pallas
from sentinel_tpu.ops.cms_pallas import cms_decide_update_pallas

# What a kernel that cannot be built for the chip raises: Mosaic's own
# compile error (layout, VMEM) arrives as JaxRuntimeError; a Pallas lowering
# rule that rejects an op raises LoweringException or a builtin below. The
# "auto" probes catch exactly these, report the message and pick XLA.
KERNEL_BUILD_ERRORS = (
    jax.errors.JaxRuntimeError,
    LoweringException,
    NotImplementedError,
    ValueError,
    TypeError,
)

__all__ = [
    "KERNEL_BUILD_ERRORS",
    "segment_prefix_pallas",
    "cms_decide_update_pallas",
]
