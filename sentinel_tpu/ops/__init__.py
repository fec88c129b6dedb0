"""Pallas TPU kernels for the engine's hot ops.

These are the "native" compute components of the framework (SURVEY.md §2:
the reference is 100% Java, so its JVM-concurrency hot paths — LongAdder
arrays, CAS window loops — map to device kernels here, not to C/C++):

- :mod:`sentinel_tpu.ops.prefix_pallas` — tiled in-batch segment prefix sums
  (the admission primitive) that never materializes the [N, N] mask in HBM.
- :mod:`sentinel_tpu.ops.cms_pallas` — the count-min-sketch decide+update
  kernel: whole sketch resident in VMEM, gathers/scatters expressed as
  one-hot MXU matmuls.
- :mod:`sentinel_tpu.ops.cms_commit` — the plain count-min core's commit
  (PR 39): the batch's ``(cell, amount)`` pairs sorted, each touched row of
  128 cells of the HBM-resident sketch read, added to and written back once
  by DMA.

The first two have a pure-jax reference implementation elsewhere in the tree
(`engine/prefix.py`, `engine/param.py`). A kernel a config selects is
compiled by Mosaic or raises: nothing here derives ``interpret=`` from the
backend. The CPU parity tests ask for the interpreter themselves
(``interpret=True`` on the kernel entry points, or the ``pallas_interpret``
fixture in ``tests/conftest.py`` around a config-selected step). The commit
kernel is no config's choice: it is the only commit the XLA core
(``impl="jax"``) has, so its one caller (``engine/param._cms_flat``) runs the
same kernel under the Pallas interpreter where the backend is not a TPU.
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
