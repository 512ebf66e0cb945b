"""The correctness contract between two runs of the serve path.

* **Same shapes** (same batch rows, buckets and devices): bit-identical.
  Compare with ``np.testing.assert_array_equal``.
* **Across shapes** (another batch size, bucket decomposition, pair
  bucket or device count): XLA may order a reduction differently, so a
  float32 value can move in its last bits.  Every decision is still
  identical — token ids, parsed labels and lengths, retrieved anchor
  indices, policy choices — and every float agrees within
  ``CROSS_SHAPE_RTOL`` / ``CROSS_SHAPE_ATOL``.  ``assert_cross_shape``
  applies exactly that: exact for integer and boolean arrays, the
  tolerance for floating ones.

The tolerance is a few float32 ulp (2^-23 ~ 1.2e-7 relative) of a
probability or cosine in [-1, 1]: wide enough for a reordered sum of a
few hundred terms, far too tight to hide a wrong mask, position or
dropped term.  A bfloat16 model rounds each partial sum to 8 bits, so
across device counts its logits are held to the bfloat16 bound of
``models.reference.TOLERANCE`` instead (``chip_smoke.py --chips 4``).
"""
from __future__ import annotations

import numpy as np

CROSS_SHAPE_RTOL = 1e-6
CROSS_SHAPE_ATOL = 1e-6


def assert_cross_shape(got, want, err_msg: str = "") -> None:
    """Assert ``got`` matches ``want`` under the cross-shape contract."""
    got, want = np.asarray(got), np.asarray(want)
    if np.issubdtype(want.dtype, np.floating):
        np.testing.assert_allclose(got, want, rtol=CROSS_SHAPE_RTOL,
                                   atol=CROSS_SHAPE_ATOL, err_msg=err_msg)
    else:
        np.testing.assert_array_equal(got, want, err_msg=err_msg)
