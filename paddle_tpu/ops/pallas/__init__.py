"""Pallas TPU kernels — the PHI fused-kernel library analog.

Reference parity: paddle/phi/kernels/fusion/ + flash_attn_kernel
(SURVEY.md §2.1) — here written as Mosaic/Pallas kernels tiled for the
MXU instead of CUDA.  fused_train.py holds the train-step regions
(one-pass clip+optimizer update, add+norm, matmul+rotary).

Kernel-or-reference policy: a call site that has a jnp reference takes
it ONLY off the TPU (``runtime.device.is_compiled_with_tpu()``) or when
the kernel's wrapper raises ``ShapeNotCovered`` while it is traced — the
one documented "this shape is outside what the kernel tiles" signal.
Anything else (a Mosaic compile error, a lowering NotImplementedError)
propagates: on the chip a failed kernel fails the program, it never
silently becomes the reference.
"""


class ShapeNotCovered(NotImplementedError):
    """Raised by a kernel wrapper, at trace time, for a shape / dtype /
    mesh it does not tile.  The only exception a kernel-or-reference
    call site may catch."""
