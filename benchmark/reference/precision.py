"""The arithmetic of one side of a comparison.

``Precision("f64")`` is the reference: float64 throughout.
``Precision("bf16")`` is the control: every value handed from one step to
the next is stored in bfloat16 and each step computes in float32, as the
TPU's matrix unit does at its default precision (bfloat16 operands, float32
accumulation). It is the step a later change could be tempted to take:
dropping ``Precision.HIGHEST`` from the float32 matmuls the program states.
"""

import ml_dtypes
import numpy as np


class Precision:
    def __init__(self, name):
        if name not in ("f64", "bf16"):
            raise ValueError(f"unknown precision {name!r}")
        self.name = name
        self.dtype = np.float64 if name == "f64" else np.float32

    def __call__(self, a):
        """Store ``a`` at this precision."""
        a = np.asarray(a, dtype=self.dtype)
        if self.name == "bf16":
            a = a.astype(ml_dtypes.bfloat16).astype(np.float32)
        return a

    def mm(self, a, b):
        """Matrix product of stored operands, accumulated in ``dtype``."""
        return self(a) @ self(b)


F64 = Precision("f64")
BF16 = Precision("bf16")
