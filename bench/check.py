"""The number that decides ``correct``: how far served logits lie from the
reference's on the same frames.

``logit_err`` of a set of frames is the worst frame's largest absolute
logit difference over the largest absolute reference logit of that frame.
The program and the reference compute the same integer accumulates and
the same float epilogues in the same order, so a sound run reads zero or
a few float32 rounding steps; a changed activation code moves a logit by
about a weight level times its scale.
"""

from __future__ import annotations

import numpy as np


def logit_err(out: np.ndarray, want: np.ndarray) -> float:
    """Worst frame's max |out - want| / max |want|; ``out`` and ``want``
    are [..., n_classes] of the same shape."""
    out = np.asarray(out, np.float64)
    want = np.asarray(want, np.float64)
    span = np.maximum(np.abs(want).max(axis=-1), 1e-30)
    return float((np.abs(out - want).max(axis=-1) / span).max())
