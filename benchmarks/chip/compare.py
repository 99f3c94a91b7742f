"""The arithmetic every check shares: how far served values lie from the
reference's, and how far below the reference's best logit a served
prediction lies."""

from __future__ import annotations

import numpy as np


def rel_err(pairs) -> float:
    """max |served - ref| over max |ref|, across all (served, ref) pairs."""
    if not pairs:
        return 0.0
    err = max(float(np.max(np.abs(np.asarray(g, np.float64) - w)))
              for g, w in pairs)
    scale = max(float(np.max(np.abs(w))) for _, w in pairs)
    return err / max(scale, 1e-30)


def pred_gap(preds, ref_logits) -> float:
    """Widest gap, over the reference's full scale, by which the reference
    logit of a served prediction lies below the reference's best."""
    scale = max((float(np.max(np.abs(w))) for w in ref_logits), default=1.0)
    return max((float(np.max(w) - w[p]) / scale
                for p, w in zip(preds, ref_logits)), default=0.0)
