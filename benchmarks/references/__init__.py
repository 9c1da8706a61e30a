"""The plain references, one file a family (``<family>.py``: a forward
pass in ``jax.numpy`` and float32 that shares no code with the
program), and the one comparison behind ``correct`` that every family
holds its served logits to."""

from __future__ import annotations


def compare(got, ref, share_of_spread: float = 0.05) -> dict:
    """Serving logits against the reference's.

    Tolerance: the served network carries bf16 (8 significant bits)
    through 34 convolution layers with float32 accumulation; the same
    comparison for the 18-layer net lands near 1% of the logits'
    spread (chip_smoke.py, PR 21). 5% of the reference's spread holds
    a wrong ingest, a wrong layout, lost or mismatched weights (each
    moves logits by the spread itself) and an 8-bit integer path out,
    and lets bf16 rounding in. Logits, not classes: with random
    weights the largest logit changes on rounding."""
    import numpy as np
    got = np.asarray(got, np.float32)
    ref = np.asarray(ref, np.float32)
    if got.shape != ref.shape:
        return {"ok": False, "why": "shape %r against %r"
                % (got.shape, ref.shape)}
    if not np.isfinite(got).all():
        return {"ok": False, "why": "non-finite logits"}
    spread = float(ref.std())
    worst = float(np.abs(got - ref).max())
    return {"ok": bool(spread > 0 and worst <= share_of_spread * spread),
            "max_abs_diff": worst, "ref_spread": spread,
            "share_of_spread": worst / spread if spread else None}
