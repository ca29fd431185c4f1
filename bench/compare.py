"""The comparison that decides ``correct``: served logits against the plain
reference on the same inputs.

The number compared is ``logit_err``: over the checked requests, the
largest ``max|served - reference| / max|reference|`` of one request's
logits. Its limit is the configuration's ``check.logit_err``, set between
the program's readings and the control's (``PERF.md``).
"""
from __future__ import annotations

import jax
import numpy as np

from bench import reference as ref


def reference_logits(cfg: dict, params, pool: np.ndarray, idx: np.ndarray,
                     block: int, precision: str = "highest") -> np.ndarray:
    """Reference logits of ``pool[idx]``, computed ``block`` rows at a time
    by one compiled program."""
    fwd = jax.jit(lambda p, x: ref.forward(cfg, p, x, precision))
    out = []
    for lo in range(0, len(idx), block):
        x = pool[idx[lo:lo + block]]
        pad = block - len(x)
        if pad:
            x = np.concatenate([x, np.zeros((pad,) + x.shape[1:], x.dtype)])
        out.append(np.asarray(fwd(params, x))[:block - pad])
    return np.concatenate(out) if out else np.zeros((0, cfg["num_classes"]))


def logit_err(got: np.ndarray, want: np.ndarray) -> np.ndarray:
    """Per row: ``max|got - want| / max|want|`` (inf where not finite)."""
    got = np.asarray(got, np.float64)
    want = np.asarray(want, np.float64)
    err = np.abs(got - want).max(axis=1) / np.abs(want).max(axis=1)
    return np.where(np.isfinite(err), err, np.inf)


def check_sample(cfg: dict, params, pool: np.ndarray, sample_idx,
                 sample_logits, block: int,
                 precision: str = "highest") -> np.ndarray:
    """``logit_err`` of each checked request against the reference."""
    uniq, inv = np.unique(np.asarray(sample_idx, np.int64),
                          return_inverse=True)
    want = reference_logits(cfg, params, pool, uniq, block, precision)
    return logit_err(sample_logits, want[inv])
