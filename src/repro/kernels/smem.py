"""Scalar-prefetch budget: split a launch so its plan tables fit in SMEM.

Every event kernel drives its DMAs from scalar-prefetched tables — event
addresses, live counts, window/tap plans — and Mosaic copies all of them
into the core's SMEM before the first grid step.  SMEM is 1 MiB on a v5e
and a 2-D table's minor dimension pads to 128 words, so a whole-layer
plan outgrows it at real widths: AlexNet@224's pool1 plan alone needs
1.5 MB at batch 1.  Two rules keep every launch inside the budget
(DESIGN.md §4):

  * tables are passed **flat** (1-D), so no minor dimension is padded;
  * a launch whose tables would still exceed :data:`SMEM_PLAN_WORDS` is
    split into several launches over contiguous chunks of its leading
    (output row-group) grid axis.  Each output tile is still computed by
    one launch, walking the same (tap, event) steps in the same order, so
    chunking never changes a reduction tree — the bitwise contracts hold.

:func:`plan_chunks` is the one place the split is decided; the kernel
wrappers launch one ``pallas_call`` per chunk and the engine's trace
records count the same chunks (``launches=``).
"""
from __future__ import annotations

import numpy as np

__all__ = ["SMEM_PLAN_WORDS", "plan_chunks", "src_chunks"]

#: int32 words of scalar-prefetched tables one launch may hold: a quarter
#: of a v5e core's 1 MiB SMEM, leaving the rest to Mosaic's own scalars
#: and the per-table padding of flat operands.
SMEM_PLAN_WORDS = 1 << 16


def plan_chunks(n_rows: int, row_words: int, *, src=None, src_words: int = 0,
                fixed_words: int = 0) -> list[tuple[int, int, int, int]]:
    """Split grid rows [0, n_rows) into contiguous launches that fit SMEM.

    A launch over rows [r0, r1) holds ``fixed_words`` of per-launch tables,
    ``row_words`` words per row (e.g. a row's event addresses and count)
    and ``src_words`` words per *source* row it reads — rows
    [s0, s1) of a table indexed through ``src`` (an (n_rows, T) numpy plan
    of source row ids, e.g. a pool window's input row groups).  Without
    ``src`` the source range is the row range itself.

    Returns ``[(r0, r1, s0, s1), ...]`` — equal-size chunks, halved until
    the largest fits :data:`SMEM_PLAN_WORDS` (read per call, so a test can
    shrink it to exercise the split).  Raises ``ValueError`` when a single
    row cannot fit.
    """
    budget = SMEM_PLAN_WORDS
    if n_rows <= 0:
        return [(0, 0, 0, 0)]
    if src is not None:
        flat = np.asarray(src).reshape(n_rows, -1)
        row_lo, row_hi = flat.min(axis=1), flat.max(axis=1) + 1
    size = n_rows
    while True:
        starts = np.arange(0, n_rows, size)
        ends = np.minimum(starts + size, n_rows)
        if src is None:
            lo, hi = starts, ends
        else:
            lo = np.minimum.reduceat(row_lo, starts)
            hi = np.maximum.reduceat(row_hi, starts)
        words = fixed_words + (ends - starts) * row_words + (hi - lo) * src_words
        if words.max() <= budget:
            return [tuple(int(v) for v in c)
                    for c in zip(starts, ends, lo, hi)]
        if size == 1:
            raise ValueError(
                f"one grid row needs {int(words.max())} words of "
                f"scalar-prefetched tables; the SMEM budget is {budget}")
        size = -(-size // 2)


def src_chunks(src: np.ndarray, *, row_words: int, src_words: int,
               fixed_words: int = 0) -> list[tuple]:
    """:func:`plan_chunks` over an (n_rows, T) plan of source row ids.

    Returns ``[(r0, r1, s0, s1, src_c), ...]`` where ``src_c`` is the
    chunk's plan rows rebased to its source range [s0, s1) and flattened —
    the table a launch prefetches next to the address rows [s0, s1).
    """
    return [(r0, r1, s0, s1, (src[r0:r1] - s0).reshape(-1))
            for r0, r1, s0, s1 in plan_chunks(
                src.shape[0], row_words, src=src, src_words=src_words,
                fixed_words=fixed_words)]
