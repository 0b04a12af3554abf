"""The comparison that decides ``correct``.

The plain reference itself (a forward pass in float32, no cache, no kernels)
belongs to a model family and lives beside its config mapping in
``families/<family>.py`` (``logits_many``). What is here holds the system's
answers to it, for every family alike, in units of the reference's own
spread so that one tolerance means the same at every width.
"""

from __future__ import annotations

import numpy as np


def compare_logits(system: np.ndarray, ref: np.ndarray) -> float:
    """Largest absolute difference in units of the reference's spread."""
    return float(np.max(np.abs(system - ref)) / max(float(ref.std()), 1e-9))


def greedy_slack(ref_logits: np.ndarray, tokens) -> float:
    """How far below the reference's largest logit the emitted tokens lie,
    at worst, in units of the reference's spread. ``ref_logits[j]`` is the
    reference's distribution for emitted token ``j``."""
    toks = np.asarray(tokens, int)
    gap = ref_logits.max(-1) - ref_logits[np.arange(len(toks)), toks]
    return float(np.max(gap / np.maximum(ref_logits.std(-1), 1e-9)))
