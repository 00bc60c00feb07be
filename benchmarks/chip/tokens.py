"""The token streams the benchmark feeds the training step.

A copy of ``repro.data.pipeline.synth_batch`` for one host: zipf-ish
token ids clipped into the vocabulary, labels the next token of the same
stream.  Every batch is a pure function of (seed, step), so a run, its
reference and any later rerun see the same rows.  The traffic file gives
the exponent; seeds may be any non-negative integer (NumPy's
``SeedSequence`` takes arbitrary width).
"""

from __future__ import annotations

import numpy as np


def batch(seed: int, step: int, rows: int, seq_len: int, vocab: int,
          zipf_a: float) -> dict:
    base = np.random.SeedSequence([seed, step])
    rng = np.random.default_rng(base.spawn(1)[0])
    z = rng.zipf(zipf_a, size=(rows, seq_len + 1))
    toks = np.minimum(z - 1, vocab - 1).astype(np.int32)
    return {"tokens": toks[:, :-1], "labels": toks[:, 1:]}
