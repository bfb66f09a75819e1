"""The benchmark's traffic generator: Zipf-distributed tokens with repeated
motifs, made from ``(seed, batch index)`` alone.

A copy of the arithmetic of ``repro.data.pipeline.SyntheticLM``, kept here so
that no change to the program can change what the benchmark feeds it.  Token
ids are drawn from the configuration's vocabulary (its slice, where the
configuration holds one).  ``labels`` equal ``tokens``: the program's loss
shifts by one position itself (``LM.loss``), so the objective is next-token
prediction over the first ``S - 1`` positions.
"""

from __future__ import annotations

from typing import Dict, List

import numpy as np


def batches(traffic: Dict, vocab_size: int, seed: int) -> List[Dict[str, np.ndarray]]:
    """``traffic["batches"]`` batches of ``(batch, seq_len)`` int32 tokens;
    every row of every batch is drawn afresh."""
    B, S = traffic["batch"], traffic["seq_len"]
    g = traffic["generator"]
    root = np.random.default_rng(np.random.SeedSequence([seed, 0]))
    motifs = root.integers(0, vocab_size, size=(g["num_motifs"], g["motif_len"]),
                           dtype=np.int32)
    w = np.arange(1, vocab_size + 1, dtype=np.float64) ** (-g["zipf_a"])
    probs = w / w.sum()
    n_spans = int(g["motif_prob"] * (S // g["motif_len"]))
    out = []
    for i in range(traffic["batches"]):
        rng = np.random.default_rng(np.random.SeedSequence([seed, 1, i]))
        toks = rng.choice(vocab_size, size=(B, S), p=probs).astype(np.int32)
        for b in range(B):
            starts = rng.integers(0, S + 1 - g["motif_len"], size=n_spans)
            ids = rng.integers(0, g["num_motifs"], size=n_spans)
            for s0, mid in zip(starts, ids):
                toks[b, s0: s0 + g["motif_len"]] = motifs[mid]
        out.append({"tokens": toks, "labels": toks.copy()})
    return out
