"""The comparison that decides ``correct``.

A sample of finished turns is compared with the plain reference at
every position where the greedy branch served a token: the logits the
decode step computed there (kept by the harness as the window ran)
against the reference's logits over the turn's prompt and the tokens
served before it.  Two numbers:

``logit_rel_err``   the widest relative error of a served position's
                    logits: the norm of the difference over the norm of
                    the reference's logits less their mean.
``greedy_argmax_mismatches`` served greedy tokens that are not the
                    first of the logits that produced them (exact).

The reference is the configuration's family's (``logits`` of
``bench/families/<family>.py``).  The control puts the reference
computed in float8 in the program's place, at the same positions.
"""

from __future__ import annotations

from types import ModuleType
from typing import Dict, List, Optional, Sequence, Tuple

import numpy as np

from bench import generator

NO_READING = 1e30      # what a run that compared nothing reports


def sample(done: Sequence, seed: int, k: int) -> List:
    """``k`` finished turns whose greedy branch's logits were kept, drawn
    from the seed, the longest among them."""
    ok = [r for r in done if r.error is None and r.greedy_gen
          and r.final_tokens is not None and not r.degraded
          and r.logits is not None and len(r.logits) == len(r.greedy_gen)]
    if not ok:
        return []
    longest = max(range(len(ok)),
                  key=lambda i: len(ok[i].prompt) + len(ok[i].greedy_gen))
    rest = [i for i in range(len(ok)) if i != longest]
    pick = generator.rng(seed, 7).permutation(rest)[: max(k - 1, 0)]
    return [ok[longest]] + [ok[i] for i in sorted(pick)]


def _rows(prompt: Sequence[int], served: Sequence[int]) -> Tuple:
    tokens = list(prompt) + list(served)
    positions = np.arange(len(prompt) - 1, len(tokens) - 1)
    return tokens, positions


def rel_err(ref: np.ndarray, got: np.ndarray) -> np.ndarray:
    """Per row: |got - ref| over |ref - mean(ref)|."""
    centred = ref - ref.mean(-1, keepdims=True)
    return (np.linalg.norm(got - ref, axis=-1)
            / np.linalg.norm(centred, axis=-1))


def compare(fam: ModuleType, model: dict, seed: int, seqs,
            control: Optional[str] = None) -> Dict[str, float]:
    """Both numbers over ``seqs``, each ``(prompt, served tokens, the
    program's logits at the served positions)``, against the family
    ``fam``'s reference; with ``control`` its logits in that precision
    stand in for the program's."""
    worst, mism = 0.0, 0
    for prompt, served, rows in seqs:
        tokens, pos = _rows(prompt, served)
        ref = np.asarray(fam.logits(model, seed, tokens, pos))
        got = (np.asarray(rows, np.float32) if control is None else
               np.asarray(fam.logits(model, seed, tokens, pos, control)))
        worst = max(worst, float(rel_err(ref, got).max()))
        mism += int((got.argmax(-1) != np.asarray(served)).sum())
    return {"logit_rel_err": worst, "greedy_argmax_mismatches": mism}
