"""The comparison that decides ``correct``.

Once the window has closed and the program's state is freed, a sample of
the finished requests, drawn from the seed and always holding the one
with the most served tokens, is run through the family's float32
reference: each prompt followed by the tokens the engine served.  At every
served token the reference's logits give the gap by which the served
token lies below the reference's best token (0 where they agree).  The
run's numbers are the widest such gap and the mean gap over all compared
tokens; the engine samples greedily, so a sound program serves the
reference's best token up to its own rounding.  A configuration compares
the numbers it gives limits for.

The control puts the reference, computed in float8, in the program's
place: its gap is that of the token the float8 logits put first.
"""

from __future__ import annotations

from typing import Dict
from typing import List
from typing import NamedTuple

import numpy as np


class Picked(NamedTuple):
    seq: np.ndarray          # prompt + served tokens but the last
    rows: np.ndarray         # positions whose next token was served
    served: np.ndarray       # the served tokens


def pick(served, seed: int, min_tokens: int, max_ref_tokens: int
         ) -> List[Picked]:
    """Finished requests: the longest, then others in an order drawn from
    ``seed``, until ``min_tokens`` served tokens are held or the next
    request would take the reference past ``max_ref_tokens``."""
    done = [s for s in served if s.req.done]
    if not done:
        return []
    done.sort(key=lambda s: (-len(s.req.tokens_out), s.uid))
    rng = np.random.default_rng(np.random.SeedSequence([seed, 0xC0]))
    order = [done[0]] + [done[1:][i] for i in rng.permutation(len(done) - 1)]
    out: List[Picked] = []
    n_served = n_ref = 0
    for s in order:
        if n_served >= min_tokens:
            break
        toks = np.asarray(s.req.tokens_out, np.int32)
        prompt = np.asarray(s.req.prompt, np.int32)
        size = len(prompt) + len(toks) - 1
        if out and n_ref + size > max_ref_tokens:
            continue
        out.append(Picked(np.concatenate([prompt, toks[:-1]]),
                          np.arange(len(prompt) - 1, size), toks))
        n_served += len(toks)
        n_ref += size
    return out


def gaps(ref_logits, chosen: np.ndarray) -> np.ndarray:
    """Reference best minus the reference's logit of ``chosen``, per row."""
    ref = np.asarray(ref_logits, np.float32)
    return ref.max(-1) - ref[np.arange(len(chosen)), chosen]


def gap_numbers(ref_logits: List, chosen: List[np.ndarray]
                ) -> Dict[str, float]:
    """The compared numbers over every compared token: the widest gap and
    the mean gap."""
    g = np.concatenate([gaps(r, c) for r, c in zip(ref_logits, chosen)])
    return {"logit_gap": float(g.max()), "logit_gap_mean": float(g.mean())}


def served_numbers(ref_logits: List, picked: List[Picked]
                   ) -> Dict[str, float]:
    return gap_numbers(ref_logits, [p.served for p in picked])


def control_numbers(ref_logits: List, low_logits: List) -> Dict[str, float]:
    """The same numbers for the tokens that lower-precision logits put
    first."""
    return gap_numbers(ref_logits, [np.asarray(lo).argmax(-1)
                                    for lo in low_logits])


def verdict(numbers: Dict[str, float], limits: Dict[str, float]) -> bool:
    """Every number at or below its limit; a missing number fails."""
    return all(numbers.get(k) is not None and numbers[k] <= lim
               for k, lim in limits.items())
