"""What decides `correct` for a served model.

Once the window has closed and the program is freed, a sample of the
requests the run finished, drawn from the seed and holding the one with
the most served tokens, goes through the plain reference
(core/reference.py) as prompt + served tokens.  At each position that
produced a served token, the reference's best logit minus its logit for
the served token is that token's gap.  Served tokens are greedy, so a
sound program only picks a token the reference ranks below its best where
the two sit within rounding of each other.

The number compared is `logit_gap`, the widest gap over the sample.
The mean gap, the 99th percentile and the share of tokens that flipped
are reported beside it (`gap_stats`).

The control (`control_gaps`) puts the reference in the program's place at
float8, one step below the configuration's bfloat16: at every position of
the same sequences it takes the token the float8 reference ranks first
and reads that token's gap in the float32 reference.
"""
from __future__ import annotations

from typing import Dict, List, Sequence

import numpy as np

from bench.core import reference, traffic

SAMPLE_TOKENS = 600      # served tokens the sample holds at least
SAMPLE_MAX = 8           # requests the sample holds at most
NOTHING_CHECKED = 1e12   # the gap printed when no request finished


def sample(finished: Sequence, seed: int) -> List:
    """Finished requests to check: the one with the most served tokens,
    then others in an order drawn from the seed, until the sample holds
    SAMPLE_TOKENS served tokens or SAMPLE_MAX requests."""
    if not finished:
        return []
    reqs = sorted(finished, key=lambda r: r.rid)
    longest = max(reqs, key=lambda r: (len(r.out), -r.rid))
    rest = [r for r in reqs if r is not longest]
    order = traffic.rng_for(seed, 9).permutation(len(rest))
    out, n = [longest], len(longest.out)
    for i in order:
        if n >= SAMPLE_TOKENS or len(out) >= SAMPLE_MAX:
            break
        out.append(rest[i])
        n += len(rest[i].out)
    return out


def _sequences(reqs):
    """prompt + served tokens but the last, and at each position the
    served token it produced (0 where none)."""
    seqs, ids, spans = [], [], []
    for r in reqs:
        p = np.asarray(r.prompt, np.int32)
        out = np.asarray(r.out, np.int32)
        s = np.concatenate([p, out[:-1]])
        tok = np.zeros((len(s), 1), np.int32)
        tok[len(p) - 1:, 0] = out
        seqs.append(s)
        ids.append(tok)
        spans.append((len(p) - 1, len(s)))
    return seqs, ids, spans


def served_gaps(c: dict, seed: int, reqs) -> np.ndarray:
    """Gap of every served token of `reqs` in the float32 reference."""
    seqs, ids, spans = _sequences(reqs)
    res = reference.run(c, seed, seqs, ids)
    return np.concatenate([r["best"][a:b] - r["picked"][a:b, 0]
                           for r, (a, b) in zip(res, spans)])


def control_gaps(c: dict, seed: int, reqs, quant: str = "fp8") -> np.ndarray:
    """Gap, in the float32 reference, of the token the `quant` reference
    ranks first, at every position that produced a served token."""
    seqs, ids, spans = _sequences(reqs)
    low = reference.run(c, seed, seqs, ids, quant=quant)
    ids2 = [np.stack([d[:, 0], r["arg"]], 1) for d, r in zip(ids, low)]
    ref = reference.run(c, seed, seqs, ids2)
    return np.concatenate([r["best"][a:b] - r["picked"][a:b, 1]
                           for r, (a, b) in zip(ref, spans)])


def gap_stats(gaps: np.ndarray) -> Dict[str, float]:
    return {"mean": float(np.mean(gaps)), "max": float(np.max(gaps)),
            "p99": float(np.percentile(gaps, 99)),
            "flipped_share": float(np.mean(gaps > 0))}


def check_served(c: dict, seed: int, finished, limit: float):
    """(checks, gap_stats): the numbers compared for `correct`, each with
    its limit, and the gap distribution of the sample."""
    reqs = sample(finished, seed)
    if not reqs:
        # nothing finished: no gap to read, and the run is not correct
        return ({"logit_gap": {"value": NOTHING_CHECKED, "limit": limit},
                 "served_tokens_checked": {"value": 0, "limit": 1}}, {})
    gaps = served_gaps(c, seed, reqs)
    return ({"logit_gap": {"value": float(np.max(gaps)), "limit": limit},
             "served_tokens_checked": {"value": int(gaps.size),
                                       "limit": 1}}, gap_stats(gaps))


def passes(checks: Dict[str, Dict[str, float]]) -> bool:
    """Every number within its limit: `served_tokens_checked` at least
    its limit, every other number at most its limit (NaN fails)."""
    ok = True
    for name, v in checks.items():
        x, lim = v["value"], v["limit"]
        if name == "served_tokens_checked":
            ok &= x >= lim
        else:
            ok &= bool(np.isfinite(x)) and x <= lim
    return bool(ok)
