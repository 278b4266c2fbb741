"""The comparison that decides ``correct``.

After the window, a sample of the requests the engine finished, drawn from
the seed and always holding the longest, is run once through the plain
reference of the configuration's architecture (``Reference`` of
``bench/arch/<name>.py``) over its prompt and its served tokens.  At
every served token, the gap by which the reference's logit for that token
lies below the reference's best is read; the widest gap of the sample is
the number compared.  The engine decodes greedily, so a sound engine serves
the reference's best token up to rounding: only near-ties give it a gap.

The control runs the reference in float8 at the same positions and reads the
gap of the token that float8 puts first.
"""
from __future__ import annotations

import numpy as np

from bench import arch as archs

# requests to compare: each is one greedy trajectory, and a random model's
# trajectory soon settles into repeating a token by a wide margin, where no
# rounding can change the choice; so the check's power is in the number of
# trajectories, not of tokens (a few long ones let the float8 control pass
# on some seeds)
SAMPLE_REQUESTS = 16


def sample(finished: list, seed: int) -> list:
    """The longest finished request (prompt + served), then others in an
    order drawn from the seed, SAMPLE_REQUESTS in all where there are that
    many."""
    if not finished:
        return []
    reqs = sorted(finished, key=lambda r: r.rid)
    longest = max(reqs, key=lambda r: (len(r.prompt) + len(r.out), r.rid))
    rest = [r for r in reqs if r is not longest]
    rng = np.random.default_rng([seed, 7])
    return [longest] + [rest[i] for i in
                        rng.permutation(len(rest))[:SAMPLE_REQUESTS - 1]]


def compare(cj: dict, seed: int, reqs: list, control: bool = False,
            arch=None) -> dict:
    """Widest gap over ``reqs`` (and the float8 control's, if asked).
    ``arch`` is the configuration's architecture module, found by the name
    the file gives where it is not passed."""
    arch = arch or archs.of(cj)
    ref = arch.Reference(cj["model"], seed, cj["dtype"]["weights"])
    max_len = cj["engine"]["max_len"]
    gaps, ctl = [], []
    for r in reqs:
        seq = list(r.prompt) + list(r.out[:-1])
        tokens = np.zeros(max_len, np.int32)
        tokens[:len(seq)] = seq
        rows = np.zeros(max_len, np.int32)
        k = len(r.out)
        rows[:k] = np.arange(len(r.prompt) - 1, len(r.prompt) - 1 + k)
        best = np.asarray(ref.logits(tokens, rows), np.float64)[:k]
        top = best.max(-1)
        gaps.append(top - best[np.arange(k), np.asarray(r.out)])
        if control:
            pick = np.asarray(ref.logits(tokens, rows, fp8=True))[:k].argmax(-1)
            ctl.append(top - best[np.arange(k), pick])
    out = _summary(gaps)
    out["requests_compared"] = len(reqs)
    if control:
        out.update({f"control_{k}": v for k, v in _summary(ctl).items()
                    if k != "tokens_compared"})
    return out


def _summary(gaps: list) -> dict:
    g = np.concatenate(gaps) if gaps else np.zeros(0)
    return {"widest_logit_gap": float(g.max()) if g.size else 0.0,
            "mean_logit_gap": float(g.mean()) if g.size else 0.0,
            "mismatch_share": float((g > 0).mean()) if g.size else 0.0,
            "tokens_compared": int(g.size)}
