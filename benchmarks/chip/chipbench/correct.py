"""Is what the timed path served right? Compared with the plain reference.

After the window closes, a sample of the requests the system finished,
drawn from the seed and always holding the one with the most served
tokens, is run once through the configuration's reference: each prompt
with its served tokens. For every served token the reference gives the
next-token logits at that position; the number compared is the widest gap
by which a served token's reference logit lies below the reference's best
logit there. Greedy decoding serves the argmax of the system's own
logits, so a correct system lands on the reference's best token, or on a
near tie whose gap is the size of its rounding.
"""
from __future__ import annotations

from typing import List

import numpy as np

TARGET_TOKENS = 200      # served tokens in the sample, at least
MAX_REQUESTS = 8


def sample(recs, seed: int) -> list:
    """Served requests, finished or still in flight (a chat request can
    outlive the window), drawn from the seed, always with the one that was
    served the most tokens."""
    fin = [r for r in recs if r.toks and r.req.state.value in (
        "finished", "running")]
    if not fin:
        return []
    longest = max(fin, key=lambda r: len(r.toks))
    chosen, n = [longest], len(longest.toks)
    for i in np.random.default_rng([int(seed), 7]).permutation(len(fin)):
        if n >= TARGET_TOKENS or len(chosen) >= MAX_REQUESTS:
            break
        if fin[i] is not longest:
            chosen.append(fin[i])
            n += len(fin[i].toks)
    return chosen


def sequences(chosen) -> tuple:
    """(token sequences, logit rows, served tokens) per sampled request:
    prompt + served[:-1]; the rows that predicted each served token."""
    seqs, rows, served = [], [], []
    for r in chosen:
        out = np.asarray(r.toks, np.int32)
        p = len(r.plan.prompt)
        seqs.append(np.concatenate([r.plan.prompt, out[:-1]]))
        rows.append(np.arange(p - 1, p - 1 + len(out)))
        served.append(out)
    return seqs, rows, served


def gaps(ref_logits: List[np.ndarray], tokens: List[np.ndarray]) -> np.ndarray:
    """Per token: reference best logit minus the reference logit of
    ``tokens`` at that row (0 where the reference agrees)."""
    out = []
    for lg, t in zip(ref_logits, tokens):
        lg = np.asarray(lg, np.float64)
        out.append(lg.max(axis=-1) - lg[np.arange(len(t)), t])
    return np.concatenate(out) if out else np.zeros(0)


def served_gaps(ref, cfg: dict, seed: int, chosen) -> np.ndarray:
    seqs, rows, served = sequences(chosen)
    return gaps(ref.logits(cfg, seed, seqs, rows, "float32"), served)


def live_sample(recs, seed: int, n: int = 2) -> list:
    """Requests in flight (at least two tokens served), drawn from the
    seed, always with the one that has written the most KV."""
    live = [r for r in recs if r.req.state.value == "running"
            and len(r.toks) >= 2]
    if not live:
        return []
    top = max(live, key=lambda r: len(r.plan.prompt) + len(r.toks))
    rest = [live[i] for i in np.random.default_rng([int(seed), 8])
            .permutation(len(live)) if live[i] is not top]
    return [top] + rest[:n - 1]


def kv_mismatch(ref, cfg: dict, seed: int, live, snaps,
                act_dtype: str = "float32"):
    """The share of the first layer's MXFP8 K and V values the timed path
    wrote to the pool that differ from the reference's, over the sampled
    requests' written positions. None with no request in flight."""
    if not live:
        return None
    seqs = []
    for r, snap in zip(live, snaps):
        toks = np.concatenate([r.plan.prompt, np.asarray(r.toks, np.int32)])
        seqs.append(toks[:snap[0]])
    diff = total = 0
    for (fill, kc, ks, vc, vs), kv in zip(
            snaps, ref.kv_layer0(cfg, seed, seqs, act_dtype)):
        for n, (c, s) in enumerate(((kc, ks), (vc, vs))):
            prog = ref.decode_kv(c, s).reshape(-1, c.shape[-1])[:fill]
            diff += int((prog != kv[n]).sum())
            total += prog.size
    return diff / total
