"""Decide ``correct``: served tokens against the plain reference.

After the window, a sample of the requests the engine finished, drawn
from the seed with the longest among them, is run through the
configuration's reference once each: prompt and served tokens, padded at
the end to one fixed length (the causal mask keeps the padding out of
every position that counts). At each position whose next token was
served, the gap is the reference's largest logit minus its logit of the
served token (0 where the served token is its first choice). From the
gaps over the sample come the readings (``readings``): the widest gap
and the mean gap. The configuration file's ``limits`` name the readings
compared, each with its limit; ``limits_from`` says where it came from. Valid for
greedy tokens, which is all the mixes send.
"""
from __future__ import annotations

from typing import Any, Dict, List

import numpy as np

from bench import weights

#: sequences per reference call
REF_BATCH = 2
#: served tokens the sample reaches before it stops growing
SAMPLE_TOKENS = 1024
SAMPLE_MAX_REQUESTS = 48


def sample(finished: List[Any], seed: int) -> List[Any]:
    """The finished request with the longest context, then others in an
    order drawn from the seed, until SAMPLE_TOKENS served tokens."""
    if not finished:
        return []
    def ctx(t):
        return t.spec.plen + len(t.req.output)
    longest = max(range(len(finished)), key=lambda i: (ctx(finished[i]), -i))
    order = np.random.default_rng(
        np.random.SeedSequence([int(seed), 11])).permutation(len(finished))
    picked = [finished[longest]]
    served = len(finished[longest].req.output)
    for i in order:
        if served >= SAMPLE_TOKENS or len(picked) >= SAMPLE_MAX_REQUESTS:
            break
        if i != longest:
            picked.append(finished[i])
            served += len(finished[i].req.output)
    return picked


def ref_length(max_seq: int, chunk: int) -> int:
    return -(-max_seq // chunk) * chunk


def sequences(pairs, length: int):
    """(tokens, targets, mask) arrays, (N, length): tokens are prompt plus
    served tokens but the last; targets the next token at each position;
    mask marks the positions whose target was served."""
    n = len(pairs)
    tokens = np.zeros((n, length), np.int32)
    targets = np.zeros((n, length), np.int32)
    mask = np.zeros((n, length), bool)
    for i, (prompt, out) in enumerate(pairs):
        seq = np.concatenate([np.asarray(prompt, np.int32),
                              np.asarray(out, np.int32)])
        tokens[i, :len(seq) - 1] = seq[:-1]
        targets[i, :len(seq) - 1] = seq[1:]
        mask[i, len(prompt) - 1:len(seq) - 1] = True
    return tokens, targets, mask


def batched(fn, tokens: np.ndarray, *rest: np.ndarray) -> np.ndarray:
    """``fn`` over REF_BATCH rows at a time (the last block padded with
    copies of its first row, whose readings are dropped)."""
    out = []
    for s in range(0, len(tokens), REF_BATCH):
        blk = [a[s:s + REF_BATCH] for a in (tokens, *rest)]
        k = len(blk[0])
        if k < REF_BATCH:
            blk = [np.concatenate([b, np.repeat(b[:1], REF_BATCH - k, 0)])
                   for b in blk]
        out.append(np.asarray(fn(*blk))[:k])
    return np.concatenate(out)


def readings(gaps: np.ndarray) -> Dict[str, float]:
    """The numbers a configuration's ``limits`` can name, from the gaps at
    the positions whose next token was served."""
    return {"max_logit_gap": float(np.max(gaps)),
            "mean_logit_gap": float(np.mean(gaps))}


def run(cfg_file: Dict[str, Any], ref, max_seq: int, finished: List[Any],
        seed: int, control: bool = False) -> Dict[str, Any]:
    """Check a sample of ``finished`` (the harness's tracked requests)
    against ``ref``, the configuration's reference module.
    ``control`` also puts the control through the same comparison: at the
    same positions, the gaps of the tokens that the reference computed in
    float8 puts first, and whether those pass the limits."""
    m = cfg_file["model"]
    picked = sample(finished, seed)
    limits = cfg_file["limits"]
    if not picked:
        return {"correct": False, "sample_requests": 0, "sample_tokens": 0,
                "compared": {k: (float("inf"), lim)
                             for k, lim in limits.items()}}
    length = ref_length(max_seq, ref.LOGIT_CHUNK)
    tokens, targets, mask = sequences(
        [(t.spec.prompt, t.req.output) for t in picked], length)
    w = weights.make(ref.shapes(m), m["dtype"], seed)
    gaps = batched(lambda a, b: ref.gaps(w, a, b, m), tokens, targets)
    got = readings(gaps[mask])
    compared = {k: (got[k], lim) for k, lim in limits.items()}
    out = {"correct": all(v <= lim for v, lim in compared.values()),
           "sample_requests": len(picked), "sample_tokens": int(mask.sum()),
           "readings": got, "compared": compared}
    if control:
        firsts = batched(lambda a: ref.argmax(w, a, m, fp8=True), tokens)
        cgaps = batched(lambda a, b: ref.gaps(w, a, b, m), tokens,
                        firsts.astype(np.int32))
        cgot = readings(cgaps[mask])
        # the control through the same comparison: it has to fail it
        out["control"] = dict(cgot, correct=all(
            cgot[k] <= lim for k, lim in limits.items()))
    return out
