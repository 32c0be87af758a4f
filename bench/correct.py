"""Whether the timed path served the right tokens.

Once the window has closed and the program's state is freed, a sample of
the requests the window served (finished, or in a lane at the close with
the tokens served so far), drawn from the seed and holding the longest, goes through the family's plain float32 reference, layer
by layer: each prompt followed by the tokens served for it.  For every
served token the reading is how far its reference logit lies below the
reference's best logit at that position; the run's number is the widest
such gap.  Greedy serving in the configuration's precision keeps it near
rounding; a wrong token, a stale state or a lane's tokens given to another
lie far above it.

``control=True`` reads the same gaps for the tokens that the float8
reference (the step below bf16) puts first at each position of the same
sequences: the number the limit has to stay below.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from .refmath import seed_key

LOGIT_BLOCK = 256


def pick_sample(served: dict, reqs: dict, schedule_prompts: dict, seed: int,
                k: int, max_len: int) -> list[tuple]:
    """[(prompt, served tokens)] of up to ``k`` served requests: the
    longest, then others drawn from the seed.  ``served``: rid -> tokens;
    ``schedule_prompts``: rid -> prompt ids."""
    rids = sorted(r for r in served if r in reqs and served[r])
    if not rids:
        return []
    size = lambda r: len(schedule_prompts[r]) + len(served[r])
    longest = max(rids, key=lambda r: (size(r), -r))
    rest = [r for r in rids if r != longest]
    rng = np.random.default_rng([int(seed) % 2**63, 7])
    take = list(rng.choice(rest, min(k - 1, len(rest)), replace=False)) \
        if rest else []
    out = []
    for r in [longest] + sorted(int(x) for x in take):
        p, t = np.asarray(schedule_prompts[r]), list(served[r])
        if len(p) + len(t) - 1 > max_len:
            raise ValueError(f"request {r}: {len(p)} + {len(t)} - 1 "
                             f"positions exceed the reference's {max_len}")
        out.append((p, t))
    return out


class Reference:
    """The family's reference at fixed shapes: ``rows`` sequences of
    ``length`` positions, so every run reuses one set of programs."""

    def __init__(self, model, conf: dict, rows: int, length: int):
        self.model, self.conf, self.rows, self.length = (model, conf, rows,
                                                         length)
        self._embed = jax.jit(functools.partial(model.ref_embed, conf))
        self._layer = jax.jit(functools.partial(model.ref_layer, conf),
                              static_argnames="quant")
        self._logits = jax.jit(functools.partial(model.ref_logits, conf),
                               static_argnames="quant")

    def _inputs(self, sample):
        toks = np.zeros((self.rows, self.length), np.int32)
        r_idx, p_idx, served = [], [], []
        for i, (prompt, out) in enumerate(sample):
            seq = np.concatenate([prompt, np.asarray(out[:-1], np.int32)])
            toks[i, :len(seq)] = seq
            n0 = len(prompt) - 1                 # logits of the first token
            r_idx += [i] * len(out)
            p_idx += list(range(n0, n0 + len(out)))
            served += list(out)
        return toks, np.asarray(r_idx), np.asarray(p_idx), np.asarray(served)

    def hidden(self, seed: int, toks, quant: bool):
        key = seed_key(seed)
        h = self._embed(key, jnp.asarray(toks))
        for layer in range(self.model.dims(self.conf)["L"]):
            h = self._layer(key, layer, h, quant=quant)
        return h

    def gaps(self, seed: int, sample, control: bool = False) -> np.ndarray:
        """One gap per served token (``control``: per position, for the
        token the float8 reference puts first)."""
        if len(sample) > self.rows:
            raise ValueError("sample larger than the reference's rows")
        toks, r_idx, p_idx, served = self._inputs(sample)
        key = seed_key(seed)
        h = self.hidden(seed, toks, False)[r_idx, p_idx]
        hq = self.hidden(seed, toks, True)[r_idx, p_idx] if control else None
        out = []
        n = len(served)
        pad = -n % LOGIT_BLOCK
        h = jnp.pad(h, ((0, pad), (0, 0)))
        if control:
            hq = jnp.pad(hq, ((0, pad), (0, 0)))
        for b in range(0, n + pad, LOGIT_BLOCK):
            ref = self._logits(key, h[b:b + LOGIT_BLOCK], quant=False)
            if control:
                pick = jnp.argmax(self._logits(key, hq[b:b + LOGIT_BLOCK],
                                               quant=True), -1)
            else:
                pick = jnp.asarray(np.pad(served[b:b + LOGIT_BLOCK],
                                          (0, LOGIT_BLOCK)))[:LOGIT_BLOCK]
            best = jnp.max(ref, -1)
            got = jnp.take_along_axis(ref, pick[:, None], -1)[:, 0]
            out.append(np.asarray(best - got))
        return np.concatenate(out)[:n]


def in_vocab(sample, vocab: int) -> bool:
    return all(0 <= t < vocab for _, out in sample for t in out)
