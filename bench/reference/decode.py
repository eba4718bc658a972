"""The served tokens against a plain f32 forward of each user's private
model (base parameters plus the user's delta rows), at full precision.

Greedy decoding serves the token with the largest logit.  So for every
served token, the reference runs the whole sequence (prompt and served
tokens) once, causally, and reads how far the served token's logit lies
below the reference's best at that position.  The widest such gap over a
sample of the finished requests is the number compared.  The
configuration's family (``bench/reference/<family>.py``) gives the forward
and the stacked segments that a delta layer's rows lie in.
"""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from bench.gen import rng_for
from bench.harness import family_module, split_layers
from bench.weights import leaf_names, make_params


def sample_requests(served: list, tokens: int, seed: int) -> list:
    """The longest finished request, then others drawn from the seed, until
    the sample holds ``tokens`` served tokens."""
    if not served:
        return []
    order = sorted(range(len(served)),
                   key=lambda i: -(len(served[i][0]) + len(served[i][1])))
    first, rest = order[0], order[1:]
    picked, n = [served[first]], len(served[first][1])
    for i in rng_for(seed, 9).permutation(rest) if rest else []:
        if n >= tokens:
            break
        picked.append(served[int(i)])
        n += len(served[int(i)][1])
    return picked


class ServeReference:
    def __init__(self, config: dict, shapes, seed: int, max_len: int,
                 layers: Optional[list] = None, rows: Optional[dict] = None):
        """``shapes``: the program's parameter tree of ShapeDtypeStructs (the
        benchmark makes the same values from ``seed``); ``layers``/``rows``:
        the users' delta layers and, per segment, ``{leaf: (users, k,
        *shape)}`` rows (entry j for the user's j-th layer)."""
        family = family_module(config, "reference")
        self.a = family.Arch.from_config(config)
        self.segments = family.segments(config)
        self.max_len = max_len
        names = leaf_names(shapes)
        self.params = {n: jnp.asarray(v, jnp.float32) for n, v in
                       zip(names, jax.tree.leaves(make_params(shapes, seed)))}
        self.layers, self.rows = layers, rows
        self._fwd = jax.jit(
            lambda p, t, lowp=None: family.lm_logits(p, t, self.a, lowp)[0],
            static_argnums=2)

    def private(self, user: int) -> dict:
        if user < 0 or self.layers is None:
            return self.params
        p = dict(self.params)
        for seg, (pos, idx) in split_layers(self.segments,
                                            self.layers[user]).items():
            for name, rows in self.rows[seg].items():
                key = f"{seg}/{name}"
                p[key] = p[key].at[jnp.asarray(idx)].add(
                    jnp.asarray(rows[user][pos]))
        return p

    def logits(self, prompt, served, user: int, lowp=None) -> np.ndarray:
        """(len(served), V) logits at the positions that chose each served
        token: the sequence is padded to one fixed length at its end, which
        a causal model never looks at."""
        seq = list(prompt) + list(served[:-1])
        toks = np.zeros((1, self.max_len), np.int32)
        toks[0, :len(seq)] = seq
        with jax.default_matmul_precision("highest"):
            out = self._fwd(self.private(user), jnp.asarray(toks), lowp)
        P = len(prompt)
        return np.asarray(out[P - 1:P - 1 + len(served)], np.float64)

    def widest_gap(self, sample: list) -> float:
        """max over served tokens of (best logit − served token's logit)."""
        gap = 0.0
        for prompt, served, user in sample:
            lg = self.logits(prompt, served, user)
            got = lg[np.arange(len(served)), np.asarray(served)]
            gap = max(gap, float(np.max(lg.max(1) - got)))
        return gap

    def control_gap(self, sample: list, lowp) -> float:
        """The same gap for the tokens a lower precision puts first, at the
        same prompts and served tokens."""
        gap = 0.0
        for prompt, served, user in sample:
            lg = self.logits(prompt, served, user)
            low = self.logits(prompt, served, user, lowp)
            got = lg[np.arange(len(served)), low.argmax(1)]
            gap = max(gap, float(np.max(lg.max(1) - got)))
        return gap
