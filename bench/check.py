"""What decides ``correct``: the served tokens against the plain reference.

Once the window has closed, a sample drawn from the seed of the requests
the window finished, the longest among them, is compared two ways:

* ``logit_gap``: the plain f32 reference (``bench/refs/<kind>.py``) runs
  once over each sampled prompt with its served tokens; the number is the
  widest gap by which a served token's reference logit lies below the
  reference's best logit at that position.  Every request is greedy, so a
  sound server's gap is rounding; a wrong token reads the distance from
  the best logit to an arbitrary one.
* ``det_streams_differ``: a few sampled deterministic requests are served
  again, alone, on a fresh engine built the same way; the number is how
  many committed streams differ from what the window delivered (an exact
  comparison, limit 0).

The control (``control=True``) also reads, at the same positions, the gap
of the token that the reference computed through float8 puts first.
"""

from __future__ import annotations

from functools import partial
from typing import Dict, List, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from bench.traffic import RequestSpec, seeded_rng


def sample(served: Sequence, seed: int, target_tokens: int,
           max_requests: int) -> List:
    """The longest finished request, then others in seeded order, until
    ``target_tokens`` served tokens or ``max_requests`` requests."""
    done = [s for s in served if s.done]
    if not done:
        return []
    longest = max(done, key=lambda s: (len(s.spec.prompt)
                                       + s.spec.max_new_tokens,
                                       -s.spec.index))
    rest = [s for s in done if s is not longest]
    order = seeded_rng(seed, 2).permutation(len(rest))
    out, n = [longest], longest.spec.max_new_tokens
    for i in order:
        if n >= target_tokens or len(out) >= max_requests:
            break
        out.append(rest[i])
        n += rest[i].spec.max_new_tokens
    return out


def det_sample(served: Sequence, seed: int, k: int) -> List:
    done = [s for s in served if s.done and s.spec.deterministic]
    order = seeded_rng(seed, 3).permutation(len(done))
    return [done[i] for i in order[:k]]


@partial(jax.jit, static_argnames=("width",))
def _gaps(logits, start, targets, n, width, qlogits=None):
    """Gaps of ``targets[:n]`` at rows ``start ..``; ``width`` rows are
    read so that one program serves every request (rows past ``n`` give
    0)."""
    rows = jax.lax.dynamic_slice_in_dim(logits, start, width, axis=0)
    valid = jnp.arange(width) < n
    best = jnp.max(rows, axis=-1)
    got = jnp.take_along_axis(rows, targets[:, None], axis=-1)[:, 0]
    out = [jnp.max(jnp.where(valid, best - got, 0.0))]
    if qlogits is not None:
        qrows = jax.lax.dynamic_slice_in_dim(qlogits, start, width, axis=0)
        qtok = jnp.argmax(qrows, axis=-1)
        qgot = jnp.take_along_axis(rows, qtok[:, None], -1)[:, 0]
        out.append(jnp.max(jnp.where(valid, best - qgot, 0.0)))
    return out


def logit_gaps(ref, params: Dict, model: Dict, items: Sequence,
               max_prompt: int, max_output: int,
               control: bool = False) -> Dict[str, float]:
    """Widest reference-logit gap of the served tokens of ``items``
    (and of the float8 reference's first choices, for the control).
    Every sequence is padded to ``max_prompt + max_output`` positions, so
    the reference compiles once; padding after a position never reaches
    it (causal)."""
    length = max_prompt + max_output
    gap, cgap, n_tok = 0.0, 0.0, 0
    for s in items:
        prompt, out = list(s.spec.prompt), list(s.req.committed)
        n = len(out)
        seq = np.zeros((length,), np.int32)
        toks = prompt + out[:-1]
        seq[:len(toks)] = toks
        seq = jnp.asarray(seq)
        lg = ref.logits(params, model, seq)
        q = ref.logits(params, model, seq, quant="fp8") if control else None
        tgt = np.zeros((max_output,), np.int32)
        tgt[:n] = out
        res = _gaps(lg, jnp.int32(len(prompt) - 1), jnp.asarray(tgt),
                    jnp.int32(n), max_output, q)
        gap = max(gap, float(res[0]))
        if control:
            cgap = max(cgap, float(res[1]))
        n_tok += n
        del lg, q
    return {"gap": gap, "control_gap": cgap, "tokens": n_tok}


def reserve(make_engine, items: Sequence, rid_base: int) -> int:
    """Serve ``items`` again, alone, on a fresh engine; count the
    committed streams that differ from the window's."""
    from bench.loop import drain

    specs = [RequestSpec(i, list(s.spec.prompt), s.spec.max_new_tokens, True)
             for i, s in enumerate(items)]
    engine = make_engine()
    again = drain(engine, specs, rid_base)
    del engine
    return sum(list(a.committed) != list(s.req.committed)
               for a, s in zip(again, items))


def verdict(numbers: Dict[str, Dict[str, float]]) -> bool:
    return all(v["value"] is not None and v["value"] <= v["limit"]
               for v in numbers.values())


def describe(numbers: Dict[str, Dict[str, float]]) -> List[str]:
    return [f"check {k}: {v['value']!r} (limit {v['limit']!r})"
            for k, v in numbers.items()]


def empty(limit: Optional[float]) -> Dict[str, float]:
    """A number that could not be read (nothing to compare): it fails."""
    return {"value": None, "limit": limit}
