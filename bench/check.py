"""The check that decides ``correct``.

Once the window has closed, a sample of the requests it finished, drawn
from the seed and always holding the longest, is run through the
configuration's plain reference (``references/<family>.py``, which the
caller passes in), each prompt with its served tokens in one pass.  For every served token, the gap is how far its reference logit
lies below the reference's best logit at that position: 0 where the
served greedy token is the reference's own choice.  The widest gap over
the sample is compared with the cell's limit (``limits/<cell>.json``).

The control, read by ``control.py`` and never by a benchmark run, puts
the reference computed with fp8 matmul operands in the program's place:
at the same positions, the gap of the token that it puts first.
"""

from __future__ import annotations

from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from bench import traffic


@jax.jit
def _widest(ref_logits, targets):
    """Widest gap ``best - logit[target]`` over rows with a target."""
    best = ref_logits.max(-1)
    got = jnp.take_along_axis(ref_logits, jnp.maximum(targets, 0)[:, None],
                              -1)[:, 0]
    return jnp.max(jnp.where(targets >= 0, best - got, 0.0))


@jax.jit
def _argmax_where(logits, targets):
    return jnp.where(targets >= 0, jnp.argmax(logits, -1), -1)


def sample(finished: list, seed: int, tokens: int) -> list:
    """The longest finished request (prompt and answer), then others in
    an order drawn from the seed, until ``tokens`` served tokens."""
    if not finished:
        return []
    longest = max(finished, key=lambda r: (len(r.prompt) + len(r.tokens),
                                           r.rid))
    rest = [r for r in finished if r is not longest]
    order = traffic.rng_of(seed, 3).permutation(len(rest))
    out, n = [longest], len(longest.tokens)
    for i in order:
        if n >= tokens:
            break
        out.append(rest[i])
        n += len(rest[i].tokens)
    return out


def widest_gaps(reference, model: dict, params, reqs: list,
                control: bool = False
                ) -> Tuple[float, Optional[float], int]:
    """(widest gap of the served tokens, widest gap of the control's
    tokens or None, tokens compared) over ``reqs``, by ``reference``, the
    module of the family that the configuration names, on its ``model``
    block."""
    gap, gap_c, n = 0.0, None, 0
    for r in reqs:
        seq = list(r.prompt) + list(r.tokens[:-1])
        lg = reference.logits(model, params, seq)
        targets = np.full(lg.shape[0], -1, np.int32)
        p = len(r.prompt)
        targets[p - 1:p - 1 + len(r.tokens)] = r.tokens
        t = jnp.asarray(targets)
        gap = max(gap, float(_widest(lg, t)))
        if control:
            lc = reference.logits(model, params, seq, quant=True)
            gc = float(_widest(lg, _argmax_where(lc, t)))
            gap_c = gc if gap_c is None else max(gap_c, gc)
            del lc
        n += len(r.tokens)
        del lg
    return gap, gap_c, n
