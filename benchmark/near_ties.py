"""A routed layer's comparison and the router's tie-break: the ONE rule
(PR 62), which the seven routed configurations import.

A router ranks a token's experts by a score that is a float32 sum of
``d`` products (``d`` = 2048-2560), and the reference forms the same sum
in a separately compiled program. Where a token's k-th and (k+1)-th
scores agree to the last bits, the two programs' summation ORDER decides
which expert is chosen, and no PR controls that order (the compiler
tiles each program's product from what else the program holds). The
router's own comparison already grants such a token
(``router_set_mismatch_share`` against ``router_set_tol``); the LAYER's
comparison then held the system's output to a reference that had chosen
another expert: a whole expert's worth of one token, ``routed_token_err``
~0.48 against 0.075, with the layer right (PR 40: one lfm2 run of eleven;
PR 61: one smallthinker run of seven).

The rule, for a token ``t`` with ``s[t, e]`` the reference's own
selection scores (what its ``top_k`` ranks), ``R(t)`` the reference's
set, ``S(t)`` the system's and ``theta(t)`` the reference's k-th largest
score:

1. ``t`` is NEAR-TIED iff ``S(t) != R(t)`` and every expert in the
   symmetric difference has ``|s[t, e] - theta(t)| <= NEAR_TIE_MARGIN x
   rms(s)`` (the layer's root mean square score: one scale a layer).
2. The layer's comparisons evaluate the reference on ``S(t)`` where
   ``t`` is near-tied and on ``R(t)`` everywhere else, with the weights
   from the reference's OWN scores. No token is skipped: a near-tied
   token's experts are still held to the worst-token limit, on the set
   the system chose.
3. A token whose sets differ and is not near-tied is compared as before
   (on ``R(t)``: it reads ~0.5 and fails the run). The router's own
   comparisons are computed from ``R`` as before.

``NEAR_TIE_MARGIN``, ONE constant, is what float32 summation order can
move the gap between two scores by, in units of ``rms(s)``. Adding ``d``
terms of random sign one after the other, the partial sum after ``i``
terms is ``sqrt(i / d)`` of the score's size and each addition rounds it
by at most ``u = 2**-24`` of itself, uniformly: a variance of ``u**2 / 3
x i / d`` an addition, ``u**2 d / 6`` a score. One chain of ``d``
additions is the longest any order has (a tiled order's chains are
shorter and it errs less), so ``u sqrt(d / 6)`` bounds a score's
standard error in ANY order. A flip takes the reference's gap under the
difference of two scores' errors in two programs, four such errors: ``2
u sqrt(d / 6)``, and four standard deviations of that at the widest
router input, ``d`` = 2560, is

    8 x 2**-24 x sqrt(2560 / 6) = 9.85e-6,

under the 1e-5 that ISSUE 62 caps it at and under what the accepted
``router_weight_atol`` (5e-5) already grants a score. It was NOT fitted
to a run. The readings it was checked against afterwards (my chip runs,
PR 62, TPU v5 lite; ``PERF.md`` section 6 has every cell's):

- the token that flipped under PR 61's program (smallthinker, seed
  2610000503, third routed layer): in a program that holds the four
  routers together the reference's two scores are EQUAL to the last bit
  (gap 0.0: its ``top_k`` takes the lower index, the system's sum had
  the other ahead); in two other programs that layer's sets agree and
  its nearest pair stands 4.6e-7 x rms apart: 1 / 20 of the margin, and
  what one order moves a score by;
- the smallest lead of a k-th score over the (k+1)-th among the tokens
  whose sets AGREED, which every run prints (``routed_least_gap``):
  the smallest over a cell's runs 1.1e-7 (kimi), 2.2e-7 (glm), 2.3e-7
  (smallthinker), 3.2e-7 (qwen3-next), 4.8e-7 (keye), and 0.0 in lfm2
  (every run) and laguna (one of two), whose sigmoid scores near 0.5 are
  6e-8 apart so that EXACT ties are met. With 16 384 tokens a layer the
  nearest pair of scores is of one ulp's order, which is why a flip is
  met one run in seven wherever two programs sum in different orders;
- a swap planted at 100 x the margin fails, three near-tied tokens pass
  and are still compared, six of 4096 are refused
  (``benchmark/tests/test_near_ties.py``).
"""

from __future__ import annotations

from typing import NamedTuple

NEAR_TIE_MARGIN = 8 * 2.0**-24 * (2560 / 6) ** 0.5  # 9.85e-6
# The keys of ``compared`` (a layer's worst evaluation, then the worst
# layer): the largest of all but ``LEAST``, which is a smallest.
TOKENS, GAP, LEAST = (
    "routed_near_tied_tokens", "routed_near_tie_gap", "routed_least_gap"
)


class Ties(NamedTuple):
    """One evaluation's near-ties: how many tokens were compared on the
    system's set, the largest gap among them over ``rms(s)`` (0 with
    none), and the smallest lead of the k-th score over the best score
    outside the set among the tokens whose sets agree."""

    tokens: object
    gap: object
    least: object


def settle(scores, reference, system):
    """``scores`` [tokens, experts]: the reference's own selection
    scores; ``reference`` [tokens, k]: the experts its ``top_k`` chose;
    ``system`` [tokens, k]: the system's, in any order. Returns (the
    sets to evaluate the reference on: the system's where the token is
    near-tied, the reference's elsewhere; ``Ties``)."""
    import jax
    import jax.numpy as jnp

    scores = jax.lax.stop_gradient(scores)
    system = system.reshape(reference.shape)
    experts = jnp.arange(scores.shape[-1])

    def held(sets):  # [tokens, experts]: is the expert in the set
        return (sets[..., None] == experts).any(-2)

    ours, theirs = held(reference), held(system)
    disputed = ours != theirs
    differ = disputed.any(-1)
    theta = jnp.min(jnp.where(ours, scores, jnp.inf), -1, keepdims=True)
    scale = jnp.sqrt(jnp.mean(scores**2))
    gap = jnp.max(
        jnp.where(disputed, jnp.abs(scores - theta), 0.0), -1
    ) / scale
    near = differ & (gap <= NEAR_TIE_MARGIN)
    lead = (
        theta[..., 0] - jnp.max(jnp.where(ours, -jnp.inf, scores), -1)
    ) / scale
    return jnp.where(near[..., None], system, reference), Ties(
        tokens=near.sum(),
        gap=jnp.max(jnp.where(near, gap, 0.0)),
        least=jnp.min(
            jnp.where(differ, jnp.finfo(scores.dtype).max, lead)
        ),
    )


def worst(*ties: Ties) -> dict:
    """Several evaluations' near-ties (a layer's forward and backward)
    under the keys of ``compared``; nothing where none was settled."""
    import jax.numpy as jnp

    if not ties:
        return {}
    return {
        TOKENS: jnp.max(jnp.stack([t.tokens for t in ties])),
        GAP: jnp.max(jnp.stack([t.gap for t in ties])),
        LEAST: jnp.min(jnp.stack([t.least for t in ties])),
    }


def worst_layer(found: list[dict]) -> dict:
    """The worst layer's reading under every key of the layers'
    results: the largest, of ``LEAST`` the smallest."""
    return {
        k: (min if k == LEAST else max)(float(f[k]) for f in found)
        for k in found[0]
    }


def within(result: dict, router_set_tol: float, tokens: int) -> bool:
    """Whether a check's near-tied tokens stay inside the margin and
    inside what the router's own comparison grants a layer:
    ``router_set_tol`` x tokens."""
    return bool(
        result[GAP] <= NEAR_TIE_MARGIN
        and result[TOKENS] <= router_set_tol * tokens
    )
