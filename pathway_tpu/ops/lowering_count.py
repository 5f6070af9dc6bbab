"""Which lowering a compiled program took, counted where it is decided.

An operation with two lowerings (``jax.lax.platform_dependent``: a TPU
kernel, and plain JAX for every other backend) traces both and picks one
when the program is lowered, so the count is made there: :func:`took` is
an identity on the operation's result whose lowering rule (or eager
evaluation) notes the operation's ``family`` and the ``lowering`` taken.
``ops/deltanet.py`` (the delta-rule scan) and ``ops/attention.py`` (the
blocked attention) use it; ``/metrics`` shows the counts.
"""

from __future__ import annotations

import jax.extend
from jax.interpreters import ad, batching, mlir

_COUNTS: dict[tuple[str, str], int] = {}

_took_p = jax.extend.core.Primitive("lowering_took")
_took_p.def_abstract_eval(lambda x, *, family, lowering: x)


@_took_p.def_impl
def _note(x, *, family: str, lowering: str):
    _COUNTS[family, lowering] = _COUNTS.get((family, lowering), 0) + 1
    return x


mlir.register_lowering(
    _took_p, lambda ctx, x, *, family, lowering: [
        _note(x, family=family, lowering=lowering)],
    cacheable=False)
# the operation stays what it was under ``grad`` and ``vmap``
ad.deflinear2(_took_p, lambda ct, x, *, family, lowering: [ct])
batching.defvectorized(_took_p)


def took(x, family: str, lowering: str):
    """``x``, with one count for (``family``, ``lowering``) wherever this
    line is lowered into a program or evaluated eagerly."""
    return _took_p.bind(x, family=family, lowering=lowering)


def counts(family: str, lowerings: tuple[str, ...]) -> dict:
    """Operations of ``family`` lowered in this process, by lowering (one
    count an operation of a compiled program; an eager call counts as
    one)."""
    return {name: _COUNTS.get((family, name), 0) for name in lowerings}
