"""Shared recursive jaxpr walker.

One walker for every structural audit in the repo (the rule classes in
``audit.rules`` and the jaxpr-level acceptance tests).  It descends into
every sub-jaxpr an equation carries in its params — ``scan`` / ``while`` /
``cond`` branches, ``jit``, ``custom_jvp``/``custom_vjp`` callables,
``remat`` (``checkpoint``) bodies — because all of them store their bodies
as ``Jaxpr`` / ``ClosedJaxpr`` values (possibly inside lists or tuples).

``pallas_call`` is the one exception: its body is a hand-written kernel
whose inner program is *supposed* to gather, multiply indices, and copy
tiles — auditing it with graph-level rules would be meaningless.  The
walker surfaces the ``pallas_call`` equation itself as an opaque audited
leaf and does not descend, so a census counts kernel dispatches, not
kernel internals.
"""
from __future__ import annotations

from collections import Counter
from typing import Iterator

from jax.extend.core import ClosedJaxpr, Jaxpr

# Primitives surfaced as opaque leaves: yielded, never descended into.
OPAQUE_PRIMITIVES = frozenset({"pallas_call"})


def iter_eqns(jaxpr) -> Iterator:
    """Yield every equation in ``jaxpr`` and (recursively) its sub-jaxprs.

    Accepts a ``Jaxpr`` or a ``ClosedJaxpr`` (``jax.make_jaxpr`` returns
    the latter).  Equations whose primitive is in :data:`OPAQUE_PRIMITIVES`
    are yielded but not descended into.
    """
    if isinstance(jaxpr, ClosedJaxpr):
        jaxpr = jaxpr.jaxpr
    for eqn in jaxpr.eqns:
        yield eqn
        if eqn.primitive.name in OPAQUE_PRIMITIVES:
            continue
        for v in eqn.params.values():
            sub = v if isinstance(v, (list, tuple)) else (v,)
            for s in sub:
                if isinstance(s, (ClosedJaxpr, Jaxpr)):
                    yield from iter_eqns(s)


def as_eqns(jaxpr_or_eqns) -> list:
    """Materialise the recursive equation list once.

    Pass-through for an already-materialised ``list`` of equations, so every
    rule pass over one audit point shares a single walk of the trace
    (``points.trace_point`` builds the lists; ``--point`` runs lean on them).
    """
    if isinstance(jaxpr_or_eqns, list):
        return jaxpr_or_eqns
    return list(iter_eqns(jaxpr_or_eqns))


def op_census(jaxpr) -> dict[str, int]:
    """Primitive name -> occurrence count over the whole (recursive) program.

    Sorted by name so the result is JSON-stable — the audit manifest diffs
    censuses across commits to catch silent graph drift.  Accepts a jaxpr
    or a pre-walked equation list (see :func:`as_eqns`).
    """
    counts = Counter(eqn.primitive.name for eqn in as_eqns(jaxpr))
    return dict(sorted(counts.items()))
