"""Jaxpr/HLO introspection helpers for the hot-path auditor.

These are the mechanical layers the hotpath rules build on:

* :func:`iter_eqns` — walk a (Closed)Jaxpr recursively through call/scan/
  shard_map sub-jaxprs hidden in ``eqn.params``.
* :func:`collective_census` — count collective primitives by normalized
  name (jax suffixes channel indices, e.g. ``psum`` lowers as ``psum2``
  inside ``shard_map``; we strip trailing digits so contracts stay
  stable across jax versions).
* :func:`forbidden_primitives` — host-callback / transfer primitives
  that break the zero-sync claim if they appear in a serving step.
* :func:`donation_alias_count` — parse the compiled HLO module header's
  ``input_output_alias={...}`` and count actual aliases. jax silently
  *prunes* unusable donations (no warning), so the only reliable check
  is alias-count == donated-leaf-count.
* :func:`jaxpr_dtypes` — the set of dtypes appearing anywhere in the
  jaxpr (vars and literals), for the no-f64 / layout rules.
"""

from __future__ import annotations

import re
from typing import Dict, Iterator, List, Sequence, Set

import jax
import jax.numpy as jnp
from jax.extend import core as jax_core

# Normalized primitive names that perform cross-device communication.
# pbroadcast is deliberately absent: shard_map inserts it as replication
# *bookkeeping* (it lowers to identity — no data ever moves), so counting
# it would make the census a function of the rep-rule checker, not of
# the program's real collectives.
COLLECTIVE_NAMES = frozenset({
    "psum", "pmax", "pmin", "pmean", "all_gather", "all_to_all",
    "psum_scatter", "reduce_scatter", "ppermute",
})

# Normalized primitive-name fragments that imply a host round-trip or an
# explicit transfer — none of these may appear in a zero-sync step.
FORBIDDEN_FRAGMENTS = ("callback", "infeed", "outfeed", "device_put")


def _normalize(name: str) -> str:
    """Strip jax's trailing channel-index digits: ``psum2`` -> ``psum``."""
    return re.sub(r"\d+$", "", name)


def _sub_jaxprs(params: Dict) -> Iterator[jax_core.Jaxpr]:
    for val in params.values():
        vals = val if isinstance(val, (list, tuple)) else (val,)
        for v in vals:
            if isinstance(v, jax_core.ClosedJaxpr):
                yield v.jaxpr
            elif isinstance(v, jax_core.Jaxpr):
                yield v


def iter_eqns(jaxpr) -> Iterator[jax_core.JaxprEqn]:
    """Depth-first walk over every equation, including nested jaxprs."""
    if isinstance(jaxpr, jax_core.ClosedJaxpr):
        jaxpr = jaxpr.jaxpr
    for eqn in jaxpr.eqns:
        yield eqn
        for sub in _sub_jaxprs(eqn.params):
            yield from iter_eqns(sub)


def primitive_names(jaxpr) -> List[str]:
    return [_normalize(eqn.primitive.name) for eqn in iter_eqns(jaxpr)]


def collective_census(jaxpr) -> Dict[str, int]:
    """Normalized-name -> count for every collective in the jaxpr."""
    census: Dict[str, int] = {}
    for name in primitive_names(jaxpr):
        if name in COLLECTIVE_NAMES:
            census[name] = census.get(name, 0) + 1
    return census


def forbidden_primitives(jaxpr) -> List[str]:
    """Host-sync / transfer primitive names present in the jaxpr."""
    hits = []
    for name in primitive_names(jaxpr):
        if any(frag in name for frag in FORBIDDEN_FRAGMENTS):
            hits.append(name)
    return hits


def jaxpr_dtypes(jaxpr) -> Set[str]:
    """Every dtype appearing on a var or literal anywhere in the jaxpr."""
    dtypes: Set[str] = set()
    if isinstance(jaxpr, jax_core.ClosedJaxpr):
        closed = jaxpr
        jaxpr = jaxpr.jaxpr
        for const in closed.consts:
            aval = jax.typeof(const)
            if hasattr(aval, "dtype"):
                dtypes.add(str(aval.dtype))

    def visit(jx: jax_core.Jaxpr) -> None:
        for v in list(jx.invars) + list(jx.outvars) + list(jx.constvars):
            aval = getattr(v, "aval", None)
            if aval is not None and hasattr(aval, "dtype"):
                dtypes.add(str(aval.dtype))
        for eqn in jx.eqns:
            for v in list(eqn.invars) + list(eqn.outvars):
                aval = getattr(v, "aval", None)
                if aval is not None and hasattr(aval, "dtype"):
                    dtypes.add(str(aval.dtype))
            for sub in _sub_jaxprs(eqn.params):
                visit(sub)

    visit(jaxpr)
    return dtypes


_ALIAS_RE = re.compile(r"input_output_alias=\{(.*?)\},\s*entry_computation_layout",
                       re.DOTALL)


def donation_alias_count(compiled_text: str) -> int:
    """Number of input->output aliases in a compiled HLO module header.

    jax expresses honoured donations as
    ``input_output_alias={ {0}: (1, {}, may-alias), ... }``; a donated
    buffer that could not be aliased is simply absent (pruned without
    warning), which is why the auditor counts instead of trusting
    ``donate_argnums``.
    """
    m = _ALIAS_RE.search(compiled_text)
    if m is None:
        return 0
    return m.group(1).count(": (")


def count_donated_leaves(args: Sequence, donate_argnums: Sequence[int]) -> int:
    """Flat array-leaf count across the donated positional arguments."""
    total = 0
    for i in donate_argnums:
        total += len(jax.tree_util.tree_leaves(args[i]))
    return total


def compiled_text(jitted, *args) -> str:
    """Lowered+compiled HLO text for a jitted callable at these args."""
    return jitted.lower(*args).compile().as_text()


def closed_jaxpr(jitted, *args):
    return jax.make_jaxpr(jitted)(*args)


def abstractify(tree):
    """Shape/dtype skeleton of a pytree (for eval_shape-style tracing)."""
    return jax.tree_util.tree_map(
        lambda x: jax.ShapeDtypeStruct(jnp.shape(x), jnp.result_type(x)), tree)
