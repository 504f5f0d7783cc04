"""Jaxpr internals the plan builder and the compiled executor rely on.

Code outside ``repro/compat`` never touches ``jax._src`` (the
``compat-surface`` lint); it goes through these functions. Written for the
installed JAX release only.
"""

from __future__ import annotations

from jax._src import core as _src_core


def fresh_var(aval):
    """A new jaxpr ``Var`` of the given aval."""
    return _src_core.Var(aval)


def is_dropvar(v) -> bool:
    """True for the ``_`` outvar of an eqn whose result nothing reads."""
    return isinstance(v, _src_core.DropVar)


def eval_jaxpr(jaxpr, consts, *args):
    """Evaluate an open jaxpr with its consts on concrete or traced args."""
    return _src_core.eval_jaxpr(jaxpr, consts, *args)


def get_aval(x):
    """The abstract value (shape, dtype, weak type) of an array or scalar."""
    return _src_core.get_aval(x)
