"""``mx.sym.contrib.foreach``: a loop in the Symbol graph (reference:
python/mxnet/symbol/contrib.py ``foreach``).  The operator, with what a
body may hold and what is refused, is ``ops/control_flow.py``."""
from __future__ import annotations

from ..base import MXNetError
from .. import name as _name
from .. import attribute as _attribute
from .symbol import Symbol, Node, Group, var


def _as_list(x, what):
    if x is None:
        return [], False
    if isinstance(x, Symbol):
        return [x], False
    if isinstance(x, (list, tuple)) and all(isinstance(s, Symbol) for s in x):
        return list(x), True
    raise TypeError(f"foreach: {what} must be a Symbol or a list of Symbols")


def foreach(body, data, init_states, name=None, num_iter=None, remat=False):
    """Run ``body`` over the leading axis of ``data``, carrying states.

    ``body(data_t, states) -> (outs, new_states)`` is a Python callable
    over Symbols and is called ONCE, with placeholder Variables: what it
    builds becomes the sub-Symbol the node holds.  ``data`` and
    ``init_states`` are a Symbol or a list of Symbols (``body`` receives
    the same form); ``data`` may be ``None``, with ``num_iter`` iterations
    in its place, and ``body`` then receives ``None``.  Every other
    Variable the body touches (its weights) becomes an input of the node
    under its own name, used by every iteration; its gradient is the sum
    over the iterations.  A body may not use a non-Variable Symbol from
    outside: pass it as data or as a state.

    Returns ``(outs, states)``: each per-iteration output stacked on a new
    leading axis, and the states as they end, in the forms ``body``
    returned.  ``remat=True`` (this body's activations do not fit) makes
    the backward pass keep of each iteration only what is dear to make
    again -- the flash kernel's output and log-sum-exp, and the output of
    a matmul or convolution whose contraction is at least its width -- and
    recompute the rest of one iteration's forward at a time
    (``jax.checkpoint`` of the scanned body, ``base.tag_for_remat``)."""
    data_syms, data_is_list = _as_list(data, "data")
    state_syms, states_is_list = _as_list(init_states, "init_states")
    if not data_syms and num_iter is None:
        raise MXNetError("foreach: give data to scan over, or num_iter")
    name = _name.current().get(name, "foreach")
    data_vars = [var(f"{name}_data{i}") for i in range(len(data_syms))]
    state_vars = [var(f"{name}_state{i}") for i in range(len(state_syms))]
    outs, new_states = body(
        (data_vars if data_is_list else data_vars[0]) if data_vars else None,
        state_vars if states_is_list else state_vars[0])
    out_syms, outs_is_list = _as_list(outs, "the body's outputs")
    new_syms, _ = _as_list(new_states, "the body's new states")
    if len(new_syms) != len(state_syms):
        raise MXNetError(
            f"foreach {name!r}: the body returned {len(new_syms)} states "
            f"for {len(state_syms)} initial ones")
    sub = Group(out_syms + new_syms)
    if len(sub) != len(out_syms) + len(new_syms):
        raise MXNetError(f"foreach {name!r}: every output and state of the "
                         "body must be a single-output Symbol")
    placeholders = {v.name for v in data_vars + state_vars}
    free = [n for n in sub.nodes()
            if n.is_variable and n.name not in placeholders]
    attrs = {"subgraph": sub,
             "data_names": tuple(v.name for v in data_vars),
             "state_names": tuple(v.name for v in state_vars),
             "free_names": tuple(n.name for n in free),
             "num_out_data": len(out_syms), "remat": bool(remat)}
    if num_iter is not None:
        attrs["num_iter"] = int(num_iter)
    heads = [h for s in data_syms + state_syms for h in s.heads]
    if len(heads) != len(data_syms) + len(state_syms):
        raise MXNetError(f"foreach {name!r}: data and init_states must be "
                         "single-output Symbols")
    node = Node("_foreach", name, attrs, heads + [(n, 0) for n in free],
                _attribute.current().get(None))
    n_out = len(out_syms)
    stacked = [Symbol([(node, i)]) for i in range(n_out)]
    final = [Symbol([(node, n_out + i)]) for i in range(len(new_syms))]
    return (stacked if outs_is_list else (stacked[0] if stacked else None),
            final if states_is_list else final[0])
