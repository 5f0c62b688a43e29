"""Control flow: a node that holds a graph.

``_foreach`` is the MXNet control-flow operator (reference, 1.3 on:
src/operator/control_flow.cc ``_foreach``; python/mxnet/symbol/contrib.py
``foreach``), the one registered op whose attr is a sub-Symbol.  The
frontend (``mx.sym.contrib.foreach``, mxnet_tpu/symbol/control_flow.py)
calls a Python body ONCE over placeholder Variables; the Group of what it
returns is the node's ``subgraph``.  The node's inputs are, in order: the
scanned data, the initial states, then every other Variable the body
touched (its *free* variables: the weights), each under its own name, so
the parent graph lists, shapes, initialises, saves and updates each once.

It lowers to one ``jax.lax.scan`` over the interpreter of the sub-Symbol:
the body is traced once however many iterations run, a free variable's
gradient is the sum over the iterations, and with ``remat=True`` the
scanned body is a ``jax.checkpoint``: the backward pass keeps each
iteration's carried state and what is dear to make again (the flash
kernel's output and log-sum-exp, a matmul's or convolution's output where
its contraction is at least its width: ``base.tag_for_remat``) and
recomputes the rest of one iteration's forward at a time.

What a body may hold: any registered op without auxiliary state or
randomness, other ``_foreach`` nodes included.  Refused by name when the
graph is bound (``executor.body_interpreter``), never ignored: a body
with auxiliary states (BatchNorm's moving statistics have no meaning
across iterations of one step) and a body with an RNG op (Dropout: every
iteration would draw the same mask from the node's one key).
"""
from __future__ import annotations

import jax
from jax import lax

from ..base import LOOP_KEPT_NAMES, MXNetError, loop_body
from .nn import _node_name
from .registry import register


@register("_foreach", variadic=True, num_outputs=-1, takes_is_train=True,
          subgraph_attr="subgraph",
          attr_defaults={"num_iter": None, "remat": False})
def _foreach(*ins, subgraph=None, data_names=(), state_names=(),
             free_names=(), num_out_data=0, num_iter=None, remat=False,
             is_train=True, _interpret=None, **kw):
    """Run ``subgraph`` once per slice of the data's leading axis (or
    ``num_iter`` times where there is no data), carrying the states.

    Inputs: ``len(data_names)`` arrays scanned over axis 0, then
    ``len(state_names)`` initial states, then the body's free variables in
    ``free_names`` order.  The sub-Symbol's outputs are ``num_out_data``
    per-iteration outputs followed by the new states (cast to the carried
    state's dtype, which a scan fixes).  Returns the per-iteration outputs
    stacked on a new leading axis, then the states as they end."""
    from .. import profiler, tracing
    from ..executor import body_interpreter
    nd, ns = len(data_names), len(state_names)
    data, states, free = ins[:nd], ins[nd:nd + ns], ins[nd + ns:]
    node = _node_name()
    if len(free) != len(free_names):
        raise MXNetError(
            f"_foreach {node!r}: {len(ins)} inputs for {nd} data, {ns} "
            f"states and {len(free_names)} free variables")
    if data:
        iters = data[0].shape[0]
        if any(d.shape[0] != iters for d in data):
            raise MXNetError(
                f"_foreach {node!r}: data disagree on the scanned axis: "
                f"{[tuple(d.shape) for d in data]}")
    elif num_iter is None:
        raise MXNetError(f"_foreach {node!r}: no data and no num_iter")
    else:
        iters = int(num_iter)
    run = _interpret or body_interpreter(subgraph, None, node)
    arg_names = subgraph.list_arguments()
    num_out_data = int(num_out_data)
    # {name: bytes} of what the body's checkpoint keeps of one iteration,
    # filled by base.tag_for_remat while the body is traced
    kept = {} if remat else None

    def iteration(free, carry, xs):
        # under a trace: once per program that holds the node, not once
        # per iteration (chipbench: loop_body_traces)
        profiler.record_dispatch("loop.body_trace")
        if kept is not None:
            kept.clear()        # a scan may trace its body a second time
        vals = dict(zip(free_names, free))
        vals.update(zip(data_names, xs))
        vals.update(zip(state_names, carry))
        outs, _ = run(tuple(vals[n] for n in arg_names), (), None, is_train)
        new = tuple(o.astype(c.dtype)
                    for o, c in zip(outs[num_out_data:], carry))
        return new, tuple(outs[:num_out_data])

    if remat:
        iteration = jax.checkpoint(
            iteration, policy=jax.checkpoint_policies.save_only_these_names(
                *LOOP_KEPT_NAMES))
    # the operators name what is kept while the body is traced, and the
    # attention op when the scan is differentiated: both inside this call
    with loop_body(kept):
        final, stacked = lax.scan(lambda c, xs: iteration(free, c, xs),
                                  tuple(states), tuple(data), length=iters)
    tracing.instant("mx.loop.lower", "ops", args={
        "node": node, "iterations": iters, "remat": bool(remat),
        "carried": [[list(s.shape), str(s.dtype)] for s in states],
        "weights_lifted": len(free_names), "kept": dict(kept or {})})
    return tuple(stacked) + tuple(final)
