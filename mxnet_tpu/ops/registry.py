"""Single-path operator registry.

The reference has two op-registration generations (legacy OperatorProperty and
NNVM attrs — SURVEY.md §2.2, include/mxnet/op_attr_types.h:184-263) bridged by
src/nnvm/legacy_op_util.cc.  Here there is exactly ONE path: an ``OpDef``
holding a pure JAX implementation plus metadata.  The same definition serves

* the imperative frontend (``mx.nd.*`` — eager dispatch, autograd tape),
* the symbolic frontend (``mx.sym.*`` — graph nodes replayed under jit),
* shape/dtype inference (via ``jax.eval_shape`` — the XLA-native equivalent of
  the reference's FInferShape/FInferType passes,
  src/executor/infer_graph_attr_pass.cc:368,386).

Implementation functions are *pure*: ``fn(*inputs, **attrs) -> array | tuple``
on jax.Arrays.  Ops that draw randomness declare ``needs_rng`` and receive a
PRNG key as leading argument — the key is threaded explicitly so traced graphs
stay pure (the TPU-native replacement for the reference's per-device PRNG
resource, src/resource.cc kRandom).  Ops with mutable auxiliary state
(BatchNorm moving stats) declare ``num_aux``: in training mode the impl
returns ``num_aux`` extra trailing outputs which the frontends write back into
the aux arrays — the functional replacement for in-kernel aux mutation.
"""
from __future__ import annotations

import functools
from dataclasses import dataclass, field
from typing import Callable, Dict, List, Optional, Sequence

from ..base import MXNetError

_OP_REGISTRY: Dict[str, "OpDef"] = {}

# Names of ops that have actually executed (imperative dispatch or symbolic
# trace) in this process.  Consumed by the test suite's registry-coverage
# gate: an op counts as covered only if it genuinely ran, not if its name
# merely appears in a test file (the reference enforces coverage the same
# way — by running tests/python/unittest/test_operator.py over every op).
EXECUTED_OPS: set = set()


def record_execution(name: str) -> None:
    EXECUTED_OPS.add(name)


@dataclass
class OpDef:
    name: str
    fn: Callable  # pure jax impl
    num_outputs: int = 1  # -1 = variadic (determined at call time)
    # how many outputs the *imperative* frontend returns (reference:
    # num_visible_outputs in imperative dispatch — e.g. Dropout exposes only
    # `out`, not the mask, when called eagerly)
    num_visible: Optional[int] = None
    needs_rng: bool = False
    num_aux: int = 0  # trailing inputs that are mutable aux states
    # grad of outputs flows only when True (e.g. argmax has no grad)
    differentiable: bool = True
    # when set, the op is train/eval polymorphic: impl takes is_train kwarg
    takes_is_train: bool = False
    # names of data inputs for symbol composition, e.g. ["data","weight","bias"]
    arg_names: Optional[List[str]] = None
    aux_names: Optional[List[str]] = None
    # attrs with defaults for introspection / docs
    attr_defaults: Dict[str, object] = field(default_factory=dict)
    doc: str = ""
    # variadic input op (Concat, add_n, ...): single list input
    variadic: bool = False
    # name of the attr that holds a sub-Symbol (control flow: the node holds
    # a graph).  The executor hands such an op ``_interpret``, the
    # interpreter of that sub-Symbol built with the parent's compute dtype;
    # tojson / load_json write and read the attr as a nested graph
    subgraph_attr: Optional[str] = None

    def __call__(self, *args, **kwargs):
        return self.fn(*args, **kwargs)


def register(name, *, num_outputs=1, needs_rng=False, num_aux=0,
             differentiable=True, takes_is_train=False, arg_names=None,
             aux_names=None, attr_defaults=None, variadic=False,
             aliases=(), num_visible=None, subgraph_attr=None):
    """Decorator: register a pure-jax op implementation under an MXNet name."""
    def _reg(fn):
        op = OpDef(name=name, fn=fn, num_outputs=num_outputs,
                   num_visible=num_visible,
                   needs_rng=needs_rng, num_aux=num_aux,
                   differentiable=differentiable,
                   takes_is_train=takes_is_train,
                   arg_names=list(arg_names) if arg_names else None,
                   aux_names=list(aux_names) if aux_names else None,
                   attr_defaults=dict(attr_defaults or {}),
                   doc=fn.__doc__ or "", variadic=variadic,
                   subgraph_attr=subgraph_attr)
        if name in _OP_REGISTRY:
            raise MXNetError(f"op {name!r} registered twice")
        _OP_REGISTRY[name] = op
        for a in aliases:
            _OP_REGISTRY[a] = op
        return fn
    return _reg


def alias(new_name: str, existing: str):
    _OP_REGISTRY[new_name] = _OP_REGISTRY[existing]


def get(name: str) -> OpDef:
    try:
        return _OP_REGISTRY[name]
    except KeyError:
        raise MXNetError(f"operator {name!r} is not registered")


def find(name: str) -> Optional[OpDef]:
    return _OP_REGISTRY.get(name)


def list_ops() -> List[str]:
    return sorted(_OP_REGISTRY)


def op_count() -> int:
    return len({id(v) for v in _OP_REGISTRY.values()})


def build_op_doc(opdef, name, flavor="nd"):
    """Rich docstring for an auto-generated wrapper: synthesized
    signature (inputs + attrs with defaults) followed by the registered
    doc (register() takes it from the implementing function's docstring,
    which carries the reference file:line citations).  The TPU answer to
    the reference's introspected dmlc-Parameter docs
    (MXSymbolGetAtomicSymbolInfo → generated Python signatures)."""
    args = list(opdef.arg_names or []) + list(opdef.aux_names or [])
    if opdef.variadic:
        args = ["*args"]
    parts = args + ["%s=%r" % (k, v)
                    for k, v in (opdef.attr_defaults or {}).items()]
    parts.append("out=None" if flavor == "nd" else "name=None")
    lines = ["%s(%s)" % (name, ", ".join(parts))]
    body = (opdef.doc or "").strip()
    if body:
        lines += ["", body]
    lines += ["", "Registered op %r (auto-generated %s wrapper)."
              % (opdef.name, "mx.nd" if flavor == "nd" else "mx.sym")]
    return "\n".join(lines)
