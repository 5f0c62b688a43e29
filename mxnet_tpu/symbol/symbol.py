"""Symbol: the declarative graph frontend.

TPU-native equivalent of the reference's nnvm ``Symbol``/``Graph``
(python/mxnet/symbol/symbol.py; nnvm op graph built by
src/c_api/c_api_symbolic.cc).  A Symbol is a list of (node, output-index)
heads over a DAG of ``Node`` objects.  Unlike the reference there is no
C++ graph IR — the graph *is* the trace program: binding a symbol builds a
pure jax function that an :class:`~mxnet_tpu.executor.Executor` jit-compiles
(the XLA-native replacement for GraphExecutor's memory planning / op bulking,
src/executor/graph_executor.cc:507-1456 — XLA buffer assignment and fusion
subsume both).

Graph JSON save/load mirrors the nnvm JSON layout (nodes / arg_nodes /
heads — nnvm SaveJSON as used by mx.model.save_checkpoint, model.py:340) so
checkpoints remain structurally familiar.
"""
from __future__ import annotations

import json
import numbers
from typing import Dict, List, Optional, Sequence, Tuple

import numpy as np
import jax
import jax.numpy as jnp

from ..base import MXNetError
from .. import name as _name
from .. import attribute as _attribute
from ..ops import registry as _reg


class Node:
    """One graph node: an op application or (op=None) a variable."""
    __slots__ = ("op", "name", "attrs", "inputs", "_user_attrs")

    def __init__(self, op: Optional[str], name: str, attrs: dict,
                 inputs: List[Tuple["Node", int]], user_attrs=None):
        self.op = op
        self.name = name
        self.attrs = attrs
        self.inputs = inputs
        self._user_attrs = dict(user_attrs or {})

    @property
    def is_variable(self):
        return self.op is None

    def opdef(self) -> Optional[_reg.OpDef]:
        return _reg.get(self.op) if self.op else None

    def num_outputs(self) -> int:
        return node_num_outputs(self)


def node_num_outputs(node: Node) -> int:
    if node.op is None:
        return 1
    opdef = _reg.get(node.op)
    n = opdef.num_visible if opdef.num_visible is not None else opdef.num_outputs
    if callable(n):  # attr-dependent (reference NumVisibleOutputs)
        n = n(node.attrs)
    if n == -1:
        # attr-dependent output count (reference: SliceChannel num_outputs)
        if node.op in ("SliceChannel", "split"):
            return int(node.attrs.get("num_outputs", 1))
        if node.op == "topk":
            return 2 if node.attrs.get("ret_typ", "indices") == "both" else 1
        if node.op == "RNN":
            return 3 if node.attrs.get("state_outputs") else 1
        if node.op == "Custom":
            from .. import operator as _custom_mod
            return _custom_mod.num_outputs_for(node.attrs)
        if node.op == "_foreach":
            return int(node.attrs["num_out_data"]) \
                + len(node.attrs["state_names"])
        return 1
    return n


def _topo_sort(heads: Sequence[Tuple[Node, int]]) -> List[Node]:
    order: List[Node] = []
    visited = set()

    def visit(node):
        stack = [(node, False)]
        while stack:
            n, processed = stack.pop()
            if processed:
                order.append(n)
                continue
            if id(n) in visited:
                continue
            visited.add(id(n))
            stack.append((n, True))
            for inp, _ in reversed(n.inputs):
                if id(inp) not in visited:
                    stack.append((inp, False))

    for node, _ in heads:
        visit(node)
    return order


# ---------------------------------------------------------------------------
# parameter-shape inference hooks (reference: per-op InferShape filling
# unknown arg shapes, src/executor/infer_graph_attr_pass.cc:368; e.g.
# FullyConnectedProp::InferShape derives weight from data × num_hidden)
# ---------------------------------------------------------------------------
def _fc_param_shapes(attrs, in_shapes):
    data = in_shapes.get("data")
    if data is None:
        return {}
    nh = int(attrs.get("num_hidden", 0))
    flatten = attrs.get("flatten", True)
    in_dim = int(np.prod(data[1:])) if flatten else data[-1]
    out = {"weight": (nh, in_dim)}
    if not attrs.get("no_bias", False):
        out["bias"] = (nh,)
    return out


def _conv_param_shapes(attrs, in_shapes):
    data = in_shapes.get("data")
    if data is None:
        return {}
    kernel = tuple(int(k) for k in attrs.get("kernel", ()))
    nf = int(attrs.get("num_filter", 0))
    ng = int(attrs.get("num_group", 1))
    # NHWC activations keep channels last; the weight stays OIHW either way
    cin = data[-1] if attrs.get("layout") == "NHWC" else data[1]
    out = {"weight": (nf, cin // ng) + kernel}
    if not attrs.get("no_bias", False):
        out["bias"] = (nf,)
    return out


def _deconv_param_shapes(attrs, in_shapes):
    data = in_shapes.get("data")
    if data is None:
        return {}
    kernel = tuple(int(k) for k in attrs.get("kernel", ()))
    nf = int(attrs.get("num_filter", 0))
    ng = int(attrs.get("num_group", 1))
    cin = data[1]
    out = {"weight": (cin, nf // ng) + kernel}
    if not attrs.get("no_bias", True):
        out["bias"] = (nf,)
    return out


def _bn_param_shapes(attrs, in_shapes):
    data = in_shapes.get("data")
    if data is None:
        return {}
    ax = int(attrs.get("axis", 1)) % len(data)
    c = data[ax]
    return {"gamma": (c,), "beta": (c,),
            "moving_mean": (c,), "moving_var": (c,)}


def _in_param_shapes(attrs, in_shapes):
    data = in_shapes.get("data")
    if data is None:
        return {}
    return {"gamma": (data[1],), "beta": (data[1],)}


def _ln_param_shapes(attrs, in_shapes):
    data = in_shapes.get("data")
    if data is None:
        return {}
    ax = int(attrs.get("axis", -1)) % len(data)
    return {"gamma": (data[ax],), "beta": (data[ax],)}


def _rms_param_shapes(attrs, in_shapes):
    data = in_shapes.get("data")
    if data is None:
        return {}
    return {"gamma": (data[int(attrs.get("axis", -1)) % len(data)],)}


def _embedding_param_shapes(attrs, in_shapes):
    return {"weight": (int(attrs["input_dim"]), int(attrs["output_dim"]))}


def _prelu_param_shapes(attrs, in_shapes):
    data = in_shapes.get("data")
    if data is None or attrs.get("act_type", "leaky") != "prelu":
        return {}
    return {"gamma": (data[1] if len(data) > 1 else 1,)}


def _rnn_param_shapes(attrs, in_shapes):
    data = in_shapes.get("data")  # (seq, batch, input)
    if data is None:
        return {}
    from ..ops.rnn import rnn_param_size
    mode = attrs.get("mode", "lstm")
    sh = int(attrs["state_size"])
    nl = int(attrs.get("num_layers", 1))
    bidir = bool(attrs.get("bidirectional", False))
    d = 2 if bidir else 1
    psize = rnn_param_size(nl, data[2], sh, bidir, mode)
    shapes = {"parameters": (psize,),
              "state": (nl * d, data[1], sh)}
    if mode == "lstm":
        shapes["state_cell"] = (nl * d, data[1], sh)
    return shapes


def _input_names(node, opdef):
    """Names of ``node``'s inputs, position by position: the op's declared
    arguments less those its attrs drop; a loop node's are in its attrs."""
    if node.op == "_foreach":
        a = node.attrs
        return list(a["data_names"]) + list(a["state_names"]) \
            + list(a["free_names"])
    skip = _skip_args(node.op, node.attrs)
    return [a for a in (opdef.arg_names or []) + (opdef.aux_names or [])
            if a not in skip]


def _foreach_param_shapes(attrs, in_shapes):
    """Shapes of a loop node's free variables (the body's weights) from
    its data and states: the body's own inference, given one slice of each
    scanned input."""
    known = {n: tuple(in_shapes[n][1:]) for n in attrs["data_names"]
             if n in in_shapes}
    known.update({n: tuple(in_shapes[n]) for n in attrs["state_names"]
                  + attrs["free_names"] if n in in_shapes})
    shapes, _ = _infer_graph_shapes(attrs["subgraph"], known, {})
    return {n: shapes[n] for n in attrs["free_names"]
            if shapes.get(n) is not None}


PARAM_SHAPE_INFER = {
    "_foreach": _foreach_param_shapes,
    "FullyConnected": _fc_param_shapes,
    "Convolution": _conv_param_shapes,
    "Deconvolution": _deconv_param_shapes,
    "BatchNorm": _bn_param_shapes,
    "InstanceNorm": _in_param_shapes,
    "LayerNorm": _ln_param_shapes,
    "RMSNorm": _rms_param_shapes,
    "L2Normalization": lambda a, s: {},
    "Embedding": _embedding_param_shapes,
    "LeakyReLU": _prelu_param_shapes,
    "RNN": _rnn_param_shapes,
}

# args skipped at composition time depending on attrs (reference: each op's
# ListArguments respects flags like no_bias)
def _skip_args(op: str, attrs: dict) -> set:
    skip = set()
    opdef = _reg.find(op)
    no_bias_default = (opdef.attr_defaults.get("no_bias", False)
                       if opdef else False)
    if attrs.get("no_bias", no_bias_default) in (True, "True", "true", 1):
        skip.add("bias")
    if op == "LeakyReLU" and attrs.get("act_type", "leaky") != "prelu":
        skip.add("gamma")
    if op == "RNN" and attrs.get("mode", "lstm") != "lstm":
        skip.add("state_cell")
    if op == "CTCLoss":
        if attrs.get("use_data_lengths", False) not in (True, "True",
                                                        "true", 1):
            skip.add("data_lengths")
        if attrs.get("use_label_lengths", False) not in (True, "True",
                                                         "true", 1):
            skip.add("label_lengths")
    if op in ("SequenceReverse", "SequenceMask", "SequenceLast"):
        # the optional length input EXISTS only under
        # use_sequence_length=True (reference: sequence_reverse-inl.h) —
        # otherwise it must not auto-materialize as a learnable arg
        if attrs.get("use_sequence_length", False) not in (True, "True",
                                                           "true", 1):
            skip.add("sequence_length")
    return skip


class Symbol:
    """A list of output heads over the op DAG (reference Symbol semantics)."""
    __slots__ = ("_heads",)

    def __init__(self, heads: List[Tuple[Node, int]]):
        self._heads = list(heads)

    # -- identity -----------------------------------------------------------
    @property
    def name(self):
        if len(self._heads) == 1:
            return self._heads[0][0].name
        return None

    def __repr__(self):
        names = ", ".join(n.name for n, _ in self._heads)
        return f"<Symbol {names}>"

    def __iter__(self):
        return (self[i] for i in range(len(self)))

    def __len__(self):
        return sum(node_num_outputs(n) if i is None else 1
                   for n, i in self._heads)

    # -- composition helpers ------------------------------------------------
    def _single_head(self) -> Tuple[Node, int]:
        if len(self._heads) != 1:
            raise MXNetError("operation requires a single-output symbol")
        return self._heads[0]

    def __getitem__(self, index):
        outputs = self._expanded_heads()
        if isinstance(index, str):
            names = self.list_outputs()
            matches = [i for i, n in enumerate(names)
                       if n == index or n == index + "_output"]
            if not matches:
                raise ValueError(f"no output named {index!r}")
            return Symbol([outputs[matches[0]]])
        if isinstance(index, slice):
            return Symbol(outputs[index])
        return Symbol([outputs[index]])

    def _expanded_heads(self) -> List[Tuple[Node, int]]:
        out = []
        for node, idx in self._heads:
            if idx is None:
                for i in range(node_num_outputs(node)):
                    out.append((node, i))
            else:
                out.append((node, idx))
        return out

    @property
    def heads(self):
        return self._expanded_heads()

    # -- graph introspection ------------------------------------------------
    def nodes(self) -> List[Node]:
        return _topo_sort(self._expanded_heads())

    def list_arguments(self) -> List[str]:
        return [n.name for n in self.nodes()
                if n.is_variable and not n._user_attrs.get("__is_aux__")]

    def list_outputs(self) -> List[str]:
        names = []
        for node, idx in self._expanded_heads():
            if node.is_variable:
                names.append(node.name)
            elif node_num_outputs(node) == 1:
                names.append(node.name + "_output")
            else:
                names.append(f"{node.name}_output{idx}")
        return names

    def list_auxiliary_states(self) -> List[str]:
        return [n.name for n in self.nodes()
                if n.is_variable and n._user_attrs.get("__is_aux__")]

    def list_inputs(self):
        return [n.name for n in self.nodes() if n.is_variable]

    def get_internals(self) -> "Symbol":
        heads = []
        for n in self.nodes():
            for i in range(node_num_outputs(n)):
                heads.append((n, i))
        return Symbol(heads)

    def get_children(self) -> Optional["Symbol"]:
        """Inputs of every head, in head order (reference Symbol
        semantics: on a grouped/multi-output symbol the children of all
        heads concatenate; leaf variables contribute none).  None when
        no head has inputs."""
        heads = []
        seen = set()
        for node, _ in self._heads:
            # reference nnvm GetChildren visits each head NODE once:
            # three expanded outputs of one SliceChannel contribute its
            # inputs a single time
            if id(node) in seen:
                continue
            seen.add(id(node))
            heads.extend(node.inputs)
        if not heads:
            return None
        return Symbol(heads)

    # -- attributes ---------------------------------------------------------
    def attr(self, key):
        node, _ = self._single_head()
        return node._user_attrs.get(key)

    def list_attr(self):
        node, _ = self._single_head()
        return {k: v for k, v in node._user_attrs.items()
                if not k.startswith("__is_aux")}

    def attr_dict(self):
        out = {}
        for n in self.nodes():
            attrs = {k: v for k, v in n._user_attrs.items()
                     if not k.startswith("__is_aux")}
            attrs.update({k: str(v) for k, v in n.attrs.items()})
            if attrs:
                out[n.name] = attrs
        return out

    def _set_attr(self, **kwargs):
        node, _ = self._single_head()
        node._user_attrs.update(kwargs)

    # -- shape/type inference ----------------------------------------------
    def infer_shape(self, *args, **kwargs):
        try:
            return self._infer_shape_impl(False, *args, **kwargs)
        except MXNetError:
            raise
        except Exception as e:
            raise MXNetError(f"infer_shape error: {e}")

    def infer_shape_partial(self, *args, **kwargs):
        try:
            return self._infer_shape_impl(True, *args, **kwargs)
        except Exception:
            n_args = len(self.list_arguments())
            return ([None] * n_args, None, None)

    def _infer_shape_impl(self, partial, *args, **kwargs):
        arg_names = self.list_arguments()
        known: Dict[str, tuple] = {}
        if args:
            for n, s in zip(arg_names, args):
                if s is not None:
                    known[n] = tuple(s)
        known.update({k: tuple(v) for k, v in kwargs.items() if v is not None})
        shapes, dtypes = _infer_graph_shapes(self, known, {})
        aux_names = self.list_auxiliary_states()
        out_shapes = shapes["__outputs__"]
        arg_shapes = [shapes.get(n) for n in arg_names]
        aux_shapes = [shapes.get(n) for n in aux_names]
        if not partial and any(s is None for s in arg_shapes):
            missing = [n for n, s in zip(arg_names, arg_shapes) if s is None]
            raise MXNetError(f"infer_shape: cannot infer shapes for {missing}")
        return arg_shapes, out_shapes, aux_shapes

    def infer_type(self, *args, **kwargs):
        arg_names = self.list_arguments()
        known: Dict[str, np.dtype] = {}
        if args:
            for n, t in zip(arg_names, args):
                if t is not None:
                    known[n] = np.dtype(t)
        known.update({k: np.dtype(v) for k, v in kwargs.items()
                      if v is not None})
        # Variable(dtype=...) attrs pin that variable; FLOAT attr dtypes
        # also join the default election below (a float16 data input
        # retypes the whole homogeneous graph, the reference InferType
        # behavior) — INTEGER pins do not (an int32 index input must not
        # retype every untyped parameter)
        float_attr_dtypes = []
        for node in self.nodes():
            if node.is_variable and "__dtype__" in node._user_attrs:
                dt = np.dtype(node._user_attrs["__dtype__"])
                known.setdefault(node.name, dt)
                if np.issubdtype(dt, np.floating):
                    float_attr_dtypes.append(dt)
        # propagate: any explicitly-passed dtype becomes the default for
        # all unspecified inputs (the reference's InferType propagation
        # collapses to this for homogeneous-dtype graphs)
        explicit = [v for k, v in known.items()]
        float_explicit = [v for v in explicit
                          if np.issubdtype(v, np.floating)]
        default = next(iter(float_explicit + float_attr_dtypes),
                       np.dtype("float32"))
        all_known = dict(known)
        for n in arg_names + self.list_auxiliary_states():
            all_known.setdefault(n, default)
        _, dtypes = _infer_graph_shapes(self, {}, all_known,
                                        shapes_optional=True,
                                        dummy_shapes=True)
        arg_types = [dtypes.get(n, default) for n in arg_names]
        aux_types = [dtypes.get(n, default)
                     for n in self.list_auxiliary_states()]
        out_types = dtypes.get("__outputs__",
                               [default] * len(self.list_outputs()))
        return arg_types, out_types, aux_types

    # -- save/load ----------------------------------------------------------
    def tojson(self):
        nodes = self.nodes()
        node_index = {id(n): i for i, n in enumerate(nodes)}
        jnodes = []
        for n in nodes:
            jn = {
                "op": n.op if n.op else "null",
                "name": n.name,
                "inputs": [[node_index[id(src)], idx, 0]
                           for src, idx in n.inputs],
            }
            attrs = {k: _attr_to_str(v) for k, v in n.attrs.items()}
            sub = n.op and _reg.get(n.op).subgraph_attr
            if sub:     # a loop node: the graph it holds, nested
                attrs[sub] = n.attrs[sub].tojson()
            attrs.update({k: str(v) for k, v in n._user_attrs.items()})
            if attrs:
                jn["attrs"] = attrs
            jnodes.append(jn)
        heads = [[node_index[id(n)], i, 0] for n, i in self._expanded_heads()]
        arg_nodes = [i for i, n in enumerate(nodes) if n.is_variable]
        return json.dumps({
            "nodes": jnodes,
            "arg_nodes": arg_nodes,
            "node_row_ptr": list(range(len(nodes) + 1)),
            "heads": heads,
            "attrs": {"mxnet_version": ["int", 1200]},
        }, indent=2)

    def save(self, fname):
        with open(fname, "w") as f:
            f.write(self.tojson())

    # -- arithmetic ---------------------------------------------------------
    def __abs__(self):
        return _compose("abs", [self], {}, None)

    def _binop(self, other, op, scalar_op, rop=False):
        if isinstance(other, Symbol):
            a, b = (other, self) if rop else (self, other)
            return _compose(op, [a, b], {}, None)
        if isinstance(other, numbers.Number):
            return _compose(scalar_op, [self], {"scalar": float(other)}, None)
        return NotImplemented

    def __add__(self, o): return self._binop(o, "broadcast_add", "_plus_scalar")
    __radd__ = __add__
    def __sub__(self, o): return self._binop(o, "broadcast_sub", "_minus_scalar")
    def __rsub__(self, o): return self._binop(o, "broadcast_sub", "_rminus_scalar", rop=True)
    def __mul__(self, o): return self._binop(o, "broadcast_mul", "_mul_scalar")
    __rmul__ = __mul__
    def __truediv__(self, o): return self._binop(o, "broadcast_div", "_div_scalar")
    def __rtruediv__(self, o): return self._binop(o, "broadcast_div", "_rdiv_scalar", rop=True)
    __div__ = __truediv__
    __rdiv__ = __rtruediv__
    def __pow__(self, o): return self._binop(o, "broadcast_power", "_power_scalar")
    def __mod__(self, o): return self._binop(o, "broadcast_mod", "_mod_scalar")
    def __neg__(self): return _compose("negative", [self], {}, None)
    def __eq__(self, o): return self._binop(o, "broadcast_equal", "_equal_scalar")
    def __ne__(self, o): return self._binop(o, "broadcast_not_equal", "_not_equal_scalar")
    def __gt__(self, o): return self._binop(o, "broadcast_greater", "_greater_scalar")
    def __ge__(self, o): return self._binop(o, "broadcast_greater_equal", "_greater_equal_scalar")
    def __lt__(self, o): return self._binop(o, "broadcast_lesser", "_lesser_scalar")
    def __le__(self, o): return self._binop(o, "broadcast_lesser_equal", "_lesser_equal_scalar")

    def __hash__(self):
        return id(self)

    def __copy__(self):
        return Symbol(list(self._heads))

    def __deepcopy__(self, memo):
        # graph nodes are immutable-by-convention; sharing is safe
        return Symbol(list(self._heads))

    # -- convenience method mirrors (subset used by models/tests) -----------
    def reshape(self, *shape, **kw):
        if len(shape) == 1 and isinstance(shape[0], (tuple, list)):
            shape = tuple(shape[0])
        return _compose("Reshape", [self], {"shape": shape, **kw}, None)

    def transpose(self, *axes):
        if len(axes) == 1 and isinstance(axes[0], (tuple, list)):
            axes = tuple(axes[0])
        return _compose("transpose", [self], {"axes": axes}, None)

    def astype(self, dtype):
        return _compose("Cast", [self], {"dtype": np.dtype(dtype).name}, None)

    def sum(self, axis=None, keepdims=False):
        return _compose("sum", [self], {"axis": axis, "keepdims": keepdims}, None)

    def mean(self, axis=None, keepdims=False):
        return _compose("mean", [self], {"axis": axis, "keepdims": keepdims}, None)

    def flatten(self):
        return _compose("Flatten", [self], {}, None)

    def slice_axis(self, axis, begin, end):
        return _compose("slice_axis", [self],
                        {"axis": axis, "begin": begin, "end": end}, None)

    def expand_dims(self, axis):
        return _compose("expand_dims", [self], {"axis": axis}, None)

    def softmax(self, axis=-1):
        return _compose("softmax", [self], {"axis": axis}, None)

    # -- evaluation / binding ----------------------------------------------
    def eval(self, ctx=None, **kwargs):
        from ..executor import Executor
        ex = self.bind(ctx, kwargs)
        return ex.forward()

    def bind(self, ctx=None, args=None, args_grad=None, grad_req="write",
             aux_states=None, group2ctx=None, shared_exec=None):
        from ..executor import Executor
        return Executor(self, ctx, args=args, args_grad=args_grad,
                        grad_req=grad_req, aux_states=aux_states,
                        group2ctx=group2ctx, shared_exec=shared_exec)

    def simple_bind(self, ctx=None, grad_req="write", type_dict=None,
                    group2ctx=None, shared_arg_names=None, shared_exec=None,
                    shared_buffer=None, **kwargs):
        from ..executor import Executor
        return Executor.simple_bind(self, ctx, grad_req=grad_req,
                                    type_dict=type_dict,
                                    shared_exec=shared_exec,
                                    shapes=kwargs)

    # gradient symbol (reference: nnvm Gradient pass exposed as Symbol.grad
    # in old API) — not needed: Executor differentiates via jax.vjp.
    def grad(self, wrt):
        raise MXNetError("Symbol.grad is not supported; bind and use "
                         "backward (jax.vjp differentiates the whole graph)")


def _attr_to_str(v):
    if isinstance(v, bool):
        return str(v)
    if isinstance(v, (tuple, list)):
        return "(" + ", ".join(str(x) for x in v) + ")"
    return str(v)


# ---------------------------------------------------------------------------
# composition (reference: MXSymbolCreateAtomicSymbol + Compose,
# c_api_symbolic.cc)
# ---------------------------------------------------------------------------
def _compose(op_name: str, inputs: List[Symbol], attrs: dict,
             name: Optional[str], user_attr: Optional[dict] = None) -> Symbol:
    opdef = _reg.get(op_name)
    attrs = {k: v for k, v in attrs.items() if v is not None}
    hint = op_name.lower().lstrip("_")
    name = _name.current().get(name, hint)
    # explicit attr= dict merges over the ambient AttrScope (reference:
    # atomic-symbol attrs, test_attr.py test_list_attr/test_attr_dict)
    user_attrs = _attribute.current().get(user_attr)

    heads: List[Tuple[Node, int]] = []
    for s in inputs:
        hs = s._expanded_heads()
        heads.extend(hs)

    if not opdef.variadic:
        # auto-create missing parameter/aux variables; they inherit the
        # op's attr dict like the reference's Compose does
        arg_names = list(opdef.arg_names or [])
        aux_names = list(opdef.aux_names or [])
        skip = _skip_args(op_name, attrs)
        wanted = [a for a in arg_names + aux_names if a not in skip]
        n_missing = len(wanted) - len(heads)
        if n_missing > 0:
            for extra in wanted[len(heads):]:
                is_aux = extra in aux_names
                v = Variable(f"{name}_{extra}", attr=user_attr,
                             __is_aux__="1" if is_aux else None)
                heads.extend(v._expanded_heads())

    node = Node(op_name, name, attrs, heads, user_attrs)
    return Symbol([(node, None)])


def var(name, attr=None, shape=None, lr_mult=None, wd_mult=None, dtype=None,
        init=None, stype=None, **kwargs) -> Symbol:
    """Create a variable symbol (reference: symbol.py var/Variable)."""
    if not isinstance(name, str):
        raise TypeError("Expect a string for variable name")
    user_attrs = _attribute.current().get(attr)
    if shape is not None:
        user_attrs["__shape__"] = str(tuple(shape))
    if lr_mult is not None:
        user_attrs["__lr_mult__"] = str(lr_mult)
    if wd_mult is not None:
        user_attrs["__wd_mult__"] = str(wd_mult)
    if dtype is not None:
        user_attrs["__dtype__"] = np.dtype(dtype).name
    if init is not None:
        if not isinstance(init, str):
            init = init.dumps()
        user_attrs["__init__"] = init
    for k, v in kwargs.items():
        if v is None:
            continue
        if k.startswith("__") and k.endswith("__"):
            user_attrs[k] = str(v)
        else:
            user_attrs[k] = str(v)
    user_attrs = {k: v for k, v in user_attrs.items() if v is not None}
    node = Node(None, name, {}, [], user_attrs)
    return Symbol([(node, None)])


Variable = var


def Group(symbols) -> Symbol:
    heads = []
    for s in symbols:
        if not isinstance(s, Symbol):
            raise TypeError("Group expects Symbols")
        heads.extend(s._expanded_heads())
    return Symbol(heads)


def _entry(e):
    """Graph entry → (node_id, output_idx).  nnvm-era JSON writes
    [node, idx, version] triplets; the reference's pre-nnvm v0.8 format
    (the checked-in save_000800.json fixture, upgraded there by
    src/nnvm/legacy_json_util.cc) writes [node, idx] pairs — accept
    both so reference-written symbol files load unchanged."""
    return e[0], e[1]


def load_json(json_str: str) -> Symbol:
    g = json.loads(json_str)
    nodes: List[Node] = []
    for jn in g["nodes"]:
        attrs = dict(jn.get("attrs", jn.get("param", {})) or {})
        user_attrs = {k: v for k, v in attrs.items()
                      if k.startswith("__") or k in ("ctx_group",)}
        # ONLY the pre-nnvm v0.8 format (identified by its sibling
        # "param" dict) keeps USER attrs (lr_mult, ctx_group, ...) in a
        # separate "attr" dict; nnvm-era files spell op params "attr",
        # and merging those here would silently strip them from the op
        if "param" in jn:
            user_attrs.update(jn.get("attr", {}) or {})
        op = jn["op"]
        if op == "null":
            node = Node(None, jn["name"], {}, [], user_attrs)
        else:
            opdef = _reg.find(op)
            if opdef is None:
                raise MXNetError(f"cannot load graph: unknown op {op!r}")
            op_attrs = {k: _parse_attr(v, opdef.attr_defaults.get(k))
                        for k, v in attrs.items() if not k.startswith("__")}
            if opdef.subgraph_attr:
                op_attrs[opdef.subgraph_attr] = load_json(
                    attrs[opdef.subgraph_attr])
            inputs = [(nodes[i], idx)
                      for i, idx in map(_entry, jn["inputs"])]
            # pre-nnvm JSON omits implicit inputs (BatchNorm's
            # moving_mean/var aux states, SoftmaxOutput's label);
            # synthesize the missing TRAILING ones with composition's
            # standard names — the reference's legacy upgrade pass
            # (legacy_json_util.cc) re-ran composition to the same effect
            # same conditional-arg filter as composition (no_bias drops
            # bias, non-prelu LeakyReLU drops gamma, ...): without it a
            # tojson/load round trip would fabricate phantom arguments
            skip = _skip_args(op, op_attrs)
            args_w = [a for a in (opdef.arg_names or [])
                      if a not in skip]
            aux_w = [a for a in (opdef.aux_names or []) if a not in skip]
            want = args_w + aux_w
            if not opdef.variadic and args_w and len(inputs) < len(want):
                for pos, missing in enumerate(want[len(inputs):],
                                              start=len(inputs)):
                    # NOTE: synthesized variables must NOT enter `nodes`
                    # — the JSON's input indices refer to the original
                    # node list, and shifting it corrupts later edges
                    var = Node(None, f"{jn['name']}_{missing}", {}, [],
                               {"__is_aux__": True}
                               if pos >= len(args_w) else {})
                    inputs.append((var, 0))
            node = Node(op, jn["name"], op_attrs, inputs, user_attrs)
        nodes.append(node)
    heads = [(nodes[i], idx) for i, idx in map(_entry, g["heads"])]
    return Symbol(heads)


def load(fname: str) -> Symbol:
    with open(fname) as f:
        return load_json(f.read())


def _parse_attr(v, default=None):
    """Parse a stringified attr back to python (tuples, bools, numbers)."""
    if not isinstance(v, str):
        return v
    s = v.strip()
    if s in ("True", "true"):
        return True
    if s in ("False", "false"):
        return False
    if s in ("None", ""):
        return None
    if s.startswith("(") or s.startswith("["):
        inner = s[1:-1].strip()
        if not inner:
            return ()
        parts = [p.strip() for p in inner.split(",") if p.strip()]
        return tuple(_parse_attr(p) for p in parts)
    try:
        return int(s)
    except ValueError:
        pass
    try:
        return float(s)
    except ValueError:
        pass
    return v


# ---------------------------------------------------------------------------
# partial (bidirectional) shape inference
# ---------------------------------------------------------------------------
# The reference's InferShape pass (src/executor/infer_graph_attr_pass.cc:368)
# iterates forward AND backward so a 0 ("unknown") dim anywhere can be pinned
# by constraints elsewhere (tests/python/unittest/test_infer_shape.py).  The
# main engine below is forward abstract interpretation; this fixpoint
# pre-pass resolves unknown dims for the structural ops where backward
# propagation matters (elementwise/broadcast binaries, FullyConnected,
# Convolution, Concat, SliceChannel, shape-preserving unaries), then hands
# fully-resolved variable shapes to the forward engine.

_SHAPE_PRESERVING_OPS = frozenset({
    "Activation", "relu", "sigmoid", "tanh", "softsign", "exp", "log",
    "negative", "abs", "square", "sqrt", "BlockGrad", "stop_gradient",
    "_copy", "identity", "make_loss", "zeros_like", "ones_like",
    "LeakyReLU", "softmax", "log_softmax", "Dropout", "BatchNorm",
    "InstanceNorm", "L2Normalization", "Cast", "cast",
})
# strict same-shape binaries: inputs and output all unify dim-wise
_ELEMWISE_BINARY_OPS = frozenset({
    "elemwise_add", "elemwise_sub", "elemwise_mul", "elemwise_div",
    "elemwise_mod", "_identity_with_attr_like_rhs", "_grad_add",
})
# numpy-broadcast binaries: right-aligned, 1s broadcast; unknown input
# dims fill OPTIMISTICALLY from the output (assume no broadcast), the
# same call the reference's BinaryBroadcastShape makes
_BROADCAST_BINARY_OPS = frozenset({
    "broadcast_add", "broadcast_sub", "broadcast_mul", "broadcast_div",
    "broadcast_mod", "broadcast_power", "broadcast_maximum",
    "broadcast_minimum", "broadcast_hypot",
})


def _unify_dims(a, b, where=""):
    """Dim-wise merge of two patterns (None/0 = unknown)."""
    if a is None:
        return list(b) if b is not None else None
    if b is None:
        return list(a)
    if len(a) != len(b):
        raise MXNetError(f"infer_shape: rank mismatch {a} vs {b} {where}")
    out = []
    for x, y in zip(a, b):
        x = None if not x else x
        y = None if not y else y
        if x is not None and y is not None and x != y:
            raise MXNetError(
                f"infer_shape: inconsistent dims {a} vs {b} {where}")
        out.append(x if x is not None else y)
    return out


def _partial_prepass(nodes, var_pat, generic_eval=True):
    """Fixpoint bidirectional dim propagation.  ``var_pat``: id(node) ->
    list pattern (None = unknown) for variables; mutated in place.
    ``generic_eval=False`` skips abstract-eval of unhandled ops (used on
    the fully-specified path, where the main engine traces them anyway —
    the special-cased rules still run for constraint VALIDATION)."""
    pat: Dict[Tuple[int, int], list] = {}
    for n in nodes:
        if n.is_variable and var_pat.get(id(n)) is not None:
            pat[(id(n), 0)] = list(var_pat[id(n)])

    def get(src, idx):
        return pat.get((id(src), idx))

    def put(src, idx, p, where):
        if p is None:
            return False
        merged = _unify_dims(get(src, idx), p, where)
        if merged != get(src, idx):
            pat[(id(src), idx)] = merged
            if src.is_variable:
                var_pat[id(src)] = merged
            return True
        return False

    def complete(p):
        return p is not None and all(d for d in p)

    for _ in range(3 * len(nodes) + 8):
        changed = False
        for n in nodes:
            if n.is_variable:
                continue
            ins = [get(s, i) for s, i in n.inputs]
            out0 = get(n, 0)
            op = n.op
            w = f"at {n.name!r} ({op})"
            try:
                if op in _ELEMWISE_BINARY_OPS and len(n.inputs) == 2:
                    m = _unify_dims(_unify_dims(ins[0], ins[1], w), out0, w)
                    changed |= put(*n.inputs[0], m, w)
                    changed |= put(*n.inputs[1], m, w)
                    changed |= put(n, 0, m, w)
                elif op in _BROADCAST_BINARY_OPS and len(n.inputs) == 2:
                    # output rank = max input rank — only deducible when
                    # both input ranks are known, or pinned by the output
                    if out0 is not None:
                        r = len(out0)
                    elif ins[0] is not None and ins[1] is not None:
                        r = max(len(ins[0]), len(ins[1]))
                    else:
                        continue

                    def aligned(p):
                        # right-align; absent leading dims behave as 1
                        if p is None:
                            return [None] * r
                        return [1] * (r - len(p)) + list(p)

                    a, b, o = aligned(ins[0]), aligned(ins[1]), \
                        aligned(out0)
                    new_a, new_b, new_o = list(a), list(b), list(o)
                    for d in range(r):
                        cand = {v for v in (a[d], b[d]) if v and v != 1}
                        if len(cand) > 1:
                            raise MXNetError(
                                f"infer_shape: broadcast mismatch "
                                f"{ins[0]} vs {ins[1]} {w}")
                        if cand:
                            new_o[d] = _unify_dims([o[d]],
                                                   [cand.pop()], w)[0]
                        elif a[d] == 1 and b[d] == 1:
                            new_o[d] = _unify_dims([o[d]], [1], w)[0]
                        # optimistic backward fill: unknown input dim
                        # takes the output dim (assume non-broadcast)
                        if new_o[d]:
                            if a[d] is None:
                                new_a[d] = new_o[d]
                            if b[d] is None:
                                new_b[d] = new_o[d]
                    if ins[0] is not None:
                        changed |= put(*n.inputs[0],
                                       new_a[r - len(ins[0]):], w)
                    if ins[1] is not None:
                        changed |= put(*n.inputs[1],
                                       new_b[r - len(ins[1]):], w)
                    changed |= put(n, 0, new_o, w)
                elif op in _SHAPE_PRESERVING_OPS and n.inputs:
                    m = _unify_dims(ins[0], out0, w)
                    changed |= put(*n.inputs[0], m, w)
                    changed |= put(n, 0, m, w)
                elif op == "FullyConnected" and \
                        n.attrs.get("flatten", True) in (True, "True", 1):
                    # flatten=False keeps leading dims — rank unknown
                    # here, so that variant stays with the forward engine
                    nh = int(n.attrs.get("num_hidden", 0))
                    data = ins[0]
                    o = _unify_dims(out0, [None, nh], w)
                    if data is not None and len(data) == 2:
                        o = _unify_dims(o, [data[0], nh], w)
                        changed |= put(*n.inputs[0], [o[0], data[1]], w)
                        if len(n.inputs) > 1 and data[1]:
                            changed |= put(*n.inputs[1], [nh, data[1]], w)
                    changed |= put(n, 0, o, w)
                elif op == "Convolution":
                    kern = tuple(n.attrs.get("kernel", ()) or ())
                    rank = len(kern)
                    if rank and ins[0] is not None \
                            and len(ins[0]) == rank + 2:
                        stride = tuple(n.attrs.get("stride", ()) or
                                       (1,) * rank)
                        pad = tuple(n.attrs.get("pad", ()) or (0,) * rank)
                        dil = tuple(n.attrs.get("dilate", ()) or
                                    (1,) * rank)
                        nf = int(n.attrs.get("num_filter", 0))
                        # channel/spatial axis positions flip for NHWC
                        nhwc = n.attrs.get("layout") == "NHWC" and rank == 2
                        sp0, c_ax = (1, rank + 1) if nhwc else (2, 1)
                        data = list(ins[0])
                        o = out0 or [None] * (rank + 2)
                        hint = [None] * (rank + 2)
                        hint[0], hint[c_ax] = data[0], nf
                        o = _unify_dims(o, hint, w)
                        for d in range(rank):
                            ke = dil[d] * (kern[d] - 1) + 1
                            if data[sp0 + d]:
                                o[sp0 + d] = (data[sp0 + d] + 2 * pad[d]
                                              - ke) // stride[d] + 1
                            elif o[sp0 + d]:
                                data[sp0 + d] = ((o[sp0 + d] - 1) * stride[d]
                                                 - 2 * pad[d] + ke)
                        data[0] = o[0]
                        changed |= put(*n.inputs[0], data, w)
                        changed |= put(n, 0, o, w)
                elif op in ("Concat", "concat"):
                    dim = int(n.attrs.get("dim", 1))
                    parts = [get(s, i) for s, i in n.inputs]
                    rank = next((len(p) for p in parts + [out0]
                                 if p is not None), None)
                    if rank is not None:
                        dim %= rank
                        # unify non-concat dims across all parts + output
                        base = [None] * rank
                        for p in parts + [out0]:
                            if p is None:
                                continue
                            for d in range(rank):
                                if d != dim and p[d]:
                                    base[d] = _unify_dims(
                                        [base[d]], [p[d]], w)[0]
                        tot = 0
                        missing = []
                        for j, p in enumerate(parts):
                            if p is not None and p[dim]:
                                tot += p[dim]
                            else:
                                missing.append(j)
                        o = list(base)
                        o[dim] = tot if not missing else (
                            out0[dim] if out0 and out0[dim] else None)
                        changed |= put(n, 0, o, w)
                        if out0 and out0[dim] and len(missing) == 1:
                            j = missing[0]
                            rem = out0[dim] - tot
                            if rem <= 0:
                                raise MXNetError(
                                    f"infer_shape: concat parts sum to "
                                    f"{tot} but output dim is "
                                    f"{out0[dim]} {w}")
                            fill = list(base)
                            fill[dim] = rem
                            changed |= put(*n.inputs[j], fill, w)
                        for j, p in enumerate(parts):
                            fill = list(base)
                            fill[dim] = p[dim] if p and p[dim] else None
                            changed |= put(*n.inputs[j], fill, w)
                elif op in ("SliceChannel", "split"):
                    num = int(n.attrs.get("num_outputs", 1))
                    axis = int(n.attrs.get("axis", 1))
                    squeeze = bool(n.attrs.get("squeeze_axis", False))
                    data = ins[0]
                    nouts = node_num_outputs(n)
                    if axis < 0:
                        # normalize against the INPUT rank (outputs are one
                        # dim shorter when squeezing)
                        in_rank = len(data) if data is not None else next(
                            (len(get(n, i)) + (1 if squeeze else 0)
                             for i in range(nouts)
                             if get(n, i) is not None), None)
                        if in_rank is None:
                            continue
                        axis %= in_rank
                    for i in range(nouts):
                        oi = get(n, i)
                        if oi is None and data is None:
                            continue
                        if data is not None:
                            exp = list(data)
                            exp[axis] = (data[axis] // num
                                         if data[axis] else None)
                            if squeeze:
                                exp = exp[:axis] + exp[axis + 1:]
                            changed |= put(n, i, exp, w)
                        if oi is not None:
                            if squeeze:
                                back = (list(oi[:axis]) + [num]
                                        + list(oi[axis:]))
                            else:
                                back = list(oi)
                                back[axis] = (oi[axis] * num
                                              if oi[axis] else None)
                            changed |= put(*n.inputs[0], back, w)
                else:
                    # generic forward: all inputs complete -> exact eval
                    if generic_eval and ins and not complete(out0) and \
                            all(complete(p) for p in ins):
                        opdef = _reg.get(op)
                        specs = [jax.ShapeDtypeStruct(tuple(p),
                                                      np.float32)
                                 for p in ins]
                        outs = _eval_node_shape(n, opdef, specs)
                        for i, sds in enumerate(outs):
                            changed |= put(n, i, list(sds.shape), w)
            except MXNetError:
                raise
            except Exception:
                continue
        if not changed:
            break


def _infer_graph_shapes(sym: Symbol, known_shapes: Dict[str, tuple],
                        known_dtypes: Dict[str, np.dtype],
                        shapes_optional=False, dummy_shapes=False):
    """Forward abstract interpretation with parameter-shape back-fill.

    Returns (shapes, dtypes) dicts keyed by variable name, plus
    ``"__outputs__"`` entries listing per-head results.
    """
    nodes = _topo_sort(sym._expanded_heads())
    default_dtype = np.dtype("float32")
    var_shape: Dict[int, Optional[tuple]] = {}
    var_dtype: Dict[int, np.dtype] = {}
    val: Dict[Tuple[int, int], jax.ShapeDtypeStruct] = {}

    partial_pat: Dict[int, list] = {}
    has_partial = False
    for n in nodes:
        if n.is_variable:
            shp = known_shapes.get(n.name)
            if shp is None and "__shape__" in n._user_attrs:
                shp = _parse_attr(n._user_attrs["__shape__"])
            if shp is None and dummy_shapes:
                shp = (1,)  # dtype-only inference: shapes are throwaway
            if shp is not None and any(not d for d in shp):
                # 0 = unknown dim (MXNet convention): resolve via the
                # bidirectional pre-pass below, not as a literal 0-size
                partial_pat[id(n)] = [d if d else None for d in shp]
                shp = None
                has_partial = True
            elif shp is not None:
                partial_pat[id(n)] = list(shp)
            var_shape[id(n)] = tuple(shp) if shp else None
            dt = known_dtypes.get(n.name)
            if dt is None and "__dtype__" in n._user_attrs:
                dt = np.dtype(n._user_attrs["__dtype__"])
            var_dtype[id(n)] = dt or default_dtype

    if not dummy_shapes:
        # always: resolves 0-dim unknowns bidirectionally AND validates
        # caller-supplied shapes against op constraints (the reference's
        # InferShape CHECKs, e.g. FC weight vs num_hidden)
        _partial_prepass(nodes, partial_pat, generic_eval=has_partial)
        # adopt anything the bidirectional pass fully resolved — including
        # variables that had NO shape hint at all (e.g. an FC weight pinned
        # purely by backward constraints)
        for n in nodes:
            if n.is_variable and var_shape.get(id(n)) is None:
                p = partial_pat.get(id(n))
                if p is not None and all(d for d in p):
                    var_shape[id(n)] = tuple(p)

    for n in nodes:
        if n.is_variable:
            if var_shape[id(n)] is not None:
                val[(id(n), 0)] = jax.ShapeDtypeStruct(
                    var_shape[id(n)], var_dtype[id(n)])
            continue
        opdef = _reg.get(n.op)
        # back-fill parameter shapes from data shapes
        infer_hook = PARAM_SHAPE_INFER.get(n.op)
        argmap = {}
        for an, (src, idx) in zip(_input_names(n, opdef), n.inputs):
            argmap[an] = (src, idx)
        if infer_hook:
            in_shapes = {an: val[(id(src), idx)].shape
                         for an, (src, idx) in argmap.items()
                         if (id(src), idx) in val}
            try:
                fills = infer_hook(n.attrs, in_shapes)
            except Exception:
                fills = {}
            for an, shp in fills.items():
                if an in argmap:
                    src, idx = argmap[an]
                    if src.is_variable and var_shape.get(id(src)) is None:
                        var_shape[id(src)] = tuple(shp)
                        val[(id(src), 0)] = jax.ShapeDtypeStruct(
                            tuple(shp), var_dtype.get(id(src), default_dtype))
        # elementwise mirroring: same-shape binary ops
        in_specs = []
        missing = []
        for src, idx in n.inputs:
            sds = val.get((id(src), idx))
            if sds is None:
                missing.append((src, idx))
            in_specs.append(sds)
        if missing:
            knowns = [s for s in in_specs if s is not None]
            if knowns and all(m[0].is_variable for m in missing):
                for src, idx in missing:
                    val[(id(src), idx)] = knowns[0]
                    var_shape[id(src)] = knowns[0].shape
                in_specs = [val[(id(src), idx)] for src, idx in n.inputs]
            elif shapes_optional:
                continue
            else:
                raise MXNetError(
                    f"infer_shape: insufficient information at node "
                    f"{n.name!r} ({n.op})")
        try:
            out_specs = _eval_node_shape(n, opdef, in_specs)
        except Exception:
            if shapes_optional:
                continue  # dtype-only mode with throwaway shapes
            raise
        for i, sds in enumerate(out_specs):
            val[(id(n), i)] = sds

    shapes = {"__outputs__": []}
    dtypes = {"__outputs__": []}
    for node in nodes:
        if node.is_variable:
            shapes[node.name] = var_shape.get(id(node))
            dtypes[node.name] = var_dtype.get(id(node), default_dtype)
    for hn, hi in sym._expanded_heads():
        sds = val.get((id(hn), hi))
        shapes["__outputs__"].append(tuple(sds.shape)
                                     if sds is not None else None)
        dtypes["__outputs__"].append(np.dtype(str(sds.dtype))
                                     if sds is not None else default_dtype)
    return shapes, dtypes


def _eval_node_shape(n: Node, opdef: _reg.OpDef, in_specs):
    import jax.random as jrandom
    attrs = dict(n.attrs)
    kwargs = dict(attrs)
    if opdef.takes_is_train:
        kwargs["is_train"] = True

    def f(*vals):
        if opdef.needs_rng:
            out = opdef.fn(jrandom.PRNGKey(0), *vals, **kwargs)
        else:
            out = opdef.fn(*vals, **kwargs)
        return out if isinstance(out, (tuple, list)) else (out,)

    out = jax.eval_shape(f, *in_specs)
    return list(out)[:node_num_outputs(n)]


def zeros(shape, dtype="float32", **kw):
    if isinstance(shape, numbers.Integral):
        shape = (shape,)
    return _compose("_zeros", [], {"shape": tuple(shape),
                                   "dtype": np.dtype(dtype).name},
                    kw.get("name"))


def ones(shape, dtype="float32", **kw):
    if isinstance(shape, numbers.Integral):
        shape = (shape,)
    return _compose("_ones", [], {"shape": tuple(shape),
                                  "dtype": np.dtype(dtype).name},
                    kw.get("name"))


def arange(start, stop=None, step=1.0, repeat=1, name=None, dtype="float32"):
    return _compose("_arange", [], {"start": start, "stop": stop,
                                    "step": step, "repeat": repeat,
                                    "dtype": np.dtype(dtype).name}, name)
