"""`mx.sym` — symbolic graph namespace.

Ref: python/mxnet/symbol/symbol.py + the nnvm C++ Symbol/Graph
(3rdparty/tvm/nnvm :: nnvm::Symbol, nnvm::Graph, JSON ser/de).

TPU-native role (SURVEY.md §7.0): the reference needed its own graph
compiler (GraphExecutor + nnvm passes: PlanMemory, CSE, AttachOpExecs);
XLA does all of that. So Symbol here is a *thin declarative DAG* whose
only jobs are (a) the hybridize trace target, (b) JSON save/load for
checkpoint/export parity, (c) the legacy Module/bind API. Compilation
is: topological interpretation of the DAG with pure-JAX op impls under
``jax.jit`` — one XLA program, fused and memory-planned by the compiler.
"""
from __future__ import annotations

import json
import threading
from typing import Any, Dict, List, Optional, Sequence, Tuple

import jax
import numpy as np

from ..base import MXNetError
from ..ops import Operator, get_op, list_ops, _OPS, _ALIASES, canonical_attrs

__all__ = ["Symbol", "Variable", "var", "Group", "load", "load_json",
           "compile_graph"]


class _NameManager(threading.local):
    def __init__(self):
        self.counters: Dict[str, int] = {}

    def get(self, hint: str) -> str:
        idx = self.counters.get(hint, 0)
        self.counters[hint] = idx + 1
        return "%s%d" % (hint, idx)


_NAMES = _NameManager()


class _Node:
    """Graph node: an op application or a variable (op is None)."""

    __slots__ = ("op", "name", "attrs", "inputs", "num_outputs")

    def __init__(self, op: Optional[Operator], name: str, attrs: Dict[str, Any],
                 inputs: List["Symbol"]):
        self.op = op
        self.name = name
        self.attrs = attrs
        self.inputs = inputs  # list of Symbol (node+index refs)
        self.num_outputs = 1

    @property
    def is_variable(self) -> bool:
        return self.op is None


# A node holds no null inputs. Where an optional tensor is left out
# before one that is given (a mixer without its q/k norms but with its
# gate), the node's attrs name the impl's positional slot of each input
# under this key; every site that runs a node puts the inputs back in
# their slots (:func:`_in_slots`). Graph rewrites copy attrs, so the
# key travels with the node, and through tojson / load_json.
_SLOTS = "_input_slots"


def _in_slots(node: "_Node", ins: List) -> Tuple[List, Dict[str, Any]]:
    """(``ins`` in the impl's positional order, None where an optional
    tensor was left out; the node's attrs without the slots' key)."""
    attrs = dict(node.attrs)
    slots = attrs.pop(_SLOTS, None)
    if slots is None:
        return ins, attrs
    # (the slots ascend; ``max`` is an op in this namespace)
    placed = [None] * (int(slots[-1]) + 1)
    for slot, value in zip(slots, ins):
        placed[int(slot)] = value
    return placed, attrs


class Symbol:
    """An output entry of a graph node (node, out_index) — possibly a
    group of several outputs (ref: nnvm SymbolEntry list)."""

    __slots__ = ("_entries",)

    def __init__(self, entries: List[Tuple[_Node, int]]):
        self._entries = entries

    # ------------------------------------------------------------------
    @property
    def _node(self) -> _Node:
        if len(self._entries) != 1:
            raise MXNetError("operation on a grouped symbol is ambiguous")
        return self._entries[0][0]

    @property
    def name(self) -> str:
        node, idx = self._entries[0]
        return node.name

    def __repr__(self):
        return "<Symbol %s>" % ",".join(n.name for n, _ in self._entries)

    def __iter__(self):
        return (Symbol([e]) for e in self._entries)

    def __len__(self):
        return len(self._entries)

    def __getitem__(self, idx):
        if isinstance(idx, str):
            names = self.list_outputs()
            idx = names.index(idx)
        return Symbol([self._entries[idx]])

    # ------------------------------------------------------------------
    # graph introspection
    # ------------------------------------------------------------------
    def _topo(self) -> List[_Node]:
        order, seen = [], set()

        def visit(node):
            st = [(node, iter(node.inputs))]
            seen.add(id(node))
            while st:
                n, it = st[-1]
                advanced = False
                for child_sym in it:
                    child = child_sym._entries[0][0]
                    if id(child) not in seen:
                        seen.add(id(child))
                        st.append((child, iter(child.inputs)))
                        advanced = True
                        break
                if not advanced:
                    order.append(n)
                    st.pop()

        for node, _ in self._entries:
            if id(node) not in seen:
                visit(node)
        return order

    def list_inputs(self) -> List[str]:
        return [n.name for n in self._topo() if n.is_variable]

    def list_arguments(self) -> List[str]:
        return self.list_inputs()

    def list_auxiliary_states(self) -> List[str]:
        return [n.name for n in self._topo()
                if n.is_variable and n.attrs.get("__aux__")]

    def list_outputs(self) -> List[str]:
        outs = []
        for node, idx in self._entries:
            if node.num_outputs > 1:
                outs.append("%s_output%d" % (node.name, idx))
            else:
                outs.append("%s_output" % node.name)
        return outs

    def get_internals(self) -> "Symbol":
        entries = []
        for n in self._topo():
            for i in range(n.num_outputs):
                entries.append((n, i))
        return Symbol(entries)

    def attr(self, key):
        return self._node.attrs.get(key)

    def list_attr(self):
        return dict(self._node.attrs)

    # ------------------------------------------------------------------
    # arithmetic — builds graph nodes through the same registry
    # ------------------------------------------------------------------
    def _binop(self, other, opname, scalar_opname, reverse=False):
        if isinstance(other, Symbol):
            lhs, rhs = (other, self) if reverse else (self, other)
            return _create(opname, [lhs, rhs], {})
        if isinstance(other, (int, float)):
            name = scalar_opname
            if reverse and scalar_opname in ("_minus_scalar", "_div_scalar",
                                             "_power_scalar", "_mod_scalar"):
                name = "_r" + scalar_opname[1:]
            return _create(name, [self], {"scalar": float(other)})
        return NotImplemented

    def __add__(self, o): return self._binop(o, "broadcast_add", "_plus_scalar")
    def __radd__(self, o): return self._binop(o, "broadcast_add", "_plus_scalar")
    def __sub__(self, o): return self._binop(o, "broadcast_sub", "_minus_scalar")
    def __rsub__(self, o): return self._binop(o, "broadcast_sub", "_minus_scalar", True)
    def __mul__(self, o): return self._binop(o, "broadcast_mul", "_mul_scalar")
    def __rmul__(self, o): return self._binop(o, "broadcast_mul", "_mul_scalar")
    def __truediv__(self, o): return self._binop(o, "broadcast_div", "_div_scalar")
    def __rtruediv__(self, o): return self._binop(o, "broadcast_div", "_div_scalar", True)
    def __pow__(self, o): return self._binop(o, "broadcast_power", "_power_scalar")
    def __neg__(self): return _create("negative", [self], {})

    # fluent methods mirroring NDArray's
    def reshape(self, *shape, **kw):
        if len(shape) == 1 and isinstance(shape[0], (tuple, list)):
            shape = tuple(shape[0])
        shape = kw.get("shape", shape)
        return _create("Reshape", [self], {"shape": tuple(shape)})

    def transpose(self, *axes):
        if len(axes) == 1 and isinstance(axes[0], (tuple, list)):
            axes = tuple(axes[0])
        return _create("transpose", [self], {"axes": axes if axes else None})

    def sum(self, axis=None, keepdims=False):
        return _create("sum", [self], {"axis": axis, "keepdims": keepdims})

    def mean(self, axis=None, keepdims=False):
        return _create("mean", [self], {"axis": axis, "keepdims": keepdims})

    def astype(self, dtype):
        return _create("Cast", [self], {"dtype": np.dtype(dtype).name})

    def slice_axis(self, axis, begin, end):
        return _create("slice_axis", [self],
                       {"axis": axis, "begin": begin, "end": end})

    def expand_dims(self, axis):
        return _create("expand_dims", [self], {"axis": axis})

    def flatten(self):
        return _create("Flatten", [self], {})

    def softmax(self, axis=-1):
        return _create("softmax", [self], {"axis": axis})

    def log_softmax(self, axis=-1):
        return _create("log_softmax", [self], {"axis": axis})

    def square(self):
        return _create("square", [self], {})

    def sqrt(self):
        return _create("sqrt", [self], {})

    def exp(self):
        return _create("exp", [self], {})

    def log(self):
        return _create("log", [self], {})

    def abs(self):
        return _create("abs", [self], {})

    # ------------------------------------------------------------------
    # evaluation / shape inference
    # ------------------------------------------------------------------
    def eval(self, ctx=None, _train=False, **kwargs):
        """Evaluate eagerly with named NDArray inputs (ref: Symbol.eval)."""
        from ..ndarray import NDArray
        from ..ndarray.ndarray import invoke as nd_invoke
        from ..context import current_context
        ctx = ctx or (next(iter(kwargs.values())).ctx if kwargs
                      else current_context())
        env: Dict[int, List] = {}
        order = self._topo()
        results = _interpret_with(order, kwargs, mode="ndarray", train=_train)
        outs = [results[id(node)][idx] for node, idx in self._entries]
        return outs if len(outs) > 1 else outs[0]

    def infer_shape(self, *args, **kwargs):
        """Shape inference (ref: MXSymbolInferShapeEx backed by nnvm
        InferShape). Unknown parameter shapes are backward-inferred
        from the data shapes for the standard layers (FC/conv/norms/
        embedding), then every node is abstractly evaluated
        (jax.eval_shape). Returns (arg_shapes, out_shapes, aux_shapes)
        aligned with list_arguments()/list_outputs()/
        list_auxiliary_states(); raises MXNetError on failure instead
        of silently returning Nones."""
        if args:
            kwargs.update(zip(self.list_arguments(), args))
        shapes_by_name, out_avals, _ = _walk_infer(
            self, {k: tuple(v) for k, v in kwargs.items()}, {})
        arg_shapes = [shapes_by_name.get(n) for n in self.list_arguments()]
        out_shapes = [tuple(o.shape) for o in out_avals]
        aux_shapes = [shapes_by_name.get(n)
                      for n in self.list_auxiliary_states()]
        return arg_shapes, out_shapes, aux_shapes

    def infer_shape_partial(self, *args, **kwargs):
        """Like infer_shape but tolerates unresolved inputs (ref:
        MXSymbolInferShapePartialEx): unknowns come back as None."""
        try:
            return self.infer_shape(*args, **kwargs)
        except MXNetError:
            return None, None, None

    def infer_type(self, *args, **kwargs):
        """Dtype inference by abstract evaluation (ref:
        MXSymbolInferTypeEx). kwargs map input name -> dtype; unlisted
        inputs default to float32."""
        if args:
            kwargs.update(zip(self.list_arguments(), args))
        dtypes = {k: np.dtype(v) for k, v in kwargs.items()}
        # shapes are unknown here: use rank-1 placeholders, which every
        # registered impl accepts for dtype propagation purposes; fall
        # back to None on ops that demand real shapes
        input_names = self.list_inputs()
        try:
            shapes_by_name, out_avals, _ = _walk_infer(
                self, {n: (1,) for n in input_names}, dtypes)
        except Exception:
            return None, None, None
        by_name = dict(zip(input_names,
                           [dtypes.get(n, np.dtype(np.float32))
                            for n in input_names]))
        return ([by_name[n] for n in self.list_arguments()],
                [np.dtype(o.dtype) for o in out_avals],
                [by_name[n] for n in self.list_auxiliary_states()])

    # ------------------------------------------------------------------
    # serialization (MXNet symbol-JSON layout: nodes/arg_nodes/heads)
    # ------------------------------------------------------------------
    def tojson(self) -> str:
        order = self._topo()
        nid = {id(n): i for i, n in enumerate(order)}
        nodes = []
        for n in order:
            entry = {
                "op": "null" if n.is_variable else n.op.name,
                "name": n.name,
                "inputs": [[nid[id(s._entries[0][0])], s._entries[0][1], 0]
                           for s in n.inputs],
            }
            if n.attrs:
                entry["attrs"] = {k: json.dumps(v) if not isinstance(v, str)
                                  else v for k, v in n.attrs.items()
                                  if not k.startswith("__")}
            nodes.append(entry)
        heads = [[nid[id(n)], i, 0] for n, i in self._entries]
        arg_nodes = [i for i, n in enumerate(order) if n.is_variable]
        return json.dumps({"nodes": nodes, "arg_nodes": arg_nodes,
                           "node_row_ptr": list(range(len(nodes) + 1)),
                           "heads": heads,
                           "attrs": {"mxnet_version": ["int", 10900]}},
                          indent=2)

    def save(self, fname: str):
        with open(fname, "w") as f:
            f.write(self.tojson())

    # legacy executor API
    def simple_bind(self, ctx=None, grad_req="write", **shapes):
        from .executor import Executor
        return Executor(self, ctx, shapes, grad_req)

    def bind(self, ctx=None, args=None, args_grad=None, grad_req="write",
             aux_states=None, **kwargs):
        from .executor import Executor
        return Executor(self, ctx, None, grad_req, args=args,
                        args_grad=args_grad, aux_states=aux_states)


# ---------------------------------------------------------------------------
def _resolve_param_shapes(node, in_avals, shapes):
    """Backward-infer obvious parameter shapes (FC/conv weights, norms,
    embeddings) from the op's attrs + known data shape — the nnvm
    backward-InferShape role. Exotic graphs pass explicit shapes."""
    out = [None] * len(in_avals)
    opn = node.op.name
    data = in_avals[0] if in_avals else None
    if data is None:
        return out
    dshape = data.shape
    if opn == "FullyConnected":
        num_hidden = int(node.attrs["num_hidden"])
        flatten = node.attrs.get("flatten", True)
        d = int(np.prod(dshape[1:])) if flatten else dshape[-1]
        if len(in_avals) > 1 and in_avals[1] is None:
            out[1] = jax.ShapeDtypeStruct((num_hidden, d), np.float32)
        if len(in_avals) > 2 and in_avals[2] is None:
            out[2] = jax.ShapeDtypeStruct((num_hidden,), np.float32)
    elif opn == "Convolution":
        nf = int(node.attrs["num_filter"])
        k = tuple(node.attrs["kernel"])
        ng = int(node.attrs.get("num_group", 1))
        if len(in_avals) > 1 and in_avals[1] is None:
            out[1] = jax.ShapeDtypeStruct((nf, dshape[1] // ng) + k,
                                          np.float32)
        if len(in_avals) > 2 and in_avals[2] is None:
            out[2] = jax.ShapeDtypeStruct((nf,), np.float32)
    elif opn in ("BatchNorm", "LayerNorm", "InstanceNorm", "GroupNorm"):
        ax = int(node.attrs.get("axis", 1 if opn == "BatchNorm" else -1))
        c = dshape[ax % len(dshape)]
        for j in range(1, len(in_avals)):
            if in_avals[j] is None:
                out[j] = jax.ShapeDtypeStruct((c,), np.float32)
    elif opn == "Embedding":
        if len(in_avals) > 1 and in_avals[1] is None:
            out[1] = jax.ShapeDtypeStruct(
                (int(node.attrs["input_dim"]),
                 int(node.attrs["output_dim"])), np.float32)
    return out


def _walk_infer(sym: "Symbol", feed_shapes: Dict[str, tuple],
                feed_dtypes: Dict[str, Any]):
    """Iterative whole-graph shape/dtype inference: topo walk with
    per-node jax.eval_shape, backward-resolving unknown parameter
    shapes from op attrs (the nnvm InferShape role; shared by
    Symbol.infer_shape/infer_type, Module._infer_param_shapes, and
    visualization.print_summary). Returns (shapes_by_input_name,
    output avals, out-avals-by-node-name)."""
    from ..ops import canonical_attrs

    order = sym._topo()
    known: Dict[int, List] = {}
    shapes: Dict[str, tuple] = {}
    for node in order:
        if node.is_variable:
            if node.name in feed_shapes:
                dt = np.dtype(feed_dtypes.get(node.name, np.float32))
                known[id(node)] = [jax.ShapeDtypeStruct(
                    tuple(feed_shapes[node.name]), dt)]
                shapes[node.name] = tuple(feed_shapes[node.name])
            else:
                known[id(node)] = [None]
            continue
        ins = [known[id(s._entries[0][0])][s._entries[0][1]]
               for s in node.inputs]
        resolved = _resolve_param_shapes(node, ins, shapes)
        for s, sym_in in zip(resolved, node.inputs):
            src = sym_in._entries[0][0]
            if src.is_variable and known[id(src)][0] is None \
                    and s is not None:
                known[id(src)] = [s]
                shapes[src.name] = tuple(s.shape)
        ins = [known[id(s._entries[0][0])][s._entries[0][1]]
               for s in node.inputs]
        if any(i is None for i in ins):
            missing = [s._entries[0][0].name
                       for s, i in zip(node.inputs, ins) if i is None]
            raise MXNetError(
                "shape inference failed at %s: unknown input shape(s) %s"
                % (node.name, missing))
        ins, attrs = _in_slots(node, ins)
        attrs = dict(canonical_attrs(attrs))
        if node.op.needs_train_flag:
            attrs["_train"] = False
        fn = node.op.bind_attrs(attrs)
        if node.op.needs_rng:
            key_aval = jax.ShapeDtypeStruct((2,), np.uint32)
            outs = jax.eval_shape(fn, key_aval, *ins)
        else:
            outs = jax.eval_shape(fn, *ins)
        outs = list(outs) if isinstance(outs, (tuple, list)) else [outs]
        known[id(node)] = outs

    out_avals = [known[id(n)][i] for n, i in sym._entries]
    node_avals = {n.name: known[id(n)] for n in order if not n.is_variable}
    return shapes, out_avals, node_avals


def _create(opname: str, inputs: List[Symbol], attrs: Dict[str, Any],
            name: Optional[str] = None) -> Symbol:
    op = get_op(opname)
    attrs = {k: v for k, v in attrs.items() if v is not None}
    name = name or _NAMES.get(opname.lower())
    node = _Node(op, name, attrs, list(inputs))
    # determine output arity by abstract evaluation later; default 1,
    # fixed up during interpret. For known multi-output ops use metadata.
    node.num_outputs = _static_num_outputs(op, attrs)
    return Symbol([(node, i) for i in range(node.num_outputs)])


def _static_num_outputs(op: Operator, attrs) -> int:
    if op.name in ("split", "amp_multicast"):
        return int(attrs.get("num_outputs", 1))
    if isinstance(op.num_outputs, int) and op.num_outputs > 1:
        # registry-declared multi-output ops (quantize_v2 etc.):
        # num_outputs counts the visible ones, whatever a mutate_aux
        # op writes back beside them
        return op.num_outputs
    if op.name == "RNN":
        return 3 if attrs.get("mode", "lstm") == "lstm" else 2
    if op.name == "topk" and attrs.get("ret_typ") == "both":
        return 2
    return 1


def Variable(name: str, attr=None, shape=None, dtype=None, init=None,
             stype=None, **kwargs) -> Symbol:
    node = _Node(None, name, dict(attr or {}), [])
    if shape is not None:
        node.attrs["__shape__"] = tuple(shape)
    if dtype is not None:
        node.attrs["__dtype__"] = np.dtype(dtype).name
    return Symbol([(node, 0)])


var = Variable


def Group(symbols: Sequence[Symbol]) -> Symbol:
    entries = []
    for s in symbols:
        entries.extend(s._entries)
    return Symbol(entries)


def load_json(json_str: str) -> Symbol:
    data = json.loads(json_str)
    nodes_data = data["nodes"]
    built: List[Symbol] = []
    for nd_ in nodes_data:
        if nd_["op"] == "null":
            built.append(Variable(nd_["name"],
                                  attr=_parse_attrs(nd_.get("attrs", {}))))
        else:
            ins = [built[i][j] for i, j, *_ in nd_["inputs"]]
            attrs = _parse_attrs(nd_.get("attrs", {}))
            built.append(_create(nd_["op"], ins, attrs, name=nd_["name"]))
    heads = data.get("heads", [[len(nodes_data) - 1, 0, 0]])
    entries = []
    for h in heads:
        i, j = h[0], h[1]
        entries.append(built[i]._entries[j])
    return Symbol(entries)


def _parse_attrs(attrs):
    out = {}
    for k, v in attrs.items():
        if isinstance(v, str):
            try:
                out[k] = json.loads(v)
            except (ValueError, TypeError):
                out[k] = v
        else:
            out[k] = v
    return out


def load(fname: str) -> Symbol:
    with open(fname) as f:
        return load_json(f.read())


# ---------------------------------------------------------------------------
# graph interpretation / compilation
# ---------------------------------------------------------------------------
def _interpret_with(order: List[_Node], feed: Dict[str, Any], mode: str,
                    train: bool, rng=None):
    """Topo-order evaluation. mode='ndarray': eager NDArray invoke (keeps
    autograd recording); mode='jax': raw jax arrays (for jit tracing)."""
    results: Dict[int, List] = {}
    from ..ndarray.ndarray import invoke as nd_invoke
    from .. import random as rand_mod
    for node in order:
        if node.is_variable:
            if node.name not in feed:
                raise MXNetError("missing input %r" % node.name)
            results[id(node)] = [feed[node.name]]
            continue
        ins = [results[id(s._entries[0][0])][s._entries[0][1]]
               for s in node.inputs]
        ins, attrs = _in_slots(node, ins)
        if mode == "ndarray":
            out = nd_invoke(node.op, ins, attrs)
            outs = list(out) if isinstance(out, tuple) else [out]
        else:
            if node.op.needs_train_flag:
                attrs["_train"] = train
            fn = node.op.bind_attrs(dict(canonical_attrs(attrs)))
            if node.op.needs_rng:
                key = rng[0]
                rng[0], sub = jax.random.split(key)
                out = fn(sub, *ins)
            else:
                out = fn(*ins)
            outs = list(out) if isinstance(out, (tuple, list)) else [out]
            # apply mutate-aux writebacks within the trace: the new aux
            # value replaces the variable's value for downstream nodes
            if node.op.mutate_aux:
                n_extra = 0
                for extra_idx, in_idx in node.op.mutate_aux.items():
                    if extra_idx < len(outs):
                        src = node.inputs[in_idx]._entries[0][0]
                        results[id(src)] = [outs[extra_idx]]
                        n_extra += 1
                outs = outs[:len(outs) - n_extra]
        results[id(node)] = outs
        if len(outs) > node.num_outputs:
            node.num_outputs = len(outs)
    return results


def compile_graph(sym: Symbol, input_names: List[str], train: bool = False,
                  return_aux: bool = False):
    """Build a pure function jax_fn(feed_dict[, rng]) -> list of jax arrays.

    This is the whole replacement for GraphExecutor::Init + nnvm passes:
    XLA receives one traced program and does fusion/memory planning
    (SURVEY.md §7.0 table, row "GraphExecutor + nnvm passes")."""
    order = sym._topo()
    rng_ops = [n.op for n in order if (not n.is_variable) and n.op.needs_rng]
    # one key feeds the whole graph; if any op is restricted to a specific
    # PRNG impl (poisson family -> threefry2x32), the key must be created
    # with that impl — threefry keys work for every sampler, the rbg
    # hardware PRNG does not (jax.random.poisson is threefry-only).
    # needs_rng is falsy (no rng) or the impl string to create keys with.
    needs_rng = False
    if rng_ops:
        needs_rng = next((op.rng_impl for op in rng_ops if op.rng_impl),
                         "default")
    aux_nodes = [n for n in order if n.is_variable and n.attrs.get("__aux__")]

    def fn(feed, rng=None):
        if rng is None:
            from .. import random as _random
            impl = needs_rng if needs_rng not in (False, "default") \
                else _random._IMPL
            rng = jax.random.key(0, impl=impl)
        rng_box = [rng]
        results = _interpret_with(order, feed, mode="jax", train=train,
                                  rng=rng_box)
        outs = [results[id(node)][idx] for node, idx in sym._entries]
        if return_aux:
            aux = {n.name: results[id(n)][0] for n in aux_nodes}
            return outs, aux
        return outs

    return fn, needs_rng


# generated op namespace: mx.sym.<op> builds graph nodes
def _make_sym_function(op: Operator):
    from ..ndarray.register import op_array_params
    array_params = op_array_params(op)
    variadic = any(n.startswith("*") for n in array_params)
    fixed_names = [n for n in array_params if not n.startswith("*")]

    def fn(*args, **kwargs):
        name = kwargs.pop("name", None)
        kwargs.pop("out", None)
        inputs = []
        args = list(args)
        if variadic and len(args) == 1 and isinstance(args[0], (list, tuple)):
            args = list(args[0])
        seen_none = False
        for a in args:
            if isinstance(a, Symbol):
                if seen_none:
                    # a skipped middle None would shift this Symbol into
                    # the wrong input slot — only trailing Nones are safe
                    raise TypeError(
                        "%s: positional Symbol after a None argument"
                        % op.name)
                inputs.append(a)
            elif a is None:
                seen_none = True  # optional input omitted (e.g. no-bias FC)
            else:
                raise TypeError("%s: positional args must be Symbols" % op.name)
        if not variadic:
            # bind keyword tensors BY NAME; a gap before a provided
            # tensor is kept as the inputs' slots in the node's attrs
            # (nodes hold no null inputs: _in_slots). An op that writes
            # an input back names it by position among the node's
            # inputs, so a gap may only come after the ones it writes
            pending = []
            slots = list(range(len(inputs)))
            for slot, pname in enumerate(fixed_names[len(inputs):],
                                         len(inputs)):
                if pname in kwargs and isinstance(kwargs[pname], Symbol):
                    if pending and any(
                            i >= fixed_names.index(pending[0])
                            for i in op.mutate_aux.values()):
                        raise TypeError(
                            "%s: optional tensor(s) %s omitted before an "
                            "input the op writes back — symbolic mode "
                            "needs the earlier inputs too"
                            % (op.name, pending))
                    inputs.append(kwargs.pop(pname))
                    slots.append(slot)
                else:
                    if pname in kwargs and kwargs[pname] is None:
                        kwargs.pop(pname)
                    pending.append(pname)
            if slots != list(range(len(slots))):
                kwargs[_SLOTS] = tuple(slots)
        return _create(op.name, inputs, kwargs, name=name)

    fn.__name__ = op.name
    fn.__doc__ = op.impl.__doc__
    return fn


def _populate():
    g = globals()
    for name in list_ops():
        op = _OPS[name]
        f = _make_sym_function(op)
        g[name] = f
        for alias, canon in _ALIASES.items():
            if canon == name:
                g[alias] = f


_populate()
from . import subgraph  # noqa: E402,F401
