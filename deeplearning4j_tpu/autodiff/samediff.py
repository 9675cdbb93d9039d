"""SameDiff-equivalent: declare a graph, compile the whole step.

Reference: `org/nd4j/autodiff/samediff/SameDiff.java` (~7k LoC) + sessions
(`internal/{AbstractSession,InferenceSession,TrainingSession}.java`) +
codegen'd op namespaces (`samediff/ops/SD{Math,NN,CNN,RNN,Loss}.java`).

Architectural inversion (SURVEY.md §3.2): the reference interprets the graph
op-by-op in Java with a JNI crossing per op and hand-built `doDiff` gradient
graphs; here the declared graph is *traced into one jax function*, `jax.jit`
compiles the entire training step to a single XLA executable, and autodiff is
`jax.grad` — no per-op gradient rules, no interpreter.  Control-flow ops
(Enter/Exit/Switch/Merge/NextIteration frames) are replaced by structured
`lax.cond`/`lax.while_loop`/`lax.scan` via `SameDiff.cond`/
`SameDiff.while_loop`/`SameDiff.scan`: each body is traced into a
serializable child graph (`_SubGraph`), so control flow survives save/load
and differentiates through `jax.grad` (cond and scan; while is fwd-only,
as lax defines).

Serialization replaces FlatBuffers with a zip of graph-JSON + raw tensors
(same zip discipline as utils.serialization).
"""
from __future__ import annotations

import dataclasses
import json
import zipfile
from typing import Any, Callable, Dict, List, Optional, Sequence, Tuple, Union

import jax
import jax.numpy as jnp
import numpy as np

from deeplearning4j_tpu.autodiff.ops import OP_TABLE
from deeplearning4j_tpu.nn.trainer import CompiledStepOwner
from deeplearning4j_tpu.ops.initializers import init_weights
from deeplearning4j_tpu.train.updaters import Adam, IUpdater


# ---------------------------------------------------------------------------
# Graph nodes
# ---------------------------------------------------------------------------

@dataclasses.dataclass
class Node:
    name: str
    kind: str                   # placeholder | variable | constant | op
    op: Optional[str] = None
    inputs: Tuple[str, ...] = ()
    attrs: Dict[str, Any] = dataclasses.field(default_factory=dict)
    shape: Optional[Tuple[int, ...]] = None
    dtype: str = "float32"


class SDVariable:
    """Symbolic handle into a SameDiff graph (reference `SDVariable`)."""

    def __init__(self, sd: "SameDiff", name: str):
        self.sd = sd
        self.name = name

    # -- operator sugar (reference SDVariable.add/mul/mmul/...) --
    def _coerce(self, other) -> "SDVariable":
        return other if isinstance(other, SDVariable) \
            else self.sd.constant(None, other)

    def __add__(self, o): return self.sd.op("add", self, self._coerce(o))
    def __radd__(self, o): return self.sd.op("add", self._coerce(o), self)
    def __sub__(self, o): return self.sd.op("sub", self, self._coerce(o))
    def __rsub__(self, o): return self.sd.op("sub", self._coerce(o), self)
    def __mul__(self, o): return self.sd.op("mul", self, self._coerce(o))
    def __rmul__(self, o): return self.sd.op("mul", self._coerce(o), self)
    def __truediv__(self, o): return self.sd.op("div", self, self._coerce(o))
    def __rtruediv__(self, o): return self.sd.op("div", self._coerce(o), self)
    def __pow__(self, o): return self.sd.op("pow", self, self._coerce(o))
    def __neg__(self): return self.sd.op("neg", self)
    def __matmul__(self, o): return self.sd.op("matmul", self, self._coerce(o))

    def mmul(self, o): return self.sd.op("matmul", self, self._coerce(o))
    def add(self, o): return self.__add__(o)
    def sub(self, o): return self.__sub__(o)
    def mul(self, o): return self.__mul__(o)
    def reshape(self, *shape): return self.sd.op("reshape", self, shape=list(shape))
    def transpose(self, *perm):
        return self.sd.op("transpose", self, perm=list(perm) or None)
    def sum(self, axis=None, keepdims=False):
        return self.sd.op("sum", self, axis=axis, keepdims=keepdims)
    def mean(self, axis=None, keepdims=False):
        return self.sd.op("mean", self, axis=axis, keepdims=keepdims)
    def max(self, axis=None, keepdims=False):
        return self.sd.op("max", self, axis=axis, keepdims=keepdims)
    def min(self, axis=None, keepdims=False):
        return self.sd.op("min", self, axis=axis, keepdims=keepdims)
    def std(self, axis=None, keepdims=False):
        return self.sd.op("std", self, axis=axis, keepdims=keepdims)
    def argmax(self, axis=-1): return self.sd.op("argmax", self, axis=axis)
    def rename(self, name: str) -> "SDVariable":
        return self.sd.rename(self.name, name)

    def eval(self, feeds: Optional[Dict[str, Any]] = None):
        return self.sd.output(feeds or {}, self.name)[self.name]

    def get_arr(self):
        """Current value for variables/constants (reference
        `SDVariable.getArr`)."""
        node = self.sd._nodes[self.name]
        if node.kind == "variable":
            return self.sd.variables_[self.name]
        if node.kind == "constant":
            return self.sd._constants[self.name]
        raise ValueError(f"{self.name} has no stored array (kind={node.kind})")

    def __repr__(self):
        return f"SDVariable({self.name!r})"


# ---------------------------------------------------------------------------
# Op namespaces (reference codegen'd SDMath / SDNN / SDCNN / SDRNN / SDLoss)
# ---------------------------------------------------------------------------

class _Namespace:
    def __init__(self, sd: "SameDiff"):
        self._sd = sd


class SDMath(_Namespace):
    def __getattr__(self, op):
        if op.startswith("_"):
            raise AttributeError(op)
        if op not in OP_TABLE:
            raise AttributeError(
                f"No op '{op}' registered (reference: unmapped op error in "
                "ImportGraph — add via autodiff.ops.register_op)")

        def call(*args, name=None, **attrs):
            return self._sd.op(op, *args, name=name, **attrs)
        return call


class SDNN(_Namespace):
    def relu(self, x, name=None): return self._sd.op("relu", x, name=name)
    def sigmoid(self, x, name=None): return self._sd.op("sigmoid", x, name=name)
    def tanh(self, x, name=None): return self._sd.op("tanh", x, name=name)
    def gelu(self, x, name=None): return self._sd.op("gelu", x, name=name)
    def elu(self, x, name=None): return self._sd.op("elu", x, name=name)
    def softmax(self, x, axis=-1, name=None):
        return self._sd.op("softmax", x, axis=axis, name=name)
    def log_softmax(self, x, axis=-1, name=None):
        return self._sd.op("log_softmax", x, axis=axis, name=name)
    def linear(self, x, w, b=None, name=None):
        args = (x, w) if b is None else (x, w, b)
        return self._sd.op("linear", *args, name=name)
    def layer_norm(self, x, gain, bias=None, eps=1e-5, name=None):
        args = (x, gain) if bias is None else (x, gain, bias)
        return self._sd.op("layer_norm", *args, eps=eps, name=name)
    def dropout(self, x, p=0.5, name=None):
        """Active only during fit() (rng is fed by the train step); each
        dropout site folds its own tag so masks are independent."""
        site = self._sd.op("rng_fold_opt", self._sd._rng_var(),
                           tag=self._sd._next_rng_tag())
        return self._sd.op("dropout", x, site, p=p, name=name)
    def batch_norm(self, x, mean, var, gamma=None, beta=None, eps=1e-5,
                   name=None):
        args = [x, mean, var] + ([gamma] if gamma is not None else []) \
            + ([beta] if beta is not None else [])
        return self._sd.op("batch_norm", *args, eps=eps, name=name)
    def multi_head_dot_product_attention(self, q, k, v, mask=None, name=None):
        args = (q, k, v) if mask is None else (q, k, v, mask)
        return self._sd.op("dot_product_attention", *args, name=name)


class SDCNN(_Namespace):
    def conv2d(self, x, w, b=None, stride=(1, 1), padding="SAME",
               dilation=(1, 1), name=None):
        args = (x, w) if b is None else (x, w, b)
        return self._sd.op("conv2d", *args, stride=tuple(stride),
                           padding=padding, dilation=tuple(dilation),
                           name=name)
    def max_pooling2d(self, x, kernel=(2, 2), stride=(2, 2), padding="VALID",
                      name=None):
        return self._sd.op("max_pooling2d", x, kernel=tuple(kernel),
                           stride=tuple(stride), padding=padding, name=name)
    def avg_pooling2d(self, x, kernel=(2, 2), stride=(2, 2), padding="VALID",
                      name=None):
        return self._sd.op("avg_pooling2d", x, kernel=tuple(kernel),
                           stride=tuple(stride), padding=padding, name=name)


class SDRNN(_Namespace):
    def lstm_layer(self, x, w, rw, b, name=None):
        """Whole-sequence LSTM via lax.scan (the cuDNN-LSTM → scan item,
        SURVEY.md §7 hard part (d)); IFOG gate order, [B,T,F] in,
        [B,T,H] out."""
        return self._sd.op("lstm_layer", x, w, rw, b, name=name)


class _TableNamespace(_Namespace):
    """Generic OP_TABLE delegation scoped by a name list (the codegen'd
    namespace classes collapse to a whitelist over the registry)."""

    OPS: tuple = ()

    def __getattr__(self, op):
        if op.startswith("_") or (self.OPS and op not in self.OPS):
            raise AttributeError(
                f"{type(self).__name__} has no op '{op}'")
        if op not in OP_TABLE:
            raise AttributeError(
                f"No op '{op}' registered (reference: unmapped op error in "
                "ImportGraph — add via autodiff.ops.register_op)")

        def call(*args, name=None, **attrs):
            return self._sd.op(op, *args, name=name, **attrs)
        return call


class SDBitwise(_TableNamespace):
    """Reference `SDBitwise` namespace."""
    OPS = ("bitwise_and", "bitwise_or", "bitwise_xor", "bitwise_not",
           "shift_left", "shift_right", "cyclic_shift_left",
           "bits_hamming_distance", "toggle_bits")


class SDImage(_TableNamespace):
    """Reference `SDImage` namespace."""
    OPS = ("rgb_to_hsv", "hsv_to_rgb", "rgb_to_yiq", "yiq_to_rgb",
           "rgb_to_yuv", "yuv_to_rgb", "rgb_to_grs", "adjust_hue",
           "adjust_saturation", "adjust_contrast", "crop_and_resize",
           "extract_image_patches", "non_max_suppression",
           "resize_bilinear", "resize_nearest", "image_resize")


class SDLinalg(_TableNamespace):
    """Reference `SDLinalg` namespace."""
    OPS = ("cholesky", "solve", "triangular_solve", "matrix_inverse",
           "matrix_determinant", "log_matrix_determinant", "qr", "svd",
           "eig_sym", "lstsq", "lu", "pinv", "expm", "matrix_band_part",
           "matrix_diag", "matrix_diag_part", "matrix_set_diag", "mmul",
           "matmul", "tri", "tril", "triu", "cross", "diag", "diag_part",
           "trace", "einsum")


class SDRandom(_Namespace):
    """Reference `SDRandom` namespace; the PRNG key is the train step's
    per-iteration rng feed (same mechanism as dropout), so samples change
    every fit() step and are deterministic per (seed, iteration).  Each
    random node folds a unique tag into the shared per-step key so
    independent sample sites draw independent streams."""

    _OPS = ("random_uniform", "random_normal", "random_bernoulli",
            "random_exponential", "random_gamma", "random_poisson",
            "random_shuffle", "multinomial")

    def _site_key(self):
        return self._sd.op("rng_fold", self._sd._rng_var(),
                           tag=self._sd._next_rng_tag())

    def __getattr__(self, op):
        if op.startswith("_") or op not in self._OPS:
            raise AttributeError(f"SDRandom has no op '{op}'")

        def call(*args, name=None, **attrs):
            return self._sd.op(op, self._site_key(), *args, name=name,
                               **attrs)
        return call

    # reference-style aliases (shape/params ride as attrs: the executor
    # calls OP_TABLE[op](*inputs, **attrs))
    def uniform(self, low, high, shape, name=None):
        return self._sd.op("random_uniform", self._site_key(),
                           shape=tuple(shape), minval=low, maxval=high,
                           name=name)

    def normal(self, mean, stddev, shape, name=None):
        return self._sd.op("random_normal", self._site_key(),
                           shape=tuple(shape), mean=mean, stddev=stddev,
                           name=name)

    def bernoulli(self, p, shape, name=None):
        return self._sd.op("random_bernoulli", self._site_key(),
                           shape=tuple(shape), p=p, name=name)


class SDLoss(_Namespace):
    def softmax_cross_entropy(self, labels, logits, name=None):
        return self._sd.op("softmax_cross_entropy", labels, logits, name=name)
    def sparse_softmax_cross_entropy(self, labels, logits, name=None):
        return self._sd.op("sparse_softmax_cross_entropy", labels, logits,
                           name=name)
    def sigmoid_cross_entropy(self, labels, logits, name=None):
        return self._sd.op("sigmoid_cross_entropy", labels, logits, name=name)
    def mean_squared_error(self, labels, preds, name=None):
        return self._sd.op("mean_squared_error", labels, preds, name=name)
    def absolute_difference(self, labels, preds, name=None):
        return self._sd.op("absolute_difference", labels, preds, name=name)
    def l2_loss(self, x, name=None):
        return self._sd.op("l2_loss", x, name=name)
    def huber_loss(self, labels, preds, delta=1.0, name=None):
        return self._sd.op("huber_loss", labels, preds, delta=delta, name=name)
    def log_loss(self, labels, probs, name=None):
        return self._sd.op("log_loss", labels, probs, name=name)
    def cosine_distance(self, labels, preds, axis=-1, name=None):
        return self._sd.op("cosine_distance", labels, preds, axis=axis,
                           name=name)


# lstm_layer is registered in autodiff.ops (IFOG single-output form —
# the sd.rnn.lstm_layer contract); lstm_layer_full carries the reference
# lstmLayer's (ys, h, c) output mode.


# ---------------------------------------------------------------------------
# TrainingConfig
# ---------------------------------------------------------------------------

@dataclasses.dataclass
class TrainingConfig:
    """Reference `TrainingConfig`: updater + which placeholders receive
    features/labels + l1/l2."""

    updater: IUpdater = dataclasses.field(default_factory=lambda: Adam(1e-3))
    data_set_feature_mapping: Sequence[str] = ()
    data_set_label_mapping: Sequence[str] = ()
    l1: float = 0.0
    l2: float = 0.0

    def to_json(self) -> dict:
        return {"updater": self.updater.to_json(),
                "features": list(self.data_set_feature_mapping),
                "labels": list(self.data_set_label_mapping),
                "l1": self.l1, "l2": self.l2}

    @staticmethod
    def from_json(d: dict) -> "TrainingConfig":
        return TrainingConfig(updater=IUpdater.from_json(d["updater"]),
                              data_set_feature_mapping=d["features"],
                              data_set_label_mapping=d["labels"],
                              l1=d.get("l1", 0.0), l2=d.get("l2", 0.0))


# ---------------------------------------------------------------------------
# Control flow (reference: Switch/Merge/Enter/Exit/NextIteration frames in
# `org/nd4j/autodiff/samediff/internal/AbstractSession.java`; here each body
# is traced into a child graph and lowered to lax.cond/while_loop/scan —
# SURVEY.md §3.2's "frames → structured lax control flow" inversion)
# ---------------------------------------------------------------------------

_CONTROL_FLOW_OPS = ("cond", "while_loop", "scan")


class _SubGraph:
    """A traced sub-function: its own node set + constants, positional
    placeholder args, named outputs.  Serializes to plain JSON so
    control-flow nodes survive SameDiff.save/load."""

    def __init__(self, sd: "SameDiff", arg_names: List[str],
                 out_names: List[str]):
        self.sd = sd
        self.arg_names = arg_names
        self.out_names = out_names

    @staticmethod
    def trace(fn: Callable, n_args: int) -> "_SubGraph":
        child = SameDiff()
        phs = [child.placeholder(f"__arg{i}__") for i in range(n_args)]
        outs = fn(child, *phs)
        if isinstance(outs, SDVariable):
            outs = (outs,)
        out_names = []
        for o in outs:
            if not isinstance(o, SDVariable) or o.sd is not child:
                raise ValueError(
                    "control-flow body must return SDVariable(s) built in "
                    "the scope it was handed (fn(scope, *args) -> vars)")
            out_names.append(o.name)
        if child.variables_:
            raise ValueError(
                "control-flow bodies cannot declare trainable variables — "
                "declare them in the outer graph and pass as operands")
        return _SubGraph(child, [p.name for p in phs], out_names)

    def call(self, args: Sequence[Any]) -> Tuple[Any, ...]:
        feeds = dict(zip(self.arg_names, args))
        outs = self.sd._eval_graph(feeds, {}, self.out_names)
        return tuple(outs[n] for n in self.out_names)

    def to_json(self) -> dict:
        consts = {}
        for k, v in self.sd._constants.items():
            a = np.asarray(v)
            consts[k] = {"data": a.tolist(), "dtype": str(a.dtype),
                         "shape": list(a.shape)}
        return {"nodes": [dataclasses.asdict(n)
                          for n in self.sd._nodes.values()],
                "constants": consts,
                "args": self.arg_names, "outputs": self.out_names}

    @staticmethod
    def from_json(d: dict) -> "_SubGraph":
        child = SameDiff()
        for nd in d["nodes"]:
            node = Node(name=nd["name"], kind=nd["kind"], op=nd.get("op"),
                        inputs=tuple(nd["inputs"]),
                        attrs=_detuple_attrs(nd.get("attrs", {})),
                        shape=None if nd.get("shape") is None
                        else tuple(nd["shape"]),
                        dtype=nd.get("dtype", "float32"))
            child._nodes[node.name] = node
        child._constants = {
            k: jnp.asarray(np.array(v["data"], dtype=v["dtype"])
                           .reshape(v["shape"]))
            for k, v in d["constants"].items()}
        return _SubGraph(child, list(d["args"]), list(d["outputs"]))


def _eval_control_flow(node: "Node", args: List[Any]) -> Any:
    """Lower a control-flow node to the matching lax primitive.  Runs at
    trace time only (inside jit), so re-hydrating subgraphs from their JSON
    attrs costs nothing at execution time."""
    a = node.attrs
    if node.op == "cond":
        tg = _SubGraph.from_json(a["true_graph"])
        fg = _SubGraph.from_json(a["false_graph"])
        pred, operands = args[0], tuple(args[1:])
        pred = jnp.reshape(jnp.asarray(pred), ()).astype(bool)
        # lax.cond requires identical output types; promote pairwise so a
        # weakly-typed constant in one branch doesn't poison the node.
        t_shape = jax.eval_shape(tg.call, operands)
        f_shape = jax.eval_shape(fg.call, operands)
        dts = [jnp.promote_types(t.dtype, f.dtype)
               for t, f in zip(t_shape, f_shape)]
        out = jax.lax.cond(
            pred,
            lambda ops: tuple(o.astype(d)
                              for o, d in zip(tg.call(ops), dts)),
            lambda ops: tuple(o.astype(d)
                              for o, d in zip(fg.call(ops), dts)),
            operands)
        return out[0] if len(out) == 1 else tuple(out)
    if node.op == "while_loop":
        cg = _SubGraph.from_json(a["cond_graph"])
        bg = _SubGraph.from_json(a["body_graph"])
        init = tuple(jnp.asarray(x) for x in args)
        dts = [x.dtype for x in init]     # body must preserve state types
        state = jax.lax.while_loop(
            lambda s: jnp.reshape(cg.call(s)[0], ()).astype(bool),
            lambda s: tuple(o.astype(d) for o, d in zip(bg.call(s), dts)),
            init)
        return state[0] if len(state) == 1 else tuple(state)
    if node.op == "scan":
        bg = _SubGraph.from_json(a["body_graph"])
        n_carry = int(a["n_carry"])
        consts = tuple(args[n_carry + 1:])

        def body(carry, x):
            outs = bg.call(tuple(carry) + (x,) + consts)
            new_carry = tuple(o.astype(c.dtype)
                              for o, c in zip(outs[:n_carry], carry))
            return new_carry, tuple(outs[n_carry:])

        carry, ys = jax.lax.scan(body, tuple(args[:n_carry]), args[n_carry])
        return tuple(carry) + tuple(ys)
    raise KeyError(node.op)


# ---------------------------------------------------------------------------
# SameDiff
# ---------------------------------------------------------------------------

RNG_FEED = "__dropout_rng__"


class SameDiff(CompiledStepOwner):
    """The graph container (reference `SameDiff.create()`).  The executable
    cache, the schedule and the disk key of its compiled train step are
    `CompiledStepOwner`'s (nn/trainer.py); the step builder is its own."""

    _AOT_PREFIX = "samediff"
    _DONATED = (0, 1)         # variables, opt_state

    def __init__(self):
        super().__init__()
        self._nodes: Dict[str, Node] = {}
        self.variables_: Dict[str, jnp.ndarray] = {}   # trainable values
        self._constants: Dict[str, jnp.ndarray] = {}
        self._loss_names: List[str] = []
        self._counter = 0
        self.training_config: Optional[TrainingConfig] = None
        self.opt_state_: Optional[Any] = None
        self.iteration = 0
        self.epoch = 0
        self._train_step = None
        self._scan_step = None
        self._output_fns: Dict[Tuple[str, ...], Callable] = {}
        self._key = jax.random.PRNGKey(0)
        self.math = SDMath(self)
        self.nn = SDNN(self)
        self.cnn = SDCNN(self)
        self.rnn = SDRNN(self)
        self.loss = SDLoss(self)
        self.bitwise = SDBitwise(self)
        self.image = SDImage(self)
        self.linalg = SDLinalg(self)
        self.random = SDRandom(self)

    @staticmethod
    def create() -> "SameDiff":
        return SameDiff()

    # ---- naming ----
    def _fresh(self, base: str) -> str:
        # ':' is illegal in TF/ONNX node names, so auto-generated names can
        # never collide with names arriving later from a model import
        self._counter += 1
        name = f"{base}:{self._counter}"
        while name in self._nodes:
            self._counter += 1
            name = f"{base}:{self._counter}"
        return name

    def _add(self, node: Node) -> SDVariable:
        if node.name in self._nodes:
            raise ValueError(f"Duplicate variable name '{node.name}'")
        self._nodes[node.name] = node
        self._invalidate()
        return SDVariable(self, node.name)

    def _invalidate_steps(self) -> None:
        self._train_step = None
        self._scan_step = None

    def _invalidate(self):
        self._invalidate_steps()
        self._output_fns = {}

    # ---- declaration API ----
    def placeholder(self, name: str, shape: Optional[Sequence[int]] = None,
                    dtype: str = "float32") -> SDVariable:
        """reference `sd.placeHolder` (-1 = batch dim, kept as None)."""
        shp = None if shape is None else tuple(
            None if s in (-1, None) else int(s) for s in shape)
        return self._add(Node(name, "placeholder", shape=shp, dtype=dtype))

    place_holder = placeholder

    def var(self, name: str, init: Union[np.ndarray, str],
            *shape: int, dtype: str = "float32") -> SDVariable:
        """Trainable variable: `sd.var("w", array)` or
        `sd.var("w", "XAVIER", 784, 10)` (reference weight-init schemes)."""
        if isinstance(init, str):
            self._key, sub = jax.random.split(self._key)
            arr = init_weights(sub, tuple(shape), init, jnp.dtype(dtype))
        else:
            arr = jnp.asarray(init)
        v = self._add(Node(name, "variable", shape=tuple(arr.shape),
                           dtype=str(arr.dtype)))
        self.variables_[name] = arr
        return v

    def zero(self, name: str, *shape: int, dtype: str = "float32"):
        return self.var(name, np.zeros(shape, dtype))

    def one(self, name: str, *shape: int, dtype: str = "float32"):
        return self.var(name, np.ones(shape, dtype))

    def constant(self, name: Optional[str], value) -> SDVariable:
        arr = jnp.asarray(value)
        name = name or self._fresh("const")
        v = self._add(Node(name, "constant", shape=tuple(arr.shape),
                           dtype=str(arr.dtype)))
        self._constants[name] = arr
        return v

    def op(self, opname: str, *inputs, name: Optional[str] = None,
           **attrs) -> SDVariable:
        if opname not in OP_TABLE and opname not in _CONTROL_FLOW_OPS:
            raise KeyError(
                f"Unmapped op '{opname}' — the reference raises the same "
                "named error from ImportGraph/OpMappingRegistry; register "
                "via autodiff.ops.register_op")
        ins = []
        for x in inputs:
            if isinstance(x, SDVariable):
                if x.sd is not self:
                    raise ValueError(
                        f"'{x.name}' belongs to a different SameDiff scope "
                        "(reference: cross-frame use needs Enter; here, pass "
                        "it as an operand to the control-flow op instead)")
                ins.append(x.name)
            else:
                ins.append(self.constant(None, x).name)
        name = name or self._fresh(opname)
        return self._add(Node(name, "op", op=opname, inputs=tuple(ins),
                              attrs=dict(attrs)))

    def rename(self, old: str, new: str) -> SDVariable:
        if new in self._nodes:
            raise ValueError(f"Cannot rename '{old}' to '{new}': name taken")
        node = self._nodes.pop(old)
        node.name = new
        self._nodes[new] = node
        if old in self.variables_:
            self.variables_[new] = self.variables_.pop(old)
        if old in self._constants:
            self._constants[new] = self._constants.pop(old)
        for n in self._nodes.values():
            n.inputs = tuple(new if i == old else i for i in n.inputs)
        self._loss_names = [new if n == old else n for n in self._loss_names]
        self._invalidate()
        return SDVariable(self, new)

    def get_variable(self, name: str) -> SDVariable:
        return SDVariable(self, name)

    def _rng_var(self) -> SDVariable:
        """Hidden placeholder feeding dropout rng during training."""
        if RNG_FEED not in self._nodes:
            self._add(Node(RNG_FEED, "placeholder", dtype="uint32"))
        return SDVariable(self, RNG_FEED)

    def _next_rng_tag(self) -> int:
        """Unique static tag per stochastic node; folded into the shared
        per-step key so sample sites draw independent streams.  Seeded from
        the tags already present in the graph so nodes added after a
        save()/load() round-trip never reuse an existing tag."""
        tag = getattr(self, "_rng_tag", None)
        if tag is None:
            tag = 1 + max(
                (int(n.attrs.get("tag", -1)) for n in self._nodes.values()
                 if n.kind == "op" and n.op in ("rng_fold", "rng_fold_opt")),
                default=-1)
        self._rng_tag = tag + 1
        return tag

    # ---- control flow (reference Switch/Merge/Enter/Exit → lax) ----
    def _split_outputs(self, v: SDVariable, n_out: int):
        if n_out == 1:
            return v
        return tuple(self.op("tuple_get", v, index=i) for i in range(n_out))

    def cond(self, pred, true_fn: Callable, false_fn: Callable,
             *operands, name: Optional[str] = None):
        """`sd.cond(pred, lambda s, x: ..., lambda s, x: ..., x)` →
        lax.cond.  Each branch fn receives a fresh scope plus one SDVariable
        per operand and returns the same number of outputs as the other
        branch.  Differentiable (reference: Switch/Merge frames in
        AbstractSession.java had no gradient support at all)."""
        n = len(operands)
        tg = _SubGraph.trace(true_fn, n)
        fg = _SubGraph.trace(false_fn, n)
        if len(tg.out_names) != len(fg.out_names):
            raise ValueError(
                f"cond branches disagree on output arity "
                f"({len(tg.out_names)} vs {len(fg.out_names)})")
        v = self.op("cond", pred, *operands, name=name,
                    true_graph=tg.to_json(), false_graph=fg.to_json(),
                    n_out=len(tg.out_names))
        return self._split_outputs(v, len(tg.out_names))

    def while_loop(self, cond_fn: Callable, body_fn: Callable,
                   *init, name: Optional[str] = None):
        """`sd.while_loop(lambda s, i, acc: ..., lambda s, i, acc: (...), i0,
        acc0)` → lax.while_loop.  `cond_fn` returns one scalar-bool output;
        `body_fn` returns one output per loop-state operand.  Forward-only
        (lax.while_loop is not reverse-differentiable; use scan for trainable
        recurrences — same restriction the reference's While frames had in
        practice)."""
        n = len(init)
        cg = _SubGraph.trace(cond_fn, n)
        if len(cg.out_names) != 1:
            raise ValueError("while_loop cond_fn must return exactly one "
                             "(scalar bool) output")
        bg = _SubGraph.trace(body_fn, n)
        if len(bg.out_names) != n:
            raise ValueError(
                f"while_loop body_fn must return {n} outputs (one per loop "
                f"state operand), got {len(bg.out_names)}")
        v = self.op("while_loop", *init, name=name,
                    cond_graph=cg.to_json(), body_graph=bg.to_json())
        return self._split_outputs(v, n)

    def scan(self, body_fn: Callable, init, xs, *, consts=(),
             name: Optional[str] = None):
        """`sd.scan(lambda s, carry..., x, *consts: (new_carry..., y...),
        init, xs, consts=(w, ...))` → lax.scan over the leading axis of
        `xs`.  `consts` are loop-invariant operands (weights etc.) handed to
        every step — the closure-free substitute for the reference frames'
        Enter-as-constant edges.  Returns `(final_carry, ys)` where `ys` are
        the per-step outputs stacked on a new leading axis.  Fully
        differentiable — this is the structured replacement for the
        reference's NextIteration/loop frames."""
        carry = tuple(init) if isinstance(init, (tuple, list)) else (init,)
        n_carry = len(carry)
        consts = tuple(consts)
        bg = _SubGraph.trace(body_fn, n_carry + 1 + len(consts))
        n_ys = len(bg.out_names) - n_carry
        if n_ys < 1:
            raise ValueError(
                f"scan body_fn must return the {n_carry} new carry value(s) "
                "plus at least one per-step output")
        v = self.op("scan", *carry, xs, *consts, name=name,
                    body_graph=bg.to_json(), n_carry=n_carry,
                    n_consts=len(consts))
        parts = self._split_outputs(v, n_carry + n_ys)
        fc = parts[:n_carry]
        ys = parts[n_carry:]
        final_carry = fc if isinstance(init, (tuple, list)) else fc[0]
        return final_carry, (ys[0] if n_ys == 1 else ys)

    def set_loss_variables(self, *names):
        self._loss_names = [n.name if isinstance(n, SDVariable) else n
                            for n in names]
        self._invalidate()

    def set_training_config(self, cfg: TrainingConfig):
        self.training_config = cfg
        self._invalidate()

    # ---- evaluation (the compiled InferenceSession replacement) ----
    def _eval_graph(self, feeds: Dict[str, Any], variables: Dict[str, Any],
                    names: Sequence[str]) -> Dict[str, Any]:
        """Iterative post-order walk (explicit stack, no Python recursion —
        deep chains of ops would blow the recursion limit during tracing)."""
        cache: Dict[str, Any] = {}

        def leaf_value(node: Node):
            n = node.name
            if node.kind == "placeholder":
                if n not in feeds:
                    if n == RNG_FEED:
                        return None
                    raise KeyError(f"Placeholder '{n}' not fed")
                return feeds[n]
            if node.kind == "variable":
                return variables[n]
            return self._constants[n]          # constant

        for target in names:
            stack = [target]
            while stack:
                n = stack[-1]
                if n in cache:
                    stack.pop()
                    continue
                node = self._nodes[n]
                if node.kind != "op":
                    cache[n] = leaf_value(node)
                    stack.pop()
                    continue
                pending = [i for i in node.inputs if i not in cache]
                if pending:
                    stack.extend(pending)
                    continue
                args = [cache[i] for i in node.inputs]
                if node.op in _CONTROL_FLOW_OPS:
                    cache[n] = _eval_control_flow(node, args)
                else:
                    cache[n] = OP_TABLE[node.op](*args, **node.attrs)
                stack.pop()

        return {n: cache[n] for n in names}

    def output(self, feeds: Dict[str, Any], *names) -> Dict[str, Any]:
        """Compiled multi-output inference (reference
        `sd.output(Map, String...)`). One executable per requested-name set."""
        names = tuple(n.name if isinstance(n, SDVariable) else n
                      for n in names)
        feeds = {k: jnp.asarray(v) for k, v in feeds.items()}
        if names not in self._output_fns:
            def f(variables, feeds):
                return self._eval_graph(feeds, variables, names)
            self._output_fns[names] = jax.jit(f)
        return self._output_fns[names](self.variables_, feeds)

    def batch_output(self, feeds, *names):
        return self.output(feeds, *names)

    # ---- training (the compiled TrainingSession replacement) ----
    def _total_loss(self, variables, feeds):
        vals = self._eval_graph(feeds, variables, self._loss_names)
        loss = 0.0
        for v in vals.values():
            loss = loss + (v if jnp.ndim(v) == 0 else jnp.sum(v))
        cfg = self.training_config
        if cfg and (cfg.l1 or cfg.l2):
            for arr in variables.values():
                if cfg.l1:
                    loss = loss + cfg.l1 * jnp.sum(jnp.abs(arr))
                if cfg.l2:
                    loss = loss + 0.5 * cfg.l2 * jnp.sum(arr * arr)
        return loss

    def _build_step_body(self):
        cfg = self.training_config
        has_rng = RNG_FEED in self._nodes   # static at trace time; the step
        # cache is invalidated whenever the graph mutates
        zt = self._step_transform   # ZeRO-1 sharded weight update, or None

        def step(variables, opt_state, feeds, rng, iteration, epoch):
            if has_rng:
                rng, sub = jax.random.split(rng)
                feeds = dict(feeds)
                feeds[RNG_FEED] = sub
            master = variables
            if zt is not None:
                variables = zt.gather_all(variables)

            def loss_fn(vs):
                return self._total_loss(vs, feeds)
            loss, grads = jax.value_and_grad(loss_fn)(variables)
            if zt is None:
                upd, new_opt = cfg.updater.apply(opt_state, grads, iteration,
                                                 epoch, params=variables)
                new_vars = jax.tree_util.tree_map(lambda p, u: p - u,
                                                  variables, upd)
            else:
                # reduce-scatter grads over the data axis, run the updater
                # on the local shard, all-gather via restore()
                grads = zt.scatter(None, grads)
                p_upd = zt.update_view(None, master)
                upd, new_opt = cfg.updater.apply(opt_state, grads, iteration,
                                                 epoch, params=p_upd)
                new_vars = jax.tree_util.tree_map(lambda p, u: p - u,
                                                  p_upd, upd)
                new_vars = zt.restore(None, new_vars)
                new_opt = zt.constrain_opt(None, new_opt)
            return new_vars, new_opt, loss, rng, iteration + 1

        return step

    def _build_train_step(self):
        from deeplearning4j_tpu.compile import step_function
        return step_function(self._build_step_body(),
                             donate_argnums=self._donate_argnums(),
                             key_base=self._aot_key_parts,
                             cache=self._exec_cache(),
                             dynamic_argnums=(2,))

    def _build_scan_step(self):
        """k steps per dispatch (see utils/scan_fit.py); SameDiff's carry
        is (variables, opt_state, rng, iteration), scanning over feeds."""
        from deeplearning4j_tpu.utils.scan_fit import make_scan_step
        body = self._build_step_body()

        def tick(carry, epoch, feed):
            v, o, r, it = carry
            v, o, loss, r, it = body(v, o, feed, r, it, epoch)
            return (v, o, r, it), loss

        return make_scan_step(
            tick,
            key_base=lambda: self._aot_key_parts("scan_step"),
            cache=self._exec_cache(),
            donate=(self._schedule is None or self._schedule.donation))

    def fit(self, data=None, labels=None, *, iterator=None, epochs: int = 1,
            feeds: Optional[Dict[str, Any]] = None,
            fused_steps: Optional[int] = None) -> "SameDiff":
        """fit(features, labels) / fit(feeds={...}) for one batch, or
        fit(iterator=multi_data_set_iterator, epochs=N).  `fused_steps=k`
        fuses blocks of k consecutive same-shape batches from the
        iterator into one `fit_steps` dispatch (tails fall back); unset,
        it defaults to the installed schedule's (`apply_schedule`),
        else 1."""
        if fused_steps is None:
            fused_steps = (self._schedule.fused_steps
                           if self._schedule is not None else 1)
        if self.training_config is None:
            raise ValueError("set_training_config(...) first (reference "
                             "throws the same)")
        if not self._loss_names:
            raise ValueError("set_loss_variables(...) first")
        if self.opt_state_ is None:
            self.opt_state_ = self.training_config.updater.init_state(
                self.variables_)
        if self._train_step is None:
            self._train_step = self._build_train_step()

        if iterator is not None:
            from deeplearning4j_tpu.utils.scan_fit import blocks_of
            for _ in range(epochs):
                if hasattr(iterator, "reset"):
                    iterator.reset()
                if fused_steps > 1:
                    for block in blocks_of(iterator, fused_steps):
                        if len(block) == 1:
                            self._fit_feeds(self._map_dataset(block[0]))
                        else:
                            fl = [self._map_dataset(ds) for ds in block]
                            self.fit_steps(
                                {k: np.stack([np.asarray(f[k]) for f in fl])
                                 for k in fl[0]})
                else:
                    for ds in iterator:
                        self._fit_feeds(self._map_dataset(ds))
                self.epoch += 1
            return self
        if feeds is None:
            cfg = self.training_config
            feeds = {}
            xs = data if isinstance(data, (list, tuple)) else [data]
            ys = labels if isinstance(labels, (list, tuple)) else [labels]
            for n, v in zip(cfg.data_set_feature_mapping, xs):
                feeds[n] = v
            for n, v in zip(cfg.data_set_label_mapping, ys):
                feeds[n] = v
        self._fit_feeds(feeds)
        return self

    def _map_dataset(self, ds):
        cfg = self.training_config
        feeds = {}
        feats = ds.features if isinstance(ds.features, (list, tuple)) \
            else [ds.features]
        labs = ds.labels if isinstance(ds.labels, (list, tuple)) \
            else [ds.labels]
        for n, v in zip(cfg.data_set_feature_mapping, feats):
            feeds[n] = v
        for n, v in zip(cfg.data_set_label_mapping, labs):
            feeds[n] = v
        return feeds

    def _fit_feeds(self, feeds: Dict[str, Any]):
        from deeplearning4j_tpu.utils.counters import advance, device_counters
        feeds = {k: jnp.asarray(v) for k, v in feeds.items()}
        it_dev, ep_dev = device_counters(self)
        (self.variables_, self.opt_state_, loss, self._key,
         new_it) = self._train_step(
            self.variables_, self.opt_state_, feeds, self._key,
            it_dev, ep_dev)
        self._score = loss
        advance(self, new_it)

    def fit_steps(self, feeds: Dict[str, Any]):
        """Run k training steps in one device dispatch: every feed array
        carries a leading `[k, batch, ...]` steps axis.  Same math as k
        sequential `fit(feeds=...)` calls (variables/updater-state/rng/
        iteration flow step-to-step as scan carries); returns the
        length-k per-step loss array."""
        from deeplearning4j_tpu.utils.counters import advance, device_counters
        if self.training_config is None:
            raise ValueError("set_training_config(...) first (reference "
                             "throws the same)")
        if not self._loss_names:
            raise ValueError("set_loss_variables(...) first")
        if self.opt_state_ is None:
            self.opt_state_ = self.training_config.updater.init_state(
                self.variables_)
        from deeplearning4j_tpu.utils.scan_fit import check_steps_axes
        feeds = {k: jnp.asarray(v) for k, v in feeds.items()}
        k = check_steps_axes(feeds.items())
        if self._scan_step is None:
            self._scan_step = self._build_scan_step()
        it_dev, ep_dev = device_counters(self)
        ((self.variables_, self.opt_state_, self._key, new_it),
         losses, last_loss) = self._scan_step(
            (self.variables_, self.opt_state_, self._key, it_dev),
            ep_dev, feeds)
        self._score = last_loss
        advance(self, new_it, steps=int(k))
        return losses

    def score(self) -> float:
        s = getattr(self, "_score", None)
        return float(s) if s is not None else float("nan")

    def evaluate(self, iterator, output_name, evaluation=None,
                 label_index: int = 0):
        """Classification eval over a DataSetIterator (reference
        `sd.evaluate(iterator, outputVariable, new Evaluation())`): feeds
        come from the TrainingConfig mappings, predictions from the named
        output."""
        from deeplearning4j_tpu.train.evaluation import Evaluation
        if self.training_config is None:
            raise ValueError("set_training_config(...) first — evaluate "
                             "uses its feature/label mappings")
        output_name = output_name.name if isinstance(output_name,
                                                     SDVariable) \
            else output_name
        ev = evaluation or Evaluation()
        if hasattr(iterator, "reset"):
            iterator.reset()
        for ds in iterator:
            feeds = self._map_dataset(ds)
            labels = ds.labels[label_index] \
                if isinstance(ds.labels, (list, tuple)) else ds.labels
            # drop label placeholders the forward pass doesn't need
            preds = self.output(
                {k: v for k, v in feeds.items()
                 if k not in self.training_config.data_set_label_mapping},
                output_name)[output_name]
            lmask = getattr(ds, "labels_mask", None)
            if lmask is None:
                lmasks = getattr(ds, "labels_masks", None)
                if lmasks is not None:
                    lmask = lmasks[label_index]
            ev.eval(np.asarray(labels), np.asarray(preds),
                    mask=None if lmask is None else np.asarray(lmask))
        return ev

    def calculate_gradients(self, feeds: Dict[str, Any],
                            *wrt) -> Dict[str, np.ndarray]:
        """Analytic gradients of the summed loss wrt named variables
        (reference `sd.calculateGradients`) — the OpValidation hook."""
        wrt = [w.name if isinstance(w, SDVariable) else w for w in wrt] \
            or list(self.variables_)
        feeds = {k: jnp.asarray(v) for k, v in feeds.items()}

        def loss_fn(vs):
            return self._total_loss(vs, feeds)
        grads = jax.grad(loss_fn)(self.variables_)
        return {w: np.asarray(grads[w]) for w in wrt}

    # ---- serialization (FlatBuffers replacement) ----
    def save(self, path: str, save_updater_state: bool = True):
        graph = {
            "format": "deeplearning4j_tpu.samediff.v1",
            "nodes": [dataclasses.asdict(n) for n in self._nodes.values()],
            "loss_variables": self._loss_names,
            "iteration": self.iteration, "epoch": self.epoch,
            "training_config": (self.training_config.to_json()
                                if self.training_config else None),
        }
        with zipfile.ZipFile(path, "w", zipfile.ZIP_DEFLATED) as z:
            z.writestr("graph.json", json.dumps(graph, default=_json_default))
            np_vars = {k: np.asarray(v) for k, v in self.variables_.items()}
            z.writestr("variables.npz", _npz_bytes(np_vars))
            np_consts = {k: np.asarray(v) for k, v in self._constants.items()}
            z.writestr("constants.npz", _npz_bytes(np_consts))
            if save_updater_state and self.opt_state_ is not None:
                leaves = jax.tree_util.tree_leaves(self.opt_state_)
                z.writestr("updater.npz", _npz_bytes(
                    {str(i): np.asarray(l) for i, l in enumerate(leaves)}))

    @staticmethod
    def load(path: str, load_updater_state: bool = True) -> "SameDiff":
        sd = SameDiff()
        with zipfile.ZipFile(path, "r") as z:
            graph = json.loads(z.read("graph.json").decode())
            variables = _npz_load(z.read("variables.npz"))
            constants = _npz_load(z.read("constants.npz"))
            for nd in graph["nodes"]:
                node = Node(name=nd["name"], kind=nd["kind"], op=nd["op"],
                            inputs=tuple(nd["inputs"]),
                            attrs=_detuple_attrs(nd["attrs"]),
                            shape=None if nd["shape"] is None
                            else tuple(nd["shape"]),
                            dtype=nd["dtype"])
                sd._nodes[node.name] = node
            sd.variables_ = {k: jnp.asarray(v) for k, v in variables.items()}
            sd._constants = {k: jnp.asarray(v) for k, v in constants.items()}
            sd._loss_names = graph["loss_variables"]
            sd.iteration = graph["iteration"]
            sd.epoch = graph["epoch"]
            if graph["training_config"]:
                sd.training_config = TrainingConfig.from_json(
                    graph["training_config"])
            if load_updater_state and "updater.npz" in z.namelist() \
                    and sd.training_config is not None:
                tmpl = sd.training_config.updater.init_state(sd.variables_)
                leaves, treedef = jax.tree_util.tree_flatten(tmpl)
                saved = _npz_load(z.read("updater.npz"))
                new_leaves = [jnp.asarray(saved[str(i)])
                              for i in range(len(leaves))]
                sd.opt_state_ = jax.tree_util.tree_unflatten(treedef,
                                                             new_leaves)
        return sd

    def summary(self) -> str:
        lines = [f"{'name':30s} {'kind':12s} {'op':24s} inputs"]
        for n in self._nodes.values():
            lines.append(f"{n.name:30s} {n.kind:12s} {n.op or '-':24s} "
                         f"{list(n.inputs)}")
        return "\n".join(lines)


def _json_default(o):
    if isinstance(o, (np.integer,)):
        return int(o)
    if isinstance(o, (np.floating,)):
        return float(o)
    if isinstance(o, (np.ndarray, jnp.ndarray)):
        return np.asarray(o).tolist()
    if isinstance(o, tuple):
        return list(o)
    raise TypeError(f"not json-serializable: {type(o)}")


def _detuple_attrs(attrs: Dict[str, Any]) -> Dict[str, Any]:
    """JSON turns tuples into lists; ops that need tuples re-tuple them."""
    out = {}
    for k, v in attrs.items():
        out[k] = tuple(v) if isinstance(v, list) and k in (
            "stride", "kernel", "dilation", "perm") else v
    return out


def _npz_bytes(arrays: Dict[str, np.ndarray]) -> bytes:
    import io
    buf = io.BytesIO()
    np.savez(buf, **arrays)
    return buf.getvalue()


def _npz_load(data: bytes) -> Dict[str, np.ndarray]:
    import io
    with np.load(io.BytesIO(data), allow_pickle=False) as z:
        return {k: z[k] for k in z.files}
