"""ComputationGraph: arbitrary-DAG model with a compiled train step.

Reference: `deeplearning4j-nn/.../nn/graph/ComputationGraph.java` (~4.5k LoC),
`nn/conf/ComputationGraphConfiguration.java` (GraphBuilder DSL) and the vertex
zoo `nn/graph/vertex/impl/**` (MergeVertex, ElementWiseVertex, SubsetVertex,
L2NormalizeVertex, StackVertex, UnstackVertex, ScaleVertex, ShiftVertex,
ReshapeVertex, PreprocessorVertex).

TPU design: the reference walks `GraphVertex[]` in topological order with
per-vertex workspace choreography (`outputOfLayersDetached`); here the whole
DAG forward + losses + `jax.grad` + updaters trace into ONE function that
`jax.jit` compiles, so XLA owns scheduling and activation memory.  Multi-input
/ multi-output and multiple loss heads (summed, as the reference does in
`computeGradientAndScore`) are plain pytree plumbing.
"""
from __future__ import annotations

import dataclasses
import json
from typing import Any, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from deeplearning4j_tpu.nn.core import InputType, Layer, PyTree
from deeplearning4j_tpu.nn.multilayer import _masked_leaves
from deeplearning4j_tpu.nn.trainer import LayerwiseTrainer
from deeplearning4j_tpu.train.updaters import IUpdater, Sgd

Params = Dict[str, PyTree]


# ---------------------------------------------------------------------------
# Graph vertices (reference nn/graph/vertex/impl/**)
# ---------------------------------------------------------------------------

@dataclasses.dataclass(kw_only=True)
class GraphVertex:
    """Non-layer graph node combining/reshaping activations.  Like `Layer`,
    a vertex is a config dataclass; `initialize` infers the output InputType,
    `apply` is the pure forward over its input list."""

    name: Optional[str] = None

    def initialize(self, rng: jax.Array, input_types: List[InputType],
                   dtype=jnp.float32) -> Tuple[PyTree, PyTree, InputType]:
        return {}, {}, self.output_type(input_types)

    def output_type(self, input_types: List[InputType]) -> InputType:
        raise NotImplementedError

    def apply(self, params: PyTree, state: PyTree, inputs: List[jnp.ndarray],
              *, train: bool = False, rng: Optional[jax.Array] = None
              ) -> Tuple[jnp.ndarray, PyTree]:
        raise NotImplementedError

    def to_json(self) -> dict:
        d = {f.name: getattr(self, f.name) for f in dataclasses.fields(self)}
        d["@vertex"] = type(self).__name__
        return d

    @staticmethod
    def from_json(d: dict) -> "GraphVertex":
        d = dict(d)
        cls = VERTEX_REGISTRY[d.pop("@vertex")]
        field_names = {f.name for f in dataclasses.fields(cls)}
        return cls(**{k: v for k, v in d.items() if k in field_names})


@dataclasses.dataclass(kw_only=True)
class MergeVertex(GraphVertex):
    """Concatenate along the feature/channel axis (reference `MergeVertex`):
    last axis in NHWC/[B,F]/[B,T,F] — the TPU-native layout's channel dim."""

    def output_type(self, input_types):
        t0 = input_types[0]
        feat = sum(t.shape[-1] for t in input_types)
        return InputType(t0.kind, t0.shape[:-1] + (feat,))

    def apply(self, params, state, inputs, *, train=False, rng=None):
        return jnp.concatenate(inputs, axis=-1), state


@dataclasses.dataclass(kw_only=True)
class ElementWiseVertex(GraphVertex):
    """Pointwise combine (reference `ElementWiseVertex`):
    Add | Subtract | Product | Average | Max.  The residual-connection
    workhorse (ResNet shortcut = Add)."""

    op: str = "Add"

    def output_type(self, input_types):
        return input_types[0]

    def apply(self, params, state, inputs, *, train=False, rng=None):
        op = self.op.lower()
        if op == "subtract":
            if len(inputs) != 2:
                raise ValueError("ElementWiseVertex Subtract requires exactly "
                                 f"2 inputs, got {len(inputs)}")
            return inputs[0] - inputs[1], state
        acc = inputs[0]
        for x in inputs[1:]:
            if op == "add":
                acc = acc + x
            elif op == "product":
                acc = acc * x
            elif op == "max":
                acc = jnp.maximum(acc, x)
            elif op == "average":
                acc = acc + x
            else:
                raise ValueError(f"Unknown ElementWiseVertex op {self.op}")
        if op == "average":
            acc = acc / len(inputs)
        return acc, state


@dataclasses.dataclass(kw_only=True)
class SubsetVertex(GraphVertex):
    """Feature-axis slice [from, to] inclusive (reference `SubsetVertex`)."""

    range_from: int = 0
    range_to: int = 0

    def output_type(self, input_types):
        t = input_types[0]
        return InputType(t.kind, t.shape[:-1] + (self.range_to - self.range_from + 1,))

    def apply(self, params, state, inputs, *, train=False, rng=None):
        return inputs[0][..., self.range_from:self.range_to + 1], state


@dataclasses.dataclass(kw_only=True)
class L2NormalizeVertex(GraphVertex):
    """x / ||x||_2 over non-batch dims (reference `L2NormalizeVertex`)."""

    eps: float = 1e-8

    def output_type(self, input_types):
        return input_types[0]

    def apply(self, params, state, inputs, *, train=False, rng=None):
        x = inputs[0]
        axes = tuple(range(1, x.ndim))
        norm = jnp.sqrt(jnp.sum(x * x, axis=axes, keepdims=True))
        return x / jnp.maximum(norm, self.eps), state


@dataclasses.dataclass(kw_only=True)
class ScaleVertex(GraphVertex):
    """x * scale (reference `ScaleVertex`)."""

    scale: float = 1.0

    def output_type(self, input_types):
        return input_types[0]

    def apply(self, params, state, inputs, *, train=False, rng=None):
        return inputs[0] * self.scale, state


@dataclasses.dataclass(kw_only=True)
class ShiftVertex(GraphVertex):
    """x + shift (reference `ShiftVertex`)."""

    shift: float = 0.0

    def output_type(self, input_types):
        return input_types[0]

    def apply(self, params, state, inputs, *, train=False, rng=None):
        return inputs[0] + self.shift, state


@dataclasses.dataclass(kw_only=True)
class StackVertex(GraphVertex):
    """Stack along batch axis (reference `StackVertex`)."""

    def output_type(self, input_types):
        return input_types[0]

    def apply(self, params, state, inputs, *, train=False, rng=None):
        return jnp.concatenate(inputs, axis=0), state


@dataclasses.dataclass(kw_only=True)
class UnstackVertex(GraphVertex):
    """Inverse of StackVertex: take slice `from_index` of `stack_size` equal
    batch chunks (reference `UnstackVertex`)."""

    from_index: int = 0
    stack_size: int = 1

    def output_type(self, input_types):
        return input_types[0]

    def apply(self, params, state, inputs, *, train=False, rng=None):
        x = inputs[0]
        n = x.shape[0] // self.stack_size
        return x[self.from_index * n:(self.from_index + 1) * n], state


@dataclasses.dataclass(kw_only=True)
class ReshapeVertex(GraphVertex):
    """Reshape non-batch dims (reference `ReshapeVertex`); `shape` excludes
    the batch dimension."""

    shape: Sequence[int] = ()

    def output_type(self, input_types):
        return InputType("feedforward" if len(self.shape) == 1 else
                         input_types[0].kind, tuple(self.shape))

    def apply(self, params, state, inputs, *, train=False, rng=None):
        x = inputs[0]
        return x.reshape((x.shape[0],) + tuple(self.shape)), state


@dataclasses.dataclass(kw_only=True)
class LayerVertex(GraphVertex):
    """Wraps a `Layer` config as a single-input graph vertex (reference
    `LayerVertex`)."""

    layer: Layer = None

    def initialize(self, rng, input_types, dtype=jnp.float32):
        return self.layer.initialize(rng, input_types[0], dtype)

    def apply(self, params, state, inputs, *, train=False, rng=None):
        return self.layer.apply(params, state, inputs[0], train=train, rng=rng)

    def to_json(self) -> dict:
        return {"@vertex": "LayerVertex", "name": self.name,
                "layer": self.layer.to_json()}


VERTEX_REGISTRY = {c.__name__: c for c in [
    MergeVertex, ElementWiseVertex, SubsetVertex, L2NormalizeVertex,
    ScaleVertex, ShiftVertex, StackVertex, UnstackVertex, ReshapeVertex,
    LayerVertex]}


def register_vertex(cls):
    VERTEX_REGISTRY[cls.__name__] = cls
    return cls


# ---------------------------------------------------------------------------
# Configuration + builder
# ---------------------------------------------------------------------------

@dataclasses.dataclass
class ComputationGraphConfiguration:
    """DAG config (reference `ComputationGraphConfiguration`): named inputs,
    vertices with their input edges, named outputs; JSON round-trip is the
    checkpoint contract."""

    network_inputs: List[str]
    input_types: Dict[str, InputType]
    vertices: Dict[str, GraphVertex]            # insertion order preserved
    vertex_inputs: Dict[str, List[str]]
    network_outputs: List[str]
    seed: int = 0
    updater: IUpdater = dataclasses.field(default_factory=lambda: Sgd(1e-2))
    weight_init: str = "XAVIER"
    activation: Any = "identity"
    l1: float = 0.0
    l2: float = 0.0
    weight_decay: float = 0.0
    dtype: str = "float32"
    compute_dtype: Optional[str] = None   # bf16 compute path (see multilayer)
    remat: bool = False                   # per-vertex jax.checkpoint
    gradient_normalization: Optional[str] = None
    gradient_normalization_threshold: float = 1.0

    def topological_order(self) -> List[str]:
        """Kahn topological sort over vertex names (the reference precomputes
        `topologicalOrder` in ComputationGraphConfiguration)."""
        indeg = {n: 0 for n in self.vertices}
        children: Dict[str, List[str]] = {n: [] for n in self.vertices}
        for name, ins in self.vertex_inputs.items():
            for src in ins:
                if src in self.vertices:
                    indeg[name] += 1
                    children[src].append(name)
                elif src not in self.network_inputs:
                    raise ValueError(f"Vertex '{name}' input '{src}' unknown")
        order = [n for n in self.vertices if indeg[n] == 0]
        i = 0
        while i < len(order):
            for ch in children[order[i]]:
                indeg[ch] -= 1
                if indeg[ch] == 0:
                    order.append(ch)
            i += 1
        if len(order) != len(self.vertices):
            cyc = set(self.vertices) - set(order)
            raise ValueError(f"Graph has a cycle involving {sorted(cyc)}")
        return order

    def to_json(self) -> str:
        return json.dumps({
            "format": "deeplearning4j_tpu.ComputationGraphConfiguration.v1",
            "network_inputs": self.network_inputs,
            "input_types": {k: v.to_json() for k, v in self.input_types.items()},
            "vertices": {k: v.to_json() for k, v in self.vertices.items()},
            "vertex_inputs": self.vertex_inputs,
            "network_outputs": self.network_outputs,
            "seed": self.seed,
            "updater": self.updater.to_json(),
            "weight_init": self.weight_init,
            "activation": self.activation if isinstance(self.activation, str)
                          else getattr(self.activation, "__name__", "identity"),
            "l1": self.l1, "l2": self.l2, "weight_decay": self.weight_decay,
            "dtype": self.dtype,
            "compute_dtype": self.compute_dtype,
            "remat": self.remat,
            "gradient_normalization": self.gradient_normalization,
            "gradient_normalization_threshold": self.gradient_normalization_threshold,
        }, indent=2)

    @staticmethod
    def from_json(s: str) -> "ComputationGraphConfiguration":
        d = json.loads(s)

        def load_vertex(vd):
            if vd["@vertex"] == "LayerVertex":
                return LayerVertex(name=vd.get("name"),
                                   layer=Layer.from_json(vd["layer"]))
            return GraphVertex.from_json(vd)

        return ComputationGraphConfiguration(
            network_inputs=d["network_inputs"],
            input_types={k: InputType.from_json(v)
                         for k, v in d["input_types"].items()},
            vertices={k: load_vertex(v) for k, v in d["vertices"].items()},
            vertex_inputs={k: list(v) for k, v in d["vertex_inputs"].items()},
            network_outputs=d["network_outputs"],
            seed=d["seed"], updater=IUpdater.from_json(d["updater"]),
            weight_init=d["weight_init"], activation=d["activation"],
            l1=d["l1"], l2=d["l2"], weight_decay=d.get("weight_decay", 0.0),
            dtype=d.get("dtype", "float32"),
            compute_dtype=d.get("compute_dtype"),
            remat=d.get("remat", False),
            gradient_normalization=d.get("gradient_normalization"),
            gradient_normalization_threshold=d.get(
                "gradient_normalization_threshold", 1.0),
        )


class GraphBuilder:
    """Fluent DAG builder (reference
    `NeuralNetConfiguration.Builder.graphBuilder()` -> `GraphBuilder`)."""

    def __init__(self):
        self._inputs: List[str] = []
        self._input_types: Dict[str, InputType] = {}
        self._vertices: Dict[str, GraphVertex] = {}
        self._vertex_inputs: Dict[str, List[str]] = {}
        self._outputs: List[str] = []
        self._seed = 0
        self._updater: IUpdater = Sgd(1e-2)
        self._weight_init = "XAVIER"
        self._activation: Any = "identity"
        self._l1 = 0.0
        self._l2 = 0.0
        self._weight_decay = 0.0
        self._dtype = "float32"
        self._compute_dtype = None
        self._remat = False
        self._grad_norm = None
        self._grad_norm_threshold = 1.0

    # global defaults (mirror NeuralNetConfiguration.Builder)
    def seed(self, s): self._seed = int(s); return self
    def updater(self, u): self._updater = u; return self
    def weight_init(self, w): self._weight_init = w; return self
    def activation(self, a): self._activation = a; return self
    def l1(self, v): self._l1 = float(v); return self
    def l2(self, v): self._l2 = float(v); return self
    def weight_decay(self, v): self._weight_decay = float(v); return self
    def dtype(self, dt): self._dtype = dt; return self
    def compute_dtype(self, dt): self._compute_dtype = dt; return self

    def gradient_checkpointing(self, on: bool = True):
        """Rematerialize each vertex in the backward pass (jax.checkpoint);
        HBM for FLOPs on deep graphs."""
        self._remat = bool(on); return self

    def gradient_normalization(self, mode, threshold=1.0):
        self._grad_norm = mode; self._grad_norm_threshold = threshold; return self

    # graph topology
    def add_inputs(self, *names: str):
        self._inputs.extend(names); return self

    def set_input_types(self, *types: InputType):
        if len(types) != len(self._inputs):
            raise ValueError(
                f"set_input_types got {len(types)} types for "
                f"{len(self._inputs)} declared inputs (call add_inputs first)")
        for name, t in zip(self._inputs, types):
            self._input_types[name] = t
        return self

    def add_layer(self, name: str, layer: Layer, *inputs: str):
        layer.name = layer.name or name
        return self.add_vertex(name, LayerVertex(layer=layer), *inputs)

    def add_vertex(self, name: str, vertex: GraphVertex, *inputs: str):
        if name in self._vertices or name in self._inputs:
            raise ValueError(f"Duplicate vertex name '{name}'")
        vertex.name = name
        self._vertices[name] = vertex
        self._vertex_inputs[name] = list(inputs)
        return self

    def set_outputs(self, *names: str):
        self._outputs = list(names); return self

    def build(self) -> ComputationGraphConfiguration:
        if not self._outputs:
            raise ValueError("set_outputs(...) is required")
        for name in self._inputs:
            if name not in self._input_types:
                raise ValueError(f"Input '{name}' has no InputType "
                                 "(set_input_types required for shape inference)")
        return ComputationGraphConfiguration(
            network_inputs=self._inputs, input_types=dict(self._input_types),
            vertices=self._vertices, vertex_inputs=self._vertex_inputs,
            network_outputs=self._outputs, seed=self._seed,
            updater=self._updater, weight_init=self._weight_init,
            activation=self._activation, l1=self._l1, l2=self._l2,
            weight_decay=self._weight_decay, dtype=self._dtype,
            compute_dtype=self._compute_dtype,
            remat=self._remat,
            gradient_normalization=self._grad_norm,
            gradient_normalization_threshold=self._grad_norm_threshold)


# ---------------------------------------------------------------------------
# Network
# ---------------------------------------------------------------------------

class ComputationGraph(LayerwiseTrainer):
    """DAG network (reference `ComputationGraph`).  API parity:
    `init`, `fit(MultiDataSet | (features, labels))`, `output(*features)`,
    `score`, `evaluate`, `gradient_for`, `save`/`load`.  The compiled train
    step, its cache and its dispatch are `LayerwiseTrainer`'s
    (nn/trainer.py)."""

    _AOT_PREFIX = "cg"
    _BATCH_ARITY = 3          # inputs, labels, lmasks
    # a vertex with no parameters passes through the update loop untouched
    _SKIP_EMPTY_ENTRIES = True

    def __init__(self, conf: ComputationGraphConfiguration):
        super().__init__()
        self.conf = conf
        self.params_: Optional[Params] = None
        self.state_: Optional[Params] = None
        self.opt_state_: Optional[PyTree] = None
        self.iteration = 0
        self.epoch = 0
        self.listeners: List[Any] = []
        self._rng = jax.random.PRNGKey(conf.seed)
        self._topo = conf.topological_order()
        self._output_fn = None
        self._vertex_types: Dict[str, InputType] = {}
        self._device_norm: Dict[str, Any] = {}  # input name -> DeviceNormalizer

    def _layer_of(self, name: str) -> Optional[Layer]:
        v = self.conf.vertices[name]
        return v.layer if isinstance(v, LayerVertex) else None

    # ---- init ----
    def init(self) -> "ComputationGraph":
        dtype = jnp.dtype(self.conf.dtype)
        types: Dict[str, InputType] = dict(self.conf.input_types)
        params: Params = {}
        state: Params = {}
        key = jax.random.PRNGKey(self.conf.seed)
        for name in self._topo:
            vertex = self.conf.vertices[name]
            layer = self._layer_of(name)
            if layer is not None:
                if layer.weight_init is None:
                    layer.weight_init = self.conf.weight_init
                if layer.activation is None and not hasattr(layer, "loss"):
                    layer.activation = self.conf.activation
            in_types = [types[s] for s in self.conf.vertex_inputs[name]]
            key, sub = jax.random.split(key)
            p, s, out_t = vertex.initialize(sub, in_types, dtype)
            params[name] = p
            state[name] = s
            types[name] = out_t
        self._vertex_types = types
        self.params_ = params
        self.state_ = state
        self.opt_state_ = self._init_opt_state(params)
        return self

    def _updater_for(self, name: str) -> IUpdater:
        layer = self._layer_of(name)
        if layer is not None and layer.updater is not None:
            return layer.updater
        return self.conf.updater

    def _init_opt_state(self, params: Params) -> PyTree:
        return {name: self._updater_for(name).init_state(params[name])
                for name in self._topo}

    # ---- forward ----
    def _forward(self, params: Params, state: Params, inputs: Dict[str, Any],
                 *, train: bool, rng: Optional[jax.Array],
                 want_head_inputs: bool = False):
        """Run the DAG; returns activations for every vertex (plus, when
        `want_head_inputs`, the raw input of each loss head for
        `compute_loss` — heads still produce their normal activation so
        downstream consumers see real outputs; XLA dead-code-eliminates an
        unused head forward)."""
        cd = self.conf.compute_dtype
        if cd is not None:
            dt = jnp.dtype(cd)
            cast = (lambda a: a.astype(dt)
                    if jnp.issubdtype(a.dtype, jnp.floating) else a)
            with jax.named_scope("param_cast"):
                params = jax.tree_util.tree_map(cast, params)
                inputs = {k: cast(jnp.asarray(v)) for k, v in inputs.items()}
        acts: Dict[str, jnp.ndarray] = dict(inputs)
        head_inputs: Dict[str, jnp.ndarray] = {}
        new_state = dict(state)
        for i, name in enumerate(self._topo):
            vertex = self.conf.vertices[name]
            layer = self._layer_of(name)
            vrng = None
            if rng is not None and layer is not None and layer.STOCHASTIC:
                vrng = jax.random.fold_in(rng, i)
            xs = [acts[s] for s in self.conf.vertex_inputs[name]]
            if (want_head_inputs and name in self.conf.network_outputs
                    and layer is not None and hasattr(layer, "compute_loss")):
                head_inputs[name] = xs[0]
            # the device ops' `op_name` says which vertex asked for them
            # (docs/observability.md): `ConvolutionLayer/res2a_branch2a`
            kind = type(vertex if layer is None else layer).__name__
            with jax.named_scope(f"{kind}/{name}"):
                if self.conf.remat and train:
                    # train only (see MultiLayerNetwork._forward)
                    def _apply(p_, s_, xs_, r_, _v=vertex, _train=train):
                        return _v.apply(p_, s_, xs_, train=_train, rng=r_)
                    acts[name], new_state[name] = jax.checkpoint(_apply)(
                        params[name], state[name], xs, vrng)
                else:
                    acts[name], new_state[name] = vertex.apply(
                        params[name], state[name], xs, train=train, rng=vrng)
        if want_head_inputs:
            return acts, new_state, head_inputs
        return acts, new_state

    def _loss(self, params: Params, state: Params, inputs: Dict[str, Any],
              labels: List[Any], rng, labels_masks: Optional[List[Any]] = None,
              train: bool = True) -> Tuple[jnp.ndarray, Params]:
        """Summed loss over all output heads + regularization (reference
        `ComputationGraph.computeGradientAndScore` sums output-layer scores)."""
        acts, new_state, head_inputs = self._forward(
            params, state, inputs, train=train, rng=rng, want_head_inputs=True)
        loss = 0.0
        with jax.named_scope("loss"):
            for j, name in enumerate(self.conf.network_outputs):
                layer = self._layer_of(name)
                if layer is None or not hasattr(layer, "compute_loss"):
                    raise ValueError(
                        f"Output vertex '{name}' is not a loss head")
                lrng = (None if rng is None
                        else jax.random.fold_in(rng, 10_000 + j))
                lmask = labels_masks[j] if labels_masks else None
                loss = loss + layer.compute_loss(
                    params[name], state[name], head_inputs[name], labels[j],
                    train=train, rng=lrng, mask=lmask)
            loss = loss + self._reg_penalty(params)
        return loss, new_state

    def _reg_penalty(self, params: Params):
        penalty = 0.0
        for name in self._topo:
            layer = self._layer_of(name)
            if layer is None:
                continue
            l1 = layer.l1 if layer.l1 is not None else self.conf.l1
            l2 = layer.l2 if layer.l2 is not None else self.conf.l2
            if l1 == 0.0 and l2 == 0.0:
                continue
            rmask = layer.regularizable_mask(params[name])
            for w in _masked_leaves(params[name], rmask):
                if l1:
                    penalty = penalty + l1 * jnp.sum(jnp.abs(w))
                if l2:
                    penalty = penalty + 0.5 * l2 * jnp.sum(w * w)
        return penalty

    # ---- what the trainer asks of the model (nn/trainer.py) ----
    def _update_entries(self):
        return [(name, self._layer_of(name), self._updater_for(name))
                for name in self._topo]

    def _batch_loss(self, params, state, batch, rng):
        inputs, labels, lmasks = batch
        return self._loss(params, state, inputs, labels, rng, lmasks)

    def _normalize_batch(self, batch):
        inputs, labels, lmasks = batch
        return self._apply_device_norm(inputs), labels, lmasks

    @staticmethod
    def _batch_rows(batch, axis: int) -> int:
        return int(next(iter(batch[0].values())).shape[axis])

    def fit_steps(self, features, labels, labels_masks=None):
        """Run k training steps in one device dispatch; every array in
        `features`/`labels`/`labels_masks` carries a leading `[k, batch]`
        steps axis.  Same math as k sequential `fit` calls (scan carries
        params/updater/rng/iteration); listeners fire once per block."""
        from deeplearning4j_tpu.utils.scan_fit import check_steps_axes
        inputs = self._as_input_dict(features)
        labels = self._as_list(labels)
        lmasks = self._as_list(labels_masks)
        k = check_steps_axes(
            [(f"input '{n}'", a) for n, a in inputs.items()]
            + [(f"label {i}", l) for i, l in enumerate(labels)]
            + [(f"labels_mask {i}", m) for i, m in enumerate(lmasks or [])])
        return self._fit_block((inputs, labels, lmasks), int(k))

    # ---- public API ----
    def _as_input_dict(self, features) -> Dict[str, jnp.ndarray]:
        if isinstance(features, dict):
            return {k: jnp.asarray(v) for k, v in features.items()}
        if not isinstance(features, (list, tuple)):
            features = [features]
        return {n: jnp.asarray(f)
                for n, f in zip(self.conf.network_inputs, features)}

    @staticmethod
    def _as_list(labels) -> List[jnp.ndarray]:
        if labels is None:
            return None
        if not isinstance(labels, (list, tuple)):
            labels = [labels]
        return [jnp.asarray(l) for l in labels]

    def fit(self, data, labels=None, *, epochs: int = 1,
            fused_steps: Optional[int] = None):
        """fit(features, labels) for one batch (single- or multi-output), or
        fit(MultiDataSetIterator | DataSetIterator, epochs=N).

        `fused_steps=k` fuses blocks of k consecutive same-shape batches
        into one compiled dispatch (`fit_steps`); tails and shape changes
        fall back to per-step dispatch (identical math either way).  Unset,
        it defaults to the installed schedule's (`apply_schedule`), else 1."""
        if labels is None:
            return self._fit_epochs(data, epochs, fused_steps)
        if fused_steps not in (None, 1):
            raise ValueError(
                "fused_steps applies to the iterator form only; for a "
                "pre-stacked [k, batch, ...] block call fit_steps")
        self._fit_batch(self._as_input_dict(data), self._as_list(labels))
        return self

    def _fit_dataset(self, ds):
        self._fit_batch(self._as_input_dict(ds.features),
                        self._as_list(ds.labels),
                        self._as_list(getattr(ds, "labels_mask", None)))

    def _fit_epoch_fused(self, iterator, k: int):
        # blocks stack ON DEVICE (jnp.stack over staged per-batch arrays):
        # no per-block host np.stack copy, and prefetched batches fuse
        # without touching the host again (data.pipeline).
        from deeplearning4j_tpu.data.pipeline import _stack_staged
        from deeplearning4j_tpu.utils.scan_fit import blocks_of
        for block in blocks_of(iterator, k):
            if len(block) == 1:
                self._fit_dataset(block[0])
                continue
            feats = [self._as_input_dict(ds.features) for ds in block]
            labs = [self._as_list(ds.labels) for ds in block]
            lms = [self._as_list(getattr(ds, "labels_mask", None))
                   for ds in block]
            if any(m is None for m in lms) and not all(m is None for m in lms):
                for ds in block:            # mixed-mask block: not fusable
                    self._fit_dataset(ds)
                continue
            stacked_feats = {n: _stack_staged([f[n] for f in feats])
                             for n in feats[0]}
            stacked_labs = [_stack_staged([l[i] for l in labs])
                            for i in range(len(labs[0]))]
            stacked_lms = (None if lms[0] is None else
                           [_stack_staged([m[i] for m in lms])
                            for i in range(len(lms[0]))])
            self.fit_steps(stacked_feats, stacked_labs, stacked_lms)

    def _apply_device_norm(self, inputs: Dict[str, Any]) -> Dict[str, Any]:
        if not self._device_norm:
            return inputs
        return {n: (self._device_norm[n].apply_features(a)
                    if n in self._device_norm else a)
                for n, a in inputs.items()}

    def set_normalizer(self, normalizers) -> "ComputationGraph":
        """Fold fitted normalizers into the compiled step/output as an
        on-device prologue.  `normalizers` is `{input_name: normalizer}`
        (a bare normalizer is applied to every network input), or None to
        clear.  Labels pass through untouched (the MultiNormalizer
        features-only contract)."""
        from deeplearning4j_tpu.data.pipeline import DeviceNormalizer
        if normalizers is None:
            self._device_norm = {}
        else:
            if not isinstance(normalizers, dict):
                normalizers = {n: normalizers
                               for n in self.conf.network_inputs}
            unknown = set(normalizers) - set(self.conf.network_inputs)
            if unknown:
                raise ValueError(f"unknown network inputs: {sorted(unknown)}")
            self._device_norm = {n: DeviceNormalizer.from_host(nz)
                                 for n, nz in normalizers.items()}
        self._invalidate_steps()
        self._output_fn = None
        return self

    def score_for(self, features, labels) -> float:
        loss, _ = self._loss(self.params_, self.state_,
                             self._apply_device_norm(
                                 self._as_input_dict(features)),
                             self._as_list(labels), None, train=False)
        return float(loss)

    def output(self, *features, train: bool = False) -> List[jnp.ndarray]:
        """Inference outputs in `network_outputs` order (reference
        `output(INDArray...)`), jitted."""
        if len(features) == 1 and isinstance(features[0], (list, tuple, dict)):
            features = features[0]
        else:
            features = list(features)
        inputs = self._as_input_dict(features)
        if self._output_fn is None:
            def fwd(p, s, ins, train):
                # train=True runs stochastic layers deterministically off
                # (no rng at inference — matches reference output(train) which
                # only toggles BN/eval-mode semantics, not dropout sampling)
                ins = self._apply_device_norm(ins)
                acts, _ = self._forward(p, s, ins, train=train, rng=None)
                return [acts[n] for n in self.conf.network_outputs]
            self._output_fn = jax.jit(fwd, static_argnums=(3,))
        return self._output_fn(self.params_, self.state_, inputs, train)

    def feed_forward(self, *features, train: bool = False) -> Dict[str, jnp.ndarray]:
        """All vertex activations by name (reference `feedForward()`)."""
        if len(features) == 1 and isinstance(features[0], (list, tuple, dict)):
            features = features[0]
        else:
            features = list(features)
        acts, _ = self._forward(self.params_, self.state_,
                                self._as_input_dict(features),
                                train=train, rng=None)
        return acts

    def evaluate(self, iterator, evaluation=None):
        """Single-output classification eval (the reference likewise rejects
        multi-output graphs in `evaluate()`); for multi-head graphs run
        `output()` and feed an Evaluation per head."""
        if len(self.conf.network_outputs) != 1:
            raise ValueError(
                "evaluate() requires a single-output graph; this one has "
                f"{self.conf.network_outputs} — use output() + Evaluation "
                "per head")
        from deeplearning4j_tpu.train.evaluation import Evaluation
        ev = evaluation or Evaluation()
        if hasattr(iterator, "reset"):
            iterator.reset()
        for ds in iterator:
            out = self.output(ds.features)
            labels = ds.labels if isinstance(ds.labels, (list, tuple)) else [ds.labels]
            ev.eval(np.asarray(labels[0]), np.asarray(out[0]))
        return ev

    # ---- params / gradients ----
    def num_params(self) -> int:
        return sum(int(np.prod(l.shape))
                   for l in jax.tree_util.tree_leaves(self.params_))

    def params(self) -> np.ndarray:
        leaves = jax.tree_util.tree_leaves(self.params_)
        return np.concatenate([np.asarray(l).ravel() for l in leaves]) if leaves \
            else np.zeros((0,), np.float32)

    def set_params(self, flat: np.ndarray):
        leaves, treedef = jax.tree_util.tree_flatten(self.params_)
        out, off = [], 0
        for l in leaves:
            n = int(np.prod(l.shape))
            out.append(jnp.asarray(flat[off:off + n], l.dtype).reshape(l.shape))
            off += n
        if off != flat.size:
            raise ValueError(f"Param count mismatch: {flat.size} vs {off}")
        self.params_ = jax.tree_util.tree_unflatten(treedef, out)

    def gradient_for(self, features, labels) -> Params:
        """Analytic gradients (GradientCheckUtil hook).  Eval mode, matching
        `score_for` — finite differences of score_for are only comparable to
        gradients taken in the same mode (BN running stats, no dropout)."""
        inputs = self._as_input_dict(features)
        labels = self._as_list(labels)

        def loss_fn(p):
            return self._loss(p, self.state_, inputs, labels, None,
                              train=False)[0]
        return jax.grad(loss_fn)(self.params_)

    def set_listeners(self, *listeners):
        self.listeners = list(listeners)
        return self

    # ---- persistence ----
    def save(self, path, save_updater: bool = True):
        from deeplearning4j_tpu.utils.serialization import write_model
        write_model(self, path, save_updater=save_updater)

    @staticmethod
    def load(path, load_updater: bool = True) -> "ComputationGraph":
        from deeplearning4j_tpu.utils.serialization import read_model
        return read_model(path, load_updater=load_updater)
