"""Keras-style model engine: Layer base, symbolic graph, Sequential/Model.

The TPU-native analogue of the reference's Keras API
(`zoo/.../pipeline/api/keras/models/Topology.scala`: `KerasNet` `:67`,
`compile` `:139`, `fit` `:347`, `evaluate` `:504`, `predict`, `Model` `:631`,
`Sequential` `:854`; python mirror `pyzoo/zoo/pipeline/api/keras/engine/
topology.py:200-246`). Design differences are deliberate and TPU-first:

- A layer is a *pure function* plus a parameter pytree — no mutable module
  state. `build(rng, input_shape) -> params`, `call(params, x)`.
- `Sequential`/`Model` compose layers into one pure `apply(params, inputs)`
  which jit-compiles to a single fused XLA program (the reference instead
  interprets a JVM graph node-by-node per minibatch).
- The same symbolic `Node` graph that powers the functional `Model` API also
  powers the autograd `Variable` DSL (`ops/autograd.py`), mirroring how the
  reference's autograd builds on its graph nodes (`autograd/math.scala:378`).
- `fit` delegates to the distributed trainer (`learn/trainer.py`): batch
  sharding over the mesh's data axes; one train step = one XLA program.

Keras semantics preserved: `input_shape` excludes the batch dim; compile
strings for loss/optimizer/metrics resolve through the reference registries
(`ops/objectives.py`, `ops/optimizers.py`, `ops/metrics.py`).
"""

from __future__ import annotations

import collections
import contextlib
from typing import Any, Callable, Dict, List, Optional, Sequence, Tuple, Union

import jax
import jax.numpy as jnp
import numpy as np

Shape = Tuple[Optional[int], ...]
Params = Dict[str, Any]

_name_counters: Dict[str, int] = collections.defaultdict(int)


def _auto_name(cls_name: str) -> str:
    _name_counters[cls_name] += 1
    return f"{cls_name.lower()}_{_name_counters[cls_name]}"


def reset_name_scope() -> None:
    _name_counters.clear()


class Layer:
    """Base layer. Subclasses implement `build`, `call`,
    `compute_output_shape`. Stateless: parameters live in the pytree returned
    by build and are passed back into call."""

    def __init__(self, input_shape: Optional[Shape] = None,
                 name: Optional[str] = None):
        self.name = name or _auto_name(type(self).__name__)
        # Keras contract: input_shape excludes the batch dimension.
        self.input_shape = (None,) + tuple(input_shape) if input_shape else None

    # True for layers carrying non-gradient state (e.g. BatchNorm moving
    # stats); they implement call_and_state.
    stateful = False

    # A `jax.named_scope` a functional `Model` runs this layer under:
    # metadata of the compiled program (device time by scope,
    # `observability/device_time.py`), never a different program.
    scope: Optional[str] = None

    # -- subclass API ------------------------------------------------------
    def build(self, rng: jax.Array, input_shape: Shape) -> Params:
        return {}

    def call(self, params: Params, x, *, training: bool = False,
             rng: Optional[jax.Array] = None):
        raise NotImplementedError

    def call_and_state(self, params: Params, x, *, training: bool = False,
                       rng: Optional[jax.Array] = None):
        """Stateful layers return (y, updated-param-entries); the trainer
        merges the updates back into params outside the gradient path."""
        return self.call(params, x, training=training, rng=rng), {}

    def compute_output_shape(self, input_shape: Shape) -> Shape:
        return input_shape

    # -- graph building ----------------------------------------------------
    def __call__(self, inputs: Union["Node", Sequence["Node"]]) -> "Node":
        """Symbolic call: layer applied to graph node(s) yields a node.
        Node-wrapper objects (autograd Variables — anything exposing `.node`
        as a Node) are accepted; the result is re-wrapped in the same type."""
        raw = inputs if isinstance(inputs, (list, tuple)) else [inputs]
        wrapper_cls = None
        nodes = []
        for item in raw:
            if isinstance(item, Node):
                nodes.append(item)
            elif isinstance(getattr(item, "node", None), Node):
                wrapper_cls = type(item)
                nodes.append(item.node)
            else:
                raise TypeError(
                    f"{self.name} called on non-Node inputs; use Input(shape) "
                    "to start a functional graph, or Sequential for linear "
                    "stacks")
        in_shapes = [n.shape for n in nodes]
        shape_in = in_shapes if len(in_shapes) > 1 else in_shapes[0]
        out_shape = self.compute_output_shape(shape_in)
        out = Node(layer=self, inputs=nodes, shape=out_shape)
        return wrapper_cls(node=out) if wrapper_cls is not None else out

    def __repr__(self):
        return f"{type(self).__name__}(name={self.name})"


class Node:
    """A symbolic tensor in the layer graph (the reference's `ModuleNode`/
    autograd `Variable` substrate)."""

    def __init__(self, layer: Optional[Layer], inputs: List["Node"],
                 shape: Shape):
        self.layer = layer
        self.inputs = inputs
        self.shape = shape

    # Autograd DSL operators are attached by ops/autograd.py to avoid a
    # circular import; see `autograd._install_operators`.

    def __repr__(self):
        lname = self.layer.name if self.layer else "input"
        return f"Node({lname}, shape={self.shape})"


def Input(shape: Shape, name: Optional[str] = None) -> Node:
    """Entry node of a functional graph. `shape` excludes the batch dim
    (Keras contract, `keras/models/Topology.scala` Input)."""
    return Node(layer=None, inputs=[], shape=(None,) + tuple(shape))


def _topo_sort(outputs: Sequence[Node]) -> List[Node]:
    order: List[Node] = []
    seen: set = set()

    def visit(n: Node):
        if id(n) in seen:
            return
        seen.add(id(n))
        for i in n.inputs:
            visit(i)
        order.append(n)

    for out in outputs:
        visit(out)
    return order


class KerasNet:
    """Shared compile/fit/evaluate/predict surface (`Topology.scala:67`)."""

    def __init__(self, name: Optional[str] = None):
        self.name = name or _auto_name(type(self).__name__)
        self.loss = None
        self.optimizer = None
        self.metrics: List[Any] = []
        self._tensorboard_dir: Optional[str] = None
        self._checkpoint_path: Optional[str] = None
        self.params: Optional[Params] = None
        self._built_shape: Optional[Shape] = None

    # -- subclass API ------------------------------------------------------
    def build(self, rng: jax.Array, input_shape) -> Params:
        raise NotImplementedError

    def apply(self, params: Params, inputs, *, training: bool = False,
              rng: Optional[jax.Array] = None):
        raise NotImplementedError

    def apply_and_state(self, params: Params, inputs, *,
                        training: bool = False,
                        rng: Optional[jax.Array] = None):
        """Like apply, but also returns {layer_name: updated entries} from
        stateful layers (BatchNorm moving stats)."""
        return self.apply(params, inputs, training=training, rng=rng), {}

    def compute_output_shape(self, input_shape):
        raise NotImplementedError

    # -- Keras surface -----------------------------------------------------
    def compile(self, optimizer, loss, metrics: Optional[Sequence] = None):
        """`Topology.scala:139`: resolve compile strings through the
        registries; `"accuracy"` dispatches on the loss string."""
        from analytics_zoo_tpu.ops import metrics as zmetrics
        from analytics_zoo_tpu.ops import objectives, optimizers
        # remembered so features that re-derive per-parameter update rules
        # (lazy embeddings) can check hyperparameter compatibility
        self._optimizer_spec = optimizer if isinstance(optimizer, str) \
            else None
        loss_str = loss if isinstance(loss, str) else None
        if isinstance(loss, (list, tuple)):
            # Keras multi-output contract: one loss per output, summed
            fns = [objectives.get(l) for l in loss]

            def _combined(y_true, y_pred):
                if not isinstance(y_pred, (list, tuple)) \
                        or len(y_pred) != len(fns):
                    n = len(y_pred) if isinstance(y_pred, (list, tuple)) \
                        else 1
                    raise ValueError(
                        f"compile() got {len(fns)} losses but the model "
                        f"produces {n} output(s)")
                if not isinstance(y_true, (list, tuple)) \
                        or len(y_true) != len(fns):
                    raise ValueError(
                        f"multi-output loss needs a list of {len(fns)} "
                        "label arrays (got a single array — it would zip "
                        "batch rows, not outputs)")
                return sum(fn(t, p)
                           for fn, t, p in zip(fns, y_true, y_pred))

            self.loss = _combined
        else:
            self.loss = objectives.get(loss)
        self.optimizer = optimizers.get(optimizer)
        self.metrics = zmetrics.resolve(metrics, loss_str)
        # recompiling invalidates any jitted closures built over the old
        # optimizer/loss/metrics (id() reuse after GC makes key checks
        # alone unreliable)
        for cache in ("_train_cache", "_eval_cache", "_predict_cache"):
            if hasattr(self, cache):
                delattr(self, cache)

    def set_tensorboard(self, log_dir: str, app_name: str):
        """`Topology.scala:208`."""
        self._tensorboard_dir = f"{log_dir.rstrip('/')}/{app_name}"

    def set_checkpoint(self, path: str, over_write: bool = True):
        """`Topology.scala:249`."""
        self._checkpoint_path = path

    def ensure_built(self, sample_input, rng: Optional[jax.Array] = None):
        """Initialise parameters from a sample batch (shape source)."""
        if self.params is not None:
            return self.params
        if rng is None:
            rng = jax.random.PRNGKey(0)
        shape = jax.tree_util.tree_map(
            lambda a: (None,) + tuple(np.shape(a))[1:], sample_input,
            is_leaf=lambda a: hasattr(a, "shape") or isinstance(a, np.ndarray))
        self.params = self.build(rng, shape)
        return self.params

    def fit(self, x, y=None, batch_size: int = 32, nb_epoch: int = 1,
            validation_data=None, distributed: bool = True, **kwargs):
        """`Topology.scala:347` / `topology.py:200`. Delegates to the
        distributed trainer; returns the history dict."""
        from analytics_zoo_tpu.learn.trainer import fit_keras
        return fit_keras(self, x, y, batch_size=batch_size, epochs=nb_epoch,
                         validation_data=validation_data,
                         distributed=distributed, **kwargs)

    def evaluate(self, x, y=None, batch_per_thread: int = 32, **kwargs):
        """`Topology.scala:504`: per-device batch for eval (the reference's
        batch-per-thread contract, `tf_dataset.py:116-157`)."""
        from analytics_zoo_tpu.learn.trainer import evaluate_keras
        return evaluate_keras(self, x, y, batch_per_thread=batch_per_thread,
                              **kwargs)

    def predict(self, x, batch_per_thread: int = 32, **kwargs):
        from analytics_zoo_tpu.learn.trainer import predict_keras
        return predict_keras(self, x, batch_per_thread=batch_per_thread,
                             **kwargs)

    # -- persistence (`models/common/ZooModel.scala` save/load) -----------
    def save_weights(self, path: str, params: Optional[Params] = None):
        """Persist `params` (default: this model's) + the layer-order
        sidecar. `params` lets derived trees (e.g. int8-quantized,
        serving/quantization.py) reuse the one artifact protocol."""
        import json
        from analytics_zoo_tpu.learn import checkpoint as ckpt
        if params is None:
            params = self.params
        if params is None:
            raise ValueError("Model has no parameters yet; call fit or "
                             "ensure_built first")
        ckpt.save_pytree(path, jax.device_get(params))
        order = self._layer_order()
        if order:
            with open(self._order_path(path), "w") as fh:
                json.dump(order, fh)

    def load_weights_tree(self, path: str) -> Params:
        """Read an artifact written by save_weights and remap it onto
        THIS instance's layer names — without assigning it. Callers that
        serve derived trees (int8 artifacts) use this; `load_weights`
        assigns the result."""
        import json
        import os
        from analytics_zoo_tpu.learn import checkpoint as ckpt
        loaded = ckpt.load_pytree(path)
        order = None
        if os.path.exists(self._order_path(path)):
            with open(self._order_path(path)) as fh:
                order = json.load(fh)
        return self._remap_loaded(loaded, order)

    def load_weights(self, path: str):
        self.params = self.load_weights_tree(path)
        return self

    @staticmethod
    def _order_path(path: str) -> str:
        base = path[:-4] if path.endswith(".npz") else path
        return base + ".layers.json"

    def _ordered_layers(self) -> List[Layer]:
        """Deterministic layer order for positional weight remapping;
        subclasses with named sub-layers override."""
        return []

    def _layer_order(self) -> List[str]:
        return [l.name for l in self._ordered_layers()]

    def _remap_loaded(self, loaded: Params,
                      order: Optional[List[str]] = None) -> Params:
        """Auto-generated layer names differ across instances; remap saved
        params onto this instance's names, recursing into nested
        Sequential/Model blocks. Matching is per-class-prefix by the numeric
        suffix of the auto names (creation order within a class equals
        structural order for identical architectures) — dict ordering is NOT
        relied on, since jax tree ops re-sort dict keys."""
        import re
        layers = self._ordered_layers()
        if not layers:
            return loaded
        if order is not None and (len(order) != len(loaded)
                                  or set(order) != set(loaded)):
            raise ValueError(
                f"Stale/mismatched layer-order sidecar: order has "
                f"{len(order)} names, saved params have {len(loaded)}")
        if len(loaded) != len(layers):
            raise ValueError(
                f"Saved weights have {len(loaded)} layers, model has "
                f"{len(layers)}")

        def remap_child(layer: Layer, value):
            if isinstance(layer, KerasNet):
                return layer._remap_loaded(value)
            return value

        if order is not None:
            # The sidecar records saved names in STRUCTURAL order — map
            # positionally onto this instance's structural order. Handles
            # custom layer names and same-class layers created out of
            # add() order (where prefix/suffix matching would mis-map).
            # Auto-generated names ("<class>_<n>") still carry their class:
            # cross-class positional assignment is an architecture mismatch.
            import re
            for layer, sname in zip(layers, order):
                saved_auto = re.match(r"^(.*)_(\d+)$", sname)
                cur_auto = re.match(r"^(.*)_(\d+)$", layer.name)
                if saved_auto and cur_auto \
                        and cur_auto.group(1) == type(layer).__name__.lower() \
                        and saved_auto.group(1) != cur_auto.group(1):
                    raise ValueError(
                        f"Saved layer {sname!r} does not match model layer "
                        f"{layer.name!r} ({type(layer).__name__}) at the "
                        "same structural position")
            return {layer.name: remap_child(layer, loaded[sname])
                    for layer, sname in zip(layers, order)}

        if set(loaded) == {l.name for l in layers}:
            return {l.name: remap_child(l, loaded[l.name]) for l in layers}

        def split(name: str):
            m = re.match(r"^(.*)_(\d+)$", name)
            return (m.group(1), int(m.group(2))) if m else (name, 0)

        saved_by_prefix: Dict[str, List] = {}
        for name in loaded:
            p, n = split(name)
            saved_by_prefix.setdefault(p, []).append((n, name))
        cur_by_prefix: Dict[str, List] = {}
        for layer in layers:
            p, n = split(layer.name)
            cur_by_prefix.setdefault(p, []).append((n, layer))
        if {p: len(v) for p, v in saved_by_prefix.items()} != \
                {p: len(v) for p, v in cur_by_prefix.items()}:
            raise ValueError(
                f"Saved layer classes {sorted(saved_by_prefix)} do not match "
                f"model layer classes {sorted(cur_by_prefix)}")
        result: Params = {}
        for p, cur_list in cur_by_prefix.items():
            for (_, layer), (_, sname) in zip(sorted(cur_list,
                                                     key=lambda t: t[0]),
                                              sorted(saved_by_prefix[p],
                                                     key=lambda t: t[0])):
                result[layer.name] = remap_child(layer, loaded[sname])
        return result

    def summary(self):
        lines = [f"Model: {self.name}", "-" * 60]
        for layer, shape, count in self._summary_rows():
            lines.append(f"{layer:<30} {str(shape):<20} {count}")
        lines.append("-" * 60)
        total = sum(r[2] for r in self._summary_rows())
        lines.append(f"Total params: {total}")
        text = "\n".join(lines)
        print(text)
        return text

    def _summary_rows(self):
        return []

    @staticmethod
    def _count(params) -> int:
        return sum(int(np.prod(np.shape(p)))
                   for p in jax.tree_util.tree_leaves(params))


class Sequential(KerasNet):
    """Linear stack (`Topology.scala:854`). First layer must carry
    `input_shape`, like Keras."""

    def __init__(self, layers: Optional[Sequence[Layer]] = None,
                 name: Optional[str] = None):
        super().__init__(name)
        self.layers: List[Layer] = []
        for l in (layers or []):
            self.add(l)

    def add(self, layer: Layer) -> "Sequential":
        if not self.layers and layer.input_shape is None \
                and not isinstance(layer, (Sequential, Model)):
            # allowed: shape may come later via ensure_built(sample)
            pass
        self.layers.append(layer)
        return self

    def build(self, rng: jax.Array, input_shape: Shape) -> Params:
        if self.layers and self.layers[0].input_shape is not None:
            input_shape = self.layers[0].input_shape
        if input_shape is None:
            raise ValueError(
                "Cannot build Sequential: no input_shape on first layer")
        params: Params = {}
        shape = input_shape
        for layer in self.layers:
            rng, sub = jax.random.split(rng)
            params[layer.name] = layer.build(sub, shape)
            shape = layer.compute_output_shape(shape)
        self._built_shape = shape
        return params

    def apply(self, params: Params, inputs, *, training: bool = False,
              rng: Optional[jax.Array] = None):
        x = inputs
        for layer in self.layers:
            if rng is not None:
                rng, sub = jax.random.split(rng)
            else:
                sub = None
            x = layer.call(params[layer.name], x, training=training, rng=sub)
        return x

    def apply_and_state(self, params: Params, inputs, *,
                        training: bool = False,
                        rng: Optional[jax.Array] = None):
        x = inputs
        updates: Params = {}
        for layer in self.layers:
            if rng is not None:
                rng, sub = jax.random.split(rng)
            else:
                sub = None
            x, upd = layer.call_and_state(params[layer.name], x,
                                          training=training, rng=sub)
            if upd:
                updates[layer.name] = upd
        return x, updates

    def compute_output_shape(self, input_shape: Shape) -> Shape:
        shape = input_shape
        for layer in self.layers:
            shape = layer.compute_output_shape(shape)
        return shape

    # Sequential itself can be nested as a layer or called on a Node.
    def call(self, params, x, *, training=False, rng=None):
        return self.apply(params, x, training=training, rng=rng)

    def call_and_state(self, params, x, *, training=False, rng=None):
        return self.apply_and_state(params, x, training=training, rng=rng)

    stateful = True  # may contain stateful layers

    def __call__(self, inputs):
        return Layer.__call__(self, inputs)

    @property
    def input_shape(self):
        return self.layers[0].input_shape if self.layers else None

    @input_shape.setter
    def input_shape(self, v):
        pass  # satisfied by first layer

    def _summary_rows(self):
        rows = []
        if self.params:
            for layer in self.layers:
                rows.append((f"{layer.name} ({type(layer).__name__})",
                             "-", self._count(self.params.get(layer.name))))
        return rows

    def _ordered_layers(self):
        return self.layers


class Model(KerasNet):
    """Functional graph model (`Topology.scala:631`): built from `Input`
    nodes and symbolic layer calls."""

    def __init__(self, inputs: Union[Node, Sequence[Node]],
                 outputs: Union[Node, Sequence[Node]],
                 name: Optional[str] = None):
        super().__init__(name)

        def unwrap(x):  # accept autograd Variables interchangeably with Nodes
            return x.node if hasattr(x, "node") else x
        inputs = [unwrap(i) for i in inputs] \
            if isinstance(inputs, (list, tuple)) else [unwrap(inputs)]
        outputs = [unwrap(o) for o in outputs] \
            if isinstance(outputs, (list, tuple)) else [unwrap(outputs)]
        self.inputs = inputs
        self.outputs = outputs
        self._order = _topo_sort(self.outputs)
        # deduplicate shared layers (weight sharing): one param set per layer
        # *object*; two distinct layers with the same name is an error (Keras
        # raises too — silent aliasing would corrupt weights)
        self._layers: List[Layer] = []
        seen: Dict[int, Layer] = {}
        by_name: Dict[str, Layer] = {}
        for node in self._order:
            if node.layer is not None and id(node.layer) not in seen:
                dup = by_name.get(node.layer.name)
                if dup is not None and dup is not node.layer:
                    raise ValueError(
                        f"Duplicate layer name {node.layer.name!r} for two "
                        "distinct layers in one graph")
                seen[id(node.layer)] = node.layer
                by_name[node.layer.name] = node.layer
                self._layers.append(node.layer)

    def build(self, rng: jax.Array, input_shape=None) -> Params:
        params: Params = {}
        shapes: Dict[int, Shape] = {}
        for node in self._order:
            if node.layer is None:
                shapes[id(node)] = node.shape
            else:
                in_shapes = [shapes[id(i)] for i in node.inputs]
                # zero-input nodes are parameter/constant sources
                # (ops/autograd.py Parameter): build sees shape_in=None
                shape_in = in_shapes if len(in_shapes) > 1 else (
                    in_shapes[0] if in_shapes else None)
                if node.layer.name not in params:
                    rng, sub = jax.random.split(rng)
                    params[node.layer.name] = node.layer.build(sub, shape_in)
                shapes[id(node)] = node.layer.compute_output_shape(shape_in)
        return params

    def apply(self, params: Params, inputs, *, training: bool = False,
              rng: Optional[jax.Array] = None):
        out, _ = self.apply_and_state(params, inputs, training=training,
                                      rng=rng)
        return out

    def apply_and_state(self, params: Params, inputs, *,
                        training: bool = False,
                        rng: Optional[jax.Array] = None):
        xs = inputs if isinstance(inputs, (list, tuple)) else [inputs]
        if len(xs) != len(self.inputs):
            raise ValueError(
                f"Model {self.name} expects {len(self.inputs)} inputs, "
                f"got {len(xs)}")
        values: Dict[int, Any] = {id(n): x for n, x in zip(self.inputs, xs)}
        updates: Params = {}
        for node in self._order:
            if id(node) in values:
                continue
            if node.layer is None:
                raise ValueError("Disconnected input node in graph")
            args = [values[id(i)] for i in node.inputs]
            arg = args if len(args) > 1 else (args[0] if args else None)
            if rng is not None:
                rng, sub = jax.random.split(rng)
            else:
                sub = None
            # (a nested model is a node too, and has no scope of its own)
            scope = getattr(node.layer, "scope", None)
            with jax.named_scope(scope) if scope \
                    else contextlib.nullcontext():
                y, upd = node.layer.call_and_state(
                    params[node.layer.name], arg, training=training,
                    rng=sub)
            values[id(node)] = y
            if upd:
                updates.setdefault(node.layer.name, {}).update(upd)
        outs = [values[id(o)] for o in self.outputs]
        return (outs if len(outs) > 1 else outs[0]), updates

    def compute_output_shape(self, input_shape):
        outs = [o.shape for o in self.outputs]
        return outs if len(outs) > 1 else outs[0]

    # nested-as-layer support
    def call(self, params, x, *, training=False, rng=None):
        return self.apply(params, x, training=training, rng=rng)

    def call_and_state(self, params, x, *, training=False, rng=None):
        return self.apply_and_state(params, x, training=training, rng=rng)

    stateful = True  # may contain stateful layers

    def __call__(self, inputs):
        return Layer.__call__(self, inputs)

    def _summary_rows(self):
        rows = []
        if self.params:
            for layer in self._layers:
                rows.append((f"{layer.name} ({type(layer).__name__})",
                             "-", self._count(self.params.get(layer.name))))
        return rows

    def _ordered_layers(self):
        return self._layers
