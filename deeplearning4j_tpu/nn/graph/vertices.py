"""Graph vertices (reference nn/graph/vertex/impl/*: LayerVertex, MergeVertex,
ElementWiseVertex, Stack/Unstack/Subset/Scale/Shift/L2/L2Normalize/
Preprocessor vertices, rnn/{LastTimeStepVertex, DuplicateToTimeSeriesVertex};
SURVEY.md §2.1 ComputationGraph row).

Each vertex is a dataclass with ``forward(params, state, inputs, ...)`` over a
LIST of input activations; LayerVertex wraps a layer conf and owns its params.
Backprop is autodiff through the whole DAG."""

from __future__ import annotations

import dataclasses
from typing import Dict, List, Optional

import jax.numpy as jnp

from ..conf.input_type import InputType
from ..conf.serde import register_config
from ..conf.layers.base import LayerConf


class GraphVertexConf:
    """Base: parameter-free vertex over input activations."""

    def n_inputs(self):          # None = any
        return None

    def init_params(self, key, dtype=jnp.float32) -> Dict:
        return {}

    def init_state(self) -> Dict:
        return {}

    def output_type(self, input_types: List[InputType]) -> InputType:
        return input_types[0]

    def forward(self, params, state, inputs: List, *, train=False, rng=None,
                masks=None):
        raise NotImplementedError


@register_config
@dataclasses.dataclass
class LayerVertex(GraphVertexConf):
    """Wraps a layer conf (reference LayerVertex); single input."""
    layer: LayerConf = None
    preprocessor: Optional[object] = None

    def n_inputs(self):
        return 1

    def init_params(self, key, dtype=jnp.float32):
        return self.layer.init_params(key, dtype)

    def init_state(self):
        return self.layer.init_state()

    def output_type(self, input_types):
        it = input_types[0]
        if self.preprocessor is not None:
            it = self.preprocessor.output_type(it)
        return self.layer.get_output_type(it)

    def forward(self, params, state, inputs, *, train=False, rng=None,
                masks=None):
        x = inputs[0]
        mask = masks[0] if masks else None
        if self.preprocessor is not None:
            x = self.preprocessor.pre_process(x, mask)
            mask = self.preprocessor.feed_forward_mask(mask)
        y, nstate = self.layer.forward(params, state, x, train=train, rng=rng,
                                       mask=mask)
        return y, nstate


@register_config
@dataclasses.dataclass
class MergeVertex(GraphVertexConf):
    """Concatenate along the feature (last) axis (reference MergeVertex)."""

    def output_type(self, input_types):
        it = input_types[0]
        total = sum(t.flat_size() if t.kind == "ff" else t.size
                    for t in input_types) if it.kind in ("ff", "rnn") else None
        if it.kind == "ff":
            return InputType.feed_forward(total)
        if it.kind == "rnn":
            return InputType.recurrent(total, it.timesteps)
        # cnn: channels concat
        return InputType.convolutional(
            it.height, it.width, sum(t.channels for t in input_types))

    def forward(self, params, state, inputs, *, train=False, rng=None,
                masks=None):
        return jnp.concatenate(inputs, axis=-1), state


@register_config
@dataclasses.dataclass
class ElementWiseVertex(GraphVertexConf):
    """Pointwise add/subtract/product/average/max (reference ElementWiseVertex).
    ``branch_scale`` multiplies every input after the first of an ``add``
    (a residual branch scaled where the graph adds it)."""
    op: str = "add"
    branch_scale: float = 1.0

    def forward(self, params, state, inputs, *, train=False, rng=None,
                masks=None):
        op = self.op.lower()
        if op == "add":
            out = inputs[0]
            for x in inputs[1:]:
                if self.branch_scale != 1.0:
                    x = x * jnp.asarray(self.branch_scale, x.dtype)
                out = out + x
        elif op == "subtract":
            out = inputs[0] - inputs[1]
        elif op in ("product", "prod", "mul"):
            out = inputs[0]
            for x in inputs[1:]:
                out = out * x
        elif op in ("average", "avg"):
            out = sum(inputs) / len(inputs)
        elif op == "max":
            out = inputs[0]
            for x in inputs[1:]:
                out = jnp.maximum(out, x)
        else:
            raise ValueError(f"Unknown elementwise op {self.op}")
        return out, state


@register_config
@dataclasses.dataclass
class SubsetVertex(GraphVertexConf):
    """Feature-axis slice [from, to] inclusive (reference SubsetVertex)."""
    from_index: int = 0
    to_index: int = 0

    def output_type(self, input_types):
        size = self.to_index - self.from_index + 1
        it = input_types[0]
        if it.kind == "rnn":
            return InputType.recurrent(size, it.timesteps)
        return InputType.feed_forward(size)

    def forward(self, params, state, inputs, *, train=False, rng=None,
                masks=None):
        return inputs[0][..., self.from_index:self.to_index + 1], state


@register_config
@dataclasses.dataclass
class StackVertex(GraphVertexConf):
    """Stack along the batch axis (reference StackVertex)."""

    def forward(self, params, state, inputs, *, train=False, rng=None,
                masks=None):
        return jnp.concatenate(inputs, axis=0), state


@register_config
@dataclasses.dataclass
class UnstackVertex(GraphVertexConf):
    """Take batch slice ``index`` of ``num_stacks`` (reference UnstackVertex)."""
    index: int = 0
    num_stacks: int = 1

    def forward(self, params, state, inputs, *, train=False, rng=None,
                masks=None):
        x = inputs[0]
        size = x.shape[0] // self.num_stacks
        return x[self.index * size:(self.index + 1) * size], state


@register_config
@dataclasses.dataclass
class ScaleVertex(GraphVertexConf):
    """Multiply by a fixed scalar (reference ScaleVertex)."""
    scale: float = 1.0

    def forward(self, params, state, inputs, *, train=False, rng=None,
                masks=None):
        return inputs[0] * self.scale, state


@register_config
@dataclasses.dataclass
class ShiftVertex(GraphVertexConf):
    """Add a fixed scalar (reference ShiftVertex)."""
    shift: float = 0.0

    def forward(self, params, state, inputs, *, train=False, rng=None,
                masks=None):
        return inputs[0] + self.shift, state


@register_config
@dataclasses.dataclass
class L2Vertex(GraphVertexConf):
    """Pairwise L2 distance between two inputs → [N, 1] (reference L2Vertex)."""
    eps: float = 1e-8

    def output_type(self, input_types):
        return InputType.feed_forward(1)

    def forward(self, params, state, inputs, *, train=False, rng=None,
                masks=None):
        a, b = inputs
        d = a - b
        axes = tuple(range(1, d.ndim))
        return jnp.sqrt(jnp.sum(d * d, axis=axes) + self.eps)[:, None], state


@register_config
@dataclasses.dataclass
class L2NormalizeVertex(GraphVertexConf):
    """Normalize activations to unit L2 norm (reference L2NormalizeVertex)."""
    eps: float = 1e-8

    def forward(self, params, state, inputs, *, train=False, rng=None,
                masks=None):
        x = inputs[0]
        axes = tuple(range(1, x.ndim))
        norm = jnp.sqrt(jnp.sum(x * x, axis=axes, keepdims=True) + self.eps)
        return x / norm, state


@register_config
@dataclasses.dataclass
class PreprocessorVertex(GraphVertexConf):
    """Standalone InputPreProcessor as a vertex (reference PreprocessorVertex)."""
    preprocessor: object = None

    def output_type(self, input_types):
        return self.preprocessor.output_type(input_types[0])

    def forward(self, params, state, inputs, *, train=False, rng=None,
                masks=None):
        return self.preprocessor.pre_process(inputs[0]), state


@register_config
@dataclasses.dataclass
class LastTimeStepVertex(GraphVertexConf):
    """[N,T,F] → [N,F] last (mask-aware) timestep (reference
    rnn/LastTimeStepVertex)."""
    mask_input: Optional[str] = None

    def output_type(self, input_types):
        return InputType.feed_forward(input_types[0].size)

    def forward(self, params, state, inputs, *, train=False, rng=None,
                masks=None):
        x = inputs[0]
        mask = masks[0] if masks else None
        if mask is None:
            return x[:, -1, :], state
        idx = jnp.maximum(jnp.sum(mask.astype(jnp.int32), axis=1) - 1, 0)
        return x[jnp.arange(x.shape[0]), idx], state


@register_config
@dataclasses.dataclass
class DuplicateToTimeSeriesVertex(GraphVertexConf):
    """[N,F] → [N,T,F] broadcast over the time axis of a reference input
    (reference rnn/DuplicateToTimeSeriesVertex). The second input supplies T."""
    ts_input: Optional[str] = None

    def output_type(self, input_types):
        it = input_types[0]
        t = input_types[1].timesteps if len(input_types) > 1 else None
        return InputType.recurrent(it.flat_size(), t)

    def forward(self, params, state, inputs, *, train=False, rng=None,
                masks=None):
        x, ref = inputs[0], inputs[1]
        t = ref.shape[1]
        return jnp.broadcast_to(x[:, None, :], (x.shape[0], t, x.shape[-1])), \
            state
