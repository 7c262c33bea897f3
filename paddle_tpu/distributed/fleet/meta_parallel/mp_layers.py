"""Megatron-style tensor-parallel layers.

Ref parity: python/paddle/distributed/fleet/meta_parallel/parallel_layers/
mp_layers.py:30,97,170,249 (VocabParallelEmbedding, ColumnParallelLinear,
RowParallelLinear, ParallelCrossEntropy) built on _c_identity /
_mp_allreduce / _c_lookup_table collective ops.

TPU-native design (GSPMD path): parameters keep their FULL logical shape
and carry a PartitionSpec over the 'mp' mesh axis (`Parameter.param_spec`).
Forward code is ordinary dense math plus `shard_hint` constraints; the XLA
SPMD partitioner inserts the all-reduces/all-gathers the reference issues
by hand — and overlaps them with compute. Eager single-process execution
is exact dense math (degree-1 behaviour).
"""

from __future__ import annotations

import jax
from jax.sharding import PartitionSpec as P

from ....core.dispatch import apply
from ....core.tensor import Tensor
from ....nn import functional as F
from ....nn import initializer as I
from ....nn.layer.layers import Layer
from ...topology import MP_AXIS, get_hybrid_communicate_group


def _mesh():
    hcg = get_hybrid_communicate_group()
    return hcg.get_mesh() if hcg is not None else None


def shard_hint(x, *spec):
    """with_sharding_constraint when tracing on a mesh; no-op eagerly."""
    mesh = _mesh()
    if mesh is None:
        return x
    v = x._value if isinstance(x, Tensor) else x
    if not isinstance(v, jax.core.Tracer):
        return x
    from jax.sharding import NamedSharding

    # inside shard_map (e.g. the pipeline's manual 'pp' region) the trace
    # carries an abstract mesh; constraints must be built on it
    am = jax.sharding.get_abstract_mesh()
    if am.axis_names:
        mesh = am
    constrained = jax.lax.with_sharding_constraint(
        v, NamedSharding(mesh, P(*spec)))
    if isinstance(x, Tensor):
        out = Tensor(constrained)
        out.stop_gradient = x.stop_gradient
        out._tape = x._tape
        return out
    return constrained


class VocabParallelEmbedding(Layer):
    """Embedding with the vocab axis sharded over 'mp'
    (ref: mp_layers.py:30)."""

    def __init__(self, num_embeddings, embedding_dim, weight_attr=None,
                 mp_group=None, name=None):
        super().__init__()
        self.num_embeddings = num_embeddings
        self.embedding_dim = embedding_dim
        self.weight = self.create_parameter(
            shape=[num_embeddings, embedding_dim], attr=weight_attr,
            default_initializer=I.XavierNormal())
        self.weight.param_spec = P(MP_AXIS, None)
        self.weight.is_distributed = True

    def forward(self, x):
        out = F.embedding(x, self.weight)
        return shard_hint(out, None, None, None)


class ColumnParallelLinear(Layer):
    """Linear with out_features sharded over 'mp' (ref: mp_layers.py:97)."""

    def __init__(self, in_features, out_features, weight_attr=None,
                 has_bias=None, gather_output=True, fuse_matmul_bias=False,
                 mp_group=None, name=None):
        super().__init__()
        self.in_features = in_features
        self.out_features = out_features
        self.gather_output = gather_output
        self.weight = self.create_parameter(
            shape=[in_features, out_features], attr=weight_attr,
            default_initializer=I.XavierNormal())
        self.weight.param_spec = P(None, MP_AXIS)
        self.weight.is_distributed = True
        if has_bias or has_bias is None:
            self.bias = self.create_parameter(
                shape=[out_features], attr=None, is_bias=True)
            self.bias.param_spec = P(MP_AXIS)
            self.bias.is_distributed = True
        else:
            self.bias = None

    def forward(self, x):
        if not self.gather_output:
            # latency-hiding path: the SP seq all-gather decomposes into
            # ring hops hidden behind per-chunk partial matmuls
            from ....ops import overlap as _overlap

            out = _overlap.maybe_column_parallel(x, self.weight)
            if out is not None:
                if self.bias is not None:
                    out = out + self.bias
                return shard_hint(out, *([None] * (out.ndim - 1)), MP_AXIS)
        out = F.linear(x, self.weight, self.bias)
        if self.gather_output:
            return shard_hint(out, *([None] * out.ndim))
        # keep the hidden axis sharded: activations stay model-parallel
        return shard_hint(out, *([None] * (out.ndim - 1)), MP_AXIS)


class RowParallelLinear(Layer):
    """Linear with in_features sharded over 'mp'; output needs the partial
    -sum reduction, which XLA emits from the contraction sharding
    (ref: mp_layers.py:170)."""

    def __init__(self, in_features, out_features, weight_attr=None,
                 has_bias=True, input_is_parallel=False,
                 fuse_matmul_bias=False, mp_group=None, name=None):
        super().__init__()
        self.in_features = in_features
        self.out_features = out_features
        self.input_is_parallel = input_is_parallel
        self.weight = self.create_parameter(
            shape=[in_features, out_features], attr=weight_attr,
            default_initializer=I.XavierNormal())
        self.weight.param_spec = P(MP_AXIS, None)
        self.weight.is_distributed = True
        if has_bias:
            self.bias = self.create_parameter(
                shape=[out_features], attr=None, is_bias=True)
        else:
            self.bias = None

    def forward(self, x):
        if self.input_is_parallel:
            x = shard_hint(x, *([None] * (x.ndim - 1)), MP_AXIS)
        # latency-hiding path: the mp all-reduce (or SP reduce-scatter)
        # decomposes into ring hops hidden behind partial matmuls; the
        # shard_map output already carries its final sharding, so no
        # forcing hint is needed
        from ....ops import overlap as _overlap

        out = _overlap.maybe_row_parallel(x, self.weight)
        if out is None:
            # F.linear (not raw matmul_v2) so the FLAGS_lowp_matmul
            # route applies to the GSPMD row-parallel path too
            out = F.linear(x, self.weight)
            out = shard_hint(out, *([None] * out.ndim))  # forces all-reduce
        if self.bias is not None:
            out = out + self.bias
        return out


class ParallelCrossEntropy(Layer):
    """Vocab-sharded softmax cross-entropy (ref: mp_layers.py:249 over
    c_softmax_with_cross_entropy). With GSPMD the logits stay sharded on
    the class axis and XLA partitions the log-sum-exp reduction."""

    def __init__(self, mp_group=None, name=None, ignore_index=-100):
        super().__init__()
        self.ignore_index = ignore_index

    def forward(self, input, label):
        input = shard_hint(input, *([None] * (input.ndim - 1)), MP_AXIS)
        loss, _ = apply("softmax_with_cross_entropy", input, label,
                        soft_label=False, axis=-1,
                        ignore_index=self.ignore_index)
        return loss


def parallel_linear_split(x, size, operation, axis=0, num_partitions=1,
                          gather_out=True, weight_attr=None, bias_attr=None):
    """paddle.distributed.split (ref: distributed/collective.py:1283)."""
    if operation == "linear":
        if axis == 0:
            layer = RowParallelLinear(size[0], size[1], weight_attr,
                                      has_bias=bias_attr is not False)
        else:
            layer = ColumnParallelLinear(size[0], size[1], weight_attr,
                                         has_bias=bias_attr is not False,
                                         gather_output=gather_out)
        return layer(x)
    if operation == "embedding":
        layer = VocabParallelEmbedding(size[0], size[1], weight_attr)
        return layer(x)
    raise ValueError(f"unsupported split operation {operation!r}")
