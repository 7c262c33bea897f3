"""Partition-rule-driven weight sharding for mesh-sharded serving.

Serving reuses the training TP conventions (ISSUE 17 tentpole a): the
GPT/ERNIE layers name their projections identically whether built with
`nn.Linear` or the `Column/RowParallelLinear` pair from
`distributed/fleet/meta_parallel/mp_layers.py`, so a small ordered rule
table over *parameter names* is enough to recover the GSPMD layout the
hybrid trainer derives from `Parameter.param_spec`:

    qkv_proj / fc1        column-parallel  -> weight P(None, "mp"),
                                              bias   P("mp")
    out_proj / fc2        row-parallel     -> weight P("mp", None)
    word_embeddings       vocab-parallel   -> weight P("mp", None)
    everything else       replicated       -> P()

The serving mesh is a 2-axis (dp, mp) slice of the training topology
(`distributed/topology.py` axis names), specified as ``dpD.mpM`` via
`FLAGS_serving_mesh`. GSPMD pads uneven dimensions (e.g. a vocab of 97
on mp=4), so no divisibility guard is needed on weights; the paged
pools are sharded over the head axis their model's cache layout names,
and only when the head count divides the mp degree — otherwise (and
for a layout with no head axis, such as latent rows) they stay
replicated and the engine still serves (block tables are host-side
numpy either way, so they remain replica-global; see
`ShardingPlan.pool_sharding`). The per-slot state arrays of a layout
that declares them follow the same rule over the head axis the layout
names for each (`ShardingPlan.state_sharding`).
"""

from __future__ import annotations

import re

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ..distributed.topology import DP_AXIS, MP_AXIS

__all__ = [
    "GPT_PARTITION_RULES", "ShardingPlan", "build_mesh",
    "match_partition_rules", "mesh_spec_of", "parse_mesh_spec",
    "resolve_mesh",
]

_SPEC_RE = re.compile(r"^dp(\d+)\.mp(\d+)$")


def parse_mesh_spec(spec):
    """'dpD.mpM' -> {'dp': D, 'mp': M} (both >= 1)."""
    m = _SPEC_RE.match(spec.strip())
    if not m:
        raise ValueError(
            f"bad serving mesh spec {spec!r}: want 'dpD.mpM', e.g. "
            "'dp1.mp2'")
    dp, mp = int(m.group(1)), int(m.group(2))
    if dp < 1 or mp < 1:
        raise ValueError(f"mesh degrees must be >= 1: {spec!r}")
    return {"dp": dp, "mp": mp}


def build_mesh(spec):
    """Build the 2-axis (dp, mp) serving mesh from a 'dpD.mpM' spec."""
    deg = parse_mesh_spec(spec) if isinstance(spec, str) else dict(spec)
    total = deg["dp"] * deg["mp"]
    devices = jax.devices()
    if total > len(devices):
        raise ValueError(
            f"mesh {deg} needs {total} devices, have {len(devices)}")
    grid = np.array(devices[:total]).reshape(deg["dp"], deg["mp"])
    return Mesh(grid, (DP_AXIS, MP_AXIS))


def resolve_mesh(mesh):
    """Normalize an engine's mesh argument: None -> FLAGS_serving_mesh
    ('' -> no mesh), 'dpD.mpM' string -> built Mesh, Mesh -> as-is."""
    if mesh is None:
        from ..framework.flags import flag

        mesh = flag("FLAGS_serving_mesh") or None
    if mesh is None or isinstance(mesh, Mesh):
        return mesh
    return build_mesh(mesh)


def mesh_spec_of(mesh):
    """Mesh -> canonical 'dpD.mpM' label (for metrics / compile keys)."""
    if mesh is None:
        return ""
    shape = dict(mesh.shape)
    return f"dp{shape.get(DP_AXIS, 1)}.mp{shape.get(MP_AXIS, 1)}"


#: ordered (regex, PartitionSpec) pairs over state-dict names; first
#: match wins, so the catch-all replicates layernorms / position
#: embeddings / biases of row-parallel layers. Mirrors the param_spec
#: assignments in mp_layers.py (paddle Linear weights are [in, out]).
GPT_PARTITION_RULES = (
    (r"qkv_proj\.weight$", P(None, MP_AXIS)),
    (r"qkv_proj\.bias$", P(MP_AXIS)),
    (r"fc1\.weight$", P(None, MP_AXIS)),
    (r"fc1\.bias$", P(MP_AXIS)),
    (r"out_proj\.weight$", P(MP_AXIS, None)),
    (r"fc2\.weight$", P(MP_AXIS, None)),
    (r"word_embeddings\.weight$", P(MP_AXIS, None)),
    (r".*", P()),
)


def match_partition_rules(rules, params):
    """Map each param name to the PartitionSpec of the first matching
    rule (re.search). Scalar leaves are always replicated. Raises on an
    unmatched name so a renamed layer cannot silently lose its layout —
    keep a catch-all ``.*`` rule last for the replicated remainder."""
    specs = {}
    for name, value in params.items():
        if getattr(value, "ndim", 0) == 0:
            specs[name] = P()
            continue
        for rule, spec in rules:
            if re.search(rule, name):
                specs[name] = spec
                break
        else:
            raise ValueError(f"no partition rule matches param {name!r}")
    return specs


class ShardingPlan:
    """All NamedShardings a mesh-sharded SlotEngine needs, in one place.

    Weights follow `rules` (default GPT_PARTITION_RULES); a spec naming
    an axis a tensor is too small or too low-rank for degrades to
    replicated rather than failing (GSPMD handles uneven *padding*, but
    a rank-1 bias cannot take a rank-2 spec). A paged pool shards
    over the head axis its layout names (axis 2 of ``[num_blocks,
    block_size, num_heads, head_dim]``) iff ``num_heads % mp == 0``;
    block tables / allocator stay host-side numpy and therefore
    replica-global.
    """

    def __init__(self, mesh, rules=GPT_PARTITION_RULES):
        self.mesh = mesh
        self.rules = rules
        self.spec = mesh_spec_of(mesh)
        self.mp = dict(mesh.shape).get(MP_AXIS, 1)

    def _named(self, spec):
        return NamedSharding(self.mesh, spec)

    def replicated(self):
        return self._named(P())

    def _fit(self, spec, value):
        """Degrade a rule spec to what this tensor can actually carry:
        a rank-1 bias cannot take a rank-2 spec, and an explicitly
        placed array (device_put / jit in_shardings) must divide the
        mesh axis exactly — GSPMD only pads *internal* values, so an
        uneven dim (e.g. a vocab of 97 on mp=2) falls back to
        replicated on that dim while the rest stay sharded."""
        if len(spec) > getattr(value, "ndim", 0):
            return P()
        fitted = []
        for dim, axis in enumerate(spec):
            if axis is not None:
                size = dict(self.mesh.shape).get(axis, 1)
                if value.shape[dim] % size != 0:
                    axis = None
            fitted.append(axis)
        return P(*fitted)

    def values_shardings(self, values):
        """name -> NamedSharding for a weight-values dict (quantized
        int8 companions like ``<name>.scale`` fall through the rules to
        the scalar/replicated cases)."""
        specs = match_partition_rules(self.rules, values)
        return {k: self._named(self._fit(specs[k], values[k]))
                for k in values}

    def place_values(self, values):
        sh = self.values_shardings(values)
        return {k: jax.device_put(v, sh[k]) for k, v in values.items()}

    def pool_sharding(self, group, shape):
        """Sharding of one pool of `shape` under a block group of the
        model's `paging.CacheLayout` (or the layout itself, which reads
        as its first group): the head axis the group names over mp
        when mp divides its heads; a pool with no head axis (latent
        rows) or whose heads do not divide mp is replicated (the
        engine still serves; it just stops saving cache memory — same
        silent-guard stance as the overlap kernels). Each group of a
        layout is placed by its own rule."""
        return self._over_heads(group.head_axis, shape,
                                getattr(group, "heads", None))

    def state_sharding(self, layout, name, shape):
        """Sharding of the per-slot state array `name` of `shape`
        ``[rows, ...]``: over the head axis the layout names for it
        when mp divides it, else replicated, as `pool_sharding` does
        for pools."""
        return self._over_heads(layout.state_head_axis.get(name), shape)

    def _over_heads(self, axis, shape, heads=None):
        """`heads` lie along `axis` (None = one an element): sharded
        over mp only where mp divides them."""
        if self.mp > 1 and axis is not None \
                and (shape[axis] if heads is None else heads) % self.mp == 0 \
                and shape[axis] % self.mp == 0:
            spec = [None] * len(shape)
            spec[axis] = MP_AXIS
            return self._named(P(*spec))
        return self.replicated()
