"""Mesh-aware sharding rules (pure: no devices needed to compute specs).

Conventions (production LM geometry):

* mesh axes: one ``model`` (TP) axis; every other axis is data-parallel and
  gets folded into a single logical DP group (``("pod", "data")`` on a
  multi-pod mesh) — so rules written for (data, model) generalize.
* parameters: matrices shard (row -> data, col -> model) except output
  projections (``wo``/``w_down``/``out_proj``/...) which flip, embeddings
  (vocab -> model, d_model -> data) and norm vectors (replicated). Leading
  layer-stack axes are never sharded.
* decode state: KV caches shard batch on data and SEQUENCE on model
  (sequence-parallel decode) — the largest axis wins the model axis.
* every rule applies a divisibility guard: a dim that does not divide by the
  axis group size stays unsharded instead of erroring at device_put time.
"""

from __future__ import annotations

import jax
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

# parameter names whose LAST TWO dims are (model, data) instead of (data, model):
# output-side projections, whose input dim arrives model-sharded from the heads
_OUTPUT_PROJ_NAMES = frozenset(
    {"wo", "w_down", "w_out", "out_proj", "cm_wv", "w_o", "wv_out"})

# logical activation axis -> physical mesh axis family
_LOGICAL_TO_PHYSICAL = {
    "batch": "__data__",
    "expdp": "__data__",
    "heads": "model",
    "model": "model",
    "vocab": "model",
    "seqtp": "model",
    "kvseq": "model",
}


# ---------------------------------------------------------------------------
# mesh introspection helpers (work on jax.sharding.Mesh AND shape-only fakes)

def _axis_sizes(mesh) -> dict[str, int]:
    if isinstance(mesh, jax.sharding.AbstractMesh):   # inside a trace
        return dict(zip(mesh.axis_names, mesh.axis_sizes))
    return dict(zip(tuple(mesh.axis_names), tuple(np.shape(mesh.devices))))

def _data_axes(mesh) -> tuple[str, ...]:
    return tuple(n for n in mesh.axis_names if n != "model")


def _data_entry(mesh):
    """The PartitionSpec entry for the folded DP group."""
    d = _data_axes(mesh)
    if not d:
        return None
    return d[0] if len(d) == 1 else d


def _entry_size(mesh, entry) -> int:
    # An axis name the mesh does not carry is a degenerate axis group of
    # size 1 — the guard then unshards that dim instead of erroring (rules
    # written for (data, model) must run unchanged on a data-only mesh).
    sizes = _axis_sizes(mesh)
    if entry is None:
        return 1
    if isinstance(entry, tuple):
        total = 1
        for n in entry:
            total *= sizes.get(n, 1)
        return total
    return sizes.get(entry, 1)


def _guard(mesh, shape, entries):
    """Divisibility guard: unshard any dim the mesh does not divide.

    Falls back (entry -> None) when the dim does not divide the axis group
    size AND when the axis group itself is degenerate: size 1, or an axis
    name the mesh does not have at all."""
    out = []
    for dim, e in zip(shape, entries):
        size = _entry_size(mesh, e)
        if e is not None and (size <= 1 or dim % size != 0 or dim < size):
            e = None
        out.append(e)
    return out


def mesh_axis_sizes(mesh) -> dict[str, int]:
    """Axis-name -> size for a Mesh (or any shape-only stand-in)."""
    return _axis_sizes(mesh)


def dp_size(mesh) -> int:
    """Folded data-parallel group size (1 on a model-only or empty mesh)."""
    if mesh is None:
        return 1
    return _entry_size(mesh, _data_entry(mesh))


def tp_size(mesh) -> int:
    """Tensor-parallel (``model`` axis) size (1 when the mesh has none)."""
    if mesh is None:
        return 1
    return _axis_sizes(mesh).get("model", 1)


def mesh_shards(mesh) -> int:
    """Total shard count = dp * tp (1 when unmeshed)."""
    return dp_size(mesh) * tp_size(mesh)


# ---------------------------------------------------------------------------
# parameter rules

def _param_spec_one(mesh, path: tuple[str, ...], shape) -> P:
    name = path[-1] if path else ""
    if any("norm" in part for part in path) or len(shape) <= 1:
        return P()
    data = _data_entry(mesh)
    lead = [None] * (len(shape) - 2)
    if "embed" in name:
        row, col = "model", data
    elif name in _OUTPUT_PROJ_NAMES:
        row, col = "model", data
    else:  # generic input-side matrix, router, head, moe experts, ...
        row, col = data, "model"
    entries = _guard(mesh, shape, lead + [row, col])
    return P(*entries)


def param_specs(mesh, params):
    """PartitionSpec pytree for a parameter pytree (leaves need ``.shape``)."""

    def walk(path, node):
        if isinstance(node, dict):
            return {k: walk(path + (k,), v) for k, v in node.items()}
        return _param_spec_one(mesh, path, tuple(node.shape))

    return walk((), params)


def param_shardings(mesh, params):
    """NamedSharding pytree matching :func:`param_specs`."""
    return jax.tree.map(lambda s: NamedSharding(mesh, s), param_specs(mesh, params),
                        is_leaf=lambda x: isinstance(x, P))


# ---------------------------------------------------------------------------
# decode-state rules

def _state_spec_one(mesh, shape, token_axis="auto", batch_axis=1) -> P:
    if len(shape) < 3:
        return P(*([None] * len(shape)))
    entries = [None] * len(shape)
    sizes = _axis_sizes(mesh)
    model_size = sizes.get("model", 1)
    batch_i = batch_axis
    if token_axis == "auto":
        # sequence axis = largest NON-batch dim (a huge decode batch must not
        # steal the model axis from the sequence dim)
        seq_i = max((i for i in range(len(shape)) if i != batch_i),
                    key=lambda i: shape[i])
    else:
        # family-declared token axis (state_page_axes contract); None marks a
        # fixed-size recurrent leaf with NO sequence axis — sharding one of
        # its feature/contraction axes on ``model`` would reassociate the
        # reductions that consume it and break token-for-token equivalence,
        # so such leaves stay batch-on-data only.
        seq_i = token_axis
    if (seq_i is not None and seq_i != batch_i and model_size > 1
            and shape[seq_i] % model_size == 0 and shape[seq_i] >= model_size):
        entries[seq_i] = "model"
    data = _data_entry(mesh)
    if data is not None:
        dsize = _entry_size(mesh, data)
        if dsize > 1 and shape[batch_i] % dsize == 0 and shape[batch_i] >= dsize:
            entries[batch_i] = data
    return P(*entries)


def state_specs(mesh, state, token_axes=None, batch_axes=None):
    """PartitionSpec pytree for a decode-state pytree (KV caches, SSM states).

    ``token_axes`` (optional, dict-state only): name -> token-axis int or
    None, the :func:`state_page_axes` contract each model family declares.
    When given it overrides the largest-dim heuristic — leaves declared
    ``None`` (recurrent tails) get no ``model`` entry at all.
    ``batch_axes`` (optional, dict-state only): name -> request-axis int,
    the ``state_batch_axes`` contract (defaults to 1 per leaf)."""
    if token_axes is not None and isinstance(state, dict):
        batch_axes = batch_axes or {}
        return {
            k: _state_spec_one(mesh, tuple(v.shape),
                               token_axis=token_axes.get(k, "auto"),
                               batch_axis=batch_axes.get(k, 1))
            for k, v in state.items()
        }
    return jax.tree.map(lambda leaf: _state_spec_one(mesh, tuple(leaf.shape)), state)


def state_shardings(mesh, state, token_axes=None, batch_axes=None):
    return jax.tree.map(lambda s: NamedSharding(mesh, s),
                        state_specs(mesh, state, token_axes=token_axes,
                                    batch_axes=batch_axes),
                        is_leaf=lambda x: isinstance(x, P))


# ---------------------------------------------------------------------------
# batch rules

def batch_spec(mesh, batch_size: int) -> P:
    """Spec for a (B, ...) batch leaf given its leading dim."""
    data = _data_entry(mesh)
    size = _entry_size(mesh, data)
    if data is not None and size > 1 and batch_size % size == 0 and batch_size >= size:
        return P(data, None)
    return P(None)


def batch_shardings(mesh, batch):
    """NamedSharding pytree for an input batch: leading dim on data, rest replicated."""

    def one(leaf):
        shape = tuple(leaf.shape)
        if not shape:
            return NamedSharding(mesh, P())
        data = _data_entry(mesh)
        entries = _guard(mesh, shape, [data] + [None] * (len(shape) - 1))
        return NamedSharding(mesh, P(*entries))

    return jax.tree.map(one, batch)


# ---------------------------------------------------------------------------
# in-graph logical constraints

def ambient_mesh():
    """The mesh installed by ``jax.set_mesh`` (None when unmeshed); inside
    a trace only its abstract form is known, which is all a sharding
    constraint needs."""
    m = jax.sharding.get_abstract_mesh()
    return None if m.empty else m


def dim_entry(mesh, dim: int, *, model: bool = False):
    """The PartitionSpec entry a dimension of size ``dim`` takes: ``model``,
    or the folded data-parallel group; None where the guard unshards it."""
    entry = "model" if model else _data_entry(mesh)
    return _guard(mesh, (dim,), (entry,))[0]


def ambient_dp_size() -> int:
    """Total data-parallel size of the ambient mesh (1 when unmeshed)."""
    mesh = ambient_mesh()
    if mesh is None:
        return 1
    return _entry_size(mesh, _data_entry(mesh))


def logical_constraint(x, *axes):
    """Pin ``x`` to a logical layout ("batch"/"heads"/"vocab"/"seqtp"/...).

    A no-op outside a mesh context, and per-dim a no-op when the mesh does not
    divide that dim — safe to sprinkle on every residual boundary."""
    mesh = ambient_mesh()
    if mesh is None:
        return x
    entries = []
    for i, axis in enumerate(axes):
        entry = None
        if axis is not None:
            phys = _LOGICAL_TO_PHYSICAL.get(axis)
            if phys == "__data__":
                entry = _data_entry(mesh)
            elif phys is not None:
                entry = phys
        if entry is not None:
            size = _entry_size(mesh, entry)
            if size <= 1 or i >= x.ndim or x.shape[i] % size != 0:
                entry = None
        entries.append(entry)
    return jax.lax.with_sharding_constraint(x, NamedSharding(mesh, P(*entries)))
