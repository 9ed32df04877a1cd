"""Flat parameter buffers: pack per-client pytrees into contiguous arrays.

The HFL engines stack every state tree with leading topology axes
(``[G, K, ...]`` per-client, ``[G, ...]`` per-group). Stored as pytrees,
each round executes its algebra *per leaf*: one XLA op (or one Pallas
dispatch plus one lane-padding) per parameter tensor per operation, and the
trace/compile cost scales with ``leaves x steps``. This module packs all
model leaves into **one contiguous buffer per dtype** -- leading topology
axes preserved, trailing axis the concatenation of every raveled leaf -- so
the round's element-wise algebra and reductions become a handful of
whole-model ops.

Layout::

    FlatBuffers(bufs={"float32": f32_buf, ...}, packer=<static Packer>)
      f32_buf: [*lead, N_f32]   N_f32 = sum of sizes of all f32 leaves

``Packer`` is the static segment table: for every template leaf it records
which dtype-buffer it lives in, its offset/size and its shape, plus the
treedef to rebuild the tree. It is hashable and comparable, so it rides
along as pytree aux data: a ``FlatBuffers`` is itself a registered pytree
(children = the per-dtype buffers) and moves through ``jit`` / ``scan`` /
``vmap`` / ``jax.grad`` like any other state, while every consumer can
recover tree views via :meth:`FlatBuffers.to_tree` without a side channel.

The repack boundary is chosen by the engines, not forced per step: packing
and unpacking are plain slice/reshape/concat ops (no autodiff through the
segment table -- gradients are taken per leaf and repacked), so the engines
unpack once per local phase, keep the gradient hot loop on tree views, and
run every aggregation / correction / dissemination on the flat buffers.
"""
from __future__ import annotations

import dataclasses
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np

PyTree = Any


@dataclasses.dataclass(frozen=True)
class Segment:
    """Where one template leaf lives inside its dtype buffer."""

    buffer: str            # dtype key, e.g. "float32"
    offset: int            # start (in elements) inside the buffer
    size: int              # number of elements
    shape: tuple[int, ...]  # original leaf shape (without leading axes)


@dataclasses.dataclass(frozen=True)
class Packer:
    """Static pack/unpack table built from a template pytree.

    The template is the *single-model* tree (no topology axes); ``flatten``
    and ``unflatten`` then accept any number of leading axes, inferred per
    call from the difference between actual and template leaf ranks.
    """

    treedef: Any                      # jax treedef (hashable)
    segments: tuple[Segment, ...]     # one per template leaf, in leaf order
    buffer_sizes: tuple[tuple[str, int], ...]  # (dtype key, total elements)

    @property
    def num_params(self) -> int:
        return sum(n for _, n in self.buffer_sizes)

    def flatten(self, tree: PyTree) -> "FlatBuffers":
        """Pack ``tree`` (template structure + arbitrary leading axes).

        Traced under the ``state_repack`` scope, as is :meth:`unflatten`."""
        leaves = self.treedef.flatten_up_to(tree)
        lead = None
        parts: dict[str, list[jax.Array]] = {key: [] for key, _ in self.buffer_sizes}
        with jax.named_scope("state_repack"):
            for seg, leaf in zip(self.segments, leaves):
                nlead = leaf.ndim - len(seg.shape)
                if lead is None:
                    lead = leaf.shape[:nlead]
                parts[seg.buffer].append(leaf.reshape(lead + (seg.size,)))
            bufs = {
                key: (chunks[0] if len(chunks) == 1 else jnp.concatenate(chunks, axis=-1))
                for key, chunks in parts.items()
            }
        return FlatBuffers(bufs, self)

    def unflatten(self, flat: "FlatBuffers | dict[str, jax.Array]") -> PyTree:
        """Rebuild the template-structured tree (leading axes preserved)."""
        bufs = flat.bufs if isinstance(flat, FlatBuffers) else flat
        leaves = []
        with jax.named_scope("state_repack"):
            for seg in self.segments:
                buf = bufs[seg.buffer]
                lead = buf.shape[:-1]
                leaves.append(
                    buf[..., seg.offset:seg.offset + seg.size].reshape(lead + seg.shape)
                )
        return self.treedef.unflatten(leaves)

    def zeros(self, lead: tuple[int, ...] = ()) -> "FlatBuffers":
        """Zero-filled flat buffers with the given leading axes."""
        bufs = {
            key: jnp.zeros(tuple(lead) + (n,), jnp.dtype(key))
            for key, n in self.buffer_sizes
        }
        return FlatBuffers(bufs, self)

    def state_bytes(self, lead: tuple[int, ...] = ()) -> int:
        """Total bytes of the flat buffers under the given leading axes.

        Computed from the static segment table -- no arrays are built -- so
        memory claims (e.g. cohort-vs-population device footprints in
        ``benchmarks/bench_population.py``) derive from the same table that
        drives pack/unpack rather than from sampled process RSS.
        """
        mult = int(np.prod(lead)) if lead else 1
        return sum(
            mult * n * np.dtype(key).itemsize for key, n in self.buffer_sizes
        )

    def size_report(self, lead: tuple[int, ...] = ()) -> dict[str, Any]:
        """Per-dtype-buffer size breakdown under the given leading axes.

        Returns ``{"lead": lead, "total_bytes": ..., "buffers": {dtype:
        {"elements", "bytes", "leaves"}}}`` -- the machine-readable form the
        benchmarks embed in their ``BENCH_*.json`` artifacts.
        """
        mult = int(np.prod(lead)) if lead else 1
        leaves_per = {key: 0 for key, _ in self.buffer_sizes}
        for seg in self.segments:
            leaves_per[seg.buffer] += 1
        buffers = {
            key: {
                "elements": mult * n,
                "bytes": mult * n * np.dtype(key).itemsize,
                "leaves": leaves_per[key],
            }
            for key, n in self.buffer_sizes
        }
        return {
            "lead": tuple(lead),
            "total_bytes": self.state_bytes(lead),
            "buffers": buffers,
        }


def make_packer(template: PyTree) -> Packer:
    """Build the static segment table from a single-model template tree."""
    leaves, treedef = jax.tree.flatten(template)
    offsets: dict[str, int] = {}
    segments = []
    for leaf in leaves:
        key = jnp.asarray(leaf).dtype.name
        size = int(np.prod(leaf.shape)) if leaf.shape else 1
        off = offsets.get(key, 0)
        segments.append(Segment(key, off, size, tuple(leaf.shape)))
        offsets[key] = off + size
    return Packer(
        treedef=treedef,
        segments=tuple(segments),
        buffer_sizes=tuple(sorted(offsets.items())),
    )


class FlatBuffers:
    """A pytree of contiguous per-dtype buffers + the packer that made them.

    Children are the buffers (stable, key-sorted order); the ``(keys,
    packer)`` pair is static aux data, so two FlatBuffers from the same
    packer are tree-compatible and flow through ``jax.tree.map`` together.
    """

    __slots__ = ("bufs", "packer")

    def __init__(self, bufs: dict[str, jax.Array], packer: Packer):
        self.bufs = dict(bufs)
        self.packer = packer

    def to_tree(self) -> PyTree:
        """Unpack back into the template-structured tree."""
        return self.packer.unflatten(self)

    @property
    def lead_shape(self) -> tuple[int, ...]:
        return next(iter(self.bufs.values())).shape[:-1]

    def __repr__(self) -> str:
        shapes = {k: tuple(v.shape) for k, v in self.bufs.items()}
        return f"FlatBuffers({shapes})"


def _flat_buffers_flatten_with_keys(fb: FlatBuffers):
    keys = tuple(sorted(fb.bufs))
    children = tuple(
        (jax.tree_util.DictKey(k), fb.bufs[k]) for k in keys
    )
    return children, (keys, fb.packer)


def _flat_buffers_flatten(fb: FlatBuffers):
    keys = tuple(sorted(fb.bufs))
    return tuple(fb.bufs[k] for k in keys), (keys, fb.packer)


def _flat_buffers_unflatten(aux, children) -> FlatBuffers:
    keys, packer = aux
    return FlatBuffers(dict(zip(keys, children)), packer)


jax.tree_util.register_pytree_with_keys(
    FlatBuffers, _flat_buffers_flatten_with_keys, _flat_buffers_unflatten,
    _flat_buffers_flatten,
)


def is_flat(tree: PyTree) -> bool:
    return isinstance(tree, FlatBuffers)


def as_tree(tree: PyTree) -> PyTree:
    """Unpack FlatBuffers into its template tree; identity on plain trees.

    Callers unpack the exact object they index (e.g. ``as_tree(state.z)["w"]``);
    nested containers of FlatBuffers are not searched.
    """
    return tree.to_tree() if isinstance(tree, FlatBuffers) else tree
