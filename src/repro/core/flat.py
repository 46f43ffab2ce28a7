"""FlatParams — the single-buffer wire representation of a model pytree.

The FedPC wire path (Eq. (4)/(5) ternarization, §3.3 2-bit packing, Eq. (3)
master update) is elementwise over *every* parameter, so nothing about it is
per-leaf. Flattening the whole pytree once into a single padded ``(rows, 128)``
float32 buffer lets the fused Pallas kernels (``repro.kernels.fused_wire``)
run the entire round's wire math in a handful of launches instead of four
kernels × leaves × workers, and makes the packed buffer the thing that feeds
collectives directly.

Layout
------
Leaves are raveled in ``tree_flatten`` order and concatenated into one vector
of ``n`` scalars, zero-padded to ``rows * 128`` with ``rows % ROW_MULTIPLE
== 0``. ``ROW_MULTIPLE = 256`` guarantees every view the kernels need is
aligned, and that the chip's 64-row wire tile (``tune.BLOCK_ROWS``)
divides the kernel views exactly, whatever the model's parameter count:

* ``(rows, 128)``          — float32 buffer, 8-sublane aligned;
* ``(rows // 4, 512)``     — the uplink kernel's input view (4 consecutive
  codes per output byte, matching §3.3 / ``core.packing.pack2bit`` order);
* ``(rows // 4, 128)``     — the packed uint8 wire buffer, lane-aligned
  (and a whole number of 32-row uint8 tiles).

The zero padding is a fixed point of the whole wire path: ternarizing
``q = p1 = p2 = 0`` yields code 0, and the master update maps a zero tail to
a zero tail, so padded scalars never leak into real parameters.

Model sharding
--------------
``layout_of(tree, shards=M)`` rounds ``rows`` up to a multiple of
``ROW_MULTIPLE * M`` so the buffer splits into ``M`` equal ``(rows/M, 128)``
*slabs*, each itself satisfying every alignment above. The distributed fed
sync shards the wire buffers over the model mesh axis this way: every model
shard runs the fused kernels on its own slab and the collectives move
``rows/M`` rows per device instead of a replicated full buffer.

``FlatLayout`` is cached per (treedef, shapes, dtypes, shards) so repeated
rounds pay for layout computation once; the cache is a small LRU so
long-lived multi-model processes don't grow it without bound.
"""
from __future__ import annotations

import math
from collections import OrderedDict
from typing import Any, NamedTuple

import jax
import jax.numpy as jnp

from repro.utils import PyTree, round_up

LANES = 128
ROW_MULTIPLE = 256         # rows//4 % 64 == 0: whole chip tiles (see above)
PACK = 4                   # ternary codes per wire byte (§3.3)


class FlatLayout(NamedTuple):
    """Static description of how a pytree maps into the flat buffer."""
    treedef: Any
    shapes: tuple[tuple[int, ...], ...]
    dtypes: tuple[Any, ...]
    sizes: tuple[int, ...]
    offsets: tuple[int, ...]   # start of each leaf in the flat vector
    n: int                     # total real scalars
    rows: int                  # padded buffer rows (rows % ROW_MULTIPLE == 0)
    shards: int = 1            # model-axis slabs (rows % (ROW_MULTIPLE*shards) == 0)

    @property
    def padded(self) -> int:
        return self.rows * LANES

    @property
    def packed_rows(self) -> int:
        """Rows of the (packed_rows, 128) uint8 wire buffer."""
        return self.rows // PACK

    @property
    def shard_rows(self) -> int:
        """Rows of one model shard's (shard_rows, 128) slab."""
        return self.rows // self.shards

    @property
    def packed_shard_rows(self) -> int:
        """Rows of one model shard's (·, 128) packed uint8 slab."""
        return self.shard_rows // PACK

    @property
    def packed_bytes(self) -> int:
        """Exact §3.3 wire bytes for the *real* scalars (Eq. (8) accounting
        is over ``n``, not the padded buffer)."""
        return round_up(self.n, PACK) // PACK


class FlatParams(NamedTuple):
    """A model pytree flattened to one padded (rows, 128) float32 buffer."""
    buf: jax.Array
    layout: FlatLayout

    @classmethod
    def from_tree(cls, tree: PyTree, layout: FlatLayout | None = None
                  ) -> "FlatParams":
        layout = layout or layout_of(tree)
        return cls(flatten_tree(tree, layout), layout)

    def to_tree(self) -> PyTree:
        return unflatten_tree(self.buf, self.layout)


LAYOUT_CACHE_MAX = 32
_layout_cache: OrderedDict = OrderedDict()


def layout_of(tree: PyTree, shards: int = 1) -> FlatLayout:
    """Cached FlatLayout for a pytree (keyed on structure+shapes+dtypes+shards).

    ``shards`` pads ``rows`` to a multiple of ``ROW_MULTIPLE * shards`` so the
    buffer splits into ``shards`` aligned slabs (model-axis wire sharding).
    """
    if shards < 1:
        raise ValueError(f"shards must be >= 1, got {shards}")
    leaves, treedef = jax.tree_util.tree_flatten(tree)
    shapes = tuple(tuple(l.shape) for l in leaves)
    dtypes = tuple(jnp.dtype(l.dtype) for l in leaves)
    key = (treedef, shapes, dtypes, shards)
    hit = _layout_cache.get(key)
    if hit is not None:
        _layout_cache.move_to_end(key)
        return hit
    sizes = tuple(math.prod(s) for s in shapes)
    offsets, off = [], 0
    for s in sizes:
        offsets.append(off)
        off += s
    n = off
    rows = round_up(max(-(-n // LANES), 1), ROW_MULTIPLE * shards)
    layout = FlatLayout(treedef, shapes, dtypes, sizes, tuple(offsets),
                        n, rows, shards)
    _layout_cache[key] = layout
    while len(_layout_cache) > LAYOUT_CACHE_MAX:
        _layout_cache.popitem(last=False)
    return layout


def flatten_tree(tree: PyTree, layout: FlatLayout) -> jax.Array:
    """Pytree → padded (rows, 128) float32 buffer (device scope
    ``fed/flatten``)."""
    with jax.named_scope("fed/flatten"):
        leaves = jax.tree_util.tree_leaves(tree)
        flat = jnp.concatenate(
            [l.reshape(-1).astype(jnp.float32) for l in leaves])
        flat = jnp.pad(flat, (0, layout.padded - layout.n))
        return flat.reshape(layout.rows, LANES)


def unflatten_tree(buf: jax.Array, layout: FlatLayout) -> PyTree:
    """Padded (rows, 128) buffer → pytree (leaves cast back to their dtypes;
    device scope ``fed/unflatten``)."""
    with jax.named_scope("fed/unflatten"):
        flat = buf.reshape(-1)
        leaves = [
            jax.lax.slice(flat, (o,), (o + s,)).reshape(shape).astype(dt)
            for o, s, shape, dt in zip(layout.offsets, layout.sizes,
                                       layout.shapes, layout.dtypes)
        ]
        return jax.tree_util.tree_unflatten(layout.treedef, leaves)


def flatten_stacked(tree_F: PyTree, layout: FlatLayout) -> jax.Array:
    """Pytree with (F, *shape) leaves → (F, rows, 128) float32 buffers.

    Used by the distributed runtime where all fed workers' models arrive
    stacked over the leading axis.
    """
    leaves = jax.tree_util.tree_leaves(tree_F)
    f = leaves[0].shape[0]
    flat = jnp.concatenate(
        [l.reshape(f, -1).astype(jnp.float32) for l in leaves], axis=1)
    flat = jnp.pad(flat, ((0, 0), (0, layout.padded - layout.n)))
    return flat.reshape(f, layout.rows, LANES)
