"""A tree of numpy leaves as one upload: a device buffer a dtype.

What a leaf costs on the chip's host is the call and the device buffer,
not the bytes (a pass's 63 leaves are 435 KB): ~0.24 ms a jnp.asarray,
~0.17 ms a leaf of one jax.device_put(tree), ~0.055 ms an output buffer of
a jitted call.  So a tree travels as one contiguous host buffer per dtype
(pack_tree), and whoever consumes its leaves cuts them out of the buffers
INSIDE its own executable (unpack_leaves: the sequential scan,
framework/replay.py; a resident array's patch, state/resident.py), or
asks for some of them as device arrays of their own (PackedPass.take: one
jitted dispatch; upload_tree for a whole tree).  state/compile.py's one
upload site is the caller.

A leaf of many long rows is the exception (OWN_ROWS, OWN_ELEMS): it
travels in the same device_put as a buffer of its own and stands in the
tree as the device array it is.  Cutting `[64, 5000]` out of a flat
buffer is a relayout a row in the consumer's executable, and the TPU's
compiler takes its time over each: with four such leaves and two of
`[32, 5000]` (BASELINE config 4's pass on the bucket of 32 pods) the
packed scan compiled for a described v5e in 61.6 s, and in 19.4 s with
the six as arguments (44.9 -> 19.4 on the bucket of 16, 32.8 -> 19.3 on
8; PERF.md section 6, PR 52), for ~0.17 ms a leaf and a pass.
"""

from __future__ import annotations

import math

import jax
import numpy as np
from jax import lax

from ..utils.tracing import TRACER

# a numpy leaf of at least this many rows AND elements is a buffer of its
# own (module doc).  Rows, because the cost is a row's; 16, so that a
# resident array's payload (state/resident.py: ROWS_MAX rows, which its
# patch cuts out of the buffers) never is one; 2**16 elements, so that the
# rows are long: [16, 5000] is, [64, 1000] and [2, 15000] are not
OWN_ROWS = 16
OWN_ELEMS = 1 << 16


def _own_buffer(leaf: np.ndarray) -> bool:
    return (leaf.ndim >= 2 and leaf.shape[0] >= OWN_ROWS
            and leaf.size >= OWN_ELEMS)


class Packed:
    """Where a leaf lies in a PackedPass's buffers: leaf `k` of its layout.
    Says shape and dtype as the array it stands for would, which is all the
    scan-cache key reads of a leaf."""

    __slots__ = ("k", "shape", "dtype")

    def __init__(self, k: int, shape: tuple, dtype: np.dtype):
        self.k, self.shape, self.dtype = k, shape, dtype


class PackedPass:
    """A tree of numpy leaves as ONE upload: a contiguous device buffer per
    dtype, and the static layout that says where each leaf lies in them.

    bufs    dtype name -> the 1-D device buffer of that dtype's leaves
    layout  (dtype name, shape) a leaf, in the order they were laid in
    tree    the tree that was packed, a Packed in each numpy leaf's place
            (a device array in the place of one that travelled as a buffer
            of its own, _own_buffer); what was no numpy array (a device
            array: a resident leaf of state/resident.py; a Python int the
            step reads as a constant) stays as it was
    """

    __slots__ = ("bufs", "layout", "tree")

    def __init__(self, bufs: dict, layout: tuple, tree):
        self.bufs, self.layout, self.tree = bufs, layout, tree

    def take(self, tree):
        """`tree`, a part of self.tree -> the same with a device array in
        each Packed's place, every one of the shape, dtype and (non-)weak
        type jnp.asarray would have given its numpy leaf."""
        leaves, treedef = jax.tree.flatten(tree)
        at = [i for i, leaf in enumerate(leaves) if isinstance(leaf, Packed)]
        if at:
            TRACER.count("pass_device_dispatches_total")
            picks = tuple(leaves[i].k for i in at)
            for i, leaf in zip(at, _unpack(self.layout, picks, self.bufs)):
                leaves[i] = leaf
        return jax.tree.unflatten(treedef, leaves)


def pack_tree(tree) -> PackedPass:
    """The one transfer of a tree of numpy leaves (counter
    workload_h2d_transfers_total: a buffer a dtype, and one a leaf of many
    long rows, _own_buffer)."""
    leaves, treedef = jax.tree.flatten(tree)
    layout, parts, own = [], {}, []
    for i, leaf in enumerate(leaves):
        if isinstance(leaf, np.ndarray) and _own_buffer(leaf):
            own.append(i)
        elif isinstance(leaf, (np.ndarray, np.generic)):
            parts.setdefault(leaf.dtype.name, []).append(np.ravel(leaf))
            leaves[i] = Packed(len(layout), leaf.shape, leaf.dtype)
            layout.append((leaf.dtype.name, leaf.shape))
    bufs = {}
    if parts or own:
        TRACER.count("workload_h2d_transfers_total", len(parts) + len(own))
        TRACER.count("pass_device_dispatches_total")
        bufs = {dt: np.concatenate(p) for dt, p in parts.items()}
        if own:
            # copies, as the concatenated buffers are: a device_put may
            # alias the host's memory (the CPU backend does), and a build
            # may have handed a row of the node table's memo
            bufs, sent = jax.device_put(
                (bufs, [leaves[i].copy() for i in own]))
            for i, leaf in zip(own, sent):
                leaves[i] = leaf
        else:
            bufs = jax.device_put(bufs)
    return PackedPass(bufs, tuple(layout), jax.tree.unflatten(treedef, leaves))


def upload_tree(tree):
    """A tree of numpy leaves -> the same tree of device arrays, each with
    the shape, dtype and (non-)weak type jnp.asarray would give it; what
    is not a numpy array stays as it is.  One transfer (pack_tree) and one
    jitted dispatch that hands every leaf back as a buffer of its own
    (PackedPass.take): the route for what a jitted step CLOSES over, the
    closure statics of a changed node table, and for whoever needs leaves."""
    packed = pack_tree(tree)
    return packed.take(packed.tree)


def unpack_leaves(layout, picks, bufs) -> list:
    """Traced: leaves `picks` of `layout` cut out of their dtype's buffer.
    layout: (dtype name, shape) per leaf, in the order the leaves were
    laid into their dtype's buffer."""
    offs, at = dict.fromkeys(bufs, 0), []
    for dt, shape in layout:
        at.append(offs[dt])
        offs[dt] += math.prod(shape)
    out = []
    for k in picks:
        dt, shape = layout[k]
        out.append(lax.slice(bufs[dt], (at[k],), (at[k] + math.prod(shape),))
                   .reshape(shape))
    return out


# the same layout from pass to pass, so this compiles with the scan (or
# with a session's first patched pass, for a resident leaf's payload) and
# never after
_unpack = jax.jit(unpack_leaves, static_argnums=(0, 1))
